"""Readings that set the limit of ``correct``, on the chip, in one process.

    python chipbench/calibrate.py --workload internvl2-2b.conv \\
        --seconds 20 --seeds 11 12 13 ...

For each seed it makes one run of the cell as ``run.py`` does (at the
cell's own sizes and load, with a shorter window), and reads two numbers
over the same sample of served requests: the program's widest logit gap
against the float32 reference, and the control's, the reference computed
with float8 matmuls.  The control, put in the program's place, is judged
by the same comparison (``run.judge``) that decides the program's
``correct``.  The lower reading is the largest program gap over the seeds;
the upper, the smallest control gap.  The limit in the configuration file
lies between them (see PERF.md).  It exits non-zero unless every seed's
program reads ``correct`` and every seed's control does not.  The
benchmark's own runs never read the control.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run as R  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    reg = R.Registry()
    cell = reg.cell(args.workload)
    R._import_program()
    counter = R.CompileCounter()
    devices = R.require_chip(cell.chips)
    rows = []
    for seed in args.seeds:
        res = R.run_cell(reg, cell, seed=seed, seconds=args.seconds,
                         trace=False, devices=devices, counter=counter,
                         t_start=time.perf_counter(), control=True)
        row = {"seed": seed, "correct": res["correct"],
               "program_gap": res["compared"]["max_logit_gap"]["value"],
               "control_gap": res["control"]["max_logit_gap"],
               "control_correct": res["control"]["correct"],
               "control_top1": [res["control"]["top1_agree"],
                                res["control"]["tokens"]],
               "metrics": {k: v["value"] for k, v in res["metrics"].items()}}
        print(json.dumps(row), flush=True)
        rows.append(row)
        gc.collect()
    lower = max(r["program_gap"] for r in rows)
    upper = min(r["control_gap"] for r in rows)
    separated = all(r["correct"] and not r["control_correct"] for r in rows)
    print(json.dumps({"workload": cell.name, "seeds": len(rows),
                      "lower": lower, "upper": upper,
                      "ratio": upper / lower if lower else None,
                      "separated": separated}))
    return 0 if separated else 1


if __name__ == "__main__":
    sys.exit(main())
