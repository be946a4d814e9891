"""Shared set-up for the benchmark's CPU tests: the harness on the path,
and one run of a test cell with the chip check skipped."""

import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
DATA = os.path.join(HERE, "data")
sys.path.insert(0, BENCH)

import run as R  # noqa: E402
from harness.registry import Registry  # noqa: E402

#: peaks handed to a CPU run in place of a chip's; no CPU number is a
#: device metric, and the readers of device metrics find no device there
TEST_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def registry(bench_json=None, dirs=()):
    return Registry(bench_json or os.path.join(DATA, "bench.json"),
                    dirs=tuple(dirs) + (DATA, BENCH))


def cpu_run(cell, *, reg=None, seed=3, seconds=1.5, trace=False, **kw):
    """One run of a test cell on the CPU, past the harness's chip check."""
    import jax
    R._import_program()
    reg = reg or registry()
    return R.run_cell(reg, reg.cell(cell), seed=seed, seconds=seconds,
                      trace=trace, devices=jax.devices(),
                      counter=R.CompileCounter(),
                      t_start=time.perf_counter(), peaks=TEST_PEAKS, **kw)
