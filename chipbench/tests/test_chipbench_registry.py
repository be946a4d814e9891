"""Every piece is found by its name; an unknown name is refused; a new
configuration, mix or metric is a new file and a new entry."""

import json
import os
import shutil

import pytest

from chipbench_testlib import BENCH, DATA, cpu_run, registry
from harness.registry import UnknownName

ROOT = os.path.dirname(BENCH)


def test_every_committed_cell_resolves():
    reg = registry(os.path.join(ROOT, "BENCHMARK.json"))
    for w in reg.bench["workloads"]:
        cell = reg.cell(w["name"])
        assert cell.config["name"] == w["config"]
        reg.arch(cell.config["arch"])
        for m in cell.end_to_end + cell.per_layer:
            assert callable(reg.reader(m["name"]))


@pytest.mark.parametrize("what", ["workload", "config", "traffic", "metric",
                                  "arch", "device_kind", "rate"])
def test_unknown_names_are_refused(what, tmp_path):
    reg = registry()
    with pytest.raises(UnknownName):
        if what == "workload":
            reg.cell("tiny.nothing")
        elif what == "config":
            reg.config("internvl2-2b")        # a file, but not in the bench
        elif what == "traffic":
            reg.traffic("no-such-mix")
        elif what == "metric":
            reg.reader("no_such_metric")
        elif what == "arch":
            reg.arch("mixture_of_nothing")
        elif what == "device_kind":
            reg.peaks("TPU v99")
        else:
            bench = dict(reg.bench, workloads=[
                {"name": "tiny.unrated", "config": "tiny",
                 "traffic": "tiny-open", "chips": 1}])
            path = tmp_path / "bench.json"
            path.write_text(json.dumps(bench))
            registry(str(path)).cell("tiny.unrated")


def test_a_new_config_mix_and_metric_need_only_new_files(tmp_path):
    """A throwaway configuration, mix and metric, added as files in a
    directory of their own and as entries, run without touching a file
    that is there."""
    for sub in ("configs", "traffic", "metrics"):
        (tmp_path / sub).mkdir()
    conf = json.loads((open(os.path.join(DATA, "configs", "tiny.json"))
                       .read()))
    conf["name"] = "tiny-wide"
    conf["program"]["fields"]["d_ff"] = conf["intermediate_size"] = 256
    (tmp_path / "configs" / "tiny-wide.json").write_text(json.dumps(conf))
    shutil.copy(os.path.join(DATA, "traffic", "tiny-closed.json"),
                tmp_path / "traffic" / "tiny-batch.json")
    (tmp_path / "metrics" / "ticks_run.py").write_text(
        "def read(run):\n    return float(len(run.ticks))\n")
    bench = json.loads(open(os.path.join(DATA, "bench.json")).read())
    bench["configs"].append({"name": "tiny-wide"})
    bench["workloads"].append({"name": "tiny-wide.batch",
                               "config": "tiny-wide",
                               "traffic": "tiny-batch", "chips": 1})
    bench["end_to_end"].append({"name": "ticks_run", "unit": "ticks",
                                "workloads": ["tiny-wide.batch"]})
    path = tmp_path / "bench.json"
    path.write_text(json.dumps(bench))
    reg = registry(str(path), dirs=(str(tmp_path),))
    res = cpu_run("tiny-wide.batch", reg=reg, seconds=1.0)
    assert res["correct"], res["compared"]
    assert res["metrics"]["ticks_run"]["value"] > 0
    assert "ticks_run" not in [m["name"] for m in
                               reg.cell("tiny.closed").end_to_end]
