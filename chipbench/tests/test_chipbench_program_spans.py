"""A traced run of a test cell under a program that writes host spans of
its own (``serve.*``, ``repro.serve.spans``): the profiler records them
beside the benchmark's, and the loader keeps the benchmark's alone, so
every metric and ``breakdown`` reading that reads host spans reads what
it read before the program had spans."""

import glob
import os

from chipbench_testlib import cpu_run
from harness import trace as tr


def test_loader_keeps_the_benchmark_spans_alone(monkeypatch):
    recorded, loaded = set(), []
    real = tr.load

    def load(log_dir):
        from jax.profiler import ProfileData
        (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile",
                                         "*", "*.xplane.pb"))
        recorded.update(ev.name for plane in ProfileData.from_file(
            path).planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events)
        loaded.append(real(log_dir))
        return loaded[-1]

    monkeypatch.setattr(tr, "load", load)
    res = cpu_run("tiny.open", seed=2**31 + 17, trace=True)
    assert res["correct"], res["compared"]
    assert {"serve.route", "serve.decode", "serve.sync",
            "serve.drain"} <= recorded
    (plain,) = loaded
    names = {h[0] for h in plain["host"]}
    assert tr.TICK in names and names <= set(tr.SPANS)
    assert {n for n, _ in res["breakdown"]["idle_gaps"]} <= \
        set(tr.SPANS) | {"outside_spans"}
