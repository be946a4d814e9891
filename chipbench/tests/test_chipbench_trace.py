"""The reduction from a device trace to metrics, on a trace recorded on
one TPU v5e chip: internvl2-2b under the conv mix, six fleet ticks of a
traced window, each a 128-token prompt chunk and a decode step over the
8-slot pool.  Numbers from the reduction are checked against a plain
timeline of the same events at 1 microsecond resolution."""

import gzip
import json
import os

import numpy as np
import pytest

from chipbench_testlib import DATA
from harness import trace as tr

FIXTURE = os.path.join(DATA, "trace_v5e_internvl2_conv.json.gz")


@pytest.fixture(scope="module")
def trace():
    with gzip.open(FIXTURE, "rt") as f:
        return json.load(f)


def timeline(trace):
    """Boolean device-busy timeline over the window, one bin per us."""
    lo, hi = trace["window"]
    busy = np.zeros((hi - lo) // 1000 + 1, bool)
    for _, s, d in trace["devices"]["/device:TPU:0"]["ops"]:
        a, b = max(s, lo), min(s + d, hi)
        if a < b:
            busy[(a - lo) // 1000:(b - lo) // 1000] = True
    return busy


def test_busy_and_idle_share_match_a_plain_timeline(trace):
    busy = timeline(trace)
    assert tr.busy_s(trace) == pytest.approx(busy.sum() / 1e6, abs=2e-4)
    assert tr.window_s(trace) == pytest.approx(0.552628126)
    assert 0 < tr.busy_s(trace) < tr.window_s(trace)


def test_step_executions(trace):
    dec = tr.executions(trace, "decode_fn")
    chunk = tr.executions(trace, "chunk_fn")
    assert len(dec) == len(chunk) == 6
    assert np.mean(dec) / 1e6 == pytest.approx(38.665, abs=0.01)
    assert np.mean(chunk) / 1e6 == pytest.approx(31.422, abs=0.01)
    assert tr.executions(trace, "no_such_fn") == []


def test_host_time_per_tick(trace):
    busy = timeline(trace)
    lo = trace["window"][0]
    ticks = tr.host_spans(trace, tr.TICK)
    assert len(ticks) == 6
    intervals = tr.busy(trace, "/device:TPU:0")
    for a, b in ticks:
        plain = busy[(a - lo) // 1000:(b - lo) // 1000].sum() * 1000
        assert tr.overlap(intervals, a, b) == pytest.approx(plain, abs=3e4)


def test_self_times_of_nested_ops():
    events = [["while", 0, 100], ["a", 10, 20], ["b", 40, 30],
              ["c", 45, 5], ["d", 150, 10]]
    own = {n: t for n, _, t in tr.self_times(events)}
    assert own == {"while": 50, "a": 20, "b": 25, "c": 5, "d": 10}


def test_breakdown(trace):
    ops = tr.top_ops(trace)
    assert len(ops) == 10
    assert all(s > 0 for _, s in ops)
    assert sum(s for _, s in ops) <= tr.busy_s(trace) + 1e-9
    assert all(n.split("/")[0] in ("decode_fn", "chunk_fn", "?")
               or n.startswith("convert") for n, _ in ops)
    gaps = tr.idle_gaps(trace)
    assert [g for _, g in gaps] == sorted((g for _, g in gaps), reverse=True)
    assert sum(g for _, g in gaps) <= tr.window_s(trace) - tr.busy_s(trace) \
        + 1e-9
    assert {n for n, _ in gaps} <= set(tr.SPANS) | {"outside_spans"}


def test_merged_intervals():
    assert tr.merged([["x", 0, 10], ["y", 5, 10], ["z", 30, 5]], 2, 32) == \
        [(2, 15), (30, 32)]
    assert tr.overlap([(0, 10), (20, 30)], 5, 25) == 10
