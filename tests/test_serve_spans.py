"""The serving loop's host spans and the paged steps' named scopes.

The spans (``repro.serve.spans``) are ``jax.profiler.TraceAnnotation``s.
A profiled run of a small fleet must hold each of them: inside the
caller's tick those of one fleet step, none overlapping another of the
same level, and each request's submission paired with its admission by
``uid``.  (The named scopes of the jitted steps are checked where those
are compiled for the chip, in ``test_tpu_compile.py``.)
"""

import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.serve import spans
from repro.serve.fleet import FleetEngine
from repro.serve.frontend import FleetFrontend

TICK = "tick"
PROGRAM = (spans.SUBMIT, spans.ROUTE, spans.ADMIT, spans.ADMITTED,
           spans.PREFILL, spans.DECODE, spans.SYNC, spans.COMMIT,
           spans.DRAIN)
#: the host work of a tick, one span after another
SIBLINGS = (spans.ROUTE, spans.ADMIT, spans.PREFILL, spans.DECODE,
            spans.SYNC, spans.COMMIT, spans.DRAIN)
MAX_LEN, PAGE_LEN = 32, 4


@pytest.fixture(scope="module")
def micro():
    cfg = ModelConfig(name="micro", family="dense", num_layers=2,
                      d_model=32, d_ff=64, vocab_size=64, num_heads=2,
                      num_kv_heads=1, dtype="float32",
                      param_dtype="float32")
    return cfg, T.init_params(cfg, jax.random.key(0))


def _host_events(log_dir):
    """[name, start, end, stats] of every host event in the one trace."""
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [[ev.name, int(ev.start_ns),
                         int(ev.start_ns + ev.duration_ns), dict(ev.stats)]
                        for ev in line.events]
    return out


@pytest.fixture(scope="module")
def traced(micro, tmp_path_factory):
    """Six requests over two slots, submitted while earlier ones run, each
    tick inside a ``tick`` span, under the profiler."""
    cfg, params = micro
    front = FleetFrontend(FleetEngine(cfg, params, max_slots=2,
                                      max_len=MAX_LEN, replicas=1,
                                      page_len=PAGE_LEN))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(cfg.vocab_size, size=n).astype(np.int32)
               for n in (9, 3, 6, 5, 11, 4)]
    front.submit(prompts[0], 2, uid=100)      # compile outside the trace
    front.run()
    log_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        for uid, prompt in enumerate(prompts):
            front.submit(prompt, 3, uid=uid)
            with TraceAnnotation(TICK):
                front.tick()
        while any(not h.settled for h in front.handles.values()):
            with TraceAnnotation(TICK):
                front.tick()
    finally:
        jax.profiler.stop_trace()
    return [e for e in _host_events(log_dir)
            if e[0] == TICK or e[0].startswith("serve.")]


def _inside(ev, outer):
    return outer[1] <= ev[1] and ev[2] <= outer[2]


def test_every_span_recorded(traced):
    assert {e[0] for e in traced} == {TICK, *PROGRAM}
    assert all(name.startswith("serve.") for name in PROGRAM)


def test_tick_spans_nest_in_the_tick_one_after_another(traced):
    ticks = [e for e in traced if e[0] == TICK]
    for ev in traced:
        if ev[0] not in (TICK, spans.SUBMIT):
            assert any(_inside(ev, t) for t in ticks), ev
    for t in ticks:
        inner = sorted((e for e in traced
                        if e[0] in SIBLINGS and _inside(e, t)),
                       key=lambda e: e[1])
        assert [e[0] for e in inner][:1] == [spans.ROUTE]
        assert inner[-1][0] == spans.DRAIN
        for a, b in zip(inner, inner[1:]):
            assert a[2] <= b[1], (a, b)
        assert sum(e[2] - e[1] for e in inner) <= t[2] - t[1]
    admits = [e for e in traced if e[0] == spans.ADMIT]
    for ev in traced:
        if ev[0] == spans.ADMITTED:
            assert any(_inside(ev, a) for a in admits), ev


def test_each_submission_pairs_with_one_admission(traced):
    submits = {e[3]["uid"]: e for e in traced if e[0] == spans.SUBMIT}
    admitted = [e for e in traced if e[0] == spans.ADMITTED]
    assert sorted(submits) == list(range(6))
    assert sorted(e[3]["uid"] for e in admitted) == list(range(6))
    for ev in admitted:
        assert submits[ev[3]["uid"]][2] <= ev[1]
