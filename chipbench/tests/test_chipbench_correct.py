"""``correct`` on the CPU at a test size: a sound run passes; the
control and each fault the served path can have fail it.

The faults are planted in the program's step function underneath the
harness, which then runs as it does on the chip, past its chip check.
"""

import jax.numpy as jnp
import pytest

from chipbench_testlib import R, cpu_run, registry


def test_sound_run_is_correct():
    res = cpu_run("tiny.open", seed=2**31 + 3)
    assert res["correct"], res["compared"]
    c = res["compared"]
    assert c["checked_requests"]["value"] == c["checked_requests"]["limit"]
    assert c["max_logit_gap"]["value"] < c["max_logit_gap"]["limit"]
    assert list(res)[-1] == "compared"
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"ttft_p90_ms", "itl_p95_ms",
                                   "output_tok_s", "setup_s"}


def test_control_fails_the_limit():
    """The reference in float8, put in the program's place, reads a gap
    beyond the limit on every seed tried."""
    reg = registry()
    conf = reg._json("configs", "tiny")
    res = cpu_run("tiny.closed", seed=11, control=True)
    assert res["correct"]
    assert res["control"]["max_logit_gap"] > \
        conf["check"]["max_logit_gap"], res["control"]
    # judged by the comparison that decides the program's correct
    assert res["control"]["correct"] is False
    assert res["control"]["compared"]["max_logit_gap"]["limit"] == \
        res["compared"]["max_logit_gap"]["limit"]


def _unchanged_state(step):
    def faulty(params, cfg, cache, *a):
        logits, _ = step(params, cfg, cache, *a)
        return logits, cache
    return faulty


def _half_batch(step):
    def faulty(params, cfg, cache, tokens, *a):
        logits, new = step(params, cfg, cache, tokens, *a)
        if tokens.shape[1] == 1:           # decode: odd rows not computed
            logits = logits.at[1::2].set(0.0)
        return logits, new
    return faulty


def _altered_token(step):
    def faulty(params, cfg, cache, tokens, *a):
        logits, new = step(params, cfg, cache, tokens, *a)
        if tokens.shape[1] == 1:           # each decoded token moves by one
            logits = jnp.roll(logits, 1, axis=-1)
        return logits, new
    return faulty


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _altered_token])
def test_fault_is_not_correct(fault, monkeypatch):
    R._import_program()
    from repro.models import transformer as T
    monkeypatch.setattr(T, "paged_step", fault(T.paged_step))
    res = cpu_run("tiny.closed", seed=5)
    assert not res["correct"], res["compared"]
