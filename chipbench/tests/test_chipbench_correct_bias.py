"""``correct`` on the CPU for a biased, tied configuration
(``arch/dense_gqa_bias.py``): a sound run passes; the program serving
the same weights without its biases fails."""

import dataclasses
import os

from chipbench_testlib import DATA, R, cpu_run, registry


def _reg():
    return registry(os.path.join(DATA, "bench_bias.json"))


def test_sound_biased_run_is_correct():
    res = cpu_run("tiny-bias.closed", reg=_reg(), seed=2**31 + 5)
    assert res["correct"], res["compared"]
    c = res["compared"]
    assert c["checked_requests"]["value"] == c["checked_requests"]["limit"]
    assert c["max_logit_gap"]["value"] < c["max_logit_gap"]["limit"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_program_without_biases_is_not_correct(monkeypatch):
    """The weights hold the biases; a program that leaves them out reads
    far beyond the limit."""
    plain = R.program_config
    monkeypatch.setattr(R, "program_config", lambda conf: dataclasses.replace(
        plain(conf), attention_bias=False, mlp_bias=False))
    res = cpu_run("tiny-bias.closed", reg=_reg(), seed=5)
    assert not res["correct"]
    c = res["compared"]["max_logit_gap"]
    assert c["value"] > 10 * c["limit"], c
