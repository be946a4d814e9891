"""chip_smoke.py off the chip: it refuses to run without a TPU, and its
serving phase and checks hold on the CPU at a tiny size."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402

from repro import configs  # noqa: E402
from repro.core.devices import TPU_V5E, tpu_spec_for_kind  # noqa: E402
from repro.models import transformer as T  # noqa: E402

TINY = dataclasses.replace(
    configs.get_config(chip_smoke.ARCH), num_layers=2, d_model=128,
    d_ff=256, vocab_size=512, head_dim=16)


def test_exits_nonzero_without_a_tpu(capsys):
    if jax.default_backend() == "tpu":
        pytest.skip("a TPU is attached")
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_unknown_device_kind_has_no_spec():
    assert tpu_spec_for_kind("TPU v5 lite") is TPU_V5E
    with pytest.raises(ValueError):
        tpu_spec_for_kind("TPU v4")


def test_requests_stay_in_their_ranges():
    reqs = chip_smoke.make_requests(TINY, seed=0)
    assert len(reqs) == chip_smoke.REQUESTS
    assert all(chip_smoke.PROMPT_LEN[0] <= len(p) <= chip_smoke.PROMPT_LEN[1]
               and chip_smoke.NEW_TOKENS[0] <= n <= chip_smoke.NEW_TOKENS[1]
               for p, n in reqs)
    worst = max(len(p) + n for p, n in reqs)
    assert worst <= chip_smoke.MAX_LEN
    assert chip_smoke.NUM_PAGES == (chip_smoke.SLOTS * chip_smoke.MAX_LEN
                                    // chip_smoke.PAGE_LEN + 1)


def test_serves_and_prefill_logits_match_forward(capsys):
    params = jax.jit(lambda k: T.init_params(TINY, k))(jax.random.key(0))
    reqs = chip_smoke.make_requests(TINY, seed=1, n=4, prompt_len=(40, 200),
                                    new_tokens=(3, 8))
    streams, probe, _, _ = chip_smoke.serve(
        TINY, params, reqs, TPU_V5E, slots=2, max_len=256, page_len=32,
        num_pages=2 * 8 + 1)
    assert [len(s) for s in streams] == [n for _, n in reqs]
    assert int(jnp.argmax(probe.first_prefill)) == streams[0][0]
    err = chip_smoke.reference_error(TINY, params, reqs[0][0],
                                     probe.first_prefill)
    assert err <= chip_smoke.LOGIT_TOL
    assert "leaked=0" in capsys.readouterr().out


def test_probe_flags_non_finite_logits():
    probe = chip_smoke.LogitsProbe()
    probe(jnp.array([[0.0, 1.0], [2.0, 3.0]]))
    assert bool(probe.finite) and probe.first_prefill is None
    probe(jnp.array([jnp.nan, 1.0]))
    assert not bool(probe.finite)
    assert probe.first_prefill.shape == (2,)
