"""Granite Code's biases and tied head, served through the paged engine,
against the benchmark's plain float32 reference
(``chipbench/arch/dense_gqa_bias.py``), on seeded random weights at a
CPU size.

Prefill (in chunks over several pages) and then decode run through
``PagedServeEngine``; every logit row the engine samples from is compared
with the reference's full forward pass over the same tokens.  Both sides
compute in float32, so what differs is the order of summation (chunked
paged attention against one causal pass), which moves a logit by about
4e-6 here.  ``TOL`` = 1e-4 leaves twenty-five times that, and lies more
than eight thousand times below what leaving out any one kind of bias,
or untying the head, does to the logits (0.86 to 5.9).
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import layers as L
from repro.models import transformer as T
from repro.serve.engine import PagedServeEngine, Request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "granite_reference", os.path.join(ROOT, "chipbench", "arch",
                                      "dense_gqa_bias.py"))
REF = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(REF)

#: the reference's sizes, in Hugging Face key names: granite's smoke size
CONF = {"num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 256, "rope_theta": 1e7, "rms_norm_eps": 1e-5}
CFG = configs.get_smoke_config("granite-8b")
TOL = 1e-4
PLEN, N_NEW = 19, 12

BIAS_LEAVES = ("bq", "bk", "bv", "bo", "b_gate", "b_up", "b_down")


@pytest.fixture(scope="module")
def weights():
    w = jax.jit(lambda k: REF.make_weights(CONF, k))(jax.random.key(7))
    return jax.tree.map(lambda a: a.astype(jnp.float32), w)


def _serve(cfg, params, prompt, n_new):
    """Greedy tokens and the logit row each was sampled from."""
    rows = []

    def sampler(logits):
        a = np.asarray(logits)
        rows.append(a if a.ndim == 1 else a[0])
        return jnp.argmax(logits, -1)

    eng = PagedServeEngine(cfg, params, max_slots=1, max_len=64, page_len=8,
                           sampler=sampler)
    eng.submit(Request(0, prompt, n_new))
    eng.run_to_completion()
    return eng.finished[0].generated, np.stack(rows)


def _max_error(weights, cfg, params) -> float:
    """Widest gap between the engine's logits and the reference's, at
    every position the engine sampled."""
    prompt = np.random.default_rng(0).integers(
        CONF["vocab_size"], size=PLEN).astype(np.int32)
    generated, got = _serve(cfg, params, prompt, N_NEW)
    seq = np.concatenate([prompt, np.asarray(generated[:-1], np.int32)])
    tokens = np.zeros(REF.G.Q_BLOCK, np.int32)
    tokens[:len(seq)] = seq
    ref = jax.jit(lambda p, t: REF.logits(p, t, CONF))(weights,
                                                       jnp.asarray(tokens))
    want = np.asarray(ref)[PLEN - 1:PLEN - 1 + N_NEW]
    return float(np.abs(got - want).max())


def test_config_carries_the_published_flags():
    full = configs.get_config("granite-8b")
    assert (full.attention_bias, full.mlp_bias, full.tie_embeddings) == \
        (True, True, True)
    assert (full.rope_theta, full.norm_eps) == (1e7, 1e-5)
    assert (CFG.attention_bias, CFG.mlp_bias, CFG.tie_embeddings) == \
        (True, True, True)


def test_reference_tree_is_the_programs(weights):
    """The reference's weights have the program's tree: the bias leaves
    and no head leaf."""
    prog = jax.eval_shape(lambda k: T.init_params(CFG, k), jax.random.key(0))
    shapes = lambda t: jax.tree.map(lambda a: a.shape, t)
    assert shapes(weights) == shapes(prog)
    assert "head" not in weights
    for name in BIAS_LEAVES:
        assert float(jnp.abs(weights["units"]["b0"][name]).max()) > 0


def test_each_bias_takes_its_matrix_output_axis():
    matrix = {"bq": "wq", "bk": "wk", "bv": "wv", "bo": "wo",
              "b_gate": "w_gate", "b_up": "w_up", "b_down": "w_down"}
    for bias, w in matrix.items():
        assert L.PARAM_AXES[bias] == (L.PARAM_AXES[w][1],), bias


def test_paged_prefill_and_decode_match_the_reference(weights):
    assert _max_error(weights, CFG, weights) < TOL


def _without(weights, names):
    units = dict(weights["units"]["b0"])
    for n in names:
        units[n] = jnp.zeros_like(units[n])
    return CFG, dict(weights, units={"b0": units})


def _untied(weights):
    head = jax.random.normal(jax.random.key(9), (CONF["hidden_size"],
                                                 CONF["vocab_size"]))
    return (dataclasses.replace(CFG, tie_embeddings=False),
            dict(weights, head=head * CONF["hidden_size"] ** -0.5))


MUTATIONS = {
    "qkv_biases_dropped": lambda w: _without(w, ("bq", "bk", "bv")),
    "bo_dropped": lambda w: _without(w, ("bo",)),
    "mlp_biases_dropped": lambda w: _without(w, ("b_gate", "b_up",
                                                 "b_down")),
    "head_untied": _untied,
}


@pytest.mark.parametrize("mutation", list(MUTATIONS))
def test_each_bias_kind_and_the_tie_are_needed(mutation, weights):
    """A program that leaves out one kind of bias, or serves an untied
    head, fails the comparison."""
    cfg, params = MUTATIONS[mutation](weights)
    err = _max_error(weights, cfg, params)
    assert err > 100 * TOL, f"{mutation}: {err}"


def test_bias_free_config_keeps_its_parameter_tree():
    """With the flags off no leaf is added, and every leaf a biased config
    shares with it is drawn alike: the flags only add leaves."""
    plain = configs.get_smoke_config("internvl2-2b")
    params = T.init_params(plain, jax.random.key(3))
    unit = params["units"]["b0"]
    assert sorted(unit) == ["attn_norm", "ffn_norm", "w_down", "w_gate",
                            "w_up", "wk", "wo", "wq", "wv"]
    assert "head" in params
    biased = T.init_params(dataclasses.replace(plain, attention_bias=True,
                                               mlp_bias=True),
                           jax.random.key(3))
    assert sorted(biased["units"]["b0"]) == sorted(set(unit) |
                                                   set(BIAS_LEAVES))
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        other = biased
        for p in path:
            other = other[p.key]
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(other))


def test_bias_free_logits_are_bit_identical():
    """The same weights served with the flags off, and with the flags on
    and every bias zero, give the same logits bit for bit: with the
    flags off the step is the bias-free step, and the bias adds are the
    only thing the flags change."""
    plain = dataclasses.replace(configs.get_smoke_config("granite-8b"),
                                attention_bias=False, mlp_bias=False)
    on = dataclasses.replace(plain, attention_bias=True, mlp_bias=True)
    params = T.init_params(on, jax.random.key(4))
    zeroed = jax.tree_util.tree_map_with_path(
        lambda p, a: jnp.zeros_like(a) if p[-1].key in BIAS_LEAVES else a,
        params)
    stripped = dict(params, units={"b0": {
        k: v for k, v in params["units"]["b0"].items()
        if k not in BIAS_LEAVES}})
    prompt = np.arange(PLEN, dtype=np.int32) * 7 % plain.vocab_size
    tok_a, a = _serve(plain, stripped, prompt, N_NEW)
    tok_b, b = _serve(on, zeroed, prompt, N_NEW)
    assert tok_a == tok_b
    np.testing.assert_array_equal(a, b)
