"""Dense decoder with grouped-query attention, a bias on every linear
layer of a block and a head tied to the embedding (llama with
``attention_bias``, ``mlp_bias`` and ``tie_word_embeddings``: Granite
Code).

The same interface as ``dense_gqa.py``, whose helpers it shares (norm,
rotary, causal attention, float8 rounding, matmul), and like it written
from the published description, importing nothing of the program under
test:

* ``make_weights``: random weights from a key in the program's tree, with
  the bias leaves and no ``head`` leaf;
* ``gap_fn``: the plain reference in float32 at the highest matmul
  precision, and the widest gap by which a served token's logit lies
  below the reference's best; with ``control=True`` the same reference
  with every weight matmul in float8 e4m3 (bias adds stay in float32);
* ``param_count``, ``kv_bytes_per_token``, ``decode_work``,
  ``chunk_work``: counts from shapes alone.

A layer: ``h = n1(x)``; ``q = rope(wq(h) + bq)``, ``k = rope(wk(h) +
bk)``, ``v = wv(h) + bv``; ``x += wo(attn(q, k, v)) + bo``; ``h =
n2(x)``; ``x += w_down(silu(w_gate(h) + b_gate) * (w_up(h) + b_up)) +
b_down``.  The q and k biases are added before rotary, as llama does.
After the last layer an RMS norm, then logits against the embedding
table's rows (the tied head).

Departures from the published model: depth, which the configuration file
gives (its ``reduced``); the weights are random, drawn from the seed.
"""

from __future__ import annotations

import functools
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp


def _load_dense_gqa():
    """The sibling ``dense_gqa.py``, loaded by its path as the harness
    loads architectures."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "dense_gqa.py")
    key = f"chipbench_arch_dense_gqa_shared_{abs(hash(path))}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


G = _load_dense_gqa()
Dims, dims, HIGHEST = G.Dims, G.dims, G.HIGHEST
_rms, _rope, _attention, _mm = G._rms, G._rope, G._attention, G._mm

#: standard deviation of every bias, against the unit spread of a
#: projection's output entries (a unit-RMS input through fan-in-scaled
#: weights).  Summed over the layers, biases of this size still move the
#: logits far past the limit of ``correct`` when any one kind is left
#: out.  Larger ones (0.2 to 0.5) fill the residual stream with one
#: constant vector: every position then ranks the vocabulary alike, by a
#: margin that the float8 control rarely crosses, so the control would
#: pass the limit it has to fail.
BIAS_STD = 0.05


def _bias_shapes(m: Dims) -> dict:
    q, kv = m.heads * m.head_dim, m.kv_heads * m.head_dim
    return {"bq": (q,), "bk": (kv,), "bv": (kv,), "bo": (m.d,),
            "b_gate": (m.ff,), "b_up": (m.ff,), "b_down": (m.d,)}


def _layer_params(m: Dims) -> int:
    """Parameters of one layer: matrices, biases and two norms."""
    return (G.layer_matmul_params(m)
            + sum(s[0] for s in _bias_shapes(m).values()) + 2 * m.d)


# -- weights -------------------------------------------------------------------


def make_weights(conf: dict, key: jax.Array) -> dict:
    """Weights in bf16, in the tree the serving program takes:
    ``embed`` (vocab, d), which is also the head, ``final_norm`` (d,), and
    ``units.b0`` holding each layer matrix and bias stacked over layers.
    Jit it: each layer is drawn in turn."""
    m = dims(conf)
    bf16 = jnp.bfloat16
    k_embed, k_layers = jax.random.split(key)
    shapes = G._layer_shapes(m)
    biases = _bias_shapes(m)

    def one_layer(k):
        km, kb = jax.random.split(k)
        ks = jax.random.split(km, len(shapes))
        out = {name: (jax.random.normal(kk, shape, jnp.float32)
                      * shape[0] ** -0.5).astype(bf16)
               for kk, (name, shape) in zip(ks, sorted(shapes.items()))}
        ks = jax.random.split(kb, len(biases))
        out.update({name: (jax.random.normal(kk, shape, jnp.float32)
                           * BIAS_STD).astype(bf16)
                    for kk, (name, shape) in zip(ks, sorted(biases.items()))})
        return out

    units = jax.lax.map(one_layer, jax.random.split(k_layers, m.layers))
    ones = jnp.ones((m.layers, m.d), bf16)
    units.update(attn_norm=ones, ffn_norm=ones)
    embed = (jax.random.normal(k_embed, (m.vocab, m.d), jnp.float32)
             * m.d ** -0.5).astype(bf16)
    return {"embed": embed, "final_norm": jnp.ones((m.d,), bf16),
            "units": {"b0": units}}


# -- reference and control -------------------------------------------------------


def _f32(b):
    return b.astype(jnp.float32)


def _hidden(params, tokens, m: Dims, low: bool):
    """Final-norm hidden states (T, d) in float32 for one sequence."""
    t = tokens.shape[0]
    pos = jnp.arange(t)
    x = params["embed"][tokens].astype(jnp.float32)

    def layer(x, p):
        h = _rms(x, p["attn_norm"], m.eps)
        q = (_mm(h, p["wq"], low) + _f32(p["bq"])).reshape(
            t, m.heads, m.head_dim)
        k = (_mm(h, p["wk"], low) + _f32(p["bk"])).reshape(
            t, m.kv_heads, m.head_dim)
        v = (_mm(h, p["wv"], low) + _f32(p["bv"])).reshape(
            t, m.kv_heads, m.head_dim)
        q, k = _rope(q, pos, m.rope_theta), _rope(k, pos, m.rope_theta)
        x = x + _mm(_attention(q, k, v, m), p["wo"], low) + _f32(p["bo"])
        h = _rms(x, p["ffn_norm"], m.eps)
        f = jax.nn.silu(_mm(h, p["w_gate"], low) + _f32(p["b_gate"])) \
            * (_mm(h, p["w_up"], low) + _f32(p["b_up"]))
        return x + _mm(f, p["w_down"], low) + _f32(p["b_down"]), None

    x, _ = jax.lax.scan(layer, x, params["units"]["b0"])
    return _rms(x, params["final_norm"], m.eps)


def _gaps(params, tokens, targets, valid, *, m: Dims, control: bool):
    """As ``dense_gqa._gaps``, with the head read from the embedding."""
    h = _hidden(params, tokens, m, low=False)
    h_low = _hidden(params, tokens, m, low=True) if control else h
    head = params["embed"].T
    t = tokens.shape[0]
    nb = t // G.HEAD_BLOCK

    def block(args):
        hb, lb, tb = args
        logits = _mm(hb, head, False)
        chosen = jnp.argmax(_mm(lb, head, True), -1) if control else tb
        best = logits.max(-1)
        mine = jnp.take_along_axis(logits, chosen[:, None], -1)[:, 0]
        return best - mine, chosen == jnp.argmax(logits, -1)

    gap, agree = jax.lax.map(block, (h.reshape(nb, G.HEAD_BLOCK, -1),
                                     h_low.reshape(nb, G.HEAD_BLOCK, -1),
                                     targets.reshape(nb, G.HEAD_BLOCK)))
    gap, agree = gap.reshape(t), agree.reshape(t)
    return (jnp.where(valid, gap, 0.0).max(),
            jnp.sum(valid & agree), jnp.sum(valid))


def gap_fn(conf: dict, *, control: bool = False):
    """Jitted ``(params, tokens, targets, valid) -> (max_gap, agree, n)``
    for one sequence padded to a fixed length (a multiple of 512)."""
    return jax.jit(functools.partial(_gaps, m=dims(conf), control=control))


def logits(params, tokens, conf: dict):
    """The reference's logits (T, vocab) in float32 for one sequence."""
    m = dims(conf)
    return _mm(_hidden(params, tokens, m, low=False), params["embed"].T,
               False)


# -- work from shapes -----------------------------------------------------------


def param_count(conf: dict) -> int:
    """Every parameter held: layers (matrices, biases, two norms), the
    embedding that is also the head, and the final norm."""
    m = dims(conf)
    return m.layers * _layer_params(m) + m.vocab * m.d + m.d


kv_bytes_per_token = G.kv_bytes_per_token


def _bias_adds(m: Dims) -> int:
    """Additions of one token's biases over all layers."""
    return m.layers * sum(s[0] for s in _bias_shapes(m).values())


def _weight_bytes(m: Dims, head: bool) -> int:
    """bf16 bytes of the layers, and with ``head`` of the tied table read
    whole by the head and of the final norm."""
    return (m.layers * _layer_params(m) * 2
            + (m.d * m.vocab + m.d) * 2 * head)


def decode_work(conf: dict, contexts) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one decode step for the live rows, where row
    ``i`` attends to ``contexts[i]`` tokens, its own new one included:
    every weight and bias read once, the tied table read once by the
    head, one embedding row per live row, the K/V of earlier tokens read
    and the new K/V written; the bias adds of every row counted."""
    m = dims(conf)
    n, ctx = len(contexts), sum(contexts)
    attn = 4 * m.heads * m.head_dim * m.layers
    flops = n * (2 * (m.layers * G.layer_matmul_params(m) + m.d * m.vocab)
                 + _bias_adds(m)) + attn * ctx
    kv = kv_bytes_per_token(conf)
    nbytes = _weight_bytes(m, head=True) + n * m.d * 2 + (ctx - n) * kv \
        + n * kv
    return float(flops), float(nbytes)


def chunk_work(conf: dict, start: int, n: int,
               completes: bool) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one prefill chunk of ``n`` prompt tokens at
    positions ``start ..``: as ``dense_gqa.chunk_work``, with the biases
    read and added, and the tied table read by the head only in the chunk
    that completes the prompt."""
    m = dims(conf)
    attn = 4 * m.heads * m.head_dim * m.layers
    ctx = n * start + n * (n + 1) // 2
    flops = n * (2 * m.layers * G.layer_matmul_params(m) + _bias_adds(m)) \
        + attn * ctx + completes * 2 * m.d * m.vocab
    kv = kv_bytes_per_token(conf)
    nbytes = _weight_bytes(m, head=completes) + n * m.d * 2 + start * kv \
        + n * kv
    return float(flops), float(nbytes)
