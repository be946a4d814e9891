"""Dense decoder with grouped-query attention and no biases (llama,
InternLM2).

Everything the benchmark needs of this architecture, written from the
published description and importing nothing of the program under test:

* ``make_weights``: random weights from a key, in the parameter tree the
  serving program takes, in the dtype they are served in (bf16);
* ``gaps``: the plain reference, a float32 forward pass at the highest
  matmul precision, and the widest gap by which a served token's logit
  lies below the reference's best logit at its position;
* the control (``control=True``): the same reference computed with every
  weight matmul in float8 (e4m3, scaled per row of activations and per
  output column of weights), the precision below bf16; its gap is read
  for the token the float8 pass puts first;
* ``decode_work`` and ``chunk_work``: the FLOPs and HBM bytes the
  algorithm needs for one step, from shapes alone.

A layer: ``x += wo(attn(rope(wq(n1(x))), rope(wk(n1(x))), wv(n1(x))))``
then ``x += w_down(silu(w_gate(n2(x))) * w_up(n2(x)))``, with RMS norms
``n1``, ``n2`` (weight 1), rotary embedding by rotating halves, and query
head ``h`` reading K/V head ``h // (heads / kv_heads)``.  After the last
layer an RMS norm and an untied head give the logits.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

#: query rows per block of the reference's attention, and positions per
#: block of its head, so that the reference fits beside the weights
Q_BLOCK = 512
HEAD_BLOCK = 256

#: the largest finite float8 e4m3 value: scales map each row's amax to it
E4M3_MAX = 448.0


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    ff: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    rope_theta: float
    eps: float


def dims(conf: dict) -> Dims:
    """Sizes from a configuration file (Hugging Face key names)."""
    return Dims(layers=conf["num_hidden_layers"], d=conf["hidden_size"],
                ff=conf["intermediate_size"],
                heads=conf["num_attention_heads"],
                kv_heads=conf["num_key_value_heads"],
                head_dim=conf["head_dim"], vocab=conf["vocab_size"],
                rope_theta=float(conf["rope_theta"]),
                eps=float(conf["rms_norm_eps"]))


# -- weights -------------------------------------------------------------------


def _layer_shapes(m: Dims) -> dict:
    """Matrix shapes of one layer, each scaled by its fan-in ** -1/2."""
    q, kv = m.heads * m.head_dim, m.kv_heads * m.head_dim
    return {"wq": (m.d, q), "wk": (m.d, kv), "wv": (m.d, kv),
            "wo": (q, m.d), "w_gate": (m.d, m.ff), "w_up": (m.d, m.ff),
            "w_down": (m.ff, m.d)}


def make_weights(conf: dict, key: jax.Array) -> dict:
    """Weights in bf16, in the tree the serving program takes:
    ``embed`` (vocab, d), ``head`` (d, vocab), ``final_norm`` (d,), and
    ``units.b0`` holding each layer matrix stacked over layers.  Jit it:
    each layer is drawn in turn, so no float32 copy of a whole stack is
    ever held."""
    m = dims(conf)
    bf16 = jnp.bfloat16
    k_embed, k_head, k_layers = jax.random.split(key, 3)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(bf16)

    shapes = _layer_shapes(m)

    def one_layer(k):
        ks = jax.random.split(k, len(shapes))
        return {name: normal(kk, shape, shape[0])
                for kk, (name, shape) in zip(ks, sorted(shapes.items()))}

    units = jax.lax.map(one_layer, jax.random.split(k_layers, m.layers))
    ones = jnp.ones((m.layers, m.d), bf16)
    units.update(attn_norm=ones, ffn_norm=ones)
    return {"embed": normal(k_embed, (m.vocab, m.d), m.d),
            "head": normal(k_head, (m.d, m.vocab), m.d),
            "final_norm": jnp.ones((m.d,), bf16),
            "units": {"b0": units}}


# -- reference and control -------------------------------------------------------


def _fp8(x, axis):
    """Round to float8 e4m3 after scaling each slice along ``axis`` so its
    largest magnitude is e4m3's largest value; back to float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, E4M3_MAX / amax, 1.0)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _mm(a, w, low: bool):
    a, w = a.astype(jnp.float32), w.astype(jnp.float32)
    if low:
        a, w = _fp8(a, -1), _fp8(w, 0)
    return jnp.matmul(a, w, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        w.astype(jnp.float32)


def _rope(x, pos, theta):
    """x: (T, H, D); rotate the two halves of each head by pos * freq."""
    dim = x.shape[-1]
    freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = pos[:, None, None].astype(jnp.float32) * freq
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, m: Dims):
    """Causal attention, q (T, H, D), k/v (T, Hkv, D), in query blocks."""
    t = q.shape[0]
    group = m.heads // m.kv_heads
    qb = q.reshape(t // Q_BLOCK, Q_BLOCK, m.kv_heads, group, m.head_dim)
    keys = jnp.arange(t)

    def block(args):
        i, qi = args
        s = jnp.einsum("skgd,tkd->kgst", qi, k,
                       precision=HIGHEST) * m.head_dim ** -0.5
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(keys[None, None, None, :] <= rows[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgst,tkd->skgd", p, v, precision=HIGHEST)

    o = jax.lax.map(block, (jnp.arange(t // Q_BLOCK), qb))
    return o.reshape(t, m.heads * m.head_dim)


def _hidden(params, tokens, m: Dims, low: bool):
    """Final-norm hidden states (T, d) in float32 for one sequence."""
    t = tokens.shape[0]
    pos = jnp.arange(t)
    x = params["embed"][tokens].astype(jnp.float32)

    def layer(x, p):
        h = _rms(x, p["attn_norm"], m.eps)
        q = _rope(_mm(h, p["wq"], low).reshape(t, m.heads, m.head_dim),
                  pos, m.rope_theta)
        k = _rope(_mm(h, p["wk"], low).reshape(t, m.kv_heads, m.head_dim),
                  pos, m.rope_theta)
        v = _mm(h, p["wv"], low).reshape(t, m.kv_heads, m.head_dim)
        x = x + _mm(_attention(q, k, v, m), p["wo"], low)
        h = _rms(x, p["ffn_norm"], m.eps)
        f = jax.nn.silu(_mm(h, p["w_gate"], low)) * _mm(h, p["w_up"], low)
        return x + _mm(f, p["w_down"], low), None

    x, _ = jax.lax.scan(layer, x, params["units"]["b0"])
    return _rms(x, params["final_norm"], m.eps)


def _gaps(params, tokens, targets, valid, *, m: Dims, control: bool):
    """Widest gap, over the positions where ``valid``, between the
    reference's best logit and its logit for the token chosen there:
    ``targets`` (the served tokens), or under ``control`` the token that
    the float8 pass ranks first.  Also counts the positions where the
    chosen token is the reference's own first choice."""
    h = _hidden(params, tokens, m, low=False)
    h_low = _hidden(params, tokens, m, low=True) if control else h
    t = tokens.shape[0]
    nb = t // HEAD_BLOCK

    def block(args):
        hb, lb, tb = args
        logits = _mm(hb, params["head"], False)
        chosen = (jnp.argmax(_mm(lb, params["head"], True), -1)
                  if control else tb)
        best = logits.max(-1)
        mine = jnp.take_along_axis(logits, chosen[:, None], -1)[:, 0]
        return best - mine, chosen == jnp.argmax(logits, -1)

    gap, agree = jax.lax.map(block, (h.reshape(nb, HEAD_BLOCK, -1),
                                     h_low.reshape(nb, HEAD_BLOCK, -1),
                                     targets.reshape(nb, HEAD_BLOCK)))
    gap, agree = gap.reshape(t), agree.reshape(t)
    return (jnp.where(valid, gap, 0.0).max(),
            jnp.sum(valid & agree), jnp.sum(valid))


def gap_fn(conf: dict, *, control: bool = False):
    """Jitted ``(params, tokens, targets, valid) -> (max_gap, agree, n)``
    for one sequence padded to a fixed length (a multiple of 512)."""
    return jax.jit(functools.partial(_gaps, m=dims(conf), control=control))


# -- work from shapes -----------------------------------------------------------


def layer_matmul_params(m: Dims) -> int:
    return sum(a * b for a, b in _layer_shapes(m).values())


def param_count(conf: dict) -> int:
    """Every parameter held: layers (matrices and two norms), embedding,
    head and final norm."""
    m = dims(conf)
    return (m.layers * (layer_matmul_params(m) + 2 * m.d)
            + 2 * m.vocab * m.d + m.d)


def kv_bytes_per_token(conf: dict) -> int:
    """bf16 K and V of one token over all layers."""
    m = dims(conf)
    return m.layers * 2 * m.kv_heads * m.head_dim * 2


def _weight_bytes(m: Dims, head: bool) -> int:
    layers = m.layers * (layer_matmul_params(m) + 2 * m.d) * 2
    return layers + (m.d * m.vocab + m.d) * 2 * head


def decode_work(conf: dict, contexts) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one decode step for the live rows, where row
    ``i`` attends to ``contexts[i]`` tokens, its own new one included:
    every weight read once, the K/V of earlier tokens read, the new K/V
    written, one embedding row read and one row of logits per live row."""
    m = dims(conf)
    n, ctx = len(contexts), sum(contexts)
    attn = 4 * m.heads * m.head_dim * m.layers
    flops = n * 2 * (m.layers * layer_matmul_params(m) + m.d * m.vocab) \
        + attn * ctx
    kv = kv_bytes_per_token(conf)
    nbytes = _weight_bytes(m, head=True) + n * m.d * 2 + (ctx - n) * kv \
        + n * kv
    return float(flops), float(nbytes)


def chunk_work(conf: dict, start: int, n: int,
               completes: bool) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one prefill chunk of ``n`` prompt tokens at
    positions ``start ..``: causal attention over the prefix, the prefix's
    K/V read and the chunk's written, and the head only for the prompt's
    last position, in the chunk that completes it."""
    m = dims(conf)
    attn = 4 * m.heads * m.head_dim * m.layers
    ctx = n * start + n * (n + 1) // 2
    flops = n * 2 * m.layers * layer_matmul_params(m) + attn * ctx \
        + completes * 2 * m.d * m.vocab
    kv = kv_bytes_per_token(conf)
    nbytes = _weight_bytes(m, head=completes) + n * m.d * 2 + start * kv \
        + n * kv
    return float(flops), float(nbytes)
