"""VMEM-tiled flash attention (the model hot-spot, memory-model-tuned).

This is the paper's optimization story applied to the framework's dominant
compute: attention is memory-bound at long context unless the S×S score
matrix never leaves VMEM.  The kernel streams (block_q × d) query tiles
against (block_k × d) key/value tiles with the classic online-softmax
recurrence, so HBM traffic drops from O(S²) to O(S·d) — block sizes are
chosen by ``core.autotune`` from the calibrated memory model
(``tpu_min_block_bytes`` / VMEM capacity), not hand-guessed.

Grid: (batch·heads, q_blocks, kv_blocks), kv innermost ("arbitrary"
semantics — the accumulator scratch carries across kv steps).  GQA is
handled in the BlockSpec index maps (q head → kv head), so no KV
replication is materialized.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

_NEG_BIG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, block_q: int, block_k: int):
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_BIG)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Causal block skip: compute only if some (row, col) with col <= row.
    run = (j * block_k <= i * block_q + block_q - 1) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0].astype(jnp.float32)            # (bk, d)
        v = v_ref[0].astype(jnp.float32)            # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = rows >= cols
            s = jnp.where(mask, s, _NEG_BIG)
        m_prev = m_ref[...]                          # (bq, 1)
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if causal:
            p = jnp.where(mask, p, 0.0)              # kill all-masked rows
        l_ref[...] = alpha * l_prev + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "num_q_heads",
                     "num_kv_heads", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    num_q_heads: int, num_kv_heads: int,
                    causal: bool = True, scale: float | None = None,
                    block_q: int = 256, block_k: int = 256,
                    interpret: bool | None = None) -> jax.Array:
    """q: (B·H, S, D); k/v: (B·Hkv, S, D) — GQA folded into the lead axis."""
    bh, sq, d = q.shape
    bhkv, sk, _ = k.shape
    batch = bh // num_q_heads
    assert bhkv == batch * num_kv_heads
    group = num_q_heads // num_kv_heads
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(f"seq ({sq},{sk}) not divisible by blocks "
                         f"({block_q},{block_k})")
    scale = float(scale if scale is not None else d ** -0.5)

    def kv_row(bh_idx):
        b, h = bh_idx // num_q_heads, bh_idx % num_q_heads
        return b * num_kv_heads + h // group

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k)
    return pl.pallas_call(
        kernel,
        grid=(bh, sq // block_q, sk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (kv_row(b), j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (kv_row(b), j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(q, k, v)
