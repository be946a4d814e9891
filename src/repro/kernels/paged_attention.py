"""Paged decode attention: one query token per row against its live pages.

The serving engine keeps K and V in a shared pool of fixed-size pages,
stacked over layers: ``(layers, num_pages, page_len, Hkv, D)``.  Row ``b``
of a decode batch owns the physical pages ``table[b, :]`` and attends to
its first ``lengths[b]`` positions.  The kernel reads only the pages those
lengths cover, ``ceil(lengths[b] / page_len)`` of them, straight from the
stacked pool in HBM: no page past a row's length and no page the table
does not name is ever touched, and no gathered copy of the table is made.

One grid step walks every row's live pages in order with manual DMAs,
double-buffered: while page ``i`` is being attended to, page ``i + 1``
(the row's next page, or the next row's first) is already in flight.
Each page arrives whole, all its KV heads at once, and is viewed as
``(page_len * Hkv, D)`` rows (token-major, head-minor).  GQA is a
block-diagonal mask over that view: query head ``r`` (KV head ``r //
group``) scores every row of the page and keeps only the rows of its own
KV head, so one matmul serves every head and any integer group size.
The online softmax carries the running max, sum and output per query
head from page to page.

Precision follows the XLA reference (``models.layers._sdpa``): scores,
softmax and ``p @ V`` in float32 from the stored K/V.  ``p`` is not
rounded before it meets ``V``: it enters one matmul with ``V`` as three
parts in the stored dtype, stacked as rows, whose sum is ``p`` exactly.
Positions past a row's length may hold anything, NaN included; they are
masked out of the scores and zeroed in ``V``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

_NEG_BIG = -1e30


def _page_copy(pool, layer, page, buf, sem):
    """The DMA of one layer's page of a stacked pool into a VMEM buffer."""
    return pltpu.make_async_copy(pool.at[layer, page], buf, sem)


def _kernel(layer_ref, table_ref, lens_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, *, scale: float, group: int,
            pages_per_row: int):
    batch, heads, dim = q_ref.shape
    _, page_len, kv_heads, _ = k_buf.shape
    rows = page_len * kv_heads
    li = layer_ref[0]

    def copies(b, j, slot):
        page = table_ref[b * pages_per_row + j]
        return (_page_copy(k_hbm, li, page, k_buf.at[slot], sems.at[0, slot]),
                _page_copy(v_hbm, li, page, v_buf.at[slot], sems.at[1, slot]))

    def start(b, j, slot):
        for c in copies(b, j, slot):
            c.start()

    # the KV head of each score column and of each query row, and the
    # token of each column and of each row of V within its page
    col = jax.lax.broadcasted_iota(jnp.int32, (heads, rows), 1)
    col_tok = col // kv_heads
    same_head = (col - col_tok * kv_heads) == (
        jax.lax.broadcasted_iota(jnp.int32, (heads, rows), 0) // group)
    v_tok = jax.lax.broadcasted_iota(jnp.int32, (page_len, kv_heads, dim), 0)

    start(0, 0, 0)

    def row(b, slot):
        length = lens_ref[b]
        n = (length + page_len - 1) // page_len
        q = q_ref[b]                                        # (H, D)

        def page(j, carry):
            m, l, acc, slot = carry
            last = j + 1 == n
            nb = jnp.where(last, b + 1, b)
            nj = jnp.where(last, 0, j + 1)

            @pl.when(nb < batch)
            def _():
                start(nb, nj, 1 - slot)

            for c in copies(b, j, slot):
                c.wait()
            k_ref = k_buf.at[slot].reshape(rows, dim)
            v_ref = v_buf.at[slot].reshape(rows, dim)
            live = length - j * page_len        # tokens of this page in use

            # 0 * NaN would reach p @ V: zero V past the length (only the
            # last page of a row has such positions)
            @pl.when(live < page_len)
            def _():
                v_page = v_buf.at[slot]
                v_page[...] = jnp.where(v_tok < live, v_page[...], 0)

            s = jax.lax.dot_general(
                q, k_ref[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # (H, rows)
            s = jnp.where(same_head & (col_tok < live), s, _NEG_BIG)
            m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            # p = hi + mid + lo exactly, each part exact in V's dtype
            hi = p.astype(v_ref.dtype).astype(jnp.float32)
            mid = (p - hi).astype(v_ref.dtype).astype(jnp.float32)
            lo = p - hi - mid
            parts = jnp.concatenate([hi, mid, lo], axis=0).astype(v_ref.dtype)
            pv = jax.lax.dot_general(
                parts, v_ref[...], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)            # (3H, D)
            pv = pv[:heads] + pv[heads:2 * heads] + pv[2 * heads:]
            return (m_new, alpha * l + p.sum(axis=1, keepdims=True),
                    alpha * acc + pv, 1 - slot)

        init = (jnp.full((heads, 1), _NEG_BIG, jnp.float32),
                jnp.zeros((heads, 1), jnp.float32),
                jnp.zeros((heads, dim), jnp.float32), slot)
        _, l, acc, slot = jax.lax.fori_loop(0, n, page, init)
        o_ref[b] = (acc / l).astype(o_ref.dtype)
        return slot

    jax.lax.fori_loop(0, batch, row, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, layer: jax.Array,
                           page_table: jax.Array, lengths: jax.Array, *,
                           interpret: bool | None = None) -> jax.Array:
    """Attention of one query token per row over its live pages.

    q: (B, H, D); k_pages, v_pages: (layers, num_pages, page_len, Hkv, D),
    every layer's pool stacked; layer: () index of the layer read;
    page_table: (B, P) physical page of each logical page; lengths: (B,)
    positions each row attends to, at least 1 and at most ``P *
    page_len``.  H must be a multiple of Hkv.  Returns (B, H, D) in
    ``q.dtype``."""
    batch, heads, dim = q.shape
    _, _, page_len, kv_heads, _ = k_pages.shape
    if heads % kv_heads:
        raise ValueError(f"{heads} query heads over {kv_heads} KV heads")
    pages_per_row = page_table.shape[1]
    kernel = functools.partial(_kernel, scale=float(dim ** -0.5),
                               group=heads // kv_heads,
                               pages_per_row=pages_per_row)
    full = pl.BlockSpec((batch, heads, dim), lambda i, *_: (0, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[full, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=full,
            scratch_shapes=[
                pltpu.VMEM((2, page_len, kv_heads, dim), k_pages.dtype),
                pltpu.VMEM((2, page_len, kv_heads, dim), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=resolve_interpret(interpret),
        name="paged_decode_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      page_table.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      q, k_pages, v_pages)
