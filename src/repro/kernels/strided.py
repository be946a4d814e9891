"""Strided VMEM access kernel — the shared-memory bank-conflict analogue
(paper §6.2 / Listing 4, adapted).

The paper's Listing 4 reads ``sdata[tid * stride]`` across a warp; the
conflict degree (distinct rows per bank) serializes the access.  On TPU the
same physics appears when a VMEM gather makes one *lane* serve many rows:
``out[i, :] = x[(i * stride) % n, :]`` with stride s costs ≈
``tpu_conflict_degree(s)`` sequential row reads in the worst lane
(``core.bankconflict``).  This kernel is the measurable artifact: identical
semantics to the model, validated against ``ref.strided_ref`` and — on real
hardware — timed across strides to reproduce the Table 8 latency-vs-ways
curve for VMEM.

The gather is a serial loop of one-row VMEM copies at a dynamic sublane
offset: Mosaic lowers no in-kernel vector gather.  Dynamic row offsets
need 32-bit rows on the chip (packed dtypes want offsets a multiple of
their packing).
"""

from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _strided_kernel(x_ref, o_ref, *, stride: int):
    n = x_ref.shape[0]

    def row(i, carry):
        src = jax.lax.rem(i * stride, n)
        o_ref[pl.ds(i, 1), :] = x_ref[pl.ds(src, 1), :]
        return carry

    jax.lax.fori_loop(0, n, row, 0)


@functools.partial(jax.jit, static_argnames=("stride", "interpret"))
def strided_gather(x: jax.Array, *, stride: int,
                   interpret: bool | None = None) -> jax.Array:
    """out[i] = x[(i * stride) % n] over the leading axis, in one VMEM block."""
    return pl.pallas_call(
        functools.partial(_strided_kernel, stride=stride),
        in_specs=[pl.BlockSpec(x.shape, lambda: (0,) * x.ndim)],
        out_specs=pl.BlockSpec(x.shape, lambda: (0,) * x.ndim),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=resolve_interpret(interpret),
    )(x)
