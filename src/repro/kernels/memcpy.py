"""Streaming-copy throughput kernel (paper §5.1 / Fig 12, adapted).

The paper sweeps (#CTAs, CTA size, ILP) for a plain global-memory copy and
explains saturation with Little's law.  The TPU analogue sweeps

  grid size      ≈ #CTAs          (number of sequential/parallel programs)
  block_rows     ≈ CTA size       (rows of (8,128)-tiles per program)
  cols/128       ≈ ILP            (independent lanes-vectors per row)

Each grid step copies one (block_rows, cols) tile HBM→VMEM→HBM through the
automatic Pallas pipeline (double-buffered DMA — the in-flight bytes that
Little's law says must cover latency × bandwidth).
``core.littles_law.tpu_min_block_bytes`` picks the smallest block that
saturates; the benchmark sweeps around it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _memcpy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def memcpy(x: jax.Array, *, block_rows: int = 256,
           interpret: bool | None = None) -> jax.Array:
    """Copy a (rows, cols) array through VMEM in (block_rows, cols) tiles."""
    rows, cols = x.shape
    if rows % block_rows:
        raise ValueError(f"rows={rows} not divisible by block_rows={block_rows}")
    return pl.pallas_call(
        _memcpy_kernel,
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, cols), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=resolve_interpret(interpret),
    )(x)
