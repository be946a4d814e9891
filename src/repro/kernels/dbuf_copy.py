"""Manual multi-buffered HBM→VMEM→HBM copy — Little's law made explicit.

Where ``memcpy.py`` relies on the automatic Pallas pipeline, this kernel
hand-rolls the DMA schedule: ``num_buffers`` VMEM slots, each block's
inbound copy started ``num_buffers-1`` iterations ahead of its use.  The
outstanding-bytes knob IS the paper's in-flight-requests knob (§5.1): with
1 buffer the stream serializes (latency-bound); with ≥2 the inbound DMA
overlaps the outbound and throughput follows
``min(peak, inflight/latency)`` — `core.littles_law.tpu_min_block_bytes`
picks the block size that saturates.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _dbuf_kernel(x_hbm, o_hbm, bufs, in_sems, out_sems, *,
                 block_rows: int, nblocks: int, num_buffers: int):
    def in_copy(i, slot):
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(i * block_rows, block_rows)],
            bufs.at[slot], in_sems.at[slot])

    def out_copy(i, slot):
        return pltpu.make_async_copy(
            bufs.at[slot],
            o_hbm.at[pl.ds(i * block_rows, block_rows)],
            out_sems.at[slot])

    # prologue: fill the pipeline with num_buffers-1 outstanding inbound DMAs
    for k in range(min(num_buffers - 1, nblocks)):
        in_copy(k, k).start()

    def body(i, _):
        slot = jax.lax.rem(i, num_buffers)
        # start the inbound copy that keeps the pipe num_buffers-1 deep
        nxt = i + num_buffers - 1

        @pl.when(nxt < nblocks)
        def _():
            in_copy(nxt, jax.lax.rem(nxt, num_buffers)).start()

        in_copy(i, slot).wait()
        # drain any previous outbound use of this slot before reusing it
        @pl.when(i >= num_buffers)
        def _():
            out_copy(i - num_buffers, slot).wait()
        out_copy(i, slot).start()
        return 0

    jax.lax.fori_loop(0, nblocks, body, 0)
    # epilogue: wait for the trailing outbound copies
    for k in range(min(num_buffers, nblocks)):
        i = nblocks - 1 - k
        out_copy(i, jax.lax.rem(jnp.int32(i), num_buffers)).wait()


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "num_buffers", "interpret"))
def dbuf_copy(x: jax.Array, *, block_rows: int = 256, num_buffers: int = 2,
              interpret: bool | None = None) -> jax.Array:
    """Copy (rows, cols) through `num_buffers` VMEM slots of block_rows."""
    rows, cols = x.shape
    if rows % block_rows:
        raise ValueError(f"rows={rows} % block_rows={block_rows} != 0")
    nblocks = rows // block_rows
    kernel = functools.partial(_dbuf_kernel, block_rows=block_rows,
                               nblocks=nblocks, num_buffers=num_buffers)
    return pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[
            pltpu.VMEM((num_buffers, block_rows, cols), x.dtype),
            pltpu.SemaphoreType.DMA((num_buffers,)),
            pltpu.SemaphoreType.DMA((num_buffers,)),
        ],
        interpret=resolve_interpret(interpret),
    )(x)
