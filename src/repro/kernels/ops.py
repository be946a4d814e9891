"""Public jit'd entry points for the Pallas kernels.

``interpret=None`` (the default) follows the backend through
:func:`repro.kernels.resolve_interpret`: on a TPU every kernel compiles
with Mosaic; on the CPU, where the tests run (``JAX_PLATFORMS=cpu``), the
kernel bodies execute in the Pallas interpreter exactly as written.
``tests/test_tpu_compile.py`` compiles the kernels for a described v5e
chip, so a kernel Mosaic refuses fails there and not only on the chip.
Model code calls ``paged_decode_attention`` on a TPU's one-token paged
decode step; the flash kernel is reached through ``attention(impl="flash")``
and its correctness is pinned by tests.
"""

from __future__ import annotations

import time

import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import memcpy as _mc
from repro.kernels import paged_attention as _pa
from repro.kernels import pchase as _pc
from repro.kernels import ref
from repro.kernels import strided as _st


# -- pointer chase -----------------------------------------------------------


def pchase_trace(array, iterations: int, start: int = 0, *,
                 interpret: bool | None = None):
    return _pc.pchase_trace(jnp.asarray(array, jnp.int32), start,
                            iterations=iterations, interpret=interpret)


def pchase_latency_slope(array, k_small: int, k_large: int, *,
                         repeats: int = 3, interpret: bool | None = None
                         ) -> float:
    """Differential timing (DESIGN.md §4): per-access seconds from the
    wall-time slope between two iteration counts of the same serial chase."""
    times = []
    for k in (k_small, k_large):
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            pchase_trace(array, k, interpret=interpret).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        times.append(best)
    return (times[1] - times[0]) / (k_large - k_small)


# -- streaming copy ----------------------------------------------------------


def memcpy(x, *, block_rows: int = 256, interpret: bool | None = None):
    return _mc.memcpy(x, block_rows=block_rows, interpret=interpret)


def memcpy_throughput_gbps(shape=(4096, 512), *, block_rows: int = 256,
                           dtype=jnp.float32, repeats: int = 5,
                           interpret: bool | None = None) -> float:
    """2 · bytes / wall-time, as the paper computes copy throughput."""
    x = jnp.ones(shape, dtype)
    memcpy(x, block_rows=block_rows, interpret=interpret).block_until_ready()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        memcpy(x, block_rows=block_rows, interpret=interpret).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return 2 * x.size * x.dtype.itemsize / best / 1e9


# -- strided gather ----------------------------------------------------------


def strided_gather(x, stride: int, *, interpret: bool | None = None):
    return _st.strided_gather(x, stride=stride, interpret=interpret)


# -- attention ---------------------------------------------------------------


def flash_attention(q, k, v, *, num_q_heads: int, num_kv_heads: int,
                    causal: bool = True, scale: float | None = None,
                    block_q: int = 256, block_k: int = 256,
                    interpret: bool | None = None):
    return _fa.flash_attention(
        q, k, v, num_q_heads=num_q_heads, num_kv_heads=num_kv_heads,
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret)


def paged_decode_attention(q, k_pages, v_pages, layer, page_table, lengths,
                           *, interpret: bool | None = None):
    """One query token per row over the pages its length covers, read
    from the stacked paged pool (see ``kernels.paged_attention``)."""
    return _pa.paged_decode_attention(q, k_pages, v_pages, layer,
                                      page_table, lengths,
                                      interpret=interpret)


def attention(q, k, v, *, num_q_heads: int, num_kv_heads: int,
              causal: bool = True, scale: float | None = None,
              impl: str = "ref", **kw):
    """Dispatch: 'flash' (Pallas) or 'ref' (pure XLA, dry-run default)."""
    if impl == "flash":
        return flash_attention(q, k, v, num_q_heads=num_q_heads,
                               num_kv_heads=num_kv_heads, causal=causal,
                               scale=scale, **kw)
    return ref.attention_ref(q, k, v, num_q_heads=num_q_heads,
                             num_kv_heads=num_kv_heads, causal=causal,
                             scale=scale)
