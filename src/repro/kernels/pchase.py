"""Fine-grained P-chase as a Pallas TPU kernel (paper Listing 3, adapted).

Faithful structure: ``j = A[j]`` in a serial loop, with the visited index
recorded per iteration (the paper's ``s_index[]`` in shared memory → our
SMEM trace buffer).  The chase array lives in HBM (``memory_space=ANY``)
as rows of ``LANES`` int32; every dereference issues one DMA of the
aligned row holding its target into an SMEM scratch line — deliberately
uncached, exactly the transaction the paper measures.  A row is the
smallest HBM→SMEM transfer Mosaic lowers, and SMEM is where a scalar
index has to land for the next address computation.

TPU adaptation (DESIGN.md §2/§4): Pallas-TPU exposes no in-kernel cycle
counter, so per-access *latency* comes from host-side differential timing
(the chase is serially dependent ⇒ wall-time slope over iteration count =
per-access latency); the per-access *index* trace from this kernel is
bit-exact and feeds the same ``core.inference`` analyzer as the simulator
backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

#: int32 lanes in one HBM row of the chase array: a dereference moves one
#: such 512-byte row
LANES = 128


def _pchase_kernel(start_ref, a_ref, o_ref, line_ref, sem):
    """One serial chase; o_ref[t] = the t-th visited index."""

    def body(t, j):
        # One row-sized HBM->SMEM DMA per dereference (the paper's single
        # memory transaction): the aligned row that holds index j.
        cp = pltpu.make_async_copy(a_ref.at[pl.ds(j // LANES, 1)],
                                   line_ref, sem)
        cp.start()
        cp.wait()
        nj = line_ref[0, j % LANES]
        o_ref[t] = nj
        return nj

    jax.lax.fori_loop(0, o_ref.shape[0], body, start_ref[0], unroll=False)


@functools.partial(jax.jit, static_argnames=("iterations", "interpret"))
def pchase_trace(array: jax.Array, start: jax.Array | int = 0, *,
                 iterations: int, interpret: bool | None = None
                 ) -> jax.Array:
    """Run the chase; returns the int32 index trace (length `iterations`)."""
    n = array.shape[0]
    rows = -(-n // LANES)
    table = jnp.pad(array.astype(jnp.int32),
                    (0, rows * LANES - n)).reshape(rows, LANES)
    start = jnp.asarray(start, jnp.int32).reshape((1,))
    return pl.pallas_call(
        _pchase_kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # start index (scalar)
            pl.BlockSpec(memory_space=pl.ANY),       # chase array in HBM
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((iterations,), jnp.int32),
        scratch_shapes=[pltpu.SMEM((1, LANES), jnp.int32),
                        pltpu.SemaphoreType.DMA],
        interpret=resolve_interpret(interpret),
    )(start, table)


def uniform_init(num_elems: int, stride_elems: int) -> jax.Array:
    """Paper Listing 1: ``A[i] = (i + s) % N``."""
    i = jnp.arange(num_elems, dtype=jnp.int32)
    return (i + stride_elems) % num_elems


# ---------------------------------------------------------------------------
# TraceBackend adapter: the kernel behind the simulator backends' contract
# ---------------------------------------------------------------------------


def chase_array_from_indices(indices, num_elems: int):
    """Chase array A with ``A[x_t] = x_{t+1}`` for an explicit visit stream.

    Only *functional* streams (each index has a single successor — true for
    every probe ``core.inference`` emits) can run on hardware, since the
    kernel dereferences memory instead of replaying a list; inconsistent
    streams raise ValueError.  The last index wraps to the first so the
    chase is closed.
    """
    import numpy as np
    idx = np.asarray(indices, dtype=np.int64)
    succ: dict[int, int] = {}
    for a, b in zip(idx[:-1], idx[1:]):
        prev = succ.setdefault(int(a), int(b))
        if prev != int(b):
            raise ValueError(
                f"index stream is not a chase: {a} has successors "
                f"{prev} and {int(b)}")
    succ.setdefault(int(idx[-1]), int(idx[0]))
    arr = np.arange(num_elems, dtype=np.int32)   # self-loop for unvisited
    for a, b in succ.items():
        arr[a] = b
    return jnp.asarray(arr)


def pallas_trace_backend(*, interpret: bool | None = None,
                         repeats: int = 2):
    """A :class:`repro.core.pchase.TraceBackend` driving the Pallas kernel.

    The per-access *index* stream comes bit-exact from the kernel; the
    per-access *latency* is the host-side differential-timing slope
    (wall-time difference between a full-length and a half-length chase
    divided by the iteration delta — valid because the chase is serially
    dependent), repeated ``repeats`` times and min-reduced.  The slope is a
    single number, so hardware traces carry one flat latency per access:
    ``tavg`` is meaningful, hit/miss separation needs the simulator
    backends.  Trace contract (``PChaseConfig``/``PChaseTrace``) is
    identical to theirs, so ``core.inference``'s size/line searches and the
    classic methods run unchanged on hardware.
    """
    import time

    import numpy as np

    from repro.core.trace import PChaseConfig, PChaseTrace

    def _timed_chase(arr: jax.Array, start: int, iters: int) -> tuple:
        t0 = time.perf_counter()
        out = pchase_trace(arr, start, iterations=iters,
                           interpret=interpret)
        out.block_until_ready()
        return np.asarray(out), time.perf_counter() - t0

    def run(config: PChaseConfig, indices=None) -> PChaseTrace:
        n = config.num_elems
        if indices is None:
            arr = uniform_init(n, config.stride_elems)
            # chase from the predecessor of 0 so the recorded stream equals
            # uniform_chase_indices: 0, s, 2s, ... (kernel records A[j])
            start = (-config.stride_elems) % n
            k = config.iterations
            rec_full, _ = _timed_chase(arr, start, k)
            rec = rec_full.astype(np.int64)
        else:
            rec = np.asarray(indices, dtype=np.int64)
            arr = chase_array_from_indices(rec, n)
            k = len(rec)
            out, _ = _timed_chase(arr, int(rec[0]), max(1, k - 1))
            got = np.concatenate([[rec[0]], out[:k - 1].astype(np.int64)])
            if not np.array_equal(got, rec):
                raise ValueError("kernel chase diverged from index stream")
        # differential timing: slope between full- and half-length chases,
        # entering the chase where the recorded stream does (index 0 may be
        # a self-loop for explicit streams that never visit it)
        t_start = int(rec[0]) if len(rec) else 0
        half = max(1, k // 2)
        best = float("inf")
        for _ in range(repeats):
            _, t_full = _timed_chase(arr, t_start, k)
            _, t_half = _timed_chase(arr, t_start, half)
            if k > half:
                best = min(best, (t_full - t_half) / (k - half))
        per_access_ns = 0.0 if best == float("inf") else max(0.0, best * 1e9)
        lat = np.full(k, per_access_ns, dtype=np.float64)
        return PChaseTrace(config, rec[:k], lat,
                           meta={"timing": "differential",
                                 "per_access_ns": per_access_ns,
                                 "interpret": resolve_interpret(interpret)})

    return run
