"""The paged decode-attention kernel against the gather path it replaces.

``kernels.paged_attention`` reads only the pages each row's length covers.
Its reference is what the model runs elsewhere: ``_paged_gather`` of every
page of the table, then ``_sdpa`` masked by length.  Every position past a
row's length, and every page the table does not name, holds NaN for the
kernel, whose output must stay finite; and the kernel's page DMAs are
recorded as it runs, to be exactly the live pages.  On the CPU the kernel
runs in the Pallas interpreter.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.kernels import paged_attention as pa
from repro.models import layers as L
from repro.models import transformer as T
from repro.parallel import sharding
from repro.serve.engine import PagedServeEngine, Request

PAGE_LEN = 128
PAGES_PER_ROW = 4
#: one row per case: (length, whether it sits on scratch page 0)
ROWS = [(1, True), (127, False), (128, False), (129, False),
        (PAGES_PER_ROW * PAGE_LEN, False)]


def _case(group, kv_heads=2, dim=128, layers=3, layer=1, seed=0):
    """A stacked bf16 pool, a shuffled page table and the query; returns
    (q, clean pool, NaN-filled pool, table, lengths)."""
    rng = np.random.default_rng(seed)
    lengths = np.array([n for n, _ in ROWS], np.int32)
    need = [0 if scratch else -(-n // PAGE_LEN) for n, scratch in ROWS]
    num_pages = 1 + sum(need) + 3              # scratch, live, unnamed
    phys = iter(rng.permutation(np.arange(1, num_pages)))
    table = np.zeros((len(ROWS), PAGES_PER_ROW), np.int32)
    for b, n in enumerate(need):
        table[b, :n] = [next(phys) for _ in range(n)]
    live = np.zeros((layers, num_pages, PAGE_LEN), bool)
    for b, n in enumerate(lengths):
        pos = np.arange(n)
        live[layer, table[b, pos // PAGE_LEN], pos % PAGE_LEN] = True
    shape = (layers, num_pages, PAGE_LEN, kv_heads, dim)
    k, v = (rng.standard_normal(shape, np.float32) for _ in range(2))
    k_nan, v_nan = (np.where(live[..., None, None], a, np.nan)
                    for a in (k, v))
    q = rng.standard_normal((len(ROWS), kv_heads * group, dim), np.float32)
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)
    return (bf(q), (bf(k), bf(v)), (bf(k_nan), bf(v_nan)),
            jnp.asarray(table), jnp.asarray(lengths))


def _gather_sdpa(q, k_pages, v_pages, layer, table, lengths):
    kg = L._paged_gather_impl(k_pages, layer, table)
    vg = L._paged_gather_impl(v_pages, layer, table)
    cfg = configs.get_smoke_config("granite-8b")
    positions = (lengths - 1)[:, None]
    o = L._sdpa(q[:, None], kg, vg, cfg, causal=False,
                kv_len_mask=L._paged_valid(kg.shape[1], positions))
    return o[:, 0]


class _Recorded:
    """A page DMA that reports (layer, page) to ``fetched`` as it starts."""

    def __init__(self, copy, layer, page, fetched):
        self.copy, self.layer, self.page = copy, layer, page
        self.fetched = fetched

    def start(self):
        jax.debug.callback(
            lambda li, pg: self.fetched.append((int(li), int(pg))),
            self.layer, self.page)
        self.copy.start()

    def wait(self):
        self.copy.wait()


@pytest.mark.parametrize("group", [1, 2, 4, 7])
def test_kernel_reads_only_live_pages_and_matches_the_gather(group,
                                                             monkeypatch):
    """GQA groups 1, 2, 4 and 7; rows of 1, 127, 128, 129 and a whole
    table of tokens, one of them at length 1 on scratch page 0; physical
    pages shuffled; a middle layer of three."""
    q, clean, nan, table, lengths = _case(group)
    layer = jnp.int32(1)
    fetched = []
    copy = pa._page_copy
    monkeypatch.setattr(pa, "_page_copy", lambda pool, li, page, buf, sem:
                        _Recorded(copy(pool, li, page, buf, sem), li, page,
                                  fetched))
    # the kernel traced afresh, so that its DMAs are the recorded ones
    got = pa.paged_decode_attention.__wrapped__(q, *nan, layer, table,
                                                lengths)
    jax.effects_barrier()
    live = [(1, int(table[b, j])) for b, n in enumerate(lengths)
            for j in range(-(-int(n) // PAGE_LEN))]
    assert sorted(fetched) == sorted(live * 2)          # K and V
    want = _gather_sdpa(q, *clean, layer, table, lengths)
    assert got.shape == q.shape and got.dtype == q.dtype
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-2, atol=1e-2)


def _paged_smoke():
    return configs.get_smoke_config("granite-8b")


def test_dispatch_follows_what_the_step_can_observe(monkeypatch):
    """The kernel runs for one-token decode at lane-wide heads on a TPU
    with no sharding context; the gather path everywhere else."""
    decode = jnp.zeros((4, 1, 4, 128), jnp.bfloat16)
    assert not L._paged_decode_kernel_applies(decode)          # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert L._paged_decode_kernel_applies(decode)
    chunk = jnp.zeros((1, PAGE_LEN, 4, 128), jnp.bfloat16)
    assert not L._paged_decode_kernel_applies(chunk)
    narrow = jnp.zeros((4, 1, 4, 16), jnp.bfloat16)
    assert not L._paged_decode_kernel_applies(narrow)
    mesh = jax.make_mesh((1,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    with sharding.use(sharding.ShardingCtx(mesh, {})):
        assert not L._paged_decode_kernel_applies(decode)


def _force_kernel(monkeypatch):
    """Decode through the kernel (interpreted) at the smoke config's
    narrow heads, as the chip would at lane-wide ones."""
    monkeypatch.setattr(
        L, "_paged_decode_kernel_applies",
        lambda q: q.shape[1] == 1 and sharding.current() is None)


def _spy_decode(engine):
    """Record the positions, tables and slot ids of every decode step."""
    calls, step = [], engine._decode_step

    def spy(p, c, t, positions, tables, slots):
        calls.append((np.asarray(positions), np.asarray(tables),
                      np.asarray(slots)))
        return step(p, c, t, positions, tables, slots)

    engine._decode_step = spy
    return calls


def _serve(cfg, params, work, **kw):
    rng = np.random.default_rng(0)
    engine = PagedServeEngine(cfg, params, max_slots=3, max_len=32, **kw)
    for uid, (plen, n_new) in enumerate(work):
        engine.submit(Request(uid, rng.integers(cfg.vocab_size, size=plen)
                              .astype(np.int32), n_new))
    calls = _spy_decode(engine)
    done = engine.run_to_completion()
    return engine, calls, {r.uid: r.generated for r in done}


def test_engine_decodes_the_same_tokens_through_the_kernel(monkeypatch):
    """Admissions, finishes and preemptions (a five-page pool): the kernel
    path serves the gather path's greedy tokens.  Rows without a decoding
    request are sent position 0 over scratch page 0, and
    ``kv_pages_read`` counts the decoding rows' live pages."""
    cfg = _paged_smoke()
    params = T.init_params(cfg, jax.random.key(0))
    work = [(2, 10), (2, 10), (3, 9), (5, 4)]
    kw = dict(page_len=4, num_pages=5)
    _, _, want = _serve(cfg, params, work, **kw)
    _force_kernel(monkeypatch)
    engine, calls, got = _serve(cfg, params, work, **kw)
    assert got == want
    assert engine.preemptions > 0, "the pool was sized to preempt"
    read = idle_rows = 0
    for positions, tables, slots in calls:
        idle = slots == engine.max_slots
        idle_rows += idle.sum()
        assert not positions[idle].any() and not tables[idle].any()
        read += int((positions[~idle] // engine.page_len + 1).sum())
    assert idle_rows
    stats = engine.stats()
    assert stats["kv_pages_read"] == read
    assert stats["kv_pages_tabled"] == (len(calls) * engine.max_slots
                                        * engine.pages_per_seq)


def test_kv_pages_read_counts_each_decode_position():
    """With no preemption, a request of ``plen`` prompt tokens and ``n``
    new ones decodes at positions plen .. plen + n - 2, each reading
    ceil((position + 1) / page_len) pages."""
    cfg = dataclasses.replace(_paged_smoke(), num_layers=1)
    params = T.init_params(cfg, jax.random.key(1))
    work = [(3, 6), (9, 2), (4, 11), (1, 1)]
    engine, calls, _ = _serve(cfg, params, work, page_len=4)
    assert engine.preemptions == 0
    want = sum(p // 4 + 1 for plen, n in work
               for p in range(plen, plen + n - 1))
    assert engine.stats()["kv_pages_read"] == want
    assert engine.stats()["kv_pages_tabled"] == (
        len(calls) * 3 * engine.pages_per_seq)
