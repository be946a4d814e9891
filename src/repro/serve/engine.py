"""Continuous-batching serving engines: dense slots and paged KV cache.

:class:`ServeEngine` is the original vLLM-style *dense-slot* engine: a
fixed pool of ``max_slots`` cache slots, each reserving ``max_len`` worth
of HBM; requests are admitted into free slots (whole-prompt prefill at
batch 1), every engine tick runs ONE batched decode step for all active
slots at their own positions.  It stays as the differential ORACLE for
the paged engine — token-for-token greedy equality is a tier-1 test.

:class:`PagedServeEngine` replaces the dense block with the paged cache
from ``repro.serve.paging``: attention K/V live in fixed-size pages handed
out on demand, prompts are admitted in page-sized *chunks* interleaved
with decode ticks (no more batch-1 monopoly ticks), admission is gated by
free-page count, and HBM held per request tracks the tokens it has
actually produced to within one page.  Page length is derived from the
paper's laws (Little's law + bank-conflict row model) by
``paging.choose_page_len``, not hard-coded.

Shared design notes
* inactive slots decode garbage that is masked out by the per-slot valid
  mask; their tokens are pinned to 0 — wasted flops are bounded by
  (free/active) ratio, the standard continuous-batching trade.  In the
  paged engine their page-table rows point at the reserved scratch page,
  so garbage writes cannot touch live pages;
* greedy sampling (argmax) keeps the engines deterministic for tests; a
  temperature hook is provided;
* when the free list runs dry mid-decode the paged engine preempts the
  youngest request (pages freed copy-free, request re-queued for a full
  deterministic re-run), so the oldest request always makes progress.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.parallel import sharding
from repro.serve import paging, spans
from repro.serve.paging import OutOfPages, PageAllocator

#: rule overrides for a serving mesh: ONLY the paged pool shards (KV
#: heads on "model"; pages replicated unless a caller overrides
#: "cache_pages" to "data").  Every activation rule is neutralized so
#: all compute runs on width-invariant replicated operands — mesh
#: sharding here buys pool HBM capacity and per-shard gather bandwidth
#: while token streams stay bit-identical across mesh widths (the
#: oracle chain the sharded tests pin).
MESH_SERVE_RULES: dict = {k: None for k in sharding.DEFAULT_RULES}
MESH_SERVE_RULES["cache_kv_heads"] = "model"

#: cache-leaf names that live in the shared (num_pages, page_len, ...)
#: pool; everything else (SSM conv/state) is slot-resident.  The KV
#: handoff (export_pages/import_pages) repacks paged leaves token-major
#: so source and destination may disagree on page_len.
_PAGED_LEAVES = frozenset({"k", "v", "c_kv", "k_rope"})


def _leaf_name(path) -> str:
    entry = path[-1]
    return entry.key if hasattr(entry, "key") else str(entry)


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray                 # (prompt_len,) int32
    max_new_tokens: int
    generated: list[int] = dataclasses.field(default_factory=list)
    slot: int | None = None
    prefill_pos: int = 0               # chunked prefill progress (paged)
    admit_seq: int = -1                # admission order (preemption victim)

    @property
    def done(self) -> bool:
        return len(self.generated) >= self.max_new_tokens


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_slots: int,
                 max_len: int,
                 sampler: Callable[[jax.Array], jax.Array] | None = None):
        if cfg.is_encoder:
            raise ValueError("encoder-only model has no decode path")
        self.cfg = cfg
        self.params = params
        self.max_slots = max_slots
        self.max_len = max_len
        self.cache = T.init_cache(cfg, max_slots, max_len)
        self.free: deque[int] = deque(range(max_slots))
        self.active: dict[int, Request] = {}       # slot -> request
        self.waiting: deque[Request] = deque()
        self.finished: list[Request] = []
        # per-slot position of the NEXT token to be written
        self.positions = np.zeros(max_slots, dtype=np.int32)
        self.last_tokens = np.zeros(max_slots, dtype=np.int32)
        self.sampler = sampler or (lambda logits: jnp.argmax(logits, -1))
        self.steps = 0
        self.decoded_tokens = 0

        self._prefill = jax.jit(
            lambda p, toks: T.prefill(p, cfg, {"tokens": toks},
                                      max_len=max_len))
        self._decode = jax.jit(
            lambda p, c, t, idx: T.decode(p, cfg, c, t, idx),
            donate_argnums=1)

    # -- queue management ---------------------------------------------------

    def submit(self, req: Request) -> None:
        if len(req.prompt) + req.max_new_tokens > self.max_len:
            raise ValueError("request exceeds max_len")
        self.waiting.append(req)

    def _admit(self) -> None:
        while self.waiting and self.free:
            req = self.waiting.popleft()
            slot = self.free.popleft()
            req.slot = slot
            logits, pcache = self._prefill(
                self.params, jnp.asarray(req.prompt[None, :], jnp.int32))
            # scatter the prefilled slot into the batched cache (axis 1 is
            # the slot/batch axis for every cache leaf)
            self.cache = jax.tree.map(
                lambda c, p: c.at[:, slot].set(p[:, 0].astype(c.dtype)),
                self.cache, pcache)
            tok = int(np.asarray(self.sampler(logits[0, -1])))
            req.generated.append(tok)
            self.last_tokens[slot] = tok
            self.positions[slot] = len(req.prompt)
            self.active[slot] = req
            self._maybe_finish(slot)

    def _maybe_finish(self, slot: int) -> None:
        req = self.active.get(slot)
        if req is not None and req.done:
            del self.active[slot]
            self.free.append(slot)
            self.finished.append(req)

    # -- the engine tick ------------------------------------------------------

    def step(self) -> int:
        """Admit + one batched decode step.  Returns #active slots."""
        self._admit()
        if not self.active:
            return 0
        toks = jnp.asarray(self.last_tokens[:, None], jnp.int32)
        idx = jnp.asarray(self.positions, jnp.int32)
        logits, self.cache = self._decode(self.params, self.cache, toks, idx)
        sampled = np.asarray(self.sampler(logits[:, 0]))
        for slot, req in list(self.active.items()):
            tok = int(sampled[slot])
            req.generated.append(tok)
            self.last_tokens[slot] = tok
            self.positions[slot] += 1
            self.decoded_tokens += 1
            self._maybe_finish(slot)
        self.steps += 1
        return len(self.active)

    def run_to_completion(self, max_steps: int = 10_000) -> list[Request]:
        while (self.waiting or self.active) and self.steps < max_steps:
            self.step()
            if not self.active and self.waiting:
                # all slots drained but work remains: admit next tick
                continue
        return sorted(self.finished, key=lambda r: r.uid)

    def stats(self) -> dict:
        return {"steps": self.steps, "decoded_tokens": self.decoded_tokens,
                "finished": len(self.finished),
                "avg_batch_occupancy":
                    self.decoded_tokens / max(1, self.steps) / self.max_slots}

    def hbm_reserved_bytes(self) -> int:
        """Attention-cache HBM the dense engine reserves, occupancy-blind."""
        return (self.max_slots * self.max_len
                * paging.kv_bytes_per_token(self.cfg))


# ---------------------------------------------------------------------------
# paged engine
# ---------------------------------------------------------------------------


class PagedServeEngine:
    """Continuous batching over a paged KV cache (see module docstring).

    ``page_len`` defaults to ``paging.choose_page_len`` — sized by the
    repo's own cost model, not a magic number.  ``num_pages`` defaults to
    dense-equivalent capacity (every slot can reach ``max_len``); size it
    by the real workload to realize the HBM savings.  ``prefill_chunk``
    (a multiple of ``page_len``; default one page) bounds how much of a
    tick a long prompt can monopolize — and also bounds per-request page
    slack, so keep it one page where admission latency doesn't matter.
    """

    def __init__(self, cfg: ModelConfig, params, *, max_slots: int,
                 max_len: int, page_len: int | None = None,
                 num_pages: int | None = None,
                 prefill_chunk: int | None = None,
                 sampler: Callable[[jax.Array], jax.Array] | None = None,
                 spec=None, mesh=None, shard_rules: dict | None = None,
                 hold_after_prefill: bool = False):
        if cfg.is_encoder:
            raise ValueError("encoder-only model has no decode path")
        self.cfg = cfg
        self.params = params
        self.max_slots = max_slots
        self.max_len = max_len
        # `mesh` shards the paged pool leaves across devices (heads on
        # "model" via MESH_SERVE_RULES + shard_rules overrides); the
        # allocator and page tables below stay host-side and unchanged
        self.mesh = mesh
        if mesh is not None:
            rules = dict(MESH_SERVE_RULES)
            rules.update(shard_rules or {})
            self._shard_ctx = sharding.ShardingCtx(mesh, rules)
            # every device of the mesh holds the whole model: replicate the
            # weights once here (a no-op when they already are), or each
            # step would copy them onto the mesh again
            self.params = jax.device_put(
                params, NamedSharding(mesh, PartitionSpec()))
        else:
            self._shard_ctx = None
        self.shards = paging.gather_shards(cfg, self._shard_ctx)
        # `spec` may be a dissected DeviceProfile (launcher --profile) —
        # page sizing then follows measured parameters, not constants;
        # under a mesh the gather term prices each shard's OWN partition
        # bandwidth against its 1/shards-thin rows
        self.page_len = page_len or paging.choose_page_len(
            cfg, spec=spec, expected_tokens=max_len, shards=self.shards)
        self.prefill_chunk = prefill_chunk or self.page_len
        if self.prefill_chunk % self.page_len:
            raise ValueError(
                f"prefill_chunk {self.prefill_chunk} must be a multiple of "
                f"page_len {self.page_len}")
        # page-table rows must cover the CHUNK-PADDED prefill frontier: a
        # prompt of max_len-1 tokens pads its last chunk past max_len when
        # prefill_chunk does not divide max_len
        frontier = -(-max_len // self.prefill_chunk) * self.prefill_chunk
        self.pages_per_seq = -(-frontier // self.page_len)
        if num_pages is None:
            num_pages = max_slots * self.pages_per_seq + paging.SCRATCH_PAGES
        self.alloc = PageAllocator(num_pages, self.page_len)
        self.cache = T.init_paged_cache(cfg, num_pages, self.page_len,
                                        max_slots, mesh=self._shard_ctx)
        self.page_tables = np.zeros((max_slots, self.pages_per_seq),
                                    dtype=np.int32)
        self.free_slots: deque[int] = deque(range(max_slots))
        self.waiting: deque[Request] = deque()
        self.prefilling: deque[Request] = deque()
        self.active: dict[int, Request] = {}       # slot -> decoding request
        # hold_after_prefill parks a request here the tick its prefill
        # completes instead of decoding it — the prefill-specialist mode
        # of a tiered fleet: the fleet drains `ready` through
        # export_pages into a decode replica.  Off (the default) the
        # deque stays empty and nothing changes.
        self.hold_after_prefill = hold_after_prefill
        self.ready: deque[Request] = deque()
        self.finished: list[Request] = []
        self.cancelled: list[Request] = []
        self.positions = np.zeros(max_slots, dtype=np.int32)
        self.last_tokens = np.zeros(max_slots, dtype=np.int32)
        self.sampler = sampler or (lambda logits: jnp.argmax(logits, -1))
        self.steps = 0
        self.decoded_tokens = 0
        self.preemptions = 0
        self.peak_pages = 0
        self.max_slack_tokens = 0
        self.exports = 0               # KV handoffs out (tiered fleet)
        self.imports = 0               # KV handoffs in
        # decode attention's pages: those the decoding rows' lengths cover
        # (what the paged kernel reads) and those the page tables span
        self.kv_pages_read = 0
        self.kv_pages_tabled = 0
        self._admit_counter = 0

        # the ctx must be ACTIVE at trace time (layers' paged scatter /
        # gather pick their shard_map path off it); a None ctx is pinned
        # too, so an ambient test ctx can never leak into engine traces
        ctx = self._shard_ctx

        def chunk_fn(p, c, t, st, tab, sl, sq):
            with sharding.use(ctx):
                return T.paged_step(p, cfg, c, t, st, tab, sl, sq)

        def decode_fn(p, c, t, st, tab, sl):
            with sharding.use(ctx):
                return T.paged_step(p, cfg, c, t, st, tab, sl, None)

        jit_kw: dict = {"donate_argnums": 1}
        if ctx is not None:
            # pin out shardings: logits replicated, new cache EXACTLY the
            # input cache's layout — donation then aliases every pool
            # shard in place (copy-free update, asserted by the donation
            # regression test)
            jit_kw["out_shardings"] = (
                NamedSharding(ctx.mesh, PartitionSpec()),
                T.paged_cache_shardings(self.cache, ctx))
        self._chunk_step = jax.jit(chunk_fn, **jit_kw)
        self._decode_step = jax.jit(decode_fn, **jit_kw)

    # -- bookkeeping --------------------------------------------------------

    def _worst_case_pages(self, req: Request) -> int:
        """Pages a request can ever hold: the chunk-padded prefill frontier
        or the fully-decoded length, whichever is larger."""
        plen = len(req.prompt)
        pad_end = -(-plen // self.prefill_chunk) * self.prefill_chunk
        return self.alloc.pages_for(max(pad_end, plen + req.max_new_tokens))

    def submit(self, req: Request) -> None:
        plen = len(req.prompt)
        if plen + req.max_new_tokens > self.max_len:
            raise ValueError("request exceeds max_len")
        if self._worst_case_pages(req) > self.alloc.capacity:
            raise ValueError(
                f"request {req.uid} can need {self._worst_case_pages(req)} "
                f"pages; pool only has {self.alloc.capacity}")
        self.waiting.append(req)

    def _sync_table(self, req: Request) -> None:
        row = self.page_tables[req.slot]
        row[:] = 0
        pages = self.alloc.pages.get(req.uid, ())
        row[:len(pages)] = pages

    def _live(self) -> list[Request]:
        return (list(self.prefilling) + list(self.ready)
                + list(self.active.values()))

    def _drop_live(self, req: Request) -> None:
        """Remove ``req`` from whichever live structure holds it."""
        if req.slot in self.active and self.active[req.slot] is req:
            del self.active[req.slot]
        elif req in self.ready:
            self.ready.remove(req)
        else:
            self.prefilling.remove(req)

    def _preempt(self, victim: Request) -> None:
        """Copy-free rollback: pages to the free list, request re-queued
        for a full (deterministic, greedy) re-run."""
        self.alloc.release(victim.uid)
        self.page_tables[victim.slot][:] = 0
        self.free_slots.append(victim.slot)
        self._drop_live(victim)
        victim.slot = None
        victim.generated = []
        victim.prefill_pos = 0
        self.waiting.appendleft(victim)
        self.preemptions += 1

    def _ensure_pages(self, req: Request, tokens: int) -> bool:
        """Grow ``req`` to cover ``tokens``, preempting the youngest
        STRICTLY-YOUNGER request while the free list is short.  Seniority
        (``admit_seq``) is assigned once and survives preemption, so a
        request can never evict anything admitted before it — the oldest
        live request is never a victim and always makes progress (no
        livelock, no starvation under a continuous arrival stream)."""
        while True:
            try:
                if self.alloc.ensure(req.uid, tokens):
                    self._sync_table(req)
                    self.peak_pages = max(self.peak_pages,
                                          self.alloc.allocated_pages)
                return True
            except OutOfPages:
                victims = [r for r in self._live()
                           if r is not req and r.admit_seq > req.admit_seq]
                if not victims:
                    return False
                self._preempt(max(victims, key=lambda r: r.admit_seq))

    # -- admission surface (shared with the fleet router) -------------------

    def servable(self, req: Request) -> bool:
        """Can this engine EVER run ``req`` (geometry, not current load)?"""
        return (len(req.prompt) + req.max_new_tokens <= self.max_len
                and self._worst_case_pages(req) <= self.alloc.capacity)

    def can_accept(self, req: Request) -> bool:
        """Would ``req`` be admitted next tick, counting work already
        queued in ``waiting``?  This is the SAME predicate ``_admit``
        applies (free slot + a first chunk's worth of free pages), with
        queued-but-unadmitted requests charged against the slot headroom —
        the fleet router must not over-dispatch onto a replica whose
        slots are already spoken for."""
        return (self.servable(req)
                and len(self.free_slots) > len(self.waiting)
                and self.alloc.free_pages
                >= self.alloc.pages_for(self.prefill_chunk))

    @property
    def saturated(self) -> bool:
        """No slot or page headroom for even a minimal new request — the
        condition the fleet front end surfaces as backpressure."""
        return (len(self.free_slots) <= len(self.waiting)
                or self.alloc.free_pages
                < self.alloc.pages_for(self.prefill_chunk))

    def live_count(self) -> int:
        return len(self.prefilling) + len(self.ready) + len(self.active)

    def live_committed_tokens(self) -> int:
        """Σ (prompt + max_new) over live requests: the sequence lengths
        this engine is committed to serving.  Deterministic and monotone
        within a request's lifetime, which is what admission pricing
        wants (per-tick positions would make route scores depend on
        phase, not load)."""
        return sum(len(r.prompt) + r.max_new_tokens for r in self._live())

    # -- scheduling ---------------------------------------------------------

    def _admit(self) -> None:
        """Admission gated by FREE PAGES (first chunk's worth), not by a
        whole max_len-sized slot."""
        while (self.waiting and self.free_slots
               and self.alloc.free_pages
               >= self.alloc.pages_for(self.prefill_chunk)):
            req = self.waiting.popleft()
            with TraceAnnotation(spans.ADMITTED, uid=req.uid):
                req.slot = self.free_slots.popleft()
                if req.admit_seq < 0:  # preempted requests keep seniority
                    req.admit_seq = self._admit_counter
                    self._admit_counter += 1
                req.prefill_pos = 0
                req.generated = []
                self.page_tables[req.slot][:] = 0
                self.positions[req.slot] = 0
                self.last_tokens[req.slot] = 0
                self.prefilling.append(req)

    def _prefill_tick(self) -> None:
        """One page-sized chunk of the oldest prefilling request."""
        req = self.prefilling[0]
        plen = len(req.prompt)
        with TraceAnnotation(spans.PREFILL):
            start = req.prefill_pos
            # the chunk's padded tail writes garbage up to the chunk
            # boundary, so pages must cover it (chunk = 1 page by default
            # -> <=1 page of slack, reclaimed as decode writes fill the
            # tail back in)
            if not self._ensure_pages(req, start + self.prefill_chunk):
                return                  # stall; decode ticks will free pages
            s_real = min(self.prefill_chunk, plen - start)
            toks = np.zeros(self.prefill_chunk, dtype=np.int32)
            toks[:s_real] = req.prompt[start:start + s_real]
            logits, self.cache = self._chunk_step(
                self.params, self.cache, jnp.asarray(toks[None]),
                jnp.asarray([start], jnp.int32),
                jnp.asarray(self.page_tables[req.slot][None]),
                jnp.asarray([req.slot], jnp.int32),
                jnp.asarray([s_real], jnp.int32))
            req.prefill_pos += s_real
        if req.prefill_pos < plen:
            return
        with TraceAnnotation(spans.SYNC):
            tok = int(np.asarray(self.sampler(logits[0, s_real - 1])))
        with TraceAnnotation(spans.COMMIT):
            req.generated.append(tok)
            self.last_tokens[req.slot] = tok
            self.positions[req.slot] = plen
            self.prefilling.popleft()
            if self.hold_after_prefill and not req.done:
                # prefill-specialist mode: park for the fleet's handoff
                # instead of decoding here (a done-after-prefill request
                # has nothing to hand off and retires below as usual)
                self.ready.append(req)
            else:
                self.active[req.slot] = req
                self._maybe_finish(req.slot)

    def _maybe_finish(self, slot: int) -> None:
        req = self.active.get(slot)
        if req is not None and req.done:
            del self.active[slot]
            self.alloc.release(req.uid)
            self.page_tables[slot][:] = 0
            self.free_slots.append(slot)
            self.finished.append(req)

    def _decode_tick(self) -> np.ndarray | None:
        """Dispatch one batched decode step and return every row's sampled
        token, or None when no request decodes."""
        with TraceAnnotation(spans.DECODE):
            # grow every decoding request to cover its next write position;
            # a request that cannot get a page even after preempting
            # younger work rolls itself back
            for slot in sorted(self.active):
                req = self.active.get(slot)
                if req is None:
                    continue           # preempted by an earlier slot's grow
                if not self._ensure_pages(req,
                                          int(self.positions[slot]) + 1):
                    self._preempt(req)
            if not self.active:
                return None
            # batch rows without a DECODING request (free slots, but also
            # slots still mid-prefill) are retargeted at the scratch page /
            # scratch slot row at position 0 so their garbage writes cannot
            # corrupt live state, and their attention reads one page
            mask = np.zeros(self.max_slots, dtype=bool)
            mask[list(self.active)] = True
            tables = np.where(mask[:, None], self.page_tables, 0)
            slot_ids = np.where(mask, np.arange(self.max_slots),
                                self.max_slots)
            positions = np.where(mask, self.positions, 0)
            self.kv_pages_read += int(
                (positions[mask] // self.page_len + 1).sum())
            self.kv_pages_tabled += tables.size
            toks = jnp.asarray(self.last_tokens[:, None], jnp.int32)
            logits, self.cache = self._decode_step(
                self.params, self.cache, toks,
                jnp.asarray(positions, jnp.int32),
                jnp.asarray(tables, jnp.int32),
                jnp.asarray(slot_ids, jnp.int32))
        with TraceAnnotation(spans.SYNC):
            return np.asarray(self.sampler(logits[:, 0]))

    def _commit(self, sampled: np.ndarray) -> None:
        for slot, req in list(self.active.items()):
            tok = int(sampled[slot])
            req.generated.append(tok)
            self.last_tokens[slot] = tok
            self.positions[slot] += 1
            self.decoded_tokens += 1
            self._maybe_finish(slot)

    def step(self) -> int:
        """Admit + at most one prefill chunk + one batched decode step.
        Returns the number of live (prefilling or decoding) requests."""
        with TraceAnnotation(spans.ADMIT):
            self._admit()
        if self.prefilling:
            self._prefill_tick()
        sampled = self._decode_tick()
        with TraceAnnotation(spans.COMMIT):
            if sampled is not None:
                self._commit(sampled)
            self.steps += 1
            self._record_slack()
        return len(self.active) + len(self.prefilling) + len(self.ready)

    def cancel(self, uid: int) -> bool:
        """Abort a request wherever it is; frees its pages copy-free."""
        for q in (self.waiting, self.prefilling, self.ready):
            for r in q:
                if r.uid == uid:
                    q.remove(r)
                    if r.slot is not None:
                        self.alloc.release(uid)
                        self.page_tables[r.slot][:] = 0
                        self.free_slots.append(r.slot)
                        r.slot = None
                    self.cancelled.append(r)
                    return True
        for slot, r in list(self.active.items()):
            if r.uid == uid:
                del self.active[slot]
                self.alloc.release(uid)
                self.page_tables[slot][:] = 0
                self.free_slots.append(slot)
                r.slot = None
                self.cancelled.append(r)
                return True
        return False

    def run_to_completion(self, max_steps: int = 10_000) -> list[Request]:
        while (self.waiting or self.prefilling or self.ready or self.active) \
                and self.steps < max_steps:
            self.step()
        return sorted(self.finished, key=lambda r: r.uid)

    # -- failover surface (consumed by the fleet's chaos tier) --------------

    def evacuate(self) -> list[Request]:
        """Roll back every LIVE request copy-free (pages to the free
        list, generation reset for a deterministic greedy re-run) and
        re-queue the rollbacks at the FRONT of ``waiting`` in admission
        order.  Seniority (``admit_seq``) survives, exactly as under
        preemption — greedy re-runs regenerate identical token prefixes,
        which is what lets streams ride out a replica death or
        quarantine byte-stably.  Returns the rolled-back requests,
        oldest first."""
        victims = sorted(self._live(), key=lambda r: r.admit_seq,
                         reverse=True)
        for req in victims:            # youngest first + appendleft ==
            self.alloc.release(req.uid)  # oldest ends at the queue head
            self.page_tables[req.slot][:] = 0
            self.free_slots.append(req.slot)
            self._drop_live(req)
            req.slot = None
            req.generated = []
            req.prefill_pos = 0
            self.waiting.appendleft(req)
        return victims[::-1]

    def reset_paging(self) -> None:
        """Discard ALL paging bookkeeping: fresh allocator, zeroed page
        tables and positions.  Only sound when no request is live (call
        :meth:`evacuate` first) — this is the quarantine heal, run after
        detected page-table corruption so the replica readmits with
        books that are clean by construction.  Page *contents* are left
        alone: every rolled-back request re-prefills from position 0, so
        stale K/V is always overwritten before it is read."""
        assert not self.active and not self.prefilling and not self.ready, \
            "reset_paging with live requests — evacuate first"
        self.alloc = PageAllocator(self.alloc.num_pages, self.page_len)
        self.page_tables[:] = 0
        self.positions[:] = 0
        self.last_tokens[:] = 0
        self.free_slots = deque(range(self.max_slots))

    # -- KV handoff surface (consumed by the fleet's tiered router) ---------

    def can_import(self, tokens: int) -> bool:
        """Could a handed-off request carrying ``tokens`` of KV land here
        next tick?  Same shape as :meth:`can_accept` — a free slot beyond
        what ``waiting`` has spoken for, plus pages for the WHOLE stored
        prefix (an import is not chunked: the pages arrive together)."""
        return (len(self.free_slots) > len(self.waiting)
                and self.alloc.free_pages
                >= self.alloc.pages_for(max(1, tokens)))

    def export_pages(self, uid: int) -> tuple[Request, dict]:
        """Extract a READY request (prefill complete, held for handoff)
        and its KV as a token-major host payload; the source side is
        copy-free exactly like :meth:`evacuate` — pages go straight back
        to the free list, the slot is freed, and the allocator's books
        are re-checked before returning.  The payload repacks paged
        leaves as ``(units, tokens, ...)`` so a destination with a
        different ``page_len`` can take it; slot-resident (SSM) leaves
        ride along as their single row."""
        req = next((r for r in self.ready if r.uid == uid), None)
        assert req is not None, f"uid {uid} is not ready for export"
        slot = req.slot
        tokens = int(self.positions[slot])
        pages = np.asarray(self.alloc.pages.get(uid, ()), dtype=np.int32)

        def one(path, leaf):
            if _leaf_name(path) in _PAGED_LEAVES:
                rows = np.asarray(leaf[:, pages])  # (units, n, page_len, ..)
                flat = rows.reshape(
                    (rows.shape[0], len(pages) * self.page_len)
                    + rows.shape[3:])
                return flat[:, :tokens].copy()
            return np.asarray(leaf[:, slot]).copy()

        payload = {
            "tokens": tokens,
            "pages": len(pages),
            "page_len": self.page_len,
            "last_token": int(self.last_tokens[slot]),
            "leaves": jax.tree_util.tree_map_with_path(one, self.cache),
        }
        self.alloc.release(uid)
        self.page_tables[slot][:] = 0
        self.free_slots.append(slot)
        self.ready.remove(req)
        req.slot = None
        self.exports += 1
        self.alloc.check_invariants()
        return req, payload

    def import_pages(self, req: Request, payload: dict) -> bool:
        """Land a handed-off request: allocate pages for its stored
        prefix, scatter the payload into this pool's geometry, and put
        it straight into decode.  Seniority is engine-local, so the
        arrival enters this engine's admission order at the back (the
        same rule migration uses).  Returns False — leaving the engine
        untouched — when capacity evaporated since the routing decision;
        the fleet then rolls the request back instead."""
        tokens = payload["tokens"]
        if not self.can_import(tokens):
            return False
        slot = self.free_slots.popleft()
        req.slot = slot
        req.admit_seq = self._admit_counter
        self._admit_counter += 1
        ok = self.alloc.ensure(req.uid, max(1, tokens))
        assert ok, "can_import promised pages the allocator refused"
        self._sync_table(req)
        self.peak_pages = max(self.peak_pages, self.alloc.allocated_pages)
        pages = np.asarray(self.alloc.pages[req.uid], dtype=np.int32)

        def one(path, leaf, row):
            if _leaf_name(path) in _PAGED_LEAVES:
                buf = np.zeros(
                    (row.shape[0], len(pages) * self.page_len)
                    + row.shape[2:], dtype=row.dtype)
                buf[:, :tokens] = row
                buf = buf.reshape(
                    (row.shape[0], len(pages), self.page_len) + row.shape[2:])
                return leaf.at[:, pages].set(jnp.asarray(buf, leaf.dtype))
            return leaf.at[:, slot].set(jnp.asarray(row, leaf.dtype))

        self.cache = jax.tree_util.tree_map_with_path(
            one, self.cache, payload["leaves"])
        self.positions[slot] = tokens
        self.last_tokens[slot] = payload["last_token"]
        req.prefill_pos = tokens
        self.active[slot] = req
        self.imports += 1
        self.alloc.check_invariants()
        return True

    def check_invariants(self) -> None:
        """Allocator invariants plus engine<->allocator cross-consistency
        (page tables mirror the allocator's page lists, pages cover every
        stored token, nothing dead holds pages).  Cheap enough for every
        tick — the soak tests and the fleet's corruption detection both
        call it."""
        self.alloc.check_invariants()
        live = {r.uid: r for r in self._live()}
        # every allocated page belongs to a LIVE request (a just-admitted
        # request may hold zero pages while it waits for its first chunk)
        assert set(self.alloc.pages) <= set(live), \
            (f"pages held by non-live uids "
             f"{sorted(set(self.alloc.pages) - set(live))}")
        for uid, req in live.items():
            pages = self.alloc.pages.get(uid, [])
            row = self.page_tables[req.slot]
            assert list(row[:len(pages)]) == pages, \
                f"uid {uid}: page table row diverges from allocator"
            assert not row[len(pages):].any(), \
                f"uid {uid}: page table row has a nonzero tail"
            assert len(pages) * self.page_len >= self._tokens_stored(req), \
                f"uid {uid}: pages do not cover stored tokens"
        for r in list(self.waiting) + self.finished + self.cancelled:
            assert r.uid not in self.alloc.pages or r.uid in live, \
                f"non-live uid {r.uid} still owns pages"

    def integrity_violations(self) -> list[str]:
        """Non-raising :meth:`check_invariants` — the detection hook the
        fleet polls under fault injection to decide quarantine."""
        try:
            self.check_invariants()
        except AssertionError as e:
            return [str(e) or "engine invariant violated"]
        return []

    # -- accounting ---------------------------------------------------------

    def _tokens_stored(self, req: Request) -> int:
        if req.slot is None:
            return 0
        if req.slot in self.active and self.active[req.slot] is req:
            return int(self.positions[req.slot])
        return req.prefill_pos

    def _record_slack(self) -> None:
        for req in self._live():
            held = len(self.alloc.pages.get(req.uid, ())) * self.page_len
            slack = held - self._tokens_stored(req)
            self.max_slack_tokens = max(self.max_slack_tokens, slack)

    def hbm_reserved_bytes(self) -> int:
        """Attention-cache HBM held RIGHT NOW for live requests (pages in
        circulation), the number that scales with actual output length."""
        return (self.alloc.allocated_pages * self.page_len
                * paging.kv_bytes_per_token(self.cfg))

    def page_table_bytes(self) -> int:
        return self.page_tables.nbytes

    def stats(self) -> dict:
        return {"steps": self.steps, "decoded_tokens": self.decoded_tokens,
                "finished": len(self.finished),
                "cancelled": len(self.cancelled),
                "preemptions": self.preemptions,
                "exports": self.exports,
                "imports": self.imports,
                "page_len": self.page_len,
                "gather_shards": self.shards,
                "num_pages": self.alloc.num_pages,
                "peak_pages": self.peak_pages,
                "max_slack_tokens": self.max_slack_tokens,
                "kv_pages_read": self.kv_pages_read,
                "kv_pages_tabled": self.kv_pages_tabled,
                "avg_batch_occupancy":
                    self.decoded_tokens / max(1, self.steps) / self.max_slots}
