"""Disaggregated prefill/decode tiers: the oracle-chain and two-stage
router contracts.

* a single-tier plan (every replica in both tiers) reproduces the
  symmetric fleet token-for-token, tick-for-tick, with a bit-identical
  decision log — extending the oracle chain dense→paged→fleet→tiered;
* a heterogeneous 2-tier fleet streams byte-identical tokens vs the
  symmetric oracle (greedy outputs are schedule-independent);
* handoff-priced routing: prefill placements go to bandwidth-rich
  replicas, the margin audit covers BOTH stages, and a handoff lands in
  the tick that routes it, so the first token streams with the prefill;
* the admission-pricing regression (satellite of this PR): admissions
  are priced with ``prefill_cell_cost``, so a bandwidth-rich replica
  wins a contested prefill-heavy admission that the old live-load
  ``decode_cell_cost`` pricing would have routed away from it.
"""

import jax
import numpy as np
import pytest

from repro.core.devices import TpuSpec
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.profile import published_profile
from repro.serve import tiers as tiering
from repro.serve.engine import Request
from repro.serve.fleet import FleetEngine
from repro.serve.frontend import FleetFrontend
from repro.serve.planner import plan_tiers
from repro.serve.tiers import TierPlan

WORK = [(8, 6), (12, 4), (5, 9), (16, 3), (7, 7), (3, 5)]
MAX_LEN = 32


@pytest.fixture(scope="module")
def micro():
    cfg = ModelConfig(name="micro", family="dense", num_layers=2,
                      d_model=32, d_ff=64, vocab_size=64, num_heads=2,
                      num_kv_heads=2, dtype="float32",
                      param_dtype="float32")
    return cfg, T.init_params(cfg, jax.random.key(0))


def _requests(cfg, work=WORK, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(uid, rng.integers(cfg.vocab_size, size=plen)
                    .astype(np.int32), n_new)
            for uid, (plen, n_new) in enumerate(work)]


def _run(fleet, cfg, work=WORK, seed=0):
    for r in _requests(cfg, work=work, seed=seed):
        fleet.submit(r)
    out = {r.uid: r.generated for r in fleet.run_to_completion()}
    fleet.check_invariants()
    assert fleet.stats()["pages_leaked"] == 0
    return out


class TestTierPlan:
    def test_parse_roundtrip(self):
        plan = tiering.parse_tiers("prefill:0,1/decode:2,3", 4)
        assert plan.prefill == (0, 1) and plan.decode == (2, 3)
        assert plan.tiered
        assert tiering.parse_tiers(plan.describe(), 4) == plan
        # either order, overlap allowed
        plan = tiering.parse_tiers("decode:0,1/prefill:1", 2)
        assert plan.prefill == (1,) and plan.decode == (0, 1)

    @pytest.mark.parametrize("bad", [
        "prefill:0",                     # missing decode
        "prefill:0/decode:",             # empty tier
        "prefill:0/decode:x",            # non-integer
        "prefill:0/prefill:1",           # duplicate tier
        "prefill:0/decode:5",            # out of range (n=2)
        "warmup:0/decode:1",             # unknown tier name
        "prefill:0/decode:0",            # replica 1 orphaned (n=2)
    ])
    def test_parse_rejects(self, bad):
        with pytest.raises(ValueError):
            tiering.parse_tiers(bad, 2)

    def test_symmetric_plan_is_not_tiered(self):
        assert not tiering.symmetric(3).tiered
        assert not TierPlan(prefill=(0, 1), decode=(1, 0)).tiered

    def test_auto_ranks_bandwidth_to_prefill_latency_to_decode(self):
        fat = TpuSpec(name="fat", hbm_bytes_per_s=2e12,
                      hbm_latency_s=2e-6)           # bandwidth-rich
        quick = TpuSpec(name="quick", hbm_bytes_per_s=4e11,
                        hbm_latency_s=2e-7)         # latency-lean
        plan = tiering.auto_tiers([quick, fat])
        assert plan.prefill == (1,) and plan.decode == (0,)
        # deterministic under permutation of the same specs
        plan = tiering.auto_tiers([fat, quick])
        assert plan.prefill == (0,) and plan.decode == (1,)
        # a 1-replica fleet has nothing to specialize
        assert not tiering.auto_tiers([fat]).tiered

    def test_resolve_front_door(self):
        specs = [TpuSpec(name="a"), TpuSpec(name="b")]
        assert not tiering.resolve_tiers(None, 2, specs).tiered
        assert not tiering.resolve_tiers("none", 2, specs).tiered
        assert not tiering.resolve_tiers("symmetric", 2, specs).tiered
        got = tiering.resolve_tiers("prefill:0/decode:1", 2, specs)
        assert got.tiered
        assert tiering.resolve_tiers(got, 2, specs) == got
        with pytest.raises(TypeError):
            tiering.resolve_tiers(3.14, 2, specs)

    def test_handoff_pricing_monotone_and_never_free(self):
        fast = TpuSpec(name="f", hbm_bytes_per_s=1e12, hbm_latency_s=1e-7)
        slow = TpuSpec(name="s", hbm_bytes_per_s=1e11, hbm_latency_s=1e-6)
        # the slower endpoint gates the wire, either direction
        t = tiering.handoff_seconds(1 << 20, fast, slow)
        assert t == tiering.handoff_seconds(1 << 20, slow, fast)
        assert t > tiering.handoff_seconds(1 << 20, fast, fast)
        # whole pages move: bytes scale with the page count
        cfg = ModelConfig(name="m", family="dense", num_layers=2,
                          d_model=32, d_ff=64, vocab_size=64, num_heads=2,
                          num_kv_heads=2)
        assert (tiering.handoff_bytes(cfg, 4, 8)
                == 2 * tiering.handoff_bytes(cfg, 2, 8))
        # quantization never rounds to zero ticks
        assert tiering.handoff_ticks(1e-12, 1.0) == 1
        assert tiering.handoff_ticks(2.5, 1.0) == 3


class TestOracleChain:
    def test_single_tier_equals_symmetric_bit_for_bit(self, micro):
        """Every replica in both tiers ⇒ the two-stage router must
        degenerate to the symmetric fleet exactly: same tokens, same
        tick schedule, same decision log."""
        cfg, params = micro
        sym = FleetEngine(cfg, params, max_slots=2, max_len=MAX_LEN,
                          replicas=2, page_len=4)
        want = _run(sym, cfg)
        single = FleetEngine(cfg, params, max_slots=2, max_len=MAX_LEN,
                             replicas=2, page_len=4,
                             tiers="prefill:0,1/decode:0,1")
        assert not single.tiered
        got = _run(single, cfg)
        assert got == want
        assert single.ticks == sym.ticks
        assert single.decision_log() == sym.decision_log()
        assert single.stats()["handoffs"] == 0

    def test_two_tier_tokens_match_symmetric_oracle(self, micro):
        cfg, params = micro
        sym = FleetEngine(cfg, params, max_slots=3, max_len=MAX_LEN,
                          replicas=2, page_len=4)
        want = _run(sym, cfg)
        tiered = FleetEngine(cfg, params, max_slots=3, max_len=MAX_LEN,
                             replicas=2, page_len=4,
                             tiers="prefill:0/decode:1")
        got = _run(tiered, cfg)
        assert got == want
        s = tiered.stats()
        assert s["handoffs"] == len(WORK)
        # the prefill specialist never decoded a single token
        assert tiered.replicas[0].engine.stats()["decoded_tokens"] == 0
        assert {d.kind for d in tiered.decisions} >= {"admit", "handoff"}

    def test_hetero_two_tier_streams_match_oracle(self, micro):
        """TeslaV100 prefilling for tpu_v5e: the streamed (frontend)
        bytes must equal the symmetric hetero oracle's, request for
        request."""
        cfg, params = micro

        def mk(tiers):
            profs = [published_profile(d)
                     for d in ("TeslaV100", "tpu_v5e")]
            return FleetEngine(cfg, params, max_slots=2, max_len=MAX_LEN,
                               profiles=profs, page_len=4, tiers=tiers)

        want = _run(mk(None), cfg)
        fleet = mk("prefill:0/decode:1")
        front = FleetFrontend(fleet, max_pending=len(WORK))
        streamed: dict[int, list[int]] = {}
        for r in _requests(cfg):
            front.submit(r.prompt, r.max_new_tokens, uid=r.uid,
                         on_token=lambda u, t:
                         streamed.setdefault(u, []).append(t))
        front.run()
        fleet.check_invariants()
        assert streamed == want
        assert fleet.stats()["pages_leaked"] == 0
        assert not fleet.margin_violations()

    def test_two_stage_replay_bit_identical(self, micro):
        cfg, params = micro

        def run():
            fleet = FleetEngine(cfg, params, max_slots=2, max_len=MAX_LEN,
                                replicas=3, page_len=4,
                                tiers="prefill:0,1/decode:2")
            _run(fleet, cfg)
            return fleet

        a, b = run(), run()
        assert a.decision_log() == b.decision_log()
        assert any(d.kind == "handoff" for d in a.decisions)
        sa, sb = a.stats(), b.stats()
        for k in ("ticks", "decisions", "handoffs", "decoded_tokens"):
            assert sa[k] == sb[k], k


class TestHandoffRouting:
    def test_handoff_ticks_land_in_ttft(self, micro):
        """The handoff lands in the TTFT tick: the pages move in the
        fleet tick that routes them, so the first token streams in the
        tick the prefill completes (the symmetric fleet's TTFT), and from
        the end of that tick the uid holds pages on the decode replica
        only, none on the prefill replica."""
        cfg, params = micro
        [req] = _requests(cfg, work=[(8, 4)])

        def serve(tiers):
            fleet = FleetEngine(cfg, params, max_slots=2, max_len=MAX_LEN,
                                replicas=2, page_len=4, tiers=tiers)
            front = FleetFrontend(fleet)
            h = front.submit(req.prompt, req.max_new_tokens, uid=req.uid)
            return fleet, front, h

        base_fleet, base_front, _ = serve(None)
        base_front.run()
        fleet, front, h = serve("prefill:0/decode:1")
        src, dst = (r.engine for r in fleet.replicas)
        while h.request.prefill_pos < len(req.prompt):
            assert not h.tokens
            front.tick()
        assert len(h.tokens) == 1, "first token streams with the prefill"
        assert fleet.stats()["handoffs"] == 1
        while not h.settled:
            assert req.uid not in src.alloc.pages
            assert dst.alloc.pages.get(req.uid)
            front.tick()
        fleet.check_invariants()
        assert h.tokens == base_fleet.finished()[0].generated
        assert front.slo.ttfts() == base_front.slo.ttfts()

    def test_prefill_placement_prefers_bandwidth(self, micro):
        """Stage-1 routing is prefill-priced: the bandwidth-rich
        prefill replica takes the admissions; stage-2 margin holds."""
        cfg, params = micro
        # huge peak FLOPs make the prefill price memory-bound, so the
        # 20x bandwidth gap is the whole story
        fat = TpuSpec(name="fat", hbm_bytes_per_s=8e11,
                      peak_bf16_flops=1e18)
        thin = TpuSpec(name="thin", hbm_bytes_per_s=8e11 / 20,
                       peak_bf16_flops=1e18)
        dec = TpuSpec(name="dec")
        fleet = FleetEngine(cfg, params, max_slots=2, max_len=MAX_LEN,
                            profiles=[thin, fat, dec], page_len=4,
                            tiers="prefill:0,1/decode:2")
        _run(fleet, cfg)
        admits = [d for d in fleet.decisions if d.kind == "admit"]
        # whenever BOTH prefill replicas can accept, bandwidth wins;
        # single-candidate decisions are overflow, not preference
        contested = [d for d in admits if len(d.scores) > 1]
        assert contested and all(d.chosen == 1 for d in contested), \
            "bandwidth-rich prefill replica must win contested admissions"
        assert not fleet.margin_violations(), \
            "margin audit covers both routing stages"

    def test_handoff_prices_the_transfer(self, micro):
        """Stage-2 scores carry the KV-transfer term: every handoff
        decision's chosen score includes a positive handoff_s computed
        from min-endpoint bandwidth."""
        cfg, params = micro
        fleet = FleetEngine(cfg, params, max_slots=2, max_len=MAX_LEN,
                            replicas=2, page_len=4,
                            tiers="prefill:0/decode:1")
        _run(fleet, cfg)
        handoffs = [d for d in fleet.decisions if d.kind == "handoff"]
        assert handoffs
        for d in handoffs:
            by_rep = {s.replica: s for s in d.scores}
            chosen = by_rep[d.chosen]
            assert chosen.handoff_s > 0
            assert chosen.step_cost_s > chosen.handoff_s


class TestAdmissionPricingRegression:
    def test_bandwidth_rich_wins_contested_prefill_heavy_admission(
            self, micro):
        """Admissions are priced with ``prefill_cell_cost`` (this PR's
        fix): a bandwidth-rich-but-busy replica wins a prefill-heavy
        admission that the old live-load ``decode_cell_cost`` pricing —
        still used for stage-2 handoffs and recomputed here — would
        have priced OUT of the margin."""
        cfg, params = micro
        # memory-bound pricing (huge peak FLOPs): the 1.5x bandwidth
        # edge decides prefill, while live load decides decode
        fast = TpuSpec(name="fast", hbm_bytes_per_s=8e11,
                       peak_bf16_flops=1e18)
        slow = TpuSpec(name="slow", hbm_bytes_per_s=8e11 / 1.5,
                       peak_bf16_flops=1e18)
        fleet = FleetEngine(cfg, params, max_slots=8, max_len=64,
                            profiles=[fast, slow], page_len=4)
        # contested: the fast replica is already busy (externally placed
        # work, as after a failover) with long decode commitments that
        # swell its live-load decode price; the slow one is idle
        for r in _requests(cfg, work=[(4, 44)] * 7, seed=1):
            r.uid += 100
            fleet.replicas[0].engine.submit(r)
        req = _requests(cfg, work=[(16, 2)], seed=2)[0]
        req.uid = 99
        fleet.submit(req)

        # old pricing, recomputed: decode_cell_cost at live load (the
        # formula the "handoff" stage still uses)
        old = {r.index: r.score(req, kind="handoff").step_cost_s
               for r in fleet.replicas}
        assert old[0] > old[1] * (1 + fleet.margin), \
            "under decode pricing the busy fast replica is out of margin"

        fleet.step()
        d = fleet.decisions[0]
        assert d.kind == "admit" and d.chosen == 0, \
            "prefill pricing must route the prompt to the fast replica"
        new = {s.replica: s.step_cost_s for s in d.scores}
        assert new[1] > new[0] * (1 + fleet.margin)
        fleet.run_to_completion()
        fleet.check_invariants()


class TestTieredPlanner:
    def test_plan_tiers_answers_per_tier(self, micro):
        cfg, _ = micro
        tp = plan_tiers(cfg, ["GTX980", "TeslaV100", "tpu_v5e"],
                        arrival_per_tick=0.2, mean_prompt=12,
                        mean_new=8, max_slots=4, max_len=64)
        assert tp.prefill.tier == "prefill"
        assert tp.decode.tier == "decode"
        assert tp.prefill.replicas >= 1 and tp.decode.replicas >= 1
        assert tp.handoff_ticks >= 1
        assert tp.predicted_ttft_ticks > tp.handoff_ticks
        assert len(tp.ranked_prefill) == 3 == len(tp.ranked_decode)
        # ranked best-first: the winner leads its list
        assert tp.ranked_prefill[0] == tp.prefill
        assert tp.ranked_decode[0] == tp.decode
        assert any("handoff" in ln for ln in tp.lines())

    def test_plan_tiers_deterministic(self, micro):
        cfg, _ = micro
        kw = dict(arrival_per_tick=0.4, mean_prompt=10, mean_new=6,
                  max_slots=3, max_len=48)
        a = plan_tiers(cfg, ["TeslaV100", "tpu_v5e"], **kw)
        b = plan_tiers(cfg, ["TeslaV100", "tpu_v5e"], **kw)
        assert a == b
