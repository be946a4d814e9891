"""Serving launcher: batched prefill+decode loop, the continuous-
batching engines, or the multi-replica fleet over a synthetic workload.

  # fixed-batch loop (the original launcher)
  python -m repro.launch.serve --arch granite-8b --smoke --batch 4 \
      --prompt-len 64 --gen 32

  # continuous batching, paged KV cache (page_len derived from the cost
  # model when --page-len is omitted; --num-pages sizes the HBM pool)
  python -m repro.launch.serve --arch granite-8b --smoke --engine paged \
      --requests 16 --slots 4 --max-len 96 [--page-len 8] [--num-pages 32] \
      [--prefill-chunk 16]

  # dense-slot oracle engine on the same workload (for A/B)
  python -m repro.launch.serve --arch granite-8b --smoke --engine dense \
      --requests 16 --slots 4 --max-len 96

  # profile-aware fleet: N paged replicas behind the cost-model router,
  # streamed through the deterministic front end.  --fleet-profiles
  # binds each replica to its own device profile (artifact path, device
  # name under experiments/profiles/, or a registered device's published
  # profile) — heterogeneous fleets are the point
  python -m repro.launch.serve --arch granite-8b --smoke --engine fleet \
      --replicas 2 --fleet-profiles tpu_v5e,TeslaV100 \
      --requests 16 --slots 4 --max-len 96

  # always-measure fleet: blind-dissect the named device at startup
  # (batched jax engine, sub-second per GPU) and bind each replica to
  # the fresh in-memory profile through the resolve_spec() seam
  python -m repro.launch.serve --arch granite-8b --smoke --engine fleet \
      --dissect-on-start GTX980 --requests 8 --slots 4 --max-len 96

  # chaos tier: seeded fault campaign against the fleet (replica death,
  # page-table corruption, latency spikes), run TWICE and verified to
  # replay bit-identically — exits 1 on any replay divergence, leaked
  # page, or unclassified request
  python -m repro.launch.serve --arch granite-8b --smoke --engine fleet \
      --replicas 2 --requests 12 --faults 1 [--fault-rate 0.05]

  # realistic traffic: drive the fleet with a seeded workload trace
  # (chat / rag / agent / batch scenarios, poisson / bursty / diurnal
  # arrivals) and report TTFT/TPOT percentiles from the SLO tracker;
  # --workload-replay runs the trace twice and exits 1 on divergence
  python -m repro.launch.serve --arch granite-8b --smoke --engine fleet \
      --replicas 2 --workload chat --arrival bursty --rate 0.5 \
      --horizon 48 [--workload-replay]

  # capacity planner: how many replicas of which profile for this
  # traffic at this SLO — Little's law + queueing, no simulation
  python -m repro.launch.serve --arch granite-8b --smoke --plan \
      --workload rag --rate 0.8 --slo-ttft 24 \
      --fleet-profiles tpu_v5e,TeslaV100

  # disaggregated tiers: prefill specialists hand finished prompts to
  # decode specialists over a priced KV handoff; 'auto' ranks replicas
  # by measured profile (bandwidth-rich -> prefill, low-latency ->
  # decode); an explicit plan pins indices per tier
  python -m repro.launch.serve --arch granite-8b --smoke --engine fleet \
      --replicas 2 --fleet-tiers auto --requests 16 --slots 4 --max-len 96
  python -m repro.launch.serve --arch granite-8b --smoke --engine fleet \
      --fleet-profiles tpu_v5e,TeslaV100 \
      --fleet-tiers prefill:0/decode:1 --requests 16

  # any of the above under the JAX profiler: device ops beside the
  # serving loop's serve.* host spans, in DIR/plugins/profile/<run>/
  python -m repro.launch.serve --arch granite-8b --smoke --engine fleet \
      --requests 8 --trace-dir /tmp/serve-trace
"""

from __future__ import annotations

import argparse
import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs, jaxcache
from repro.models import transformer as T
from repro.train.loop import make_serve_step


def _batch_loop(cfg, params, args):
    max_len = args.prompt_len + args.gen
    prompts = jax.random.randint(jax.random.key(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    t0 = time.time()
    logits, cache = jax.jit(
        lambda p, b: T.prefill(p, cfg, b, max_len=max_len))(
        params, {"tokens": prompts})
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    serve_step = jax.jit(make_serve_step(cfg), donate_argnums=1)
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    for i in range(args.gen - 1):
        logits, cache = serve_step(params, cache, tok,
                                   jnp.int32(args.prompt_len + i))
        tok = jnp.argmax(logits[:, 0], axis=-1)[:, None].astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0
    gen = jnp.concatenate(out, axis=1)
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"gen={args.gen}")
    print(f"prefill: {t_prefill*1e3:.1f} ms "
          f"({args.batch*args.prompt_len/t_prefill:,.0f} tok/s)")
    print(f"decode:  {t_decode*1e3:.1f} ms "
          f"({args.batch*(args.gen-1)/max(t_decode,1e-9):,.0f} tok/s)")
    print("sample tokens:", gen[0, :16].tolist())


def _parse_mesh(args):
    """``--mesh-shape`` -> a serving mesh (or None): '4' or '2,4'."""
    if not args.mesh_shape:
        return None
    from repro.launch.mesh import make_serve_mesh
    shape = tuple(int(s) for s in str(args.mesh_shape).split(",") if s)
    mesh = make_serve_mesh(shape)
    print(f"serve mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"({mesh.devices.size} devices, "
          f"{mesh.devices.flat[0].platform} backend)")
    return mesh


def _workload(cfg, args):
    from repro.serve.engine import Request
    rng = np.random.default_rng(args.seed)
    reqs = []
    for uid in range(args.requests):
        plen = int(rng.integers(4, max(5, args.max_len // 3)))
        n_new = int(rng.integers(4, max(5, args.max_len // 3)))
        reqs.append(Request(uid, rng.integers(cfg.vocab_size, size=plen)
                            .astype(np.int32), n_new))
    return reqs


def _engine_run(cfg, params, args):
    from repro.serve import paging
    from repro.serve.engine import PagedServeEngine, ServeEngine
    mesh = _parse_mesh(args)
    if args.engine == "paged":
        eng = PagedServeEngine(cfg, params, max_slots=args.slots,
                               max_len=args.max_len, page_len=args.page_len,
                               num_pages=args.num_pages,
                               prefill_chunk=args.prefill_chunk,
                               mesh=mesh)
        print(f"page_len={eng.page_len} "
              f"({'given' if args.page_len else 'cost-model derived'}), "
              f"pool={eng.alloc.num_pages} pages"
              + (f", gather shards={eng.shards}" if mesh is not None else ""))
        for t in paging.page_len_rationale(cfg, expected_tokens=args.max_len,
                                           shards=eng.shards):
            marker = " <-- chosen" if t.page_len == eng.page_len else ""
            print(f"  candidate {t.page_len:4d}: score={t.score:.4f} "
                  f"gather={t.gather_frac:.3f} frag={t.frag_frac:.3f} "
                  f"conflict_degree={t.conflict_degree}{marker}")
    else:
        eng = ServeEngine(cfg, params, max_slots=args.slots,
                          max_len=args.max_len)
    reqs = _workload(cfg, args)
    for r in reqs:
        eng.submit(r)
    t0 = time.time()
    finished = eng.run_to_completion()
    dt = time.time() - t0
    s = eng.stats()
    toks = sum(len(r.generated) for r in finished)
    print(f"arch={cfg.name} engine={args.engine} requests={len(finished)} "
          f"slots={args.slots} max_len={args.max_len}")
    print(f"generated {toks} tokens in {s['steps']} ticks, {dt*1e3:.1f} ms "
          f"({toks/max(dt,1e-9):,.0f} tok/s wall)")
    print(f"occupancy={s['avg_batch_occupancy']:.2f}")
    if args.engine == "paged":
        print(f"peak pages={s['peak_pages']} "
              f"(dense would reserve {args.slots * args.max_len} tokens; "
              f"peak paged ~= {s['peak_pages'] * eng.page_len}), "
              f"preemptions={s['preemptions']}, "
              f"max slack={s['max_slack_tokens']} tok "
              f"(<= 1 page of {eng.page_len})")
    if finished:
        print("sample tokens:", finished[0].generated[:16])


def _resolve_fleet_profiles(args):
    """Fleet replica profile entries from the CLI.

    ``--fleet-profiles`` passes names/paths through for
    ``resolve_fleet_profile``.  ``--dissect-on-start`` instead runs the
    blind dissection pipeline against the named device(s) right now —
    the batched engine makes this a startup cost of well under a second
    per GPU — and binds replicas to the fresh in-memory DeviceProfile
    objects through the same ``resolve_spec()`` seam, so a fleet can
    always-measure whatever hardware shows up rather than trust a
    committed artifact.
    """
    if args.dissect_on_start:
        if args.fleet_profiles:
            raise SystemExit(
                "--dissect-on-start and --fleet-profiles are mutually "
                "exclusive: the first measures the profile the second "
                "would name")
        from repro.profile.pipeline import dissect_device
        profiles = []
        for dev in args.dissect_on_start.split(","):
            t0 = time.time()
            prof = dissect_device(dev.strip(), seed=args.seed)
            dt = time.time() - t0
            measured = sum(1 for c in prof.caches.values()
                           if c.provenance == "measured")
            print(f"dissect-on-start: {prof.device} engine={prof.engine} "
                  f"{measured} structures measured in {dt:.2f}s wall "
                  f"(stage total {prof.timings.get('total', 0.0):.2f}s)")
            profiles.append(prof)
        return profiles
    return args.fleet_profiles.split(",") if args.fleet_profiles else None


def _fleet_run(cfg, params, args):
    from repro.serve.fleet import FleetEngine
    from repro.serve.frontend import FleetFrontend
    profiles = _resolve_fleet_profiles(args)
    # pass --replicas through verbatim: FleetEngine validates a
    # replicas/profiles mismatch, which must reach the CLI user
    fleet = FleetEngine(cfg, params, max_slots=args.slots,
                        max_len=args.max_len,
                        replicas=args.replicas,
                        profiles=profiles,
                        page_len=args.page_len, num_pages=args.num_pages,
                        prefill_chunk=args.prefill_chunk,
                        margin=args.router_margin,
                        mesh=_parse_mesh(args),
                        tiers=args.fleet_tiers)
    if fleet.tiered:
        print(f"tiers: {fleet.tier_plan.describe()}"
              + (" (auto: profile-ranked)"
                 if args.fleet_tiers == "auto" else ""))
    for r in fleet.replicas:
        shard = (f" gather_shards={r.engine.shards}"
                 if r.mesh is not None else "")
        print(f"replica {r.name}: tier={r.tier} "
              f"page_len={r.engine.page_len} "
              f"pool={r.engine.alloc.num_pages} pages,{shard} "
              f"inflight_bound={r.inflight_bound} "
              f"(spec: {r.spec.hbm_bytes_per_s/1e9:.0f} GB/s HBM, "
              f"{r.spec.peak_bf16_flops/1e12:.1f} TFLOP/s)")
    front = FleetFrontend(fleet)
    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    for uid in range(args.requests):
        plen = int(rng.integers(4, max(5, args.max_len // 3)))
        n_new = int(rng.integers(4, max(5, args.max_len // 3)))
        prompt = rng.integers(cfg.vocab_size, size=plen).astype(np.int32)
        # tokens accumulate on the StreamHandle; no callback needed here
        front.submit_blocking(prompt, n_new, uid=uid)
    handles = front.run()
    dt = time.time() - t0
    fleet.check_invariants()
    s = fleet.stats()
    toks = sum(len(h.tokens) for h in handles)
    print(f"arch={cfg.name} engine=fleet replicas={len(fleet.replicas)} "
          f"requests={s['finished']} slots={args.slots}/replica "
          f"max_len={args.max_len}")
    print(f"streamed {toks} tokens in {s['ticks']} fleet ticks, "
          f"{dt*1e3:.1f} ms ({toks/max(dt,1e-9):,.0f} tok/s wall)")
    print(f"router: {s['decisions']} decisions, "
          f"{s['migrations']} migrations, {s['preemptions']} preemptions, "
          f"margin violations={len(fleet.margin_violations())}")
    if fleet.tiered:
        print(f"handoffs: {s['handoffs']} completed, "
              f"{s['handoff_aborts']} aborted, "
              f"{s['in_transit']} in transit at drain")
    print(f"pages: peak={s['peak_pages']} leaked={s['pages_leaked']} "
          f"max slack={s['max_slack_tokens']} tok")
    for p in s["per_replica"]:
        print(f"  {p['replica']}: finished={p['finished']} "
              f"steps={p['steps']} peak_pages={p['peak_pages']} "
              f"preemptions={p['preemptions']}")
    if handles:
        print("sample stream:", handles[0].tokens[:16])


def _mk_trace(cfg, args):
    from repro.serve.workload import WorkloadSpec, generate_trace
    spec = WorkloadSpec(scenario=args.workload, arrival=args.arrival,
                        rate=args.rate, horizon=args.horizon,
                        seed=args.seed, max_len=args.max_len,
                        vocab_size=cfg.vocab_size)
    trace = generate_trace(spec)
    st = trace.stats()
    print(f"workload: {spec.scenario}/{spec.arrival} seed={spec.seed} -> "
          f"{st['requests']} requests / {st['sessions']} sessions over "
          f"{st['span_ticks']} ticks (lambda={st['arrival_per_tick']:.3f}, "
          f"mean prompt={st['mean_prompt']:.1f}, "
          f"mean new={st['mean_new']:.1f})")
    return trace


def _plan(cfg, args):
    """``--plan``: the capacity planner — pure accounting, no params,
    no simulation.  Ranks every candidate profile."""
    from repro.serve.planner import SLOTarget, rank_profiles
    trace = _mk_trace(cfg, args)
    st = trace.stats()
    if not st["requests"]:
        raise SystemExit("empty trace: raise --rate or --horizon")
    profiles = (args.fleet_profiles.split(",") if args.fleet_profiles
                else [args.profile])
    plans = rank_profiles(
        cfg, profiles, arrival_per_tick=st["arrival_per_tick"],
        mean_prompt=st["mean_prompt"], mean_new=st["mean_new"],
        max_slots=args.slots, max_len=args.max_len,
        slo=SLOTarget(ttft_p99_ticks=args.slo_ttft),
        page_len=args.page_len, num_pages=args.num_pages,
        prefill_chunk=args.prefill_chunk)
    for i, plan in enumerate(plans):
        tag = "best" if i == 0 else f"option {i + 1}"
        print(f"-- {tag}: {plan.replica.spec_name} --")
        for ln in plan.lines():
            print(f"  {ln}")
    if args.fleet_tiers is not None:
        from repro.serve.planner import plan_tiers
        tiered = plan_tiers(
            cfg, profiles, arrival_per_tick=st["arrival_per_tick"],
            mean_prompt=st["mean_prompt"], mean_new=st["mean_new"],
            max_slots=args.slots, max_len=args.max_len,
            slo=SLOTarget(ttft_p99_ticks=args.slo_ttft),
            page_len=args.page_len, num_pages=args.num_pages,
            prefill_chunk=args.prefill_chunk)
        print("-- disaggregated (per-tier) --")
        for ln in tiered.lines():
            print(f"  {ln}")
    return plans


def _workload_run(cfg, params, args):
    """``--workload SCENARIO``: replay a seeded trace through the fleet
    front end, report the SLO tracker's percentiles, and hold the
    planner's residence prediction up against the measurement.  With
    ``--workload-replay`` the whole thing runs twice on fresh fleets and
    exits 1 on ANY divergence (trace bytes, SLO report, decision log) —
    the workload analogue of the chaos tier's replay contract."""
    from repro.serve.fleet import FleetEngine, resolve_fleet_profile
    from repro.serve.frontend import FleetFrontend
    from repro.serve.planner import SLOTarget, plan_for_trace
    from repro.serve.workload import replay_trace

    profiles = _resolve_fleet_profiles(args)
    mesh = _parse_mesh(args)
    trace = _mk_trace(cfg, args)

    def run_once():
        fleet = FleetEngine(cfg, params, max_slots=args.slots,
                            max_len=args.max_len, replicas=args.replicas,
                            profiles=profiles, page_len=args.page_len,
                            num_pages=args.num_pages,
                            prefill_chunk=args.prefill_chunk,
                            margin=args.router_margin, mesh=mesh,
                            tiers=args.fleet_tiers)
        front = FleetFrontend(fleet)
        replay_trace(front, trace)
        fleet.check_invariants()
        return front

    t0 = time.time()
    front = run_once()
    dt = time.time() - t0
    rep = front.slo.report()
    s = front.fleet.stats()
    print(f"arch={cfg.name} engine=fleet replicas={len(front.fleet.replicas)}"
          f" slots={args.slots}/replica max_len={args.max_len} "
          f"({dt * 1e3:.0f} ms wall)")
    for ln in rep.lines():
        print(ln)
    print(f"router: {s['decisions']} decisions, {s['migrations']} "
          f"migrations, {s['preemptions']} preemptions; pages: "
          f"peak={s['peak_pages']} leaked={s['pages_leaked']}")
    if front.fleet.tiered:
        print(f"tiers: {s['tiers']} -> {s['handoffs']} handoffs, "
              f"{s['handoff_aborts']} aborted")
    plan = plan_for_trace(
        cfg, trace, spec=resolve_fleet_profile(profiles[0] if profiles
                                               else args.profile),
        max_slots=args.slots, max_len=args.max_len,
        slo=SLOTarget(ttft_p99_ticks=args.slo_ttft),
        page_len=args.page_len, num_pages=args.num_pages,
        prefill_chunk=args.prefill_chunk)
    for ln in plan.lines():
        print(f"plan| {ln}")
    print(f"plan| predicted W={plan.predicted_residence_ticks:.1f} vs "
          f"measured mean residence={rep.mean_residence_ticks:.1f} ticks")

    if not args.workload_replay:
        return
    front2 = run_once()
    failures = []
    from repro.serve.workload import generate_trace
    if generate_trace(trace.spec).fingerprint() != trace.fingerprint():
        failures.append("trace generation diverged for the same spec")
    if front2.slo.report().key() != rep.key():
        failures.append("SLO report diverged between identical runs")
    if front2.fleet.decision_log() != front.fleet.decision_log():
        failures.append("decision log diverged between identical runs")
    if s["pages_leaked"]:
        failures.append(f"{s['pages_leaked']} pages leaked")
    if failures:
        for f in failures:
            print(f"FAIL: {f}")
        raise SystemExit(1)
    print("workload replay verified: bit-identical trace, SLO report and "
          "decision log across both runs")


def _fault_campaign(cfg, params, args):
    """``--faults SEED``: run the seeded campaign twice on identical
    fleets and hold the chaos tier to its replay contract."""
    from repro.serve.faults import FaultInjector, run_campaign
    from repro.serve.fleet import FleetEngine

    profiles = _resolve_fleet_profiles(args)

    mesh = _parse_mesh(args)

    def mk_fleet():
        return FleetEngine(cfg, params, max_slots=args.slots,
                           max_len=args.max_len, replicas=args.replicas,
                           profiles=profiles, page_len=args.page_len,
                           num_pages=args.num_pages,
                           prefill_chunk=args.prefill_chunk,
                           margin=args.router_margin, mesh=mesh,
                           tiers=args.fleet_tiers)

    def mk_work():
        rng = np.random.default_rng(args.seed)
        work = []
        for _ in range(args.requests):
            plen = int(rng.integers(4, max(5, args.max_len // 3)))
            n_new = int(rng.integers(4, max(5, args.max_len // 3)))
            work.append((rng.integers(cfg.vocab_size, size=plen)
                         .astype(np.int32), n_new))
        return work

    t0 = time.time()
    reports = [run_campaign(mk_fleet(), mk_work(),
                            FaultInjector.campaign(args.faults,
                                                   rate=args.fault_rate))
               for _ in range(2)]
    dt = time.time() - t0
    r = reports[0]
    print(f"arch={cfg.name} engine=fleet campaign seed={args.faults} "
          f"rate={args.fault_rate} requests={args.requests} "
          f"({dt*1e3:.0f} ms for both runs)")
    print(f"fault events: {r.event_counts or '(none fired)'}")
    print(f"outcomes: {r.outcome_counts()}")
    print(f"deaths={r.stats['deaths']} quarantines={r.stats['quarantines']} "
          f"readmits={r.stats['readmits']} degrades={r.stats['degrades']} "
          f"lost={r.stats['lost']}")
    print(f"pages leaked={r.stats['pages_leaked']} "
          f"log entries={len(r.log)}")
    failures = []
    if reports[0].log != reports[1].log:
        failures.append("decision log diverged between identical runs")
    if reports[0].outcomes != reports[1].outcomes:
        failures.append("outcome classification diverged")
    if reports[0].streams != reports[1].streams:
        failures.append("token streams diverged")
    if r.stats["pages_leaked"]:
        failures.append(f"{r.stats['pages_leaked']} pages leaked")
    if len(r.outcomes) != args.requests:
        failures.append(f"{args.requests - len(r.outcomes)} requests "
                        "left unclassified")
    if failures:
        for f in failures:
            print(f"FAIL: {f}")
        raise SystemExit(1)
    print("campaign replay verified: bit-identical log, outcomes and "
          "streams across both runs")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.serve",
        description="serving launcher: fixed-batch loop, dense/paged "
                    "continuous-batching engines, or the multi-replica "
                    "fleet with the profile-aware router")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--engine", choices=("loop", "dense", "paged", "fleet"),
                    default="loop",
                    help="loop: fixed-batch prefill+decode; dense/paged: "
                         "continuous-batching engines on a mixed workload; "
                         "fleet: N paged replicas behind the profile-aware "
                         "router with the streaming front end")
    # fixed-batch loop knobs
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    # engine knobs
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--page-len", type=int, default=None,
                    help="KV page length; omit to derive it from the cost "
                         "model (littles_law + bankconflict)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="page-pool size; omit for dense-equivalent capacity")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="prompt tokens admitted per tick (multiple of "
                         "page_len; default one page)")
    ap.add_argument("--profile", metavar="PATH_OR_DEVICE", default=None,
                    help="dissected DeviceProfile artifact (repro.profile/v1 "
                         "JSON, or a device name under experiments/profiles/) "
                         "— page sizing and cost terms consume it instead of "
                         "the built-in TPU_V5E constants")
    ap.add_argument("--mesh-shape", metavar="N[,M]", default=None,
                    help="shard each paged engine/replica's KV pool over a "
                         "device mesh (launch.mesh.make_serve_mesh): '4' is "
                         "4 devices on (model,), '2,4' is (data, model); "
                         "set XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N for host-device meshes.  Token streams "
                         "are bit-identical across mesh widths")
    # fleet knobs
    ap.add_argument("--replicas", type=int, default=None,
                    help="fleet: number of paged replicas (default 1, or "
                         "the length of --fleet-profiles)")
    ap.add_argument("--fleet-profiles", metavar="P1,P2,...", default=None,
                    help="fleet: one profile per replica — artifact path, "
                         "device name under experiments/profiles/, or a "
                         "registered device's published profile; mixed "
                         "GPU/TPU fleets are supported")
    ap.add_argument("--dissect-on-start", metavar="DEV1,DEV2,...",
                    default=None,
                    help="fleet: blind-dissect the named registered "
                         "device(s) at startup with the batched engine and "
                         "bind one replica to each fresh profile (always-"
                         "measure posture; mutually exclusive with "
                         "--fleet-profiles)")
    ap.add_argument("--fleet-tiers", metavar="PLAN", default=None,
                    help="fleet: disaggregate prefill/decode — "
                         "'prefill:0,1/decode:2,3' pins replica indices "
                         "per tier, 'auto' ranks replicas by measured "
                         "profile (bandwidth-rich -> prefill, low-latency "
                         "-> decode), 'none'/omitted keeps the symmetric "
                         "fleet; with --plan, also prints the per-tier "
                         "capacity answer")
    ap.add_argument("--faults", type=int, metavar="SEED", default=None,
                    help="fleet: run a seeded fault campaign (kill / "
                         "corrupt / degrade) twice and verify bit-identical "
                         "replay; exits 1 on divergence, leaks, or "
                         "unclassified requests")
    ap.add_argument("--fault-rate", type=float, default=0.05,
                    help="per-tick fault probability for --faults "
                         "campaigns (default 0.05)")
    # workload / SLO / planner knobs
    ap.add_argument("--workload", metavar="SCENARIO", default=None,
                    help="fleet: drive a seeded workload trace (one of "
                         "chat, rag, agent, batch — serve.workload."
                         "SCENARIOS) through the front end and report "
                         "TTFT/TPOT percentiles from the SLO tracker")
    ap.add_argument("--arrival", choices=("poisson", "bursty", "diurnal"),
                    default="poisson",
                    help="workload arrival process (default poisson)")
    ap.add_argument("--rate", type=float, default=0.5,
                    help="workload nominal arrivals per tick (default 0.5)")
    ap.add_argument("--horizon", type=int, default=64,
                    help="workload arrival window in ticks (default 64)")
    ap.add_argument("--workload-replay", action="store_true",
                    help="run the seeded trace twice on fresh fleets and "
                         "exit 1 on any divergence (trace bytes, SLO "
                         "report, decision log)")
    ap.add_argument("--plan", action="store_true",
                    help="capacity planner: smallest replica count per "
                         "candidate profile meeting --slo-ttft at the "
                         "workload's arrival rate — pure Little's-law + "
                         "queueing accounting, no simulation")
    ap.add_argument("--slo-ttft", type=float, default=32.0,
                    help="SLO target: predicted p99 TTFT in ticks "
                         "(default 32)")
    ap.add_argument("--router-margin", type=float, default=None,
                    help="fleet: replicas within this fraction of the best "
                         "predicted step cost compete on page headroom "
                         "(default: serve.fleet.ROUTER_MARGIN)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-dir", metavar="DIR", default=None,
                    help="record the served run with the JAX profiler "
                         "into DIR: device ops beside the serve.* host "
                         "spans (repro.serve.spans); open the .xplane.pb "
                         "under DIR/plugins/profile/ in TensorBoard or "
                         "read it with jax.profiler.ProfileData")
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    # jax is already imported here, so the cache is set through its config
    # (the JAX_* variables that enable_env sets are read at import)
    jaxcache.enable()
    if args.router_margin is None:
        from repro.serve.fleet import ROUTER_MARGIN
        args.router_margin = ROUTER_MARGIN

    if args.profile:
        from repro.profile import install_profile
        prof = install_profile(args.profile)
        print(f"profile: {prof.summary()}")

    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    if cfg.is_encoder:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode path")
    if args.workload is not None:
        from repro.serve.workload import SCENARIOS
        if args.workload not in SCENARIOS:
            raise SystemExit(f"unknown --workload {args.workload!r}; "
                             f"one of {', '.join(sorted(SCENARIOS))}")
    if args.plan:
        if args.workload is None:
            args.workload = "chat"
        _plan(cfg, args)       # pure accounting: no params, no device
        return
    params = T.init_params(cfg, jax.random.key(0))
    with (jax.profiler.trace(args.trace_dir) if args.trace_dir
          else contextlib.nullcontext()):
        if args.engine == "loop":
            _batch_loop(cfg, params, args)
        elif args.engine == "fleet":
            if args.faults is not None:
                _fault_campaign(cfg, params, args)
            elif args.workload is not None:
                _workload_run(cfg, params, args)
            else:
                _fleet_run(cfg, params, args)
        else:
            _engine_run(cfg, params, args)


if __name__ == "__main__":
    main()
