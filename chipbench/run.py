"""On-chip benchmark of the serving stack: one run of one cell.

    python chipbench/run.py --workload internvl2-2b.conv --seed 7 \\
        --seconds 51 --trace 0

A cell (``BENCHMARK.json`` ``workloads``) is one model configuration
under one traffic mix.  A run:

1. finds a TPU with as many chips as the cell asks for, or exits non-zero
   with no result (it never falls back to the CPU);
2. builds the configuration's weights on the device from ``--seed`` in one
   jitted call, and the fleet (``FleetFrontend`` over a one-replica
   ``FleetEngine`` over ``PagedServeEngine``) with the configuration's pool;
3. warms up the shapes the traffic uses, with one request of two prompt
   chunks and a few decode steps, and counts that as set-up;
4. drives the traffic through ``front.tick()``: a ramp of ``ramp_s``, then
   the measured window of ``--seconds``, then until every request due in
   the window has its first token.  With ``--trace 1`` the profiler
   records the last seconds of the window;
5. checks what the window served against the plain reference
   (``arch/<arch>.py``), once the program's state is freed;
6. prints, as its last line, one JSON object: ``correct``, ``attempted``,
   ``failed``, ``metrics`` (the cell's end-to-end metrics, or with
   ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
   ``breakdown``, and last ``compared``: each number compared with its
   limit.  The same numbers end standard error.

JAX's compilation cache lives in ``.cache/jax`` of the checkout (or where
``JAX_COMPILATION_CACHE_DIR`` says), so only a cell's first run compiles.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from harness.registry import Registry, UnknownName  # noqa: E402

#: seconds at the end of the window that a ``--trace 1`` run records
TRACE_S = 8.0


def log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


def _import_program():
    """Put the program under test on the path and turn on its compilation
    cache before jax loads; fails where the checkout has no program."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"no program under test: {src}/repro is missing")
    sys.path.insert(0, src)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro import jaxcache
    return jaxcache.enable_env(os.path.join(ROOT, ".cache", "jax"))


class CompileCounter:
    """Counts compilations (misses) and loads from the persistent cache
    (hits), each with the host time at which it was reported."""

    def __init__(self):
        import jax
        self.events: list[tuple[float, str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter(), "compile", duration))

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.events.append((time.perf_counter(), "cache_hit", 0.0))

    def between(self, a: float, b: float) -> dict:
        out = {"compile": 0, "cache_hit": 0, "compile_s": 0.0}
        for t, kind, d in self.events:
            if a <= t < b:
                out[kind] += 1
                out["compile_s"] += d
        return out


def require_chip(chips: int):
    """The first ``chips`` TPU devices, or SystemExit."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX's default backend is "
                         f"{devices[0].platform!r}; this benchmark needs "
                         f"the chip")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} TPU chips, found "
                         f"{len(devices)}")
    return devices[:chips]


def program_config(conf: dict):
    """The program's model configuration for a configuration file."""
    from repro import configs
    prog = conf["program"]
    return dataclasses.replace(configs.get_config(prog["registry"]),
                               **prog["fields"])


def _key(seed: int):
    import jax
    import numpy as np
    words = np.random.SeedSequence(seed % 2**64).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32))


def _sample(reqs, n: int, seed: int):
    """Finished requests to check: the one with the most served tokens,
    then, in an order drawn from the seed, one that decoded in each slot
    not yet covered, then others.  So a fault that spares some rows of the
    decode batch shows."""
    import numpy as np
    done = [r for r in reqs if r.done]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.tokens), -r.item.index))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(np.random.SeedSequence([seed % 2**64, 3]))
    order = [rest[i] for i in rng.permutation(len(rest))]
    picked = [longest]
    covered = {longest.slot} if len(longest.tokens) > 1 else set()
    for r in order:
        if len(picked) < n and len(r.tokens) > 1 \
                and r.slot is not None and r.slot not in covered:
            picked.append(r)
            covered.add(r.slot)
    chosen = {id(r) for r in picked}
    picked += [r for r in order if id(r) not in chosen][:n - len(picked)]
    return picked


def check(arch, conf: dict, weights, reqs, *, seed: int, max_len: int,
          sample: int, control: bool = False) -> dict:
    """Run the plain reference over each sampled request's prompt and
    served tokens; return the widest logit gap and what was compared."""
    import jax.numpy as jnp
    import numpy as np
    fn = arch.gap_fn(conf, control=control)
    worst, agree, n_tok = 0.0, 0, 0
    picked = _sample(reqs, sample, seed)
    for r in picked:
        served = np.asarray(r.tokens, np.int32)
        seq = np.concatenate([r.item.prompt, served[:-1]])
        tokens = np.zeros(max_len, np.int32)
        targets = np.zeros(max_len, np.int32)
        valid = np.zeros(max_len, bool)
        tokens[:len(seq)] = seq
        at = r.plen - 1 + np.arange(len(served))
        targets[at] = served
        valid[at] = True
        g, a, n = fn(weights, jnp.asarray(tokens), jnp.asarray(targets),
                     jnp.asarray(valid))
        worst = max(worst, float(g))
        agree += int(a)
        n_tok += int(n)
    return {"max_logit_gap": worst, "requests": len(picked),
            "tokens": n_tok, "top1_agree": agree,
            "longest": max((len(r.tokens) for r in picked),
                           default=0)}


def judge(checked: dict, conf: dict, cell, *, mismatched: int,
          invariants: int) -> tuple[dict, bool]:
    """Each number compared beside its limit, and whether all hold."""
    compared = {
        "max_logit_gap": {"value": checked["max_logit_gap"],
                          "limit": conf["check"]["max_logit_gap"]},
        "checked_requests": {"value": checked["requests"],
                             "limit": cell.traffic["check_requests"]},
        "length_mismatches": {"value": mismatched, "limit": 0},
        "invariant_violations": {"value": invariants, "limit": 0},
    }
    correct = (compared["max_logit_gap"]["value"]
               <= compared["max_logit_gap"]["limit"]
               and checked["requests"] == cell.traffic["check_requests"]
               and mismatched == 0 and invariants == 0)
    return compared, correct


def _step_hbm_bytes(engine) -> int:
    """Arguments + temp of the compiled decode step, as the compiler
    reserves them (donated outputs alias their arguments)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    b = engine.max_slots

    def shape(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)

    args = (jax.tree.map(shape, engine.params),
            jax.tree.map(shape, engine.cache),
            jnp.zeros((b, 1), jnp.int32), jnp.zeros((b,), jnp.int32),
            jnp.asarray(np.zeros_like(engine.page_tables)),
            jnp.zeros((b,), jnp.int32))
    mem = engine._decode_step.lower(*args).compile().memory_analysis()
    return int(mem.argument_size_in_bytes + mem.temp_size_in_bytes)


def run_cell(reg: Registry, cell, *, seed: int, seconds: float, trace: bool,
             devices, counter: CompileCounter, t_start: float,
             peaks: dict | None = None, control: bool = False) -> dict:
    """One run of ``cell`` on ``devices``; returns the result object.
    ``peaks`` replaces the table's entry for the device only in the
    benchmark's own tests, which drive a run on the CPU.  ``control``
    (``calibrate.py`` only) also reads the control over the same sample,
    judges it as the program's answers are judged, and adds both to the
    result as ``control``."""
    import jax
    import numpy as np

    from harness import serve, trace as tr
    from harness.traffic import Traffic
    from repro.core.devices import TPU_V5E, TPU_SPECS_BY_KIND
    from repro.serve.fleet import FleetEngine
    from repro.serve.frontend import FleetFrontend

    t_enter = time.perf_counter()
    dev = devices[0]
    peaks = peaks or reg.peaks(dev.device_kind)
    conf, pool = cell.config, cell.config["pool"]
    arch = reg.arch(conf["arch"])
    cfg = program_config(conf)
    log(f"device: {dev.device_kind} x{len(devices)} ({dev.platform}); "
        f"peaks {peaks['bf16_flops_per_s']:g} FLOP/s bf16, "
        f"{peaks['hbm_bytes_per_s']:g} B/s HBM")

    def init_weights(key):
        return arch.make_weights(conf, key)

    weights = jax.block_until_ready(jax.jit(init_weights)(_key(seed)))
    t_weights = time.perf_counter()
    log(f"model: {conf['name']} layers={cfg.num_layers} d={cfg.d_model} "
        f"heads={cfg.num_heads}/{cfg.num_kv_heads}x{cfg.head_dim} "
        f"ff={cfg.d_ff} vocab={cfg.vocab_size}; "
        f"{arch.param_count(conf) / 1e9:.4f} B params")
    # the fleet prices routing with the program's own spec of this chip
    spec = TPU_SPECS_BY_KIND.get(dev.device_kind, TPU_V5E)
    fleet = FleetEngine(cfg, weights, max_slots=pool["slots"],
                        max_len=pool["max_len"], replicas=1,
                        profiles=[spec], page_len=pool["page_len"],
                        num_pages=pool["num_pages"])
    front = FleetFrontend(fleet)
    warm = np.arange(pool["page_len"] + 1, dtype=np.int32) % cfg.vocab_size
    front.submit(warm, 3, uid=0)
    front.run()
    traffic = Traffic(cell.traffic, seed=seed, vocab=cfg.vocab_size,
                      seconds=seconds, rate=cell.rate)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace \
        else None
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    log(f"set-up phases: process, imports and chip "
        f"{t_enter - t_start:.3f} s, weights {t_weights - t_enter:.3f} s, "
        f"fleet and warm-up {t0 - t_weights:.3f} s")
    log(f"set-up {setup_s:.3f} s ({counter.between(0, t0)}); traffic "
        f"{cell.traffic['name']}: ramp {traffic.ramp_s} s, window "
        f"{seconds} s")
    loop = serve.Loop(front, traffic, t0=t0, seconds=seconds,
                      trace_dir=trace_dir, trace_s=TRACE_S)
    cut = loop.run()
    in_window = counter.between(loop.open, cut)
    log(f"compilations in the window and drain: {in_window}")
    stats = fleet.stats()
    invariants = 0
    try:
        fleet.check_invariants()
    except AssertionError as e:
        invariants = 1
        log(f"fleet invariant violated: {e}")
    mem = dev.memory_stats() or {}
    log(f"memory: peak_bytes_in_use={mem.get('peak_bytes_in_use')} "
        f"bytes_limit={mem.get('bytes_limit')}")
    log(f"fleet: {stats['finished'] - 1} finished, ticks={stats['ticks']}, "
        f"preemptions={stats['preemptions']}, "
        f"peak_pages={stats['peak_pages']}/{pool['num_pages']}")
    run = serve.Run(setup_s=setup_s, open=loop.open, close=loop.close,
                    cut=cut, reqs=list(loop.reqs.values()), ticks=loop.ticks,
                    conf=conf, arch=arch, peaks=peaks,
                    trace_span=loop.trace_span)
    if trace:
        run.step_hbm_bytes = _step_hbm_bytes(fleet.replicas[0].engine)
        run.trace = tr.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    late = sorted(r.submitted - r.due for r in run.window_reqs
                  if r.submitted is not None)
    if late:
        log(f"generator lateness over {len(late)} window requests: median "
            f"{late[len(late) // 2] * 1e3:.2f} ms, max "
            f"{late[-1] * 1e3:.2f} ms")
    for r in run.reqs:
        r.detach()
    del front, fleet, loop
    gc.collect()

    t_ref = time.perf_counter()
    checked = check(arch, conf, weights, run.reqs, seed=seed,
                    max_len=pool["max_len"],
                    sample=cell.traffic["check_requests"])
    log(f"reference over {checked['requests']} requests "
        f"({checked['tokens']} served tokens, longest {checked['longest']}, "
        f"top-1 agreement {checked['top1_agree']}/{checked['tokens']}): "
        f"{time.perf_counter() - t_ref:.1f} s")
    if control:
        t_ref = time.perf_counter()
        ctl = check(arch, conf, weights, run.reqs, seed=seed,
                    max_len=pool["max_len"],
                    sample=cell.traffic["check_requests"], control=True)
        log(f"control: gap {ctl['max_logit_gap']}, top-1 agreement "
            f"{ctl['top1_agree']}/{ctl['tokens']}: "
            f"{time.perf_counter() - t_ref:.1f} s")
    mismatched = sum(1 for r in run.reqs
                     if r.done and len(r.tokens) != r.item.n_new)
    compared, correct = judge(checked, conf, cell, mismatched=mismatched,
                              invariants=invariants)

    window = run.window_reqs
    failed = sum(1 for r in window if r.refused or not r.token_times)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = reg.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": mem.get("peak_bytes_in_use")}
    result = {"correct": correct, "attempted": len(window), "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = tr.busy_s(run.trace)
        device["window_s"] = tr.window_s(run.trace)
        result["breakdown"] = {"device_ops": tr.top_ops(run.trace),
                               "idle_gaps": tr.idle_gaps(run.trace)}
    if control:
        ctl_compared, ctl_correct = judge(ctl, conf, cell,
                                          mismatched=mismatched,
                                          invariants=invariants)
        result["control"] = dict(ctl, compared=ctl_compared,
                                 correct=ctl_correct)
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        reg = Registry()
        cell = reg.cell(args.workload)
    except (UnknownName, OSError) as e:
        log(f"error: {e}")
        return 2
    _import_program()
    counter = CompileCounter()
    devices = require_chip(cell.chips)
    result = run_cell(reg, cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), devices=devices,
                      counter=counter, t_start=T_START)
    for name, c in result["compared"].items():
        log(f"compared {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
