"""Execute registered experiments across registered devices.

One :class:`~repro.bench.result.ExperimentRecord` per experiment × device.
The runner never imports individual benchmark modules — it only sees the
registry — so adding an experiment is one decorated function in
``benchmarks/`` and nothing else.

With ``jobs > 1`` the experiment × device records fan out over a process
pool.  Scheduling is invisible in the output: records come back in the
same deterministic order as the serial path, each record's seed is a
stable hash of ``(base seed, experiment, device)`` rather than anything
execution-order-dependent, and ``elapsed_s`` is still measured around the
experiment body inside the worker, so the artifact schema and its timing
semantics are unchanged.  Workers rebuild the registry via
``registry.discover()`` and attach the same trace cache as the parent, so
pooled and serial runs share cached traces.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
import traceback
from concurrent.futures import ProcessPoolExecutor
from typing import Callable, Iterable

from repro.bench import registry
from repro.bench.registry import Context, Experiment
from repro.bench.result import ExperimentRecord, Metric
from repro.core import devices as device_registry
from repro.core import tracecache

Row = tuple[str, float, str]     # legacy CSV row: name, us_per_call, derived


@dataclasses.dataclass(frozen=True)
class RunOptions:
    device: str | None = None          # restrict to one device
    tag: str | None = None
    section: str | None = None
    names: tuple[str, ...] = ()
    quick: bool = False
    seed: int = 0
    jobs: int = 1                      # >1: experiment×device process pool
    trace_cache_root: str | None = None  # propagated to pool workers


def record_seed(base: int, experiment: str, device: str) -> int:
    """Deterministic per-record seed: independent of pool scheduling, run
    order, and jobs count — a record reruns identically in any context."""
    h = hashlib.sha256(f"{base}:{experiment}:{device}".encode()).digest()
    return int.from_bytes(h[:4], "little")


def run_experiments(opts: RunOptions = RunOptions(),
                    progress: Callable[[str], None] | None = None,
                    ) -> list[ExperimentRecord]:
    """Run the selected experiments on every applicable device."""
    exps = registry.select(device=opts.device, tag=opts.tag,
                           section=opts.section, names=opts.names or None)
    tasks: list[tuple[Experiment, str]] = [
        (exp, dev) for exp in exps for dev in exp.devices
        if not (opts.device and dev != opts.device)]
    if opts.jobs > 1 and len(tasks) > 1:
        return _run_pooled(tasks, opts, progress)
    records: list[ExperimentRecord] = []
    for exp, dev_name in tasks:
        if progress:
            progress(f"{exp.name} × {dev_name}")
        records.append(run_one(exp, dev_name, quick=opts.quick,
                               seed=record_seed(opts.seed, exp.name,
                                                dev_name)))
    return records


def run_one(exp: Experiment, device: str, quick: bool = False,
            seed: int = 0) -> ExperimentRecord:
    ctx = Context(device=device_registry.get_device(device), quick=quick,
                  seed=seed)
    t0 = time.perf_counter()
    metrics: list[Metric] = []
    error = None
    try:
        metrics = list(exp.run(ctx))
    except Exception:
        error = traceback.format_exc(limit=8)
    return ExperimentRecord(
        experiment=exp.name, device=device, section=exp.section,
        artifact=exp.artifact, metrics=metrics,
        elapsed_s=time.perf_counter() - t0, error=error)


# ---------------------------------------------------------------------------
# process-pool fan-out
# ---------------------------------------------------------------------------


def _worker_init(trace_cache_root: str | None) -> None:
    # workers run simulator records only; pinned to the CPU, none of them
    # can open the accelerator that the parent may hold
    os.environ["JAX_PLATFORMS"] = "cpu"
    from repro import jaxcache
    jaxcache.enable_env()        # env-only: jax stays lazy until needed
    registry.discover()
    if trace_cache_root:
        tracecache.configure(trace_cache_root)


#: artifact consulted for longest-first pool scheduling (best effort)
HINT_ARTIFACT = os.path.join("experiments", "bench", "latest.json")


def _historical_costs(path: str = HINT_ARTIFACT) -> dict[tuple[str, str], float]:
    """(experiment, device) -> elapsed_s from the committed baseline, for
    makespan-friendly submission order.  Purely a scheduling hint: results
    and their order are identical whether or not the file exists."""
    try:
        with open(path) as fh:
            payload = json.load(fh)
        return {(r["experiment"], r["device"]): float(r.get("elapsed_s", 0))
                for r in payload.get("records", [])}
    except (OSError, ValueError, KeyError):
        return {}


def _worker_run_batch(items: list[tuple[str, str, int]],
                      quick: bool) -> list[ExperimentRecord]:
    return [run_one(registry.get(name), device, quick=quick, seed=seed)
            for name, device, seed in items]


def _run_pooled(tasks: list[tuple[Experiment, str]], opts: RunOptions,
                progress: Callable[[str], None] | None,
                ) -> list[ExperimentRecord]:
    jobs = min(opts.jobs, len(tasks))
    costs = _historical_costs()

    def cost(i: int) -> float:
        return costs.get((tasks[i][0].name, tasks[i][1]), float("inf"))

    # TPU records run in this process, one after another, while the
    # simulator records run on the CPU-pinned workers: one process per
    # chip, and the TPU records share one jax import and warmup
    tpu_idx = [i for i, (_, dev) in enumerate(tasks)
               if device_registry.get_device(dev).kind == "tpu"]
    solo_idx = [i for i in range(len(tasks)) if i not in set(tpu_idx)]
    # longest-first submission; unknown records first (assume heavy)
    solo_idx.sort(key=lambda i: -cost(i))
    results: list = [None] * len(tasks)
    with ProcessPoolExecutor(
            max_workers=jobs, initializer=_worker_init,
            initargs=(opts.trace_cache_root,)) as pool:
        futures = []
        for i in solo_idx:
            exp, dev = tasks[i]
            if progress:
                progress(f"{exp.name} × {dev}")
            futures.append((i, pool.submit(
                _worker_run_batch,
                [(exp.name, dev, record_seed(opts.seed, exp.name, dev))],
                opts.quick)))
        for i in tpu_idx:
            exp, dev = tasks[i]
            if progress:
                progress(f"{exp.name} × {dev}")
            results[i] = run_one(exp, dev, quick=opts.quick,
                                 seed=record_seed(opts.seed, exp.name, dev))
        for i, fut in futures:
            results[i] = fut.result()[0]
    # original task order, not completion or submission order
    return results


def records_to_rows(records: Iterable[ExperimentRecord]) -> list[Row]:
    """Flatten records into the legacy ``name,us_per_call,derived`` rows."""
    rows: list[Row] = []
    for rec in records:
        for m in rec.metrics:
            derived = f"{m.measured}"
            if m.unit:
                derived += f"{m.unit}"
            if m.cmp != "info":
                derived += f" [expect {m.expected} -> {m.verdict}]"
            if m.detail:
                derived += f" ({m.detail})"
            rows.append((f"{rec.experiment}/{rec.device}/{m.name}", m.us,
                         derived.replace(",", ";")))
        if rec.error:
            rows.append((f"{rec.experiment}/{rec.device}/ERROR", 0.0,
                         rec.error.strip().splitlines()[-1].replace(",", ";")))
    return rows
