"""Beyond-paper: mesh-sharded paged serving — the width-invariance oracle.

One fleet replica = one device slice: the paged KV pool's heads axis is
laid out over the mesh's ``"model"`` axis (``NamedSharding``), the paged
scatter/gather runs under ``shard_map``, and the cache operand is donated
with pinned ``out_shardings`` so the sharded update stays copy-free.  The
host-side allocator and page tables are untouched — sharding moves the
pool, never the books.

Every verdict is deterministic accounting (no timings gate anything):

* **mesh-1 oracle**: a 1-device-mesh engine equals the unsharded paged
  engine token-for-token on the same tick schedule;
* **width invariance**: 2/4/8-way host-device meshes
  (``XLA_FLAGS=--xla_force_host_platform_device_count``) are
  bit-identical to the 1-device mesh, including the 8-way GQA fallback;
* **zero page leaks** after drain on every width;
* **donation honored**: the previous cache's leaves are deleted after
  every step and no "donated buffer" warning is raised.

The per-shard Little's-law page pricing (thinner rows per partition)
rides along as info metrics.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from repro.bench import Context, Metric, experiment, info

# runs in a subprocess per width: XLA_FLAGS must precede jax init
_WIDTH_CODE = """
import json
import jax, numpy as np
from repro.launch.mesh import make_serve_mesh
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.serve.engine import PagedServeEngine, Request

CFG = ModelConfig(name="micro4", family="dense", num_layers=2, d_model=32,
                  d_ff=64, vocab_size=64, num_heads=4, num_kv_heads=4,
                  dtype="float32", param_dtype="float32")
PARAMS = T.init_params(CFG, jax.random.key(0))
WORK = [(8, 6), (12, 4), (5, 9), (16, 3)]

def run(mesh):
    rng = np.random.default_rng(3)
    eng = PagedServeEngine(CFG, PARAMS, max_slots=3, max_len=32,
                           page_len=8, mesh=mesh)
    for uid, (plen, n) in enumerate(WORK):
        eng.submit(Request(uid, rng.integers(CFG.vocab_size, size=plen)
                           .astype(np.int32), n))
    fin = eng.run_to_completion()
    eng.check_invariants()
    return ({str(r.uid): [int(t) for t in r.generated] for r in fin},
            eng.steps, eng.shards, eng.alloc.allocated_pages)

base, steps0, _, leak0 = run(make_serve_mesh(1))
out = {"widths": {}, "equal": True, "schedule": True, "leaked": leak0}
for w in WIDTHS:
    got, steps, shards, leaked = run(make_serve_mesh(w))
    out["equal"] &= got == base
    out["schedule"] &= steps == steps0
    out["leaked"] += leaked
    out["widths"][str(w)] = {"shards": shards, "steps": steps}
print("RESULT " + json.dumps(out))
"""


def _src_path() -> str:
    # repro is a namespace package (__file__ is None): anchor on a module
    import repro.bench as _bench
    pkg = os.path.dirname(os.path.abspath(_bench.__file__))   # .../repro/bench
    return os.path.dirname(os.path.dirname(pkg))              # .../src


def _width_sweep(widths: tuple[int, ...]) -> dict:
    env = dict(os.environ)
    # a host-device mesh on the CPU: the parent may hold the accelerator
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _src_path()
    code = f"WIDTHS = {widths!r}\n" + textwrap.dedent(_WIDTH_CODE)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    for line in r.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"width sweep failed:\n{r.stdout}\n{r.stderr[-2000:]}")


@experiment(
    title="Mesh-sharded paged KV cache",
    section="§5.1+§6.2 applied",
    artifact="beyond-paper",
    devices=("tpu_v5e",),
    tags=("serve", "paging", "sharding", "mesh", "shard-map", "tpu"),
    expected={
        "Mesh-1 oracle": "a 1-device-mesh replica equals the unsharded "
                         "paged engine token-for-token on the same ticks",
        "Width invariance": "2/4/8-way host-device meshes are "
                            "bit-identical to the 1-device mesh",
        "Donation": "the cache updates in place on the sharded path "
                    "(buffers consumed, no XLA donation warning)",
        "Accounting": "zero pages leaked after drain on every width",
    })
def run(ctx: Context) -> list[Metric]:
    # lazy: keep registry.discover() jax-free (see tpu_roofline)
    import warnings

    import jax
    import numpy as np

    from repro.launch.mesh import make_serve_mesh
    from repro.models import transformer as T
    from repro.models.config import ModelConfig
    from repro.serve import paging
    from repro.serve.engine import PagedServeEngine, Request

    cfg = ModelConfig(name="micro", family="dense", num_layers=2,
                      d_model=32, d_ff=64, vocab_size=64, num_heads=2,
                      num_kv_heads=2, dtype="float32",
                      param_dtype="float32")
    params = T.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(ctx.seed)
    n_req = 4 if ctx.quick else 6
    work = [(int(rng.integers(3, 12)), int(rng.integers(3, 9)))
            for _ in range(n_req)]

    def drive(mesh):
        rq = np.random.default_rng(ctx.seed + 1)
        eng = PagedServeEngine(cfg, params, max_slots=3, max_len=32,
                               page_len=8, mesh=mesh)
        for uid, (plen, n) in enumerate(work):
            eng.submit(Request(uid, rq.integers(cfg.vocab_size, size=plen)
                               .astype(np.int32), n))
        fin = eng.run_to_completion()
        eng.check_invariants()
        return ({r.uid: tuple(r.generated) for r in fin}, eng.steps,
                eng.alloc.allocated_pages)

    oracle, steps_u, leak_u = drive(None)
    mesh1, steps_1, leak_1 = drive(make_serve_mesh(1))

    # donation on the sharded path: buffers consumed, no XLA warning
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        eng = PagedServeEngine(cfg, params, max_slots=2, max_len=32,
                               page_len=4, mesh=make_serve_mesh(1))
        eng.submit(Request(0, np.arange(4, dtype=np.int32) + 1, 12))
        consumed = True
        for _ in range(6):
            before = jax.tree.leaves(eng.cache)
            eng.step()
            consumed &= all(leaf.is_deleted() for leaf in before)
    donation_warns = [str(w.message) for w in caught
                      if "donat" in str(w.message).lower()]

    widths = (2,) if ctx.quick else (2, 4, 8)
    sweep = _width_sweep(widths)
    shards_seen = {int(w): d["shards"] for w, d in sweep["widths"].items()}

    gen_tokens = sum(len(v) for v in oracle.values())
    metrics = [
        Metric("mesh1_tokens_identical_to_unsharded", mesh1 == oracle,
               True, cmp="eq",
               detail=f"{len(oracle)} requests, {gen_tokens} tokens"),
        Metric("mesh1_tick_schedule_matches", steps_1 == steps_u, True,
               cmp="eq", detail=f"mesh {steps_1} vs unsharded {steps_u}"),
        Metric("width_equality_bit_identical", bool(sweep["equal"]), True,
               cmp="eq",
               detail=f"widths {widths} vs 1-device mesh, forced "
                      "host-device mesh subprocess"),
        Metric("width_tick_schedules_match", bool(sweep["schedule"]), True,
               cmp="eq"),
        Metric("pages_leaked_all_widths",
               leak_u + leak_1 + int(sweep["leaked"]), 0, cmp="eq"),
        Metric("donation_cache_consumed_in_place", consumed, True,
               cmp="eq", detail="previous cache leaves deleted after "
                                "every sharded step"),
        Metric("donation_warnings", len(donation_warns), 0, cmp="eq",
               detail="; ".join(donation_warns) or "none raised"),
        info("gather_shards_by_width",
             " ".join(f"{w}->{s}" for w, s in sorted(shards_seen.items())),
             detail="8-way falls back to 1 when KV heads do not divide "
                    "(GQA replication fallback)"),
    ]
    if 8 in shards_seen:
        metrics.append(Metric("gqa_fallback_no_divergence",
                              shards_seen[8] == 1 and bool(sweep["equal"]),
                              True, cmp="eq",
                              detail="4 KV heads on an 8-way mesh "
                                     "replicate, tokens unchanged"))
    for s in (1, 2, 4, 8):
        terms = paging.page_len_rationale(cfg, expected_tokens=32, shards=s)
        best = min(terms, key=lambda t: (t.score, t.page_len))
        metrics.append(info(
            f"page_len_pricing/shards={s}",
            f"page_len={best.page_len} row_bytes={best.row_bytes} "
            f"gather_frac={best.gather_frac}",
            detail="per-partition bandwidth against 1/shards-thin rows"))
    return metrics
