import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each supported cell this driver builds the production sharding
(FSDP/TP/EP/SP per repro.parallel.sharding), lowers the appropriate step
function against ShapeDtypeStructs (no allocation), compiles it, and
records:

  * memory_analysis()      — bytes/device: proves the cell fits 16 GB HBM
  * cost_analysis()        — HLO FLOPs / bytes for §Roofline
  * collective payloads    — parsed from the optimized HLO (§Roofline)

Results land in experiments/dryrun/<mesh>/<arch>__<shape>.json and feed
EXPERIMENTS.md §Dry-run and §Roofline.

Usage:
  python -m repro.launch.dryrun --all --mesh single
  python -m repro.launch.dryrun --arch mamba2-1.3b --shape long_500k --mesh multi
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.shapes import SHAPES, cell_supported, input_specs
from repro.core import costmodel, roofline
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.optim import AdamWConfig
from repro.parallel import sharding as sh
from repro.train.loop import (init_state, make_prefill_step, make_serve_step,
                              make_train_step)

MESHES = {
    "single": dict(multi_pod=False),                 # 16×16 = 256 chips
    "multi": dict(multi_pod=True),                   # 2×16×16 = 512 chips
    "tiny": dict(shape=(2, 2), axes=("data", "model")),        # CI
    "tiny_multi": dict(shape=(2, 2, 2), axes=("pod", "data", "model")),
}


def _sds(tree, axes_tree, ctx):
    """ShapeDtypeStructs with NamedShardings resolved from logical axes."""

    def one(leaf, axes):
        return jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=ctx.named(axes, leaf.shape))

    return jax.tree.map(one, tree, axes_tree,
                        is_leaf=lambda x: hasattr(x, "shape"))


def _batch_axes(batch_specs):
    axes = {}
    for name, spec in batch_specs.items():
        if spec.ndim == 0:
            axes[name] = ()
        else:
            axes[name] = ("batch",) + (None,) * (spec.ndim - 1)
    return axes


def _replicated(tree, ctx):
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                       sharding=ctx.named([None] * l.ndim)),
        tree, is_leaf=lambda x: hasattr(x, "shape"))


def prepare_cell(arch: str, shape_name: str, mesh, *, rules=None,
                 cfg_overrides: dict | None = None,
                 opt_overrides: dict | None = None):
    """Build (jitted_fn, example_args) for one cell. Returns (fn, args, cfg)."""
    cfg = configs.get_config(arch)
    over = {"attention_impl": "chunked"}
    if cfg_overrides:
        over.update(cfg_overrides)
    cfg = dataclasses.replace(cfg, **over)
    shape = SHAPES[shape_name]
    ok, reason = cell_supported(cfg, shape)
    if not ok:
        raise ValueError(f"cell skipped: {reason}")

    cell_rules = dict(rules or {})
    if shape.name == "long_500k":
        # SP: batch-1 long context shards the cache sequence axis
        cell_rules.setdefault("cache_seq", ("data",))
    ctx = sh.ShardingCtx(mesh, cell_rules)

    key = jax.random.key(0)
    if shape.kind == "train":
        opt = AdamWConfig(moment_dtype="bfloat16", **(opt_overrides or {}))
        state_shapes = jax.eval_shape(
            lambda k: init_state(cfg, opt, k), key)
        p_axes = T.param_logical_axes(state_shapes.params)
        state_axes = type(state_shapes)(
            params=p_axes,
            opt_state={"m": p_axes, "v": p_axes, "count": ()},
            step=(), ef_state=None)
        state_sds = _sds_with_fsdp(state_shapes, state_axes, ctx)
        batch_specs = input_specs(cfg, shape)
        batch_sds = _sds(batch_specs, _batch_axes(batch_specs), ctx)
        step = make_train_step(cfg, opt)

        def wrapped(state, batch):
            with sh.use(ctx):
                return step(state, batch)

        out_sh = (jax.tree.map(lambda l: l.sharding, state_sds,
                               is_leaf=lambda x: hasattr(x, "sharding")),
                  None)
        fn = jax.jit(wrapped, out_shardings=out_sh, donate_argnums=0)
        return fn, (state_sds, batch_sds), cfg

    # inference paths share param handling
    params_shapes = jax.eval_shape(lambda k: T.init_params(cfg, k), key)
    p_axes = T.param_logical_axes(params_shapes)
    # serving params: TP + weight-sharding over the data axis (per-layer
    # all-gather); pure TP would leave jamba at 50 GB/chip
    params_sds = _sds_with_fsdp(params_shapes, p_axes, ctx)

    if shape.kind == "prefill":
        batch_specs = input_specs(cfg, shape)
        batch_sds = _sds(batch_specs, _batch_axes(batch_specs), ctx)
        step = make_prefill_step(cfg, max_len=shape.seq_len)

        def wrapped(params, batch):
            with sh.use(ctx):
                return step(params, batch)

        fn = jax.jit(wrapped)
        return fn, (params_sds, batch_sds), cfg

    # decode
    b = shape.global_batch
    cache_shapes = jax.eval_shape(
        lambda: T.init_cache(cfg, b, shape.seq_len))
    c_axes = T.cache_logical_axes(cache_shapes)
    cache_sds = _sds(cache_shapes, c_axes, ctx)
    tok_sds = jax.ShapeDtypeStruct((b, 1), jnp.int32,
                                   sharding=ctx.named(("batch", None),
                                                      (b, 1)))
    idx_sds = jax.ShapeDtypeStruct((), jnp.int32, sharding=ctx.named(()))
    step = make_serve_step(cfg)

    def wrapped(params, cache, tokens, cache_index):
        with sh.use(ctx):
            return step(params, cache, tokens, cache_index)

    cache_out_sh = jax.tree.map(lambda l: l.sharding, cache_sds,
                                is_leaf=lambda x: hasattr(x, "sharding"))
    fn = jax.jit(wrapped, out_shardings=(None, cache_out_sh),
                 donate_argnums=1)
    return fn, (params_sds, cache_sds, tok_sds, idx_sds), cfg


def _sds_with_fsdp(shapes_tree, axes_tree, ctx, fsdp=True):
    real_ctx = ctx if fsdp else sh.ShardingCtx(ctx.mesh, ctx.rules,
                                               fsdp_params=False)

    def one(leaf, axes):
        if not hasattr(leaf, "shape"):
            return leaf
        shd = sh.param_shardings(axes, leaf, real_ctx)
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=shd)

    return jax.tree.map(one, shapes_tree, axes_tree,
                        is_leaf=lambda x: hasattr(x, "shape") or x is None)


def run_cell(arch: str, shape_name: str, mesh_name: str, out_dir: str,
             *, rules=None, cfg_overrides=None, plan_overrides=None,
             tag: str = "baseline"):
    mesh = make_production_mesh(**MESHES[mesh_name])
    chips = mesh.size
    t0 = time.time()
    fn, args, cfg = prepare_cell(arch, shape_name, mesh, rules=rules,
                                 cfg_overrides=cfg_overrides)
    lowered = fn.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    hlo = compiled.as_text()

    shape = SHAPES[shape_name]
    if shape.kind == "decode":
        tokens = shape.global_batch        # one token per sequence
    else:
        tokens = shape.seq_len * shape.global_batch
    model_flops = cfg.model_flops_per_token() * tokens
    if shape.kind != "train":
        model_flops /= 3.0                  # forward only: 2·N·D

    # spec=None resolves through repro.core.profile: an installed dissected
    # profile reaches the roofline terms here
    report = roofline.analyze(
        f"{arch}__{shape_name}__{mesh_name}", cost=cost, hlo_text=hlo,
        chips=chips, spec=None, model_flops=model_flops,
        per_device_module=True)

    # analytic roofline (authoritative: XLA cost_analysis counts scanned
    # while-bodies once — see core/costmodel.py and tests/test_costmodel.py)
    mesh_axes = dict(mesh.shape)
    plan = costmodel.ParallelismPlan(
        dp=mesh_axes.get("pod", 1) * mesh_axes.get("data", 1),
        tp=mesh_axes.get("model", 1),
        remat=cfg.remat,
        kv_cache_bytes=1 if cfg.kv_cache_dtype == "int8" else 2)
    if plan_overrides:
        for k, v in plan_overrides.items():
            setattr(plan, k, v)
    acost = costmodel.cell_cost(cfg, shape, plan)

    mem_info = {}
    if mem is not None:
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            if hasattr(mem, attr):
                mem_info[attr] = int(getattr(mem, attr))
        # the CPU backend reports temp for the whole host module (all
        # emulated devices): normalize to per-chip
        if "temp_size_in_bytes" in mem_info:
            mem_info["temp_per_chip_bytes"] = mem_info["temp_size_in_bytes"] // chips
    # analytic per-chip residency from input shardings (CPU backends don't
    # model HBM): sum of addressable shard bytes
    arg_bytes = 0
    for leaf in jax.tree.leaves(args,
                                is_leaf=lambda x: hasattr(x, "sharding")):
        if hasattr(leaf, "sharding") and leaf.sharding is not None:
            shard_shape = leaf.sharding.shard_shape(leaf.shape)
            n = 1
            for d in shard_shape:
                n *= d
            arg_bytes += n * leaf.dtype.itemsize
    mem_info["per_chip_argument_bytes"] = arg_bytes
    per_chip_total = arg_bytes + mem_info.get("temp_per_chip_bytes", 0)
    mem_info["per_chip_total_bytes"] = per_chip_total
    mem_info["fits_16gb"] = bool(per_chip_total < 16 * (1 << 30))

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "tag": tag,
        "chips": chips,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": mem_info,
        "cost": {k: cost.get(k) for k in ("flops", "bytes accessed")
                 if k in cost},
        "roofline_compiled": report.to_json(),
        "roofline": acost.to_json(),
    }
    os.makedirs(out_dir, exist_ok=True)
    fname = os.path.join(out_dir, f"{arch}__{shape_name}.json"
                         if tag == "baseline"
                         else f"{arch}__{shape_name}__{tag}.json")
    with open(fname, "w") as f:
        json.dump(rec, f, indent=2)
    return rec


def build_parser():
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.dryrun",
        description="multi-pod dry-run: lower + compile every "
                    "(arch x shape x mesh) cell, record memory/cost/"
                    "collective evidence")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=list(MESHES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--tag", default="baseline")
    return ap


def main():
    args = build_parser().parse_args()

    cells = []
    archs = configs.list_archs() if (args.all or args.arch is None) \
        else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    for arch in archs:
        for shp in shapes:
            cfg = configs.get_config(arch)
            ok, reason = cell_supported(cfg, SHAPES[shp])
            if not ok:
                print(f"SKIP {arch} × {shp}: {reason}")
                continue
            cells.append((arch, shp))

    out_dir = os.path.join(args.out, args.mesh)
    failures = []
    for arch, shp in cells:
        try:
            rec = run_cell(arch, shp, args.mesh, out_dir, tag=args.tag)
            r = rec["roofline"]
            print(f"OK   {arch} × {shp} [{args.mesh}] "
                  f"compile={rec['compile_s']}s "
                  f"dom={r['dominant']} step≥{r['step_s']*1e3:.2f}ms "
                  f"roofline={r['roofline_fraction']:.1%} "
                  f"argGB/chip={rec['memory']['per_chip_argument_bytes']/2**30:.2f}")
        except Exception as e:
            failures.append((arch, shp, repr(e)))
            print(f"FAIL {arch} × {shp}: {e}")
            traceback.print_exc()
    print(f"\n{len(cells)-len(failures)}/{len(cells)} cells compiled "
          f"on mesh '{args.mesh}'")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
