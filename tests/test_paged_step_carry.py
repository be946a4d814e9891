"""The paged step carries the stacked pool through its layer scan.

``T.paged_step`` keeps the whole stacked cache in the scan's carry: unit
``li`` scatters into and gathers from rows ``[li, ...]`` of each stacked
leaf, so no unit's pool is sliced out of the stack and written back.
The oracle here is the formulation it replaced, kept only in this test:
the pool mapped through the scan from ``xs`` to ``ys``, each unit
handed its own slice.  For every paged block kind (GQA, MLA, SSM, a
hybrid unit of several blocks) one decode step and one padded prefill
chunk must give bit-identical logits and bit-identical pool leaves,
unsharded and through the ``shard_map`` scatter and gather of a serving
mesh.
"""

import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.launch.mesh import make_serve_mesh
from repro.models import layers as L
from repro.models import transformer as T
from repro.parallel import sharding
from repro.serve.engine import MESH_SERVE_RULES

#: paged block kind -> (registered architecture, overrides of its smoke
#: config); the hybrid unit is cut to one SSM block and one attention
#: block, for compile time
KINDS = {"gqa": ("granite-8b", {}),
         "mla": ("deepseek-v2-lite-16b", {}),
         "ssm": ("mamba2-1.3b", {}),
         "hybrid": ("jamba-1.5-large-398b", {"attn_period": 2})}
UNITS = 2

SLOTS, PAGE_LEN, NUM_PAGES = 3, 4, 9


def _cfg(kind):
    arch, overrides = KINDS[kind]
    cfg = dataclasses.replace(configs.get_smoke_config(arch), **overrides)
    return dataclasses.replace(
        cfg, num_layers=UNITS * len(T.unit_spec(cfg)))


def _pool(cfg, key):
    """A paged pool with random contents, so every read is checked."""
    cache = T.init_paged_cache(cfg, NUM_PAGES, PAGE_LEN, SLOTS)
    leaves, tree = jax.tree.flatten(cache)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(tree, [
        jax.random.normal(k, a.shape, jnp.float32).astype(a.dtype)
        for k, a in zip(keys, leaves)])


def _steps(cfg):
    """(tokens, start, page_tables, slot_ids, seq_lens) of one decode step
    over every slot (the last one empty, on the scratch page and row) and
    one chunk of two pages, padded after six valid tokens."""
    rng = np.random.default_rng(0)
    i32 = lambda a: jnp.asarray(a, jnp.int32)
    decode = (i32(rng.integers(cfg.vocab_size, size=(SLOTS, 1))),
              i32([5, 9, 0]),
              i32([[1, 2, 0, 0], [3, 4, 5, 0], [0, 0, 0, 0]]),
              i32([0, 1, SLOTS]), None)
    chunk = (i32(rng.integers(cfg.vocab_size, size=(1, 2 * PAGE_LEN))),
             i32([4]), i32([[7, 8, 6, 0]]), i32([2]), i32([6]))
    return {"decode": decode, "prefill_chunk": chunk}


def _layer_at_a_time(params, cfg, cache, tokens, start, page_tables,
                     slot_ids, seq_lens):
    """The replaced formulation: the pool goes into the scan as ``xs``,
    each unit updates its own slice, and the slices come out as ``ys``."""
    x = T._embed_inputs(params, cfg, {"tokens": tokens})
    positions = (start[:, None]
                 + jnp.arange(x.shape[1], dtype=jnp.int32)[None, :])

    def unit_fn(h, inp):
        unit_params, unit_cache = inp
        one = jax.tree.map(lambda a: a[None], unit_cache)
        h, new, _ = T._apply_unit(unit_params, h, cfg, positions=positions,
                                  caches=one, cache_index=start,
                                  page_table=page_tables, slot_ids=slot_ids,
                                  seq_lens=seq_lens, layer=jnp.int32(0))
        return h, jax.tree.map(lambda a: a[0], new)

    x, cache = jax.lax.scan(unit_fn, x, (params["units"], cache))
    x = T.rms_final(params, cfg, x)
    return T.head_logits(params, cfg, x), cache


def _run(fn, params, cfg, cache, args, ctx):
    with sharding.use(ctx):
        step = jax.jit(lambda p, c, *a: fn(p, cfg, c, *a))
        return jax.device_get(step(params, cache, *args))


def _check(kind, mesh_devices):
    """Carry vs the layer-at-a-time oracle for one block kind, both under
    a serving mesh of ``mesh_devices`` (0 = none)."""
    cfg = _cfg(kind)
    params = T.init_params(cfg, jax.random.key(1))
    cache = _pool(cfg, jax.random.key(2))
    ctx = None
    if mesh_devices:
        mesh = make_serve_mesh(mesh_devices)
        ctx = sharding.ShardingCtx(mesh, dict(MESH_SERVE_RULES))
        params = jax.device_put(params, NamedSharding(mesh, P()))
        cache = jax.device_put(cache, T.paged_cache_shardings(cache, ctx))
        with sharding.use(ctx):
            for b in cache.values():
                if "k" in b:        # GQA leaves take the shard_map path
                    assert L._paged_shard_axes(b["k"]) is not None
    for step, args in _steps(cfg).items():
        want_logits, want_pool = _run(_layer_at_a_time, params, cfg, cache,
                                      args, ctx)
        got_logits, got_pool = _run(T.paged_step, params, cfg, cache, args,
                                    ctx)
        assert np.array_equal(got_logits, want_logits), (kind, step)
        assert jax.tree.structure(got_pool) == jax.tree.structure(cache)
        for (path, got), want in zip(
                jax.tree_util.tree_leaves_with_path(got_pool),
                jax.tree.leaves(want_pool)):
            assert got.dtype == want.dtype and got.shape == want.shape
            assert np.array_equal(got, want), (kind, step, path)


@pytest.mark.parametrize("meshed", [False, True], ids=["unsharded", "mesh1"])
@pytest.mark.parametrize("kind", list(KINDS))
def test_carry_matches_layer_at_a_time(kind, meshed):
    _check(kind, 1 if meshed else 0)


def test_carry_matches_layer_at_a_time_on_two_way_mesh():
    """KV heads split over two host devices: each shard scatters and
    gathers its own heads of the stacked leaf, at the replicated ``li``.
    (MLA and SSM leaves are not sharded; the hybrid's attention block is
    GQA.)"""
    code = f"""
    import sys
    sys.path.insert(0, {os.path.dirname(__file__)!r})
    import test_paged_step_carry as t
    t._check("gqa", 2)
    print("OK")
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert "OK" in r.stdout, r.stdout + r.stderr
