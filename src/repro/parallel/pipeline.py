"""GPipe-style pipeline parallelism over a mesh axis.

At the assigned scale (≤512 chips, ≤398B params) FSDP×TP covers the memory
budget, so the dry-run meshes do not reserve a stage axis (DESIGN.md §5);
this module provides the composable PP primitive for larger deployments
(>2k chips), where a ("stage", "data", "model") mesh re-uses the layer-scan
structure: one scan *unit* stack per stage.

Mechanics: ``shard_map`` over the stage axis; each device holds its stage's
parameters; microbatches stream through with ``lax.ppermute`` between
stages; a ``fori_loop`` runs M + S − 1 ticks (fill + drain).  Differentiable
(jax.grad flows through ppermute), so the same primitive backs training.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, PartitionSpec as P


def pipeline_apply(stage_fn, stage_params, x_micro, *, mesh,
                   stage_axis: str = "stage"):
    """Run ``y = stage_S-1(...stage_0(x))`` as a microbatched pipeline.

    stage_params: pytree stacked on a leading stage axis (size S).
    x_micro:      (M, micro_batch, ...) microbatched input.
    Returns       (M, micro_batch, ...) outputs (stage order preserved).
    """
    # the stage axis is manual inside shard_map; outside it the result is
    # an ordinary auto-sharded array, so callers can differentiate through
    # it without entering the mesh (jax.make_mesh defaults to explicit
    # axes, whose typed results need an ambient `jax.set_mesh`)
    mesh = Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))
    num_stages = mesh.shape[stage_axis]
    num_micro = x_micro.shape[0]
    ticks = num_micro + num_stages - 1

    def per_stage(params, xs):
        # params: this stage's slice; xs: full microbatch stream (stage 0
        # consumes it; other stages receive activations via ppermute).
        stage_id = jax.lax.axis_index(stage_axis)
        perm = [(i, i + 1) for i in range(num_stages - 1)]

        def tick(t, carry):
            state, outputs = carry
            # stage 0 injects microbatch t (if still filling)
            mb_idx = jnp.clip(t, 0, num_micro - 1)
            inject = jax.lax.dynamic_index_in_dim(xs, mb_idx, 0,
                                                  keepdims=False)
            cur = jnp.where(stage_id == 0, inject, state)
            y = stage_fn(params, cur)
            # collect at the last stage once the pipe is full
            out_idx = jnp.clip(t - (num_stages - 1), 0, num_micro - 1)
            take = jnp.logical_and(stage_id == num_stages - 1,
                                   t >= num_stages - 1)
            outputs = jax.lax.cond(
                take,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, out_idx, 0),
                lambda o: o, outputs)
            # ship activations downstream
            state = jax.lax.ppermute(y, stage_axis, perm)
            return (state, outputs)

        state0 = jnp.zeros_like(xs[0])
        out0 = jnp.zeros_like(xs)
        _, outputs = jax.lax.fori_loop(0, ticks, tick, (state0, out0))
        # only the last stage ever wrote into `outputs` (zeros elsewhere):
        # a psum replicates the result to every stage
        return jax.lax.psum(outputs, stage_axis)

    fn = jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P(),
        check_vma=False)
    return fn(stage_params, x_micro)


def stack_stages(unit_params, num_stages: int):
    """Regroup a (units, ...) layer-scan param stack into (stages,
    units/stages, ...) for pipeline placement."""

    def regroup(leaf):
        u = leaf.shape[0]
        assert u % num_stages == 0, f"{u} units across {num_stages} stages"
        return leaf.reshape(num_stages, u // num_stages, *leaf.shape[1:])

    return jax.tree.map(regroup, unit_params)
