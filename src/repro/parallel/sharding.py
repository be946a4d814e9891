"""Logical-axis sharding: one rule table maps model axes onto mesh axes.

Model code never names mesh axes; it annotates activations/params with
*logical* axes ("batch", "heads", "mlp", "experts", ...) and the active
rule set resolves them onto the ("pod", "data", "model") mesh.  Rules are
swappable per experiment.

Robustness detail: a logical rule is silently dropped for a given tensor
dimension when the dimension size is not divisible by the mesh-axis size
(e.g. 8 KV heads on a 16-way model axis — the standard GQA replication
fallback), so one rule table serves all 10 architectures.
"""

from __future__ import annotations

import contextlib
import contextvars
import math
from typing import Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axes (a tuple means "shard over both, in order")
DEFAULT_RULES: dict[str, tuple[str, ...] | str | None] = {
    "batch":        ("pod", "data"),   # data parallel
    "seq":          None,              # sequence kept whole by default
    "seq_shard":    "data",            # SP: long-context activations
    "embed":        None,
    "q_features":   "model",           # heads × head_dim, flattened
    "kv_features":  "model",
    "heads":        "model",
    "kv_heads":     "model",
    "head_dim":     None,
    "mlp":          "model",           # TP: FFN hidden
    "vocab":        "model",           # TP: embedding/logits
    "experts":      "model",           # EP
    "capacity":     None,
    "kv_lora":      None,
    "inner":        "model",           # SSM d_inner
    "state":        None,
    "conv":         None,
    "layers":       None,
    "fsdp":         "data",            # parameter sharding (ZeRO-3 style)
    "ssm_heads":    "model",
    # decode caches (serve_step): batch over DP, heads/head_dim over TP;
    # long-context batch-1 cells override cache_seq -> ("data",)
    "cache_batch":  ("pod", "data"),
    "cache_seq":    None,
    "cache_kv_heads": "model",
    "cache_head_dim": "model",
    # paged KV pool (serve.paging): the page axis is replicated by default
    # so every shard can gather any slot's pages locally; override to
    # "data" to spread pool HBM across the data axis (GSPMD handles the
    # cross-shard gather).  Heads reuse cache_kv_heads -> "model" with the
    # same GQA non-divisible fallback as dense caches.
    "cache_pages":  None,
}


class ShardingCtx:
    def __init__(self, mesh: Mesh, rules: dict | None = None,
                 fsdp_params: bool = True):
        self.mesh = mesh
        self.rules = dict(DEFAULT_RULES)
        if rules:
            self.rules.update(rules)
        self.fsdp_params = fsdp_params

    def mesh_axes(self, logical: str | None) -> tuple[str, ...]:
        if logical is None:
            return ()
        r = self.rules.get(logical)
        if r is None:
            return ()
        axes = (r,) if isinstance(r, str) else tuple(r)
        # a rule may name axes the current mesh doesn't have (single-pod
        # meshes have no "pod"): drop them
        return tuple(a for a in axes if a in self.mesh.shape)

    def axis_size(self, axes: Sequence[str]) -> int:
        return math.prod(self.mesh.shape[a] for a in axes)

    def spec(self, logical_axes: Sequence[str | None],
             dims: Sequence[int] | None = None) -> P:
        """Resolve logical axes to a PartitionSpec, dropping indivisible or
        already-used mesh axes."""
        used: set[str] = set()
        parts = []
        for i, name in enumerate(logical_axes):
            axes = tuple(a for a in self.mesh_axes(name) if a not in used)
            if dims is not None and axes:
                if dims[i] % self.axis_size(axes) != 0:
                    # try a prefix that divides (e.g. ("pod","data") -> ("pod",))
                    while axes and dims[i] % self.axis_size(axes) != 0:
                        axes = axes[:-1]
            used.update(axes)
            parts.append(axes if len(axes) != 1 else axes[0])
        return P(*[p if p != () else None for p in parts])

    def named(self, logical_axes: Sequence[str | None],
              dims: Sequence[int] | None = None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(logical_axes, dims))


_CTX: contextvars.ContextVar[ShardingCtx | None] = contextvars.ContextVar(
    "sharding_ctx", default=None)


def current() -> ShardingCtx | None:
    return _CTX.get()


@contextlib.contextmanager
def use(ctx: ShardingCtx | None):
    token = _CTX.set(ctx)
    try:
        yield ctx
    finally:
        _CTX.reset(token)


def constrain(x: jax.Array, *logical_axes: str | None) -> jax.Array:
    """with_sharding_constraint by logical names; no-op outside a ctx."""
    ctx = _CTX.get()
    if ctx is None:
        return x
    if len(logical_axes) != x.ndim:
        raise ValueError(f"{len(logical_axes)} axes for rank-{x.ndim} tensor")
    return jax.lax.with_sharding_constraint(
        x, ctx.named(logical_axes, x.shape))


# -- parameter logical axes --------------------------------------------------
# Parameters are annotated at init with `logical_axes` metadata (a parallel
# pytree of tuples).  `param_shardings` resolves them, optionally adding
# FSDP sharding of the largest divisible unsharded dimension.


def param_shardings(logical_tree, shapes_tree, ctx: ShardingCtx):
    def one(axes, shape):
        spec = list(ctx.spec(axes, shape.shape))
        while len(spec) < len(shape.shape):
            spec.append(None)
        if ctx.fsdp_params:
            fsdp_axes = ctx.mesh_axes("fsdp")
            used = {a for s in spec for a in ((s,) if isinstance(s, str)
                                              else (s or ()))}
            fsdp_axes = tuple(a for a in fsdp_axes if a not in used)
            if fsdp_axes:
                size = ctx.axis_size(fsdp_axes)
                # shard the largest free dimension divisible by the fsdp axes
                cand = sorted(
                    (i for i, s in enumerate(spec)
                     if s in (None, ()) and shape.shape[i] % size == 0),
                    key=lambda i: -shape.shape[i])
                if cand:
                    spec[cand[0]] = (fsdp_axes if len(fsdp_axes) > 1
                                     else fsdp_axes[0])
        return NamedSharding(ctx.mesh, P(*spec))

    return jax.tree.map(one, logical_tree, shapes_tree,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            isinstance(e, (str, type(None))) for e in x))
