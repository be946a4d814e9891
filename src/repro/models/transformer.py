"""Model assembly: one TransformerLM covering all 10 architectures.

Layers are stacked and driven by ``lax.scan`` so HLO size is O(1) in depth
(88-layer Mistral-Large compiles as one scanned layer).  Hybrids scan over
the repeating *period* (jamba: 8 layers = 7 mamba + 1 attention, unrolled
inside the scan body), so heterogeneous stacks stay scan-compatible.

Three entry points (what the dry-run lowers):
  forward   — training path (full sequence, no cache)
  prefill   — forward + build a KV/SSM cache padded to ``max_len``
  decode    — one-token step against the cache (serve_step)
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import ssm as S
from repro.models.config import ModelConfig
from repro.parallel.sharding import constrain

# ---------------------------------------------------------------------------
# layer-stack spec
# ---------------------------------------------------------------------------


def unit_spec(cfg: ModelConfig) -> list[tuple[str, str]]:
    """(block_kind, ffn_kind) for each layer inside one scan unit."""
    period = cfg.attn_period if cfg.family == "hybrid" else 1
    kinds = cfg.layer_kinds()[:period]
    ffns = cfg.ffn_kinds()[:period]
    return list(zip(kinds, ffns))


def num_units(cfg: ModelConfig) -> int:
    period = len(unit_spec(cfg))
    assert cfg.num_layers % period == 0
    return cfg.num_layers // period


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_block(key, cfg: ModelConfig, kind: str, ffn: str) -> dict:
    k1, k2 = jax.random.split(key)
    if kind == "attn":
        p = L.init_mla(k1, cfg) if cfg.use_mla else L.init_attention(k1, cfg)
    else:
        p = S.init_ssm(k1, cfg)
    if kind == "ssm":
        return p                      # mamba block has no separate FFN
    if ffn == "moe":
        p.update(L.init_moe(k2, cfg))
    else:
        p.update(L.init_ffn(k2, cfg))
        p["ffn_norm"] = jnp.ones((cfg.d_model,), cfg.parameter_dtype)
    return p


def _init_unit(key, cfg: ModelConfig) -> dict:
    spec = unit_spec(cfg)
    ks = jax.random.split(key, len(spec))
    out = {}
    for i, ((kind, ffn), k) in enumerate(zip(spec, ks)):
        out[f"b{i}"] = _init_block(k, cfg, kind, ffn)
        # hybrid: ssm layers that carry an FFN (jamba interleaves MLP/MoE
        # after every block)
        if cfg.family == "hybrid" and kind == "ssm":
            k2 = jax.random.fold_in(k, 1)
            ff = (L.init_moe(k2, cfg) if ffn == "moe" else
                  {**L.init_ffn(k2, cfg),
                   "ffn_norm": jnp.ones((cfg.d_model,), cfg.parameter_dtype)})
            out[f"b{i}"].update(ff)
    return out


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    ke, kl, kh, kf = jax.random.split(key, 4)
    pd = cfg.parameter_dtype
    params: dict[str, Any] = {
        "embed": (jax.random.normal(ke, (cfg.vocab_size, cfg.d_model),
                                    jnp.float32)
                  * cfg.d_model ** -0.5).astype(pd),
        "final_norm": jnp.ones((cfg.d_model,), pd),
        "units": jax.vmap(lambda k: _init_unit(k, cfg))(
            jax.random.split(kl, num_units(cfg))),
    }
    if not cfg.tie_embeddings:
        params["head"] = (jax.random.normal(kh, (cfg.d_model, cfg.vocab_size),
                                            jnp.float32)
                          * cfg.d_model ** -0.5).astype(pd)
    if cfg.frontend is not None:
        params["frontend_w1"] = (jax.random.normal(
            kf, (cfg.frontend_dim, cfg.d_model), jnp.float32)
            * cfg.frontend_dim ** -0.5).astype(pd)
        params["frontend_b"] = jnp.zeros((cfg.d_model,), pd)
        if cfg.frontend == "vision":
            params["frontend_w2"] = (jax.random.normal(
                jax.random.fold_in(kf, 1), (cfg.d_model, cfg.d_model),
                jnp.float32) * cfg.d_model ** -0.5).astype(pd)
    return params


def param_logical_axes(params) -> Any:
    """Mirror pytree of logical-axis tuples (stacked 'layers' axis added
    under units/)."""

    def one(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        axes = L.PARAM_AXES.get(name, tuple([None] * leaf.ndim))
        in_units = any(getattr(p, "key", None) == "units" for p in path)
        if in_units:
            axes = ("layers",) + tuple(axes)
        if len(axes) != leaf.ndim:
            axes = tuple([None] * leaf.ndim)
        return tuple(axes)

    return jax.tree_util.tree_map_with_path(one, params)


# ---------------------------------------------------------------------------
# apply
# ---------------------------------------------------------------------------


def _apply_block(p: dict, x, cfg: ModelConfig, kind: str, ffn: str, *,
                 positions, cache, cache_index, page_table=None,
                 slot_ids=None, seq_lens=None, layer=None):
    aux = jnp.zeros((), jnp.float32)
    if kind == "attn":
        fn = L.apply_mla if cfg.use_mla else L.apply_attention
        x, new_cache = fn(p, x, cfg, positions=positions, cache=cache,
                          cache_index=cache_index, page_table=page_table,
                          layer=layer)
    else:
        x, new_cache = S.apply_ssm(p, x, cfg, cache=cache,
                                   cache_index=cache_index,
                                   slot_ids=slot_ids, seq_lens=seq_lens,
                                   layer=layer)
    has_ffn = kind == "attn" or cfg.family == "hybrid"
    if has_ffn:
        if ffn == "moe":
            x, aux = L.apply_moe_block(p, x, cfg)
        else:
            x = L.apply_dense_block(p, x, cfg)
    return x, new_cache, aux


def _apply_unit(unit_params: dict, x, cfg: ModelConfig, *, positions,
                caches: dict | None, cache_index, page_table=None,
                slot_ids=None, seq_lens=None, layer=None):
    """One scan unit.  ``layer`` is set on the paged path only: ``caches``
    then holds every unit's stacked pool leaves, and each block reads and
    writes its own unit's rows of them."""
    spec = unit_spec(cfg)
    new_caches = {}
    aux_total = jnp.zeros((), jnp.float32)
    for i, (kind, ffn) in enumerate(spec):
        cache_i = caches[f"b{i}"] if caches is not None else None
        x, nc, aux = _apply_block(unit_params[f"b{i}"], x, cfg, kind, ffn,
                                  positions=positions, cache=cache_i,
                                  cache_index=cache_index,
                                  page_table=page_table, slot_ids=slot_ids,
                                  seq_lens=seq_lens, layer=layer)
        new_caches[f"b{i}"] = nc
        aux_total = aux_total + aux
    return x, new_caches, aux_total


def _embed_inputs(params, cfg: ModelConfig, batch: dict):
    """tokens and/or frontend embeddings -> (B, S, d) activations."""
    parts = []
    if cfg.frontend == "audio" and "frames" in batch:
        h = batch["frames"] @ params["frontend_w1"] + params["frontend_b"]
        parts.append(h.astype(cfg.activation_dtype))
    elif cfg.frontend == "vision" and "patches" in batch:
        h = jax.nn.gelu(batch["patches"] @ params["frontend_w1"]
                        + params["frontend_b"])
        parts.append((h @ params["frontend_w2"]).astype(cfg.activation_dtype))
    if "tokens" in batch:
        emb = jnp.take(params["embed"], batch["tokens"], axis=0)
        parts.append(emb.astype(cfg.activation_dtype))
    x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    return constrain(x, "batch", "seq", "embed")


def forward(params: dict, cfg: ModelConfig, batch: dict
            ) -> tuple[jax.Array, jax.Array]:
    """Training path.  Returns (logits, moe_aux_loss)."""
    x = _embed_inputs(params, cfg, batch)
    b, s, _ = x.shape
    positions = jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0)

    def unit_fn(carry, unit_params):
        h, aux = carry
        h, _, aux2 = _apply_unit(unit_params, h, cfg, positions=positions,
                                 caches=None, cache_index=None)
        return (h, aux + aux2), None

    if cfg.remat:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat_policy == "dots" else None)
        fn = jax.checkpoint(unit_fn, policy=policy)
    else:
        fn = unit_fn
    if cfg.scan_layers:
        (x, aux), _ = jax.lax.scan(fn, (x, jnp.zeros((), jnp.float32)),
                                   params["units"])
    else:
        carry = (x, jnp.zeros((), jnp.float32))
        for i in range(num_units(cfg)):
            carry, _ = fn(carry, jax.tree.map(lambda t: t[i], params["units"]))
        x, aux = carry
    x = rms_final(params, cfg, x)
    logits = head_logits(params, cfg, x)
    return logits, aux


def rms_final(params, cfg, x):
    return L.rms_norm(x, params["final_norm"], cfg.norm_eps)


@L.scoped("head")
def head_logits(params, cfg, x):
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = jnp.einsum("bsd,dv->bsv", x.astype(jnp.float32),
                        w.astype(jnp.float32))
    return constrain(logits, "batch", "seq", "vocab")


# -- caches ------------------------------------------------------------------

#: cache leaves whose axis 2 is the sequence axis (attention K/V family);
#: SSM leaves (conv, state) are sequence-length-independent
_SEQ_CACHE_LEAVES = frozenset({"k", "v", "c_kv", "k_rope",
                               "k_scale", "v_scale"})


def _init_block_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int):
    dt = cfg.activation_dtype
    if kind == "attn":
        if cfg.use_mla:
            return {"c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dt),
                    "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dt)}
        hkv, hd = cfg.num_kv_heads, cfg.head_dim
        if cfg.kv_cache_dtype == "int8":
            return {"k": jnp.zeros((batch, max_len, hkv, hd), jnp.int8),
                    "v": jnp.zeros((batch, max_len, hkv, hd), jnp.int8),
                    "k_scale": jnp.zeros((batch, max_len, hkv), jnp.float32),
                    "v_scale": jnp.zeros((batch, max_len, hkv), jnp.float32)}
        return {"k": jnp.zeros((batch, max_len, hkv, hd), dt),
                "v": jnp.zeros((batch, max_len, hkv, hd), dt)}
    return S.init_ssm_cache(cfg, batch, dt)


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    spec = unit_spec(cfg)
    units = num_units(cfg)

    def one_unit(_):
        return {f"b{i}": _init_block_cache(cfg, kind, batch, max_len)
                for i, (kind, _) in enumerate(spec)}

    return jax.vmap(one_unit)(jnp.arange(units))


def _init_block_paged_cache(cfg: ModelConfig, kind: str, num_pages: int,
                            page_len: int, max_slots: int):
    """Attention K/V leaves become a shared (num_pages, page_len, ...) pool;
    SSM leaves stay slot-resident (their state is O(1) per sequence)."""
    dt = cfg.activation_dtype
    if kind == "attn":
        if cfg.kv_cache_dtype == "int8":
            raise NotImplementedError(
                "int8 KV cache is not paged yet; use the dense ServeEngine")
        if cfg.use_mla:
            return {"c_kv": jnp.zeros((num_pages, page_len,
                                       cfg.kv_lora_rank), dt),
                    "k_rope": jnp.zeros((num_pages, page_len,
                                         cfg.qk_rope_dim), dt)}
        hkv, hd = cfg.num_kv_heads, cfg.head_dim
        return {"k": jnp.zeros((num_pages, page_len, hkv, hd), dt),
                "v": jnp.zeros((num_pages, page_len, hkv, hd), dt)}
    return S.init_ssm_cache(cfg, max_slots, dt)


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_len: int,
                     max_slots: int, *, mesh=None, rules=None) -> dict:
    """Paged twin of :func:`init_cache` (same tree structure, paged attn
    leaves).  HBM for attention K/V scales with ``num_pages`` — the pages
    actually in circulation — instead of ``max_slots * max_len``.

    Slot-resident (SSM) leaves get ``max_slots + 1`` rows: row
    ``max_slots`` is a scratch row, the slot-space twin of scratch page 0.
    A decode tick always runs the full batch, so batch rows whose slot is
    empty *or still prefilling* are pointed at the scratch row/page and
    their garbage writes can never touch live state.

    ``mesh`` (a :class:`jax.sharding.Mesh` or a prebuilt
    :class:`~repro.parallel.sharding.ShardingCtx`) lays the pool out with
    :class:`NamedSharding` resolved through ``PAGED_CACHE_AXES`` — KV
    heads on ``"model"``, pages replicated (or on ``"data"`` via
    ``rules``).  The allocator and page tables stay host-side; only the
    dense pool leaves live on the mesh."""
    spec = unit_spec(cfg)
    units = num_units(cfg)

    def one_unit(_):
        return {f"b{i}": _init_block_paged_cache(cfg, kind, num_pages,
                                                 page_len, max_slots + 1)
                for i, (kind, _) in enumerate(spec)}

    cache = jax.vmap(one_unit)(jnp.arange(units))
    if mesh is not None:
        from repro.parallel.sharding import ShardingCtx
        ctx = mesh if isinstance(mesh, ShardingCtx) else ShardingCtx(
            mesh, rules)
        cache = jax.device_put(cache, paged_cache_shardings(cache, ctx))
    return cache


def paged_step(params: dict, cfg: ModelConfig, cache: dict,
               tokens: jax.Array, start: jax.Array, page_tables: jax.Array,
               slot_ids: jax.Array, seq_lens: jax.Array | None = None
               ) -> tuple[jax.Array, dict]:
    """One step against a paged cache: decode (S=1) or a prefill chunk.

    tokens (B,S) at absolute positions ``start[b] + j``; page_tables (B,P)
    maps each slot's logical pages to physical pages (scratch page 0 for
    unallocated/inactive entries); slot_ids (B,) selects the rows of the
    slot-resident (SSM) cache leaves; seq_lens (B,) counts the valid
    tokens of a padded chunk (None = all valid).  Returns logits for every
    chunk position, (B, S, vocab), and the updated cache.

    The whole stacked cache rides the scan's carry: unit ``li`` scatters
    into and gathers from the rows ``[li, ...]`` of each stacked leaf, so
    no unit's pool is ever sliced out of the stack or written back into
    it.  With the cache operand donated the pool updates in place."""
    x = _embed_inputs(params, cfg, {"tokens": tokens})
    b, s, _ = x.shape
    positions = (start[:, None].astype(jnp.int32)
                 + jnp.arange(s, dtype=jnp.int32)[None, :])

    def unit_fn(carry, inp):
        h, pool = carry
        unit_params, li = inp
        h, pool, _ = _apply_unit(unit_params, h, cfg, positions=positions,
                                 caches=pool, cache_index=start,
                                 page_table=page_tables, slot_ids=slot_ids,
                                 seq_lens=seq_lens, layer=li)
        return (h, pool), None

    (x, cache), _ = jax.lax.scan(
        unit_fn, (x, cache),
        (params["units"], jnp.arange(num_units(cfg), dtype=jnp.int32)))
    x = rms_final(params, cfg, x)
    return head_logits(params, cfg, x), cache


def prefill(params: dict, cfg: ModelConfig, batch: dict, *,
            max_len: int | None = None) -> tuple[jax.Array, dict]:
    """Forward over the prompt, returning logits and an S_max-padded cache."""
    x = _embed_inputs(params, cfg, batch)
    b, s, _ = x.shape
    max_len = max_len or s
    positions = jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0)

    def unit_fn(h, unit_params):
        h, caches, _ = _apply_unit(unit_params, h, cfg, positions=positions,
                                   caches=None, cache_index=None)
        return h, caches

    x, caches = jax.lax.scan(unit_fn, x, params["units"])

    # pad the SEQUENCE axis of attention leaves to max_len, selected by
    # name: a shape test (leaf.shape[2] == s) misfires when an SSM leaf's
    # head count happens to equal the prompt length
    def pad_to_max(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name in _SEQ_CACHE_LEAVES and max_len != s:
            pad = [(0, 0)] * leaf.ndim
            pad[2] = (0, max_len - s)          # (units, batch, seq, ...)
            return jnp.pad(leaf, pad)
        return leaf

    caches = jax.tree_util.tree_map_with_path(pad_to_max, caches)
    x = rms_final(params, cfg, x)
    logits = head_logits(params, cfg, x[:, -1:])
    return logits, caches


def decode(params: dict, cfg: ModelConfig, cache: dict, tokens: jax.Array,
           cache_index: jax.Array) -> tuple[jax.Array, dict]:
    """One decode step: tokens (B, 1) at position ``cache_index``.

    ``cache_index`` may be a scalar (uniform position) or a (B,) vector of
    per-slot positions (continuous batching, repro.serve.engine)."""
    x = _embed_inputs(params, cfg, {"tokens": tokens})
    b = x.shape[0]
    if jnp.ndim(cache_index) == 1:
        positions = cache_index[:, None].astype(jnp.int32)
    else:
        positions = jnp.full((b, 1), cache_index, jnp.int32)

    def unit_fn(h, inp):
        unit_params, unit_cache = inp
        h, new_cache, _ = _apply_unit(unit_params, h, cfg,
                                      positions=positions, caches=unit_cache,
                                      cache_index=cache_index)
        return h, new_cache

    x, new_caches = jax.lax.scan(unit_fn, x, (params["units"], cache))
    x = rms_final(params, cfg, x)
    return head_logits(params, cfg, x), new_caches


# -- cache sharding metadata -------------------------------------------------

CACHE_AXES: dict[str, tuple[str | None, ...]] = {
    "k": ("layers", "cache_batch", "cache_seq", "cache_kv_heads",
          "cache_head_dim"),
    "v": ("layers", "cache_batch", "cache_seq", "cache_kv_heads",
          "cache_head_dim"),
    "c_kv": ("layers", "cache_batch", "cache_seq", "kv_lora"),
    "k_rope": ("layers", "cache_batch", "cache_seq", None),
    "k_scale": ("layers", "cache_batch", "cache_seq", "cache_kv_heads"),
    "v_scale": ("layers", "cache_batch", "cache_seq", "cache_kv_heads"),
    "conv": ("layers", "cache_batch", None, "inner"),
    "state": ("layers", "cache_batch", "ssm_heads", None, None),
}


def cache_logical_axes(cache) -> Any:
    """Mirror pytree of logical axes for an ``init_cache`` structure."""

    def one(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        axes = CACHE_AXES.get(name, tuple([None] * leaf.ndim))
        if len(axes) != leaf.ndim:
            axes = tuple([None] * leaf.ndim)
        return tuple(axes)

    return jax.tree_util.tree_map_with_path(one, cache)


#: paged-pool twin of CACHE_AXES: attention leaves are
#: (units, num_pages, page_len, ...) pools — heads ride the same
#: "cache_kv_heads" rule as dense caches (GQA fallback included), pages
#: ride "cache_pages" (replicated by default, "data" by rule override).
#: The page_len axis is the contiguous gather row and is never sharded.
#: Slot-resident SSM leaves are small O(slots) state; they stay
#: replicated so the scratch-row trick needs no cross-shard reasoning.
PAGED_CACHE_AXES: dict[str, tuple[str | None, ...]] = {
    "k": ("layers", "cache_pages", None, "cache_kv_heads",
          "cache_head_dim"),
    "v": ("layers", "cache_pages", None, "cache_kv_heads",
          "cache_head_dim"),
    "c_kv": ("layers", "cache_pages", None, "kv_lora"),
    "k_rope": ("layers", "cache_pages", None, None),
    "conv": ("layers", None, None, None),
    "state": ("layers", None, None, None, None),
}


def paged_cache_logical_axes(cache) -> Any:
    """Mirror pytree of logical axes for an ``init_paged_cache`` tree."""

    def one(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        axes = PAGED_CACHE_AXES.get(name, tuple([None] * leaf.ndim))
        if len(axes) != leaf.ndim:
            axes = tuple([None] * leaf.ndim)
        return tuple(axes)

    return jax.tree_util.tree_map_with_path(one, cache)


def paged_cache_shardings(cache, ctx) -> Any:
    """Mirror pytree of :class:`NamedSharding` for a paged cache, resolved
    through ``ctx``'s rule table (indivisible axes drop per leaf — the
    GQA replication fallback)."""
    axes = paged_cache_logical_axes(cache)
    return jax.tree.map(
        lambda a, leaf: ctx.named(a, leaf.shape), axes, cache,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))


# ---------------------------------------------------------------------------
# accounting
# ---------------------------------------------------------------------------


_param_counts_disk: dict | None = None


def _param_counts_path() -> str:
    from repro.jaxcache import workspace_cache_dir
    return os.path.join(workspace_cache_dir(), "param_counts.json")


@functools.lru_cache(maxsize=None)
def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    # memoized twice: per-process (the cost model calls this for every
    # workload cell of the same arch) and on disk next to the XLA cache
    # (the eval_shape trace costs ~100 ms per arch per process, which
    # dominates cold roofline sweeps).  A pure function of the frozen
    # config, so content-keyed caching is safe.
    if active_only and cfg.is_moe:
        cfg = dataclasses.replace(cfg, num_experts=max(1, cfg.top_k))
    import json
    import math
    global _param_counts_disk
    key = json.dumps(dataclasses.asdict(cfg), sort_keys=True, default=str)
    use_disk = not os.environ.get("REPRO_NO_JAX_CACHE")
    if use_disk and _param_counts_disk is None:
        try:
            with open(_param_counts_path()) as fh:
                _param_counts_disk = json.load(fh)
        except (OSError, ValueError):
            _param_counts_disk = {}
    if use_disk and key in _param_counts_disk:
        return int(_param_counts_disk[key])
    shapes = jax.eval_shape(
        functools.partial(init_params, cfg), jax.random.key(0))
    n = sum(math.prod(l.shape) if l.shape else 1
            for l in jax.tree.leaves(shapes))
    if use_disk:
        _param_counts_disk[key] = n
        try:
            path = _param_counts_path()
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = path + f".{os.getpid()}.tmp"
            with open(tmp, "w") as fh:
                json.dump(_param_counts_disk, fh)
            os.replace(tmp, path)
        except OSError:
            pass
    return n
