"""Building blocks shared by all 10 architectures (pure JAX).

Every apply-function is cache-aware: ``cache=None`` is training/prefill
(full-sequence), a ``(k, v, ...)`` cache plus ``cache_index`` is one decode
step against a preallocated ring of ``S_max`` slots — this is what
``serve_step`` lowers for the decode_32k / long_500k dry-run cells.

Parameter logical axes are registered in ``PARAM_AXES`` (resolved by
``repro.parallel.sharding``); activations carry explicit ``constrain``
annotations so pjit propagates the intended DP/TP/EP/SP layout.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.kernels import ops as kops
from repro.models.config import ModelConfig
from repro.parallel import sharding
from repro.parallel.sharding import constrain

# logical axes by parameter name (stacked layer axis prepended at stack time)
PARAM_AXES: dict[str, tuple[str | None, ...]] = {
    "embed":        ("vocab", "embed"),
    "head":         ("embed", "vocab"),
    "final_norm":   ("embed",),
    "frontend_w1":  (None, "embed"),
    "frontend_w2":  ("embed", "embed"),
    "frontend_b":   ("embed",),
    # attention
    "attn_norm":    ("embed",),
    "wq":           ("embed", "q_features"),
    "wk":           ("embed", "kv_features"),
    "wv":           ("embed", "kv_features"),
    "wo":           ("q_features", "embed"),
    "bq":           ("q_features",),
    "bk":           ("kv_features",),
    "bv":           ("kv_features",),
    "bo":           ("embed",),
    # MLA
    "w_dq":         ("embed", None),
    "w_dkv":        ("embed", "kv_lora"),
    "kv_norm":      ("kv_lora",),
    "w_uk":         ("kv_lora", "q_features"),
    "w_uv":         ("kv_lora", "q_features"),
    # FFN
    "ffn_norm":     ("embed",),
    "w_gate":       ("embed", "mlp"),
    "w_up":         ("embed", "mlp"),
    "w_down":       ("mlp", "embed"),
    "b_gate":       ("mlp",),
    "b_up":         ("mlp",),
    "b_down":       ("embed",),
    # MoE
    "router":       ("embed", "experts"),
    "moe_gate":     ("experts", "embed", "mlp"),
    "moe_up":       ("experts", "embed", "mlp"),
    "moe_down":     ("experts", "mlp", "embed"),
    "shared_gate":  ("embed", "mlp"),
    "shared_up":    ("embed", "mlp"),
    "shared_down":  ("mlp", "embed"),
    # SSM (mamba2)
    "ssm_norm":     ("embed",),
    "in_proj":      ("embed", "inner"),
    "conv_w":       ("conv", "inner"),
    "conv_b":       ("inner",),
    "A_log":        (None,),
    "ssm_D":        (None,),
    "dt_bias":      (None,),
    "gate_norm":    ("inner",),
    "out_proj":     ("inner", "embed"),
}


def _init(key, shape, scale_dim, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            * (scale_dim ** -0.5)).astype(dtype)


def scoped(name: str):
    """Trace the decorated function inside ``jax.named_scope(name)``, so
    its ops carry ``name`` in their compiled ``op_name`` metadata.
    Metadata only: the compiled program is the same.  The scope is looked
    up at call time, so a test can trace without it."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kw):
            with jax.named_scope(name):
                return fn(*args, **kw)
        return inner
    return wrap


# ---------------------------------------------------------------------------
# norms / rotary
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def rotary(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D) with llama-style half rotation; positions: (..., S)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA)
# ---------------------------------------------------------------------------



def _decode_valid(t: int, cache_index) -> jax.Array:
    """(B,t) or (1,t) valid-slot mask; supports per-slot vector indices."""
    ar = jnp.arange(t)[None, :]
    if jnp.ndim(cache_index) == 1:
        return ar <= cache_index[:, None]
    return ar <= cache_index


# -- paged KV cache (repro.serve.paging) -------------------------------------


def _paged_scatter_impl(pages: jax.Array, layer: jax.Array,
                        page_table: jax.Array, positions: jax.Array,
                        vals: jax.Array) -> jax.Array:
    pl = pages.shape[2]
    phys = jnp.take_along_axis(page_table, positions // pl, axis=1)
    return pages.at[layer, phys, positions % pl].set(vals.astype(pages.dtype))


def _paged_gather_impl(pages: jax.Array, layer: jax.Array,
                       page_table: jax.Array) -> jax.Array:
    b, p = page_table.shape
    g = pages[layer, page_table]
    return g.reshape(b, p * pages.shape[2], *pages.shape[3:])


def _paged_shard_axes(pages: jax.Array):
    """(ctx, heads_mesh_axes) when the shard_map fast path applies to this
    stacked pool leaf — an active sharding ctx whose rules put the
    KV-heads dim on present mesh axes (divisibly; the GQA fallback drops
    it otherwise) while layers, pages and head_dim stay whole.  None ->
    plain impl: unsharded engines, MLA's compressed leaves, and
    pages-on-"data" layouts (GSPMD handles the cross-shard gather there)."""
    ctx = sharding.current()
    if ctx is None or pages.ndim != 5:
        return None
    spec = tuple(ctx.spec(("layers", "cache_pages", None, "cache_kv_heads",
                           "cache_head_dim"), pages.shape))
    layers_ax, pages_ax, _, heads_ax, hd_ax = spec
    if not heads_ax or layers_ax or pages_ax or hd_ax:
        return None
    return ctx, heads_ax


@scoped("kv_write")
def _paged_scatter(pages: jax.Array, layer: jax.Array,
                   page_table: jax.Array, positions: jax.Array,
                   vals: jax.Array) -> jax.Array:
    """Write per-token values into one layer's rows of the shared pool.

    pages: (layers, num_pages, page_len, ...), every layer's pool stacked;
    layer: the () index of the layer written; page_table: (B, P) physical
    page of each logical page; positions: (B, S) absolute token positions;
    vals: (B, S, ...).  Inactive slots point at the scratch page (0), so
    their garbage writes can never land in a live request's pages.  The
    scatter is indexed by ``layer`` itself, so no layer's pool is sliced
    out and written back: the stacked leaf updates in place.

    Under a serving mesh the heads-sharded pool updates per shard via
    ``shard_map``: each shard scatters only its own heads slice (no
    collectives, no pool copy — with the engine's donated cache operand
    the update is in-place on every shard)."""
    sharded = _paged_shard_axes(pages)
    if sharded is None:
        return _paged_scatter_impl(pages, layer, page_table, positions, vals)
    ctx, ax = sharded
    return jax.shard_map(
        _paged_scatter_impl, mesh=ctx.mesh,
        in_specs=(P(None, None, None, ax, None), P(), P(None, None),
                  P(None, None), P(None, None, ax, None)),
        out_specs=P(None, None, None, ax, None))(pages, layer, page_table,
                                                 positions, vals)


@scoped("kv_gather")
def _paged_gather(pages: jax.Array, layer: jax.Array,
                  page_table: jax.Array) -> jax.Array:
    """Gather each slot's pages of one layer into a (B, P*page_len, ...)
    view, read straight from the stacked pool (see :func:`_paged_scatter`).

    The sharded path gathers per shard (each shard reads its own heads
    slice at its own partition's bandwidth — the per-partition pricing
    ``choose_page_len(shards=...)`` models), then constrains the result
    back to replicated: one all-gather of data only, so every downstream
    matmul sees width-invariant operands and token streams stay
    bit-identical across mesh widths (the oracle contract; a reassociated
    psum anywhere downstream would break it)."""
    sharded = _paged_shard_axes(pages)
    if sharded is not None:
        ctx, ax = sharded
        g = jax.shard_map(
            _paged_gather_impl, mesh=ctx.mesh,
            in_specs=(P(None, None, None, ax, None), P(), P(None, None)),
            out_specs=P(None, None, ax, None))(pages, layer, page_table)
    else:
        ctx = sharding.current()
        g = _paged_gather_impl(pages, layer, page_table)
    if ctx is not None:
        g = jax.lax.with_sharding_constraint(
            g, NamedSharding(ctx.mesh, P()))
    return g


def _paged_valid(t: int, positions: jax.Array) -> jax.Array:
    """(B, S, t) causal mask against absolute per-token positions."""
    return jnp.arange(t)[None, None, :] <= positions[:, :, None]


def _paged_decode_kernel_applies(q: jax.Array) -> bool:
    """Whether paged attention for ``q`` (B, S, H, D) runs as the paged
    decode kernel: one query token per row, no sharding context (the
    kernel reads whole pages of one device's pool; under a serving mesh
    the pool is sharded), lane-wide heads (D a multiple of 128), and a TPU
    to compile it for.  Everything else (prefill chunks, serving meshes,
    narrow test models, the CPU) gathers the table and runs
    :func:`_sdpa`."""
    return (q.shape[1] == 1 and sharding.current() is None
            and q.shape[-1] % 128 == 0 and jax.default_backend() == "tpu")


@scoped("attention")
def _paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                            v_pages: jax.Array, layer: jax.Array,
                            page_table: jax.Array,
                            positions: jax.Array) -> jax.Array:
    """q: (B, 1, H, D) at ``positions`` (B, 1), whose K/V are already in
    the pool: each row attends to its first ``position + 1`` tokens, read
    page by page from the stacked pool (``kernels.paged_attention``)."""
    o = kops.paged_decode_attention(q[:, 0], k_pages, v_pages, layer,
                                    page_table, positions[:, 0] + 1)
    return o[:, None]


def init_attention(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 5)
    d, hq, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pd = cfg.parameter_dtype
    out = {
        "attn_norm": jnp.ones((d,), pd),
        "wq": _init(ks[0], (d, hq * hd), d, pd),
        "wk": _init(ks[1], (d, hkv * hd), d, pd),
        "wv": _init(ks[2], (d, hkv * hd), d, pd),
        "wo": _init(ks[3], (hq * hd, d), hq * hd, pd),
    }
    if cfg.attention_bias:
        kb = jax.random.split(ks[4], 4)
        out.update(bq=_init(kb[0], (hq * hd,), d, pd),
                   bk=_init(kb[1], (hkv * hd,), d, pd),
                   bv=_init(kb[2], (hkv * hd,), d, pd),
                   bo=_init(kb[3], (d,), hq * hd, pd))
    return out


@scoped("attn_proj")
def _qkv_proj(p: dict, xn: jax.Array, cfg: ModelConfig):
    """q (B,S,H,D), k and v (B,S,Hkv,D): projections of the normed input,
    each with its bias under ``attention_bias`` (added before rotary, as
    llama does)."""
    b, s, _ = xn.shape

    def proj(w, bias, heads):
        y = xn @ p[w]
        if cfg.attention_bias:
            y = y + p[bias]
        return y.reshape(b, s, heads, cfg.head_dim)

    return (proj("wq", "bq", cfg.num_heads),
            proj("wk", "bk", cfg.num_kv_heads),
            proj("wv", "bv", cfg.num_kv_heads))


@scoped("attn_proj")
def _out_proj(p: dict, o: jax.Array, cfg: ModelConfig) -> jax.Array:
    y = o @ p["wo"]
    return y + p["bo"] if cfg.attention_bias else y


@scoped("attention")
def _sdpa(q, k, v, cfg: ModelConfig, *, causal: bool,
          kv_len_mask: jax.Array | None = None) -> jax.Array:
    """q: (B,S,H,D); k/v: (B,T,Hkv,D).  kv_len_mask: (B,T) valid-slot mask
    (decode against a preallocated cache) or (B,S,T) per-query positional
    mask (paged chunked prefill)."""
    b, s, h, dh = q.shape
    t, hkv = k.shape[1], k.shape[2]
    if cfg.attention_impl == "flash" and kv_len_mask is None and s == t:
        qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, dh)
        kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, t, dh)
        vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, t, dh)
        o = kops.flash_attention(qf, kf, vf, num_q_heads=h, num_kv_heads=hkv,
                                 causal=causal)
        return o.reshape(b, h, s, dh).transpose(0, 2, 1, 3)
    if (cfg.attention_impl == "chunked" and s > cfg.attention_chunk
            and s % cfg.attention_chunk == 0
            and (kv_len_mask is None or kv_len_mask.ndim == 2)):
        return _sdpa_chunked(q, k, v, cfg, causal=causal,
                             kv_len_mask=kv_len_mask)
    group = h // hkv
    qg = q.reshape(b, s, hkv, group, dh)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * (dh ** -0.5)
    if causal and s == t:
        mask = jnp.tril(jnp.ones((s, t), bool))
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    if kv_len_mask is not None:
        m = (kv_len_mask[:, None, None, None, :] if kv_len_mask.ndim == 2
             else kv_len_mask[:, None, None, :, :])
        scores = jnp.where(m, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v.astype(jnp.float32))
    return o.reshape(b, s, h, v.shape[-1]).astype(q.dtype)


def _sdpa_chunked(q, k, v, cfg: ModelConfig, *, causal: bool,
                  kv_len_mask: jax.Array | None = None) -> jax.Array:
    """Pure-XLA flash-style attention: scan over q blocks so the S×S score
    matrix never materializes — the dry-run-safe impl for 32K/500K cells
    (the Pallas kernel is the on-TPU equivalent; same math, same FLOPs)."""
    b, s, h, dh = q.shape
    t, hkv = k.shape[1], k.shape[2]
    group = h // hkv
    bq = cfg.attention_chunk
    nq = s // bq
    qb = (q.reshape(b, nq, bq, hkv, group, dh)
          .transpose(1, 0, 2, 3, 4, 5))                       # (nq,B,bq,K,G,D)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    def block(carry, inp):
        qi, i = inp
        scores = jnp.einsum("bskgd,btkd->bkgst", qi.astype(jnp.float32),
                            kf) * (dh ** -0.5)
        if causal:
            rows = i * bq + jnp.arange(bq)
            mask = rows[:, None] >= jnp.arange(t)[None, :]
            scores = jnp.where(mask[None, None, None], scores, -1e30)
        if kv_len_mask is not None:
            scores = jnp.where(kv_len_mask[:, None, None, None, :],
                               scores, -1e30)
        p = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bkgst,btkd->bskgd", p, vf)
        return carry, o.reshape(b, bq, h, v.shape[-1])

    _, ob = jax.lax.scan(block, 0, (qb, jnp.arange(nq)))
    return (ob.transpose(1, 0, 2, 3, 4)
            .reshape(b, s, h, v.shape[-1]).astype(q.dtype))


def apply_attention(p: dict, x: jax.Array, cfg: ModelConfig, *,
                    positions: jax.Array,
                    cache: dict | None = None,
                    cache_index: jax.Array | None = None,
                    page_table: jax.Array | None = None,
                    layer: jax.Array | None = None
                    ) -> tuple[jax.Array, dict | None]:
    b, s, d = x.shape
    hq, hd = cfg.num_heads, cfg.head_dim
    xn = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    q, k, v = _qkv_proj(p, xn, cfg)
    q = rotary(q, positions, cfg.rope_theta)
    k = rotary(k, positions, cfg.rope_theta)
    q = constrain(q, "batch", "seq", "heads", "head_dim")
    k = constrain(k, "batch", "seq", "kv_heads", "head_dim")

    new_cache = None
    if cache is None:
        causal = cfg.causal and not cfg.is_encoder
        o = _sdpa(q, k, v, cfg, causal=causal)
        new_cache = {"k": k, "v": v}
    elif page_table is not None:
        # paged cache: scatter this step's K/V into this layer's rows of
        # the stacked page pool, then attend over each slot's pages: a
        # one-token decode reads only its live pages in the paged kernel
        # where that applies; otherwise (chunked prefill, s=chunk, among
        # others) every slot's pages are gathered back and masked by
        # absolute position.
        ck = _paged_scatter(cache["k"], layer, page_table, positions, k)
        cv = _paged_scatter(cache["v"], layer, page_table, positions, v)
        if _paged_decode_kernel_applies(q):
            o = _paged_decode_attention(q, ck, cv, layer, page_table,
                                        positions)
        else:
            kg = _paged_gather(ck, layer, page_table)
            vg = _paged_gather(cv, layer, page_table)
            o = _sdpa(q, kg, vg, cfg, causal=False,
                      kv_len_mask=_paged_valid(kg.shape[1], positions))
        new_cache = {"k": ck, "v": cv}
    elif cache_index is not None and jnp.ndim(cache_index) == 1:
        # continuous batching: per-slot cache positions (B,)
        b_idx = jnp.arange(b)
        ck = cache["k"].at[b_idx, cache_index].set(k[:, 0])
        cv = cache["v"].at[b_idx, cache_index].set(v[:, 0])
        t = ck.shape[1]
        valid = jnp.arange(t)[None, :] <= cache_index[:, None]
        o = _sdpa(q, ck, cv, cfg, causal=False, kv_len_mask=valid)
        new_cache = {"k": ck, "v": cv}
    elif cache["k"].dtype == jnp.int8:
        # int8-quantized cache (per token×head symmetric scales): halves the
        # decode HBM traffic — the memory-hierarchy optimization of §Perf
        def quant(x):
            s = jnp.maximum(jnp.abs(x).max(axis=-1), 1e-6) / 127.0
            qx = jnp.clip(jnp.round(x / s[..., None]), -127, 127
                          ).astype(jnp.int8)
            return qx, s.astype(jnp.float32)
        kq, ks = quant(k.astype(jnp.float32))
        vq, vs = quant(v.astype(jnp.float32))
        ck = jax.lax.dynamic_update_slice(cache["k"], kq, (0, cache_index, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], vq, (0, cache_index, 0, 0))
        cks = jax.lax.dynamic_update_slice(cache["k_scale"], ks,
                                           (0, cache_index, 0))
        cvs = jax.lax.dynamic_update_slice(cache["v_scale"], vs,
                                           (0, cache_index, 0))
        kf = (ck.astype(jnp.float32) * cks[..., None]).astype(x.dtype)
        vf = (cv.astype(jnp.float32) * cvs[..., None]).astype(x.dtype)
        t = ck.shape[1]
        valid = _decode_valid(t, cache_index)
        o = _sdpa(q, kf, vf, cfg, causal=False, kv_len_mask=valid)
        new_cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}
    else:
        # one-token decode against a preallocated S_max ring
        ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, cache_index, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, cache_index, 0, 0))
        t = ck.shape[1]
        valid = _decode_valid(t, cache_index)
        o = _sdpa(q, ck, cv, cfg, causal=False, kv_len_mask=valid)
        new_cache = {"k": ck, "v": cv}
    o = o.reshape(b, s, hq * hd)
    o = constrain(o, "batch", "seq", "q_features")
    return x + _out_proj(p, o, cfg).astype(x.dtype), new_cache


# ---------------------------------------------------------------------------
# MLA (deepseek-v2): low-rank KV with decoupled RoPE; cache = (c_kv, k_rope)
# ---------------------------------------------------------------------------


def init_mla(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 6)
    d, h = cfg.d_model, cfg.num_heads
    nd, rd, vd, r = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    pd = cfg.parameter_dtype
    return {
        "attn_norm": jnp.ones((d,), pd),
        "wq": _init(ks[0], (d, h * (nd + rd)), d, pd),
        "w_dkv": _init(ks[1], (d, r + rd), d, pd),
        "kv_norm": jnp.ones((r,), pd),
        "w_uk": _init(ks[2], (r, h * nd), r, pd),
        "w_uv": _init(ks[3], (r, h * vd), r, pd),
        "wo": _init(ks[4], (h * vd, d), h * vd, pd),
    }


def apply_mla(p: dict, x: jax.Array, cfg: ModelConfig, *,
              positions: jax.Array, cache: dict | None = None,
              cache_index: jax.Array | None = None,
              page_table: jax.Array | None = None,
              layer: jax.Array | None = None
              ) -> tuple[jax.Array, dict | None]:
    b, s, d = x.shape
    h = cfg.num_heads
    nd, rd, vd, r = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    xn = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    q = (xn @ p["wq"]).reshape(b, s, h, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = rotary(q_rope, positions, cfg.rope_theta)

    dkv = xn @ p["w_dkv"]                       # (b, s, r + rd)
    c_kv = rms_norm(dkv[..., :r], p["kv_norm"], cfg.norm_eps)
    k_rope = rotary(dkv[..., r:][:, :, None, :], positions,
                    cfg.rope_theta)[:, :, 0]    # (b, s, rd), shared per head

    vector_idx = cache_index is not None and jnp.ndim(cache_index) == 1
    paged = cache is not None and page_table is not None
    valid = None
    if paged:
        # compressed cache lives in the shared page pool (like k/v above)
        ckv_pages = _paged_scatter(cache["c_kv"], layer, page_table,
                                   positions, c_kv)
        kr_pages = _paged_scatter(cache["k_rope"], layer, page_table,
                                  positions, k_rope)
        new_cache = {"c_kv": ckv_pages, "k_rope": kr_pages}
        c_kv = _paged_gather(ckv_pages, layer, page_table)
        k_rope = _paged_gather(kr_pages, layer, page_table)
        valid = _paged_valid(c_kv.shape[1], positions)
    elif cache is not None:
        if vector_idx:      # continuous batching: per-slot positions
            b_idx = jnp.arange(b)
            c_kv = cache["c_kv"].at[b_idx, cache_index].set(c_kv[:, 0])
            k_rope = cache["k_rope"].at[b_idx, cache_index].set(k_rope[:, 0])
        else:
            c_kv = jax.lax.dynamic_update_slice(cache["c_kv"], c_kv,
                                                (0, cache_index, 0))
            k_rope = jax.lax.dynamic_update_slice(cache["k_rope"], k_rope,
                                                  (0, cache_index, 0))
    if not paged:
        new_cache = {"c_kv": c_kv, "k_rope": k_rope}
        if cache is not None:
            valid = _decode_valid(c_kv.shape[1], cache_index)
    t = c_kv.shape[1]

    if cache is not None and cfg.mla_absorbed:
        # Absorbed-matmul decode: fold W_uk into the query and W_uv into the
        # output so attention runs against the COMPRESSED cache directly —
        # kills the per-step O(T) re-expansion (exact same math):
        #   qᵀ(c W_uk) = (q W_ukᵀ)ᵀ c      p (c W_uv) = (p c) W_uv
        w_uk = p["w_uk"].reshape(r, h, nd)
        w_uv = p["w_uv"].reshape(r, h, vd)
        q_abs = jnp.einsum("bshd,rhd->bshr", q_nope.astype(jnp.float32),
                           w_uk.astype(jnp.float32))
        scale = (nd + rd) ** -0.5
        scores = (jnp.einsum("bshr,btr->bhst", q_abs,
                             c_kv.astype(jnp.float32)) +
                  jnp.einsum("bshd,btd->bhst", q_rope.astype(jnp.float32),
                             k_rope.astype(jnp.float32))) * scale
        vm = (valid[:, None, None, :] if valid.ndim == 2
              else valid[:, None])          # (B,1,S,T) per-query paged mask
        scores = jnp.where(vm, scores, -1e30)
        pr = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bhst,btr->bshr", pr, c_kv.astype(jnp.float32))
        o = jnp.einsum("bshr,rhd->bshd", ctx, w_uv.astype(jnp.float32))
        o = o.reshape(b, s, h * vd).astype(x.dtype)
        return x + (o @ p["wo"]).astype(x.dtype), new_cache

    # Expand the compressed cache to per-head K/V and run standard SDPA
    # (naive MLA; the absorbed-matmul decode variant is the §Perf item).
    k_nope = (c_kv @ p["w_uk"]).reshape(b, t, h, nd)
    vfull = (c_kv @ p["w_uv"]).reshape(b, t, h, vd)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, t, h, rd))],
        axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    if cache is None:
        o = _sdpa(q_full, k_full, vfull, cfg, causal=True)
    else:
        o = _sdpa(q_full, k_full, vfull, cfg, causal=False, kv_len_mask=valid)
    o = o.reshape(b, s, h * vd)
    return x + (o @ p["wo"]).astype(x.dtype), new_cache


# ---------------------------------------------------------------------------
# dense FFN (SwiGLU)
# ---------------------------------------------------------------------------


def init_ffn(key, cfg: ModelConfig, d_ff: int | None = None,
             prefix: str = "") -> dict:
    ks = jax.random.split(key, 3)
    d, f = cfg.d_model, d_ff or cfg.d_ff
    pd = cfg.parameter_dtype
    n = lambda s: (prefix + s) if prefix else s
    out = {
        n("w_gate"): _init(ks[0], (d, f), d, pd),
        n("w_up"): _init(ks[1], (d, f), d, pd),
        n("w_down"): _init(ks[2], (f, d), f, pd),
    }
    if not prefix:
        out["ffn_norm"] = jnp.ones((d,), pd)
        if cfg.mlp_bias:
            kb = jax.random.split(jax.random.fold_in(key, 1), 3)
            out.update(b_gate=_init(kb[0], (f,), d, pd),
                       b_up=_init(kb[1], (f,), d, pd),
                       b_down=_init(kb[2], (d,), f, pd))
    return out


def apply_ffn(p: dict, x: jax.Array, cfg: ModelConfig,
              prefix: str = "") -> jax.Array:
    n = lambda s: (prefix + s) if prefix else s
    bias = cfg.mlp_bias and not prefix      # shared experts have none
    gate = x @ p[n("w_gate")]
    gate = jax.nn.silu(gate + p["b_gate"] if bias else gate)
    up = x @ p[n("w_up")]
    h = gate * (up + p["b_up"] if bias else up)
    h = (constrain(h, "batch", "seq", "mlp") if h.ndim == 3
         else constrain(h, "batch", "mlp"))   # shared-expert path: (T, d)
    y = h @ p[n("w_down")]
    return (y + p["b_down"] if bias else y).astype(x.dtype)


@scoped("mlp")
def apply_dense_block(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    xn = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
    return x + apply_ffn(p, xn, cfg)


# ---------------------------------------------------------------------------
# MoE: top-k token choice, capacity buffers, EP-sharded expert matmuls
# ---------------------------------------------------------------------------


def init_moe(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 5)
    d, e, fe = cfg.d_model, cfg.num_experts, cfg.d_ff_expert
    pd = cfg.parameter_dtype
    out = {
        "ffn_norm": jnp.ones((d,), pd),
        "router": _init(ks[0], (d, e), d, jnp.float32),
        "moe_gate": _init(ks[1], (e, d, fe), d, pd),
        "moe_up": _init(ks[2], (e, d, fe), d, pd),
        "moe_down": _init(ks[3], (e, fe, d), fe, pd),
    }
    if cfg.num_shared_experts:
        shared = init_ffn(ks[4], cfg, d_ff=cfg.num_shared_experts * fe,
                          prefix="shared_")
        out.update(shared)
    return out


def moe_capacity(tokens: int, cfg: ModelConfig) -> int:
    cap = math.ceil(tokens * cfg.top_k / cfg.num_experts * cfg.capacity_factor)
    return max(8, -(-cap // 8) * 8)   # round up to 8 for tiling


def apply_moe_block(p: dict, x: jax.Array, cfg: ModelConfig
                    ) -> tuple[jax.Array, jax.Array]:
    """Returns (residual_out, router_aux_loss)."""
    b, s, d = x.shape
    xn = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
    t = b * s
    xt = xn.reshape(t, d)
    e, k = cfg.num_experts, cfg.top_k

    logits = (xt.astype(jnp.float32) @ p["router"])          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)                   # (T, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # load-balance aux (Switch-style) + router z-loss
    me = probs.mean(axis=0)
    ce = jnp.zeros((e,), jnp.float32).at[top_i.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(me * ce) + cfg.router_z_coef * jnp.mean(
        jax.nn.logsumexp(logits, axis=-1) ** 2)

    # capacity dispatch: rank of each (token, choice) within its expert
    cap = moe_capacity(t, cfg)
    flat_e = top_i.reshape(-1)                               # (T·k,)
    order = jnp.argsort(flat_e, stable=True)
    counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts
    ranks_sorted = jnp.arange(t * k, dtype=jnp.int32) - starts[flat_e[order]]
    slot = jnp.zeros((t * k,), jnp.int32).at[order].set(ranks_sorted)
    keep = slot < cap
    tok = jnp.arange(t * k, dtype=jnp.int32) // k

    buf = jnp.zeros((e, cap, d), xt.dtype)
    buf = buf.at[jnp.where(keep, flat_e, e - 1),
                 jnp.where(keep, slot, cap - 1)].add(
        jnp.where(keep[:, None], xt[tok], 0))
    buf = constrain(buf, "experts", "capacity", "embed")

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["moe_gate"])) * \
        jnp.einsum("ecd,edf->ecf", buf, p["moe_up"])
    h = constrain(h, "experts", "capacity", "mlp")
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["moe_down"])
    out_buf = constrain(out_buf, "experts", "capacity", "embed")

    gathered = out_buf[flat_e, slot]                         # (T·k, d)
    gathered = jnp.where(keep[:, None], gathered, 0)
    y = jnp.zeros((t, d), xt.dtype).at[tok].add(
        gathered * top_p.reshape(-1)[:, None].astype(xt.dtype))

    if cfg.num_shared_experts:
        y = y + apply_ffn(p, xt, cfg, prefix="shared_")
    y = y.reshape(b, s, d)
    y = constrain(y, "batch", "seq", "embed")
    return x + y.astype(x.dtype), aux
