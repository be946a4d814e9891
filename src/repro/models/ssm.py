"""Mamba2 / SSD block (state-space duality, arXiv:2405.21060), pure JAX.

Training path: the chunked SSD algorithm — intra-chunk attention-like term
plus an inter-chunk recurrent state carried by ``lax.scan`` — O(S·L) compute
with chunk length L, which is what makes the long_500k cells sub-quadratic.
Decode path: the O(1) per-token recurrence on the (heads, head_dim, state)
SSM state plus a rolling depthwise-conv window.

Layout notes: x/B/C share one input projection and one depthwise conv (as
in the reference implementation); A is scalar-per-head; gated RMSNorm
before the output projection.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import rms_norm
from repro.parallel.sharding import constrain


def _dims(cfg: ModelConfig) -> tuple[int, int, int, int, int]:
    d_in = cfg.d_inner
    h = cfg.ssm_heads
    p = cfg.ssm_head_dim
    g = cfg.ssm_groups
    n = cfg.ssm_state
    return d_in, h, p, g, n


def conv_dim(cfg: ModelConfig) -> int:
    d_in, _, _, g, n = _dims(cfg)
    return d_in + 2 * g * n


def init_ssm(key, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    d_in, h, _, g, n = _dims(cfg)
    pd = cfg.parameter_dtype
    ks = jax.random.split(key, 4)
    proj_out = 2 * d_in + 2 * g * n + h          # z, x, B, C, dt
    return {
        "ssm_norm": jnp.ones((d,), pd),
        "in_proj": (jax.random.normal(ks[0], (d, proj_out), jnp.float32)
                    * d ** -0.5).astype(pd),
        "conv_w": (jax.random.normal(ks[1], (cfg.ssm_conv, conv_dim(cfg)),
                                     jnp.float32)
                   * cfg.ssm_conv ** -0.5).astype(pd),
        "conv_b": jnp.zeros((conv_dim(cfg),), pd),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h).astype(jnp.float32)),
        "ssm_D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.full((h,), -4.0, jnp.float32),
        "gate_norm": jnp.ones((d_in,), pd),
        "out_proj": (jax.random.normal(ks[2], (d_in, d), jnp.float32)
                     * d_in ** -0.5).astype(pd),
    }


def _split_proj(zxbcdt: jax.Array, cfg: ModelConfig):
    d_in, h, _, g, n = _dims(cfg)
    z = zxbcdt[..., :d_in]
    xbc = zxbcdt[..., d_in:d_in + conv_dim(cfg)]
    dt = zxbcdt[..., -h:]
    return z, xbc, dt


def _split_xbc(xbc: jax.Array, cfg: ModelConfig):
    d_in, h, p, g, n = _dims(cfg)
    x = xbc[..., :d_in]
    bmat = xbc[..., d_in:d_in + g * n]
    cmat = xbc[..., d_in + g * n:]
    return x, bmat, cmat


def ssd_chunked(x, dt, a_log, b, c, d_skip, chunk: int,
                initial_state=None):
    """x: (B,S,H,P); dt: (B,S,H) (post-softplus); b/c: (B,S,G,N).
    Returns y: (B,S,H,P) and the final state (B,H,P,N).

    ``initial_state`` (B,H,N,P) carries the recurrence across chunked
    prefill steps (repro.serve: page-sized prompt chunks); ``None`` is a
    zero state (training / whole-prompt prefill)."""
    s_orig = x.shape[1]
    if s_orig % chunk:
        # pad to a chunk multiple: dt=0 ⇒ decay 1 and zero input, so padded
        # steps are state-neutral
        pad = chunk - s_orig % chunk
        pz = lambda t: jnp.pad(t, [(0, 0), (0, pad)] +
                               [(0, 0)] * (t.ndim - 2))
        x, dt, b, c = pz(x), pz(dt), pz(b), pz(c)
    bs, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    rep = h // g
    nc = s // chunk
    a = -jnp.exp(a_log)                                    # (H,) negative

    la = dt * a                                            # (B,S,H) log decay
    xb = x * dt[..., None]

    def ch(t):                                             # (B,nc,L,...)
        return t.reshape(bs, nc, chunk, *t.shape[2:])

    xc, lc, bc_, cc = ch(xb), ch(la), ch(b), ch(c)
    lcum = jnp.cumsum(lc, axis=2)                          # (B,nc,L,H)
    ltot = lcum[:, :, -1]                                  # (B,nc,H)

    bh = jnp.repeat(bc_, rep, axis=3) if rep > 1 else bc_  # (B,nc,L,H,N)
    chh = jnp.repeat(cc, rep, axis=3) if rep > 1 else cc

    # intra-chunk (the "attention-like" SSD term)
    sc = jnp.einsum("bclhn,bcmhn->bchlm", chh.astype(jnp.float32),
                    bh.astype(jnp.float32))
    # decay D[l,m] = exp(lcum[l] - lcum[m]) for l >= m
    ll = lcum.transpose(0, 1, 3, 2)                        # (B,nc,H,L)
    dmat = jnp.exp(ll[..., :, None] - ll[..., None, :])    # (B,nc,H,L,M)
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))
    m_ = jnp.where(mask, sc * dmat, 0.0)
    y_diag = jnp.einsum("bchlm,bcmhp->bclhp", m_, xc.astype(jnp.float32))

    # per-chunk state contribution: sum_m exp(ltot - lcum[m]) B_m x_m^T
    wt = jnp.exp(ltot[:, :, None] - lcum)                  # (B,nc,L,H)
    hc = jnp.einsum("bclhn,bclh,bclhp->bchnp", bh.astype(jnp.float32), wt,
                    xc.astype(jnp.float32))                # (B,nc,H,N,P)

    # inter-chunk scan
    def step(hprev, inp):
        hc_c, ltot_c = inp                                 # (B,H,N,P), (B,H)
        hnew = hprev * jnp.exp(ltot_c)[..., None, None] + hc_c
        return hnew, hprev

    h0 = (initial_state.astype(jnp.float32) if initial_state is not None
          else jnp.zeros((bs, h, n, p), jnp.float32))
    hlast, hprevs = jax.lax.scan(
        step, h0, (hc.transpose(1, 0, 2, 3, 4), ltot.transpose(1, 0, 2)))
    hprevs = hprevs.transpose(1, 0, 2, 3, 4)               # (B,nc,H,N,P)

    y_off = jnp.einsum("bclhn,bclh,bchnp->bclhp", chh.astype(jnp.float32),
                       jnp.exp(lcum), hprevs)
    y = (y_diag + y_off).reshape(bs, s, h, p)
    y = y + d_skip[None, None, :, None] * x.astype(jnp.float32)
    return y[:, :s_orig].astype(x.dtype), hlast


def apply_ssm(params: dict, xres: jax.Array, cfg: ModelConfig, *,
              cache: dict | None = None, cache_index: jax.Array | None = None,
              slot_ids: jax.Array | None = None,
              seq_lens: jax.Array | None = None,
              layer: jax.Array | None = None
              ) -> tuple[jax.Array, dict | None]:
    """Full mamba2 block with residual.  cache = {conv (B,W,Cd), state
    (B,H,N,P)} for one-token decode.

    Paged serving (repro.serve): the cache leaves are stacked over layers,
    ``layer`` is this block's index into them and ``slot_ids`` (B,)
    selects the rows to read/update (the SSM state is slot-resident — O(1)
    per sequence, so it is never paged); a row whose ``cache_index`` is 0
    starts fresh (first prefill chunk).  With s>1 this is one
    *chunked-prefill* step: the SSD recurrence carries the cached state,
    and ``seq_lens`` (B,) masks the chunk's padded tail (dt=0 ⇒
    state-neutral, excluded from the conv window)."""
    bs, s, _ = xres.shape
    d_in, h, p, g, n = _dims(cfg)
    xn = rms_norm(xres, params["ssm_norm"], cfg.norm_eps)
    zxbcdt = xn @ params["in_proj"]
    z, xbc, dt = _split_proj(zxbcdt, cfg)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])

    w = params["conv_w"].astype(jnp.float32)               # (W, Cd)
    if cache is None:
        # causal depthwise conv over the sequence
        pad = jnp.pad(xbc.astype(jnp.float32),
                      ((0, 0), (cfg.ssm_conv - 1, 0), (0, 0)))
        xbc_c = sum(pad[:, i:i + s] * w[i] for i in range(cfg.ssm_conv))
        xbc_c = jax.nn.silu(xbc_c + params["conv_b"].astype(jnp.float32))
        x, bmat, cmat = _split_xbc(xbc_c.astype(xres.dtype), cfg)
        x = x.reshape(bs, s, h, p)
        x = constrain(x, "batch", "seq", None, None)
        bmat = bmat.reshape(bs, s, g, n)
        cmat = cmat.reshape(bs, s, g, n)
        y, state = ssd_chunked(x, dt, params["A_log"], bmat, cmat,
                               params["ssm_D"], min(cfg.ssm_chunk, s))
        conv_tail = jnp.pad(xbc, ((0, 0), (cfg.ssm_conv - 1, 0), (0, 0))
                            )[:, -( cfg.ssm_conv - 1):]
        new_cache = {"conv": conv_tail.astype(xres.dtype), "state": state}
    else:
        conv_prev, state_prev = cache["conv"], cache["state"]
        if slot_ids is not None:
            conv_prev = conv_prev[layer, slot_ids]
            state_prev = state_prev[layer, slot_ids]
            # a row starting at position 0 is a fresh request: its slot may
            # hold a previous occupant's state, which must not leak in
            fresh = cache_index == 0
            conv_prev = jnp.where(fresh[:, None, None], 0.0, conv_prev)
            state_prev = jnp.where(fresh[:, None, None, None], 0.0,
                                   state_prev)
        if s == 1:
            # O(1) decode: roll conv window, one recurrence step
            window = jnp.concatenate([conv_prev,
                                      xbc.astype(xres.dtype)], axis=1)
            xbc_c = jnp.einsum("bwc,wc->bc", window.astype(jnp.float32), w)
            xbc_c = jax.nn.silu(xbc_c + params["conv_b"].astype(jnp.float32))
            x, bmat, cmat = _split_xbc(xbc_c[:, None].astype(xres.dtype), cfg)
            x = x.reshape(bs, 1, h, p)
            bmat = bmat.reshape(bs, 1, g, n)
            cmat = cmat.reshape(bs, 1, g, n)
            a = -jnp.exp(params["A_log"])
            decay = jnp.exp(dt[:, 0] * a)                      # (B,H)
            bh = jnp.repeat(bmat[:, 0], h // g, axis=1)        # (B,H,N)
            chh = jnp.repeat(cmat[:, 0], h // g, axis=1)
            xb = (x[:, 0] * dt[:, 0, :, None]).astype(jnp.float32)   # (B,H,P)
            state = (state_prev * decay[..., None, None] +
                     jnp.einsum("bhn,bhp->bhnp", bh.astype(jnp.float32), xb))
            y = jnp.einsum("bhn,bhnp->bhp", chh.astype(jnp.float32), state)
            y = (y + params["ssm_D"][None, :, None]
                 * x[:, 0].astype(jnp.float32))
            y = y[:, None].astype(xres.dtype)
            new_conv, new_state = window[:, 1:], state
        else:
            # chunked prefill: one multi-token step carrying the cached
            # state; padded chunk-tail tokens are state-neutral (dt=0)
            if seq_lens is None:
                seq_lens = jnp.full((bs,), s, jnp.int32)
            tok_valid = jnp.arange(s)[None, :] < seq_lens[:, None]
            dt = jnp.where(tok_valid[:, :, None], dt, 0.0)
            window_f = jnp.concatenate([conv_prev.astype(jnp.float32),
                                        xbc.astype(jnp.float32)], axis=1)
            xbc_c = sum(window_f[:, i:i + s] * w[i]
                        for i in range(cfg.ssm_conv))
            xbc_c = jax.nn.silu(xbc_c + params["conv_b"].astype(jnp.float32))
            x, bmat, cmat = _split_xbc(xbc_c.astype(xres.dtype), cfg)
            x = x.reshape(bs, s, h, p)
            bmat = bmat.reshape(bs, s, g, n)
            cmat = cmat.reshape(bs, s, g, n)
            y, new_state = ssd_chunked(x, dt, params["A_log"], bmat, cmat,
                                       params["ssm_D"], min(cfg.ssm_chunk, s),
                                       initial_state=state_prev)
            # conv window = last (W-1) inputs ending at the last VALID
            # token, so the padded tail never reaches the next step
            win_src = jnp.concatenate([conv_prev, xbc.astype(xres.dtype)],
                                      axis=1)
            cd = win_src.shape[-1]
            new_conv = jax.vmap(
                lambda wnd, l: jax.lax.dynamic_slice(
                    wnd, (l, 0), (cfg.ssm_conv - 1, cd)))(win_src, seq_lens)
        if slot_ids is not None:
            new_cache = {
                "conv": cache["conv"].at[layer, slot_ids].set(
                    new_conv.astype(cache["conv"].dtype)),
                "state": cache["state"].at[layer, slot_ids].set(new_state)}
        else:
            new_cache = {"conv": new_conv, "state": new_state}

    y = y.reshape(bs, s, d_in)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                 params["gate_norm"], cfg.norm_eps)
    y = constrain(y, "batch", "seq", "inner")
    return xres + (y @ params["out_proj"]).astype(xres.dtype), new_cache


def init_ssm_cache(cfg: ModelConfig, batch: int, dtype) -> dict:
    d_in, h, p, g, n = _dims(cfg)
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, conv_dim(cfg)), dtype),
        "state": jnp.zeros((batch, h, n, p), jnp.float32),
    }
