"""Mamba2 (SSD — state-space duality, arXiv:2405.21060) block.

TPU adaptation (DESIGN.md §2): the SSD "chunked" algorithm is matmul-form —
intra-chunk attention-like matmuls feed the MXU, inter-chunk recurrence is a
short ``lax.scan`` over chunk states. Decode keeps an explicit recurrent state
``h: (B, n_heads, head_dim, d_state)`` so one-token steps are O(1) in seq len
(this is what makes ``long_500k`` native for SSM/hybrid architectures).

Parameterisation follows the Mamba2 reference: a single ``in_proj`` produces
(z, x, B, C, dt); depthwise causal conv over (x, B, C); scalar-per-head decay
A; gated RMSNorm before ``out_proj``.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import MODEL, _scoped, dense, lora_pair

Params = Dict[str, Any]


def _dims(cfg):
    d_in = cfg.ssm_d_inner
    n_h = cfg.ssm_n_heads
    d_st = cfg.ssm_d_state
    n_g = cfg.ssm_n_groups
    conv_dim = d_in + 2 * n_g * d_st
    proj_dim = 2 * d_in + 2 * n_g * d_st + n_h
    return d_in, n_h, d_st, n_g, conv_dim, proj_dim


def init_mamba(key, cfg, dtype) -> Params:
    d = cfg.d_model
    d_in, n_h, d_st, n_g, conv_dim, proj_dim = _dims(cfg)
    ks = jax.random.split(key, 5)
    s = d ** -0.5
    p = {
        "in_proj": (jax.random.normal(ks[0], (d, proj_dim)) * s).astype(dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.ssm_d_conv, conv_dim)) * 0.1).astype(dtype),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, n_h)).astype(jnp.float32),
        "dt_bias": jnp.zeros((n_h,), dtype=jnp.float32),
        "d_skip": jnp.ones((n_h,), dtype=jnp.float32),
        "norm_scale": jnp.ones((d_in,), dtype=jnp.float32),
        "out_proj": (jax.random.normal(ks[2], (d_in, d)) * d_in ** -0.5).astype(dtype),
    }
    if cfg.ssm_conv_bias:
        p["conv_b"] = jnp.zeros((conv_dim,), dtype=dtype)
    return p


def mamba_specs(cfg) -> Params:
    p = {
        "in_proj": P(None, MODEL),
        "conv_w": P(None, MODEL),
        "a_log": P(None),
        "dt_bias": P(None),
        "d_skip": P(None),
        "norm_scale": P(MODEL),
        "out_proj": P(MODEL, None),
    }
    if cfg.ssm_conv_bias:
        p["conv_b"] = P(MODEL)
    return p


def _split_proj(cfg, zxbcdt):
    d_in, n_h, d_st, n_g, _, _ = _dims(cfg)
    z, xbc, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * n_g * d_st], axis=-1)
    return z, xbc, dt


def _causal_conv(xbc: jnp.ndarray, w: jnp.ndarray,
                 state: Optional[jnp.ndarray] = None,
                 n_valid: Optional[jnp.ndarray] = None,
                 bias: Optional[jnp.ndarray] = None):
    """Depthwise causal conv1d. xbc: (B, S, C), w: (K, C), ``bias`` (C,).

    Training: zero left-pad. Decode: ``state`` is the last K-1 inputs
    (B, K-1, C); returns updated state.  Chunked decode with ragged fill:
    ``n_valid`` (B,) int32 counts the valid leading tokens per row — the new
    state is the last K-1 inputs ENDING at each row's valid fill, so rows
    fed only padding keep their state bit-for-bit.
    """
    K = w.shape[0]
    if state is None:
        pad = jnp.zeros((xbc.shape[0], K - 1, xbc.shape[2]), dtype=xbc.dtype)
        xp = jnp.concatenate([pad, xbc], axis=1)
        new_state = xp[:, -(K - 1):, :]
    else:
        xp = jnp.concatenate([state.astype(xbc.dtype), xbc], axis=1)
        if n_valid is None:
            new_state = xp[:, -(K - 1):, :]
        else:
            idx = n_valid[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None]
            new_state = jnp.take_along_axis(xp, idx[:, :, None], axis=1)
    # windowed sum: out[t] = sum_k w[k] * xp[t + k]
    out = jnp.zeros_like(xbc, dtype=jnp.float32)
    S = xbc.shape[1]
    for k in range(K):
        out = out + xp[:, k:k + S, :].astype(jnp.float32) * w[k].astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return jax.nn.silu(out).astype(xbc.dtype), new_state


def _gated_norm(x, z, scale, eps=1e-6):
    xf = x.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int, h0=None, n_new=None):
    """Chunked SSD scan (matmul form), from the state ``h0``.

    x:  (B, S, H, P)   inputs per head
    dt: (B, S, H)      softplus'd timestep (>0)
    A:  (H,)           negative decay rate (A < 0)
    Bm: (B, S, G, N)   input->state projection
    Cm: (B, S, G, N)   state->output projection
    h0: (B, H, P, N)   state before the first position (zeros if None)
    n_new: (B,) int32  valid leading positions per row (ragged chunks):
        later positions get dt = 0 (decay 1, no input), so the returned
        state is each row's state after its last valid token, and a row
        with ``n_new = 0`` returns ``h0`` bit for bit.
    Returns y: (B, S, H, P), final_state: (B, H, P, N).

    The state is read once and written once; within a chunk every
    position's output comes from matmuls (C·Bᵀ once per group, shared by
    its heads), across chunks from a short scan over chunk states.  S
    need not divide by ``chunk``: the tail pads with dt = 0.
    """
    Bsz, S, H, Pd = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    R = H // G                                     # heads per group
    f32 = jnp.float32
    dt = dt.astype(f32)
    if n_new is not None:
        valid = jnp.arange(S, dtype=jnp.int32)[None, :] < n_new[:, None]
        dt = jnp.where(valid[:, :, None], dt, 0.0)
    pad = -S % chunk
    if pad:
        widths = lambda t: [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2)  # noqa: E731
        x, dt, Bm, Cm = (jnp.pad(t, widths(t)) for t in (x, dt, Bm, Cm))
    nc = (S + pad) // chunk

    xc = x.astype(f32).reshape(Bsz, nc, chunk, G, R, Pd)
    dtc = dt.reshape(Bsz, nc, chunk, G, R)
    Bc = Bm.astype(f32).reshape(Bsz, nc, chunk, G, N)
    Cc = Cm.astype(f32).reshape(Bsz, nc, chunk, G, N)

    # per-step log decay  a_t = A * dt_t  (A<0), running within a chunk
    cum = jnp.cumsum(dtc * A.reshape(G, R), axis=2)     # (B,nc,c,G,R)
    # intra-chunk: y[t] = sum_{s<=t} C_t . B_s x_s dt_s * exp(cum_t - cum_s)
    # Mask BEFORE the exp: for s > t the exponent is positive-large; exp would
    # overflow to inf and the masked backward produces 0·inf = NaN.
    diff = cum[:, :, :, None] - cum[:, :, None, :]       # (B,nc,t,s,G,R)
    idx = jnp.arange(chunk)
    causal = (idx[:, None] >= idx[None, :])[None, None, :, :, None, None]
    decay = jnp.exp(jnp.where(causal, diff, -1e30))
    cb = jnp.einsum("bztgn,bzsgn->bztsg", Cc, Bc)       # once per group
    xdt = xc * dtc[..., None]                            # (B,nc,c,G,R,P)
    y = jnp.einsum("bztsgr,bzsgrp->bztgrp", cb[..., None] * decay, xdt)

    # chunk-level states: state_z = sum_s exp(cum_end - cum_s) B_s x_s dt_s.
    # Like every matmul here it runs at the default precision (on a TPU,
    # bf16 operands with float32 sums, as the published SSD kernels feed
    # their matmuls); the state itself is summed and carried in float32.
    seg = jnp.exp(cum[:, :, -1:] - cum)                  # (B,nc,c,G,R)
    states = jnp.einsum("bzsgn,bzsgrp->bzgrpn", Bc, xdt * seg[..., None])
    chunk_decay = jnp.exp(cum[:, :, -1])                 # (B,nc,G,R)

    # inter-chunk recurrence over nc chunks
    def step(h, inp):
        st, cd = inp                               # (B,G,R,P,N), (B,G,R)
        return h * cd[..., None, None] + st, h

    h_init = (jnp.zeros((Bsz, G, R, Pd, N), f32) if h0 is None
              else h0.astype(f32).reshape(Bsz, G, R, Pd, N))
    h_final, h_prevs = jax.lax.scan(
        step, h_init, (jnp.moveaxis(states, 1, 0),
                       jnp.moveaxis(chunk_decay, 1, 0)))
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)          # state entering each chunk

    # contribution of the entering state, decayed from the chunk's start
    y = y + jnp.einsum("bztgn,bzgrpn->bztgrp", Cc, h_prevs) \
        * jnp.exp(cum)[..., None]

    y = y.reshape(Bsz, nc * chunk, H, Pd)[:, :S]
    h_final = h_final.reshape(Bsz, H, Pd, N)
    if n_new is not None and h0 is not None:
        h_final = jnp.where((n_new > 0)[:, None, None, None], h_final,
                            h0.astype(f32))
    return y, h_final


def apply_mamba(params: Params, x: jnp.ndarray, cfg,
                adapters: Optional[Params] = None, lora_scale: float = 1.0,
                ssm_cache: Optional[Params] = None,
                adapter_ids: Optional[jnp.ndarray] = None,
                n_new: Optional[jnp.ndarray] = None,
                period: Optional[jnp.ndarray] = None):
    """x: (B, S, d) -> (out, new_cache).

    ``ssm_cache`` = {"h": (n_periods,B,H,P,N), "conv": (n_periods,B,K-1,
    conv_dim)} for decode: every period's state, stacked, of which this
    layer reads and writes (in place) the one at ``period`` (a scalar
    index); the whole stack comes back.
    Decode accepts S >= 1: one token steps the recurrence; a chunk of
    S > 1 (chunked prefill) runs :func:`ssd_chunked` from the cached
    state, reading and writing it once.  ``n_new`` (B,) int32 marks each
    row's valid leading tokens (ragged chunks): rows beyond their fill get
    dt masked to 0 — decay exp(0)=1, update 0 — so their recurrent and
    conv state pass through untouched.
    """
    la = partial(lora_pair, adapters)
    zxbcdt = dense(x, params["in_proj"], la("in_proj"), lora_scale,
                   adapter_ids=adapter_ids)
    y, new_cache = _ssm(params, zxbcdt, cfg, ssm_cache, n_new, period)
    out = dense(y, params["out_proj"], la("out_proj"), lora_scale,
                adapter_ids=adapter_ids)
    return out, new_cache


@_scoped("ssm")
def _ssm(params: Params, zxbcdt: jnp.ndarray, cfg,
         ssm_cache: Optional[Params], n_new: Optional[jnp.ndarray],
         period: Optional[jnp.ndarray] = None):
    """The mixer between its projections: causal conv, the state-space
    scan (or one recurrent step) and the gated norm, with the state's
    read and write.  zxbcdt: (B, S, proj_dim) -> (y (B, S, d_inner), new
    cache)."""
    stack = ssm_cache
    if stack is not None:
        ssm_cache = jax.tree.map(lambda a: a[period], stack)
    B, S, _ = zxbcdt.shape
    d_in, n_h, d_st, n_g, conv_dim, _ = _dims(cfg)
    z, xbc, dt_raw = _split_proj(cfg, zxbcdt)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])

    conv_state = ssm_cache["conv"] if ssm_cache is not None else None
    xbc, new_conv = _causal_conv(xbc, params["conv_w"], conv_state,
                                 n_valid=n_new if ssm_cache is not None
                                 else None, bias=params.get("conv_b"))
    xs, Bm, Cm = jnp.split(xbc, [d_in, d_in + n_g * d_st], axis=-1)
    xs = xs.reshape(B, S, n_h, cfg.ssm_head_dim)
    Bm = Bm.reshape(B, S, n_g, d_st)
    Cm = Cm.reshape(B, S, n_g, d_st)
    A = -jnp.exp(params["a_log"])                  # (H,) negative
    chunk = min(cfg.ssm_chunk, S)

    if ssm_cache is None:
        y, h = ssd_chunked(xs, dt, A, Bm, Cm, chunk)
    elif S == 1:
        # single-token recurrent update: h' = h*exp(dt*A) + dt * B x^T
        h = ssm_cache["h"].astype(jnp.float32)
        rep = n_h // n_g
        Bh = jnp.repeat(Bm[:, 0].astype(jnp.float32), rep, axis=1)  # (B,H,N)
        Ch = jnp.repeat(Cm[:, 0].astype(jnp.float32), rep, axis=1)
        dt0 = dt[:, 0]                                               # (B,H)
        if n_new is not None:
            dt0 = jnp.where(n_new[:, None] > 0, dt0, 0.0)
        decay = jnp.exp(dt0 * A[None, :])                            # (B,H)
        upd = jnp.einsum("bh,bhp,bhn->bhpn", dt0, xs[:, 0].astype(jnp.float32), Bh)
        h = h * decay[:, :, None, None] + upd
        y = jnp.einsum("bhpn,bhn->bhp", h, Ch)[:, None]              # (B,1,H,P)
    else:
        y, h = ssd_chunked(xs, dt, A, Bm, Cm, chunk, h0=ssm_cache["h"],
                           n_new=n_new)

    y = y + xs.astype(jnp.float32) * params["d_skip"][None, None, :, None]
    y = y.reshape(B, S, d_in).astype(zxbcdt.dtype)
    y = _gated_norm(y, z, params["norm_scale"], cfg.norm_eps)
    new = {"h": h.astype(jnp.float32), "conv": new_conv}
    if stack is not None:
        new = jax.tree.map(lambda a, v: jax.lax.dynamic_update_index_in_dim(
            a, v.astype(a.dtype), period, 0), stack, new)
    return y, new


def init_ssm_cache(cfg, batch: int) -> Params:
    d_in, n_h, d_st, n_g, conv_dim, _ = _dims(cfg)
    return {
        "h": jnp.zeros((batch, n_h, cfg.ssm_head_dim, d_st), dtype=jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_d_conv - 1, conv_dim), dtype=jnp.bfloat16),
    }


def ssm_cache_specs() -> Params:
    from repro.models.layers import DATA
    return {"h": P(DATA, MODEL, None, None), "conv": P(DATA, None, MODEL)}
