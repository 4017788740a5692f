"""Flash attention (online softmax) Pallas kernel with causal + sliding
window masking — the sub-quadratic variant that removes the S² HBM traffic
the roofline analysis flags as the dominant memory term for long contexts,
and the qualifier for running dense architectures at ``long_500k``.

Layout: q (B, H, Sq, d), k/v (B, H, Sk, d) — GQA callers repeat KV heads (or
vmap over groups) before the call. Grid (B·H, Sq/bq, Sk/bk); the kv loop is
innermost with running max/denominator scratch carried across kv steps
(standard online-softmax recurrence). Positions align at the end: query i
has absolute position Sk − Sq + i, so the same kernel serves training
(Sq == Sk), chunked prefill, and single-token decode (Sq == 1 is padded to a
block by the wrapper).

Sliding-window + causal masking is applied per tile from absolute positions.
Fully-masked kv tiles still execute (Pallas TPU grids are static) but a
`pl.when` skips their MXU work; on TPU the win over masked XLA attention is
the removed HBM round-trip of the (Sq, Sk) logits, not the mask itself.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import runtime

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int, bq: int, bk: int,
            sq: int, sk: int, kv_steps: int):
    kv_i = pl.program_id(2)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_idx = pl.program_id(1)
    q_pos = (q_idx * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
             + (sk - sq))
    k_pos = kv_i * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), dtype=bool)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > (q_pos - window)

    # block-level early-out: fully masked tiles skip the MXU work
    any_valid = jnp.any(mask)

    @pl.when(any_valid)
    def _compute():
        q = q_ref[0]                                   # (bq, d)
        k = k_ref[0]                                   # (bk, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                            # (bq, 128) row-carried
        m_cur = jnp.max(s, axis=1, keepdims=True)      # (bq, 1)
        m_new = jnp.maximum(m_prev[:, :1], m_cur)
        alpha = jnp.exp(m_prev[:, :1] - m_new)
        p = jnp.exp(s - m_new)                         # (bq, bk)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kv_i == kv_steps - 1)
    def _finish():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "sliding_window",
                                             "scale", "bq", "bk"))
def flash_attention(q, k, v, *, causal: bool = True, sliding_window: int = 0,
                    scale: float | None = None, bq: int = 128, bk: int = 128):
    """q: (B, H, Sq, d), k/v: (B, H, Sk, d) -> (B, H, Sq, d)."""
    B, H, Sq, d = q.shape
    Sk = k.shape[2]
    scale = scale if scale is not None else d ** -0.5
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    kv_steps = Sk // bk

    qf = q.reshape(B * H, Sq, d)
    kf = k.reshape(B * H, Sk, d)
    vf = v.reshape(B * H, Sk, d)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal,
                          window=sliding_window, bq=bq, bk=bk, sq=Sq, sk=Sk,
                          kv_steps=kv_steps),
        grid=(B * H, Sq // bq, kv_steps),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # running max (col-bcast)
            pltpu.VMEM((bq, 128), jnp.float32),   # running denom
            pltpu.VMEM((bq, d), jnp.float32),     # output accumulator
        ],
        interpret=runtime.interpret_mode(),
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, d)
