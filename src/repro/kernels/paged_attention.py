"""Paged-attention decode kernel: block-table K/V gather inside the grid.

Continuous batching stores K/V in fixed-size blocks of a shared pool; each
serving slot holds a *block table* naming the physical blocks that make up
its (ragged) context.  The jnp serving path gathers ``k_pool[table]`` into a
padded ``(B, MB·bs, Kv, hd)`` HBM tensor before attending — exactly the
materialisation this kernel removes: the block table rides as a
scalar-prefetch operand and the BlockSpec ``index_map`` reads it, so each
grid step DMAs one *physical* K/V block straight from the pool into VMEM.
Ragged per-row context lengths therefore never pad out in HBM; they only
show up as a per-row mask against the running online-softmax.

Layout: one query token per row (decode), GQA folded as (B, Kv, G, hd) with
grid (B, Kv, MB) — the block loop innermost carrying flash-style running
max / denominator / accumulator scratch across K/V blocks.  Rows whose
``lengths[b] == 0`` (empty serving slots) produce zeros, not NaNs.

Oracle: ``kernels/ref.py::paged_attention_ref`` (which *does* materialise
the gather).  Model-layout entry point with lane padding:
``kernels/ops.py::paged_gqa_attention``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import runtime

NEG_INF = -1e30


def _kernel(bt_ref, len_ref, q_ref, *rest, scale: float, bs: int, mb: int,
            quantized: bool):
    if quantized:
        # int8 pools ride with block-aligned fp32 scale tiles (1, 1, bs, 1)
        # whose index_map reads the same block-table entry as K/V
        k_ref, ks_ref, v_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = rest
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    n_valid = len_ref[b]                              # row's context length
    g = q_ref.shape[2]
    k_pos = i * bs + jax.lax.broadcasted_iota(jnp.int32, (g, bs), 1)
    mask = k_pos < n_valid

    @pl.when(jnp.any(mask))                           # skip past-the-end blocks
    def _compute():
        q = q_ref[0, 0]                               # (G, hd)
        if quantized:                                 # dequant in VMEM, fp32
            k = k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0]
            v = v_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0]
        else:
            k = k_ref[0, 0]                           # (bs, hd)
            v = v_ref[0, 0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                         # (G, 1) row-carried
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                        # (G, bs)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(i == mb - 1)
    def _finish():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)      # empty slots -> zeros
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale",))
def paged_attention(q, k_pool, v_pool, block_tables, lengths, *,
                    k_scale=None, v_scale=None, scale: float | None = None):
    """q: (B, H, hd) decode queries; k_pool/v_pool: (NB, bs, Kv, hd) shared
    block pools; block_tables: (B, MB) int32 physical block ids per row;
    lengths: (B,) int32 valid context per row.  Returns (B, H, hd).

    With int8 pools pass ``k_scale``/``v_scale`` ((NB, bs, Kv) fp32,
    written by ``paged_scatter_quant``): each grid step DMAs the block's
    scale tile alongside its values and dequantizes in VMEM — the fp32
    K/V gather still never materialises in HBM.

    ``lengths`` counts positions ALREADY WRITTEN to the pool, exclusive:
    row b attends K/V positions [0, lengths[b]).  The serving decode step
    scatters the new token's K/V at position L *then* attends it, so a
    caller replacing the jnp paged branch of ``layers.multihead_attention``
    (whose per-step ``pos`` is the pre-write count L) must pass ``L + 1``
    here after the scatter — otherwise each step omits the token being
    decoded from its own attention.

    H must be a multiple of Kv (GQA groups fold into the query tile).
    """
    B, H, hd = q.shape
    NB, bs, Kv, _ = k_pool.shape
    MB = block_tables.shape[1]
    G = H // Kv
    scale = scale if scale is not None else hd ** -0.5
    quantized = k_scale is not None

    qg = q.reshape(B, Kv, G, hd)
    # head-major pools so one (block, head) tile DMAs contiguously
    kh = k_pool.transpose(0, 2, 1, 3)                 # (NB, Kv, bs, hd)
    vh = v_pool.transpose(0, 2, 1, 3)

    # the paged gather: block i of row b is DMA'd from the physical
    # block its table names — no padded (B, MB*bs) tensor ever exists
    pool_spec = pl.BlockSpec((1, 1, bs, hd),
                             lambda b, h, i, bt, ln: (bt[b, i], h, 0, 0))
    in_specs = [pl.BlockSpec((1, 1, G, hd),
                             lambda b, h, i, bt, ln: (b, h, 0, 0))]
    operands = [qg]
    if quantized:
        scale_spec = pl.BlockSpec((1, 1, bs, 1),
                                  lambda b, h, i, bt, ln: (bt[b, i], h, 0, 0))
        ksh = k_scale.transpose(0, 2, 1)[..., None]   # (NB, Kv, bs, 1)
        vsh = v_scale.transpose(0, 2, 1)[..., None]
        in_specs += [pool_spec, scale_spec, pool_spec, scale_spec]
        operands += [kh, ksh, vh, vsh]
    else:
        in_specs += [pool_spec, pool_spec]
        operands += [kh, vh]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                        # block_tables, lengths
        grid=(B, Kv, MB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, h, i, bt, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 128), jnp.float32),        # running max
            pltpu.VMEM((G, 128), jnp.float32),        # running denominator
            pltpu.VMEM((G, hd), jnp.float32),         # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, bs=bs, mb=MB,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Kv, G, hd), q.dtype),
        interpret=runtime.interpret_mode(),
        name="paged_attention",
    )(block_tables, lengths, *operands)
    return out.reshape(B, H, hd)
