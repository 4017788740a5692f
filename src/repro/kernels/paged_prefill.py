"""Chunked paged-prefill attention kernel: one dispatch per prompt chunk.

The decode kernel (``kernels/paged_attention.py``) consumes ONE query token
per row per dispatch; feeding a 64-token prompt through it costs 64 decode
steps.  This kernel attends a whole chunk of ``T`` new prompt tokens per
serving slot against the slot's paged K/V context in a single grid pass:
the chunk's K/V must already be scattered into the pool at positions
``lengths[b] .. lengths[b] + T - 1`` through the slot's block table (the
jnp model path and ``kernels/ops.py::paged_prefill_gqa_attention`` do the
scatter — O(T) writes — before calling in; the O(context) gather is what
stays inside the kernel).

Query ``t`` of row ``b`` sits at absolute position ``lengths[b] + t`` and
attends positions ``[0, lengths[b] + t]`` — prior context plus a causal
mask *inside* the chunk — which is exactly the per-row mask applied to the
running online-softmax.  Layout: GQA folds the
chunk and the group axis into one query tile ``(T*G, hd)`` (row ``r``
holds chunk position ``r // G``), grid ``(B, Kv, MB)`` with the block loop
innermost carrying flash-style running max / denominator / accumulator
scratch, and the block table riding as a scalar-prefetch operand so each
grid step DMAs one physical block straight from the pool.

Rows past a slot's valid chunk fill (``t >= n_new[b]``, host-side raggedness)
produce finite garbage the scheduler never reads — they are masked at
scatter time (their K/V lands in scratch block 0) and discarded at
observation time, so the kernel itself needs no ``n_new`` operand.

Oracle: ``kernels/ref.py::paged_prefill_attention_ref``.  Model-layout
entry point with lane padding: ``kernels/ops.py::paged_prefill_gqa_attention``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import runtime
from repro.kernels.quant import quantize_int8

NEG_INF = -1e30


def _scatter_coords(B: int, S: int, bs_blk: int, block_tables: jnp.ndarray,
                    lengths: jnp.ndarray, n_new: jnp.ndarray | None):
    """(block ids, in-block offsets) every scatter variant writes through:
    token t of row b lands at ``(table[b, (lengths[b]+t) // bs],
    (lengths[b]+t) % bs)``; with ``n_new``, ragged-tail tokens
    (``t >= n_new[b]``) are redirected to scratch block 0."""
    rows = jnp.arange(B, dtype=jnp.int32)
    rows_t = jnp.arange(S, dtype=jnp.int32)
    pos = lengths[:, None].astype(jnp.int32) + rows_t[None, :]  # (B, S)
    blk = block_tables[rows[:, None], pos // bs_blk]
    if n_new is not None:
        blk = jnp.where(rows_t[None, :] < n_new[:, None], blk, 0)
    return blk, pos % bs_blk


def paged_scatter(k_pool: jnp.ndarray, v_pool: jnp.ndarray,
                  k: jnp.ndarray, v: jnp.ndarray,
                  block_tables: jnp.ndarray, lengths: jnp.ndarray,
                  n_new: jnp.ndarray | None = None):
    """Scatter S new K/V tokens per row into the shared paged pools.

    k/v: (B, S, Kv, hd); token t of row b lands at
    ``pool[table[b, (lengths[b]+t) // bs], (lengths[b]+t) % bs]``.  With
    ``n_new`` (B,), rows ``t >= n_new[b]`` (ragged chunk tails / inactive
    slots) are redirected to scratch block 0 — this is the ONE place the
    scatter convention lives; the jnp attention oracle
    (``models/layers.py`` paged branch) and the kernel wrapper
    (``kernels/ops.py``) both go through it.  Returns (k_pool, v_pool).

    Shared/private discipline (prefix caching): writes land only at
    positions ``>= lengths[b]``, and the allocator guarantees every block
    past a slot's sealed prefix is PRIVATE (refcount 1) while shared
    (refcounted / content-indexed) blocks are always full and sit below
    ``lengths[b]`` — so this scatter can never touch a block another slot
    (or the cross-call cache) is reading, with no copy-on-write needed.
    Scratch block 0 is never allocated or cached, so ragged-tail redirects
    stay harmless too."""
    B, S = k.shape[0], k.shape[1]
    blk, off = _scatter_coords(B, S, k_pool.shape[1], block_tables,
                               lengths, n_new)
    return (k_pool.at[blk, off].set(k.astype(k_pool.dtype)),
            v_pool.at[blk, off].set(v.astype(v_pool.dtype)))


def paged_scatter_quant(k_pool: jnp.ndarray, v_pool: jnp.ndarray,
                        k_scale: jnp.ndarray, v_scale: jnp.ndarray,
                        k: jnp.ndarray, v: jnp.ndarray,
                        block_tables: jnp.ndarray, lengths: jnp.ndarray,
                        n_new: jnp.ndarray | None = None):
    """:func:`paged_scatter` for int8 pools: quantize each new token's K/V
    per (token, kv-head) — amax over the head dim — and scatter values and
    fp32 scales through the SAME coordinates (scale pools are
    (NB, bs, Kv)), so every written position is self-contained and blocks
    never need requantizing as they fill.  Returns the four updated pools.
    """
    B, S = k.shape[0], k.shape[1]
    blk, off = _scatter_coords(B, S, k_pool.shape[1], block_tables,
                               lengths, n_new)
    qk, sk = quantize_int8(k, axis=-1)                # (B,S,Kv,hd)/(B,S,Kv)
    qv, sv = quantize_int8(v, axis=-1)
    return (k_pool.at[blk, off].set(qk),
            v_pool.at[blk, off].set(qv),
            k_scale.at[blk, off].set(sk),
            v_scale.at[blk, off].set(sv))


def _kernel(bt_ref, len_ref, q_ref, *rest, scale: float, bs: int, mb: int,
            g: int, quantized: bool):
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

    base = len_ref[b]                                 # context before chunk
    rows = q_ref.shape[2]                             # T * G folded rows
    # query row r = t*G + g  ->  absolute position base + t
    q_pos = base + jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 0) // g
    k_pos = i * bs + jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 1)
    mask = k_pos <= q_pos                             # context + intra-chunk causal

    @pl.when(jnp.any(mask))                           # skip past-the-end blocks
    def _compute():
        q = q_ref[0, 0]                               # (T*G, hd)
        if quantized:                                 # dequant in VMEM, fp32
            k = k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0]
            v = v_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0]
        else:
            k = k_ref[0, 0]                           # (bs, hd)
            v = v_ref[0, 0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                         # (T*G, 1) row-carried
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                        # (T*G, bs)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(i == mb - 1)
    def _finish():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale",))
def paged_prefill_attention(q, k_pool, v_pool, block_tables, lengths, *,
                            k_scale=None, v_scale=None,
                            scale: float | None = None):
    """q: (B, T, H, hd) — T chunk queries per row at absolute positions
    ``lengths[b] + t``; k_pool/v_pool: (NB, bs, Kv, hd) shared pools WITH
    the chunk's K/V already scattered in; block_tables: (B, MB) int32;
    lengths: (B,) int32 context written BEFORE this chunk.
    Returns (B, T, H, hd).

    Each query attends ``[0, lengths[b] + t]`` inclusive — its own position
    included, matching the decode kernel's scatter-then-attend convention.
    With int8 pools pass ``k_scale``/``v_scale`` ((NB, bs, Kv) fp32,
    written by ``paged_scatter_quant``): each grid step DMAs the block's
    scale tile alongside its values and dequantizes in VMEM — the fp32
    K/V gather still never materialises in HBM.
    H must be a multiple of Kv.
    """
    B, T, H, hd = q.shape
    NB, bs, Kv, _ = k_pool.shape
    MB = block_tables.shape[1]
    G = H // Kv
    scale = scale if scale is not None else hd ** -0.5
    quantized = k_scale is not None

    # fold (T, G) into one query tile; row r = t*G + g
    qg = (q.reshape(B, T, Kv, G, hd)
           .transpose(0, 2, 1, 3, 4)
           .reshape(B, Kv, T * G, hd))
    kh = k_pool.transpose(0, 2, 1, 3)                 # (NB, Kv, bs, hd)
    vh = v_pool.transpose(0, 2, 1, 3)

    pool_spec = pl.BlockSpec((1, 1, bs, hd),
                             lambda b, h, i, bt, ln: (bt[b, i], h, 0, 0))
    in_specs = [pl.BlockSpec((1, 1, T * G, hd),
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
        out_specs=pl.BlockSpec((1, 1, T * G, hd),
                               lambda b, h, i, bt, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((T * G, 128), jnp.float32),    # running max
            pltpu.VMEM((T * G, 128), jnp.float32),    # running denominator
            pltpu.VMEM((T * G, hd), jnp.float32),     # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, bs=bs, mb=MB, g=G,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Kv, T * G, hd), q.dtype),
        interpret=runtime.interpret_mode(),
        name="paged_prefill_attention",
    )(block_tables, lengths, *operands)
    return (out.reshape(B, Kv, T, G, hd)
               .transpose(0, 2, 1, 3, 4)
               .reshape(B, T, H, hd))
