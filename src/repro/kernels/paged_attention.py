"""Paged-attention decode kernel: many table blocks per grid step, DMA'd
from the pool in place.

Continuous batching stores K/V in fixed-size blocks of a shared pool; each
serving slot holds a *block table* naming the physical blocks that make up
its (ragged) context.  The jnp serving path gathers ``k_pool[table]`` into a
padded ``(B, MB·bs, Kv, hd)`` HBM tensor before attending — exactly the
materialisation this kernel removes.

Grid ``(B, MB / T)``: step ``(b, c)`` attends row ``b``'s table blocks
``c·T .. c·T + T - 1``, all KV heads at once.  ``T`` (blocks per step)
comes from the shapes alone (:func:`blocks_per_step`).  The pools stay in
HBM in their own ``(NB, bs, Kv, hd)`` layout (``memory_space=HBM``, no
transpose): the block table rides as a scalar-prefetch operand and the
kernel issues one DMA per live physical block — a contiguous
``(bs, Kv, hd)`` tile (plus its scale tile for int8 pools) — into a
double-buffered VMEM scratch.  While a step computes, the next
*live* step's blocks are already in flight, across the boundary from one
row to the next.  Blocks at or past ``ceil(lengths[b] / bs)`` are neither
fetched nor computed; a step whose first block is dead does nothing but
its row's bookkeeping.

In VMEM a step's K is ``(T·bs·Kv, hd)``, row ``p·Kv + h`` for position
``p`` and KV head ``h``: one MXU matmul scores every query head against
every row, and a mask keeps query head ``r``'s own KV head ``r // G`` at
live positions, so the weighted sum over V is one matmul too.  Flash-style
running max / denominator / accumulator carry across a row's steps in
f32.  Rows whose ``lengths[b] == 0`` (empty serving slots) produce zeros,
not NaNs.

Two inputs cannot DMA as they lie, and the wrapper copies them first: a
KV axis that does not fill the TPU's tile of a packed dtype
(:func:`dma_heads`: MQA's one KV head at bf16 pads to two), and int8
pools' f32 ``(NB, bs, Kv)`` scales, whose Kv axis pads to whole lanes.

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
MAX_BLOCKS_PER_STEP = 16
SCRATCH_BYTES = 4 * 2**20        # VMEM for the double-buffered K and V blocks


def dma_heads(kv: int, itemsize: int) -> int:
    """The KV-head count a pool block must have to DMA from the pool as
    it lies: the TPU tiles a packed dtype's ``(Kv, hd)`` minor pair 2, 4
    or 8k rows high (bf16 at least 2, int8 at least 4), and Mosaic DMAs
    only whole tiles.  ``kv`` itself where it fills its tile (always for
    f32; OLMo's 16 and Yi's 4 at bf16 and int8), else the next count that
    does (MQA's 1 -> 2 at bf16)."""
    if itemsize >= 4:
        return kv
    n = kv
    while n % (4 // itemsize) or (n % 8 and n & (n - 1)):
        n += 1
    return n


def blocks_per_step(mb: int, bs: int, kv: int, hd: int, itemsize: int
                    ) -> int:
    """Table blocks one grid step fetches: the largest power of two, at
    most ``MAX_BLOCKS_PER_STEP``, that divides the table width ``mb`` and
    whose double-buffered K and V blocks fit ``SCRATCH_BYTES`` of VMEM
    (a ``(Kv, hd)`` tile pads to the dtype's ``(32 / itemsize, 128)``)."""
    sub = 32 // itemsize
    block = bs * -(-kv // sub) * sub * -(-hd // 128) * 128 * itemsize
    t = MAX_BLOCKS_PER_STEP
    while t > 1 and (mb % t or 2 * 2 * t * block > SCRATCH_BYTES):
        t //= 2
    return t


def _kernel(bt_ref, len_ref, q_ref, k_hbm, v_hbm, *rest, scale: float,
            bs: int, mb: int, t: int, g: int, rows: int, quantized: bool):
    if quantized:
        (ks_hbm, vs_hbm, o_ref, kbuf, vbuf, ksbuf, vsbuf,
         sem, state, m_ref, l_ref, acc_ref) = rest
        pairs = ((k_hbm, kbuf), (v_hbm, vbuf), (ks_hbm, ksbuf),
                 (vs_hbm, vsbuf))
    else:
        o_ref, kbuf, vbuf, sem, state, m_ref, l_ref, acc_ref = rest
        pairs = ((k_hbm, kbuf), (v_hbm, vbuf))
    b, c = pl.program_id(0), pl.program_id(1)
    chunks = mb // t
    hd = q_ref.shape[1]
    kv = kbuf.shape[3]                                # KV heads, padded
    npos = t * bs                                     # positions per step

    def live_blocks(r):
        return jnp.minimum((len_ref[r] + bs - 1) // bs, mb)

    def dmas(r, cc, slot, fn):
        """``fn`` (start or wait) on the DMAs of step (r, cc)'s live
        blocks into buffer ``slot``."""
        for j in range(t):
            i = cc * t + j

            @pl.when(i < live_blocks(r))
            def _():
                blk = bt_ref[r * mb + i]
                for src, dst in pairs:
                    fn(pltpu.make_async_copy(src.at[blk], dst.at[slot, j],
                                             sem.at[slot]))

    def next_row(r):
        """The first row after ``r`` with context, else ``rows``."""
        def body(i, found):
            cand = rows - 1 - i
            return jnp.where((cand > r) & (len_ref[cand] > 0), cand, found)
        return jax.lax.fori_loop(0, rows, body, jnp.int32(rows))

    @pl.when((b == 0) & (c == 0))
    def _first():
        state[0] = 0                                  # buffer of this step
        state[1] = 0                                  # any step fetched yet
        # dead blocks of a live step are computed on (then masked): keep
        # what V holds finite before the first DMA lands
        vbuf[...] = jnp.zeros_like(vbuf)
        if quantized:
            vsbuf[...] = jnp.zeros_like(vsbuf)

    @pl.when(c == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(c * t < live_blocks(b))                 # skip dead steps
    def _step():
        slot = state[0]

        @pl.when(state[1] == 0)                       # the first live step
        def _():
            dmas(b, c, slot, lambda cp: cp.start())
            state[1] = 1

        # prefetch the next live step: this row's next chunk, else the
        # first chunk of the next row with context
        more = (c + 1) * t < live_blocks(b)
        nb = jax.lax.cond(more, lambda: b, lambda: next_row(b))
        nc = jnp.where(more, c + 1, 0)

        @pl.when(nb < rows)
        def _():
            dmas(nb, nc, 1 - slot, lambda cp: cp.start())

        state[0] = 1 - slot
        dmas(b, c, slot, lambda cp: cp.wait())

        q = q_ref[...]                                # (H, hd)
        if quantized:                                 # dequant in VMEM, f32
            q = q.astype(jnp.float32)
            ks = ksbuf[slot, :, :, :kv]                # (t, bs, Kv)
            vs = vsbuf[slot, :, :, :kv]
            k = kbuf[slot].astype(jnp.float32) * ks[..., None]
            v = vbuf[slot].astype(jnp.float32) * vs[..., None]
        else:                                         # (t, bs, Kv, hd)
            # an f32 query keeps f32 weights on bf16 K/V
            dt = jnp.promote_types(q.dtype, kbuf.dtype)
            k, v = kbuf[slot].astype(dt), vbuf[slot].astype(dt)
        k = k.reshape(npos * kv, hd)                  # row p·Kv + h
        v = v.reshape(npos * kv, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        head = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // g
        mask = (col % kv == head) & (c * npos + col // kv < len_ref[b])
        s = jnp.where(mask, s, NEG_INF)               # (H, npos·Kv)

        m_prev = m_ref[:, :1]                         # (H, 1) row-carried
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                        # masked -> exact 0
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(c == chunks - 1)
    def _finish():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)      # empty slots -> zeros
        o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale",))
def paged_attention(q, k_pool, v_pool, block_tables, lengths, *,
                    k_scale=None, v_scale=None, scale: float | None = None):
    """q: (B, H, hd) decode queries; k_pool/v_pool: (NB, bs, Kv, hd) shared
    block pools; block_tables: (B, MB) int32 physical block ids per row;
    lengths: (B,) int32 valid context per row.  Returns (B, H, hd).

    With int8 pools pass ``k_scale``/``v_scale`` ((NB, bs, Kv) fp32,
    written by ``paged_scatter_quant``): each live block's scale tile is
    DMA'd alongside its values and dequantizes them in VMEM — the fp32
    K/V gather still never materialises in HBM.  The scales' Kv axis is
    first padded to whole lanes (a copy of the scales): an f32
    ``(bs, Kv)`` tile is not a DMA-able slice of the TPU's lane-tiled
    layout.

    ``lengths`` counts positions ALREADY WRITTEN to the pool, exclusive:
    row b attends K/V positions [0, lengths[b]).  The serving decode step
    scatters the new token's K/V at position L *then* attends it, so a
    caller replacing the jnp paged branch of ``layers.multihead_attention``
    (whose per-step ``pos`` is the pre-write count L) must pass ``L + 1``
    here after the scatter — otherwise each step omits the token being
    decoded from its own attention.

    H must be a multiple of Kv (query head r reads KV head r // (H / Kv)).
    """
    B, H, hd = q.shape
    NB, bs, Kv, _ = k_pool.shape
    MB = block_tables.shape[1]
    scale = scale if scale is not None else hd ** -0.5
    quantized = k_scale is not None
    kvp = dma_heads(Kv, k_pool.dtype.itemsize)
    if kvp != Kv:        # a copy of the pool, as the KV axis cannot DMA
        pad = ((0, 0), (0, 0), (0, kvp - Kv), (0, 0))
        k_pool, v_pool = jnp.pad(k_pool, pad), jnp.pad(v_pool, pad)
    t = blocks_per_step(MB, bs, kvp, hd, k_pool.dtype.itemsize)

    hbm = pl.BlockSpec(memory_space=pltpu.HBM)        # DMA'd by the kernel
    row_spec = pl.BlockSpec((None, H, hd), lambda b, c, bt, ln: (b, 0, 0))
    scratch = [pltpu.VMEM((2, t, bs, kvp, hd), k_pool.dtype),
               pltpu.VMEM((2, t, bs, kvp, hd), v_pool.dtype)]
    operands = [q, k_pool, v_pool]
    if quantized:                                     # lane-dense scales
        pad = ((0, 0), (0, 0), (0, -(-kvp // 128) * 128 - Kv))
        k_scale, v_scale = jnp.pad(k_scale, pad), jnp.pad(v_scale, pad)
        scratch += [pltpu.VMEM((2, t) + k_scale.shape[1:], jnp.float32)] * 2
        operands += [k_scale, v_scale]
    interpret = runtime.interpret_mode()
    if not interpret:    # else XLA may stage a pool that fits into VMEM
        operands[1:] = [pltpu.with_memory_space_constraint(x, pltpu.HBM)
                        for x in operands[1:]]
    scratch += [
        pltpu.SemaphoreType.DMA((2,)),                # one per buffer
        pltpu.SMEM((2,), jnp.int32),                  # buffer, fetched flag
        pltpu.VMEM((H, 128), jnp.float32),            # running max
        pltpu.VMEM((H, 128), jnp.float32),            # running denominator
        pltpu.VMEM((H, hd), jnp.float32),             # output accumulator
    ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                        # block_tables, lengths
        grid=(B, MB // t),
        in_specs=[row_spec] + [hbm] * (len(operands) - 1),
        out_specs=row_spec,
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, bs=bs, mb=MB, t=t,
                          g=H // Kv, rows=B, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        # steps run in order: each prefetches the next live one
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_attention",
    )(block_tables.reshape(-1), lengths, *operands)
