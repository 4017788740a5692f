"""Batched multi-tenant LoRA matmul: per-row adapter routing in one kernel.

Multi-tenant serving (S-LoRA style) keeps *one* base-model program and a
stacked bank of client adapters resident; every row of a batch may belong to
a different client.  The kernel computes

    y[i] = x[i]·W + α · x[i]·A[g[i]]·B[g[i]]

for per-row adapter indices ``g`` over banks ``A: (C, K, r)``,
``B: (C, r, N)`` — the gathered per-row factors ``A[g]]`` (M·K·r) are never
materialised in HBM.  The routing rides as a one-hot matrix (M, C): the bank
is laid out as a single 2-D operand ``(K, C·r_pad)`` so the rank expansion is
one MXU matmul ``x @ A_all`` whose per-row client column-block is selected by
a VPU masked reduction against the one-hot.  The B-side applies the inverse
trick (mask-expand z to (bm, C·r_pad), one matmul with ``(C·r_pad, N)``).

Cost note: the A-side issues C·r_pad rank columns instead of r — the classic
dense-MXU batched-LoRA trade (a gather/sort-free BGMV).  With C ≲ 32 and
r ≤ 128 this stays well under the base O(K·N) term.

The dual variant fuses FDLoRA Eq. 7 *per request*: the personalized bank is
per-client, the global adapter θ_s is — as in the paper — one tree shared by
every client, and each row carries its own fusion weights (w1, w2):

    y[i] = x[i]·W + α · x[i]·(w1[i]A1[g[i]] + w2[i]A2)(w1[i]B1[g[i]] + w2[i]B2)

so switching tenants (or re-tuning fusion weights) costs nothing at serve
time.  Same tiling scheme as lora_matmul: grid (M/bm, N/bn, K/bk), k
innermost, fp32 VMEM accumulators, rank padded to 128 lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import runtime


def _kernel(x_ref, w_ref, oh_ref, *rest,
            scale: float, k_steps: int, n_clients: int, quantized: bool,
            ranked: bool):
    rest = list(rest)
    # int8 banks ride with one combined per-client scale vector
    # (s_a[c]·s_b[c], lane-padded): scalar scales commute through the
    # matmul chain, so dequant collapses to one per-row factor at finish
    cs_ref = rest.pop(0) if quantized else None
    # ragged banks ride a per-client effective-rank vector (lane-padded):
    # the finish step masks rank columns >= the row's rank to exact zero,
    # so a slot's padded columns can never contribute
    rk_ref = rest.pop(0) if ranked else None
    a_ref, b_ref, o_ref, acc_ref, zacc_ref = rest

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        zacc_ref[...] = jnp.zeros_like(zacc_ref)

    x = x_ref[...]                                  # (bm, bk)
    # one-hot arrives lane-padded to 128; only the first C columns are live
    oh = oh_ref[:, :n_clients]                      # (bm, C) fp32 one-hot
    acc_ref[...] += jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32)
    # rank expansion against ALL resident adapters: (bm, bk) @ (bk, C*r_pad)
    a = a_ref[...]
    if quantized:
        a = a.astype(x.dtype)       # int8 in [-127, 127] is exact in bf16
    xa = jnp.dot(x, a, preferred_element_type=jnp.float32)
    m = xa.shape[0]
    # per-row client select (the on-chip gather): (bm, C, r_pad) ⊙ one-hot
    z = jnp.sum(xa.reshape(m, n_clients, -1) * oh[:, :, None], axis=1)
    zacc_ref[...] += z

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _finish():
        z = zacc_ref[...]                           # (bm, r_pad) fp32
        if ranked:
            # per-row effective rank via the same one-hot select; VPU mask
            # zeroes padded rank columns before they can reach the B matmul
            rk = jnp.sum(oh * rk_ref[:1, :n_clients], axis=1,
                         keepdims=True)             # (bm, 1) fp32
            col = jax.lax.broadcasted_iota(jnp.float32, z.shape, 1)
            z = jnp.where(col < rk, z, 0.0)
        # inverse trick: scatter z into the row's client column-block so one
        # matmul against the stacked (C*r_pad, bn) B-bank applies B[g[i]]
        zt = (z[:, None, :] * oh[:, :, None]).reshape(m, -1).astype(x.dtype)
        b = b_ref[...]
        if quantized:
            b = b.astype(x.dtype)
        lora = jnp.dot(zt, b, preferred_element_type=jnp.float32)
        if quantized:
            # per-row combined dequant scale via the same one-hot select
            row_scale = jnp.sum(oh * cs_ref[:1, :n_clients], axis=1,
                                keepdims=True)      # (bm, 1)
            lora = lora * row_scale
        o_ref[...] = (acc_ref[...] + scale * lora).astype(o_ref.dtype)


def _dual_kernel(x_ref, w_ref, oh_ref, fw_ref, a1_ref, b1_ref, a2_ref, b2_ref,
                 o_ref, acc_ref, zacc_ref, *,
                 scale: float, k_steps: int, n_clients: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        zacc_ref[...] = jnp.zeros_like(zacc_ref)

    x = x_ref[...]
    oh = oh_ref[:, :n_clients]                      # (bm, C); lane-padded in
    w1 = fw_ref[:, 0:1]                             # (bm, 1) fp32
    w2 = fw_ref[:, 1:2]                             # (fw lane-padded too)
    acc_ref[...] += jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32)
    xa1 = jnp.dot(x, a1_ref[...], preferred_element_type=jnp.float32)
    m = xa1.shape[0]
    za = jnp.sum(xa1.reshape(m, n_clients, -1) * oh[:, :, None], axis=1)
    zg = jnp.dot(x, a2_ref[...], preferred_element_type=jnp.float32)
    # on-chip Eq. 7 merge of the A factors, per row: x·(w1 A1[g] + w2 A2)
    zacc_ref[...] += w1 * za + w2 * zg

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _finish():
        z = zacc_ref[...]                           # (bm, r_pad) fp32
        zt = (z[:, None, :] * oh[:, :, None]).reshape(m, -1).astype(x.dtype)
        l1 = jnp.dot(zt, b1_ref[...], preferred_element_type=jnp.float32)
        l2 = jnp.dot(z.astype(x_ref.dtype), b2_ref[...],
                     preferred_element_type=jnp.float32)
        lora = w1 * l1 + w2 * l2                    # z·(w1 B1[g] + w2 B2)
        o_ref[...] = (acc_ref[...] + scale * lora).astype(o_ref.dtype)


def _bank_2d(a, b, r_pad: int, dtype):
    """(C, K, r)/(C, r, N) banks -> (K, C*r_pad)/(C*r_pad, N) kernel layout."""
    C, K, r = a.shape
    N = b.shape[2]
    if r_pad != r:
        a = jnp.pad(a, ((0, 0), (0, 0), (0, r_pad - r)))
        b = jnp.pad(b, ((0, 0), (0, r_pad - r), (0, 0)))
    a2 = a.transpose(1, 0, 2).reshape(K, C * r_pad).astype(dtype)
    b2 = b.reshape(C * r_pad, N).astype(dtype)
    return a2, b2


def _lane_pad(x, mult: int = 128):
    """Zero-pad the last dim to a lane-aligned multiple (TPU VMEM windows
    want 128-lane minor dims; zeros are inert for both operands)."""
    pad = (-x.shape[-1]) % mult
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    return x


@functools.partial(jax.jit, static_argnames=("scale", "bm", "bn", "bk"))
def batched_lora_matmul(x, w, a, b, adapter_ids, scale: float = 1.0, *,
                        a_scale=None, b_scale=None, ranks=None,
                        bm: int = 256, bn: int = 256, bk: int = 256):
    """x: (M, K), w: (K, N), a: (C, K, r), b: (C, r, N),
    adapter_ids: (M,) int32 in [0, C) -> (M, N).

    With int8 banks pass ``a_scale``/``b_scale`` ((C,) fp32 per-client
    quantization scales): the banks stay int8 in HBM/VMEM and the kernel
    applies one combined ``s_a[g[i]]·s_b[g[i]]`` factor per row at its
    finish step — scalar scales commute through the LoRA chain, so no
    dequantized bank is ever materialised.

    With ragged-rank banks pass ``ranks`` ((C,) int32 effective rank per
    slot, <= r): the finish step zeroes each row's rank columns at or
    beyond its slot's effective rank, so padded rank columns contribute
    exact zeros regardless of what lives in them.

    M, K, N must tile by (bm, bn, bk); r is zero-padded to 128 internally.
    """
    M, K = x.shape
    N = w.shape[1]
    C, _, r = a.shape
    quantized = a_scale is not None
    ranked = ranks is not None
    r_pad = -(-r // 128) * 128
    a2, b2 = _bank_2d(a, b, r_pad, jnp.int8 if quantized else x.dtype)
    w = w.astype(x.dtype)
    oh = _lane_pad(jax.nn.one_hot(adapter_ids, C, dtype=jnp.float32))
    C_lanes = oh.shape[1]
    k_steps = K // bk

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        pl.BlockSpec((bm, C_lanes), lambda i, j, k: (i, 0)),
    ]
    operands = [x, w, oh]
    if quantized:
        cs = (a_scale.astype(jnp.float32) * b_scale.astype(jnp.float32))
        cs2 = _lane_pad(cs[None, :])                # (1, C_lanes)
        in_specs.append(pl.BlockSpec((1, C_lanes), lambda i, j, k: (0, 0)))
        operands.append(cs2)
    if ranked:
        rk2 = _lane_pad(ranks.astype(jnp.float32)[None, :])  # (1, C_lanes)
        in_specs.append(pl.BlockSpec((1, C_lanes), lambda i, j, k: (0, 0)))
        operands.append(rk2)
    in_specs += [
        pl.BlockSpec((bk, C * r_pad), lambda i, j, k: (k, 0)),
        pl.BlockSpec((C * r_pad, bn), lambda i, j, k: (0, j)),
    ]
    operands += [a2, b2]

    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, k_steps=k_steps, n_clients=C,
                          quantized=quantized, ranked=ranked),
        grid=(M // bm, N // bn, k_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, r_pad), jnp.float32),
        ],
        interpret=runtime.interpret_mode(),
        name="batched_lora_matmul",
    )(*operands)


@functools.partial(jax.jit, static_argnames=("scale", "bm", "bn", "bk"))
def batched_dual_lora_matmul(x, w, a1, b1, a2, b2, adapter_ids, fusion_w,
                             scale: float = 1.0, *,
                             bm: int = 256, bn: int = 256, bk: int = 256):
    """Per-request Eq. 7: x: (M, K), w: (K, N), a1/b1: (C, K, r)/(C, r, N)
    personalized bank, a2/b2: (K, r)/(r, N) shared global θ_s,
    adapter_ids: (M,) int32, fusion_w: (M, 2) fp32 per-row [w1, w2]."""
    M, K = x.shape
    N = w.shape[1]
    C, _, r = a1.shape
    r_pad = -(-r // 128) * 128
    a1p, b1p = _bank_2d(a1, b1, r_pad, x.dtype)
    if r_pad != r:
        a2 = jnp.pad(a2, ((0, 0), (0, r_pad - r)))
        b2 = jnp.pad(b2, ((0, r_pad - r), (0, 0)))
    a2 = a2.astype(x.dtype)
    b2 = b2.astype(x.dtype)
    w = w.astype(x.dtype)
    oh = _lane_pad(jax.nn.one_hot(adapter_ids, C, dtype=jnp.float32))
    C_lanes = oh.shape[1]
    fusion_w = _lane_pad(fusion_w.astype(jnp.float32))
    F_lanes = fusion_w.shape[1]
    k_steps = K // bk

    return pl.pallas_call(
        functools.partial(_dual_kernel, scale=scale, k_steps=k_steps,
                          n_clients=C),
        grid=(M // bm, N // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, C_lanes), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bm, F_lanes), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bk, C * r_pad), lambda i, j, k: (k, 0)),
            pl.BlockSpec((C * r_pad, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((bk, r_pad), lambda i, j, k: (k, 0)),
            pl.BlockSpec((r_pad, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, r_pad), jnp.float32),
        ],
        interpret=runtime.interpret_mode(),
        name="batched_dual_lora_matmul",
    )(x, w, oh, fusion_w, a1p, b1p, a2, b2)
