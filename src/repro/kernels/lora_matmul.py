"""Fused LoRA matmul Pallas kernel: y = x·W + α·(x·A)·B in one HBM pass.

Why a kernel (DESIGN.md §2): the naive LoRA path writes z = x·A (M×r) and
α·z·B (M×N) to HBM between matmuls and re-reads x twice. Fusing keeps the
rank-r expansion entirely in VMEM: per (i, j) output tile we stream K-tiles
of x and W once, accumulate both the base product and the x·A product in
VMEM scratch, and apply ·B once on the final K-step.

Tiling: grid (M/bm, N/bn, K/bk), k innermost (sequential reduction — scratch
accumulators persist across the k steps of a fixed (i, j)). Block shapes are
MXU-aligned multiples of 128 on every matmul dim; the LoRA rank rides as a
VMEM-resident (bm, r_pad) fp32 accumulator (r zero-padded to 128 lanes by the
wrapper, so the tile is lane-aligned).

VMEM budget per step (defaults bm=bn=bk=256, r_pad=128, bf16 in / fp32 acc):
x (256·256·2) + w (256·256·2) + a (256·128·2) + b (128·256·2) + acc fp32
(256·256·4) + zacc fp32 (256·128·4) ≈ 0.8 MB — comfortably inside the
~16 MB VMEM of a v5e core, leaving room for double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import runtime


def _kernel(x_ref, w_ref, a_ref, b_ref, o_ref, acc_ref, zacc_ref, *,
            scale: float, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        zacc_ref[...] = jnp.zeros_like(zacc_ref)

    x = x_ref[...]
    # base product: (bm, bk) @ (bk, bn), fp32 accumulation on the MXU
    acc_ref[...] += jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32)
    # rank-r expansion: (bm, bk) @ (bk, r_pad)
    zacc_ref[...] += jnp.dot(x, a_ref[...], preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _finish():
        z = zacc_ref[...].astype(x_ref.dtype)   # (bm, r_pad)
        lora = jnp.dot(z, b_ref[...], preferred_element_type=jnp.float32)
        o_ref[...] = (acc_ref[...] + scale * lora).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "bm", "bn", "bk"))
def lora_matmul(x, w, a, b, scale: float = 1.0, *, bm: int = 256,
                bn: int = 256, bk: int = 256):
    """x: (M, K), w: (K, N), a: (K, r), b: (r, N) -> (M, N).

    M, K, N must tile by (bm, bk, bn); r is zero-padded to 128 internally.
    """
    M, K = x.shape
    N = w.shape[1]
    r = a.shape[1]
    r_pad = -(-r // 128) * 128
    if r_pad != r:
        a = jnp.pad(a, ((0, 0), (0, r_pad - r)))
        b = jnp.pad(b, ((0, r_pad - r), (0, 0)))
    a = a.astype(x.dtype)
    b = b.astype(x.dtype)
    w = w.astype(x.dtype)
    k_steps = K // bk

    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, k_steps=k_steps),
        grid=(M // bm, N // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
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
    )(x, w, a, b)
