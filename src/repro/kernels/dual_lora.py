"""Fused dual-LoRA (AdaFusion, Eq. 7) serving kernel.

Computes  y = x·W + α·x·[(w1·A1 + w2·A2)(w1·B1 + w2·B2)]  without ever
materialising the merged factors (or the merged ΔW ∈ R^{K×N}) in HBM: the
per-tile merge  w1·A1 + w2·A2  happens in VMEM right before the MXU issue.

This is the FDLoRA inference hot path — after stage 3 every client serves
base + fused dual adapters; fusing the merge means switching fusion weights
(e.g. per-client weights in a multi-tenant server) costs nothing.

Same tiling scheme as lora_matmul (grid (M/bm, N/bn, K/bk), k innermost,
fp32 VMEM accumulators, rank padded to 128 lanes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import runtime


def _kernel(x_ref, w_ref, a1_ref, b1_ref, a2_ref, b2_ref, fw_ref,
            o_ref, acc_ref, zacc_ref, *, scale: float, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        zacc_ref[...] = jnp.zeros_like(zacc_ref)

    w1 = fw_ref[0]
    w2 = fw_ref[1]
    x = x_ref[...]
    acc_ref[...] += jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32)
    # on-chip Eq.7 merge of the A factors for this K-tile
    am = (w1 * a1_ref[...].astype(jnp.float32)
          + w2 * a2_ref[...].astype(jnp.float32)).astype(x.dtype)
    zacc_ref[...] += jnp.dot(x, am, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _finish():
        bm_t = (w1 * b1_ref[...].astype(jnp.float32)
                + w2 * b2_ref[...].astype(jnp.float32)).astype(x_ref.dtype)
        z = zacc_ref[...].astype(x_ref.dtype)
        lora = jnp.dot(z, bm_t, preferred_element_type=jnp.float32)
        o_ref[...] = (acc_ref[...] + scale * lora).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "bm", "bn", "bk"))
def dual_lora_matmul(x, w, a1, b1, a2, b2, fusion_w, scale: float = 1.0, *,
                     bm: int = 256, bn: int = 256, bk: int = 256):
    """x: (M,K), w: (K,N), a1/a2: (K,r), b1/b2: (r,N), fusion_w: (2,) fp32."""
    M, K = x.shape
    N = w.shape[1]
    r = a1.shape[1]
    r_pad = -(-r // 128) * 128
    pad_a = lambda a: jnp.pad(a, ((0, 0), (0, r_pad - r))) if r_pad != r else a
    pad_b = lambda b: jnp.pad(b, ((0, r_pad - r), (0, 0))) if r_pad != r else b
    a1, a2 = pad_a(a1).astype(x.dtype), pad_a(a2).astype(x.dtype)
    b1, b2 = pad_b(b1).astype(x.dtype), pad_b(b2).astype(x.dtype)
    w = w.astype(x.dtype)
    fusion_w = fusion_w.astype(jnp.float32)
    k_steps = K // bk

    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, k_steps=k_steps),
        grid=(M // bm, N // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk, r_pad), lambda i, j, k: (k, 0)),
            pl.BlockSpec((r_pad, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((bk, r_pad), lambda i, j, k: (k, 0)),
            pl.BlockSpec((r_pad, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, r_pad), jnp.float32),
        ],
        interpret=runtime.interpret_mode(),
    )(x, w, a1, b1, a2, b2, fusion_w)
