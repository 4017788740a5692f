"""Pallas-kernel microbenchmarks (interpret mode on CPU — correctness-scale
timings only; the roofline story for TPU lives in EXPERIMENTS.md §Perf) and
the jnp reference for context. ``derived`` reports achieved GFLOP/s of the
reference path and the kernel/ref agreement."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common as C
from repro.kernels.lora_matmul import lora_matmul
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import flash_attention_ref, lora_matmul_ref


def run() -> list:
    rng = np.random.default_rng(0)
    rows = []

    M = K = N = 512
    r = 16
    x = jnp.asarray(rng.standard_normal((M, K)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((K, N)) * 0.05, jnp.bfloat16)
    a = jnp.asarray(rng.standard_normal((K, r)) * 0.05, jnp.float32)
    b = jnp.asarray(rng.standard_normal((r, N)) * 0.05, jnp.float32)
    ref_fn = jax.jit(lambda: lora_matmul_ref(x, w, a, b, 2.0))
    out_ref, us_ref = C.timed(lambda: jax.block_until_ready(ref_fn()))
    flops = 2 * M * K * N + 2 * M * K * r + 2 * M * r * N
    rows.append(C.row("kernels/lora_matmul_ref_512", us_ref,
                      f"gflops={flops / us_ref / 1e3:.2f}"))
    out_k, us_k = C.timed(
        lambda: jax.block_until_ready(lora_matmul(x, w, a, b, 2.0)))
    err = float(jnp.max(jnp.abs(out_k.astype(jnp.float32)
                                - out_ref.astype(jnp.float32))))
    rows.append(C.row("kernels/lora_matmul_pallas_interp_512", us_k,
                      f"max_err_vs_ref={err:.4f}"))

    B, H, S, d = 1, 2, 256, 64
    q = jnp.asarray(rng.standard_normal((B, H, S, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, H, S, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, H, S, d)), jnp.bfloat16)
    rfn = jax.jit(lambda: flash_attention_ref(q, k, v, causal=True))
    o_ref, us_r = C.timed(lambda: jax.block_until_ready(rfn()))
    rows.append(C.row("kernels/attention_ref_256", us_r, "baseline"))
    o_k, us_f = C.timed(lambda: jax.block_until_ready(
        flash_attention(q, k, v, causal=True)))
    err = float(jnp.max(jnp.abs(o_k.astype(jnp.float32)
                                - o_ref.astype(jnp.float32))))
    rows.append(C.row("kernels/flash_attention_pallas_interp_256", us_f,
                      f"max_err_vs_ref={err:.4f}"))
    return rows


if __name__ == "__main__":
    from repro import runtime
    runtime.enable_compile_cache()
    for r in run():
        print(r)
