"""jit'd high-level wrappers over the Pallas kernels.

These adapt model-layer calling conventions ((B, S, d) activations, GQA
head layouts, adapter dicts) to the 2-D kernel interfaces. The kernels run
compiled on TPU and interpreted on CPU (``repro.runtime.interpret_mode``).

The model layer keeps pure-jnp math by default (``layers.dense`` /
``multihead_attention``) — the kernels are drop-in replacements for the
serving/training hot paths, exercised by tests and the §Perf iterations.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.batched_lora import batched_lora_matmul
from repro.kernels.dual_lora import dual_lora_matmul
from repro.kernels.flash_attention import flash_attention
from repro.kernels.lora_matmul import lora_matmul
from repro.kernels.paged_attention import paged_attention
from repro.kernels.paged_prefill import (paged_prefill_attention,
                                         paged_scatter, paged_scatter_quant)


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


def _per_data_shard(kernel, rows, shared):
    """``kernel(*rows, *shared)``, run once per "data" shard of the batch
    when the mesh in scope (``jax.set_mesh``) splits it: XLA cannot
    partition a Mosaic kernel, so under a multi-device mesh each device
    calls it on its own rows (``rows``: leading batch axis, split on
    "data") against the whole of ``shared`` (the block pools, replicated).
    ``shared`` entries may be None.  Without such a mesh: a plain call."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.shape.get("data", 1) == 1:
        return kernel(*rows, *shared)
    return jax.shard_map(
        lambda rows, shared: kernel(*rows, *shared),
        in_specs=(P("data"), P()), out_specs=P("data"),
        check_vma=False)(tuple(rows), tuple(shared))


def lora_dense(x: jnp.ndarray, w: jnp.ndarray, adapter: Dict[str, jnp.ndarray],
               scale: float, *, block: int = 256) -> jnp.ndarray:
    """(..., K) @ (K, N) + LoRA via the fused kernel. Pads M/K/N to tiles."""
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w.shape[1]
    x2 = x.reshape(-1, K)
    x2, M = _pad_to(x2, 0, block)
    x2p, _ = _pad_to(x2, 1, block)
    wp, _ = _pad_to(_pad_to(w, 0, block)[0], 1, block)
    ap, _ = _pad_to(adapter["a"], 0, block)
    bp, _ = _pad_to(adapter["b"], 1, block)
    y = lora_matmul(x2p.astype(jnp.bfloat16), wp, ap, bp, scale,
                    bm=block, bn=block, bk=block)
    return y[:M, :N].reshape(*lead, N)


def fused_dual_lora_dense(x: jnp.ndarray, w: jnp.ndarray,
                          ad_p: Dict, ad_s: Dict, fusion_w: jnp.ndarray,
                          scale: float, *, block: int = 256) -> jnp.ndarray:
    """FDLoRA serving path: base + Eq.7-merged dual adapters, one kernel."""
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w.shape[1]
    x2 = x.reshape(-1, K)
    x2, M = _pad_to(x2, 0, block)
    x2p, _ = _pad_to(x2, 1, block)
    wp, _ = _pad_to(_pad_to(w, 0, block)[0], 1, block)
    a1, _ = _pad_to(ad_p["a"], 0, block)
    b1, _ = _pad_to(ad_p["b"], 1, block)
    a2, _ = _pad_to(ad_s["a"], 0, block)
    b2, _ = _pad_to(ad_s["b"], 1, block)
    y = dual_lora_matmul(x2p.astype(jnp.bfloat16), wp, a1, b1, a2, b2,
                         fusion_w, scale, bm=block, bn=block, bk=block)
    return y[:M, :N].reshape(*lead, N)


def batched_lora_dense(x: jnp.ndarray, w: jnp.ndarray,
                       bank: Dict[str, jnp.ndarray], adapter_ids: jnp.ndarray,
                       scale: float, *, block: int = 256) -> jnp.ndarray:
    """Multi-tenant dense: (B, ..., K) @ (K, N) with per-*request* adapter
    routing. ``bank`` = {"a": (C, K, r), "b": (C, r, N)}; an int8 bank also
    carries ``a_scale``/``b_scale`` ((C,) fp32) which the kernel applies as
    one per-row combined factor. ``adapter_ids`` is (B,) int32 and
    broadcasts over the trailing (sequence) axes of ``x``.

    Ragged-rank banks arrive with per-bucket LISTS at each leaf (see
    ``AdapterRegistry(ranks=[...])``): the buckets are concatenated along
    the client axis in global-slot order, small buckets rank-padded up to
    the largest bucket, and the kernel gets a per-slot effective-rank
    vector so padded rank columns contribute exact zeros.

    Pads M/K/N to tiles; padded rows route to slot 0 and are sliced away."""
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w.shape[1]
    rows_per_item = 1
    for s in lead[1:]:
        rows_per_item *= s
    g = jnp.repeat(adapter_ids.astype(jnp.int32), rows_per_item)
    x2 = x.reshape(-1, K)
    x2, M = _pad_to(x2, 0, block)
    g = jnp.pad(g, (0, x2.shape[0] - M))
    x2p, _ = _pad_to(x2, 1, block)
    wp, _ = _pad_to(_pad_to(w, 0, block)[0], 1, block)
    ranks = None
    if isinstance(bank["a"], (list, tuple)):
        # ragged: concat buckets on the client axis at the max bucket rank;
        # the kernel's per-slot rank mask keeps the padding exact
        r_max = max(ab.shape[-1] for ab in bank["a"])
        a_all = jnp.concatenate(
            [jnp.pad(ab, ((0, 0), (0, 0), (0, r_max - ab.shape[-1])))
             for ab in bank["a"]], axis=0)
        b_all = jnp.concatenate(
            [jnp.pad(bb, ((0, 0), (0, r_max - bb.shape[1]), (0, 0)))
             for bb in bank["b"]], axis=0)
        ranks = jnp.concatenate(
            [jnp.full((ab.shape[0],), ab.shape[-1], jnp.int32)
             for ab in bank["a"]])
        a_scale = (jnp.concatenate(bank["a_scale"])
                   if "a_scale" in bank else None)
        b_scale = (jnp.concatenate(bank["b_scale"])
                   if "b_scale" in bank else None)
        bank = {"a": a_all, "b": b_all}
        if a_scale is not None:
            bank["a_scale"], bank["b_scale"] = a_scale, b_scale
    ap, _ = _pad_to(bank["a"], 1, block)
    bp, _ = _pad_to(bank["b"], 2, block)
    y = batched_lora_matmul(x2p.astype(jnp.bfloat16), wp, ap, bp, g, scale,
                            a_scale=bank.get("a_scale"),
                            b_scale=bank.get("b_scale"), ranks=ranks,
                            bm=block, bn=block, bk=block)
    return y[:M, :N].reshape(*lead, N)


def paged_gqa_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                        v_pool: jnp.ndarray, block_tables: jnp.ndarray,
                        lengths: jnp.ndarray, *,
                        k_scale: Optional[jnp.ndarray] = None,
                        v_scale: Optional[jnp.ndarray] = None
                        ) -> jnp.ndarray:
    """Model-layout adapter for the paged decode kernel.

    q: (B, 1, H, hd) (or (B, H, hd)) as produced by the serving decode step;
    k_pool/v_pool: (NB, bs, Kv, hd). Pads head_dim to 128 lanes (zero key
    lanes leave q·k unchanged; zero value lanes are sliced away) and keeps
    the block-table gather inside the kernel. Returns q's shape.

    With int8 pools pass ``k_scale``/``v_scale`` ((NB, bs, Kv) fp32) —
    they carry no head-dim axis so the lane padding leaves them alone and
    the kernel dequantizes each DMA'd block tile in VMEM.

    ``lengths`` is exclusive (positions already written): when dropping this
    into the paged branch of ``layers.multihead_attention``, pass the
    per-row step position + 1 — i.e. AFTER scattering the step's K/V — so
    the token being decoded attends itself (see ``paged_attention``)."""
    squeeze = q.ndim == 4
    if squeeze:
        q = q[:, 0]
    hd = q.shape[-1]
    scale = hd ** -0.5                       # scale from the *unpadded* head
    qp, _ = _pad_to(q, 2, 128)
    kp, _ = _pad_to(k_pool, 3, 128)
    vp, _ = _pad_to(v_pool, 3, 128)
    o = _per_data_shard(
        lambda q, bt, ln, kp, vp, ks, vs: paged_attention(
            q, kp, vp, bt, ln, k_scale=ks, v_scale=vs, scale=scale),
        (qp, block_tables.astype(jnp.int32), lengths.astype(jnp.int32)),
        (kp, vp, k_scale, v_scale))[..., :hd]
    return o[:, None] if squeeze else o


def paged_prefill_gqa_attention(q: jnp.ndarray, k_new: jnp.ndarray,
                                v_new: jnp.ndarray, k_pool: jnp.ndarray,
                                v_pool: jnp.ndarray,
                                block_tables: jnp.ndarray,
                                lengths: jnp.ndarray,
                                n_new: jnp.ndarray, *,
                                k_scale: Optional[jnp.ndarray] = None,
                                v_scale: Optional[jnp.ndarray] = None):
    """Model-layout adapter for the chunked paged-prefill kernel.

    q/k_new/v_new: (B, T, H|Kv, hd) — a whole prompt chunk per serving slot,
    as produced by the serving prefill step; k_pool/v_pool: (NB, bs, Kv, hd).
    Scatters the chunk's K/V into each row's block-table slots (positions
    ``lengths[b] .. lengths[b] + n_new[b] - 1``; ragged tails with
    ``t >= n_new[b]`` land in scratch block 0), then runs the Pallas kernel
    over the updated pools — the O(T) scatter is materialised, the
    O(context) gather never is.  Pads head_dim to 128 lanes.

    Returns (out (B, T, H, hd), new_k_pool, new_v_pool).  With int8 pools
    pass ``k_scale``/``v_scale`` ((NB, bs, Kv) fp32): the chunk quantizes
    at scatter time and the return grows to
    (out, new_k_pool, new_v_pool, new_k_scale, new_v_scale)."""
    hd = q.shape[-1]
    quantized = k_scale is not None
    if quantized:
        kp, vp, ks, vs = paged_scatter_quant(
            k_pool, v_pool, k_scale, v_scale, k_new, v_new,
            block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
            n_new.astype(jnp.int32))
    else:
        kp, vp = paged_scatter(k_pool, v_pool, k_new, v_new,
                               block_tables.astype(jnp.int32),
                               lengths.astype(jnp.int32),
                               n_new.astype(jnp.int32))
        ks = vs = None

    scale = hd ** -0.5                       # scale from the *unpadded* head
    qp, _ = _pad_to(q, 3, 128)
    kpp, _ = _pad_to(kp, 3, 128)
    vpp, _ = _pad_to(vp, 3, 128)
    o = _per_data_shard(
        lambda q, bt, ln, kp, vp, ks, vs: paged_prefill_attention(
            q, kp, vp, bt, ln, k_scale=ks, v_scale=vs, scale=scale),
        (qp, block_tables.astype(jnp.int32), lengths.astype(jnp.int32)),
        (kpp, vpp, ks, vs))[..., :hd]
    if quantized:
        return o, kp, vp, ks, vs
    return o, kp, vp


def gqa_flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool = True, sliding_window: int = 0
                        ) -> jnp.ndarray:
    """GQA layout adapter: q (B, Sq, H, d), k/v (B, Sk, Kv, d) as produced by
    the model layer -> flash kernel layout, repeating KV heads."""
    B, Sq, H, d = q.shape
    Kv = k.shape[2]
    rep = H // Kv
    qt = q.transpose(0, 2, 1, 3)
    kt = jnp.repeat(k.transpose(0, 2, 1, 3), rep, axis=1)
    vt = jnp.repeat(v.transpose(0, 2, 1, 3), rep, axis=1)
    o = flash_attention(qt, kt, vt, causal=causal,
                        sliding_window=sliding_window)
    return o.transpose(0, 2, 1, 3)
