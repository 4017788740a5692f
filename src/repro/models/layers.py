"""Shared neural-net primitives (pure-functional, param-dict based).

Conventions
-----------
* Parameters are nested dicts of ``jnp.ndarray``; init functions mirror the
  apply functions. Every init has a matching ``*_specs`` producing a
  :class:`jax.sharding.PartitionSpec` tree with axes named ``data`` / ``model``
  (mesh axis names are bound later by the launcher).
* Linear layers optionally take a LoRA adapter ``(A, B)``; the adapter path is
  ``y = x@W + (alpha/r) * (x@A)@B`` with the base weight frozen.
* All matmuls accumulate in fp32 (``preferred_element_type``) and cast back.
* The LoRA update, attention and the MLP run under ``jax.named_scope``s
  ``lora``, ``attn`` and ``mlp`` (a Mamba-2 mixer's core under ``ssm``,
  ``models/mamba2.py``): op metadata only, so a profiler trace can say
  which layer each device op belongs to.
"""
from __future__ import annotations

import dataclasses
from functools import partial, wraps
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import runtime
from repro.kernels import ops as kernel_ops
from repro.kernels.paged_prefill import paged_scatter, paged_scatter_quant

Params = Dict[str, Any]

# Mesh-axis aliases used in spec trees. The launcher rewrites "model"/"data"
# to real mesh axes; "None" dims are replicated.
MODEL = "model"
DATA = "data"


def _scoped(name: str):
    """Decorator: trace the function under ``jax.named_scope(name)``, a
    fresh scope per call (so recursion nests)."""
    def deco(fn):
        @wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return deco


# ---------------------------------------------------------------------------
# dtype helpers
# ---------------------------------------------------------------------------

def dt(name: str):
    return jnp.dtype(name)


def maybe_shard(x: jnp.ndarray, spec: P) -> jnp.ndarray:
    """with_sharding_constraint against the mesh set by ``jax.set_mesh``;
    a no-op when tracing without one (single device), and axes the spec
    names but the mesh lacks are dropped."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    axes = set(mesh.axis_names)
    fixed = []
    for entry in spec:
        names = entry if isinstance(entry, tuple) else (entry,)
        kept = tuple(n for n in names if n is None or n in axes)
        kept = tuple(n for n in kept if n is not None)
        fixed.append(kept if len(kept) > 1 else (kept[0] if kept else None))
    return jax.lax.with_sharding_constraint(x, P(*fixed))


def matmul(x, w, *, out_dtype=None):
    """x @ w with fp32 accumulation."""
    y = jnp.matmul(x, w, preferred_element_type=jnp.float32)
    return y.astype(out_dtype or x.dtype)


@_scoped("lora")
def lora_delta(x: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
               adapter_ids: Optional[jnp.ndarray] = None,
               a_scale: Optional[jnp.ndarray] = None,
               b_scale: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """fp32 LoRA update (x·A)·B, single-tenant or banked.

    Single-tenant: ``a: (d_in, r)``, ``b: (r, d_out)``. Multi-tenant serving:
    ``a: (C, d_in, r)``, ``b: (C, r, d_out)`` stacked client banks with
    ``adapter_ids: (B,)`` int32 selecting one adapter per batch row of
    ``x: (B, S, d_in)`` (the pure-jnp oracle of the batched Pallas kernel —
    the kernel path never materialises the per-row gather in HBM).

    int8 banks (``AdapterRegistry(bank_dtype="int8")``) carry one fp32
    quantization scale per client and factor: ``a_scale``/``b_scale`` (C,).
    The gathered per-row factors dequantize before the fp32 matmul chain.

    Ragged-rank banks (``AdapterRegistry(ranks=[...])``) arrive as
    per-bucket LISTS of stacked arrays: rows route to the bucket holding
    their global slot (bucket boundaries are static — read from shapes — so
    the select stays jit/scan-stable).  Each bucket evaluates at its own
    rank; zero rank-padding inside a bucket is arithmetically inert, so the
    result is bitwise the per-client native-rank delta.
    """
    if isinstance(a, (list, tuple)):  # ragged bank: route rows by bucket
        if adapter_ids is None:
            raise ValueError("banked LoRA leaves need adapter_ids")
        out, off = None, 0
        for i, (ab, bb) in enumerate(zip(a, b)):
            cb = ab.shape[0]
            local = jnp.clip(adapter_ids - off, 0, cb - 1)
            d = lora_delta(x, ab, bb, local,
                           a_scale[i] if a_scale is not None else None,
                           b_scale[i] if b_scale is not None else None)
            in_bucket = (adapter_ids >= off) & (adapter_ids < off + cb)
            mask = in_bucket.reshape((-1,) + (1,) * (d.ndim - 1))
            out = d if out is None else jnp.where(mask, d, out)
            off += cb
        return out
    xf = x.astype(jnp.float32)
    if a.ndim == 3:  # banked: per-row client routing
        if adapter_ids is None:
            raise ValueError("banked LoRA leaves need adapter_ids")
        ag = jnp.take(a.astype(jnp.float32), adapter_ids, axis=0)  # (B, d, r)
        bg = jnp.take(b.astype(jnp.float32), adapter_ids, axis=0)  # (B, r, n)
        if a_scale is not None:
            ag = ag * jnp.take(a_scale, adapter_ids, axis=0)[:, None, None]
            bg = bg * jnp.take(b_scale, adapter_ids, axis=0)[:, None, None]
        z = jnp.einsum("b...k,bkr->b...r", xf, ag)
        return jnp.einsum("b...r,brn->b...n", z, bg)
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    if a_scale is not None:
        af = af * a_scale
        bf = bf * b_scale
    z = jnp.matmul(xf, af)
    return jnp.matmul(z, bf)


def lora_pair(adapters: Optional[Params], name: str):
    """The LoRA tuple :func:`dense` expects for one adapter target, or
    ``None`` when the target carries no adapter.  fp32 targets yield
    ``(A, B)``; int8 bank targets (which store per-client ``a_scale`` /
    ``b_scale`` leaves next to the factors) yield the 4-tuple
    ``(A, B, a_scale, b_scale)``.  Every layer that routes adapters into
    ``dense`` goes through this helper so the int8 layout has exactly one
    decoding site."""
    if adapters is None or name not in adapters:
        return None
    ad = adapters[name]
    if "a_scale" in ad:
        return (ad["a"], ad["b"], ad["a_scale"], ad["b_scale"])
    return (ad["a"], ad["b"])


def dense(x: jnp.ndarray, w: jnp.ndarray,
          lora: Optional[Tuple[jnp.ndarray, ...]] = None,
          lora_scale: float = 1.0,
          adapter_ids: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Linear layer with optional LoRA adapter.

    ``lora`` is ``(A, B)`` with A: (d_in, r) fp32, B: (r, d_out) fp32 — or
    banked ``(C, d_in, r)`` / ``(C, r, d_out)`` with per-row ``adapter_ids``
    (multi-tenant serving; see :func:`lora_delta`), optionally extended to
    ``(A, B, a_scale, b_scale)`` for int8 banks (see :func:`lora_pair`).
    The adapter path always computes in fp32 (adapters are the trainable,
    numerically sensitive part) and is added to the frozen base output.
    """
    y = matmul(x, w.astype(x.dtype))
    if lora is not None:
        a, b, *scales = lora
        z = lora_delta(x, a, b, adapter_ids, *scales)
        y = (y.astype(jnp.float32) + lora_scale * z).astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Normalisation
# ---------------------------------------------------------------------------

def init_norm(key, d: int, norm_type: str, dtype) -> Params:
    if norm_type == "rmsnorm":
        return {"scale": jnp.ones((d,), dtype=jnp.float32)}
    if norm_type == "layernorm":
        return {"scale": jnp.ones((d,), dtype=jnp.float32),
                "bias": jnp.zeros((d,), dtype=jnp.float32)}
    if norm_type == "nonparametric":
        return {}
    raise ValueError(norm_type)


def norm_specs(norm_type: str) -> Params:
    if norm_type == "rmsnorm":
        return {"scale": P(None)}
    if norm_type == "layernorm":
        return {"scale": P(None), "bias": P(None)}
    return {}


def apply_norm(params: Params, x: jnp.ndarray, norm_type: str,
               eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if norm_type == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * params["scale"]
    else:
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + eps)
        if norm_type == "layernorm":
            y = y * params["scale"] + params["bias"]
        # nonparametric (OLMo): no affine params
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, n_heads, head_dim); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                       # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]                    # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA / MQA, optional sliding window, optional logit softcap)
# ---------------------------------------------------------------------------

def init_attention(key, cfg, dtype) -> Params:
    d, H, Kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = d ** -0.5
    return {
        "wq": (jax.random.normal(k1, (d, H * hd)) * s).astype(dtype),
        "wk": (jax.random.normal(k2, (d, Kv * hd)) * s).astype(dtype),
        "wv": (jax.random.normal(k3, (d, Kv * hd)) * s).astype(dtype),
        "wo": (jax.random.normal(k4, (H * hd, d)) * s).astype(dtype),
    }


def attention_specs(cfg) -> Params:
    # Head (output) dim of projections sharded on the model axis; wo sharded
    # on its input (head) dim. d_model stays replicated -> activations only
    # need a reduce-scatter/all-reduce at block boundaries.
    return {"wq": P(None, MODEL), "wk": P(None, MODEL),
            "wv": P(None, MODEL), "wo": P(MODEL, None)}


def repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """(B, S, Kv, hd) -> (B, S, Kv*n_rep, hd)"""
    if n_rep == 1:
        return x
    b, s, kv, hd = x.shape
    x = jnp.broadcast_to(x[:, :, :, None, :], (b, s, kv, n_rep, hd))
    return x.reshape(b, s, kv * n_rep, hd)


def _attn_mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray,
               sliding_window: int) -> jnp.ndarray:
    """Boolean mask (..., Sq, Sk): True = attend."""
    causal = k_pos[None, :] <= q_pos[:, None]
    if sliding_window > 0:
        causal &= k_pos[None, :] > (q_pos[:, None] - sliding_window)
    return causal




def _sdpa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, cfg,
          mask: Optional[jnp.ndarray], out_dtype) -> jnp.ndarray:
    """Masked softmax attention: q (B, Sq, H, hd), k/v (B, Sk, Kv, hd) ->
    (B, Sq, H*hd).  ``mask``: (Sq, Sk) shared, (B, Sq, Sk) per-row, or None.

    Grouped mode folds the q-heads-per-kv-head group into the einsum instead
    of materialising the (B, Sk, H, hd) repeated K/V."""
    B, Sq, H, hd = q.shape
    Kv = k.shape[2]
    scale = cfg.attention_multiplier or hd ** -0.5
    sm_dtype = dt(getattr(cfg, "attn_softmax_dtype", "float32"))
    grouped = getattr(cfg, "attn_impl", "repeat") == "grouped" and H != Kv

    if grouped:
        G = H // Kv
        qg = q.reshape(B, Sq, Kv, G, hd)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                            preferred_element_type=sm_dtype) * scale
    else:
        k = repeat_kv(k, H // Kv)
        v = repeat_kv(v, H // Kv)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=sm_dtype) * scale
    if cfg.attn_logit_softcap > 0:
        c = cfg.attn_logit_softcap
        logits = c * jnp.tanh(logits / c)
    if mask is not None:
        neg = jnp.asarray(-1e30 if sm_dtype == jnp.float32 else -3e38 / 10,
                          sm_dtype)
        if mask.ndim == 3:                             # per-row (B, Sq, Sk)
            shaped = mask[:, None, None] if grouped else mask[:, None]
        else:                                          # shared (Sq, Sk)
            shaped = mask[None, None, None] if grouped else mask[None, None]
        logits = jnp.where(shaped, logits, neg)
    probs = jax.nn.softmax(logits.astype(sm_dtype), axis=-1).astype(out_dtype)
    if grouped:
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v,
                         preferred_element_type=jnp.float32).astype(out_dtype)
    else:
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                         preferred_element_type=jnp.float32).astype(out_dtype)
    return out.reshape(B, Sq, H * hd)


def _paged_attention_pallas(params, q, k, v, x, cfg, kv_cache, block_tables,
                            lengths, n_new, dn, la):
    """The paged branch routed through the Pallas kernels (the TPU
    default; ``ServeConfig.paged_backend`` / ``cfg.paged_backend`` pin
    either backend).  Same scatter convention, same masks, same outputs
    up to online-softmax accumulation order as the jnp gather below —
    greedy token streams are bitwise-equal in the CPU tests, logits
    allclose.  Compiled on TPU, interpreted on CPU.

    Decode steps (2-tuple ``paged``, S == 1) go through
    ``ops.paged_gqa_attention`` — the kernel's exclusive ``lengths`` is the
    post-scatter count, hence the +1; prefill chunks (3-tuple, ragged
    ``n_new``) through ``ops.paged_prefill_gqa_attention`` which owns the
    scatter."""
    B, S, H, hd = q.shape
    if cfg.sliding_window > 0 or cfg.attn_logit_softcap > 0:
        raise NotImplementedError(
            "paged_backend='pallas' supports full attention only (no "
            "sliding window / logit softcap); use paged_backend='jnp'")
    quant = "k_scale" in kv_cache             # int8 pools carry scale leaves
    scale = cfg.attention_multiplier or None  # None: head_dim ** -0.5
    if n_new is None and S == 1:
        if quant:
            kp, vp, ks, vs = paged_scatter_quant(
                kv_cache["k_pool"], kv_cache["v_pool"], kv_cache["k_scale"],
                kv_cache["v_scale"], k, v, block_tables, lengths, None)
            o = kernel_ops.paged_gqa_attention(
                q, kp, vp, block_tables, lengths + 1,
                k_scale=ks, v_scale=vs, scale=scale)
        else:
            kp, vp = paged_scatter(kv_cache["k_pool"], kv_cache["v_pool"],
                                   k, v, block_tables, lengths, None)
            o = kernel_ops.paged_gqa_attention(
                q, kp, vp, block_tables, lengths + 1, scale=scale)
    else:
        nn = (n_new if n_new is not None
              else jnp.full((B,), S, dtype=jnp.int32))
        if quant:
            o, kp, vp, ks, vs = kernel_ops.paged_prefill_gqa_attention(
                q, k, v, kv_cache["k_pool"], kv_cache["v_pool"], block_tables,
                lengths, nn, k_scale=kv_cache["k_scale"],
                v_scale=kv_cache["v_scale"], scale=scale)
        else:
            o, kp, vp = kernel_ops.paged_prefill_gqa_attention(
                q, k, v, kv_cache["k_pool"], kv_cache["v_pool"], block_tables,
                lengths, nn, scale=scale)
    out = dn(o.astype(x.dtype).reshape(B, S, H * hd), params["wo"], la("wo"))
    new_cache = {"k_pool": kp, "v_pool": vp}
    if quant:
        new_cache.update(k_scale=ks, v_scale=vs)
    return out, new_cache


@_scoped("attn")
def multihead_attention(params: Params, x: jnp.ndarray, cfg,
                        positions: jnp.ndarray,
                        adapters: Optional[Params] = None,
                        lora_scale: float = 1.0,
                        kv_cache: Optional[Params] = None,
                        causal: bool = True,
                        kv_override: Optional[Tuple] = None,
                        use_flash: bool = False,
                        adapter_ids: Optional[jnp.ndarray] = None,
                        paged: Optional[Tuple] = None):
    """Attention over x: (B, S, d).

    * training / prefill: ``kv_cache`` is None, causal (+ window) mask.
    * decode: ``kv_cache`` = {"k","v": (B, S_cache, Kv, hd), "pos": scalar
      next write offset}; x has S==1. Returns (out, new_cache).
    * paged decode / chunked paged prefill (continuous batching):
      ``kv_cache`` = {"k_pool","v_pool": (num_blocks, block_size, Kv, hd)}
      shared across slots and ``paged=(block_tables (B, MB) int32,
      lengths (B,) int32[, n_new (B,) int32])`` — row b holds ``lengths[b]``
      context tokens in the blocks named by its table row.  The S incoming
      tokens are scattered to positions ``lengths[b] + t`` through the
      table (with the 3-tuple form, rows ``t >= n_new[b]`` are redirected
      to scratch block 0 — host-side chunk raggedness), and each query
      attends ``[0, lengths[b] + t]``.  Under prefix caching a table row
      may name blocks SHARED with other slots (refcounted, sealed full by
      a previous owner): they are read-only by construction — writes start
      at ``lengths[b]``, which always lies in a private block — and the
      gather treats them identically, so a cache-hit slot is bitwise-equal
      to one that prefilled the same positions itself.  Attention is computed one chunk
      position at a time so a multi-token prefill chunk stays BITWISE equal
      to feeding the same tokens one decode step each (the probs·V matmul
      is not chunk-size-invariant on CPU).  The jnp gather below is the
      oracle; ``kernels/paged_attention.py`` (decode) and
      ``kernels/paged_prefill.py`` (chunk) are the TPU drop-ins that never
      materialise it in HBM.  Speculative-decoding VERIFY dispatches
      (``Model.verify_step``) are this same chunk path fed with drafted
      tokens — no extra kernel, and the per-position bitwise equality
      above is exactly what makes greedy draft-then-verify emit the
      non-speculative token stream (rejected positions are rolled back
      host-side; their scattered K/V is masked off by ``lengths`` and
      overwritten on the next write).
    * cross-attention (whisper): ``kv_override=(k, v)`` precomputed from the
      encoder; causal=False.
    """
    H, Kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    B, S, _ = x.shape
    la = partial(lora_pair, adapters)
    dn = partial(dense, lora_scale=lora_scale, adapter_ids=adapter_ids)

    q = dn(x, params["wq"], la("wq")).reshape(B, S, H, hd)
    if kv_override is None:
        k = dn(x, params["wk"], la("wk")).reshape(B, S, Kv, hd)
        v = dn(x, params["wv"], la("wv")).reshape(B, S, Kv, hd)
        if cfg.use_rope:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
    else:
        k, v = kv_override

    new_cache = None
    if kv_cache is not None and paged is not None:
        # Paged path: scatter the S new K/V tokens to each row's
        # (block, offset) slots, then attend over the row's gathered blocks
        # with a per-row length mask. Blocks hold contiguous positions, so
        # gathered order == position order and softmax sums match the dense
        # ring buffer.
        if len(paged) == 3:
            block_tables, lengths, n_new = paged
        else:
            block_tables, lengths = paged             # (B, MB) i32, (B,) i32
            n_new = None
        if runtime.paged_backend(cfg.paged_backend) == "pallas":
            out, new_cache = _paged_attention_pallas(
                params, q, k, v, x, cfg, kv_cache, block_tables, lengths,
                n_new, dn, la)
            return out, new_cache
        bs_blk = kv_cache["k_pool"].shape[1]
        pos = (lengths[:, None].astype(jnp.int32)
               + jnp.arange(S, dtype=jnp.int32)[None, :])  # write positions
        MB = block_tables.shape[1]
        L = MB * bs_blk
        if "k_scale" in kv_cache:             # int8 pools: dequant the gather
            kp, vp, ks, vs = paged_scatter_quant(
                kv_cache["k_pool"], kv_cache["v_pool"], kv_cache["k_scale"],
                kv_cache["v_scale"], k, v, block_tables, lengths, n_new)
            new_cache = {"k_pool": kp, "v_pool": vp,
                         "k_scale": ks, "v_scale": vs}
            # elementwise dequant keeps the per-position bitwise chunk
            # invariance below: values depend only on what was scattered,
            # never on how the chunk was split
            kg = (kp[block_tables].reshape(B, L, Kv, hd).astype(jnp.float32)
                  * ks[block_tables].reshape(B, L, Kv)[..., None]
                  ).astype(x.dtype)
            vg = (vp[block_tables].reshape(B, L, Kv, hd).astype(jnp.float32)
                  * vs[block_tables].reshape(B, L, Kv)[..., None]
                  ).astype(x.dtype)
        else:
            kp, vp = paged_scatter(kv_cache["k_pool"], kv_cache["v_pool"],
                                   k, v, block_tables, lengths, n_new)
            new_cache = {"k_pool": kp, "v_pool": vp}
            kg = kp[block_tables].reshape(B, L, Kv, hd).astype(x.dtype)
            vg = vp[block_tables].reshape(B, L, Kv, hd).astype(x.dtype)
        k_pos = jnp.arange(L, dtype=jnp.int32)        # slot-logical order
        # One attend per chunk position, each with the exact decode-step
        # shapes: q_pos = lengths + t, so the (B, L) causal+window mask
        # falls out of _attn_mask directly.
        outs = [_sdpa(q[:, t:t + 1], kg, vg, cfg,
                      _attn_mask(pos[:, t], k_pos,
                                 cfg.sliding_window)[:, None, :]
                      if causal else None, x.dtype)
                for t in range(S)]
        out = outs[0] if S == 1 else jnp.concatenate(outs, axis=1)
        out = dn(out, params["wo"], la("wo"))
        return out, new_cache
    elif kv_cache is not None:
        # Ring buffer: slot = absolute_position % cache_len. For full
        # attention the cache is allocated at full context length (no wrap);
        # for sliding-window archs it is window-sized and wraps.
        cache_len = kv_cache["k"].shape[1]
        write_idx = kv_cache["pos"] % cache_len
        ck = jax.lax.dynamic_update_slice(kv_cache["k"], k.astype(kv_cache["k"].dtype),
                                          (0, write_idx, 0, 0))
        cv = jax.lax.dynamic_update_slice(kv_cache["v"], v.astype(kv_cache["v"].dtype),
                                          (0, write_idx, 0, 0))
        new_cache = {"k": ck, "v": cv, "pos": kv_cache["pos"] + S}
        k, v = ck.astype(x.dtype), cv.astype(x.dtype)
        # Absolute position held by each slot: largest p <= n-1 with
        # p % cache_len == slot (negative -> slot not written yet).
        n = kv_cache["pos"] + S  # tokens written after this update
        slot = jnp.arange(cache_len, dtype=jnp.int32)
        k_pos = slot + ((n - 1 - slot) // cache_len) * cache_len
        q_pos = positions
    else:
        k_pos = positions
        q_pos = positions

    if causal:
        mask = _attn_mask(q_pos, k_pos, cfg.sliding_window)
        mask &= (k_pos >= 0)[None, :]      # exclude never-written cache slots
    else:
        mask = None
    out = _sdpa(q, k, v, cfg, mask, x.dtype)
    out = dn(out, params["wo"], la("wo"))
    return out, new_cache


def init_kv_cache(cfg, batch: int, cache_len: int, dtype) -> Params:
    Kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    return {"k": jnp.zeros((batch, cache_len, Kv, hd), dtype=dtype),
            "v": jnp.zeros((batch, cache_len, Kv, hd), dtype=dtype),
            "pos": jnp.zeros((), dtype=jnp.int32)}


def kv_cache_specs() -> Params:
    return {"k": P(DATA, None, MODEL, None), "v": P(DATA, None, MODEL, None),
            "pos": P()}


def kv_pack(n_kv_heads: int, head_dim: int) -> int:
    """KV heads stored side by side in one row of a bf16 pool.  The TPU
    lays an array's last axis out in tiles of 128 lanes, so a head of 64
    would sit in a row half padding, and every kernel call would copy the
    pool to pad it.  Where the heads divide into whole 128-lane rows (Kv 8
    of 64 -> 4 rows of 2), the pool is ``(NB, bs, Kv // f, f * hd)``: a
    reshape of the logical ``(NB, bs, Kv, hd)``, with no padding, that the
    kernels read in place.  1 (no packing) otherwise."""
    f = 128 // head_dim if head_dim < 128 and 128 % head_dim == 0 else 1
    return f if n_kv_heads % f == 0 else 1


def init_paged_kv_cache(cfg, num_blocks: int, block_size: int, dtype,
                        kv_dtype: str = "f32") -> Params:
    """One K/V pool per layer, shared by every serving slot: blocks are
    handed to slots by the host-side block table (serving/kv_cache.py).
    Unquantized pools pack narrow heads into whole rows (:func:`kv_pack`).

    ``kv_dtype="int8"`` stores the pools as int8 with one fp32 scale per
    (block, position, kv-head) riding as ``k_scale``/``v_scale`` leaves —
    36 bytes per token per kv-head instead of 64 (bf16), so the same HBM
    budget holds ~1.78x the blocks.  ``"f32"`` keeps the unquantized pools
    in ``dtype`` exactly as before (bf16 in serving)."""
    Kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    if kv_dtype == "int8":
        return {"k_pool": jnp.zeros((num_blocks, block_size, Kv, hd),
                                    dtype=jnp.int8),
                "v_pool": jnp.zeros((num_blocks, block_size, Kv, hd),
                                    dtype=jnp.int8),
                "k_scale": jnp.zeros((num_blocks, block_size, Kv),
                                     dtype=jnp.float32),
                "v_scale": jnp.zeros((num_blocks, block_size, Kv),
                                     dtype=jnp.float32)}
    if kv_dtype != "f32":
        raise ValueError(f"kv_dtype must be 'f32' or 'int8', got {kv_dtype!r}")
    f = kv_pack(Kv, hd)
    shape = (num_blocks, block_size, Kv // f, f * hd)
    return {"k_pool": jnp.zeros(shape, dtype=dtype),
            "v_pool": jnp.zeros(shape, dtype=dtype)}


def paged_kv_cache_specs(kv_dtype: str = "f32") -> Params:
    # the block axis is a shared pool (no batch sharding); heads on MODEL
    specs = {"k_pool": P(None, None, MODEL, None),
             "v_pool": P(None, None, MODEL, None)}
    if kv_dtype == "int8":
        specs["k_scale"] = P(None, None, MODEL)
        specs["v_scale"] = P(None, None, MODEL)
    return specs


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU / GELU)
# ---------------------------------------------------------------------------

def init_mlp(key, d: int, ff: int, mlp_type: str, dtype) -> Params:
    ks = jax.random.split(key, 3)
    s_in, s_out = d ** -0.5, ff ** -0.5
    p = {"w_out": (jax.random.normal(ks[2], (ff, d)) * s_out).astype(dtype)}
    if mlp_type in ("swiglu", "geglu"):
        p["w_gate"] = (jax.random.normal(ks[0], (d, ff)) * s_in).astype(dtype)
        p["w_up"] = (jax.random.normal(ks[1], (d, ff)) * s_in).astype(dtype)
    else:
        p["w_up"] = (jax.random.normal(ks[1], (d, ff)) * s_in).astype(dtype)
    return p


def mlp_specs(mlp_type: str) -> Params:
    p = {"w_up": P(None, MODEL), "w_out": P(MODEL, None)}
    if mlp_type in ("swiglu", "geglu"):
        p["w_gate"] = P(None, MODEL)
    return p


@_scoped("mlp")
def apply_mlp(params: Params, x: jnp.ndarray, mlp_type: str,
              adapters: Optional[Params] = None, lora_scale: float = 1.0,
              adapter_ids: Optional[jnp.ndarray] = None):
    la = partial(lora_pair, adapters)
    dn = partial(dense, lora_scale=lora_scale, adapter_ids=adapter_ids)
    if mlp_type in ("swiglu", "geglu"):
        act = jax.nn.silu if mlp_type == "swiglu" else partial(jax.nn.gelu, approximate=True)
        g = dn(x, params["w_gate"], la("w_gate"))
        u = dn(x, params["w_up"], la("w_up"))
        h = act(g.astype(jnp.float32)).astype(x.dtype) * u
    else:
        h = dn(x, params["w_up"], la("w_up"))
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return dn(h, params["w_out"], la("w_out"))


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def init_embed(key, vocab: int, d: int, dtype) -> jnp.ndarray:
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)


def embed_specs() -> Any:
    return P(MODEL, None)
