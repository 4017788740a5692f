"""Unified decoder-only model covering dense / MoE / SSM / hybrid / VLM.

A model is ``cfg.n_layers`` layers arranged as ``n_periods`` repetitions of
``cfg.layer_pattern``. Per-period parameters are stacked on a leading axis and
the period loop is a ``jax.lax.scan`` — this keeps the HLO size independent of
depth (essential for the 512-device dry-run compiles) and is the idiomatic
TPU structure for deep stacks.

Public API (all pure functions):
    init_params(rng, cfg)                 -> params
    param_specs(cfg)                      -> PartitionSpec tree
    forward(params, tokens, cfg, ...)     -> (logits, aux_loss)
    init_decode_cache(cfg, batch, length) -> cache
    decode_cache_specs(cfg)               -> PartitionSpec tree
    decode_step(params, cache, tokens, pos, cfg, ...) -> (logits, cache)
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import runtime
from repro.models import layers as L
from repro.models import mamba2, moe as moe_lib

Params = Dict[str, Any]


def _block_names(cfg):
    return [f"b{i}" for i in range(len(cfg.layer_pattern))]


def _parse(entry: str) -> Tuple[str, str]:
    mixer, _, mlp = entry.partition("+")
    return mixer, (mlp or "none")


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_block(key, entry: str, cfg, dtype) -> Params:
    mixer, mlp = _parse(entry)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: Params = {"norm1": L.init_norm(k3, cfg.d_model, cfg.norm_type, dtype)}
    if mixer == "attn":
        p["mixer"] = L.init_attention(k1, cfg, dtype)
    else:
        p["mixer"] = mamba2.init_mamba(k1, cfg, dtype)
    if mlp == "mlp":
        p["norm2"] = L.init_norm(k4, cfg.d_model, cfg.norm_type, dtype)
        p["mlp"] = L.init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.mlp_type, dtype)
    elif mlp == "moe":
        p["norm2"] = L.init_norm(k4, cfg.d_model, cfg.norm_type, dtype)
        p["mlp"] = moe_lib.init_moe(k2, cfg.d_model, cfg.resolved_d_ff_moe,
                                    cfg.n_experts, cfg.mlp_type, dtype)
    return p


def _block_specs(entry: str, cfg) -> Params:
    mixer, mlp = _parse(entry)
    p: Params = {"norm1": L.norm_specs(cfg.norm_type)}
    p["mixer"] = (L.attention_specs(cfg) if mixer == "attn"
                  else mamba2.mamba_specs(cfg))
    if mlp == "mlp":
        p["norm2"] = L.norm_specs(cfg.norm_type)
        p["mlp"] = L.mlp_specs(cfg.mlp_type)
    elif mlp == "moe":
        p["norm2"] = L.norm_specs(cfg.norm_type)
        p["mlp"] = moe_lib.moe_specs(cfg.mlp_type)
    return p


def init_params(rng, cfg) -> Params:
    dtype = L.dt(cfg.param_dtype)
    n_blocks = len(cfg.layer_pattern)
    keys = jax.random.split(rng, n_blocks + 3)

    def stacked(entry, key):
        ks = jax.random.split(key, cfg.n_periods)
        return jax.vmap(lambda k: _init_block(k, entry, cfg, dtype))(ks)

    params: Params = {
        "embed": L.init_embed(keys[-1], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": L.init_norm(keys[-2], cfg.d_model, cfg.norm_type, dtype),
        "blocks": {name: stacked(entry, keys[i])
                   for i, (name, entry) in
                   enumerate(zip(_block_names(cfg), cfg.layer_pattern))},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(
            keys[-3], (cfg.d_model, cfg.vocab_size)) * 0.02).astype(dtype)
    return params


def _add_leading(spec_tree):
    """Prepend a replicated period axis to every PartitionSpec leaf."""
    return jax.tree.map(lambda s: P(*((None,) + tuple(s))), spec_tree,
                        is_leaf=lambda s: isinstance(s, P))


def param_specs(cfg) -> Params:
    specs: Params = {
        "embed": L.embed_specs(),
        "final_norm": L.norm_specs(cfg.norm_type),
        "blocks": {name: _add_leading(_block_specs(entry, cfg))
                   for name, entry in zip(_block_names(cfg), cfg.layer_pattern)},
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, L.MODEL)
    return specs


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def _apply_block(entry: str, bp: Params, x, cfg, positions,
                 adapters=None, lora_scale=1.0, cache=None,
                 adapter_ids=None, paged=None, n_new=None, period=None):
    """One layer. Returns (x, new_cache, aux).  ``n_new``: (B,) int32 valid
    leading tokens per row in a ragged prefill chunk (see prefill_step).
    ``period``: the layer's period, where ``cache`` is a Mamba-2 state
    stacked over all periods (see :func:`_cached_scan`)."""
    mixer, mlp = _parse(entry)
    ad = adapters or {}
    aux = jnp.zeros((), jnp.float32)
    h = L.apply_norm(bp["norm1"], x, cfg.norm_type, cfg.norm_eps)
    if mixer == "attn":
        out, new_mix_cache = L.multihead_attention(
            bp["mixer"], h, cfg, positions, ad.get("mixer"), lora_scale,
            kv_cache=cache, adapter_ids=adapter_ids, paged=paged)
    else:
        out, new_mix_cache = mamba2.apply_mamba(
            bp["mixer"], h, cfg, ad.get("mixer"), lora_scale, ssm_cache=cache,
            adapter_ids=adapter_ids, n_new=n_new, period=period)
    x = _residual(x, out, cfg)
    if mlp != "none":
        h = L.apply_norm(bp["norm2"], x, cfg.norm_type, cfg.norm_eps)
        if mlp == "mlp":
            out = L.apply_mlp(bp["mlp"], h, cfg.mlp_type, ad.get("mlp"),
                              lora_scale, adapter_ids=adapter_ids)
        else:
            out, aux = moe_lib.apply_moe(bp["mlp"], h, cfg, ad.get("mlp"),
                                         lora_scale, adapter_ids=adapter_ids)
        x = _residual(x, out, cfg)
    return x, new_mix_cache, aux


def _residual(x, out, cfg):
    """The residual add, with the sublayer's output scaled by
    ``cfg.residual_multiplier`` (in the activation dtype, as published)."""
    if cfg.residual_multiplier != 1.0:
        out = out * jnp.asarray(cfg.residual_multiplier, out.dtype)
    return x + out


def _embed(params: Params, tokens, cfg):
    """Token embeddings times ``cfg.resolved_embedding_multiplier``."""
    dtype = L.dt(cfg.dtype)
    x = params["embed"].astype(dtype)[tokens]
    mult = cfg.resolved_embedding_multiplier
    if mult != 1.0:
        x = x * jnp.asarray(mult, dtype)
    return x


def _unembed(params: Params, x, cfg):
    """Final norm and the (tied or separate) head: float32 logits divided
    by ``cfg.logits_scaling``."""
    x = L.apply_norm(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = L.matmul(x, head.astype(L.dt(cfg.dtype)), out_dtype=jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def forward(params: Params, tokens: jnp.ndarray, cfg,
            adapters: Optional[Params] = None, lora_scale: float = 1.0,
            extra_embeds: Optional[jnp.ndarray] = None,
            last_only: bool = False,
            adapter_ids: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens: (B, S_text) int32. extra_embeds: (B, P, d) prepended (VLM).

    ``adapter_ids``: (B,) int32 per-row client slots when ``adapters`` is a
    stacked multi-tenant bank (leaves (n_periods, C, d_in, r)).

    Returns (logits (B, S, V), aux_loss scalar)."""
    dtype = L.dt(cfg.dtype)
    x = _embed(params, tokens, cfg)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(dtype), x], axis=1)
    B, S, _ = x.shape
    x = L.maybe_shard(x, P(("pod", "data"), None, None))
    # data-dependence defeats XLA constant-folding of the (S, S) causal mask
    # (a 1 GiB bool fold at S=32k that dominates compile time otherwise)
    positions = jnp.arange(S, dtype=jnp.int32) + tokens[0, 0] * 0

    block_names = _block_names(cfg)
    ad_blocks = (adapters or {}).get("blocks", {})

    def period_body(carry, xs):
        x, aux = carry
        for name in block_names:
            entry = cfg.layer_pattern[block_names.index(name)]
            x, _, a = _apply_block(entry, xs[name], x, cfg, positions,
                                   xs.get("__ad_" + name), lora_scale,
                                   adapter_ids=adapter_ids)
            aux = aux + a
        return (x, aux), None

    xs = dict(params["blocks"])
    for name in block_names:
        if name in ad_blocks:
            xs["__ad_" + name] = ad_blocks[name]
    if cfg.remat:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat_policy == "dots" else None)
        body = jax.checkpoint(period_body, policy=policy)
    else:
        body = period_body
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs,
                           unroll=min(cfg.scan_unroll, cfg.n_periods))

    if last_only:  # serving prefill: unembed only the final position
        x = x[:, -1:]
    return _unembed(params, x, cfg), aux


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def init_decode_cache(cfg, batch: int, cache_len: int) -> Params:
    """cache_len: full context for dense attention, window for SW archs."""
    cache: Params = {"blocks": {}}
    for name, entry in zip(_block_names(cfg), cfg.layer_pattern):
        mixer, _ = _parse(entry)
        if mixer == "attn":
            eff = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len
            one = lambda: L.init_kv_cache(cfg, batch, eff, jnp.bfloat16)
        else:
            one = lambda: mamba2.init_ssm_cache(cfg, batch)
        cache["blocks"][name] = jax.tree.map(
            lambda *ls: jnp.stack(ls), *[one() for _ in range(cfg.n_periods)])
    return cache


def decode_cache_specs(cfg) -> Params:
    specs: Params = {"blocks": {}}
    for name, entry in zip(_block_names(cfg), cfg.layer_pattern):
        mixer, _ = _parse(entry)
        base = L.kv_cache_specs() if mixer == "attn" else mamba2.ssm_cache_specs()
        specs["blocks"][name] = _add_leading(base)
    return specs


def init_paged_decode_cache(cfg, num_slots: int, num_blocks: int,
                            block_size: int, kv_dtype: str = "f32") -> Params:
    """Serving-path cache for continuous batching: attention layers share one
    K/V block pool (slots reference blocks through the scheduler's block
    table); SSM/Mamba rows keep dense per-slot recurrent state.

    ``kv_dtype="int8"`` stores the K/V pools quantized with per-block fp32
    scale leaves (see :func:`layers.init_paged_kv_cache`); SSM state stays
    dense fp32 either way."""
    cache: Params = {"blocks": {}}
    for name, entry in zip(_block_names(cfg), cfg.layer_pattern):
        mixer, _ = _parse(entry)
        if mixer == "attn":
            one = lambda: L.init_paged_kv_cache(cfg, num_blocks, block_size,
                                                jnp.bfloat16,
                                                kv_dtype=kv_dtype)
        else:
            one = lambda: mamba2.init_ssm_cache(cfg, num_slots)
        cache["blocks"][name] = jax.tree.map(
            lambda *ls: jnp.stack(ls), *[one() for _ in range(cfg.n_periods)])
    return cache


def paged_decode_cache_specs(cfg, kv_dtype: str = "f32") -> Params:
    specs: Params = {"blocks": {}}
    for name, entry in zip(_block_names(cfg), cfg.layer_pattern):
        mixer, _ = _parse(entry)
        base = (L.paged_kv_cache_specs(kv_dtype) if mixer == "attn"
                else mamba2.ssm_cache_specs())
        specs["blocks"][name] = _add_leading(base)
    return specs


def _resolve_backend(cfg, paged_backend: Optional[str]):
    """Per-call override of ``cfg.paged_backend`` (the serving engine
    threads ``ServeConfig.paged_backend`` here; ``None`` keeps the config
    default).  Config replacement keeps the flag on ``cfg`` — the one
    object the attention layer already reads."""
    if paged_backend is None or paged_backend == cfg.paged_backend:
        return cfg
    return cfg.with_overrides(
        paged_backend=runtime.paged_backend(paged_backend))


def decode_step(params: Params, cache: Params, tokens: jnp.ndarray,
                pos: jnp.ndarray, cfg,
                adapters: Optional[Params] = None, lora_scale: float = 1.0,
                adapter_ids: Optional[jnp.ndarray] = None,
                block_tables: Optional[jnp.ndarray] = None,
                paged_backend: Optional[str] = None
                ) -> Tuple[jnp.ndarray, Params]:
    """One decode step. tokens: (B, 1) int32; pos: scalar int32 (tokens
    already in the cache). ``adapter_ids``: (B,) int32 client slots for
    multi-tenant banked adapters.

    Continuous batching: pass ``block_tables`` (B, MB) int32 and a *per-row*
    ``pos`` (B,) int32 of ragged context lengths; the cache must come from
    :func:`init_paged_decode_cache`. ``paged_backend`` overrides
    ``cfg.paged_backend`` ("jnp" gather oracle | "pallas" kernels).
    Returns (logits (B, 1, V), new cache)."""
    cfg = _resolve_backend(cfg, paged_backend)
    if block_tables is not None:
        pos = pos.astype(jnp.int32)                  # (B,) ragged lengths
        positions = pos[:, None]                     # (B, S=1) for RoPE
        paged = (block_tables, pos)
    else:
        positions = (pos[None].astype(jnp.int32) if pos.ndim == 0
                     else pos.astype(jnp.int32))
        paged = None
    return _cached_scan(params, cache, tokens, positions, cfg, adapters,
                        lora_scale, adapter_ids, paged=paged, n_new=None)


def prefill_step(params: Params, cache: Params, tokens: jnp.ndarray,
                 pos: jnp.ndarray, n_new: jnp.ndarray, cfg,
                 adapters: Optional[Params] = None, lora_scale: float = 1.0,
                 adapter_ids: Optional[jnp.ndarray] = None,
                 block_tables: Optional[jnp.ndarray] = None,
                 paged_backend: Optional[str] = None
                 ) -> Tuple[jnp.ndarray, Params]:
    """Chunked paged prefill: one dispatch consumes a whole prompt chunk.

    tokens: (B, T) int32 — up to T prompt tokens per serving slot, of which
    ``n_new[b]`` are valid (ragged chunks; tail positions are padding whose
    K/V scatters to scratch block 0 and whose SSM updates are masked out).
    pos: (B,) int32 per-row context lengths already written; the chunk
    occupies positions ``pos[b] .. pos[b] + n_new[b] - 1``.  Requires a
    paged cache (:func:`init_paged_decode_cache`) and ``block_tables``
    whose rows cover ``pos + n_new`` positions (the host scheduler grows
    tables before each chunk).

    Returns (logits (B, T, V), new cache) — the serving engine samples each
    row's logits at its last valid position to seed decoding."""
    cfg = _resolve_backend(cfg, paged_backend)
    if block_tables is None:
        raise ValueError("prefill_step requires block_tables (paged cache)")
    T = tokens.shape[1]
    pos = pos.astype(jnp.int32)
    n_new = n_new.astype(jnp.int32)
    positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    return _cached_scan(params, cache, tokens, positions, cfg, adapters,
                        lora_scale, adapter_ids,
                        paged=(block_tables, pos, n_new), n_new=n_new)


def _cached_scan(params: Params, cache: Params, tokens: jnp.ndarray,
                 positions: jnp.ndarray, cfg, adapters, lora_scale,
                 adapter_ids, paged, n_new) -> Tuple[jnp.ndarray, Params]:
    """Shared cache-threading scaffold of decode_step / prefill_step:
    embed, period scan with per-block caches, final norm, unembed.

    K/V pools ride the scan as inputs and outputs, one period's slice
    each.  A Mamba-2 block's state stack (every period's) rides as a
    carry instead: its layer reads and writes its own period in place,
    inside the ``ssm`` scope, so each step moves the state once."""
    x = _embed(params, tokens, cfg)
    # serving: batch rows (slots) are data-parallel over the mesh; the
    # sharded engine keeps slots shard-contiguous, so partitioning the
    # fused batch axis here lands each KV shard's rows on its devices.
    # No-op when tracing without a mesh (the single-device bitwise path).
    x = L.maybe_shard(x, P("data", None, None))

    block_names = _block_names(cfg)
    ad_blocks = (adapters or {}).get("blocks", {})

    recurrent = [name for name, entry in zip(block_names, cfg.layer_pattern)
                 if _parse(entry)[0] == "mamba"]

    def period_body(carry, xs):
        x, states = carry
        states, new_caches = dict(states), {}
        for name in block_names:
            entry = cfg.layer_pattern[block_names.index(name)]
            stacked = name in states
            x, nc, _ = _apply_block(
                entry, xs[name], x, cfg, positions, xs.get("__ad_" + name),
                lora_scale,
                cache=states[name] if stacked else xs["__cache_" + name],
                adapter_ids=adapter_ids, paged=paged, n_new=n_new,
                period=xs["__period"] if stacked else None)
            (states if stacked else new_caches)[name] = nc
        return (x, states), new_caches

    xs = dict(params["blocks"])
    if recurrent:
        xs["__period"] = jnp.arange(cfg.n_periods)
    for name in block_names:
        if name not in recurrent:
            xs["__cache_" + name] = cache["blocks"][name]
        if name in ad_blocks:
            xs["__ad_" + name] = ad_blocks[name]
    states = {name: cache["blocks"][name] for name in recurrent}
    (x, states), new_caches = jax.lax.scan(
        period_body, (x, states), xs,
        unroll=min(cfg.scan_unroll, cfg.n_periods))
    new_caches.update(states)

    logits = _unembed(params, x, cfg)
    return L.maybe_shard(logits, P("data", None, None)), {"blocks": new_caches}
