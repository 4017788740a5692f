"""Family dispatch: one uniform interface over all model families.

``Model.forward(params, batch, adapters)`` where ``batch`` is a dict:
  * decoder families: {"tokens": (B,S)} (+ "patch_embeds": (B,P,d) for vlm)
  * encdec:           {"enc_embeds": (B,T,d), "tokens": (B,S)}
``Model.decode_step(params, cache, tokens, pos, adapters)`` for serving.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax.numpy as jnp

from repro.models import encdec, model as dec

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: Any

    # ---- init ------------------------------------------------------------
    def init(self, rng) -> Params:
        if self.cfg.is_encdec:
            return encdec.init_params(rng, self.cfg)
        return dec.init_params(rng, self.cfg)

    def param_specs(self) -> Params:
        if self.cfg.is_encdec:
            return encdec.param_specs(self.cfg)
        return dec.param_specs(self.cfg)

    # ---- forward ----------------------------------------------------------
    def forward(self, params: Params, batch: Dict[str, jnp.ndarray],
                adapters: Optional[Params] = None, lora_scale: float = 1.0,
                last_only: bool = False,
                adapter_ids: Optional[jnp.ndarray] = None):
        cfg = self.cfg
        if cfg.is_encdec:
            if adapter_ids is not None:
                raise NotImplementedError("multi-tenant banked adapters are "
                                          "decoder-family only")
            return encdec.forward(params, batch["enc_embeds"], batch["tokens"],
                                  cfg, adapters, lora_scale)
        extra = batch.get("patch_embeds") if cfg.family == "vlm" else None
        return dec.forward(params, batch["tokens"], cfg, adapters, lora_scale,
                           extra_embeds=extra, last_only=last_only,
                           adapter_ids=adapter_ids)

    # ---- decode -----------------------------------------------------------
    def init_decode_cache(self, batch: int, cache_len: int) -> Params:
        if self.cfg.is_encdec:
            return encdec.init_decode_cache(self.cfg, batch, cache_len)
        return dec.init_decode_cache(self.cfg, batch, cache_len)

    def decode_cache_specs(self) -> Params:
        if self.cfg.is_encdec:
            return encdec.decode_cache_specs(self.cfg)
        return dec.decode_cache_specs(self.cfg)

    def init_paged_decode_cache(self, num_slots: int, num_blocks: int,
                                block_size: int,
                                kv_dtype: str = "f32") -> Params:
        """Continuous-batching serving cache: shared K/V block pools +
        dense per-slot SSM state (see serving/kv_cache.py).
        ``kv_dtype="int8"`` quantizes the K/V pools with per-block scales."""
        if self.cfg.is_encdec:
            raise NotImplementedError("paged decoding is decoder-family only")
        return dec.init_paged_decode_cache(self.cfg, num_slots, num_blocks,
                                           block_size, kv_dtype=kv_dtype)

    def paged_decode_cache_specs(self, kv_dtype: str = "f32") -> Params:
        if self.cfg.is_encdec:
            raise NotImplementedError("paged decoding is decoder-family only")
        return dec.paged_decode_cache_specs(self.cfg, kv_dtype)

    def prefill_step(self, params: Params, cache: Params, tokens, pos, n_new,
                     adapters: Optional[Params] = None,
                     lora_scale: float = 1.0,
                     adapter_ids: Optional[jnp.ndarray] = None,
                     block_tables: Optional[jnp.ndarray] = None,
                     paged_backend: Optional[str] = None):
        """Chunked paged prefill: tokens (B, T) with n_new (B,) valid per
        row, scattered through block_tables at per-row offsets pos (B,).
        ``paged_backend`` overrides ``cfg.paged_backend`` ("jnp" | "pallas").
        Traced under ``jax.set_mesh(mesh)``, the model's "data"-axis
        constraints bind batch rows to devices.  Returns
        (logits (B, T, V), cache)."""
        if self.cfg.is_encdec:
            raise NotImplementedError("paged prefill is decoder-family only")
        return dec.prefill_step(params, cache, tokens, pos, n_new, self.cfg,
                                adapters, lora_scale, adapter_ids=adapter_ids,
                                block_tables=block_tables,
                                paged_backend=paged_backend)

    def verify_step(self, params: Params, cache: Params, tokens, pos, n_new,
                    adapters: Optional[Params] = None,
                    lora_scale: float = 1.0,
                    adapter_ids: Optional[jnp.ndarray] = None,
                    block_tables: Optional[jnp.ndarray] = None,
                    paged_backend: Optional[str] = None):
        """Speculative-decoding verification: score a drafted chunk
        (feedback token + proposed continuation per row) causally against
        the paged cache.  This IS :meth:`prefill_step` — same scatter,
        same chunk attention, same kernels on both paged backends — named
        separately because the contract differs: the caller consumes the
        logits at EVERY chunk position (greedy acceptance needs the
        model's choice after each drafted token), and positions past the
        accepted run are rolled back by the scheduler, not kept.  Chunk
        logits are bitwise-equal to feeding the same tokens one decode
        step at a time, which is what makes greedy draft-then-verify
        bitwise-identical to non-speculative decoding."""
        return self.prefill_step(params, cache, tokens, pos, n_new,
                                 adapters=adapters, lora_scale=lora_scale,
                                 adapter_ids=adapter_ids,
                                 block_tables=block_tables,
                                 paged_backend=paged_backend)

    def decode_step(self, params: Params, cache: Params, tokens, pos,
                    adapters: Optional[Params] = None, lora_scale: float = 1.0,
                    adapter_ids: Optional[jnp.ndarray] = None,
                    block_tables: Optional[jnp.ndarray] = None,
                    paged_backend: Optional[str] = None):
        if self.cfg.is_encdec:
            if adapter_ids is not None or block_tables is not None:
                raise NotImplementedError("multi-tenant banked adapters and "
                                          "paged decoding are decoder-family "
                                          "only")
            return encdec.decode_step(params, cache, tokens, pos, self.cfg,
                                      adapters, lora_scale)
        return dec.decode_step(params, cache, tokens, pos, self.cfg,
                               adapters, lora_scale, adapter_ids=adapter_ids,
                               block_tables=block_tables,
                               paged_backend=paged_backend)


def get_model(cfg) -> Model:
    return Model(cfg)
