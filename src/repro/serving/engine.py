"""Serving engines: batched KV-cache decoding with (fused) LoRA adapters.

FDLoRA's inference story: after stage 3, each client's dual LoRA merges into
one standard adapter (Eq. 7). Two engines share one generation loop:

  * :class:`Engine` — single-tenant: one adapter tree bound at construction
    (the seed behaviour, kept for training-side evals and examples).
  * :class:`MultiTenantEngine` — one base-model program + an
    :class:`~repro.serving.registry.AdapterRegistry` bank; callers submit
    :class:`Request` objects carrying ``client_id`` and the engine serves
    *mixed-client* batches, routing every batch row to its client's adapter
    via per-row ``adapter_ids`` (gathered on-chip, see
    ``kernels/batched_lora.py``).

``MultiTenantEngine.generate_stream`` is a **continuous-batching** loop
over a paged KV cache (``serving/kv_cache.py`` + ``serving/scheduler.py``):
ragged prompts fed through CHUNKED multi-token prefill dispatches, on-demand
block growth with preemption when the pool runs dry, per-request token
budgets, per-row EOS, admission of queued requests into slots freed
mid-flight — and ``(rid, tokens, finished)`` increments yielded the moment
each chunk is observed, before the batch drains.  ``generate`` collects the
stream into per-request arrays; ``generate_fixed`` keeps the fixed-shape
one-batch-per-call path (equal-length prompts, one shared budget) —
equal-shape greedy requests produce bit-identical tokens on both.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import runtime
from repro.core.lora import lora_scale
from repro.serving.kv_cache import PagedKVCache, blocks_needed, reset_slot
from repro.serving.registry import AdapterRegistry
from repro.serving.scheduler import COUNTERS, PRIORITY_CLASSES, Scheduler
from repro.serving.sharded import ShardedPagedKVCache, ShardedScheduler

Params = Any

# prefix of the host spans the serving path writes into a jax.profiler trace
SPAN_PREFIX = "serve:"


@dataclasses.dataclass
class ServeConfig:
    batch_size: int                  # decode slots (continuous) / batch rows
    max_new_tokens: int = 32         # default per-request budget
    cache_len: int = 4096            # fixed-path cache length
    temperature: float = 0.0         # 0 => greedy
    seed: int = 0
    eos_id: Optional[int] = None     # finished rows emit pad_id afterwards
    pad_id: int = 0
    block_size: int = 16             # paged-cache block size (continuous)
    num_blocks: Optional[int] = None  # pool size; None => full residency
    max_blocks_per_slot: Optional[int] = None  # block-table width; None =>
    #                                  longest span (or the whole pool when
    #                                  prefix caching with a pinned pool)
    scan_chunk: int = 32             # max device steps between admissions
    prefill_chunk: int = 16          # prompt tokens per prefill dispatch
    prefix_cache: bool = False       # content-addressed shared blocks:
    #                                  shared prompt prefixes (and preempted
    #                                  requests' replays) skip re-prefill,
    #                                  within AND across generate calls.
    #                                  Warm-vs-cold BITWISE equality holds
    #                                  for greedy decoding (temperature 0);
    #                                  with temperature > 0 samples stay
    #                                  valid but draw a different rng
    #                                  stream (fewer dispatches = fewer
    #                                  rng splits), so runs don't replay.
    sched_policy: str = "sla"        # "sla": priority-class admission with
    #                                  aging + scored preemption victims
    #                                  (prefix-aware); "fcfs": legacy
    #                                  arrival order + newest-first victims
    sched_aging: int = 16            # admission rounds queued per one-class
    #                                  promotion under "sla" (0 disables)
    paged_backend: Optional[str] = None  # paged-attention impl for the
    #                                  continuous path: "jnp" gather oracle
    #                                  | "pallas" kernels; None = the model
    #                                  config's choice, by default the
    #                                  platform's (pallas on TPU, jnp on
    #                                  CPU — repro.runtime.paged_backend)
    spec_decode: bool = False        # speculative decoding (continuous
    #                                  path): prompt-lookup self-drafts of
    #                                  up to spec_k tokens verified in ONE
    #                                  prefill-shaped dispatch; greedy-only
    #                                  (temperature must be 0) and bitwise-
    #                                  identical to non-speculative greedy
    #                                  decoding.  Rejected drafts roll the
    #                                  paged cache back token-granularly.
    spec_k: int = 4                  # max drafted tokens per slot per round
    spec_ngram: int = 3              # longest history n-gram the drafter
    #                                  matches (see serving/spec_decode.py)
    num_shards: int = 1              # partition the paged block pool +
    #                                  request slots into this many shards
    #                                  (serving/sharded.py): per-shard free
    #                                  lists, seal chains and preemption,
    #                                  placement-aware admission, one fused
    #                                  dispatch per round.  1 (default) is
    #                                  the single-pool path, bit-identical
    #                                  to pre-shard behaviour.
    mesh: Any = None                 # optional jax.sharding.Mesh set
    #                                  (jax.set_mesh) around device
    #                                  dispatches: weights, adapter bank and
    #                                  KV pool are placed replicated on it
    #                                  and the fused batch splits over its
    #                                  "data" axis (slots are shard-
    #                                  contiguous, so shard boundaries land
    #                                  on device boundaries; the Pallas
    #                                  kernels run per device under
    #                                  shard_map).  None = one device.
    overlap: bool = True             # async overlapped dispatch (continuous
    #                                  path): the host plans and enqueues
    #                                  chunk N+1 while the device executes
    #                                  chunk N, materialising a chunk's
    #                                  samples ONLY when the next plan can
    #                                  depend on them — i.e. when the chunk
    #                                  emits tokens (feedback rows, a
    #                                  completing prompt, decode/verify).
    #                                  Prompt-only prefill chunks pipeline
    #                                  with zero host-device round-trips.
    #                                  False = the synchronous reference
    #                                  loop (one host sync per chunk); both
    #                                  run the SAME dispatches with the SAME
    #                                  inputs, so token streams are BITWISE
    #                                  identical either way.
    kv_dtype: str = "f32"            # paged K/V pool storage: "f32" keeps
    #                                  the unquantized (bf16) pools exactly
    #                                  as before; "int8" stores blocks
    #                                  quantized with per-(block, position,
    #                                  kv-head) fp32 scales — ~1.78x the
    #                                  blocks per HBM byte, dequantized at
    #                                  read inside both paged backends.
    #                                  Greedy streams match the f32 path
    #                                  within a documented error bound (see
    #                                  tests/test_quant.py), NOT bitwise.


@dataclasses.dataclass
class Request:
    """One generation request. ``prompt``: (S,) int32 — ragged lengths are
    fine under ``MultiTenantEngine.generate`` (continuous batching); the
    fixed path (``generate_fixed``) still needs every prompt to share S.
    ``max_new_tokens`` overrides ``ServeConfig.max_new_tokens`` per request.
    ``priority`` names a scheduling class (``interactive`` | ``batch`` |
    ``background``); ``None`` (the default) falls back to the client's
    registered default (``AdapterRegistry.register(...,
    default_priority=)``) and then to ``"batch"`` — an explicit request
    priority always wins.  ``deadline`` (any comparable number, e.g. a
    unix timestamp) breaks admission ties earliest-first within a class —
    both only matter under ``ServeConfig.sched_policy="sla"``."""
    client_id: Any
    prompt: Any
    max_new_tokens: Optional[int] = None
    priority: Optional[str] = None
    deadline: Optional[float] = None


class _EngineBase:
    """The generation loop, parameterised by optional per-row adapter ids."""

    def __init__(self, model, cfg):
        self.model, self.cfg = model, cfg
        self.scale = lora_scale(cfg)
        self._decode = jax.jit(self._decode_impl)
        self._prefill = jax.jit(self._prefill_impl)
        self._decode_chunk = jax.jit(self._decode_chunk_impl,
                                     static_argnames=("chunk_cap", "backend"))
        self._prefill_chunk = jax.jit(self._prefill_chunk_impl,
                                      static_argnames=("backend",))
        self._verify_chunk = jax.jit(self._verify_chunk_impl,
                                     static_argnames=("backend",))

    # -- steps ---------------------------------------------------------------
    def _prefill_impl(self, params, adapters, ids, cache, tokens):
        """Sequential prefill through the decode path (cache-filling).

        For production prefill one would run the parallel forward and scatter
        K/V into the cache; the sequential scan keeps one code path across
        attention/SSM/hybrid and is what the ``prefill_32k`` dry-run shape
        lowers via ``forward`` instead."""
        def step(carry, tok):
            cache, pos = carry
            logits, cache = self.model.decode_step(
                params, cache, tok[:, None], pos, adapters=adapters,
                lora_scale=self.scale, adapter_ids=ids)
            return (cache, pos + 1), logits[:, 0]

        (cache, pos), logits = jax.lax.scan(
            step, (cache, jnp.int32(0)), tokens.T)
        return cache, pos, logits[-1]

    def _decode_impl(self, params, adapters, ids, cache, tok, pos, rng,
                     temperature):
        logits, cache = self.model.decode_step(
            params, cache, tok, pos, adapters=adapters, lora_scale=self.scale,
            adapter_ids=ids)
        return self._sample(logits, rng, temperature), cache

    def _decode_chunk_impl(self, params, adapters, ids, cache, last, active,
                           lengths, block_tables, n_steps, rng, temperature,
                           chunk_cap, backend=None):
        """Up to ``n_steps`` (dynamic, <= static ``chunk_cap``) decode steps
        fully on device: each slot feeds its last sampled token — one
        dispatch per chunk instead of per token.  (Prompts are fed by
        ``_prefill_chunk``; every active slot here is past its prompt.)
        ``backend`` (static) selects the paged-attention impl
        (``ServeConfig.paged_backend``).  The per-round rng split lives
        INSIDE the jit (same split math as a host-side
        ``jax.random.split`` — the sampled stream is bitwise unchanged)
        so the serving loop can chain the returned key without a host
        round-trip.  Returns the (chunk_cap, K) sampled block (rows >=
        n_steps are garbage; the scheduler slices), the cache, each
        slot's final context length (``lengths + n_steps * active`` — the
        device-side mirror of the host pool's ``advance`` bookkeeping),
        each slot's final sampled token (the feed for the next chunk,
        letting steady-state decode chain device-to-device without
        materialising this chunk first; garbage for inactive rows, whose
        writes sink into reserved block 0 either way) and the advanced
        rng key."""
        K = ids.shape[0]
        rng, sub = jax.random.split(rng)

        def body(t, carry):
            cache, last, lengths, sub, out = carry
            sub, key = jax.random.split(sub)
            logits, cache = self.model.decode_step(
                params, cache, last[:, None], lengths, adapters=adapters,
                lora_scale=self.scale, adapter_ids=ids,
                block_tables=block_tables, paged_backend=backend)
            nxt = self._sample(logits, key, temperature)
            out = out.at[t].set(nxt)
            return (cache, nxt, lengths + active, sub, out)

        out0 = jnp.zeros((chunk_cap, K), jnp.int32)
        carry = jax.lax.fori_loop(
            0, n_steps, body, (cache, last, lengths, sub, out0))
        cache, new_last, new_lens, _, out = carry
        return out, cache, new_lens, new_last, rng

    def _prefill_chunk_impl(self, params, adapters, ids, cache, tokens,
                            lengths, n_new, block_tables, rng, temperature,
                            backend=None):
        """One chunked-prefill dispatch: scatter+attend ``tokens`` (K, T)
        — ``n_new[k]`` valid per row — through the paged cache, and sample
        each row's logits at its LAST valid position (the first emitted
        token for rows whose prompt just completed; garbage, discarded by
        the scheduler, for the rest).  Like ``_decode_chunk_impl`` the
        per-round rng split happens inside the jit (bitwise-identical
        stream) and the advanced lengths come back as a device array.
        Returns ((K,) sampled, cache, lengths + n_new, rng)."""
        rng, sub = jax.random.split(rng)
        logits, cache = self.model.prefill_step(
            params, cache, tokens, lengths, n_new, adapters=adapters,
            lora_scale=self.scale, adapter_ids=ids,
            block_tables=block_tables, paged_backend=backend)
        K, T, _ = logits.shape
        rows = jnp.arange(K, dtype=jnp.int32)
        lg = logits[rows, jnp.clip(n_new - 1, 0, T - 1)]       # (K, V)
        return (self._sample(lg[:, None], sub, temperature), cache,
                lengths + n_new, rng)

    def _verify_chunk_impl(self, params, adapters, ids, cache, tokens,
                           lengths, n_new, block_tables, backend=None):
        """One draft-verify dispatch: the SAME paged prefill dataflow as
        ``_prefill_chunk_impl`` (``Model.verify_step`` delegates to
        ``prefill_step`` — scatter + causal chunk attention against the
        pool, both backends), but the greedy sample comes back for EVERY
        chunk position, not just the last valid one: position ``t``'s
        argmax is the token non-speculative decoding would have emitted
        after feeding the chunk up to ``t``, which is exactly what the
        scheduler's acceptance rule compares drafts against.  Greedy-only
        (``generate_stream`` rejects spec_decode with temperature > 0),
        so no rng is threaded.  Returns ((K, T) int32 greedy, cache)."""
        logits, cache = self.model.verify_step(
            params, cache, tokens, lengths, n_new, adapters=adapters,
            lora_scale=self.scale, adapter_ids=ids,
            block_tables=block_tables, paged_backend=backend)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    @staticmethod
    def _sample(logits, rng, temperature):
        lg = logits[:, 0]
        greedy = jnp.argmax(lg, axis=-1)
        sampled = jax.random.categorical(rng, lg / jnp.maximum(temperature, 1e-6))
        return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)

    # -- loop ----------------------------------------------------------------
    def _run(self, params, adapters, ids, prompts: jnp.ndarray,
             sc: ServeConfig) -> jnp.ndarray:
        """prompts: (B, S_prompt) int32 -> (B, max_new_tokens) int32.

        With ``sc.eos_id`` set, a row that samples EOS emits ``sc.pad_id``
        from then on and the loop exits early once every row has finished
        (the output stays (B, max_new_tokens), pad-filled)."""
        B = prompts.shape[0]
        cache = self.model.init_decode_cache(B, sc.cache_len)
        cache, pos, last_logits = self._prefill(params, adapters, ids,
                                                cache, prompts)
        tok = jnp.argmax(last_logits, axis=-1)[:, None].astype(jnp.int32)
        rng = jax.random.PRNGKey(sc.seed)
        out = [tok[:, 0]]
        finished = (tok[:, 0] == sc.eos_id) if sc.eos_id is not None else None
        for _ in range(sc.max_new_tokens - 1):
            if finished is not None and bool(finished.all()):
                break
            rng, sub = jax.random.split(rng)
            nxt, cache = self._decode(params, adapters, ids, cache, tok,
                                      pos, sub, sc.temperature)
            if finished is not None:
                nxt = jnp.where(finished, jnp.int32(sc.pad_id), nxt)
                finished = finished | (nxt == sc.eos_id)
            pos = pos + 1
            tok = nxt[:, None]
            out.append(nxt)
        res = jnp.stack(out, axis=1)
        if res.shape[1] < sc.max_new_tokens:          # early all-EOS exit
            pad = jnp.full((B, sc.max_new_tokens - res.shape[1]),
                           sc.pad_id, jnp.int32)
            res = jnp.concatenate([res, pad], axis=1)
        return res


class Engine(_EngineBase):
    """Single-tenant engine: exactly one adapter tree bound per instance."""

    def __init__(self, model, cfg, params: Params,
                 adapters: Optional[Params] = None):
        super().__init__(model, cfg)
        self.params, self.adapters = params, adapters

    def generate(self, prompts: jnp.ndarray, sc: ServeConfig) -> jnp.ndarray:
        """prompts: (B, S_prompt) int32 -> (B, max_new_tokens) int32."""
        return self._run(self.params, self.adapters, None, prompts, sc)


class MultiTenantEngine(_EngineBase):
    """One compiled program serving every registered client.

    Requests carry ``client_id``; the engine resolves each to its bank slot
    (LRU-touching the registry) and threads the per-row slot vector through
    the model as ``adapter_ids``. Adapter registration/eviction between
    calls never changes bank shapes, so the jitted programs are reused
    across any tenant mix.
    """

    def __init__(self, model, cfg, params: Params, registry: AdapterRegistry):
        super().__init__(model, cfg)
        self.params, self.registry = params, registry
        self.last_stats: Optional[dict] = None   # set when a stream drains
        # cross-call prefix-cache state: (pool key, PagedKVCache, device
        # cache) persisted at stream drain so the NEXT generate call's
        # admission can match blocks sealed by this one.  Retained until a
        # prefix_cache stream with a different pool geometry replaces it or
        # release_prefix_cache() drops it — a deliberate warm cache, which
        # means the device pools stay resident across unrelated calls.
        self._warm: Optional[Tuple[tuple, PagedKVCache, Any]] = None

    def release_prefix_cache(self) -> None:
        """Drop the warm prefix-cache pool (host allocator + device K/V
        blocks).  The next ``prefix_cache=True`` stream starts cold; call
        this when a tenant mix moves on and the retained pool's device
        memory is worth more than future prefix hits."""
        self._warm = None

    def _paged_pool(self, num_slots: int, num_blocks: int, blocks_per: int,
                    sc: ServeConfig) -> Tuple[PagedKVCache, Any, bool]:
        """A (host allocator, device cache, reused) triple for one stream.
        With ``sc.prefix_cache``, reuse the pair persisted by the last
        drained stream when the pool geometry matches — sealed blocks (and
        their device K/V) survive, so shared prompt prefixes across calls
        skip prefill.  A geometry change or a stream abandoned mid-flight
        drops the warm state and starts cold (``last_stats
        ['prefix_pool_reused']`` says which happened)."""
        key = (num_slots, sc.block_size, num_blocks, blocks_per,
               sc.num_shards, sc.kv_dtype)
        if sc.prefix_cache:
            warm, self._warm = self._warm, None   # taken; restored at drain
            if warm is not None and warm[0] == key and warm[1].idle:
                return warm[1], warm[2], True
        if sc.num_shards > 1:
            kv: Any = ShardedPagedKVCache(
                sc.num_shards, num_slots, sc.block_size, num_blocks,
                blocks_per, prefix_cache=sc.prefix_cache)
        else:
            kv = PagedKVCache(num_slots, sc.block_size, num_blocks,
                              blocks_per, prefix_cache=sc.prefix_cache)
        cache = self.model.init_paged_decode_cache(num_slots, num_blocks,
                                                   sc.block_size,
                                                   kv_dtype=sc.kv_dtype)
        if sc.prefix_cache or sc.spec_decode:
            # recurrent SSM state is per-slot and dense — it cannot be
            # reconstructed from cached K/V blocks (a prefix hit would
            # silently skip state updates) nor rolled back token-granularly
            # (a verify dispatch advances it through rejected drafts)
            feature = ("prefix_cache" if sc.prefix_cache else "spec_decode")
            for entry in cache["blocks"].values():
                extra = set(entry) - {"k_pool", "v_pool",
                                      "k_scale", "v_scale"}
                if extra:
                    raise ValueError(
                        f"{feature}=True needs an attention-only model: "
                        f"recurrent per-slot state {sorted(extra)} cannot "
                        "be block-cached or rolled back")
        return kv, cache, False

    # -- continuous batching (the serving path) ------------------------------
    def session(self, sc: ServeConfig,
                requests: Optional[Sequence[Request]] = None
                ) -> "StreamSession":
        """An open-intake continuous-batching session over one paged pool.

        With ``requests`` the session starts closed-loop (the whole batch
        submitted up front — exactly what ``generate_stream`` drives).
        With ``requests=None`` it starts EMPTY and callers
        :meth:`StreamSession.submit` requests at arbitrary times between
        :meth:`StreamSession.step` calls — the open-loop mode behind
        ``launch/serve.py --serve`` and the trace harness
        (``serving/trace.py``).  Open-loop sessions need
        ``sc.num_blocks`` pinned: pool geometry cannot be derived from
        requests that have not arrived yet."""
        return StreamSession(self, sc, requests)

    def generate_stream(self, requests: Sequence[Request], sc: ServeConfig
                        ) -> Iterator[Tuple[int, List[int], bool]]:
        """Continuous batching over ``sc.batch_size`` slots of a paged KV
        cache, streamed: yields ``(rid, new_tokens, finished)`` increments
        as each device chunk is observed — callers see tokens the moment
        they exist, not when the batch drains.

        Prompts are consumed by CHUNKED prefill dispatches
        (``sc.prefill_chunk`` tokens per dispatch through the paged
        scatter+attend path) instead of one decode step per token; blocks
        are allocated on demand at chunk boundaries, and when the pool runs
        dry a victim is preempted (requeued with prompt+emitted as its new
        prompt — no tokens are lost or re-yielded): under
        ``sc.sched_policy="sla"`` the victim comes from the lowest
        priority class present, newest-first unless a candidate's
        cached/co-owned prefix makes preempting it strictly cheaper (see
        ``serving/scheduler.py::sla_victim``); under ``"fcfs"`` the
        newest active request goes, as before.
        ``rid`` is the request's index in ``requests``.  After the stream
        drains, ``self.last_stats`` records dispatch and preemption
        counters plus per-class queue-wait percentiles for the run.

        The loop body lives in :class:`StreamSession` (scheduling split
        from dispatch; ``sc.overlap`` pipelines host planning with device
        execution) — this wrapper is the closed-loop driver."""
        if not requests:
            raise ValueError("empty request batch")
        ses = StreamSession(self, sc, requests)
        while ses.has_work:
            yield from ses.step()
        ses.finalize()

    def generate(self, requests: Sequence[Request],
                 sc: ServeConfig) -> List[np.ndarray]:
        """Continuous batching over ``sc.batch_size`` slots of a paged KV
        cache: ragged prompts, per-request ``max_new_tokens``, per-row EOS.
        Requests beyond the slot count queue and are admitted as slots free
        up (preempted requests resume transparently).

        Returns one 1-D int32 array per request (request order), length <=
        its budget (EOS-terminated rows include the EOS token and stop).
        ``generate_stream`` is the incremental form this collects."""
        outs: List[List[int]] = [[] for _ in requests]
        for rid, toks, _ in self.generate_stream(requests, sc):
            outs[rid].extend(toks)
        return [np.asarray(o, np.int32) for o in outs]

    # -- fixed-shape batch (the PR-1 path, kept for equal-shape workloads) ---
    def generate_fixed(self, requests: Sequence[Request],
                       sc: ServeConfig) -> jnp.ndarray:
        """requests: B same-length prompts (possibly all different clients)
        -> (B, max_new_tokens) int32, row-aligned with ``requests``. Every
        row decodes the full shared ``sc.max_new_tokens`` budget."""
        if not requests:
            raise ValueError("empty request batch")
        ids = jnp.asarray([self.registry.acquire(r.client_id)
                           for r in requests], jnp.int32)
        prompts = jnp.stack([jnp.asarray(r.prompt, jnp.int32)
                             for r in requests])
        return self._run(self.params, self.registry.bank(), ids, prompts, sc)


class StreamSession:
    """One continuous-batching serving session over a paged KV pool.

    ``MultiTenantEngine.generate_stream``'s loop body, split into an object
    so SCHEDULING is separate from DISPATCH:

      * :meth:`submit` — enqueue a request at ANY time (open intake): the
        asyncio front end (``launch/serve.py --serve``) and the open-loop
        trace driver (``serving/trace.py``) call it between steps while
        earlier requests are mid-flight.  Closed-loop callers pass the
        whole batch at construction instead.
      * :meth:`step` — ONE engine round: admission -> chunk planning ->
        device dispatch -> observation, returning the ``(rid, new_tokens,
        finished)`` events the round produced (possibly none).
      * :meth:`finalize` — drain bookkeeping: builds ``engine.last_stats``
        and persists the warm prefix pool.  Idempotent.
      * :meth:`counters` — the scheduler's cumulative counts, readable at
        any moment; under a ``jax.profiler`` trace each step also writes
        ``serve:`` host spans (:meth:`span`).

    **Overlapped dispatch** (``ServeConfig.overlap``, default True): device
    chunks are enqueued through jax async dispatch and the host only
    BLOCKS on a chunk's samples when the next plan can depend on them.
    Decode and verify chunks always emit tokens, but a prefill chunk that
    feeds only prompt tokens emits nothing (``Scheduler.chunk_emits``) and
    its sampled array is garbage by construction — so it is handed to
    ``observe_prefill`` as the UN-materialised device array (host-side
    bookkeeping never reads it) and the host runs admission, prefix
    matching and chunk planning for chunk N+1 — and enqueues its dispatch
    — while the device is still executing chunk N.  Prompt-heavy phases,
    the open-loop TTFT bottleneck, pipeline with zero host-device
    round-trips.

    Decode rounds pipeline through ONE-ROUND-DEFERRED OBSERVATION: when
    the next plan provably cannot depend on a chunk's token values (no
    slot finishes inside it — ``Scheduler.chunk_defer_safe`` — and no
    EOS / speculation / prefix sealing / sharding is configured), the
    chunk's counts advance immediately (``observe_chunk_counts``) while
    its samples stay on device; the NEXT round dispatches chunk N+1 from
    device-chained state (final sampled token, lengths, rng, cached
    tables/ids) and only then materialises chunk N
    (``observe_chunk_values``), so the host's blocking wait overlaps
    chunk N+1's execution.  Events for a deferred chunk surface one
    round late; the tokens per rid are unchanged.  Both settings run the
    SAME dispatches with the SAME inputs, so token streams are BITWISE
    identical; ``overlap=False`` is the synchronous reference loop (one
    materialisation per chunk).
    """

    def __init__(self, engine: MultiTenantEngine, sc: ServeConfig,
                 requests: Optional[Sequence[Request]] = None):
        if sc.spec_decode:
            if sc.temperature > 0:
                raise ValueError(
                    "spec_decode is greedy-only (temperature must be 0): "
                    "acceptance compares drafts against argmax tokens, "
                    "which is what makes the stream bitwise-identical to "
                    "non-speculative decoding")
            if sc.spec_k < 1:
                raise ValueError(f"spec_decode needs spec_k >= 1, "
                                 f"got {sc.spec_k}")
        if sc.kv_dtype not in ("f32", "int8"):
            raise ValueError(
                f"kv_dtype must be 'f32' or 'int8', got {sc.kv_dtype!r}")
        if sc.num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {sc.num_shards}")
        if sc.num_shards > 1 and sc.batch_size % sc.num_shards != 0:
            raise ValueError(
                f"batch_size {sc.batch_size} not divisible by "
                f"{sc.num_shards} shards (slots split evenly)")
        self.engine, self.sc = engine, sc
        self.open_loop = requests is None
        if self.open_loop:
            # pool geometry cannot be derived from requests that have not
            # arrived yet — and must not be, or the compiled programs and
            # any warm prefix pool would churn with traffic
            if sc.num_blocks is None:
                raise ValueError(
                    "an open-loop StreamSession needs ServeConfig."
                    "num_blocks pinned (pool geometry cannot follow "
                    "requests that have not arrived yet)")
            num_slots = max(1, sc.batch_size)
            num_blocks = sc.num_blocks
            blocks_per = sc.max_blocks_per_slot or (num_blocks - 1)
            T = max(1, sc.prefill_chunk)
        else:
            prompts = [np.asarray(r.prompt, np.int32).reshape(-1)
                       for r in requests]
            budgets = [sc.max_new_tokens if r.max_new_tokens is None
                       else r.max_new_tokens for r in requests]
            max_span = max(p.size + b for p, b in zip(prompts, budgets))
            if sc.prefix_cache and sc.num_blocks is not None:
                # STABLE pool geometry: cross-call warm reuse must not
                # depend on this batch's request count or longest span (a
                # batch-derived key would silently drop the cache whenever
                # traffic varies) — slots track batch_size and the table
                # spans the whole pool unless pinned tighter.  Extra masked
                # gather lanes are exact zeros, so the wider table stays
                # bitwise-equal.
                num_slots = max(1, sc.batch_size)
                num_blocks = sc.num_blocks
                blocks_per = sc.max_blocks_per_slot or (num_blocks - 1)
            else:
                num_slots = max(1, min(sc.batch_size, len(requests)))
                if sc.num_shards > 1:      # equal per-shard slot counts
                    num_slots = (-(-num_slots // sc.num_shards)
                                 * sc.num_shards)
                blocks_per = (sc.max_blocks_per_slot
                              or blocks_needed(max_span, sc.block_size))
                num_blocks = sc.num_blocks or (1 + num_slots * blocks_per)
            # Preemption replays prompt+emitted, so prefill chunks must fit
            # the longest possible replayed prompt too — width is fixed per
            # run to keep one compiled prefill program.
            T = max(1, min(sc.prefill_chunk, max_span - 1))
        if sc.num_shards > 1 and (num_blocks - 1) % sc.num_shards != 0:
            raise ValueError(
                f"allocatable blocks {num_blocks - 1} not divisible by "
                f"{sc.num_shards} shards (set num_blocks = 1 + "
                f"{sc.num_shards}*k)")
        if sc.mesh is not None and num_slots % sc.mesh.shape.get("data", 1):
            raise ValueError(
                f"{num_slots} slots do not split over the mesh's "
                f"{sc.mesh.shape['data']}-way 'data' axis")
        self.kv, self.cache, self._reused = engine._paged_pool(
            num_slots, num_blocks, blocks_per, sc)
        # with a mesh: weights, bank and pool replicated on its devices
        # (a no-op for trees already placed there); the fused batch is
        # split by the "data" constraints in models/model.py
        self.params = self._place(engine.params)
        self.cache = self._place(self.cache)
        self.backend = runtime.paged_backend(sc.paged_backend
                                             or engine.cfg.paged_backend)
        self._geom_key = (num_slots, sc.block_size, num_blocks, blocks_per,
                          sc.num_shards, sc.kv_dtype)
        # pool-lifetime counter; stats report the delta for this session
        self._evicted0 = self.kv.evicted_cached
        if sc.num_shards > 1:
            self.sched: Any = ShardedScheduler(
                self.kv, registry=engine.registry, policy=sc.sched_policy,
                aging_ticks=sc.sched_aging,
                spec_k=sc.spec_k if sc.spec_decode else 0,
                spec_ngram=sc.spec_ngram)
        else:
            self.sched = Scheduler(self.kv, policy=sc.sched_policy,
                                   aging_ticks=sc.sched_aging,
                                   spec_k=sc.spec_k if sc.spec_decode else 0,
                                   spec_ngram=sc.spec_ngram)
        self._next_rid = 0
        if not self.open_loop:
            for r in requests:
                self.submit(r)
        self.bank = self._place(engine.registry.bank())
        # hot-swap: the registry's bank_epoch moves when an online update
        # (re-)registers a client mid-serve; step() re-snapshots the bank
        # at its next round boundary.  Untouched clients' slots hold
        # bitwise-identical weights across the swap, so their streams are
        # unchanged; the updated client's NEW requests also pick up a
        # bumped version() scope, invalidating its cached prefixes.
        self._bank_epoch = getattr(engine.registry, "bank_epoch", 0)
        self.bank_refreshes = 0
        self.ids = np.zeros((num_slots,), np.int32)
        self.rng = jax.random.PRNGKey(sc.seed)
        engine.last_stats = None     # a partially consumed stream has none
        self.T = T
        # verify chunks have their own fixed width (drafted tokens + the
        # feedback token) so the verify program also compiles once per run
        self.Tv = 1 + sc.spec_k
        # EOS can end a row long before its budget; keep chunks short so
        # its slot frees (and admits the queue head) at the next boundary.
        self.cap = (min(sc.scan_chunk, 8) if sc.eos_id is not None
                    else sc.scan_chunk)
        # overlap fast path: device-resident plan state.  Block tables /
        # adapter ids are re-marshalled only when ``kv.table_version``
        # moves (admission, growth, rollback, release); lengths chain
        # through the jit outputs (``advance`` is mirrored on device) and
        # fall back to a host refresh after verify rounds, whose
        # acceptance-dependent advance/rollback is host logic.
        self._tables_ver = -1
        self._bt_dev = None
        self._lens_dev = None
        self._lens_ok = False
        self._ids_dev = None
        # decode pipelining: in the steady decode state the feed token for
        # chunk N+1 is chunk N's final sample, available as a DEVICE array
        # from the decode jit — chaining it (with the active mask, constant
        # while ``table_version`` stands) lets the host dispatch N+1 and
        # only then materialise N ("one-round-deferred observation"),
        # so the host's observe/plan work for N overlaps N+1's execution.
        # Deferral is legal only when the next plan cannot depend on N's
        # token values — see ``Scheduler.chunk_defer_safe`` plus the config
        # gates here: EOS/speculation read values to stop or draft, prefix
        # sealing consumes them in ``advance``, and the sharded scheduler
        # doesn't implement the split.
        self._last_dev = None
        self._act_dev = None
        self._last_ok = False
        self._pending: Optional[Tuple[Any, int, List[int]]] = None
        self._defer_cfg_ok = (sc.overlap and sc.num_shards == 1
                              and sc.eos_id is None and not sc.spec_decode
                              and not sc.prefix_cache)
        self._finalized = False
        self.steps_run = 0               # step() calls: the spans' step=

    # -- intake --------------------------------------------------------------
    def submit(self, request: Request,
               arrival_time: Optional[float] = None) -> int:
        """Enqueue ``request``; returns its rid (submission order — the rid
        tagged on this request's events).  Open-loop drivers pass
        ``arrival_time`` (``time.monotonic()`` seconds) so admission also
        records WALL-CLOCK queue waits
        (``last_stats["classes"][cls]["wait_wall_ms_*"]``)."""
        rid, self._next_rid = self._next_rid, self._next_rid + 1
        reg = self.engine.registry
        p = np.asarray(request.prompt, np.int32).reshape(-1)
        b = (self.sc.max_new_tokens if request.max_new_tokens is None
             else request.max_new_tokens)
        # cached K/V depends on the adapter: scope hits by client AND by
        # the registry's version of its weights (re-registration
        # invalidates without any explicit flush)
        scope = (request.client_id, reg.version(request.client_id))
        # explicit request priority wins; else the client's registered
        # default; else the scheduler's baseline class
        priority = (request.priority
                    or reg.default_priority(request.client_id)
                    or "batch")
        self.sched.submit(rid, request.client_id, p, b, scope=scope,
                          priority=priority, deadline=request.deadline,
                          arrival_time=arrival_time)
        return rid

    @property
    def has_work(self) -> bool:
        """True while any request is queued or holds a slot."""
        return self.sched.has_work

    def counters(self) -> dict:
        """The scheduler's cumulative counts (``scheduler.COUNTERS``), cheap
        enough to snapshot at any moment: the difference of two snapshots
        is what the session did between them.  ``steps``: decode steps;
        ``decode_row_steps``: active rows summed over them (occupancy is
        ``decode_row_steps / (steps * batch_size)``); ``prefill_tokens``:
        real tokens fed by prefill chunks; ``kv_grid_blocks`` and
        ``kv_live_blocks``: the decode kernel's grid over table blocks
        (slots x table width per step) and the part of it that holds live
        K/V.  A deferred decode chunk counts when it is dispatched.  Only
        the stepping thread writes the counts; a snapshot taken from
        another thread while a step runs may see one chunk's counts half
        applied."""
        return {k: getattr(self.sched, k) for k in COUNTERS}

    # -- one engine round ----------------------------------------------------
    def span(self, what: str, **args):
        """A host span ``serve:<what>`` in a running ``jax.profiler``
        trace, on the device ops' clock, tagged with this session's step
        index; next to free when no trace is running."""
        return jax.profiler.TraceAnnotation(SPAN_PREFIX + what,
                                            step=self.steps_run, **args)

    def step(self) -> List[Tuple[int, List[int], bool]]:
        """Admission -> chunk planning -> device dispatch -> observation.
        Returns this round's ``(rid, new_tokens, finished)`` events ([] on
        an idle session).  Raises ``RuntimeError`` if queued work cannot
        make progress (a request that can never fit the pool)."""
        self.steps_run += 1
        with self.span("step"):
            return self._step()

    def _step(self) -> List[Tuple[int, List[int], bool]]:
        eng, sc, sched = self.engine, self.sc, self.sched
        with self.span("bank_swap"):
            epoch = getattr(eng.registry, "bank_epoch", 0)
            if epoch != self._bank_epoch:
                # online update landed between rounds: swap in the new bank
                # for every dispatch from here on.  A deferred (pipelined)
                # chunk was already dispatched under the old snapshot — its
                # values are unaffected by when we materialise them, so no
                # flush needed.
                self.bank = self._place(eng.registry.bank())
                self._bank_epoch = epoch
                self.bank_refreshes += 1
        flushed: List[Tuple[int, List[int], bool]] = []
        if self._pending is not None and (
                sched.queued or sched.prefill_pending
                or self._growth_possible()):
            # leave the pipelined steady state: the deferred chunk's values
            # must land BEFORE admission or planning can preempt a slot
            # (preemption replays prompt+emitted, which must include them)
            flushed = self._flush_pending()
        with self.span("admit"):
            for slot, cid in sched.admit():
                self.ids[slot] = eng.registry.acquire(cid)
                self.cache = reset_slot(self.cache, slot)
                self._ids_dev = None
        with self.span("plan"):
            plan = sched.prepare_chunk(self.T, self.cap)
        if plan is None:
            if sched.has_work:           # nothing active: admit failed
                raise RuntimeError("scheduler stalled with queued work")
            return flushed               # idle open-loop session
        kind = plan[0]
        with self.span("tables"):
            bt, lens, ids = self._marshal_tables()
            if kind == "prefill":
                arrs = sched.prefill_arrays(self.T)
            elif kind == "verify":
                arrs = sched.verify_arrays(self.Tv)
            if kind != "decode":
                feed = jnp.asarray(arrs["tokens"]), jnp.asarray(arrs["n_new"])
            elif sc.overlap and self._last_ok:
                feed = self._last_dev, self._act_dev
            else:
                st = sched.chunk_arrays()
                feed = jnp.asarray(st["last"]), jnp.asarray(st["active"])
        if kind == "prefill":
            with self.span("dispatch", kind=kind), self._mesh():
                sampled, self.cache, self._lens_dev, self.rng = (
                    eng._prefill_chunk(
                        self.params, self.bank, ids, self.cache, feed[0],
                        lens, feed[1], bt, self.rng, sc.temperature,
                        backend=self.backend))
            # THE overlap point: a chunk that emits no token has a sampled
            # array nothing will read (observe_prefill only indexes it for
            # feedback rows / completing prompts), so skip materialising it
            # — the host returns to planning the next chunk while this one
            # is still executing on device.
            if not sc.overlap or sched.chunk_emits(arrs["n_new"]):
                with self.span("sync"):
                    sampled = np.asarray(sampled)
            self._last_ok = False        # completing prompts seed next_token
            with self.span("observe"):
                events = sched.observe_prefill(arrs["n_new"], sampled,
                                               eos_id=sc.eos_id)
        elif kind == "verify":
            # keep the per-round rng consumption identical to the other
            # chunk kinds (they split inside the jit) so streams stay
            # bitwise-stable across scheduling mixes
            self.rng, _ = jax.random.split(self.rng)
            with self.span("dispatch", kind=kind), self._mesh():
                greedy, self.cache = eng._verify_chunk(
                    self.params, self.bank, ids, self.cache, feed[0], lens,
                    feed[1], bt, backend=self.backend)
            # acceptance decides the advance/rollback amounts on host
            self._lens_ok, self._last_ok = False, False
            with self.span("sync"):
                greedy = np.asarray(greedy)
            with self.span("observe"):
                events = sched.observe_verify(arrs["n_new"], greedy,
                                              eos_id=sc.eos_id)
        else:
            n = plan[1]
            defer = self._defer_cfg_ok and sched.chunk_defer_safe(n)
            last, act = feed
            with self.span("dispatch", kind=kind), self._mesh():
                (out, self.cache, self._lens_dev, self._last_dev,
                 self.rng) = eng._decode_chunk(
                    self.params, self.bank, ids, self.cache, last, act,
                    lens, bt, jnp.int32(n), self.rng, sc.temperature,
                    chunk_cap=self.cap, backend=self.backend)
            self._act_dev, self._last_ok = act, sc.overlap
            if self._pending is not None:
                # this chunk is queued behind the deferred one, so
                # materialising the latter's samples here overlaps with
                # this chunk's device execution — the pipelining payoff
                flushed = self._flush_pending()
            if defer:
                with self.span("observe"):
                    self._pending = (out, n, sched.observe_chunk_counts(n))
                return flushed
            with self.span("sync"):
                out = np.asarray(out)[:n]
            with self.span("observe"):
                events = sched.observe_chunk(out, eos_id=sc.eos_id)
        return flushed + events if flushed else events

    def _marshal_tables(self):
        """Device block tables, lengths and adapter ids for the next
        dispatch.  Synchronous reference loop: rebuilt every round.
        Overlap fast path: the cached device arrays are reused while
        ``table_version`` stands still — on advance-only rounds (the steady
        decode state) the host ships only the chunk plan, and lengths come
        chained from the previous jit output instead of a fresh
        host->device copy."""
        ver = self.kv.table_version
        if not self.sc.overlap or ver != self._tables_ver:
            self._bt_dev, self._lens_dev = self.kv.device_tables()
            self._tables_ver, self._lens_ok = ver, True
            # any table move (admit/growth/rollback/release) can change the
            # active set or a slot's feed token — drop the chained decode
            # state and remarshal it from the scheduler this round
            self._last_ok, self._act_dev = False, None
        elif not self._lens_ok:          # tables stand, verify moved lengths
            self._lens_dev = self.kv.device_tables()[1]
            self._lens_ok = True
        if self._ids_dev is None:
            # .copy(): self.ids is mutated in place on admit while an
            # earlier dispatch holding a (possibly zero-copy aliased)
            # view may still be queued — snapshot, never a live view
            self._ids_dev = jnp.asarray(self.ids.copy())
        return self._bt_dev, self._lens_dev, self._ids_dev

    # -- mesh --------------------------------------------------------------
    def _mesh(self):
        """Context for one dispatch: ``jax.set_mesh(sc.mesh)`` (the model's
        "data" constraints then bind the fused batch to devices), or
        nothing on one device.  A fresh object each time: ``set_mesh``
        takes effect when constructed."""
        if self.sc.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.sc.mesh)

    def _place(self, tree):
        """``tree`` replicated on the mesh's devices, or as is without a
        mesh."""
        if self.sc.mesh is None:
            return tree
        return jax.device_put(tree, NamedSharding(self.sc.mesh, P()))

    # -- deferred-observation plumbing ---------------------------------------
    def _growth_possible(self) -> bool:
        """Whether ANY active slot's next decode chunk (at most ``cap``
        steps) could outgrow its owned blocks.  Growth is the only path to
        preemption on a pure-decode round, so while this is False the next
        ``prepare_chunk`` provably leaves the slot set untouched and a
        deferred chunk may stay unmaterialised through it."""
        kv = self.kv
        for slot in self.sched.active_slots:
            if (int(kv.lengths[slot]) + self.cap
                    > kv.owned_blocks(slot) * kv.block_size):
                return True
        return False

    def _flush_pending(self) -> List[Tuple[int, List[int], bool]]:
        """Materialise the deferred decode chunk (blocking on its dispatch;
        anything queued behind it keeps running) and fold its values into
        the scheduler — its events, one round late."""
        with self.span("flush"):
            out, n, slots = self._pending
            self._pending = None
            with self.span("sync"):
                out = np.asarray(out)[:n]
            return self.sched.observe_chunk_values(slots, out)

    # -- drain ---------------------------------------------------------------
    def finalize(self) -> dict:
        """Build ``engine.last_stats`` for this session and (with
        ``prefix_cache``) persist the warm pool for the next one.  Safe to
        call more than once; returns the stats dict."""
        if self._finalized:
            return self.engine.last_stats
        self._finalized = True
        if self._pending is not None:    # stream abandoned mid-pipeline
            self._flush_pending()
        sc, sched, kv = self.sc, self.sched, self.kv
        classes = {}
        for cname in PRIORITY_CLASSES:
            waits = sched.wait_ticks.get(cname, [])
            walls = sched.wait_wall.get(cname, [])
            if not waits and cname not in sched.preemptions_by_class:
                continue                     # class unused this stream
            entry = {
                "admitted": len(waits),
                "wait_p50": float(np.percentile(waits, 50)) if waits else 0.0,
                "wait_p99": float(np.percentile(waits, 99)) if waits else 0.0,
                "preemptions": sched.preemptions_by_class.get(cname, 0)}
            if walls:     # only present when driven with arrival_time
                entry["wait_wall_ms_p50"] = float(
                    np.percentile(walls, 50) * 1e3)
                entry["wait_wall_ms_p99"] = float(
                    np.percentile(walls, 99) * 1e3)
            classes[cname] = entry
        counts = self.counters()
        stats = {"decode_steps": counts.pop("steps"), **counts,
                 "spec_decode": sc.spec_decode,
                 "verify_dispatches": sched.verify_dispatches,
                 "drafted_tokens": sched.drafted_tokens,
                 "accepted_tokens": sched.accepted_tokens,
                 "acceptance_rate": (sched.accepted_tokens
                                     / max(1, sched.drafted_tokens)),
                 "rollback_tokens": sched.rollback_tokens,
                 "rollback_blocks": sched.rollback_blocks,
                 "prefix_hit_rate": (counts["prefix_hit_tokens"]
                                     / max(1, counts["prompt_tokens"])),
                 "prefix_cached_blocks": kv.cached_blocks,
                 "prefix_evictions": kv.evicted_cached - self._evicted0,
                 "prefix_pool_reused": self._reused,
                 "adapter_bank_refreshes": self.bank_refreshes,
                 "sched_policy": sc.sched_policy,
                 "num_shards": sc.num_shards,
                 "kv_dtype": sc.kv_dtype,
                 "paged_backend": self.backend,
                 "overlap": sc.overlap,
                 "open_loop": self.open_loop,
                 # queue waits by class: wait_p50/p99 in admission rounds
                 # (ticks); wait_wall_ms_* in wall-clock milliseconds when
                 # the session was driven open-loop with arrival times
                 "classes": classes}
        if sc.num_shards > 1:
            stats["shard_placements"] = dict(sched.placed)
        self.engine.last_stats = stats
        if sc.prefix_cache:
            self.engine._warm = (self._geom_key, self.kv, self.cache)
        return stats
