"""Sharded serving: partitioned block pools, adapter banks and placement.

Scaling past one device's HBM means splitting the serving state, not the
engine: this module partitions the three stateful serving structures across
``num_shards`` shards while keeping ONE fused device dispatch per engine
round (request slots are data-parallel across shards — on a mesh the
``"data"`` axis carries them, see ``models/layers.py::maybe_shard``):

* :class:`ShardedPagedKVCache` — ``num_shards`` independent
  :class:`~repro.serving.kv_cache.PagedKVCache` allocators, each with its
  own free list, block tables, seal chains and prefix index over a disjoint
  slice of one global device block pool.  Shard ``s`` owns global blocks
  ``[1 + s*P, 1 + (s+1)*P)`` (``P`` allocatable blocks per shard); block 0
  stays the one global scratch target.  ``device_tables()`` translates each
  shard's local table into global ids and concatenates, so the jitted
  paged-attention steps are untouched.  ``check_invariants`` holds PER
  SHARD — conservation in a starved shard is independent of a roomy one.

* :class:`ShardedAdapterRegistry` — ``num_shards`` fixed-capacity
  :class:`~repro.serving.registry.AdapterRegistry` banks (``capacity /
  num_shards`` clients each).  A client is *homed* on one shard (fewest
  resident clients at first registration); ``bank()`` concatenates the
  per-shard banks along the client axis so global adapter slots
  (``shard * capacity_per_shard + local``) index it directly.

* :class:`ShardedScheduler` — a placement-aware coordinator over
  ``num_shards`` unmodified :class:`~repro.serving.scheduler.Scheduler`
  instances.  ``submit`` routes each request to the shard already holding
  its longest cached prefix, else its client's adapter home shard, else the
  least-loaded shard; preemption stays WITHIN a shard (each per-shard
  scheduler only ever sees its own slots).  Each engine round the
  coordinator negotiates one global round kind — any shard still
  prefilling forces a prefill round, else any shard with drafts forces a
  verify round, else a decode round of the min step count — and forces it
  through every shard's ``prepare_chunk(kind=..., steps=...)``, then
  concatenates the per-shard host arrays into one fused dispatch and
  slices the observations back.  The coordinator duck-types the single
  ``Scheduler`` interface, so the engine loop drives either unchanged.

Everything here is host bookkeeping: one device program, one block pool
tensor, one adapter bank tensor.  On a multi-device mesh the fused batch
axis is laid out over ``"data"`` (slots are shard-contiguous, so shard
boundaries coincide with device boundaries); on one device the fusion
amortises per-dispatch overhead exactly like PR 1's batched engine.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.kv_cache import PagedKVCache
from repro.serving.registry import AdapterRegistry
from repro.serving.scheduler import Scheduler

Params = Any


class ShardedPagedKVCache:
    """``num_shards`` disjoint :class:`PagedKVCache` partitions of one pool.

    ``num_slots`` and ``num_blocks`` are GLOBAL (``num_blocks`` includes
    the shared scratch block 0); both ``num_slots`` and ``num_blocks - 1``
    must divide evenly into ``num_shards``.  Global slot ``s * slots_per_
    shard + i`` is shard ``s``'s local slot ``i``; global block ``b`` (>0)
    of shard ``s`` is local block ``b - s * blocks_per_shard``.
    """

    def __init__(self, num_shards: int, num_slots: int, block_size: int,
                 num_blocks: int, max_blocks_per_slot: int,
                 prefix_cache: bool = False):
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        if num_slots % num_shards != 0:
            raise ValueError(
                f"num_slots {num_slots} not divisible by {num_shards} shards")
        if (num_blocks - 1) % num_shards != 0:
            raise ValueError(
                f"allocatable blocks {num_blocks - 1} not divisible by "
                f"{num_shards} shards")
        self.num_shards = num_shards
        self.num_slots = num_slots
        self.slots_per_shard = num_slots // num_shards
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.blocks_per_shard = (num_blocks - 1) // num_shards
        self.max_blocks_per_slot = max_blocks_per_slot
        self.prefix_cache = prefix_cache
        self.shards: List[PagedKVCache] = [
            PagedKVCache(self.slots_per_shard, block_size,
                         1 + self.blocks_per_shard, max_blocks_per_slot,
                         prefix_cache=prefix_cache)
            for _ in range(num_shards)]

    # ---- slot/block translation -------------------------------------------
    def shard_of_slot(self, slot: int) -> Tuple[int, int]:
        """Global slot -> (shard, local slot)."""
        return divmod(slot, self.slots_per_shard)

    def global_slot(self, shard: int, local: int) -> int:
        return shard * self.slots_per_shard + local

    # ---- aggregate capacity -----------------------------------------------
    @property
    def free_blocks(self) -> int:
        return sum(sh.free_blocks for sh in self.shards)

    @property
    def cached_blocks(self) -> int:
        return sum(sh.cached_blocks for sh in self.shards)

    @property
    def allocatable_blocks(self) -> int:
        return sum(sh.allocatable_blocks for sh in self.shards)

    @property
    def evicted_cached(self) -> int:
        return sum(sh.evicted_cached for sh in self.shards)

    @property
    def table_version(self) -> int:
        """Aggregate block-table mutation counter: strictly increases when
        any shard's tables change (per-shard counters are monotonic), so
        the engine's overlap fast path can key its cached device tables on
        it exactly as in the single-pool case."""
        return sum(sh.table_version for sh in self.shards)

    @property
    def lengths(self) -> np.ndarray:
        """Global per-slot context lengths (concatenated snapshot)."""
        return np.concatenate([sh.lengths for sh in self.shards])

    @property
    def idle(self) -> bool:
        return all(sh.idle for sh in self.shards)

    def fits(self, n_tokens: int) -> bool:
        """Shards are geometry-identical: fits on one == fits on any."""
        return self.shards[0].fits(n_tokens)

    # ---- placement probe ---------------------------------------------------
    def best_prefix_shard(self, scope: Any, tokens: Sequence[int]
                          ) -> Tuple[Optional[int], int]:
        """The shard holding the longest cached prefix of ``tokens`` under
        ``scope`` as ``(shard, hit tokens)``; ``(None, 0)`` when no shard
        holds any of it (or prefix caching is off)."""
        best, best_hit = None, 0
        for s, sh in enumerate(self.shards):
            hit = len(sh.match_prefix(scope, tokens)[0]) * self.block_size
            if hit > best_hit:
                best, best_hit = s, hit
        return best, best_hit

    # ---- device view -------------------------------------------------------
    def device_tables(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Global ``(block_tables, lengths)`` over the fused slot axis:
        each shard's local block ids shift into its global slice (block 0
        stays 0 — the shared scratch row)."""
        tables, lengths = [], []
        for s, sh in enumerate(self.shards):
            t = sh.block_tables
            off = s * self.blocks_per_shard
            tables.append(np.where(t > 0, t + off, 0).astype(np.int32))
            lengths.append(sh.lengths)
        return (jnp.asarray(np.concatenate(tables, axis=0)),
                jnp.asarray(np.concatenate(lengths, axis=0)))

    # ---- invariants --------------------------------------------------------
    def check_invariants(self) -> None:
        """Per-shard allocator invariants plus global disjointness: every
        global block id referenced by some shard's table falls inside that
        shard's slice (so no shard can ever gather another's content)."""
        for s, sh in enumerate(self.shards):
            sh.check_invariants()
            lo = 1 + s * self.blocks_per_shard
            hi = lo + self.blocks_per_shard
            t = sh.block_tables
            used = np.where(t > 0, t + s * self.blocks_per_shard, 0)
            bad = used[(used != 0) & ((used < lo) | (used >= hi))]
            assert bad.size == 0, \
                f"shard {s} references blocks outside [{lo}, {hi}): {bad}"


class ShardedAdapterRegistry:
    """``num_shards`` fixed-capacity adapter banks behind one interface.

    A client is homed on one shard at first registration (fewest resident
    clients, lowest index on ties) and stays there until evicted — the
    scheduler uses :meth:`shard_of` to co-locate a client's requests with
    its adapter.  Global adapter slots are ``shard * capacity_per_shard +
    local``; :meth:`bank` concatenates the per-shard banks along the
    client axis so the engine's per-row ``adapter_ids`` index it directly
    (the concatenation is cached and invalidated on register/evict).

    With ``ranks=[...]`` every shard carries the same rank-bucket layout
    and :meth:`bank` concatenates shard banks *per bucket* (the list
    leaves zip through ``jax.tree.map``), so global slots order as
    [bucket0: shard0..shardN, bucket1: shard0..shardN, ...] — see
    :meth:`_global_slot`.
    """

    def __init__(self, cfg, capacity: int, num_shards: int,
                 rank: Optional[int] = None, bank_dtype: str = "f32",
                 ranks: Optional[Sequence[int]] = None):
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        if capacity % num_shards != 0:
            raise ValueError(
                f"capacity {capacity} not divisible by {num_shards} shards")
        self.capacity = capacity
        self.num_shards = num_shards
        self.capacity_per_shard = capacity // num_shards
        self.bank_dtype = bank_dtype
        self.shards: List[AdapterRegistry] = [
            AdapterRegistry(cfg, self.capacity_per_shard, rank,
                            bank_dtype=bank_dtype, ranks=ranks)
            for _ in range(num_shards)]
        self._home: Dict[Any, int] = {}
        self._versions: Dict[Any, int] = {}  # survives cross-shard moves
        self._bank_cache: Optional[Params] = None

    # ---- bookkeeping ------------------------------------------------------
    def __contains__(self, client_id) -> bool:
        return client_id in self._home

    def __len__(self) -> int:
        return len(self._home)

    @property
    def resident(self) -> List[Any]:
        """Client ids grouped by shard (shard-major, LRU order within)."""
        return [cid for sh in self.shards for cid in sh.resident]

    @property
    def evictions(self) -> int:
        return sum(sh.evictions for sh in self.shards)

    def shard_of(self, client_id) -> Optional[int]:
        """The client's home shard, or None when not resident."""
        return self._home.get(client_id)

    @property
    def ragged(self) -> bool:
        return self.shards[0].ragged

    @property
    def bucket_ranks(self) -> List[int]:
        return self.shards[0].bucket_ranks

    @property
    def bank_epoch(self) -> int:
        """Monotone bank-content counter (sum over shards) — the serving
        session's hot-swap signal, same contract as the single registry."""
        return sum(sh.bank_epoch for sh in self.shards)

    def _global_slot(self, s: int, local_slot: int) -> int:
        """Per-shard slot -> global slot under the per-bucket concat order
        of :meth:`bank`: [bucket0: shard0..shardN, bucket1: ...].  With one
        bucket this reduces to the legacy ``s * capacity_per_shard +
        local``."""
        sub = self.shards[s]
        b, loc = sub.bucket_of_slot(local_slot)
        off = self.num_shards * sum(sub.bucket_sizes[:b])
        return off + s * sub.bucket_sizes[b] + loc

    def slot_ranks(self) -> np.ndarray:
        """(capacity,) int32 effective rank per GLOBAL slot (see
        ``AdapterRegistry.slot_ranks``)."""
        out = np.zeros(self.capacity, np.int32)
        for s, sh in enumerate(self.shards):
            sub = sh.slot_ranks()
            for local in range(sh.capacity):
                out[self._global_slot(s, local)] = sub[local]
        return out

    def _place(self, client_id) -> int:
        if client_id in self._home:
            return self._home[client_id]
        return min(range(self.num_shards),
                   key=lambda s: (len(self.shards[s]), s))

    # ---- writes -----------------------------------------------------------
    def register(self, client_id, adapters: Params,
                 default_priority: Optional[str] = None) -> int:
        """Install on the client's home shard (assigned now if new);
        returns the GLOBAL bank slot.  A full shard evicts its own LRU
        client — eviction pressure stays within the shard."""
        s = self._place(client_id)
        sub = self.shards[s]
        before = set(sub.resident)
        local = sub.register(client_id, adapters,
                             default_priority=default_priority)
        for evicted in before - set(sub.resident) - {client_id}:
            self._home.pop(evicted, None)
        self._home[client_id] = s
        # version lives at THIS level: a client evicted from one shard and
        # re-registered on another must keep climbing (per-shard counters
        # restart, which would resurrect stale prefix-cache scopes)
        self._versions[client_id] = self._versions.get(client_id, 0) + 1
        self._bank_cache = None
        return self._global_slot(s, local)

    def register_dual(self, client_id, personalized: Params, global_: Params,
                      fusion_weights,
                      default_priority: Optional[str] = None) -> int:
        from repro.core.dual_lora import merge
        self.shards[self._place(client_id)]._validate_dual(personalized,
                                                           global_)
        fused = merge(personalized, global_, jnp.asarray(fusion_weights))
        return self.register(client_id, fused,
                             default_priority=default_priority)

    def evict(self, client_id) -> None:
        if client_id not in self._home:
            raise KeyError(f"client {client_id!r} is not resident "
                           f"(resident: {self.resident})")
        s = self._home.pop(client_id)
        self.shards[s].evict(client_id)
        self._bank_cache = None

    # ---- reads ------------------------------------------------------------
    def acquire(self, client_id) -> int:
        s = self._home.get(client_id)
        if s is None:
            raise KeyError(f"client {client_id!r} is not resident "
                           f"(resident: {self.resident})")
        return self._global_slot(s, self.shards[s].acquire(client_id))

    def default_priority(self, client_id) -> Optional[str]:
        s = self._home.get(client_id)
        return None if s is None else self.shards[s].default_priority(client_id)

    def version(self, client_id) -> int:
        """Monotone per-client weight version (prefix-cache scope); raises
        ``KeyError`` for a client that was never registered.  Tracked at
        the sharded level so it survives cross-shard re-registration."""
        if client_id not in self._versions:
            raise KeyError(f"client {client_id!r} was never registered "
                           f"(resident: {self.resident})")
        return self._versions[client_id]

    def bank(self) -> Params:
        """The global stacked adapter tree: per-shard banks concatenated
        along the client axis (leaves (n_periods, capacity, d_in, r));
        ragged banks concatenate per bucket (list leaves zip through
        ``jax.tree.map``), matching :meth:`_global_slot`."""
        if self._bank_cache is None:
            banks = [sh.bank() for sh in self.shards]
            self._bank_cache = jax.tree.map(
                lambda *ls: jnp.concatenate(ls, axis=1), *banks)
        return self._bank_cache


class ShardedScheduler:
    """Placement-aware coordinator over per-shard :class:`Scheduler`\\ s.

    Duck-types the single-pool ``Scheduler`` driving interface (submit /
    admit / prepare_chunk / *_arrays / observe_* / stats counters) over the
    GLOBAL slot axis, so ``MultiTenantEngine.generate_stream`` runs either
    unchanged.  ``registry`` (optional) provides ``shard_of`` for
    adapter-affinity placement — any object without it degrades to
    prefix-affinity + least-loaded placement only.
    """

    def __init__(self, kv: ShardedPagedKVCache, registry: Any = None,
                 policy: str = "sla", aging_ticks: int = 16,
                 victim_policy: Optional[Callable] = None,
                 spec_k: int = 0, spec_ngram: int = 3):
        self.kv = kv
        self.registry = registry
        self.shards: List[Scheduler] = [
            Scheduler(pool, policy=policy, aging_ticks=aging_ticks,
                      victim_policy=victim_policy, spec_k=spec_k,
                      spec_ngram=spec_ngram)
            for pool in kv.shards]
        self.policy = policy
        self.spec_k = spec_k
        self.placements: Dict[int, int] = {}        # rid -> shard
        self.placed = {"prefix": 0, "adapter": 0, "load": 0}

    # ---- placement --------------------------------------------------------
    def _load(self, s: int) -> int:
        sh = self.shards[s]
        return len(sh.active_slots) + len(sh._queue)

    def place(self, client_id, scope: Any, prompt) -> Tuple[int, str]:
        """The shard for a new request and why: ``"prefix"`` (a shard holds
        a cached prefix of the prompt — re-prefill saved is worth more than
        balance), ``"adapter"`` (the client's adapter home shard), or
        ``"load"`` (least active+queued requests, most allocatable blocks
        and lowest index breaking ties)."""
        shard, hit = self.kv.best_prefix_shard(scope, prompt)
        if shard is not None and hit > 0:
            return shard, "prefix"
        shard_of = getattr(self.registry, "shard_of", None)
        if shard_of is not None:
            shard = shard_of(client_id)
            if shard is not None:
                return shard, "adapter"
        shard = min(range(len(self.shards)),
                    key=lambda s: (self._load(s),
                                   -self.kv.shards[s].allocatable_blocks, s))
        return shard, "load"

    # ---- intake -----------------------------------------------------------
    def submit(self, rid: int, client_id: Any, prompt, budget: int,
               scope: Any = None, priority: str = "batch",
               deadline: Optional[float] = None,
               arrival_time: Optional[float] = None) -> int:
        """Place and enqueue; returns the chosen shard."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        shard, why = self.place(client_id,
                                client_id if scope is None else scope,
                                prompt)
        self.shards[shard].submit(rid, client_id, prompt, budget,
                                  scope=scope, priority=priority,
                                  deadline=deadline,
                                  arrival_time=arrival_time)
        self.placements[rid] = shard
        self.placed[why] += 1
        return shard

    # ---- state ------------------------------------------------------------
    @property
    def has_work(self) -> bool:
        return any(sh.has_work for sh in self.shards)

    @property
    def active_slots(self) -> List[int]:
        return [self.kv.global_slot(s, slot)
                for s, sh in enumerate(self.shards)
                for slot in sh.active_slots]

    @property
    def prefill_pending(self) -> bool:
        return any(sh.prefill_pending for sh in self.shards)

    @property
    def results(self) -> Dict[int, np.ndarray]:
        merged: Dict[int, np.ndarray] = {}
        for sh in self.shards:
            merged.update(sh.results)
        return merged

    # ---- lifecycle --------------------------------------------------------
    def admit(self) -> List[Tuple[int, Any]]:
        """Per-shard admission; returns GLOBAL (slot, client_id) pairs."""
        admitted = []
        for s, sh in enumerate(self.shards):
            for slot, cid in sh.admit():
                admitted.append((self.kv.global_slot(s, slot), cid))
        return admitted

    def negotiate_round(self, decode_cap: int):
        """One global round kind across shards (a fused dispatch has one
        shape): any shard still prefilling -> prefill (others ride as
        1-token feedback rows); else any shard with speculative drafts ->
        verify (draft-less shards ride as 1-token verify rows); else decode
        for the min over shards' planned step counts (so no slot anywhere
        overshoots its budget).  None when no shard has an active slot."""
        prefs = [p for p in (sh.preferred_round(decode_cap)
                             for sh in self.shards) if p is not None]
        if not prefs:
            return None
        if any(p[0] == "prefill" for p in prefs):
            return ("prefill", None)
        if any(p[0] == "verify" for p in prefs):
            return ("verify", None)
        return ("decode", min(p[1] for p in prefs))

    def prepare_chunk(self, prefill_chunk: int, decode_cap: int):
        """Negotiate the global round and force it through every shard's
        planner (growth + within-shard preemption happen there).  Returns
        the global plan, shaped exactly like ``Scheduler.prepare_chunk``."""
        plan = self.negotiate_round(decode_cap)
        if plan is None:
            return None
        kind, steps = plan
        for sh in self.shards:
            sh.prepare_chunk(prefill_chunk, decode_cap, kind=kind,
                             steps=steps)
        return plan

    # ---- fused host arrays -------------------------------------------------
    def _concat(self, parts: List[Dict[str, np.ndarray]]
                ) -> Dict[str, np.ndarray]:
        return {k: np.concatenate([p[k] for p in parts], axis=0)
                for k in parts[0]}

    def prefill_arrays(self, width: int):
        return self._concat([sh.prefill_arrays(width) for sh in self.shards])

    def verify_arrays(self, width: int):
        return self._concat([sh.verify_arrays(width) for sh in self.shards])

    def chunk_arrays(self):
        return self._concat([sh.chunk_arrays() for sh in self.shards])

    def _rows(self, s: int) -> slice:
        K = self.kv.slots_per_shard
        return slice(s * K, (s + 1) * K)

    def chunk_emits(self, n_new) -> bool:
        """Any shard emitting makes the fused chunk an emitting chunk."""
        return any(sh.chunk_emits(n_new[self._rows(s)])
                   for s, sh in enumerate(self.shards))

    def observe_prefill(self, n_new, sampled, eos_id=None):
        events = []
        for s, sh in enumerate(self.shards):
            r = self._rows(s)
            events.extend(sh.observe_prefill(n_new[r], sampled[r],
                                             eos_id=eos_id))
        return events

    def observe_verify(self, n_new, greedy, eos_id=None):
        events = []
        for s, sh in enumerate(self.shards):
            r = self._rows(s)
            events.extend(sh.observe_verify(n_new[r], greedy[r],
                                            eos_id=eos_id))
        return events

    def observe_chunk(self, sampled, eos_id=None):
        events = []
        for s, sh in enumerate(self.shards):
            events.extend(sh.observe_chunk(sampled[:, self._rows(s)],
                                           eos_id=eos_id))
        return events

    # ---- stats (aggregated to match the single Scheduler's counters) ------
    # Dispatch counters: every shard observes every fused dispatch, so the
    # global count is the max (== each shard's count), not the sum.  Token
    # and preemption counters are per-request work, so they sum.
    @property
    def prefill_dispatches(self) -> int:
        return max(sh.prefill_dispatches for sh in self.shards)

    @property
    def decode_dispatches(self) -> int:
        return max(sh.decode_dispatches for sh in self.shards)

    @property
    def verify_dispatches(self) -> int:
        return max(sh.verify_dispatches for sh in self.shards)

    @property
    def steps(self) -> int:
        return max(sh.steps for sh in self.shards)

    @property
    def ticks(self) -> int:
        return max(sh.ticks for sh in self.shards)

    @property
    def drafted_tokens(self) -> int:
        return sum(sh.drafted_tokens for sh in self.shards)

    @property
    def accepted_tokens(self) -> int:
        return sum(sh.accepted_tokens for sh in self.shards)

    @property
    def rollback_tokens(self) -> int:
        return sum(sh.rollback_tokens for sh in self.shards)

    @property
    def rollback_blocks(self) -> int:
        return sum(sh.rollback_blocks for sh in self.shards)

    @property
    def preemptions(self) -> int:
        return sum(sh.preemptions for sh in self.shards)

    @property
    def prompt_tokens(self) -> int:
        return sum(sh.prompt_tokens for sh in self.shards)

    @property
    def prefix_hit_tokens(self) -> int:
        return sum(sh.prefix_hit_tokens for sh in self.shards)

    @property
    def decode_row_steps(self) -> int:
        return sum(sh.decode_row_steps for sh in self.shards)

    @property
    def prefill_tokens(self) -> int:
        return sum(sh.prefill_tokens for sh in self.shards)

    # each shard counts its own rows of the fused decode grid
    @property
    def kv_grid_blocks(self) -> int:
        return sum(sh.kv_grid_blocks for sh in self.shards)

    @property
    def kv_live_blocks(self) -> int:
        return sum(sh.kv_live_blocks for sh in self.shards)

    @property
    def preemptions_by_class(self) -> Dict[str, int]:
        merged: Dict[str, int] = {}
        for sh in self.shards:
            for k, v in sh.preemptions_by_class.items():
                merged[k] = merged.get(k, 0) + v
        return merged

    @property
    def victim_sealed_fractions(self) -> List[float]:
        return [f for sh in self.shards for f in sh.victim_sealed_fractions]

    @property
    def wait_ticks(self) -> Dict[str, List[int]]:
        merged: Dict[str, List[int]] = {}
        for sh in self.shards:
            for k, v in sh.wait_ticks.items():
                merged.setdefault(k, []).extend(v)
        return merged

    @property
    def wait_wall(self) -> Dict[str, List[float]]:
        merged: Dict[str, List[float]] = {}
        for sh in self.shards:
            for k, v in sh.wait_wall.items():
                merged.setdefault(k, []).extend(v)
        return merged
