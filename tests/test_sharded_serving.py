"""Sharded serving stack: partitioned pools, banks, placement, the engine.

Unit-level coverage for ``repro/serving/sharded.py`` (global<->local id
translation, per-shard allocators behind one device view, adapter homing
and bank concatenation, round negotiation) plus the end-to-end contract
through the REAL jitted engine: ``ServeConfig.num_shards=2`` must emit
greedy token streams BITWISE equal to the single-pool path — across the
jnp and Pallas paged backends, speculative decoding, and warm prefix-cache
reuse — because sharding only re-partitions host bookkeeping around the
same fused dispatch.  The mesh integration test runs wherever >=2 devices
exist (CI forces them with
``XLA_FLAGS=--xla_force_host_platform_device_count=2``).

Randomized multi-chunk schedules (preemption, growth, oracle parity per
seed) live in ``test_serving_sim.py::run_sharded_sim``.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.serving.kv_cache import PagedKVCache
from repro.serving.sharded import (ShardedAdapterRegistry,
                                   ShardedPagedKVCache, ShardedScheduler)

VOCAB = 300


def _prompt(n, seed=0):
    return (np.arange(n, dtype=np.int32) * 3 + seed) % VOCAB


# ---------------------------------------------------------------------------
# ShardedPagedKVCache: geometry, translation, disjointness
# ---------------------------------------------------------------------------

def test_sharded_kv_geometry_validation():
    with pytest.raises(ValueError, match="num_shards"):
        ShardedPagedKVCache(0, 4, 4, 17, 4)
    with pytest.raises(ValueError, match="num_slots"):
        ShardedPagedKVCache(2, 3, 4, 17, 4)
    with pytest.raises(ValueError, match="allocatable blocks"):
        ShardedPagedKVCache(2, 4, 4, 18, 4)   # 17 allocatable, odd


def test_sharded_kv_slot_translation_roundtrip():
    kv = ShardedPagedKVCache(3, 6, 4, 1 + 3 * 4, 4)
    for g in range(6):
        s, local = kv.shard_of_slot(g)
        assert kv.global_slot(s, local) == g
        assert 0 <= s < 3 and 0 <= local < 2


def test_sharded_kv_device_tables_translate_into_disjoint_slices():
    """Each shard's table entries map into its own global block slice;
    block 0 stays the shared scratch id everywhere."""
    kv = ShardedPagedKVCache(2, 4, 4, 1 + 2 * 6, 4)
    for g in range(4):
        s, local = kv.shard_of_slot(g)
        kv.shards[s].admit(local, None, _prompt(4, g))
        kv.shards[s].ensure(local, 8)
    tables, lengths = kv.device_tables()
    tables = np.asarray(tables)
    assert tables.shape[0] == 4 and np.asarray(lengths).shape == (4,)
    kv.check_invariants()
    used = tables[tables > 0]
    assert used.size == 8                        # 2 blocks per slot
    assert len(set(used.tolist())) == used.size  # globally disjoint
    lo, hi = used[:4], used[4:]                  # shard 0 rows, shard 1 rows
    assert lo.max() <= 6 and hi.min() >= 7       # per-shard slices


def test_sharded_kv_aggregates_sum_over_shards():
    kv = ShardedPagedKVCache(2, 4, 4, 1 + 2 * 6, 4)
    assert kv.free_blocks == 12 and kv.allocatable_blocks == 12
    assert kv.idle
    kv.shards[0].admit(0, None, _prompt(4))
    kv.shards[0].ensure(0, 4)
    assert kv.free_blocks == 11 and not kv.idle
    assert kv.fits(4)


def test_best_prefix_shard_finds_the_sealing_shard():
    kv = ShardedPagedKVCache(2, 4, 4, 1 + 2 * 6, 6, prefix_cache=True)
    toks = _prompt(9)
    pool = kv.shards[1]
    pool.admit(0, "c0", toks)
    pool.ensure(0, 9)
    pool.advance(0, 9, tokens=toks)              # seals two full blocks
    pool.release(0)
    assert kv.best_prefix_shard("c0", toks) == (1, 8)
    assert kv.best_prefix_shard("other", toks) == (None, 0)


# ---------------------------------------------------------------------------
# ShardedAdapterRegistry: homing, global slots, bank concatenation
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_cfg_and_adapters():
    from conftest import tiny_dense
    from repro.core.lora import init_adapters

    cfg = tiny_dense()
    ads = {f"c{i}": init_adapters(jax.random.PRNGKey(i + 1), cfg)
           for i in range(5)}
    return cfg, ads


def test_sharded_registry_capacity_validation(tiny_cfg_and_adapters):
    cfg, _ = tiny_cfg_and_adapters
    with pytest.raises(ValueError, match="capacity"):
        ShardedAdapterRegistry(cfg, capacity=3, num_shards=2)
    with pytest.raises(ValueError, match="num_shards"):
        ShardedAdapterRegistry(cfg, capacity=4, num_shards=0)


def test_sharded_registry_homes_balance_and_global_slots(
        tiny_cfg_and_adapters):
    cfg, ads = tiny_cfg_and_adapters
    reg = ShardedAdapterRegistry(cfg, capacity=4, num_shards=2)
    slots = {c: reg.register(c, ads[c]) for c in ("c0", "c1", "c2", "c3")}
    # fewest-resident homing alternates shards; global slot = shard*2+local
    assert [reg.shard_of(f"c{i}") for i in range(4)] == [0, 1, 0, 1]
    assert sorted(slots.values()) == [0, 1, 2, 3]
    for c, slot in slots.items():
        assert reg.acquire(c) == slot
    assert len(reg) == 4 and "c0" in reg
    with pytest.raises(KeyError, match="not resident"):
        reg.acquire("stranger")


def test_sharded_registry_bank_matches_flat_registry(tiny_cfg_and_adapters):
    """The concatenated bank indexed at a client's GLOBAL slot holds the
    same adapter values a flat registry serves — layout is the only
    difference."""
    from repro.serving.registry import AdapterRegistry

    cfg, ads = tiny_cfg_and_adapters
    flat = AdapterRegistry(cfg, capacity=4)
    sharded = ShardedAdapterRegistry(cfg, capacity=4, num_shards=2)
    for c in ("c0", "c1", "c2", "c3"):
        flat.register(c, ads[c])
        sharded.register(c, ads[c])
    fb, sb = flat.bank(), sharded.bank()
    assert (jax.tree.leaves(sb)[0].shape[1]
            == jax.tree.leaves(fb)[0].shape[1] == 4)
    for c in ("c0", "c1", "c2", "c3"):
        fs, ss = flat.acquire(c), sharded.acquire(c)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            a[:, fs], b[:, ss]), fb, sb)


def test_sharded_registry_evicts_within_home_shard(tiny_cfg_and_adapters):
    cfg, ads = tiny_cfg_and_adapters
    reg = ShardedAdapterRegistry(cfg, capacity=4, num_shards=2)
    for c in ("c0", "c1", "c2", "c3"):
        reg.register(c, ads[c])
    # both shards full; c4 homes to shard 0 (tie, lowest index) and its
    # LRU client c0 is evicted THERE — shard 1 residents untouched
    slot = reg.register("c4", ads["c4"])
    assert reg.shard_of("c4") == 0 and slot in (0, 1)
    assert "c0" not in reg and reg.shard_of("c0") is None
    assert all(c in reg for c in ("c1", "c2", "c3", "c4"))
    assert reg.evictions == 1
    reg.evict("c4")
    assert "c4" not in reg and len(reg) == 3


# ---------------------------------------------------------------------------
# ShardedScheduler: round negotiation
# ---------------------------------------------------------------------------

def test_negotiated_decode_steps_is_min_over_shards():
    """A decode round's step count is the min over per-shard plans, so no
    slot on any shard can overshoot its budget inside a fused chunk."""
    kv = ShardedPagedKVCache(2, 2, 4, 17, 8)
    sched = ShardedScheduler(kv)
    sched.shards[0].submit(0, "a", _prompt(4), 10)   # plans a deep chunk
    sched.shards[1].submit(1, "b", _prompt(4), 2)    # nearly done
    sched.admit()
    plan = sched.prepare_chunk(8, 8)
    assert plan == ("prefill", None)                 # both still prefilling
    arrs = sched.prefill_arrays(8)
    sched.observe_prefill(arrs["n_new"], np.ones((2,), np.int32))
    plan = sched.prepare_chunk(8, 8)
    assert plan[0] == "decode"
    assert plan[1] == sched.shards[1].plan_steps(8) == 1


def test_mixed_readiness_forces_global_prefill_round():
    """One shard mid-prompt holds the OTHER (already decoding) shard in
    prefill-shaped rounds — its rows ride as 1-token feedback — until the
    prompt is fed; decoding still advances every round."""
    kv = ShardedPagedKVCache(2, 2, 4, 17, 8)
    sched = ShardedScheduler(kv)
    sched.shards[0].submit(0, "a", _prompt(12), 4)   # 3 prefill chunks of 4
    sched.shards[1].submit(1, "b", _prompt(2), 6)    # prefills in one
    sched.admit()
    rounds = []
    while sched.has_work:
        plan = sched.prepare_chunk(4, 4)
        rounds.append(plan[0])
        K = kv.num_slots
        if plan[0] == "prefill":
            arrs = sched.prefill_arrays(4)
            sched.observe_prefill(arrs["n_new"], np.ones((K,), np.int32))
        else:
            sched.chunk_arrays()
            sched.observe_chunk(np.ones((plan[1], K), np.int32))
    assert rounds[:3] == ["prefill"] * 3             # shard 0's prompt wins
    assert sched.results[0].size == 4 and sched.results[1].size == 6


# ---------------------------------------------------------------------------
# The real jitted engine: num_shards=2 is bitwise the single-pool stream
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sharded_engine():
    from conftest import tiny_dense
    from repro.core.lora import init_adapters
    from repro.models.api import get_model
    from repro.serving.engine import MultiTenantEngine

    cfg = tiny_dense(dtype="float32", param_dtype="float32")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reg = ShardedAdapterRegistry(cfg, capacity=4, num_shards=2)
    for i in range(4):
        reg.register(f"c{i}", init_adapters(jax.random.PRNGKey(i + 1), cfg))
    return cfg, MultiTenantEngine(model, cfg, params, reg)


def _mixed_requests(cfg, n=8):
    from repro.serving.engine import Request
    rng = np.random.default_rng(11)
    reqs = [Request("c0", _prompt(12), max_new_tokens=6)]
    for i in range(n - 1):
        plen = int(rng.integers(2, 13))
        reqs.append(Request(f"c{i % 4}",
                            rng.integers(0, cfg.vocab_size, plen)
                            .astype(np.int32),
                            max_new_tokens=int(rng.integers(2, 7))))
    return reqs


def _sc(**kw):
    from repro.serving.engine import ServeConfig
    base = dict(batch_size=4, max_new_tokens=6, block_size=4,
                num_blocks=25, prefill_chunk=4)
    base.update(kw)
    return ServeConfig(**base)


def test_engine_two_shards_bitwise_equals_single_pool(sharded_engine):
    """The tentpole contract: sharding re-partitions host bookkeeping only,
    so greedy streams are bitwise identical at num_shards=1 and 2 — on the
    jnp backend, the Pallas kernels, and under speculative decoding."""
    cfg, mt = sharded_engine
    reqs = _mixed_requests(cfg)
    for extra in ({}, {"paged_backend": "pallas"}, {"spec_decode": True}):
        one = mt.generate(reqs, _sc(num_shards=1, **extra))
        two = mt.generate(reqs, _sc(num_shards=2, **extra))
        assert mt.last_stats["num_shards"] == 2
        for a, b in zip(one, two):
            np.testing.assert_array_equal(a, b)


def test_engine_sharded_reports_placements_and_uses_both_shards(
        sharded_engine):
    cfg, mt = sharded_engine
    mt.generate(_mixed_requests(cfg), _sc(num_shards=2))
    st = mt.last_stats
    assert st["num_shards"] == 2
    placed = st["shard_placements"]
    assert set(placed) == {"prefix", "adapter", "load"}
    # every client has a resident adapter -> affinity routing drove intake
    assert placed["adapter"] == 8 and placed["prefix"] == 0


def test_engine_sharded_warm_prefix_reuse_is_bitwise(sharded_engine):
    """Warm cross-call reuse through the sharded pool: the second call
    re-matches blocks sealed by the first (prefix placements appear) and
    stays bitwise equal to the cold stream."""
    cfg, mt = sharded_engine
    reqs = _mixed_requests(cfg, n=6)
    sc = _sc(num_shards=2, prefix_cache=True)
    mt.release_prefix_cache()
    cold = mt.generate(reqs, sc)
    warm = mt.generate(reqs, sc)
    assert mt.last_stats["prefix_pool_reused"]
    assert mt.last_stats["prefix_hit_tokens"] > 0
    assert mt.last_stats["shard_placements"]["prefix"] > 0
    for a, b in zip(cold, warm):
        np.testing.assert_array_equal(a, b)
    mt.release_prefix_cache()


def test_engine_sharded_geometry_validation(sharded_engine):
    cfg, mt = sharded_engine
    reqs = _mixed_requests(cfg, n=2)
    with pytest.raises(ValueError, match="num_shards"):
        mt.generate(reqs, _sc(num_shards=0))
    with pytest.raises(ValueError, match="batch_size"):
        mt.generate(reqs, _sc(batch_size=3, num_shards=2))
    with pytest.raises(ValueError, match="not divisible"):
        mt.generate(reqs, _sc(num_shards=2, num_blocks=24))


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >=2 devices (force with XLA_FLAGS="
                           "--xla_force_host_platform_device_count)")
def test_engine_sharded_under_host_mesh_is_bitwise(sharded_engine):
    """With a real 2-device host mesh set around the dispatches, weights,
    bank and pool sit replicated on both devices and the fused batch
    splits over "data" (each device computes its own slots' logits rows,
    the Pallas kernels per device under shard_map) — and the stream stays
    bitwise equal to the meshless single-pool run on both backends."""
    import jax.numpy as jnp
    from repro.core.lora import lora_scale
    from repro.launch.mesh import make_host_mesh

    cfg, mt = sharded_engine
    reqs = _mixed_requests(cfg)
    mesh = make_host_mesh()
    for backend in ("jnp", "pallas"):
        base = mt.generate(reqs, _sc(num_shards=1, paged_backend=backend))
        ses = mt.session(_sc(num_shards=2, mesh=mesh,
                             paged_backend=backend), reqs)
        meshed = [[] for _ in reqs]
        while ses.has_work:
            for rid, toks, _ in ses.step():
                meshed[rid].extend(toks)
        ses.finalize()
        assert mt.last_stats["num_shards"] == 2
        assert mt.last_stats["paged_backend"] == backend
        for a, b in zip(base, meshed):
            np.testing.assert_array_equal(a, np.asarray(b, np.int32))
        for leaf in jax.tree.leaves((ses.params, ses.bank, ses.cache)):
            assert len(leaf.sharding.device_set) == 2

        # one prefill dispatch under the mesh: logits rows split on "data"
        K, T = 4, 4
        cache = mt.model.init_paged_decode_cache(K, 25, 4)
        args = (jnp.asarray(np.arange(K * T).reshape(K, T) % cfg.vocab_size,
                            jnp.int32),
                jnp.zeros((K,), jnp.int32), jnp.full((K,), T, jnp.int32))
        kw = dict(adapters=mt.registry.bank(), lora_scale=lora_scale(cfg),
                  adapter_ids=jnp.arange(K, dtype=jnp.int32),
                  block_tables=jnp.arange(1, 25, dtype=jnp.int32)
                  .reshape(K, 6))
        step = jax.jit(mt.model.prefill_step,
                       static_argnames=("paged_backend",))
        kw["paged_backend"] = backend
        want, _ = step(mt.params, cache, *args, **kw)
        with jax.set_mesh(mesh):
            got, _ = step(mt.params, cache, *args, **kw)
        assert len(got.sharding.device_set) == 2
        assert sorted(sh.index[0].start or 0
                      for sh in got.addressable_shards) == [0, K // 2]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
