"""Continuous batching over the paged KV cache: kernel parity, block
accounting, scheduler lifecycle, and token-for-token parity of the
slot-based engine against fixed-batch and single-tenant decoding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_dense, tiny_ssm
from repro.core.lora import init_adapters
from repro.kernels.ops import paged_gqa_attention, paged_prefill_gqa_attention
from repro.kernels.paged_attention import (blocks_per_step, dma_heads,
                                           paged_attention)
from repro.kernels.paged_prefill import paged_prefill_attention
from repro.kernels.ref import paged_attention_ref, paged_prefill_attention_ref
from repro.models.api import get_model
from repro.serving.engine import (Engine, MultiTenantEngine, Request,
                                  ServeConfig)
from repro.serving.kv_cache import PagedKVCache, blocks_needed
from repro.serving.registry import AdapterRegistry
from repro.serving.scheduler import Scheduler

RNG = np.random.default_rng(11)


# ---------------------------------------------------------------------------
# Paged-attention kernel vs the gather-materialising oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("MB,hd,T", [pytest.param(32, 32, 16, id="full"),
                                      pytest.param(12, 32, 4, id="narrow"),
                                      pytest.param(12, 64, 4, id="hd64")])
@pytest.mark.parametrize("H,Kv", [(4, 4), (8, 2), (16, 16), (32, 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_matches_ref(MB, hd, T, H, Kv, dtype):
    """MHA (G = 1), GQA up to G = 8 with Kv = 4; rows empty, of one
    position, of one block, either side of a grid step's T blocks and of
    the whole table; a table whose width halves the blocks per step; and
    hd 64 through the model-layout wrapper, which pads it to 128."""
    bs = 8
    assert blocks_per_step(MB, bs, dma_heads(Kv, 2), 128, 2) == T
    lens = [0, 1, bs, T * bs - 1, T * bs, T * bs + 1, 19, MB * bs]
    B, NB = len(lens), len(lens) * MB + 1
    q = jnp.asarray(RNG.standard_normal((B, H, hd)), dtype)
    kp = jnp.asarray(RNG.standard_normal((NB, bs, Kv, hd)), dtype)
    vp = jnp.asarray(RNG.standard_normal((NB, bs, Kv, hd)), dtype)
    bt = jnp.asarray(np.stack([RNG.permutation(NB)[:MB] for _ in range(B)]),
                     jnp.int32)                      # scattered physical ids
    lens = jnp.asarray(lens, jnp.int32)
    if hd == 64:                                     # through the wrapper
        y = paged_gqa_attention(q[:, None], kp, vp, bt, lens)[:, 0]
    else:
        y = paged_attention(q, kp, vp, bt, lens)
    yr = paged_attention_ref(q, kp, vp, bt, lens)
    atol = 0.03 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), atol=atol)
    # empty slot -> exact zeros (not NaN) on both
    assert not np.isnan(np.asarray(y, np.float32)).any()
    np.testing.assert_array_equal(np.asarray(y, np.float32)[0], 0.0)


def test_paged_ops_wrapper_pads_head_dim():
    """Model layout (B, 1, H, hd) with a non-lane-aligned head dim."""
    B, H, Kv, hd, NB, bs, MB = 3, 4, 2, 24, 7, 4, 3
    q = jnp.asarray(RNG.standard_normal((B, 1, H, hd)), jnp.float32)
    kp = jnp.asarray(RNG.standard_normal((NB, bs, Kv, hd)), jnp.float32)
    vp = jnp.asarray(RNG.standard_normal((NB, bs, Kv, hd)), jnp.float32)
    bt = jnp.asarray(RNG.integers(0, NB, (B, MB)), jnp.int32)
    lens = jnp.asarray([2, 5, 12], jnp.int32)
    y = paged_gqa_attention(q, kp, vp, bt, lens)
    yr = paged_attention_ref(q[:, 0], kp, vp, bt, lens)
    assert y.shape == q.shape
    np.testing.assert_allclose(np.asarray(y[:, 0]), np.asarray(yr), atol=2e-5)


# ---------------------------------------------------------------------------
# Chunked paged-prefill kernel vs the gather-materialising oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("H,Kv", [(4, 4), (8, 2)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_prefill_matches_ref(H, Kv, dtype):
    B, T, hd, NB, bs, MB = 4, 5, 32, 13, 4, 5
    q = jnp.asarray(RNG.standard_normal((B, T, H, hd)), dtype)
    kp = jnp.asarray(RNG.standard_normal((NB, bs, Kv, hd)), dtype)
    vp = jnp.asarray(RNG.standard_normal((NB, bs, Kv, hd)), dtype)
    bt = jnp.asarray(np.stack([RNG.permutation(np.arange(1, NB))[:MB]
                               for _ in range(B)]), jnp.int32)
    lens = jnp.asarray([0, 3, 7, 11], jnp.int32)   # ragged, incl. fresh slot
    y = paged_prefill_attention(q, kp, vp, bt, lens)
    yr = paged_prefill_attention_ref(q, kp, vp, bt, lens)
    atol = 0.03 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), atol=atol)
    assert not np.isnan(np.asarray(y, np.float32)).any()


def test_paged_prefill_ops_wrapper_scatters_and_pads():
    """Model layout with a non-lane-aligned head dim: the wrapper scatters
    the chunk's K/V through the block table (ragged n_new tails land in
    scratch block 0) and matches the oracle over the updated pools."""
    B, T, H, Kv, hd, NB, bs, MB = 3, 4, 4, 2, 24, 14, 4, 4
    q = jnp.asarray(RNG.standard_normal((B, T, H, hd)), jnp.float32)
    kn = jnp.asarray(RNG.standard_normal((B, T, Kv, hd)), jnp.float32)
    vn = jnp.asarray(RNG.standard_normal((B, T, Kv, hd)), jnp.float32)
    kp = jnp.asarray(RNG.standard_normal((NB, bs, Kv, hd)), jnp.float32)
    vp = jnp.asarray(RNG.standard_normal((NB, bs, Kv, hd)), jnp.float32)
    # rows own DISJOINT physical blocks (the allocator's invariant) — the
    # scatter would otherwise cross-clobber rows
    perm = RNG.permutation(np.arange(1, NB))[:B * MB].reshape(B, MB)
    bt = jnp.asarray(perm, jnp.int32)
    lens = jnp.asarray([0, 2, 5], jnp.int32)
    n_new = jnp.asarray([4, 2, 0], jnp.int32)      # ragged chunk fill
    o, kp2, vp2 = paged_prefill_gqa_attention(q, kn, vn, kp, vp, bt, lens,
                                              n_new)
    # valid chunk tokens landed at (lengths + t) through the table
    for b, (l, n) in enumerate(zip([0, 2, 5], [4, 2, 0])):
        for t in range(n):
            p = l + t
            np.testing.assert_array_equal(
                np.asarray(kp2)[int(bt[b, p // bs]), p % bs],
                np.asarray(kn)[b, t])
    # row 2 fed nothing: none of its owned blocks changed
    own = [int(b) for b in np.asarray(bt)[2, :2]]
    np.testing.assert_array_equal(np.asarray(kp2)[own], np.asarray(kp)[own])
    yr = paged_prefill_attention_ref(q, kp2, vp2, bt, lens)
    assert o.shape == q.shape
    np.testing.assert_allclose(np.asarray(o), np.asarray(yr), atol=2e-5)


# ---------------------------------------------------------------------------
# PagedKVCache block accounting (on-demand growth)
# ---------------------------------------------------------------------------

def test_kv_cache_block_accounting():
    kv = PagedKVCache(num_slots=2, block_size=4, num_blocks=6,
                      max_blocks_per_slot=3)
    assert kv.free_blocks == 5                     # block 0 is scratch
    assert kv.fits(12) and not kv.fits(13)         # 3 blocks * 4 tokens
    kv.admit(0)                                    # claims slot, ZERO blocks
    assert kv.free_blocks == 5
    assert kv.ensure(0, 9)                         # grow to 3 blocks
    assert kv.free_blocks == 2
    assert (kv.block_tables[0, :3] > 0).all()      # scratch never handed out
    assert kv.ensure(0, 9)                         # idempotent: no growth
    assert kv.free_blocks == 2
    kv.admit(1)
    assert kv.ensure(1, 8)                         # 2 blocks
    assert not kv.ensure(1, 12)                    # pool dry: growth refused
    assert kv.free_blocks == 0                     # ...and nothing allocated
    kv.advance(0, 5)
    assert kv.lengths[0] == 5
    kv.check_invariants()
    kv.release(0)
    assert kv.free_blocks == 3 and kv.lengths[0] == 0
    assert (kv.block_tables[0] == 0).all()
    assert kv.ensure(1, 12)                        # freed blocks reusable
    kv.check_invariants()


def test_kv_cache_free_list_is_fifo():
    """Allocation pops the head (deque.popleft — O(1) on the per-chunk
    path); release appends, so block reuse is FIFO and deterministic."""
    kv = PagedKVCache(num_slots=2, block_size=2, num_blocks=6,
                      max_blocks_per_slot=4)
    kv.admit(0)
    assert kv.ensure(0, 6)                         # pops 1, 2, 3 in order
    assert list(kv.block_tables[0, :3]) == [1, 2, 3]
    kv.admit(1)
    assert kv.ensure(1, 2)
    assert list(kv.block_tables[1, :1]) == [4]
    kv.release(0)                                  # 1,2,3 append after 5
    assert kv.ensure(1, 8)
    assert list(kv.block_tables[1, :4]) == [4, 5, 1, 2]
    kv.check_invariants()


def test_scheduler_fcfs_admission_and_rejection():
    kv = PagedKVCache(num_slots=2, block_size=4, num_blocks=4,
                      max_blocks_per_slot=3)        # 3 free blocks total
    sched = Scheduler(kv)
    sched.submit(0, "a", np.arange(9), 2)           # prompt needs 3 blocks
    assert [s for s, _ in sched.admit()] == [0]
    assert kv.ensure(0, 9)                          # slot 0 grows: pool dry
    sched.submit(1, "b", np.arange(9), 2)
    # head's prompt can't be covered by free blocks -> FCFS wait
    assert sched.admit() == []
    with pytest.raises(ValueError):
        sched.submit(2, "c", np.arange(20), 4)      # span can never fit


def test_scheduler_plan_steps_empty_returns_one():
    """Regression: plan_steps with no active slot used to crash with
    ``min() arg is an empty sequence``."""
    kv = PagedKVCache(num_slots=2, block_size=4, num_blocks=4,
                      max_blocks_per_slot=3)
    sched = Scheduler(kv)
    assert sched.plan_steps(8) == 1
    sched.submit(0, "a", np.arange(4), 4)
    assert sched.plan_steps(8) == 1                 # queued but not admitted


def test_scheduler_preemption_requeues_prompt_plus_emitted():
    """A preempted slot releases its blocks and requeues at the queue head
    with prompt+emitted as the new prompt; nothing is lost."""
    kv = PagedKVCache(num_slots=2, block_size=2, num_blocks=8,
                      max_blocks_per_slot=6)
    sched = Scheduler(kv)
    sched.submit(0, "a", np.asarray([3, 1, 4]), 4)
    sched.submit(1, "b", np.asarray([2, 7]), 4)
    assert [s for s, _ in sched.admit()] == [0, 1]
    assert sched.prepare_chunk(8, 8) == ("prefill", None)
    arrs = sched.prefill_arrays(8)
    np.testing.assert_array_equal(arrs["n_new"], [3, 2])
    sched.observe_prefill(arrs["n_new"], np.asarray([10, 11]))
    # decode one chunk of 2 steps, then preempt slot 1
    assert sched.prepare_chunk(8, 2) == ("decode", 2)
    sched.observe_chunk(np.asarray([[20, 21], [30, 31]], np.int32))
    kv.check_invariants()
    sched.preempt(1)
    kv.check_invariants()
    assert sched.preemptions == 1
    rid, cid, prompt, budget, prior = sched._queue[0]
    assert rid == 1 and cid == "b"
    np.testing.assert_array_equal(prompt, [2, 7, 11, 21, 31])  # prompt+emitted
    assert budget == 1 and prior == [11, 21, 31]
    # resumed: prefill replays, then the final emission completes it
    assert [s for s, _ in sched.admit()] == [1]
    assert sched.prepare_chunk(8, 8) == ("prefill", None)
    arrs = sched.prefill_arrays(8)
    assert arrs["n_new"][1] == 5
    sched.observe_prefill(arrs["n_new"], np.asarray([99, 40]))
    np.testing.assert_array_equal(sched.results[1], [11, 21, 31, 40])


# ---------------------------------------------------------------------------
# Engine parity
# ---------------------------------------------------------------------------

def _client_adapters(cfg, seed):
    ad = init_adapters(jax.random.PRNGKey(seed), cfg)
    bump = jax.random.PRNGKey(seed + 99)
    return jax.tree.map(
        lambda l: l + 0.02 * jax.random.normal(bump, l.shape), ad)


def _mt_setup(cfg, n_clients=2):
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ads = {f"c{i}": _client_adapters(cfg, i + 1) for i in range(n_clients)}
    reg = AdapterRegistry(cfg, capacity=4)
    for cid, ad in ads.items():
        reg.register(cid, ad)
    return model, params, ads, MultiTenantEngine(model, cfg, params, reg)


def _single_tenant(model, cfg, params, ad, prompt, budget, cache_len=64):
    sc = ServeConfig(batch_size=1, max_new_tokens=budget, cache_len=cache_len)
    return np.asarray(Engine(model, cfg, params, ad).generate(
        jnp.asarray(np.asarray(prompt, np.int32))[None], sc))[0]


def test_continuous_equal_shape_bitmatches_fixed():
    """Acceptance: equal-length, equal-budget greedy requests through the
    slot engine == the PR-1 fixed-batch engine, token for token."""
    cfg = tiny_dense()
    _, _, _, mt = _mt_setup(cfg)
    prompt = np.arange(8, dtype=np.int32) % cfg.vocab_size
    sc = ServeConfig(batch_size=4, max_new_tokens=8, cache_len=32,
                     block_size=8)
    reqs = [Request(c, prompt) for c in ["c1", "c0", "c1", "c0"]]
    fixed = np.asarray(mt.generate_fixed(reqs, sc))
    cont = mt.generate(reqs, sc)
    for i in range(len(reqs)):
        np.testing.assert_array_equal(cont[i], fixed[i])


def test_continuous_ragged_matches_single_tenant():
    """Mixed prompt lengths, budgets and clients — with more requests than
    slots, so completions admit queued requests mid-flight — must equal
    per-request single-tenant greedy decoding."""
    cfg = tiny_dense()
    model, params, ads, mt = _mt_setup(cfg)
    mk = lambda n: (np.arange(n, dtype=np.int32) * 3 + 1) % cfg.vocab_size
    reqs = [Request("c0", mk(5), max_new_tokens=3),
            Request("c1", mk(11), max_new_tokens=9),
            Request("c1", mk(2), max_new_tokens=5),
            Request("c0", mk(8), max_new_tokens=1),
            Request("c0", mk(7), max_new_tokens=6)]
    sc = ServeConfig(batch_size=2, max_new_tokens=8, block_size=4)
    outs = mt.generate(reqs, sc)
    for r, o in zip(reqs, outs):
        assert o.size == r.max_new_tokens
        ref = _single_tenant(model, cfg, params, ads[r.client_id],
                             r.prompt, r.max_new_tokens)
        np.testing.assert_array_equal(o, ref)


def test_continuous_ssm_state_reset_on_slot_reuse():
    """Mamba rows keep dense per-slot state; admitting a new request into a
    freed slot must not leak the previous occupant's recurrent state."""
    cfg = tiny_ssm()
    model, params, ads, mt = _mt_setup(cfg)
    mk = lambda n, o: (np.arange(n, dtype=np.int32) + o) % cfg.vocab_size
    reqs = [Request("c0", mk(4, 0), max_new_tokens=4),
            Request("c1", mk(6, 5), max_new_tokens=6),
            Request("c0", mk(3, 2), max_new_tokens=5)]
    outs = mt.generate(reqs, ServeConfig(batch_size=1, max_new_tokens=8,
                                         block_size=4))
    for r, o in zip(reqs, outs):
        ref = _single_tenant(model, cfg, params, ads[r.client_id],
                             r.prompt, r.max_new_tokens, cache_len=32)
        np.testing.assert_array_equal(o, ref)


def test_continuous_tight_pool_serialises_but_stays_correct():
    """A pool too small for full residency forces queueing; outputs are
    unchanged."""
    cfg = tiny_dense()
    model, params, ads, mt = _mt_setup(cfg)
    prompt = np.arange(6, dtype=np.int32) % cfg.vocab_size
    reqs = [Request("c0", prompt, max_new_tokens=4),
            Request("c1", prompt, max_new_tokens=4),
            Request("c0", prompt, max_new_tokens=4)]
    # span 10 -> 3 blocks of 4; pool of 4 (1 scratch + 3) fits ONE request
    sc = ServeConfig(batch_size=3, max_new_tokens=4, block_size=4,
                     num_blocks=4)
    outs = mt.generate(reqs, sc)
    for r, o in zip(reqs, outs):
        ref = _single_tenant(model, cfg, params, ads[r.client_id],
                             r.prompt, 4)
        np.testing.assert_array_equal(o, ref)


# ---------------------------------------------------------------------------
# EOS handling (ServeConfig.eos_id)
# ---------------------------------------------------------------------------

def test_eos_legacy_engine_pads_after_eos():
    cfg = tiny_dense()
    model, params, ads, mt = _mt_setup(cfg)
    prompt = np.arange(8, dtype=np.int32) % cfg.vocab_size
    base = _single_tenant(model, cfg, params, ads["c0"], prompt, 8)
    eos = int(base[2])                       # third greedy token as "EOS"
    sc = ServeConfig(batch_size=1, max_new_tokens=8, cache_len=64,
                     eos_id=eos, pad_id=0)
    out = np.asarray(Engine(model, cfg, params, ads["c0"]).generate(
        jnp.asarray(prompt)[None], sc))[0]
    cut = np.flatnonzero(base == eos)[0]
    np.testing.assert_array_equal(out[:cut + 1], base[:cut + 1])
    np.testing.assert_array_equal(out[cut + 1:], 0)


def test_eos_continuous_row_stops_early():
    cfg = tiny_dense()
    model, params, ads, mt = _mt_setup(cfg)
    prompt = np.arange(8, dtype=np.int32) % cfg.vocab_size
    base = _single_tenant(model, cfg, params, ads["c0"], prompt, 8)
    eos = int(base[2])
    sc = ServeConfig(batch_size=2, max_new_tokens=8, block_size=4,
                     eos_id=eos)
    outs = mt.generate([Request("c0", prompt), Request("c1", prompt)], sc)
    cut = np.flatnonzero(base == eos)[0]
    np.testing.assert_array_equal(outs[0], base[:cut + 1])  # EOS incl., stops
    assert outs[1].size <= 8
