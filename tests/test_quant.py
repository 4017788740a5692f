"""int8 quantized serving (KV pools + adapter banks) and the registry /
KV-pool edge-case hardening.

Parity discipline: the int8 serving path is NOT bitwise against f32 — it is
held to (a) an exact contract between each Pallas kernel and the jnp
dequantizing oracle fed the same int8 data, (b) a documented error bound
between quantized and unquantized attention outputs, and (c) across
every serving feature (ragged batches, preemption, warm prefix reuse, spec
decode, sharding), logits within a stated bound of the unquantized
reference's, with greedy streams that part from the unquantized stream
only at a near-tie: random weights leave many top-2 margins below the
quantization noise, so argmax equality alone holds by luck.
"""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_dense
from repro.core.lora import init_adapters
from repro.kernels.ops import (batched_lora_dense, paged_gqa_attention,
                               paged_prefill_gqa_attention)
from repro.kernels.paged_attention import (blocks_per_step, dma_heads,
                                           paged_attention)
from repro.kernels.paged_prefill import (paged_prefill_attention,
                                         paged_scatter, paged_scatter_quant)
from repro.kernels.quant import dequantize_int8, quantize_int8
from repro.kernels.ref import (batched_lora_matmul_ref, paged_attention_ref,
                               paged_prefill_attention_ref)
from repro.kernels.batched_lora import batched_lora_matmul
from repro.models.api import get_model
from repro.serving.engine import MultiTenantEngine, Request, ServeConfig
from repro.serving.kv_cache import PagedKVCache, kv_bytes_per_block
from repro.serving.registry import AdapterRegistry
from repro.serving.sharded import ShardedAdapterRegistry

RNG = np.random.default_rng(23)

# |dequant(x) - x| <= scale/2 per element; scales here are amax/127 of unit
# normals, so attention outputs (convex combos of V rows) stay within a few
# quantization steps.  This is the documented error bound the int8 path is
# held to against the f32 oracle.
KV_ATOL = 0.05

# int8 vs unquantized serving logits on the smoke model, whose logits have
# a std of ~0.16.  int8 K/V (amax/127 per token and kv-head) moved them by
# at most 0.0125, and by 0.020 with the int8 bank on top (CPU, jnp and
# Pallas paths); the bound is 2x that.  Int4-coarse K/V (16x the step), or
# a read that lost its scales or a block, lands near the logits' std.
LOGIT_ATOL = 0.04


def _rand(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.standard_normal(shape) * scale, dtype)


# ---------------------------------------------------------------------------
# quantize/dequantize primitives
# ---------------------------------------------------------------------------

def test_quantize_int8_roundtrip_error_bound():
    x = _rand((16, 8, 4, 32))
    q, s = quantize_int8(x, axis=-1)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert s.shape == (16, 8, 4)
    err = np.abs(np.asarray(dequantize_int8(q, s, -1) - x))
    # rounding error is at most half a step (= scale/2) per element
    assert (err <= np.asarray(s)[..., None] / 2 + 1e-7).all()


def test_quantize_int8_zero_group_roundtrips_to_zero():
    x = jnp.zeros((4, 32))
    q, s = quantize_int8(x, axis=-1)
    dq = dequantize_int8(q, s, -1)
    assert not np.isnan(np.asarray(dq)).any()
    np.testing.assert_array_equal(np.asarray(dq), 0.0)


# ---------------------------------------------------------------------------
# Kernel parity: int8 pools, decode + prefill
# ---------------------------------------------------------------------------

def _quant_pools(NB, bs, Kv, hd):
    kf = _rand((NB, bs, Kv, hd))
    vf = _rand((NB, bs, Kv, hd))
    kq, ks = quantize_int8(kf, axis=-1)
    vq, vs = quantize_int8(vf, axis=-1)
    return kf, vf, kq, ks, vq, vs


@pytest.mark.parametrize("MB,hd,T", [pytest.param(32, 32, 16, id="full"),
                                      pytest.param(12, 32, 4, id="narrow"),
                                      pytest.param(12, 64, 4, id="hd64")])
@pytest.mark.parametrize("H,Kv", [(4, 4), (8, 2), (16, 16), (32, 4)])
def test_paged_attention_int8_matches_dequant_oracle(MB, hd, T, H, Kv):
    """Kernel vs the jnp oracle fed the SAME int8 blocks: tight tolerance
    (both dequantize identically; only accumulation order differs).  Rows
    either side of a grid step's T blocks, a table that halves the blocks
    per step, and hd 64 through the wrapper (padded to 128 lanes)."""
    bs = 8
    assert blocks_per_step(MB, bs, dma_heads(Kv, 1), 128, 1) == T
    lens = [0, 1, bs, T * bs - 1, T * bs, T * bs + 1, 19, MB * bs]
    B, NB = len(lens), len(lens) * MB + 1
    kf, vf, kq, ks, vq, vs = _quant_pools(NB, bs, Kv, hd)
    q = _rand((B, H, hd))
    bt = jnp.asarray(np.stack([RNG.permutation(NB)[:MB] for _ in range(B)]),
                     jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    if hd == 64:
        y = paged_gqa_attention(q[:, None], kq, vq, bt, lens,
                                k_scale=ks, v_scale=vs)[:, 0]
    else:
        pad = [(0, 0)] * 3 + [(0, 128 - hd)]
        y = paged_attention(jnp.pad(q, [(0, 0), (0, 0), (0, 128 - hd)]),
                            jnp.pad(kq, pad), jnp.pad(vq, pad), bt, lens,
                            k_scale=ks, v_scale=vs,
                            scale=hd ** -0.5)[..., :hd]
    yr = paged_attention_ref(q, kq, vq, bt, lens, k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-5)
    # and within the quantization error bound of the UNQUANTIZED pools
    yf = paged_attention_ref(q, kf, vf, bt, lens)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yf), atol=KV_ATOL)
    np.testing.assert_array_equal(np.asarray(y)[0], 0.0)  # empty slot


def test_paged_prefill_int8_matches_dequant_oracle():
    B, T, H, Kv, hd, NB, bs, MB = 3, 8, 4, 2, 32, 16, 8, 5
    kf, vf, kq, ks, vq, vs = _quant_pools(NB, bs, Kv, hd)
    q = _rand((B, T, H, hd))
    kn = _rand((B, T, Kv, hd))
    vn = _rand((B, T, Kv, hd))
    bt = jnp.asarray(np.stack([RNG.permutation(np.arange(1, NB))[:MB]
                               for _ in range(B)]), jnp.int32)
    lens = jnp.asarray([0, 5, 13], jnp.int32)
    n_new = jnp.asarray([8, 8, 3], jnp.int32)         # ragged chunk tails
    kq2, vq2, ks2, vs2 = paged_scatter_quant(kq, vq, ks, vs, kn, vn,
                                             bt, lens, n_new)
    pad = [(0, 0)] * 3 + [(0, 128 - hd)]
    y = paged_prefill_attention(
        jnp.pad(q, [(0, 0)] * 3 + [(0, 128 - hd)]),
        jnp.pad(kq2, pad), jnp.pad(vq2, pad), bt, lens,
        k_scale=ks2, v_scale=vs2, scale=hd ** -0.5)[..., :hd]
    yr = paged_prefill_attention_ref(q, kq2, vq2, bt, lens,
                                     k_scale=ks2, v_scale=vs2)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-5)


def test_paged_scatter_quant_matches_unquantized_scatter_coords():
    """Quantized and plain scatter write through identical coordinates:
    dequantizing the int8 pool recovers the f32 pool's written positions
    within the error bound, including the scratch-block-0 redirect."""
    B, S, Kv, hd, NB, bs = 2, 6, 2, 16, 5, 4
    k = _rand((B, S, Kv, hd))
    v = _rand((B, S, Kv, hd))
    bt = jnp.asarray([[1, 2, 0], [3, 4, 0]], jnp.int32)
    lens = jnp.asarray([2, 0], jnp.int32)
    n_new = jnp.asarray([6, 4], jnp.int32)
    kf = jnp.zeros((NB, bs, Kv, hd))
    kq0 = jnp.zeros((NB, bs, Kv, hd), jnp.int8)
    s0 = jnp.zeros((NB, bs, Kv))
    kp, vp = paged_scatter(kf, kf, k, v, bt, lens, n_new)
    kq, vq, ks, vs = paged_scatter_quant(kq0, kq0, s0, s0, k, v,
                                         bt, lens, n_new)
    dq = np.asarray(kq, np.float32) * np.asarray(ks)[..., None]
    # block 0 is scratch — exclude it (redirected garbage differs is fine,
    # but actually both paths redirect the same tokens there too)
    np.testing.assert_allclose(dq[1:], np.asarray(kp)[1:], atol=KV_ATOL)


def test_ops_wrappers_thread_scales_with_lane_padding():
    """Model-layout wrappers: non-aligned head dim, scales untouched by
    padding; prefill wrapper returns the four updated pools."""
    B, H, Kv, hd, NB, bs, MB = 3, 4, 2, 24, 7, 4, 3
    kf, vf, kq, ks, vq, vs = _quant_pools(NB, bs, Kv, hd)
    q = _rand((B, 1, H, hd))
    bt = jnp.asarray(RNG.integers(1, NB, (B, MB)), jnp.int32)
    lens = jnp.asarray([2, 5, 11], jnp.int32)
    y = paged_gqa_attention(q, kq, vq, bt, lens, k_scale=ks, v_scale=vs)
    yr = paged_attention_ref(q[:, 0], kq, vq, bt, lens,
                             k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(y[:, 0]), np.asarray(yr),
                               atol=2e-5)
    T = 4
    out = paged_prefill_gqa_attention(
        _rand((B, T, H, hd)), _rand((B, T, Kv, hd)), _rand((B, T, Kv, hd)),
        kq, vq, bt, lens, jnp.full((B,), T, jnp.int32),
        k_scale=ks, v_scale=vs)
    assert len(out) == 5
    _, kp2, vp2, ks2, vs2 = out
    assert kp2.dtype == jnp.int8 and ks2.shape == (NB, bs, Kv)


# ---------------------------------------------------------------------------
# int8 adapter banks
# ---------------------------------------------------------------------------

def test_batched_lora_int8_kernel_matches_refs():
    M, K, N, C, r = 256, 256, 256, 4, 8
    x = _rand((M, K), jnp.bfloat16)
    w = _rand((K, N), jnp.bfloat16, 0.05)
    a = _rand((C, K, r), jnp.float32, 0.05)
    b = _rand((C, r, N), jnp.float32, 0.05)
    g = jnp.asarray(RNG.integers(0, C, M), jnp.int32)
    aq, asc = quantize_int8(a, axis=(1, 2))
    bq, bsc = quantize_int8(b, axis=(1, 2))
    y = batched_lora_matmul(x, w, aq, bq, g, 2.0, a_scale=asc, b_scale=bsc,
                            bm=128, bn=128, bk=128)
    yr = batched_lora_matmul_ref(x, w, aq, bq, g, 2.0,
                                 a_scale=asc, b_scale=bsc)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               atol=0.3, rtol=0.05)  # one bf16 ulp of |y|
    # quantized vs unquantized LoRA delta stays within the scale bound
    yf = batched_lora_matmul_ref(x, w, a, b, g, 2.0)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yf, np.float32),
                               atol=0.5, rtol=0.05)


def test_batched_lora_dense_reads_bank_scales():
    K, N, C, r = 64, 64, 3, 4
    x = _rand((2, 5, K), jnp.bfloat16)
    w = _rand((K, N), jnp.bfloat16, 0.1)
    a = _rand((C, K, r), jnp.float32, 0.05)
    b = _rand((C, r, N), jnp.float32, 0.05)
    aq, asc = quantize_int8(a, axis=(1, 2))
    bq, bsc = quantize_int8(b, axis=(1, 2))
    ids = jnp.asarray([0, 2], jnp.int32)
    y = batched_lora_dense(x, w, {"a": aq, "b": bq,
                                  "a_scale": asc, "b_scale": bsc},
                           ids, 2.0, block=64)
    yr = batched_lora_dense(x, w, {"a": a, "b": b}, ids, 2.0, block=64)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), atol=0.25)


def test_registry_int8_bank_layout_and_dequant():
    cfg = tiny_dense()
    reg = AdapterRegistry(cfg, capacity=3, bank_dtype="int8")
    ad = init_adapters(jax.random.PRNGKey(1), cfg)
    reg.register("c0", ad)
    bank = reg.bank()
    tgt = bank["blocks"]["b0"]["mixer"]["wq"]
    P = ad["blocks"]["b0"]["mixer"]["wq"]["a"].shape[0]
    assert tgt["a"].dtype == jnp.int8
    assert tgt["a_scale"].shape == (P, 3)
    slot = reg.acquire("c0")
    got = dequantize_int8(tgt["a"][:, slot], tgt["a_scale"][:, slot],
                          (1, 2))
    want = np.asarray(ad["blocks"]["b0"]["mixer"]["wq"]["a"], np.float32)
    step = np.asarray(tgt["a_scale"][:, slot])[:, None, None]
    assert (np.abs(np.asarray(got) - want) <= step / 2 + 1e-7).all()
    # unregistered slots must stay an exact no-op (zero ints, zero scales)
    other = (slot + 1) % 3
    np.testing.assert_array_equal(np.asarray(tgt["a"][:, other]), 0)


def test_sharded_registry_int8_bank_concat():
    cfg = tiny_dense()
    reg = ShardedAdapterRegistry(cfg, capacity=4, num_shards=2,
                                 bank_dtype="int8")
    for i in range(3):
        reg.register(f"c{i}", init_adapters(jax.random.PRNGKey(i), cfg))
    bank = reg.bank()
    tgt = bank["blocks"]["b0"]["mixer"]["wq"]
    assert tgt["a"].shape[1] == 4 and tgt["a_scale"].shape[1] == 4
    assert tgt["a"].dtype == jnp.int8


# ---------------------------------------------------------------------------
# Registry edge-case hardening (the three bugfix regressions)
# ---------------------------------------------------------------------------

def test_lru_eviction_clears_default_priority():
    """Regression: an LRU-evicted client's SLA class must not resurrect
    when it re-registers without one (and the dict must not grow without
    bound under churn)."""
    cfg = tiny_dense()
    reg = AdapterRegistry(cfg, capacity=1)
    ad = init_adapters(jax.random.PRNGKey(0), cfg)
    reg.register("c0", ad, default_priority="interactive")
    reg.register("c1", ad)                    # evicts c0
    assert reg.evictions == 1
    assert reg.default_priority("c0") is None
    reg.register("c0", ad)                    # back, no priority given
    assert reg.default_priority("c0") is None
    # version monotonicity survives eviction (prefix-cache scoping)
    assert reg.version("c0") == 2
    # explicit evict() already cleared it (unchanged behaviour)
    reg.register("c2", ad, default_priority="batch")
    reg.evict("c2")
    assert reg.default_priority("c2") is None


def test_register_rejects_misshaped_tree_naming_leaf():
    cfg = tiny_dense()
    reg = AdapterRegistry(cfg, capacity=2)
    ad = init_adapters(jax.random.PRNGKey(0), cfg)
    bad = jax.tree.map(lambda l: l, ad)
    leaf = bad["blocks"]["b0"]["mixer"]["wq"]["a"]
    bad["blocks"]["b0"]["mixer"]["wq"]["a"] = leaf[:, :-1]
    with pytest.raises(ValueError, match=r"wq.*\['a'\]|\['a'\].*wq"):
        reg.register("c0", bad)
    assert "c0" not in reg                    # nothing half-registered
    with pytest.raises(KeyError):
        reg.version("c0")                     # no version entry leaked
    assert reg.default_priority("c0") is None  # no priority leaked either
    with pytest.raises(ValueError, match=r"wq"):
        reg.register("c0", bad, default_priority="interactive")
    assert reg.default_priority("c0") is None


def test_register_rejects_wrong_structure():
    cfg = tiny_dense()
    reg = AdapterRegistry(cfg, capacity=2)
    ad = init_adapters(jax.random.PRNGKey(0), cfg)
    extra = jax.tree.map(lambda l: l, ad)
    extra["blocks"]["b0"]["mixer"]["bogus"] = {"a": jnp.zeros((1, 2, 3))}
    with pytest.raises(ValueError, match="unexpected"):
        reg.register("c0", extra)
    missing = jax.tree.map(lambda l: l, ad)
    del missing["blocks"]["b0"]["mixer"]["wq"]
    with pytest.raises(ValueError, match="missing"):
        reg.register("c0", missing)


def test_register_dual_validates_both_trees():
    cfg = tiny_dense()
    reg = AdapterRegistry(cfg, capacity=2)
    ad = init_adapters(jax.random.PRNGKey(0), cfg)
    bad = jax.tree.map(lambda l: l, ad)
    bad["blocks"]["b0"]["mlp"]["w_up"]["b"] = \
        bad["blocks"]["b0"]["mlp"]["w_up"]["b"][:, :-1]
    with pytest.raises(ValueError, match="personalized"):
        reg.register_dual("c0", bad, ad, [0.5, 0.5])
    with pytest.raises(ValueError, match="global"):
        reg.register_dual("c0", ad, bad, [0.5, 0.5])


def test_evict_nonresident_raises_keyerror():
    cfg = tiny_dense()
    reg = AdapterRegistry(cfg, capacity=2)
    with pytest.raises(KeyError, match="not resident"):
        reg.evict("ghost")
    sharded = ShardedAdapterRegistry(cfg, capacity=2, num_shards=2)
    with pytest.raises(KeyError, match="not resident"):
        sharded.evict("ghost")


# ---------------------------------------------------------------------------
# KV-pool guards survive ``python -O`` (assert -> exception promotion)
# ---------------------------------------------------------------------------

def test_ensure_over_table_capacity_raises_valueerror():
    kv = PagedKVCache(num_slots=1, block_size=4, num_blocks=8,
                      max_blocks_per_slot=2)
    kv.admit(0)
    with pytest.raises(ValueError, match="max_blocks_per_slot"):
        kv.ensure(0, 9)                       # needs 3 > 2 blocks


def test_pool_guards_live_under_python_O():
    """The promoted guards must fire with asserts compiled out; the
    diagnostic ``check_invariants`` suite may legitimately stay assert-
    based (it is opt-in, not hot-path)."""
    code = (
        "from repro.serving.kv_cache import PagedKVCache\n"
        "kv = PagedKVCache(1, 4, 8, 2)\n"
        "kv.admit(0)\n"
        "try:\n"
        "    kv.ensure(0, 9)\n"
        "except ValueError:\n"
        "    print('GUARDED')\n")
    out = subprocess.run([sys.executable, "-O", "-c", code],
                         capture_output=True, text=True,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
                         cwd="/root/repo")
    assert out.returncode == 0, out.stderr
    assert "GUARDED" in out.stdout


def test_rollback_shared_tail_guard_is_runtimeerror():
    """Corrupting a tail block's refcount must trip the promoted
    RuntimeError (not a stripped assert) before the block is freed."""
    kv = PagedKVCache(num_slots=2, block_size=4, num_blocks=8,
                      max_blocks_per_slot=4)
    kv.admit(0)
    kv.ensure(0, 8)
    kv.advance(0, 8, tokens=None)
    tail = kv.block_tables[0, 1]
    kv._refcount[tail] = 2                    # simulate corruption
    with pytest.raises(RuntimeError, match="refcount"):
        kv.rollback(0, 2)
    kv._refcount[tail] = 1                    # restore


# ---------------------------------------------------------------------------
# Engine-level int8 parity (greedy streams vs the f32 path)
# ---------------------------------------------------------------------------

def _mt(cfg, bank_dtype="f32", n_clients=2):
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reg = AdapterRegistry(cfg, capacity=4, bank_dtype=bank_dtype)
    for i in range(n_clients):
        ad = init_adapters(jax.random.PRNGKey(42), cfg)
        bump = jax.random.PRNGKey(101 + i)
        reg.register(f"c{i}", jax.tree.map(
            lambda l: l + 0.02 * jax.random.normal(bump, l.shape), ad))
    return MultiTenantEngine(model, cfg, params, reg)


def _reqs(cfg):
    mk = lambda n, o=0: ((np.arange(n, dtype=np.int32) * 3 + 1 + o)
                         % cfg.vocab_size)
    return [Request("c0", mk(5), max_new_tokens=4),
            Request("c1", mk(11), max_new_tokens=7),
            Request("c1", mk(2, 3), max_new_tokens=5),
            Request("c0", mk(8, 1), max_new_tokens=3)]


def _contexts(reqs, outs):
    """Per request: prompt + emitted tokens, and the prompt length."""
    return [(np.concatenate([np.asarray(r.prompt, np.int32), o]),
             len(r.prompt)) for r, o in zip(reqs, outs)]


def _ref_logits(mt, reqs, ctxs):
    """Reference: the unquantized model's dense full forward (no cache,
    no paging) over each context, rows padded to one length — causal, so
    padding never reaches a valid position.  (R, L, V) float32."""
    from repro.core.lora import lora_scale
    L = max(len(c) for c, _ in ctxs)
    toks = np.zeros((len(ctxs), L), np.int32)
    for i, (c, _) in enumerate(ctxs):
        toks[i, :len(c)] = c
    ids = jnp.asarray([mt.registry.acquire(r.client_id) for r in reqs],
                      jnp.int32)
    fwd = jax.jit(lambda p, t, a, i: mt.model.forward(
        p, {"tokens": t}, adapters=a, lora_scale=lora_scale(mt.cfg),
        adapter_ids=i)[0])
    return np.asarray(fwd(mt.params, jnp.asarray(toks),
                          mt.registry.bank(), ids), np.float32)


def _paged_logits(mt, reqs, ctxs, kv_dtype, backend=None):
    """The serving path's logits over each context: one chunked paged
    prefill dispatch per row (``kv_dtype`` pools, ``backend`` kernels)."""
    from repro.core.lora import lora_scale
    bs = 4
    L = max(len(c) for c, _ in ctxs)
    mb = -(-L // bs)
    R = len(ctxs)
    toks = np.zeros((R, L), np.int32)
    for i, (c, _) in enumerate(ctxs):
        toks[i, :len(c)] = c
    cache = mt.model.init_paged_decode_cache(R, 1 + R * mb, bs,
                                             kv_dtype=kv_dtype)
    ids = jnp.asarray([mt.registry.acquire(r.client_id) for r in reqs],
                      jnp.int32)
    logits, _ = mt.model.prefill_step(
        mt.params, cache, jnp.asarray(toks), jnp.zeros((R,), jnp.int32),
        jnp.asarray([len(c) for c, _ in ctxs], jnp.int32),
        adapters=mt.registry.bank(), lora_scale=lora_scale(mt.cfg),
        adapter_ids=ids,
        block_tables=jnp.asarray(1 + np.arange(R * mb).reshape(R, mb),
                                 jnp.int32),
        paged_backend=backend)
    return np.asarray(logits, np.float32)


def _assert_near_argmax(ref, ctxs, what):
    """Every emitted token scores within 2*LOGIT_ATOL of the reference's
    best token at its position: what an int8 path whose logits sit within
    LOGIT_ATOL of the reference can emit."""
    for i, (c, p) in enumerate(ctxs):
        lg = ref[i, p - 1:len(c) - 1]               # predicts c[p:]
        gap = lg.max(-1) - lg[np.arange(len(lg)), c[p:]]
        assert (gap <= 2 * LOGIT_ATOL).all(), (what, i, gap)


def _check_int8_streams(mt_f, mt_q, reqs, ref_out, got_out, backend=None):
    """int8 vs unquantized serving, held to logits and not to argmaxes
    (greedy argmax flips on near-ties of random weights):

    * both streams fill every request's budget;
    * teacher-forced over the int8 stream, the int8 path's logits stay
      within LOGIT_ATOL of the unquantized reference's;
    * every token either stream emits is a near-argmax of the reference;
    * the streams agree until the reference's top-2 margin first falls
      within 2*LOGIT_ATOL — they part only on a near-tie.
    """
    for r, a, b in zip(reqs, ref_out, got_out):
        budget = r.max_new_tokens
        assert a.size == budget and b.size == budget
    ctx_q, ctx_f = _contexts(reqs, got_out), _contexts(reqs, ref_out)
    ref_q = _ref_logits(mt_f, reqs, ctx_q)
    got = _paged_logits(mt_q, reqs, ctx_q, "int8", backend)
    for i, (c, _) in enumerate(ctx_q):
        np.testing.assert_allclose(got[i, :len(c)], ref_q[i, :len(c)],
                                   rtol=0, atol=LOGIT_ATOL)
    ref_f = _ref_logits(mt_f, reqs, ctx_f)
    _assert_near_argmax(ref_q, ctx_q, "int8")
    _assert_near_argmax(ref_f, ctx_f, "f32")
    for i, ((c, p), a, b) in enumerate(zip(ctx_f, ref_out, got_out)):
        top2 = np.sort(ref_f[i, p - 1:len(c) - 1], axis=-1)[:, -2:]
        ties = np.flatnonzero(top2[:, 1] - top2[:, 0] <= 2 * LOGIT_ATOL)
        agree = ties[0] if ties.size else a.size
        np.testing.assert_array_equal(b[:agree], a[:agree])


def _assert_stream_parity(cfg, sc_kw, bank_dtype="f32"):
    reqs = _reqs(cfg)
    mt_f, mt_q = _mt(cfg), _mt(cfg, bank_dtype=bank_dtype)
    ref = mt_f.generate(reqs, ServeConfig(**sc_kw))
    got = mt_q.generate(reqs, ServeConfig(kv_dtype="int8", **sc_kw))
    _check_int8_streams(mt_f, mt_q, reqs, ref, got,
                        sc_kw.get("paged_backend"))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_int8_greedy_streams_match_f32_ragged(backend):
    cfg = tiny_dense()
    _assert_stream_parity(cfg, dict(batch_size=2, max_new_tokens=8,
                                    block_size=4, paged_backend=backend))


def test_int8_greedy_streams_match_under_preemption():
    cfg = tiny_dense()
    # pool of 5 allocatable blocks with spans up to 18 tokens -> forced
    # preemption churn; int8 must replay identically
    _assert_stream_parity(cfg, dict(batch_size=3, max_new_tokens=8,
                                    block_size=4, num_blocks=6))


def test_int8_greedy_streams_match_with_warm_prefix_reuse():
    cfg = tiny_dense()
    kw = dict(batch_size=2, max_new_tokens=8, block_size=4,
              prefix_cache=True)
    reqs = _reqs(cfg)
    mt_f, mt_q = _mt(cfg), _mt(cfg)
    for rnd in range(2):                      # second round hits warm pool
        ref = mt_f.generate(reqs, ServeConfig(**kw))
        got = mt_q.generate(reqs, ServeConfig(kv_dtype="int8", **kw))
        _check_int8_streams(mt_f, mt_q, reqs, ref, got)
    assert mt_q.last_stats["prefix_pool_reused"]
    assert mt_q.last_stats["prefix_hit_tokens"] > 0
    assert mt_q.last_stats["kv_dtype"] == "int8"


def test_int8_greedy_streams_match_spec_decode():
    cfg = tiny_dense()
    _assert_stream_parity(cfg, dict(batch_size=2, max_new_tokens=8,
                                    block_size=4, spec_decode=True,
                                    spec_k=3))


def test_int8_greedy_streams_match_sharded():
    cfg = tiny_dense()
    _assert_stream_parity(cfg, dict(batch_size=4, max_new_tokens=8,
                                    block_size=4, num_shards=2))


def test_int8_bank_and_int8_kv_together():
    cfg = tiny_dense()
    _assert_stream_parity(cfg, dict(batch_size=2, max_new_tokens=8,
                                    block_size=4), bank_dtype="int8")


def test_kv_dtype_validated():
    cfg = tiny_dense()
    mt = _mt(cfg)
    with pytest.raises(ValueError, match="kv_dtype"):
        mt.generate(_reqs(cfg), ServeConfig(batch_size=2, kv_dtype="fp8"))
    with pytest.raises(ValueError, match="bank_dtype"):
        AdapterRegistry(cfg, capacity=2, bank_dtype="fp4")


def test_warm_pool_not_reused_across_kv_dtype_change():
    """The warm prefix pool is keyed by kv_dtype: an f32 stream must not
    inherit int8 blocks (or vice versa)."""
    cfg = tiny_dense()
    mt = _mt(cfg)
    kw = dict(batch_size=2, max_new_tokens=4, block_size=4,
              prefix_cache=True)
    reqs = _reqs(cfg)
    mt.generate(reqs, ServeConfig(kv_dtype="int8", **kw))
    mt.generate(reqs, ServeConfig(kv_dtype="f32", **kw))
    assert not mt.last_stats["prefix_pool_reused"]


# ---------------------------------------------------------------------------
# Capacity: the point of int8 pools
# ---------------------------------------------------------------------------

def test_int8_block_bytes_give_capacity_headroom():
    """At a fixed HBM budget the int8 pool holds >= 1.5x the blocks of the
    bf16 pool (the bench gate's static counterpart)."""
    bs, Kv, hd = 16, 2, 32
    f32 = kv_bytes_per_block(bs, Kv, hd, "f32")
    i8 = kv_bytes_per_block(bs, Kv, hd, "int8")
    assert f32 / i8 >= 1.5
    # and the formula matches the actual pytree the model allocates
    cfg = tiny_dense()
    model = get_model(cfg)
    for kv_dtype in ("f32", "int8"):
        cache = model.init_paged_decode_cache(1, 4, bs, kv_dtype=kv_dtype)
        entry = cache["blocks"]["b0"]
        per_block = sum(                     # leaves are (P, NB, bs, ...)
            l.dtype.itemsize * int(np.prod(l.shape[2:]))
            for l in jax.tree.leaves(entry))
        want = kv_bytes_per_block(bs, cfg.n_kv_heads,
                                  cfg.resolved_head_dim, kv_dtype)
        assert per_block == want
