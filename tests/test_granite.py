"""The Granite-4.0-H pieces of the program: the chunked SSD scan from a
slot's state, the published scalars and their defaults, and K/V pools
that pack narrow heads into whole rows."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_dense
from repro.configs.registry import get_config
from repro.kernels.ops import paged_gqa_attention, paged_prefill_gqa_attention
from repro.models import mamba2
from repro.models.api import get_model
from repro.models.layers import kv_pack

RNG = np.random.default_rng(16)


def _recurrence(x, dt, A, Bm, Cm, h0):
    """The published Mamba-2 recurrence, one token at a time, in float64:
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T, y_t = C_t h_t."""
    x, dt, Bm, Cm, h = (np.asarray(a, np.float64)
                        for a in (x, dt, Bm, Cm, h0))
    A = np.asarray(A, np.float64)
    H, G = x.shape[2], Bm.shape[2]
    grp = np.arange(H) // (H // G)
    ys = []
    for t in range(x.shape[1]):
        b, c = Bm[:, t][:, grp], Cm[:, t][:, grp]           # (B, H, N)
        h = h * np.exp(dt[:, t] * A)[:, :, None, None] \
            + (dt[:, t, :, None] * x[:, t])[..., None] * b[:, :, None, :]
        ys.append(np.einsum("bhpn,bhn->bhp", h, c))
    return np.stack(ys, 1), h


def _ssd_inputs(B=3, S=20, H=4, P=8, G=2, N=16):
    x = RNG.standard_normal((B, S, H, P)).astype(np.float32)
    dt = np.exp(RNG.uniform(np.log(1e-3), np.log(0.5), (B, S, H))
                ).astype(np.float32)
    A = -np.arange(1, H + 1, dtype=np.float32)
    Bm = RNG.standard_normal((B, S, G, N)).astype(np.float32)
    Cm = RNG.standard_normal((B, S, G, N)).astype(np.float32)
    h0 = RNG.standard_normal((B, H, P, N)).astype(np.float32)
    return x, dt, A, Bm, Cm, h0


@pytest.mark.parametrize("chunk", [4, 7, 20], ids=["4", "ragged-7", "whole"])
def test_ssd_chunked_from_a_state_matches_the_recurrence(chunk):
    """From a nonzero state, with ragged fills (one row empty), the
    chunked scan gives the recurrence's outputs at every valid position
    and its state after each row's last valid token.  Tolerance: float32
    against float64 over 20 steps of decays <= 1 (relative 1e-5)."""
    x, dt, A, Bm, Cm, h0 = _ssd_inputs()
    n_new = np.asarray([20, 13, 0], np.int32)
    with jax.default_matmul_precision("highest"):
        y, h = mamba2.ssd_chunked(jnp.asarray(x), jnp.asarray(dt),
                                  jnp.asarray(A), jnp.asarray(Bm),
                                  jnp.asarray(Cm), chunk, h0=jnp.asarray(h0),
                                  n_new=jnp.asarray(n_new))
    for b, n in enumerate(n_new[:2]):
        yr, hr = _recurrence(x[b:b + 1, :n], dt[b:b + 1, :n], A,
                             Bm[b:b + 1, :n], Cm[b:b + 1, :n], h0[b:b + 1])
        np.testing.assert_allclose(np.asarray(y)[b, :n], yr[0], rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(h)[b], hr[0], rtol=1e-5,
                                   atol=1e-5)
    assert np.array_equal(np.asarray(h)[2], h0[2])       # bit for bit


def test_ssd_chunked_from_zero_is_the_training_scan():
    """No state given is a zero state."""
    x, dt, A, Bm, Cm, _ = _ssd_inputs()
    args = [jnp.asarray(a) for a in (x, dt, A, Bm, Cm)]
    y0, h0 = mamba2.ssd_chunked(*args, 5)
    y1, h1 = mamba2.ssd_chunked(*args, 5, h0=jnp.zeros_like(h0))
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=1e-6)
    np.testing.assert_allclose(np.asarray(h0), np.asarray(h1), atol=1e-6)


def _granite_smoke():
    cfg = get_config("granite-4.0-h-micro", smoke=True).with_overrides(
        dtype="float32", param_dtype="float32", remat=False)
    params = mamba2.init_mamba(jax.random.PRNGKey(3), cfg, jnp.float32)
    params["conv_b"] = 0.1 * jax.random.normal(jax.random.PRNGKey(4),
                                               params["conv_b"].shape)
    return cfg, params


def test_cached_chunk_reads_the_state_once_and_leaves_idle_rows_alone():
    """A cached Mamba-2 chunk (the SSD scan from the slot's state) equals
    the same tokens fed one recurrent step at a time, and a row with no
    new tokens keeps its state and conv window bit for bit.  Tolerance:
    float32 chunked scan against float32 steps (1e-4 on outputs of
    size ~1)."""
    cfg, params = _granite_smoke()
    B, S, K = 3, 12, cfg.ssm_d_conv
    cache = mamba2.init_ssm_cache(cfg, B)
    cache = {"h": jnp.asarray(RNG.standard_normal(cache["h"].shape),
                              jnp.float32),
             # kept in the model's float32, so that the steps below do not
             # round the conv window to bf16 where the chunk does not
             "conv": jnp.asarray(RNG.standard_normal(cache["conv"].shape),
                                 jnp.bfloat16).astype(jnp.float32)}
    x = jnp.asarray(RNG.standard_normal((B, S, cfg.d_model)), jnp.float32)
    n_new = jnp.asarray([12, 5, 0], jnp.int32)
    # the cache as the layer scan hands it over: a stack of one period
    stacked = lambda c: jax.tree.map(lambda a: a[None], c)  # noqa: E731
    period = jnp.int32(0)
    with jax.default_matmul_precision("highest"):
        out, new = mamba2.apply_mamba(params, x, cfg, ssm_cache=stacked(cache),
                                      n_new=n_new, period=period)
        step_cache, outs = stacked(cache), []
        for t in range(S):
            o, step_cache = mamba2.apply_mamba(
                params, x[:, t:t + 1], cfg, ssm_cache=step_cache,
                n_new=(n_new > t).astype(jnp.int32), period=period)
            outs.append(o)
    steps = jnp.concatenate(outs, 1)
    new, step_cache = (jax.tree.map(lambda a: a[0], c)
                       for c in (new, step_cache))
    for b, n in enumerate([12, 5, 0]):
        np.testing.assert_allclose(np.asarray(out)[b, :n],
                                   np.asarray(steps)[b, :n], atol=1e-4)
        np.testing.assert_allclose(np.asarray(new["h"])[b],
                                   np.asarray(step_cache["h"])[b], atol=1e-4)
        assert np.array_equal(np.asarray(new["conv"], np.float32)[b],
                              np.asarray(step_cache["conv"], np.float32)[b])
    for k in ("h", "conv"):
        assert np.array_equal(np.asarray(new[k], np.float32)[2],
                              np.asarray(cache[k], np.float32)[2])
    assert cache["conv"].shape[1] == K - 1


def test_default_scalars_keep_the_programs_own_rules():
    """Left at their defaults the scalars are today's arithmetic: a tied
    dense model's embedding times sqrt(d_model), softmax scale
    head_dim ** -0.5, no residual or logit scaling, eps 1e-6 — the same
    logits bit for bit as with those values spelled out."""
    cfg = tiny_dense(tie_embeddings=True)
    assert cfg.resolved_embedding_multiplier == cfg.d_model ** 0.5
    assert tiny_dense().resolved_embedding_multiplier == 1.0
    spelled = cfg.with_overrides(
        embedding_multiplier=cfg.d_model ** 0.5,
        attention_multiplier=cfg.resolved_head_dim ** -0.5,
        residual_multiplier=1.0, logits_scaling=1.0, norm_eps=1e-6)
    toks = jnp.asarray(RNG.integers(0, cfg.vocab_size, (2, 12)), jnp.int32)
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    a, _ = get_model(cfg).forward(params, {"tokens": toks})
    b, _ = get_model(spelled).forward(params, {"tokens": toks})
    assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("scalar,value", [
    ("embedding_multiplier", 3.0), ("residual_multiplier", 0.5),
    ("attention_multiplier", 0.05), ("logits_scaling", 4.0),
    ("norm_eps", 0.5)])
def test_each_scalar_moves_the_logits(scalar, value):
    """Every scalar is read: setting it moves the logits."""
    cfg = tiny_dense()
    toks = jnp.asarray(RNG.integers(0, cfg.vocab_size, (2, 12)), jnp.int32)
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    a, _ = get_model(cfg).forward(params, {"tokens": toks})
    moved = cfg.with_overrides(**{scalar: value})
    b, _ = get_model(moved).forward(params, {"tokens": toks})
    assert float(jnp.max(jnp.abs(a - b))) > 1e-3 * float(jnp.max(jnp.abs(a)))


@pytest.mark.parametrize("kv,hd,f", [(8, 64, 2), (4, 32, 4), (2, 64, 2),
                                     (3, 64, 1), (8, 128, 1), (2, 16, 1)])
def test_kv_pack(kv, hd, f):
    assert kv_pack(kv, hd) == f


@pytest.mark.parametrize("H,Kv,hd", [(32, 8, 64), (8, 4, 32), (4, 2, 64)])
def test_packed_pools_serve_the_same_attention(H, Kv, hd):
    """A pool packed f heads to a row (``layers.kv_pack``), read in place
    by both Pallas kernels, gives what the logical pool gives (which the
    wrappers pad to 128 lanes).  Tolerance: one bf16 step (2^-7
    relative): the two hold the same products, but a head's lanes sit at
    other positions of the packed row, so the float32 sums can round
    apart and, rarely, land on neighbouring bf16 outputs."""
    f = kv_pack(Kv, hd)
    assert f > 1
    B, bs, MB, T = 3, 8, 4, 5
    NB = B * MB + 1
    q = jnp.asarray(RNG.standard_normal((B, T, H, hd)), jnp.bfloat16)
    k = jnp.asarray(RNG.standard_normal((B, T, Kv, hd)), jnp.bfloat16)
    pool = jnp.asarray(RNG.standard_normal((NB, bs, Kv, hd)), jnp.bfloat16)
    packed = pool.reshape(NB, bs, Kv // f, f * hd)
    bt = jnp.asarray(RNG.permutation(np.arange(1, NB))[:B * MB]
                     .reshape(B, MB), jnp.int32)
    lens = jnp.asarray([0, 9, 21], jnp.int32)
    n_new = jnp.asarray([5, 3, 0], jnp.int32)
    want = paged_prefill_gqa_attention(q, k, k, pool, pool, bt, lens, n_new,
                                       scale=0.1)
    got = paged_prefill_gqa_attention(q, k, k, packed, packed, bt, lens,
                                      n_new, scale=0.1)
    np.testing.assert_allclose(np.asarray(got[0], np.float32),
                               np.asarray(want[0], np.float32),
                               rtol=2**-7, atol=1e-6)
    assert np.array_equal(np.asarray(got[1]).reshape(want[1].shape),
                          np.asarray(want[1]))
    dec_want = paged_gqa_attention(q[:, :1], want[1], want[2], bt, lens + 1,
                                   scale=0.1)
    dec_got = paged_gqa_attention(q[:, :1], got[1], got[2], bt, lens + 1,
                                  scale=0.1)
    np.testing.assert_allclose(np.asarray(dec_got, np.float32),
                               np.asarray(dec_want, np.float32),
                               rtol=2**-7, atol=1e-6)


def test_granite_serves_the_same_streams_on_both_backends():
    """A granite-pattern model whose pools pack two heads of 64 to a row
    serves, through chunked prefill (the SSD scan from each slot's
    state) and decode, the same greedy tokens on the Pallas kernels
    (interpret mode here) as on the jnp gather."""
    import dataclasses

    from repro.core.lora import init_adapters
    from repro.serving.engine import MultiTenantEngine, Request, ServeConfig
    from repro.serving.registry import AdapterRegistry

    cfg = get_config("granite-4.0-h-micro", smoke=True).with_overrides(
        head_dim=64, dtype="float32", param_dtype="float32", remat=False)
    assert kv_pack(cfg.n_kv_heads, cfg.resolved_head_dim) == 2
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reg = AdapterRegistry(cfg, capacity=2)
    for i in range(2):
        reg.register(f"c{i}", init_adapters(jax.random.PRNGKey(i + 1), cfg))
    eng = MultiTenantEngine(model, cfg, params, reg)
    rng = np.random.default_rng(7)
    reqs = [Request(f"c{i % 2}", rng.integers(0, cfg.vocab_size, n)
                    .astype(np.int32), max_new_tokens=5)
            for i, n in enumerate((19, 7, 12))]
    sc = ServeConfig(batch_size=2, block_size=4, num_blocks=24,
                     prefill_chunk=8, max_new_tokens=5)
    out_jnp = eng.generate(reqs, sc)
    out_pal = eng.generate(reqs,
                           dataclasses.replace(sc, paged_backend="pallas"))
    for a, b in zip(out_jnp, out_pal):
        np.testing.assert_array_equal(a, b)
