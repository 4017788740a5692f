"""The Granite hybrid architecture module at a tiny size on the CPU: its
seeded layers, its float32 reference (a token-by-token Mamba-2) against
the program's forward pass and against what ``MultiTenantEngine`` serves
through chunked prefill and the paged cache, each published scalar
shown to matter, and its work counts."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import harness, spec  # noqa: E402
from chipbench import reference as R  # noqa: E402

GRANITE = spec.load_arch("granite")
FULL = spec.load_config("granite-4.0-h-micro")
# a period of four (mamba, mamba, attention, mamba) twice; eps 0.5 so that
# a norm's eps moves the logits at this size
TINY = dict(
    {k: v for k, v in FULL.items() if k != "program_departures"},
    name="tiny-granite", hidden_size=64, intermediate_size=128,
    shared_intermediate_size=128, num_attention_heads=4,
    num_key_value_heads=2, mamba_n_heads=8, mamba_d_head=16,
    mamba_d_state=16, num_hidden_layers=8, vocab_size=256,
    layer_types=["mamba", "mamba", "attention", "mamba"] * 2,
    max_position_embeddings=128, norm_eps=0.5, attention_multiplier=0.125,
    lora=dict(FULL["lora"], rank=4, alpha=8.0),
    serving={"block_size": 8, "kv_pool_blocks": 49, "ssd_chunk": 8},
    check={"max_logit_gap": 1e-4},
    init={"embed_std": 0.02, "lora_b_std": 0.5})
SEED = 2**33 + 7


def test_the_period_and_the_program_config():
    cfg = harness.program_config(TINY)
    assert cfg.layer_pattern == ("mamba+mlp", "mamba+mlp", "attn+mlp",
                                 "mamba+mlp")
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling, cfg.norm_eps,
            cfg.ssm_conv_bias, cfg.use_rope) == (12.0, 0.22, 0.125, 8.0,
                                                 0.5, True, False)
    full = harness.program_config(FULL)
    assert (full.n_layers, len(full.layer_pattern), full.head_dim,
            full.ssm_n_heads, full.ssm_d_state) == (40, 10, 64, 64, 128)
    assert GRANITE.attention_layers(FULL) == 4
    assert GRANITE.mamba_layers(FULL) == 36


def test_a_file_the_program_cannot_run_is_refused():
    with pytest.raises(ValueError, match="position_embedding_type"):
        harness.program_config(dict(TINY, position_embedding_type="rope"))


def test_a_layer_made_alone_equals_its_stacked_slice():
    full = GRANITE.init_params(TINY, SEED)["blocks"]
    for layer in (1, 2, 6):              # b1 period 0, b2 period 0, b2 period 1
        one = GRANITE.layer_params(TINY, SEED, layer)
        stacked = jax.tree.map(lambda x: x[layer // 4], full[f"b{layer % 4}"])
        assert jax.tree.structure(one) == jax.tree.structure(stacked)
        for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(stacked)):
            assert np.array_equal(np.asarray(a), np.asarray(b))


def test_the_fused_adapter_of_a_layer_is_eq7_of_the_stacked_ones():
    """Equal up to float32 rounding of w1 A_p + w2 A_g, w2 = 1 - w1 taken
    in two places: terms of up to about 0.5, whose ulp is 6e-8, may
    cancel, so 1e-7 absolute."""
    per, glo, w = GRANITE.client_adapters(TINY, SEED, 1)
    fused = GRANITE.fused_adapter(TINY, SEED, 1, 6)
    for t, (a, b) in fused.items():
        part = "mixer" if t in GRANITE.ATTN + GRANITE.SSM else "mlp"
        pa = per["blocks"]["b2"][part][t]
        ga = glo["blocks"]["b2"][part][t]
        np.testing.assert_allclose(np.asarray(a), np.asarray(
            w[0] * pa["a"][1] + w[1] * ga["a"][1]), rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(b), np.asarray(
            w[0] * pa["b"][1] + w[1] * ga["b"][1]), rtol=1e-6, atol=1e-7)


def _program_logits(config, toks, conv_bias=True, **overrides):
    """The program's forward pass in float32 at highest precision, every
    client's fused adapter from the registry's bank (without the Mamba-2
    conv biases where ``conv_bias`` is false)."""
    from repro.core.lora import lora_scale
    from repro.models.api import get_model
    from repro.serving.registry import AdapterRegistry

    cfg = harness.program_config(config).with_overrides(dtype="float32",
                                                        **overrides)
    params = GRANITE.init_params(config, SEED)
    if not conv_bias:
        for block in params["blocks"].values():
            block["mixer"].pop("conv_b", None)
    reg = AdapterRegistry(cfg, capacity=len(toks))
    for c in range(len(toks)):
        reg.register_dual(f"c{c}", *GRANITE.client_adapters(config, SEED, c))
    ids = jnp.asarray([reg.acquire(f"c{c}") for c in range(len(toks))])
    with jax.default_matmul_precision("highest"):
        got, _ = get_model(cfg).forward(
            params, {"tokens": jnp.asarray(toks)}, adapters=reg.bank(),
            lora_scale=lora_scale(cfg), adapter_ids=ids)
    return np.asarray(got)


def _reference_logits(config, toks, quant=False):
    ref = GRANITE.Reference(config, SEED, quant=quant)
    hs = ref.hidden([(c, t) for c, t in enumerate(toks)], 32)
    head = ref.head()
    return np.stack([np.asarray(jnp.matmul(h[:toks.shape[1]], head,
                                           precision=R.HI)) for h in hs])


TOKS = np.random.default_rng(0).integers(1, 256, (3, 20)).astype(np.int32)


@pytest.fixture(scope="module")
def reference_logits():
    return _reference_logits(TINY, TOKS)


def test_reference_matches_program_forward(reference_logits):
    """The program's SSD scan and the reference's recurrence, both in
    float32 at highest precision, differ only by summation order: 1e-6 of
    logits whose spread is about 0.02."""
    np.testing.assert_allclose(_program_logits(TINY, TOKS), reference_logits,
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dropped", [
    {"embedding_multiplier": 1.0}, {"residual_multiplier": 1.0},
    {"attention_multiplier": 0.0}, {"logits_scaling": 1.0},
    {"norm_eps": 1e-6}, {"conv_bias": False}],
    ids=["embedding", "residual", "attention", "logits", "eps", "conv_bias"])
def test_a_dropped_scalar_misses_the_reference(dropped, reference_logits):
    """With any one published scalar back at the program's default, or
    the conv biases left out, the program leaves the reference by over a
    hundred times the tolerance above."""
    got = _program_logits(TINY, TOKS, **dropped)
    assert np.abs(got - reference_logits).max() > 1e-4


def test_control_departs_from_reference(reference_logits):
    """float8 matmul inputs move the logits far more than float32
    rounding: the control is a real step down in precision."""
    lc = _reference_logits(TINY, TOKS, quant=True)
    rel = np.linalg.norm(lc - reference_logits) / np.linalg.norm(
        reference_logits)
    assert 0.01 < rel < 0.5


def test_served_tokens_match_the_reference():
    """MultiTenantEngine in float32: ragged prompts of 2-4 prefill chunks
    (the SSD scan carrying each slot's state from chunk to chunk), then
    decode through the paged cache, with more requests than slots so that
    freed slots are reset and reused.  Every served token is the
    reference's best up to 1e-5 (a near-tie summation order can flip),
    on logits whose spread is about 0.02."""
    from repro.models.api import get_model
    from repro.serving.engine import MultiTenantEngine, Request, ServeConfig
    from repro.serving.registry import AdapterRegistry

    cfg = harness.program_config(TINY).with_overrides(dtype="float32")
    params = GRANITE.init_params(TINY, SEED)
    reg = AdapterRegistry(cfg, capacity=3)
    for c in range(3):
        reg.register_dual(f"c{c}", *GRANITE.client_adapters(TINY, SEED, c))
    eng = MultiTenantEngine(get_model(cfg), cfg, params, reg)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 256, n).astype(np.int32)
               for n in (37, 21, 50, 17)]
    clients = [0, 1, 2, 1]
    reqs = [Request(f"c{c}", p, max_new_tokens=6)
            for c, p in zip(clients, prompts)]
    sc = ServeConfig(batch_size=2, block_size=8, prefill_chunk=16,
                     scan_chunk=4, max_blocks_per_slot=8, num_blocks=17)
    with jax.default_matmul_precision("highest"):
        outs = eng.generate(reqs, sc)
    ref = GRANITE.Reference(TINY, SEED)
    seqs = [(c, np.concatenate([p, np.asarray(o[:-1], np.int32)]))
            for c, p, o in zip(clients, prompts, outs)]
    hs = ref.hidden(seqs, 64)
    gaps = [R.served_gaps(h, ref.head(), len(p), np.asarray(o))
            for h, p, o in zip(hs, prompts, outs)]
    assert all(len(o) == 6 for o in outs)
    assert max(g.max() for g in gaps) <= 1e-5


def test_work_counts():
    """The counts from the published widths: the projections and the
    head are the program's parameters less norms, conv and per-head
    vectors; a token's state is 2 MiB read and written in each of 36
    layers."""
    from repro.configs.registry import get_config

    cfg = get_config("granite-4.0-h-micro")
    other = cfg.n_layers * 2 * cfg.d_model + cfg.d_model \
        + 36 * (5 * (cfg.ssm_d_inner + 256) + 2 * cfg.ssm_n_heads)
    assert abs(GRANITE.matmul_params(FULL) - (cfg.count_params() - other)) \
        < 1e-3 * cfg.count_params()
    w = GRANITE.token_work(FULL, 1000)
    assert w["ssm_bytes"] == 36 * 2 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    assert w["ssm_flops"] == 36 * (5 * 64 * 64 * 128 + 2 * 4 * 4352)
    assert w["attn_bytes"] == 2 * 1000 * 8 * 64 * 4 * 2
    assert w["flops"] > 2 * GRANITE.matmul_params(FULL) + w["ssm_flops"]
    pw = GRANITE.prompt_work(FULL, 300, 128)
    assert "ssm_bytes" not in pw
    assert pw["attn_bytes"] == sum(2 * min(300, s + 128) * 8 * 64 * 4 * 2
                                   for s in (0, 128, 256))
