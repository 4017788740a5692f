"""The float32 reference checked against the program's own jnp forward
pass at a tiny size, with the program computing in float32 too, so the
two differ only by summation order."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from chipbench_tiny import TINY_CONFIG, TINY_TIED

from chipbench import harness, spec
from chipbench import reference as R

DENSE = spec.load_arch("dense")


def test_a_layer_made_alone_equals_its_stacked_slice():
    full = DENSE.init_params(TINY_CONFIG, 2**32 + 3)["blocks"]["b0"]
    one = DENSE.layer_params(TINY_CONFIG, 2**32 + 3, 1)
    for a, b in zip(jax.tree.leaves(one),
                    jax.tree.leaves(jax.tree.map(lambda x: x[1], full))):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    emb, head, _ = DENSE.embed_head(TINY_CONFIG, 2**32 + 3)
    assert np.array_equal(
        np.asarray(emb),
        np.asarray(DENSE.init_params(TINY_CONFIG, 2**32 + 3)["embed"]))


@pytest.mark.parametrize("config", [TINY_CONFIG, TINY_TIED],
                         ids=["rmsnorm-untied", "layernorm-tied"])
def test_reference_matches_program_forward(config):
    from repro.core.lora import lora_scale
    from repro.models.api import get_model
    from repro.serving.registry import AdapterRegistry

    seed, n_clients, S = 11, 3, 20
    cfg = harness.program_config(config).with_overrides(dtype="float32")
    params = DENSE.init_params(config, seed)
    reg = AdapterRegistry(cfg, capacity=n_clients)
    for c in range(n_clients):
        reg.register_dual(f"c{c}", *DENSE.client_adapters(config, seed, c))
    rng = np.random.default_rng(0)
    toks = rng.integers(1, config["vocab_size"], (n_clients, S)).astype(
        np.int32)
    ids = jnp.asarray([reg.acquire(f"c{c}") for c in range(n_clients)])
    with jax.default_matmul_precision("highest"):
        got, _ = get_model(cfg).forward(
            params, {"tokens": jnp.asarray(toks)}, adapters=reg.bank(),
            lora_scale=lora_scale(cfg), adapter_ids=ids)
    ref = DENSE.Reference(config, seed)
    hs = ref.hidden([(c, toks[c]) for c in range(n_clients)], 32)
    head = ref.head()
    for c in range(n_clients):
        want = np.asarray(jnp.matmul(hs[c][:S], head, precision=R.HI))
        np.testing.assert_allclose(np.asarray(got[c]), want, rtol=2e-4,
                                   atol=2e-4)


def test_control_departs_from_reference():
    """float8 matmul inputs move the logits far more than float32
    rounding: the control is a real step down in precision."""
    toks = np.arange(1, 30, dtype=np.int32)
    ref = DENSE.Reference(TINY_CONFIG, 5)
    ctl = DENSE.Reference(TINY_CONFIG, 5, quant=True)
    h = ref.hidden([(0, toks)], 32)[0]
    hc = ctl.hidden([(0, toks)], 32)[0]
    head = ref.head()
    lr = np.asarray(jnp.matmul(h, head, precision=R.HI))[:29]
    lc = np.asarray(jnp.matmul(hc, head, precision=R.HI))[:29]
    rel = np.linalg.norm(lr - lc) / np.linalg.norm(lr)
    assert 0.01 < rel < 0.5
