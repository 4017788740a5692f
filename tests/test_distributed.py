"""Distributed FDLoRA round step (single-device mesh execution) + roofline
extraction units."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_dense
from repro.analysis import roofline as rl
from repro.core.lora import init_adapters
from repro.core.outer_opt import make_outer_optimizer
from repro.federated.distributed import make_fdlora_round_step
from repro.models.api import get_model
from repro.training.optimizers import adamw


def test_fdlora_round_step_runs_and_aggregates():
    cfg = tiny_dense()
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    inner = adamw(lr=1e-3)
    outer = make_outer_optimizer("nesterov", lr=0.5, momentum=0.5)
    K, N, B, S = 2, 2, 2, 16
    round_step = make_fdlora_round_step(model, cfg, inner, outer, K)

    theta_s = init_adapters(jax.random.PRNGKey(1), cfg)
    state = {
        "inner_opt": jax.tree.map(
            lambda x: jnp.stack([x] * N), inner.init(theta_s)),
        "outer_opt": outer.init(theta_s),
    }
    batches = {
        "tokens": jax.random.randint(jax.random.PRNGKey(2), (N, K, B, S),
                                     0, cfg.vocab_size),
        "loss_mask": jnp.ones((N, K, B, S), jnp.int32),
    }
    theta_new, state2, loss = jax.jit(round_step)(params, theta_s, state, batches)
    assert bool(jnp.isfinite(loss))
    changed = any(not bool(jnp.allclose(a, b)) for a, b in
                  zip(jax.tree.leaves(theta_new), jax.tree.leaves(theta_s)))
    assert changed


def test_round_step_fedavg_equivalence():
    """With OuterOpt=SGD(lr=1) the round ends at the client mean."""
    cfg = tiny_dense()
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    inner = adamw(lr=1e-2)
    outer = make_outer_optimizer("fedavg")
    K, N, B, S = 1, 2, 2, 8
    round_step = make_fdlora_round_step(model, cfg, inner, outer, K)
    theta_s = init_adapters(jax.random.PRNGKey(1), cfg)
    state = {"inner_opt": jax.tree.map(lambda x: jnp.stack([x] * N),
                                       inner.init(theta_s)),
             "outer_opt": outer.init(theta_s)}
    batches = {"tokens": jax.random.randint(jax.random.PRNGKey(2),
                                            (N, K, B, S), 0, cfg.vocab_size),
               "loss_mask": jnp.ones((N, K, B, S), jnp.int32)}
    theta_new, _, _ = jax.jit(round_step)(params, theta_s, state, batches)
    # run the two clients by hand
    from repro.training.train_step import make_lora_train_step
    step = jax.jit(make_lora_train_step(model, cfg, inner))
    outs = []
    for i in range(N):
        st = inner.init(theta_s)
        ad = theta_s
        b = {"tokens": batches["tokens"][i, 0],
             "loss_mask": batches["loss_mask"][i, 0]}
        ad, st, _ = step(params, ad, st, b)
        outs.append(ad)
    from repro.core.lora import tree_mean
    expect = tree_mean(outs)
    for a, b in zip(jax.tree.leaves(theta_new), jax.tree.leaves(expect)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# Roofline units
# ---------------------------------------------------------------------------

def test_cost_analysis_is_per_device_and_scan_counts_once():
    """The two facts the dry-run methodology rests on (DESIGN/EXPERIMENTS)."""
    def f(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=4)
        return y

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    rolled = jax.jit(f).lower(x, w).compile().cost_analysis()["flops"]
    unrolled = jax.jit(lambda x, w: x @ w @ w @ w @ w).lower(
        x, w).compile().cost_analysis()["flops"]
    assert abs(unrolled - 4 * rolled) / unrolled < 0.05


def test_roofline_terms_and_dominance():
    cost = {"flops": 197e12, "bytes accessed": 819e9 * 2}
    roof = rl.analyze(cost, "", chips=4, model_flops=197e12 * 4,
                      device_kind="TPU v5 lite")
    assert abs(roof.compute_s - 1.0) < 1e-6
    assert abs(roof.memory_s - 2.0) < 1e-6
    assert roof.dominant == "memory"
    assert abs(roof.useful_ratio - 1.0) < 1e-6


def test_roofline_refuses_unknown_device_kind():
    with pytest.raises(KeyError, match="no peak rates"):
        rl.analyze({"flops": 1.0}, "", chips=1, device_kind="cpu")


def test_collective_factors():
    hlo = """
  %ar = bf16[1024]{0} all-reduce(%a), replica_groups={{0,1,2,3}}
  %ag = bf16[1024]{0} all-gather(%b), replica_groups=[2,4]
  %rs = bf16[256]{0} reduce-scatter(%c), replica_groups={{0,1,2,3}}
"""
    colls = rl.parse_collectives(hlo)
    by = {c.op: c for c in colls}
    assert by["all-reduce"].per_chip_bytes == 2 * 2048 * 3 / 4
    assert by["all-gather"].per_chip_bytes == 2048 * 3 / 4
    assert by["reduce-scatter"].per_chip_bytes == 512 * 3
