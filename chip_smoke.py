"""Smoke run of the multi-tenant serving path on TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the 4-chip mesh path only

One chip: ``gemma-2b`` at its published widths (18 layers, d_model 2048,
MQA with head_dim 256, vocab 256000, bf16 weights drawn from ``--seed``)
serves 16 requests over 8 slots through ``MultiTenantEngine``'s continuous
path — an ``AdapterRegistry`` of 8 Eq. 7-fused (``register_dual``)
clients, the paged KV pool at full residency, the scheduler, chunked
prefill, and the compiled Pallas paged kernels (the TPU default).  It
checks that every request gets its full token budget and that the Pallas
path agrees with the jnp reference path on the same chip: the last-position
logits of one prefill dispatch, within ``LOGIT_REL_BOUND``.

``--chips 4``: the same stream with ``num_shards=4`` and a 4-device mesh
(weights and adapter bank replicated, the 8-slot fused batch split over
"data"), compared with the same stream on one of those chips.

Wall times printed here are smoke timings, not benchmark numbers.  The
last line of stdout is one JSON object naming the device; the script
exits non-zero, printing no such line, on any failure or without a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Pallas vs jnp logits bound, relative to each row's L2 norm.  Both paths
# round to bf16 at about a dozen points per layer (projections, attention
# output, residual adds, norms, MLP); once the two attention paths round
# one value differently (probabilities rounded before vs after
# normalisation, another accumulation order) the downstream roundings
# decorrelate, so the difference grows like independent rounding noise:
# sqrt(12 * 18 layers) * 2^-9 = 2.9% at gemma-2b's depth (a CPU run at
# reduced width measured 1.0%, 1.6% and 2.3% at 2, 6 and 18 layers).  The
# bound 2^-4 = 6.25% leaves 2x headroom; attention rounded one precision
# step lower (fp8 e4m3, 2^-4 rounding) would land near 90%.
LOGIT_REL_BOUND = 2.0 ** -4

N_CLIENTS = 8
N_SLOTS = 8
N_REQUESTS = 16
PROMPT_MIN, PROMPT_MAX = 64, 512
NEW_TOKENS = 32
BLOCK = 16
PREFILL_CHUNK = 64


def log(msg: str) -> None:
    print(msg, flush=True)


def build(cfg, seed: int, registry_cls, **registry_kw):
    """Seeded bf16 weights and ``N_CLIENTS`` dual-LoRA clients, each fused
    via Eq. 7 into the registry (B factors drawn small and non-zero, so
    every client's adapter changes its outputs)."""
    import jax
    import jax.numpy as jnp

    from repro.core.lora import init_adapters
    from repro.models.api import get_model
    from repro.serving.engine import MultiTenantEngine

    model = get_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    reg = registry_cls(cfg, capacity=N_CLIENTS, **registry_kw)

    def client_tree(key):
        k0, k1 = jax.random.split(key)
        tree = init_adapters(k0, cfg)
        leaves, treedef = jax.tree.flatten(tree)
        keys = jax.random.split(k1, len(leaves))
        return jax.tree.unflatten(treedef, [
            l + 0.01 * jax.random.normal(k, l.shape, l.dtype)
            for l, k in zip(leaves, keys)])

    for i in range(N_CLIENTS):
        kp, ks = jax.random.split(jax.random.PRNGKey(seed + 1000 + i))
        reg.register_dual(f"client{i}", client_tree(kp), client_tree(ks),
                          jnp.asarray([0.6, 0.4]))
    return model, params, MultiTenantEngine(model, cfg, params, reg)


def requests(cfg, seed: int):
    import numpy as np

    from repro.serving.engine import Request

    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_MIN, PROMPT_MAX + 1, N_REQUESTS)
    return [Request(f"client{i % N_CLIENTS}",
                    rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                    max_new_tokens=NEW_TOKENS)
            for i, n in enumerate(lens)]


def serve(eng, reqs, sc):
    """Run the stream; check every request got its whole budget of valid
    token ids.  Returns (per-request tokens, seconds, engine stats)."""
    import numpy as np

    t0 = time.perf_counter()
    outs = eng.generate(reqs, sc)
    secs = time.perf_counter() - t0
    vocab = eng.cfg.vocab_size
    for rid, (r, o) in enumerate(zip(reqs, outs)):
        if o.size != r.max_new_tokens:
            raise AssertionError(f"request {rid} got {o.size} tokens, "
                                 f"budget {r.max_new_tokens}")
        if not ((o >= 0) & (o < vocab)).all():
            raise AssertionError(f"request {rid} emitted ids outside the "
                                 f"vocab: {o}")
    return [np.asarray(o) for o in outs], secs, eng.last_stats


def prefill_inputs(eng, seed: int):
    """Two chunked-prefill dispatches' worth of inputs for ``N_SLOTS`` rows:
    a first chunk of ragged length per row (the context) and a second,
    full chunk attending over it.  Row b serves client b."""
    import jax.numpy as jnp
    import numpy as np

    K, T = N_SLOTS, PREFILL_CHUNK
    rng = np.random.default_rng(seed + 1)
    toks = rng.integers(0, eng.cfg.vocab_size, (K, 2 * T)).astype(np.int32)
    n_ctx = np.asarray([T - 7 * b for b in range(K)], np.int32)
    second = np.stack([toks[b, n_ctx[b]:n_ctx[b] + T] for b in range(K)])
    mb = 2 * T // BLOCK
    return dict(
        num_blocks=1 + K * mb,
        first=(jnp.asarray(toks[:, :T]), jnp.zeros((K,), jnp.int32),
               jnp.asarray(n_ctx)),
        second=(jnp.asarray(second), jnp.asarray(n_ctx),
                jnp.full((K,), T, jnp.int32)),
        ids=jnp.asarray([eng.registry.acquire(f"client{b}")
                         for b in range(K)], jnp.int32),
        tables=jnp.asarray(1 + np.arange(K * mb).reshape(K, mb), jnp.int32))


def last_logits(eng, params, inp, backend: str, place=None):
    """Logits (N_SLOTS, T, V) of the second prefill dispatch, run on
    ``backend`` after the jnp path wrote the first chunk's context, so both
    backends see identical inputs.  ``place`` puts the fresh pool and the
    bank where ``params`` live.  Returns (device logits, lowered text of
    the compared dispatch)."""
    import jax

    from repro.core.lora import lora_scale

    place = place or (lambda t: t)
    step = jax.jit(eng.model.prefill_step, static_argnames=("paged_backend",))
    kw = dict(adapters=place(eng.registry.bank()),
              lora_scale=lora_scale(eng.cfg), adapter_ids=inp["ids"],
              block_tables=inp["tables"])
    cache = place(eng.model.init_paged_decode_cache(
        N_SLOTS, inp["num_blocks"], BLOCK))
    _, cache = step(params, cache, *inp["first"], paged_backend="jnp", **kw)
    args = (params, cache, *inp["second"])
    text = step.lower(*args, paged_backend=backend, **kw).as_text()
    logits, _ = step(*args, paged_backend=backend, **kw)
    return logits, text


def rel_errors(got, want):
    """Per-row ||got - want|| / ||want|| of the last-position logits of two
    (rows, T, V) logit arrays."""
    import numpy as np

    got = np.asarray(got[:, -1], np.float64)
    want = np.asarray(want[:, -1], np.float64)
    return (np.linalg.norm(got - want, axis=-1)
            / np.linalg.norm(want, axis=-1))


def check_logits(name: str, got, want) -> float:
    import numpy as np

    rel = rel_errors(got, want)
    if not np.isfinite(rel).all():
        raise AssertionError(f"{name}: non-finite logits")
    log(f"{name}: per-row relative L2 logit difference max {rel.max():.3e} "
        f"(bound {LOGIT_REL_BOUND:.3e}); rows {[f'{r:.2e}' for r in rel]}")
    if not (rel <= LOGIT_REL_BOUND).all():
        raise AssertionError(f"{name}: logits differ beyond the bound")
    return float(rel.max())


class CompileClock:
    """Seconds JAX spends in backend compilation (and cache lookups)."""

    def __init__(self):
        import jax

        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration


def describe(cfg, params) -> None:
    import jax

    n_bytes = sum(l.nbytes for l in jax.tree.leaves(params))
    log(f"config {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads / {cfg.n_kv_heads} kv heads of "
        f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
        f"{cfg.param_dtype} weights: {n_bytes / 2**30:.3f} GiB")


def peak_gib(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.3f} GiB"


def one_chip(cfg, seed: int) -> None:
    import jax

    from repro import runtime
    from repro.serving.engine import ServeConfig
    from repro.serving.registry import AdapterRegistry

    clock = CompileClock()
    model, params, eng = build(cfg, seed, AdapterRegistry)
    describe(cfg, params)
    reqs = requests(cfg, seed)
    sc = ServeConfig(batch_size=N_SLOTS, max_new_tokens=NEW_TOKENS,
                     block_size=BLOCK, prefill_chunk=PREFILL_CHUNK)
    outs, secs, stats = serve(eng, reqs, sc)
    if stats["paged_backend"] != "pallas" or runtime.interpret_mode():
        raise AssertionError(f"served on {stats['paged_backend']!r}, "
                             f"interpret={runtime.interpret_mode()}; the "
                             f"TPU path is the compiled Pallas kernels")
    n_tok = sum(o.size for o in outs)
    log(f"served {len(reqs)} requests ({sum(r.prompt.size for r in reqs)} "
        f"prompt tokens, ragged {PROMPT_MIN}-{PROMPT_MAX}) over {N_SLOTS} "
        f"slots: {n_tok} tokens, {stats['prefill_dispatches']} prefill + "
        f"{stats['decode_dispatches']} decode dispatches, "
        f"{stats['preemptions']} preemptions, backend "
        f"{stats['paged_backend']}; smoke wall time {secs:.3f} s incl. "
        f"compile")

    inp = prefill_inputs(eng, seed)
    got, text = last_logits(eng, params, inp, "pallas")
    if "tpu_custom_call" not in text:
        raise AssertionError("the Pallas prefill dispatch holds no "
                             "tpu_custom_call: kernels not compiled")
    want, _ = last_logits(eng, params, inp, "jnp")
    check_logits("pallas vs jnp prefill dispatch", got, want)
    log(f"compile seconds {clock.secs:.3f}; peak device memory "
        f"{peak_gib(jax.devices()[0])}")


def four_chips(cfg, seed: int) -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_host_mesh
    from repro.serving.engine import ServeConfig
    from repro.serving.sharded import ShardedAdapterRegistry

    n_dev = len(jax.devices())
    if n_dev != 4:
        raise AssertionError(f"--chips 4 needs 4 devices, JAX sees {n_dev}")
    clock = CompileClock()
    model, params, eng = build(cfg, seed, ShardedAdapterRegistry,
                               num_shards=4)
    describe(cfg, params)
    reqs = requests(cfg, seed)
    sc = dict(batch_size=N_SLOTS, max_new_tokens=NEW_TOKENS,
              block_size=BLOCK, prefill_chunk=PREFILL_CHUNK)
    inp = prefill_inputs(eng, seed)

    ref, secs1, _ = serve(eng, reqs, ServeConfig(**sc))
    want, _ = last_logits(eng, params, inp, "pallas")
    log(f"one chip: {sum(o.size for o in ref)} tokens; smoke wall time "
        f"{secs1:.3f} s incl. compile")

    mesh = make_host_mesh()
    rep = NamedSharding(mesh, P())
    eng.params = params = jax.device_put(params, rep)
    outs, secs4, stats = serve(eng, reqs,
                               ServeConfig(num_shards=4, mesh=mesh, **sc))
    log(f"4 chips: {sum(o.size for o in outs)} tokens, placements "
        f"{stats['shard_placements']}, backend {stats['paged_backend']}; "
        f"smoke wall time {secs4:.3f} s incl. compile")
    with jax.set_mesh(mesh):
        got, _ = last_logits(eng, params, inp, "pallas",
                             place=lambda t: jax.device_put(t, rep))
    rows = {sh.device.id: (sh.index[0].start or 0, sh.data.shape[0])
            for sh in got.addressable_shards}
    log(f"prefill logits: sharding {got.sharding.spec}, rows (start, "
        f"count) by device {rows}")
    if sorted(rows.values()) != [(2 * i, 2) for i in range(4)]:
        raise AssertionError("the fused batch's logits rows do not split "
                             "over 4 devices")
    check_logits("4-chip vs 1-chip prefill dispatch", got, want)
    same = sum(bool(np.array_equal(a, b)) for a, b in zip(ref, outs))
    log(f"greedy streams equal to the one-chip run: {same}/{len(reqs)}")
    log(f"compile seconds {clock.secs:.3f}; peak device memory "
        f"{[peak_gib(d) for d in jax.devices()]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from repro import runtime
    from repro.configs.gemma_2b import CONFIG

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    log(f"compile cache: {runtime.enable_compile_cache()}")
    if args.chips == 4:
        four_chips(CONFIG, args.seed)
    else:
        one_chip(CONFIG, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
