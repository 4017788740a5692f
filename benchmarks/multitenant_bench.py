"""Multi-tenant serving benchmark: batching strategies and paged-cache wins.

Sections (CSV rows ``name,us_per_call,derived``; compile excluded by a
warmup call; CPU interpret-mode numbers — the wins are architectural):

  * default: tokens/s vs number of resident adapters, comparing
      - ``per-client``: the seed architecture — N single-tenant ``Engine``s,
        one adapter tree and one compiled program each;
      - ``batched``: one ``MultiTenantEngine`` + ``AdapterRegistry`` bank,
        one fixed-shape mixed-client batch (``generate_fixed``, PR-1).
  * ``--ragged`` (also default): a mixed-length mixed-budget request stream
    served by (a) the fixed-batch engine — requests grouped by prompt
    length, every group decoding its max budget (padding waste) — and (b)
    the continuous slot scheduler over the paged KV cache.  Writes
    ``BENCH_serving.json`` (tok/s, waste, speedup).
  * prefill (also default): a prompt-heavy ragged stream served with
    chunked multi-token prefill (``prefill_chunk=16``) vs the
    one-token-per-dispatch baseline (``prefill_chunk=1``) — same outputs,
    fraction of the prefill dispatches.  Appends a ``prefill`` section to
    ``BENCH_serving.json``.
  * prefix_cache (also default): a shared-prefix stream (per-client system
    prompts) served cold vs through the content-addressed warm pool
    (``ServeConfig.prefix_cache``) — bitwise-equal outputs, prompt tokens
    served from cached blocks instead of re-prefilled.  Appends a
    ``prefix_cache`` section (hit rates, prefill-compute reduction).
  * sla (also default): a contended priority-mix stream under
    ``sched_policy="sla"`` vs ``"fcfs"`` — identical greedy outputs, the
    interactive class finishing earlier under priority admission.  Appends
    an ``sla`` section (latency win, per-class wait stats).
  * spec (also default): a decode-bound repetitive stream with
    ``spec_decode`` on vs off — BITWISE-equal outputs, >=1.5x tok/s from
    prompt-lookup drafts verified through the chunked paged prefill path.
    Appends a ``spec`` section (speedup, acceptance, dispatch counts);
    ``--gate-only`` also times it for the
    ``benchmarks/baselines/serving_spec.json`` CI gate.
  * quant (also default): equal-HBM paged pools, ``kv_dtype="f32"`` vs
    ``"int8"`` — greedy-identical streams, the int8 pool holding 1.78x the
    blocks per byte (>=1.5x concurrent residents at the fixed budget, and
    never more preemptions on a pool-thrashing stream).  Appends a
    ``quant`` section; ``--gate-only`` records the deterministic residency
    number for the ``benchmarks/baselines/serving_quant.json`` CI gate.
  * ragged_rank (also default): the same mixed-client stream served from a
    bucketed mixed-rank adapter bank (clients at ranks 2/4/8,
    ``AdapterRegistry(ranks=[...])``) vs every slot padded to the max rank
    — BITWISE-equal outputs (zero rank columns are arithmetically inert),
    the win is adapter-bank HBM: rank-proportional bytes per slot.
    Appends a ``ragged_rank`` section; ``--gate-only`` records the
    deterministic bank-byte ratio for the
    ``benchmarks/baselines/serving_ragged.json`` CI gate.
  * smoke gate (also default): a fixed small continuous workload's tok/s,
    recorded as the ``smoke`` section — CI's
    ``scripts/check_bench_regression.py`` fails the PR when it regresses
    >25% against ``benchmarks/baselines/serving_smoke.json``.
  * shard (also default): a slot-saturated request stream served at
    ``num_shards=1`` vs ``2`` (sharded pool + sharded adapter bank, twice
    the resident slots riding the same fused dispatches) — bitwise-equal
    outputs, aggregate tok/s scaling recorded as the ``shard`` section;
    ``--gate-only`` also times it for the
    ``benchmarks/baselines/serving_shard.json`` CI gate.
  * trace (also default): an open-loop bursty trace (``serving/trace.py``)
    replayed through the streaming session, overlapped dispatch
    (``ServeConfig.overlap``) vs the synchronous per-round loop —
    bitwise-equal streams on the fixed trace, >=1.3x goodput OR p99 TTFT
    win measured realtime.  Appends a ``trace`` section (per-class
    TTFT/TPOT p50/p99, goodput); ``--gate-only`` records the
    ``trace.tok_per_s`` + ``trace.p99_ttft_ms`` pair for the
    ``benchmarks/baselines/serving_trace.json`` CI gate.
  * ``--trace-sweep``: multi-seed x arrival-regime sweep (poisson, bursty,
    heavy burst) in deterministic logical mode, async-vs-sync parity
    asserted per pair — the weekly deep CI job.
  * ``--block-sweep``: ``kernels/batched_lora.py`` tile-size sweep per
    (n_clients, rank) — groundwork for the ROADMAP autotuning item.
  * ``--smoke``: tiny correctness-only run for CI (serving-path regressions
    fail fast; parity + the smoke-gate throughput row only).

  Every non-sweep run also merges a ``section_walltimes`` key into the
  JSON so the uploaded CI artifact shows where the minutes went.

    PYTHONPATH=src python benchmarks/multitenant_bench.py
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from benchmarks.common import row, timed  # noqa: E402

from repro import runtime  # noqa: E402
from repro.configs.base import ModelConfig  # noqa: E402
from repro.core.lora import init_adapters  # noqa: E402
from repro.kernels.batched_lora import batched_lora_matmul  # noqa: E402
from repro.models.api import get_model  # noqa: E402
from repro.serving.engine import (Engine, MultiTenantEngine, Request,  # noqa: E402
                                  ServeConfig)
from repro.serving.kv_cache import kv_bytes_per_block  # noqa: E402
from repro.serving.registry import AdapterRegistry  # noqa: E402
from repro.serving.sharded import ShardedAdapterRegistry  # noqa: E402
from repro.serving.trace import run_trace, synth_trace  # noqa: E402

CFG = ModelConfig(
    name="mt-bench", family="dense", n_layers=2, d_model=128, n_heads=4,
    n_kv_heads=2, d_ff=256, vocab_size=300, max_seq_len=64, lora_rank=8,
    remat=False, param_dtype="float32", dtype="float32")

PROMPT_LEN = 8
NEW_TOKENS = 16
CACHE_LEN = 64


def _merge_json(json_path: str, updates: dict) -> None:
    """Merge section records into the bench JSON (sections accumulate —
    a smoke run must not clobber the committed full-run sections)."""
    record = {}
    if os.path.exists(json_path):
        with open(json_path) as f:
            record = json.load(f)
    record.update(updates)
    with open(json_path, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")


def _adapters(seed: int, cfg=CFG, rank=None):
    kw = {} if rank is None else {"rank": rank}
    ad = init_adapters(jax.random.PRNGKey(seed), cfg, **kw)
    bump = jax.random.PRNGKey(seed + 1000)
    return jax.tree.map(
        lambda l: l + 0.02 * jax.random.normal(bump, l.shape), ad)


def _setup(n_adapters: int, cfg=CFG):
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ads = {f"c{i}": _adapters(i + 1, cfg) for i in range(n_adapters)}
    registry = AdapterRegistry(cfg, capacity=max(n_adapters, 2))
    for cid, ad in ads.items():
        registry.register(cid, ad)
    return model, params, ads, MultiTenantEngine(model, cfg, params, registry)


# ---------------------------------------------------------------------------
# Fixed-shape sections (PR-1): per-client engines vs one batched engine
# ---------------------------------------------------------------------------

def fixed_shape_sections():
    prompt = (np.arange(PROMPT_LEN, dtype=np.int32) * 7) % CFG.vocab_size
    sc = ServeConfig(batch_size=1, max_new_tokens=NEW_TOKENS,
                     cache_len=CACHE_LEN)
    for n_adapters in (2, 4, 8):
        model, params, ads, mt = _setup(n_adapters)
        total_tokens = n_adapters * NEW_TOKENS

        engines = [Engine(model, CFG, params, ad) for ad in ads.values()]
        p1 = jnp.asarray(prompt)[None]

        def per_client():
            return [eng.generate(p1, sc) for eng in engines]

        _, us_base = timed(per_client)
        tps_base = total_tokens / (us_base / 1e6)
        print(row(f"per_client_engines_n{n_adapters}", us_base,
                  f"{tps_base:.1f}"))

        reqs = [Request(cid, prompt) for cid in ads]

        def batched():
            return mt.generate_fixed(reqs, sc)

        out_mt, us_mt = timed(batched)
        tps_mt = total_tokens / (us_mt / 1e6)
        print(row(f"batched_bank_n{n_adapters}", us_mt, f"{tps_mt:.1f}"))
        print(row(f"speedup_n{n_adapters}", us_base / us_mt * 100,
                  f"{tps_mt / tps_base:.2f}x"))

        # sanity: the batched rows must equal per-client generations
        base_out = per_client()
        ok = all(bool((np.asarray(out_mt)[i] == np.asarray(o)[0]).all())
                 for i, o in enumerate(base_out))
        assert ok, "batched engine diverged from per-client baseline"


# ---------------------------------------------------------------------------
# Ragged workload: fixed-batch grouping vs continuous batching (tentpole)
# ---------------------------------------------------------------------------

def _ragged_workload(n_clients: int = 4):
    """Mixed prompt lengths x mixed budgets x mixed clients: the stream the
    fixed-shape engine can only serve by grouping + over-decoding."""
    reqs = []
    lens = (4, 8, 12)
    budgets = (4, 12, 28)
    i = 0
    for plen in lens:
        for b in budgets:
            prompt = (np.arange(plen, dtype=np.int32) * 5 + i) % CFG.vocab_size
            reqs.append(Request(f"c{i % n_clients}", prompt,
                                max_new_tokens=int(b)))
            i += 1
    return reqs


def ragged_section(json_path: str, smoke: bool = False):
    n_clients = 2 if smoke else 4
    model, params, ads, mt = _setup(n_clients)
    reqs = _ragged_workload(n_clients)
    if smoke:
        reqs = reqs[:4]
    useful = sum(r.max_new_tokens for r in reqs)

    # -- fixed-batch (PR-1): group by prompt length, decode each group to
    #    its max budget — finished rows keep burning decode steps ----------
    groups = {}
    for r in reqs:
        groups.setdefault(len(r.prompt), []).append(r)

    def fixed():
        outs = {}
        for plen, grp in sorted(groups.items()):
            sc = ServeConfig(batch_size=len(grp),
                             max_new_tokens=max(g.max_new_tokens for g in grp),
                             cache_len=CACHE_LEN)
            o = mt.generate_fixed(grp, sc)
            for g, row_ in zip(grp, np.asarray(o)):
                outs[id(g)] = row_
        return outs

    decoded = sum(len(grp) * max(g.max_new_tokens for g in grp)
                  for grp in groups.values())
    waste = 1.0 - useful / decoded

    # -- continuous: one slot-based engine over the paged KV cache ---------
    sc_cont = ServeConfig(batch_size=4, max_new_tokens=NEW_TOKENS,
                          block_size=8)

    def continuous():
        return mt.generate(reqs, sc_cont)

    if smoke:
        fixed_out, cont_out = fixed(), continuous()
        for r, o in zip(reqs, cont_out):    # parity: continuous == fixed-path
            np.testing.assert_array_equal(o, fixed_out[id(r)][:o.size])
        print(row("ragged_smoke_parity", 0.0, "ok"))
        return

    fixed_out, us_fixed = timed(fixed)
    cont_out, us_cont = timed(continuous)
    for r, o in zip(reqs, cont_out):        # parity before trusting timings
        np.testing.assert_array_equal(o, fixed_out[id(r)][:o.size])

    tps_fixed = useful / (us_fixed / 1e6)
    tps_cont = useful / (us_cont / 1e6)
    print(row("ragged_fixed_batch", us_fixed,
              f"{tps_fixed:.1f} tok/s, {waste:.1%} padding waste"))
    print(row("ragged_continuous", us_cont,
              f"{tps_cont:.1f} tok/s, 0.0% padding waste"))
    print(row("ragged_speedup", us_fixed / us_cont * 100,
              f"{tps_cont / tps_fixed:.2f}x"))
    _merge_json(json_path, {
        "workload": {"requests": len(reqs),
                     "useful_tokens": useful,
                     "num_shards": sc_cont.num_shards,
                     "prompt_lens": sorted({len(r.prompt) for r in reqs}),
                     "budgets": sorted({r.max_new_tokens for r in reqs})},
        "fixed_batch": {"us_per_call": us_fixed, "tok_per_s": tps_fixed,
                        "decoded_tokens": decoded, "padding_waste": waste},
        "continuous": {"us_per_call": us_cont, "tok_per_s": tps_cont,
                       "decoded_tokens": useful, "padding_waste": 0.0,
                       "slots": sc_cont.batch_size,
                       "block_size": sc_cont.block_size},
        "speedup": tps_cont / tps_fixed,
        "note": "CPU interpret-mode; win = fewer decode dispatches "
                "(no over-decoding, no per-length grouping)",
    })
    print(f"# wrote {json_path}")


# ---------------------------------------------------------------------------
# Chunked prefill: dispatches per prompt token vs per prompt CHUNK
# ---------------------------------------------------------------------------

def prefill_section(json_path: str, smoke: bool = False):
    """Prompt-heavy ragged stream through the continuous engine, chunked
    prefill (prefill_chunk=16) vs the one-token-per-dispatch baseline
    (prefill_chunk=1 drives the same machinery one prompt token at a time).
    Outputs must be identical; the win is the prefill-phase dispatch count
    (and wall time once prompts dominate)."""
    n_clients = 2
    model, params, ads, mt = _setup(n_clients)
    plens = (24, 40) if smoke else (24, 40, 64, 32, 48, 56)
    reqs = []
    for i, plen in enumerate(plens):
        prompt = (np.arange(plen, dtype=np.int32) * 5 + i) % CFG.vocab_size
        reqs.append(Request(f"c{i % n_clients}", prompt, max_new_tokens=4))
    prompt_tokens = sum(len(r.prompt) for r in reqs)

    sc_chunk = ServeConfig(batch_size=4, max_new_tokens=4, block_size=8,
                           prefill_chunk=16)
    sc_token = dataclasses.replace(sc_chunk, prefill_chunk=1)

    out_c = mt.generate(reqs, sc_chunk)
    st_c = dict(mt.last_stats)
    out_t = mt.generate(reqs, sc_token)
    st_t = dict(mt.last_stats)
    for a, b in zip(out_c, out_t):            # parity before trusting counts
        np.testing.assert_array_equal(a, b)

    reduction = st_t["prefill_dispatches"] / st_c["prefill_dispatches"]
    print(row("prefill_dispatches_per_token", 0.0,
              f"{st_t['prefill_dispatches']}"))
    print(row("prefill_dispatches_chunked", 0.0,
              f"{st_c['prefill_dispatches']}"))
    print(row("prefill_dispatch_reduction", 0.0, f"{reduction:.2f}x"))
    assert reduction >= 2.0, \
        f"chunked prefill must cut dispatches >=2x (got {reduction:.2f}x)"
    if smoke:
        print(row("prefill_smoke_parity", 0.0, "ok"))
        return

    _, us_c = timed(lambda: mt.generate(reqs, sc_chunk))
    _, us_t = timed(lambda: mt.generate(reqs, sc_token))
    print(row("prefill_chunked", us_c, f"chunk=16"))
    print(row("prefill_per_token", us_t, f"chunk=1"))
    print(row("prefill_walltime_speedup", us_t / us_c * 100,
              f"{us_t / us_c:.2f}x"))

    _merge_json(json_path, {"prefill": {
        "workload": {"requests": len(reqs), "prompt_tokens": prompt_tokens,
                     "prompt_lens": sorted(plens), "budget": 4,
                     "slots": sc_chunk.batch_size,
                     "num_shards": sc_chunk.num_shards,
                     "block_size": sc_chunk.block_size},
        "per_token": {"prefill_dispatches": st_t["prefill_dispatches"],
                      "us_per_call": us_t},
        "chunked": {"prefill_chunk": sc_chunk.prefill_chunk,
                    "prefill_dispatches": st_c["prefill_dispatches"],
                    "us_per_call": us_c},
        "dispatch_reduction": reduction,
        "walltime_speedup": us_t / us_c,
        "note": "CPU interpret-mode; chunked paged prefill consumes a whole "
                "prompt chunk per dispatch (kernels/paged_prefill.py)",
    }})
    print(f"# wrote {json_path} (prefill section)")


# ---------------------------------------------------------------------------
# Prefix caching: shared-prefix streams skip re-prefill (cold vs warm)
# ---------------------------------------------------------------------------

def prefix_cache_section(json_path: str, smoke: bool = False):
    """Shared-prefix request stream (per-client system prompts) through the
    continuous engine, cold pool vs content-addressed warm pool
    (``prefix_cache=True``).  Outputs must be bitwise-identical; the win is
    the prefill COMPUTE reduction — prompt tokens actually prefilled vs
    served from cached blocks — plus the dispatch count once the pool is
    warm across calls."""
    n_req = 4 if smoke else 8
    model, params, ads, mt = _setup(2)
    prefixes = {f"c{i}": (np.arange(24, dtype=np.int32) * 7 + i)
                % CFG.vocab_size for i in range(2)}
    reqs = []
    for i in range(n_req):
        cid = f"c{i % 2}"
        suffix = (np.arange(8, dtype=np.int32) * 11 + 3 * i) % CFG.vocab_size
        reqs.append(Request(cid, np.concatenate([prefixes[cid], suffix]),
                            max_new_tokens=4))
    sc_cold = ServeConfig(batch_size=4, max_new_tokens=4, block_size=8,
                          prefill_chunk=8)
    # pinned pool => stable geometry => the warm pool survives any batch
    # shape (the recommended cross-call configuration)
    sc_warm = dataclasses.replace(sc_cold, prefix_cache=True, num_blocks=25)

    out_cold = mt.generate(reqs, sc_cold)
    st_cold = dict(mt.last_stats)
    out_w1 = mt.generate(reqs, sc_warm)            # intra-call sharing
    st_w1 = dict(mt.last_stats)
    out_w2 = mt.generate(reqs, sc_warm)            # cross-call re-match
    st_w2 = dict(mt.last_stats)
    for a, b, c in zip(out_cold, out_w1, out_w2):  # parity before metrics
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)

    prefilled_cold = st_cold["prompt_tokens"] - st_cold["prefix_hit_tokens"]
    prefilled_warm = st_w2["prompt_tokens"] - st_w2["prefix_hit_tokens"]
    reduction = prefilled_cold / max(1, prefilled_warm)
    print(row("prefix_hit_rate_intra_call", 0.0,
              f"{st_w1['prefix_hit_rate']:.1%}"))
    print(row("prefix_hit_rate_cross_call", 0.0,
              f"{st_w2['prefix_hit_rate']:.1%}"))
    print(row("prefix_prefill_compute_reduction", 0.0, f"{reduction:.2f}x"))
    assert st_w2["prefix_hit_rate"] > 0.5, \
        f"warm shared-prefix stream must re-match >50% of prompt tokens " \
        f"(got {st_w2['prefix_hit_rate']:.1%})"
    assert reduction >= 2.0, \
        f"prefix cache must cut prefill compute >=2x (got {reduction:.2f}x)"
    if smoke:
        print(row("prefix_smoke_parity", 0.0, "ok"))
        return

    _, us_cold = timed(lambda: mt.generate(reqs, sc_cold))
    _, us_warm = timed(lambda: mt.generate(reqs, sc_warm))
    print(row("prefix_cold", us_cold, "prefix_cache=off"))
    print(row("prefix_warm", us_warm, "prefix_cache=on (pool stays warm)"))
    print(row("prefix_walltime_speedup", us_cold / us_warm * 100,
              f"{us_cold / us_warm:.2f}x"))
    _merge_json(json_path, {"prefix_cache": {
        "workload": {"requests": len(reqs), "prefix_len": 24,
                     "suffix_len": 8, "budget": 4, "clients": 2,
                     "slots": sc_cold.batch_size,
                     "num_shards": sc_cold.num_shards,
                     "block_size": sc_cold.block_size},
        "cold": {"prefilled_tokens": prefilled_cold,
                 "prefill_dispatches": st_cold["prefill_dispatches"],
                 "us_per_call": us_cold},
        "warm": {"prefilled_tokens": prefilled_warm,
                 "prefill_dispatches": st_w2["prefill_dispatches"],
                 "hit_rate_intra_call": st_w1["prefix_hit_rate"],
                 "hit_rate_cross_call": st_w2["prefix_hit_rate"],
                 "us_per_call": us_warm},
        "prefill_compute_reduction": reduction,
        "walltime_speedup": us_cold / us_warm,
        "note": "CPU interpret-mode; bitwise-equal outputs — cached blocks "
                "are re-matched by chained content hash per client scope "
                "(serving/kv_cache.py)",
    }})
    print(f"# wrote {json_path} (prefix_cache section)")


# ---------------------------------------------------------------------------
# SLA scheduling: interactive-class latency under priority admission vs FCFS
# ---------------------------------------------------------------------------

def sla_section(json_path: str, smoke: bool = False):
    """A contended stream (batch requests queued ahead of late-arriving
    interactive ones, fewer slots than requests) served under
    ``sched_policy="sla"`` vs ``"fcfs"``.  Outputs must be identical
    (greedy decoding is schedule-invariant — the parity the serving tests
    pin down); the win is interactive-class completion latency, measured
    in stream events (logical time, immune to CPU jitter) and wall time."""
    model, params, ads, mt = _setup(2)

    def _p(n, s):
        return (np.arange(n, dtype=np.int32) * 5 + s) % CFG.vocab_size

    n_batch = 4 if smoke else 8
    reqs = [Request(f"c{i % 2}", _p(10, i), max_new_tokens=10,
                    priority="batch") for i in range(n_batch)]
    # interactive requests arrive LAST in submission order — under FCFS
    # they wait out the whole batch backlog
    reqs += [Request(f"c{i % 2}", _p(6, 50 + i), max_new_tokens=4,
                     priority="interactive") for i in range(3)]
    inter = [rid for rid, r in enumerate(reqs) if r.priority == "interactive"]
    sc = ServeConfig(batch_size=2, max_new_tokens=10, block_size=8,
                     prefill_chunk=8)

    def run(policy):
        finish, outs, t = {}, {i: [] for i in range(len(reqs))}, 0
        stream = mt.generate_stream(
            reqs, dataclasses.replace(sc, sched_policy=policy))
        for rid, toks, fin in stream:
            t += 1
            outs[rid].extend(toks)
            if fin:
                finish[rid] = t
        return finish, outs, dict(mt.last_stats)

    fin_sla, out_sla, st_sla = run("sla")
    fin_fcfs, out_fcfs, st_fcfs = run("fcfs")
    for i in range(len(reqs)):                 # parity before trusting stats
        np.testing.assert_array_equal(np.asarray(out_sla[i], np.int32),
                                      np.asarray(out_fcfs[i], np.int32))

    lat_sla = float(np.mean([fin_sla[r] for r in inter]))
    lat_fcfs = float(np.mean([fin_fcfs[r] for r in inter]))
    win = lat_fcfs / lat_sla
    print(row("sla_interactive_finish_events", 0.0, f"{lat_sla:.1f}"))
    print(row("fcfs_interactive_finish_events", 0.0, f"{lat_fcfs:.1f}"))
    print(row("sla_interactive_latency_win", 0.0, f"{win:.2f}x"))
    assert win > 1.0, \
        f"priority admission must cut interactive latency (got {win:.2f}x)"
    if smoke:
        print(row("sla_smoke_parity", 0.0, "ok"))
        return

    _, us_sla = timed(lambda: mt.generate(reqs, sc))
    _, us_fcfs = timed(lambda: mt.generate(
        reqs, dataclasses.replace(sc, sched_policy="fcfs")))
    _merge_json(json_path, {"sla": {
        "workload": {"batch_requests": n_batch, "interactive_requests": 3,
                     "slots": sc.batch_size, "budget_batch": 10,
                     "num_shards": sc.num_shards,
                     "budget_interactive": 4},
        "interactive_mean_finish_events": {"sla": lat_sla, "fcfs": lat_fcfs},
        "interactive_latency_win": win,
        "classes_sla": st_sla["classes"],
        "classes_fcfs": st_fcfs["classes"],
        "us_per_call": {"sla": us_sla, "fcfs": us_fcfs},
        "note": "identical greedy outputs; win = priority-queue admission "
                "with aging (serving/scheduler.py) letting interactive "
                "requests jump the batch backlog; latency in stream events "
                "(logical time) to dodge CPU jitter",
    }})
    print(f"# wrote {json_path} (sla section)")


# ---------------------------------------------------------------------------
# Speculative decoding: decode-bound repetitive stream, spec vs plain greedy
# ---------------------------------------------------------------------------

def _spec_workload(mt, sc, n_req: int = 6):
    """Decode-bound repetitive stream: prompt seeds whose greedy
    continuation settles into a cycle on the bench model, each extended by
    its own first 16 greedy tokens — the timed region then starts inside
    the repetitive regime and the prompt already contains the runs the
    prompt-lookup drafter matches against (continuing a repetitive
    document: the workload speculation targets)."""
    seeds = ([5, 6] * 4)[:n_req]
    warm = [Request(f"c{s % 2}",       # cycle quality is adapter-specific
                    np.tile((np.arange(4, dtype=np.int32) * 9 + s)
                            % CFG.vocab_size, 2).astype(np.int32),
                    max_new_tokens=16)
            for s in seeds]
    outs = mt.generate(warm, dataclasses.replace(sc, spec_decode=False))
    return [Request(r.client_id,
                    np.concatenate([r.prompt, np.asarray(o, np.int32)]),
                    max_new_tokens=40)
            for r, o in zip(warm, outs)]


def _best_us(fn, repeats: int = 5) -> float:
    """Best-of-N wall time in us (see smoke_gate_section on why best)."""
    import time as _time
    fn()                                           # warmup/compile
    us = float("inf")
    for _ in range(repeats):
        t0 = _time.perf_counter()
        fn()
        us = min(us, (_time.perf_counter() - t0) * 1e6)
    return us


def spec_section(json_path: str, smoke: bool = False):
    """Draft-then-verify greedy decoding (``ServeConfig.spec_decode``) vs
    plain chunked decode on a decode-bound repetitive workload.  Outputs
    must be BITWISE equal (speculation changes when tokens are computed,
    never which); the win is model evaluations per emitted token — one
    verify dispatch scores up to spec_k+1 positions in a single eval."""
    n_req = 4 if smoke else 8
    model, params, ads, mt = _setup(2)
    sc = ServeConfig(batch_size=8, max_new_tokens=40, block_size=8)
    sc_spec = dataclasses.replace(sc, spec_decode=True, spec_k=8)
    reqs = _spec_workload(mt, sc, n_req)
    useful = sum(r.max_new_tokens for r in reqs)

    out_base = mt.generate(reqs, sc)
    out_spec = mt.generate(reqs, sc_spec)
    st = dict(mt.last_stats)
    for a, b in zip(out_base, out_spec):           # parity before timings
        np.testing.assert_array_equal(a, b)
    print(row("spec_acceptance_rate", 0.0, f"{st['acceptance_rate']:.1%}"))
    print(row("spec_verify_dispatches", 0.0, f"{st['verify_dispatches']}"))
    assert st["acceptance_rate"] > 0.5, \
        f"repetitive stream must accept >50% of drafts " \
        f"(got {st['acceptance_rate']:.1%})"
    if smoke:
        print(row("spec_smoke_parity", 0.0, "ok"))
        return

    us_base = _best_us(lambda: mt.generate(reqs, sc))
    us_spec = _best_us(lambda: mt.generate(reqs, sc_spec))
    tps_base = useful / (us_base / 1e6)
    tps_spec = useful / (us_spec / 1e6)
    speedup = us_base / us_spec
    print(row("spec_decode_off", us_base, f"{tps_base:.1f} tok/s"))
    print(row("spec_decode_on", us_spec, f"{tps_spec:.1f} tok/s"))
    print(row("spec_speedup", 0.0, f"{speedup:.2f}x"))
    assert speedup >= 1.5, \
        f"speculation must win >=1.5x on the decode-bound repetitive " \
        f"workload (got {speedup:.2f}x)"
    _merge_json(json_path, {"spec": {
        "workload": {"requests": n_req, "prompt_len": 24, "budget": 40,
                     "useful_tokens": useful, "slots": sc.batch_size,
                     "num_shards": sc.num_shards,
                     "block_size": sc.block_size},
        "tok_per_s": tps_spec, "base_tok_per_s": tps_base,
        "us_per_call": us_spec, "base_us_per_call": us_base,
        "speedup": speedup, "spec_k": sc_spec.spec_k,
        "acceptance_rate": st["acceptance_rate"],
        "verify_dispatches": st["verify_dispatches"],
        "drafted_tokens": st["drafted_tokens"],
        "accepted_tokens": st["accepted_tokens"],
        "rollback_tokens": st["rollback_tokens"],
        "note": "CPU interpret-mode; bitwise-equal greedy streams — win = "
                "fewer model evaluations per token (prompt-lookup drafts "
                "verified through the chunked paged prefill path)",
    }})
    print(f"# wrote {json_path} (spec section)")


def spec_gate_section(json_path: str):
    """Speculative throughput floor for CI: the spec workload's tok/s,
    gated against ``benchmarks/baselines/serving_spec.json`` (best-of-5,
    same rationale as :func:`smoke_gate_section`; parity runs in
    serving-smoke)."""
    model, params, ads, mt = _setup(2)
    sc_spec = ServeConfig(batch_size=8, max_new_tokens=40, block_size=8,
                          spec_decode=True, spec_k=8)
    reqs = _spec_workload(mt, ServeConfig(batch_size=8, max_new_tokens=40,
                                          block_size=8), 8)
    useful = sum(r.max_new_tokens for r in reqs)
    us = _best_us(lambda: mt.generate(reqs, sc_spec))
    tps = useful / (us / 1e6)
    print(row("spec_gate", us, f"{tps:.1f} tok/s"))
    _merge_json(json_path, {"spec": {
        "tok_per_s": tps, "us_per_call": us, "useful_tokens": useful,
        "requests": len(reqs), "slots": sc_spec.batch_size,
        "spec_k": sc_spec.spec_k, "num_shards": sc_spec.num_shards,
        "note": "speculative-decoding smoke throughput; gated by "
                "scripts/check_bench_regression.py in CI",
    }})
    print(f"# wrote {json_path} (spec gate section)")


# ---------------------------------------------------------------------------
# Smoke throughput floor: the number scripts/check_bench_regression.py gates
# ---------------------------------------------------------------------------

def smoke_gate_section(json_path: str):
    """Small fixed continuous-batching workload; CI fails if tok/s
    regresses >25% against the committed baseline
    (``benchmarks/baselines/serving_smoke.json``).  BEST-of-N timing (min
    wall time over separate calls): shared runners and this container both
    jitter 2x run-to-run, and the fastest call is the least contended —
    the mean would gate on scheduler noise, the best gates on the code."""
    import time as _time
    model, params, ads, mt = _setup(2)
    reqs = _ragged_workload(2)[:6]
    useful = sum(r.max_new_tokens for r in reqs)
    sc = ServeConfig(batch_size=4, max_new_tokens=NEW_TOKENS, block_size=8)
    mt.generate(reqs, sc)                          # warmup/compile
    us = float("inf")
    for _ in range(5):
        t0 = _time.perf_counter()
        mt.generate(reqs, sc)
        us = min(us, (_time.perf_counter() - t0) * 1e6)
    tps = useful / (us / 1e6)
    print(row("smoke_gate", us, f"{tps:.1f} tok/s"))
    _merge_json(json_path, {"smoke": {
        "tok_per_s": tps, "us_per_call": us, "useful_tokens": useful,
        "requests": len(reqs), "slots": sc.batch_size,
        "num_shards": sc.num_shards,
        "note": "continuous-batching smoke throughput; gated by "
                "scripts/check_bench_regression.py in CI",
    }})
    print(f"# wrote {json_path} (smoke section)")


# ---------------------------------------------------------------------------
# Sharded serving: aggregate throughput scaling vs shard count
# ---------------------------------------------------------------------------

# The shard section's own config: the scaling it measures is DISPATCH
# amortization (extra shards ride the same fused rounds), so the model is
# kept small enough that per-dispatch overhead — not per-row FLOPs — is
# the serving bottleneck (the regime of latency-mode online serving).
SHARD_CFG = dataclasses.replace(CFG, name="mt-shard", d_model=64, d_ff=128)


def _shard_setup():
    """Engine over a 2-way ShardedAdapterRegistry (4 tenants resident, 2
    homed per shard) — serves both shard counts: at ``num_shards=1`` the
    engine runs the single-pool path against the same concatenated bank."""
    model = get_model(SHARD_CFG)
    params = model.init(jax.random.PRNGKey(0))
    reg = ShardedAdapterRegistry(SHARD_CFG, capacity=8, num_shards=2)
    for i in range(4):
        reg.register(f"c{i}", _adapters(i + 1, SHARD_CFG))
    return reg, MultiTenantEngine(model, SHARD_CFG, params, reg)


def _shard_workload(n_req: int):
    """Slot-saturated mixed-client stream: many more requests than slots
    at either shard count, uniform spans so admission waves and
    completions stay aligned (rounds halve exactly at 2 shards)."""
    reqs = []
    for i in range(n_req):
        prompt = ((np.arange(8, dtype=np.int32) * 5 + i)
                  % SHARD_CFG.vocab_size)
        reqs.append(Request(f"c{i % 4}", prompt, max_new_tokens=12))
    return reqs


def shard_section(json_path: str, smoke: bool = False):
    """``num_shards=1`` (2 slots) vs ``num_shards=2`` (4 slots, 2 per
    shard) on a slot-saturated stream in latency-mode serving
    (``scan_chunk=1``: admission between every token).  Outputs must be
    bitwise-identical (placement re-orders nothing greedy decoding can
    see); the win is aggregate tok/s — the second shard's slots ride the
    SAME fused dispatches, so the dispatch-bound stream completes in half
    the rounds."""
    reg, mt = _shard_setup()
    reqs = _shard_workload(8 if smoke else 16)
    useful = sum(r.max_new_tokens for r in reqs)
    sc1 = ServeConfig(batch_size=2, max_new_tokens=12, block_size=8,
                      scan_chunk=1, num_shards=1)
    sc2 = dataclasses.replace(sc1, batch_size=4, num_shards=2)

    out1 = mt.generate(reqs, sc1)
    out2 = mt.generate(reqs, sc2)
    st2 = dict(mt.last_stats)
    for a, b in zip(out1, out2):               # parity before trusting times
        np.testing.assert_array_equal(a, b)
    assert st2["num_shards"] == 2
    print(row("shard_placements", 0.0, str(st2["shard_placements"])))
    if smoke:
        print(row("shard_smoke_parity", 0.0, "ok"))
        return

    # Interleave the timed passes so slow machine drift (thermal, noisy
    # neighbours) hits both configs equally instead of biasing the ratio.
    import time as _time
    us1 = us2 = float("inf")
    for _ in range(7):
        t0 = _time.perf_counter()
        mt.generate(reqs, sc1)
        us1 = min(us1, (_time.perf_counter() - t0) * 1e6)
        t0 = _time.perf_counter()
        mt.generate(reqs, sc2)
        us2 = min(us2, (_time.perf_counter() - t0) * 1e6)
    tps1 = useful / (us1 / 1e6)
    tps2 = useful / (us2 / 1e6)
    scaling = tps2 / tps1
    print(row("shard_1", us1, f"{tps1:.1f} tok/s, 2 slots"))
    print(row("shard_2", us2, f"{tps2:.1f} tok/s, 4 slots (2/shard)"))
    print(row("shard_scaling", 0.0, f"{scaling:.2f}x"))
    assert scaling > 1.5, \
        f"2 shards must scale aggregate tok/s >1.5x on a slot-saturated " \
        f"stream (got {scaling:.2f}x)"
    _merge_json(json_path, {"shard": {
        "workload": {"requests": len(reqs), "useful_tokens": useful,
                     "prompt_len": 8, "budget": 12, "clients": 4,
                     "scan_chunk": sc1.scan_chunk,
                     "block_size": sc1.block_size},
        "num_shards": sc2.num_shards,
        "one_shard": {"tok_per_s": tps1, "us_per_call": us1,
                      "slots": sc1.batch_size},
        "two_shards": {"tok_per_s": tps2, "us_per_call": us2,
                       "slots": sc2.batch_size,
                       "placements": st2["shard_placements"]},
        "tok_per_s": tps2, "scaling": scaling,
        "resident_tenants": len(reg),
        "tenants_per_shard": reg.capacity_per_shard,
        "note": "CPU interpret-mode; bitwise-equal outputs — win = the "
                "second shard's slots riding the same fused dispatches "
                "(serving/sharded.py), halving rounds on a dispatch-bound "
                "stream",
    }})
    print(f"# wrote {json_path} (shard section)")


def shard_gate_section(json_path: str):
    """Sharded throughput floor for CI: the 2-shard slot-saturated
    workload's tok/s, gated against
    ``benchmarks/baselines/serving_shard.json`` (best-of-5; parity and
    scaling assertions run in serving-smoke / the full bench)."""
    _, mt = _shard_setup()
    reqs = _shard_workload(16)
    useful = sum(r.max_new_tokens for r in reqs)
    sc2 = ServeConfig(batch_size=4, max_new_tokens=12, block_size=8,
                      scan_chunk=1, num_shards=2)
    us = _best_us(lambda: mt.generate(reqs, sc2))
    tps = useful / (us / 1e6)
    print(row("shard_gate", us, f"{tps:.1f} tok/s"))
    _merge_json(json_path, {"shard": {
        "tok_per_s": tps, "us_per_call": us, "useful_tokens": useful,
        "requests": len(reqs), "slots": sc2.batch_size,
        "num_shards": sc2.num_shards,
        "note": "2-shard smoke throughput; gated by "
                "scripts/check_bench_regression.py in CI",
    }})
    print(f"# wrote {json_path} (shard gate section)")


# ---------------------------------------------------------------------------
# Quantized KV pools: concurrent residency per HBM byte (int8 vs f32)
# ---------------------------------------------------------------------------

def _quant_capacity(block_size: int = 8, span: int = 40):
    """Static capacity math at a fixed HBM budget: how many requests of
    ``span`` tokens can hold their whole KV residently, f32 pool vs int8
    pool of the same byte cost (``kv_bytes_per_block`` prices one block of
    one layer -- the ratio is layer-count invariant)."""
    hd = CFG.d_model // CFG.n_heads
    by_f32 = kv_bytes_per_block(block_size, CFG.n_kv_heads, hd, "f32")
    by_i8 = kv_bytes_per_block(block_size, CFG.n_kv_heads, hd, "int8")
    budget = 12 * by_f32                       # a 12-block f32 pool's HBM
    blocks_f32 = budget // by_f32
    blocks_i8 = budget // by_i8
    per_req = -(-span // block_size)
    return {"block_size": block_size, "span": span,
            "hbm_budget_bytes_per_layer": budget,
            "bytes_per_block": {"f32": by_f32, "int8": by_i8},
            "blocks": {"f32": int(blocks_f32), "int8": int(blocks_i8)},
            "capacity_ratio": by_f32 / by_i8,
            "concurrent_residents": {"f32": int(blocks_f32 // per_req),
                                     "int8": int(blocks_i8 // per_req)}}


def quant_section(json_path: str, smoke: bool = False):
    """``ServeConfig(kv_dtype="int8")``: paged K/V blocks stored int8 with
    per-(block, position, kv-head) scales — 36 vs 64 bytes per token per
    kv-head (1.78x blocks per HBM byte).  The quantized path is ERROR-
    BOUND, not bitwise: tests/test_quant.py pins kernel-level tolerances
    and greedy-stream equality on the smoke model; on this larger bench
    model an occasional argmax flip is expected and greedy compounds, so
    the section asserts structural parity (every request decodes its full
    budget) and reports token agreement informationally.  The win is
    residency — at the SAME pool byte budget the int8 engine preempts
    less (or not at all) on a stream that thrashes the f32 pool."""
    model, params, ads, mt = _setup(4)
    reqs = _ragged_workload(4)
    if smoke:
        reqs = reqs[:4]
    cap = _quant_capacity()
    print(row("quant_bytes_per_block_f32", 0.0,
              str(cap["bytes_per_block"]["f32"])))
    print(row("quant_bytes_per_block_int8", 0.0,
              str(cap["bytes_per_block"]["int8"])))
    print(row("quant_capacity_ratio", 0.0,
              f"{cap['capacity_ratio']:.2f}x"))
    print(row("quant_concurrent_residents", 0.0,
              f"f32={cap['concurrent_residents']['f32']} "
              f"int8={cap['concurrent_residents']['int8']}"))
    assert cap["capacity_ratio"] >= 1.5, \
        f"int8 pool must hold >=1.5x blocks per HBM byte " \
        f"(got {cap['capacity_ratio']:.2f}x)"

    # equal-HBM pools (+1 for the scratch block): the f32 pool is sized to
    # thrash under this stream, the int8 pool gets the blocks the same
    # bytes buy
    sc_f32 = ServeConfig(batch_size=4, max_new_tokens=NEW_TOKENS,
                         block_size=8, num_blocks=cap["blocks"]["f32"] + 1)
    sc_i8 = dataclasses.replace(sc_f32, kv_dtype="int8",
                                num_blocks=cap["blocks"]["int8"] + 1)
    out_f = mt.generate(reqs, sc_f32)
    st_f = dict(mt.last_stats)
    out_q = mt.generate(reqs, sc_i8)
    st_q = dict(mt.last_stats)
    agree = total = 0
    for r, a, b in zip(reqs, out_f, out_q):
        # structural parity: spans (and thus pool pressure) are identical,
        # so the preemption comparison below is apples-to-apples
        assert len(a) == len(b) == r.max_new_tokens
        agree += int((np.asarray(a) == np.asarray(b)).sum())
        total += len(a)
    print(row("quant_token_agreement", 0.0, f"{agree / total:.1%}"))
    print(row("quant_preemptions_f32_pool", 0.0, str(st_f["preemptions"])))
    print(row("quant_preemptions_int8_pool", 0.0, str(st_q["preemptions"])))
    assert st_q["preemptions"] <= st_f["preemptions"], \
        "the int8 pool must not preempt MORE than the f32 pool it " \
        "out-capacitates at the same HBM budget"
    if smoke:
        print(row("quant_smoke_parity", 0.0, "ok"))
        return

    us_f = _best_us(lambda: mt.generate(reqs, sc_f32))
    us_q = _best_us(lambda: mt.generate(reqs, sc_i8))
    useful = sum(r.max_new_tokens for r in reqs)
    print(row("quant_f32_pool", us_f, f"{useful / (us_f / 1e6):.1f} tok/s"))
    print(row("quant_int8_pool", us_q, f"{useful / (us_q / 1e6):.1f} tok/s"))
    _merge_json(json_path, {"quant": {
        **cap,
        "workload": {"requests": len(reqs), "useful_tokens": useful,
                     "slots": sc_f32.batch_size,
                     "num_shards": sc_f32.num_shards},
        "preemptions": {"f32": st_f["preemptions"],
                        "int8": st_q["preemptions"]},
        "token_agreement": agree / total,
        "us_per_call": {"f32": us_f, "int8": us_q},
        "note": "CPU interpret-mode; error-bound (not bitwise) vs f32 — "
                "smoke-model stream equality pinned in tests/test_quant.py "
                "— win = 1.78x paged blocks per HBM byte (kernels/quant.py, "
                "dequant inside the Pallas kernels)",
    }})
    print(f"# wrote {json_path} (quant section)")


def quant_gate_section(json_path: str):
    """Residency floor for CI: concurrent int8 residents at the fixed HBM
    budget, gated against ``benchmarks/baselines/serving_quant.json``.
    Pure capacity math — deterministic, immune to runner jitter; the
    parity + preemption assertions run in serving-smoke."""
    cap = _quant_capacity()
    print(row("quant_gate", 0.0,
              f"{cap['concurrent_residents']['int8']} residents "
              f"({cap['capacity_ratio']:.2f}x blocks/byte)"))
    _merge_json(json_path, {"quant": {
        **cap,
        "note": "int8 KV residency at fixed HBM; gated by "
                "scripts/check_bench_regression.py in CI",
    }})
    print(f"# wrote {json_path} (quant gate section)")


# ---------------------------------------------------------------------------
# Ragged-rank adapter banks: mixed-rank buckets vs pad-to-max (HBM win)
# ---------------------------------------------------------------------------

RAGGED_RANKS = (2, 4, 8)


def _rank_bank_capacity(ranks=RAGGED_RANKS):
    """Static adapter-HBM math: a resident slot's bank bytes are rank-
    proportional (every LoRA pair is ``(d_in, r)`` + ``(r, d_out)``), so a
    bucketed bank holding one client per rank costs ``sum(ranks)`` rank-
    units where the pad-to-max bank costs ``len(ranks) * max(ranks)``.
    Byte counts come from the actual ``init_adapters`` trees so target-set
    changes reprice the gate automatically."""
    unit = {}
    for r in sorted(set(ranks)):
        tree = init_adapters(jax.random.PRNGKey(0), CFG, rank=r)
        unit[r] = sum(int(l.size) * l.dtype.itemsize
                      for l in jax.tree.leaves(tree))
    bucketed = sum(unit[r] for r in ranks)
    padded = len(ranks) * unit[max(ranks)]
    return {"ranks": sorted(ranks),
            "bytes_per_slot": {str(r): unit[r] for r in sorted(set(unit))},
            "bank_bytes": {"bucketed": bucketed, "pad_to_max": padded},
            "bank_bytes_saved": padded - bucketed,
            "capacity_ratio": padded / bucketed,
            "extra_min_rank_slots_at_budget":
                int((padded - bucketed) // unit[min(ranks)])}


def _pad_rank(tree, to_rank: int):
    """Zero-pad every LoRA pair to ``to_rank``: ``a: (P, d_in, r)`` on the
    last axis, ``b: (P, r, d_out)`` on the middle axis — the pad-to-max
    baseline the rank buckets compete against."""
    def walk(node):
        if isinstance(node, dict) and set(node) == {"a", "b"}:
            r = node["a"].shape[-1]
            return {"a": jnp.pad(node["a"],
                                 [(0, 0), (0, 0), (0, to_rank - r)]),
                    "b": jnp.pad(node["b"],
                                 [(0, 0), (0, to_rank - r), (0, 0)])}
        return {k: walk(v) for k, v in node.items()}
    return walk(tree)


def _ragged_rank_setup():
    """One model, two registries over the SAME client weights (native
    ranks 2/4/8): bucketed (``ranks=[2,4,8]``) vs the legacy single
    max-rank bucket with every client zero-padded to rank 8."""
    model = get_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    trees = {f"c{i}": _adapters(i + 1, rank=r)
             for i, r in enumerate(RAGGED_RANKS)}
    reg_b = AdapterRegistry(CFG, capacity=len(trees),
                            ranks=list(RAGGED_RANKS))
    reg_p = AdapterRegistry(CFG, capacity=len(trees))
    for cid, t in trees.items():
        reg_b.register(cid, t)
        reg_p.register(cid, _pad_rank(t, max(RAGGED_RANKS)))
    return (MultiTenantEngine(model, CFG, params, reg_b),
            MultiTenantEngine(model, CFG, params, reg_p))


def ragged_rank_section(json_path: str, smoke: bool = False):
    """Mixed-rank serving: clients fine-tuned at ranks 2/4/8, served from
    the bucketed bank vs every slot padded to rank 8.  Outputs must be
    BITWISE equal — zero rank columns contribute exact zeros, and the
    kernel's per-slot rank mask enforces it even for junk padding — so the
    win is pure adapter-bank HBM (rank-proportional bytes per slot), plus
    whatever the smaller per-bucket matmuls buy in wall time."""
    mt_b, mt_p = _ragged_rank_setup()
    reqs = _ragged_workload(len(RAGGED_RANKS))
    if smoke:
        reqs = reqs[:4]               # still cycles through all three ranks
    cap = _rank_bank_capacity()
    print(row("ragged_rank_bank_bytes_bucketed", 0.0,
              str(cap["bank_bytes"]["bucketed"])))
    print(row("ragged_rank_bank_bytes_padded", 0.0,
              str(cap["bank_bytes"]["pad_to_max"])))
    print(row("ragged_rank_capacity_ratio", 0.0,
              f"{cap['capacity_ratio']:.2f}x"))
    assert cap["capacity_ratio"] >= 1.5, \
        f"bucketed bank must save >=1.5x bytes vs pad-to-max for ranks " \
        f"{cap['ranks']} (got {cap['capacity_ratio']:.2f}x)"

    sc = ServeConfig(batch_size=4, max_new_tokens=NEW_TOKENS, block_size=8)
    out_b = mt_b.generate(reqs, sc)
    out_p = mt_p.generate(reqs, sc)
    for a, b in zip(out_b, out_p):             # parity before trusting HBM win
        np.testing.assert_array_equal(a, b)
    if smoke:
        print(row("ragged_rank_smoke_parity", 0.0, "ok"))
        return

    useful = sum(r.max_new_tokens for r in reqs)
    us_b = _best_us(lambda: mt_b.generate(reqs, sc))
    us_p = _best_us(lambda: mt_p.generate(reqs, sc))
    tps_b = useful / (us_b / 1e6)
    tps_p = useful / (us_p / 1e6)
    print(row("ragged_rank_bucketed", us_b, f"{tps_b:.1f} tok/s"))
    print(row("ragged_rank_pad_to_max", us_p, f"{tps_p:.1f} tok/s"))
    _merge_json(json_path, {"ragged_rank": {
        **cap,
        "workload": {"requests": len(reqs), "useful_tokens": useful,
                     "clients": len(RAGGED_RANKS), "slots": sc.batch_size,
                     "num_shards": sc.num_shards,
                     "block_size": sc.block_size},
        "tok_per_s": {"bucketed": tps_b, "pad_to_max": tps_p},
        "us_per_call": {"bucketed": us_b, "pad_to_max": us_p},
        "note": "CPU interpret-mode; bitwise-equal outputs (zero rank "
                "columns are inert, kernel masks them) — win = rank-"
                "proportional adapter-bank bytes (serving/registry.py "
                "rank buckets)",
    }})
    print(f"# wrote {json_path} (ragged_rank section)")


def ragged_rank_gate_section(json_path: str):
    """Ragged-rank HBM floor for CI: the bucketed-vs-padded bank byte
    ratio, gated against ``benchmarks/baselines/serving_ragged.json``.
    Pure capacity math — deterministic, immune to runner jitter; the
    bitwise mixed-rank parity runs in serving-smoke and
    tests/test_ragged_rank.py."""
    cap = _rank_bank_capacity()
    print(row("ragged_rank_gate", 0.0,
              f"{cap['capacity_ratio']:.2f}x bank bytes "
              f"(+{cap['extra_min_rank_slots_at_budget']} rank-"
              f"{min(cap['ranks'])} slots at the padded budget)"))
    _merge_json(json_path, {"ragged_rank": {
        **cap,
        "note": "bucketed adapter-bank bytes vs pad-to-max; gated by "
                "scripts/check_bench_regression.py in CI",
    }})
    print(f"# wrote {json_path} (ragged_rank gate section)")


# ---------------------------------------------------------------------------
# Open-loop trace serving: overlapped dispatch vs the synchronous loop
# ---------------------------------------------------------------------------

# Decode-heavy bursty workload for the overlap sections: short prompts,
# near-budget outputs, ON/OFF arrivals that pile a backlog onto the pinned
# pool.  Decode rounds with no block-table churn are exactly where the
# overlapped session skips host marshalling, so this stream is the one the
# tentpole is supposed to win.
# decode-heavy on purpose: the overlap win comes from pipelined decode
# rounds (deferred observation), and prefill/admission rounds are
# synchronous flush points that dilute it for both configs equally
TRACE_KW = dict(arrival="bursty", rate=30.0, prompt_mean=8.0,
                prompt_sigma=0.4, prompt_max=24, out_mean=56.0,
                out_sigma=0.3, out_max=64, vocab_size=CFG.vocab_size)


def _trace_sc(**kw):
    """Latency-mode serving over a pinned pool: ``scan_chunk=1`` admits
    between every token (the regime where per-round host work dominates),
    and open-loop sessions need pinned geometry up front."""
    bp = -(-(TRACE_KW["prompt_max"] + TRACE_KW["out_max"]) // 16)
    base = dict(batch_size=4, max_new_tokens=TRACE_KW["out_max"],
                block_size=16, num_blocks=1 + 4 * bp,
                max_blocks_per_slot=bp, prefill_chunk=8, scan_chunk=1)
    base.update(kw)
    return ServeConfig(**base)


def _trace_parity(mt, trace, rounds_per_s: float = 8.0):
    """Logical-mode replay, overlap on vs off: identical dispatch
    sequences, so the streams must be BITWISE equal before any timing is
    trusted.  Returns the overlapped run's report."""
    rep_on = run_trace(mt, _trace_sc(), trace, rounds_per_s=rounds_per_s)
    rep_off = run_trace(mt, _trace_sc(overlap=False), trace,
                        rounds_per_s=rounds_per_s)
    assert rep_on["completed"] == len(trace) == rep_off["completed"]
    for rid in range(len(trace)):
        np.testing.assert_array_equal(
            np.asarray(rep_on["streams"][rid], np.int32),
            np.asarray(rep_off["streams"][rid], np.int32))
    return rep_on


def trace_section(json_path: str, smoke: bool = False):
    """Open-loop bursty trace through ``StreamSession``, overlapped
    dispatch (``ServeConfig.overlap``) vs the synchronous per-round loop.
    Outputs must be bitwise-identical on the fixed trace (logical replay);
    the win is wall-clock — goodput and p99 TTFT under backlog — measured
    realtime with the two configs interleaved best-of-N so machine drift
    cancels out of the ratio."""
    n = 8 if smoke else 24
    model, params, ads, mt = _setup(2)
    trace = synth_trace(0, n, **TRACE_KW)
    rep = _trace_parity(mt, trace)
    print(row("trace_parity", 0.0, f"{n} streams bitwise equal"))
    if smoke:
        print(row("trace_smoke_parity", 0.0, "ok"))
        return

    sc_on, sc_off = _trace_sc(), _trace_sc(overlap=False)
    run_trace(mt, sc_on, trace, realtime=True)      # warmup/compile
    best_on = best_off = None
    for _ in range(3):
        r_off = run_trace(mt, sc_off, trace, realtime=True)
        r_on = run_trace(mt, sc_on, trace, realtime=True)
        if (best_off is None or r_off["goodput_tok_per_unit"]
                > best_off["goodput_tok_per_unit"]):
            best_off = r_off
        if (best_on is None or r_on["goodput_tok_per_unit"]
                > best_on["goodput_tok_per_unit"]):
            best_on = r_on
    gp_on = best_on["goodput_tok_per_unit"]
    gp_off = best_off["goodput_tok_per_unit"]
    p99_on = best_on["ttft"]["p99"]
    p99_off = best_off["ttft"]["p99"]
    goodput_win = gp_on / gp_off
    ttft_win = p99_off / max(p99_on, 1e-9)
    print(row("trace_sync", 0.0,
              f"{gp_off:.1f} tok/s, p99 TTFT {p99_off:.1f}ms"))
    print(row("trace_overlap", 0.0,
              f"{gp_on:.1f} tok/s, p99 TTFT {p99_on:.1f}ms"))
    print(row("trace_goodput_win", 0.0, f"{goodput_win:.2f}x"))
    print(row("trace_p99_ttft_win", 0.0, f"{ttft_win:.2f}x"))
    assert goodput_win >= 1.3 or ttft_win >= 1.3, \
        f"overlapped dispatch must win >=1.3x goodput OR >=1.3x p99 TTFT " \
        f"on the bursty trace (got {goodput_win:.2f}x / {ttft_win:.2f}x)"

    def _classes(rep_):
        return {cls: {"n": d["n"], "ttft": d["ttft"], "tpot": d["tpot"]}
                for cls, d in rep_["per_class"].items()}

    _merge_json(json_path, {"trace": {
        "workload": {"requests": n, "arrival": TRACE_KW["arrival"],
                     "rate_req_per_s": TRACE_KW["rate"],
                     "prompt_max": TRACE_KW["prompt_max"],
                     "out_max": TRACE_KW["out_max"],
                     "slots": sc_on.batch_size,
                     "scan_chunk": sc_on.scan_chunk,
                     "block_size": sc_on.block_size,
                     "num_shards": sc_on.num_shards,
                     "emitted_tokens": rep["emitted_tokens"]},
        "sync": {"goodput_tok_per_s": gp_off, "ttft_ms": best_off["ttft"],
                 "per_class": _classes(best_off)},
        "overlap": {"goodput_tok_per_s": gp_on, "ttft_ms": best_on["ttft"],
                    "per_class": _classes(best_on)},
        "tok_per_s": gp_on, "p99_ttft_ms": p99_on,
        "goodput_win": goodput_win, "p99_ttft_win": ttft_win,
        "note": "CPU interpret-mode; bitwise-equal streams on the fixed "
                "trace (logical replay) — win = pipelined decode: the "
                "overlapped session dispatches chunk N+1 from device-"
                "chained state (last token, lengths, rng, cached tables) "
                "and only then materialises chunk N (one-round-deferred "
                "observation), so host bookkeeping overlaps device "
                "execution",
    }})
    print(f"# wrote {json_path} (trace section)")


def trace_gate_section(json_path: str):
    """Trace-serving floor for CI: the overlapped engine's realtime
    goodput AND p99 TTFT on the fixed bursty trace, both gated against
    ``benchmarks/baselines/serving_trace.json`` (goodput 'higher', TTFT
    'lower'; best-of-N — parity and the overlap-win assertion run in the
    full bench / serving-smoke)."""
    model, params, ads, mt = _setup(2)
    trace = synth_trace(0, 24, **TRACE_KW)
    sc = _trace_sc()
    run_trace(mt, sc, trace, realtime=True)         # warmup/compile
    gp, p99 = 0.0, float("inf")
    for _ in range(3):
        rep = run_trace(mt, sc, trace, realtime=True)
        gp = max(gp, rep["goodput_tok_per_unit"])
        p99 = min(p99, rep["ttft"]["p99"])
    print(row("trace_gate", 0.0, f"{gp:.1f} tok/s, p99 TTFT {p99:.1f}ms"))
    _merge_json(json_path, {"trace": {
        "tok_per_s": gp, "p99_ttft_ms": p99, "requests": len(trace),
        "slots": sc.batch_size, "num_shards": sc.num_shards,
        "note": "open-loop bursty-trace goodput + p99 TTFT (overlap on); "
                "gated by scripts/check_bench_regression.py in CI",
    }})
    print(f"# wrote {json_path} (trace gate section)")


def trace_sweep_section(json_path: str):
    """Multi-seed arrival-regime sweep for the weekly deep job: three
    regimes (steady poisson, the default bursty mix, heavy ON/OFF bursts)
    x three seeds, each replayed logically with overlap on vs off —
    bitwise parity asserted on every pair — recording per-regime goodput
    and TTFT spreads."""
    model, params, ads, mt = _setup(2)
    regimes = {
        "poisson": dict(TRACE_KW, arrival="poisson"),
        "bursty": dict(TRACE_KW),
        "heavy_burst": dict(TRACE_KW, rate=45.0, burst_on_s=0.25,
                            burst_off_s=2.25),
    }
    sweep = {}
    for name, kw in regimes.items():
        goodputs, p99s = [], []
        for seed in (0, 1, 2):
            rep = _trace_parity(mt, synth_trace(seed, 16, **kw))
            goodputs.append(rep["goodput_tok_per_unit"])
            p99s.append(rep["ttft"]["p99"])
        sweep[name] = {
            "seeds": [0, 1, 2],
            "goodput_tok_per_round": {"min": min(goodputs),
                                      "max": max(goodputs)},
            "p99_ttft_rounds": {"min": min(p99s), "max": max(p99s)},
        }
        print(row(f"trace_sweep_{name}", 0.0,
                  f"goodput {min(goodputs):.2f}-{max(goodputs):.2f} "
                  f"tok/round, parity ok x3"))
    _merge_json(json_path, {"trace_sweep": {
        **sweep,
        "note": "logical-mode (deterministic) multi-seed sweep; every "
                "seed/regime pair asserted bitwise async-vs-sync parity",
    }})
    print(f"# wrote {json_path} (trace sweep section)")


# ---------------------------------------------------------------------------
# Block-size sweep for the batched-LoRA kernel (autotuning groundwork)
# ---------------------------------------------------------------------------

def block_sweep():
    """Tile-size table per (n_clients, rank) for batched_lora_matmul.

    Interpret-mode timings rank tile shapes only relatively; on TPU rerun
    with interpret=False to pick per-(C, r) defaults (ROADMAP autotuning)."""
    rng = np.random.default_rng(3)
    M = K = N = 256
    print("# block-sweep: name,us_per_call,derived (bm=bn=bk)")
    for C, r in ((2, 8), (4, 16), (8, 32)):
        x = jnp.asarray(rng.standard_normal((M, K)), jnp.bfloat16)
        w = jnp.asarray(rng.standard_normal((K, N)) * 0.05, jnp.bfloat16)
        a = jnp.asarray(rng.standard_normal((C, K, r)) * 0.05, jnp.float32)
        b = jnp.asarray(rng.standard_normal((C, r, N)) * 0.05, jnp.float32)
        g = jnp.asarray(rng.integers(0, C, M), jnp.int32)
        best = None
        for blk in (64, 128, 256):
            _, us = timed(batched_lora_matmul, x, w, a, b, g, 2.0,
                          bm=blk, bn=blk, bk=blk)
            print(row(f"batched_lora_C{C}_r{r}_blk{blk}", us, f"{blk}"))
            if best is None or us < best[1]:
                best = (blk, us)
        print(row(f"batched_lora_C{C}_r{r}_best", best[1], f"blk={best[0]}"))


# per-section wall times accumulate here; main() merges them into the
# bench JSON so the uploaded CI artifact shows where the minutes went
_SECTION_WALLS: dict = {}


def _run_section(name: str, fn, *args, **kwargs):
    """Run one bench section, print its wall time, and record it for the
    ``section_walltimes`` key of the bench JSON."""
    import time as _time
    t0 = _time.perf_counter()
    fn(*args, **kwargs)
    wall = _time.perf_counter() - t0
    _SECTION_WALLS[name] = round(wall, 3)
    print(f"# section {name}: {wall:.1f}s wall")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny correctness-only run for CI")
    ap.add_argument("--gate-only", action="store_true",
                    help="run ONLY the smoke-gate throughput section (the "
                         "bench-gate CI job; parity runs in serving-smoke)")
    ap.add_argument("--block-sweep", action="store_true",
                    help="batched-LoRA tile-size sweep per (n_clients, rank)")
    ap.add_argument("--trace-sweep", action="store_true",
                    help="multi-seed arrival-regime trace sweep (the "
                         "weekly deep CI job; logical-mode parity only)")
    ap.add_argument("--json", default="BENCH_serving.json",
                    help="where the ragged-workload record is written")
    args = ap.parse_args(argv)
    runtime.enable_compile_cache()

    print("name,us_per_call,derived")
    if args.block_sweep:
        _run_section("block_sweep", block_sweep)
        return
    if args.trace_sweep:
        _run_section("trace_sweep", trace_sweep_section, args.json)
    elif args.gate_only:
        _run_section("smoke_gate", smoke_gate_section, args.json)
        _run_section("spec_gate", spec_gate_section, args.json)
        _run_section("shard_gate", shard_gate_section, args.json)
        _run_section("quant_gate", quant_gate_section, args.json)
        _run_section("ragged_rank_gate", ragged_rank_gate_section, args.json)
        _run_section("trace_gate", trace_gate_section, args.json)
    elif args.smoke:
        _run_section("ragged", ragged_section, args.json, smoke=True)
        _run_section("prefill", prefill_section, args.json, smoke=True)
        _run_section("prefix_cache", prefix_cache_section, args.json,
                     smoke=True)
        _run_section("sla", sla_section, args.json, smoke=True)
        _run_section("spec", spec_section, args.json, smoke=True)
        _run_section("shard", shard_section, args.json, smoke=True)
        _run_section("quant", quant_section, args.json, smoke=True)
        _run_section("ragged_rank", ragged_rank_section, args.json,
                     smoke=True)
        _run_section("trace", trace_section, args.json, smoke=True)
        _run_section("smoke_gate", smoke_gate_section, args.json)
    else:
        _run_section("fixed_shape", fixed_shape_sections)
        _run_section("ragged", ragged_section, args.json)
        _run_section("prefill", prefill_section, args.json)
        _run_section("prefix_cache", prefix_cache_section, args.json)
        _run_section("sla", sla_section, args.json)
        _run_section("spec", spec_section, args.json)
        _run_section("shard", shard_section, args.json)
        _run_section("quant", quant_section, args.json)
        _run_section("ragged_rank", ragged_rank_section, args.json)
        _run_section("trace", trace_section, args.json)
        _run_section("smoke_gate", smoke_gate_section, args.json)
    _merge_json(args.json, {"section_walltimes": _SECTION_WALLS})
    print(f"# wrote {args.json} (section_walltimes)")


if __name__ == "__main__":
    main()
