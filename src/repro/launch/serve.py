"""Serving launcher: batched KV-cache decoding with optional fused dual-LoRA
adapters (the FDLoRA inference path).

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --smoke \
        --batch 4 --new-tokens 16

Multi-tenant demo (one engine, N resident client adapters, mixed batch):

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --smoke \
        --tenants 4 --batch 8 --new-tokens 16

Continuous batching (slot scheduler + paged KV cache: ragged prompts,
per-request budgets, admission into freed slots):

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --smoke \
        --tenants 4 --batch 4 --requests 12 --continuous

Open-loop asyncio serving (requests arrive on a synthetic trace at
arbitrary wall-clock times, tokens stream back per request, graceful
drain; see ``serving/trace.py`` for the workload generator):

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --smoke \
        --tenants 4 --batch 4 --serve --trace-requests 24 \
        --trace-arrival bursty --trace-rate 20
"""
from __future__ import annotations

import argparse
import asyncio
import time
from collections import deque
from typing import AsyncIterator, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import runtime
from repro.configs.registry import ALL_ARCHS, get_config
from repro.core.dual_lora import merge
from repro.core.lora import init_adapters
from repro.data.tokenizer import ByteTokenizer
from repro.models.api import get_model
from repro.serving.engine import (Engine, MultiTenantEngine, Request,
                                  ServeConfig)
from repro.serving.kv_cache import blocks_needed
from repro.serving.registry import AdapterRegistry
from repro.serving.sharded import ShardedAdapterRegistry
from repro.training.checkpoint import load_checkpoint


class AsyncServer:
    """Asyncio front end over an open-loop :class:`StreamSession`.

    Callers ``await submit(request)`` at ANY time — including while other
    requests are mid-flight — and consume their tokens incrementally via
    ``async for toks in stream(rid)``.  One pump coroutine owns the
    session: it drains staged submissions between engine rounds (so
    scheduler state is only ever touched from the event-loop thread) and
    runs each blocking :meth:`StreamSession.step` in the default executor,
    which keeps the event loop responsive while the device computes —
    with ``ServeConfig.overlap`` the host side of a step is mostly
    planning, so submissions interleave at chunk granularity.

    ``await drain()`` is the graceful shutdown: already-accepted requests
    run to completion, late ``submit`` calls are rejected, and the
    session's ``last_stats`` (wall-clock queue waits per class) come back.
    ``async with AsyncServer(...)`` drains on exit.
    """

    def __init__(self, engine: MultiTenantEngine, sc: ServeConfig):
        self._engine, self._sc = engine, sc
        self._ses = engine.session(sc)          # open loop: starts empty
        self._staged: deque = deque()           # (Request, arrival, Future)
        self._queues: Dict[int, asyncio.Queue] = {}
        self._wake: Optional[asyncio.Event] = None
        self._pump_task: Optional[asyncio.Task] = None
        self._closing = False
        self.stats: Optional[dict] = None

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> "AsyncServer":
        if self._pump_task is None:
            self._wake = asyncio.Event()
            self._pump_task = asyncio.ensure_future(self._pump())
        return self

    async def drain(self) -> dict:
        """Stop accepting; run accepted requests to completion; return the
        session's ``last_stats``."""
        self._closing = True
        if self._pump_task is not None:
            self._wake.set()
            await self._pump_task
        else:
            self.stats = self._ses.finalize()
        return self.stats

    async def __aenter__(self) -> "AsyncServer":
        return self.start()

    async def __aexit__(self, *exc) -> None:
        await self.drain()

    # -- client API ----------------------------------------------------------
    async def submit(self, request: Request) -> int:
        """Stage ``request`` and return its rid once the pump accepts it.
        The submission wall-clock time is recorded as the request's
        arrival, so ``last_stats`` queue waits are end-to-end."""
        if self._closing:
            raise RuntimeError("AsyncServer is draining; submit rejected")
        if self._pump_task is None:
            raise RuntimeError("AsyncServer not started (use 'async with' "
                               "or call start())")
        fut = asyncio.get_running_loop().create_future()
        self._staged.append((request, time.monotonic(), fut))
        self._wake.set()
        return await fut

    async def stream(self, rid: int) -> AsyncIterator[List[int]]:
        """Token increments for one request, ending after its final chunk
        (budget reached or EOS)."""
        q = self._queues[rid]
        while True:
            toks, fin = await q.get()
            if toks:
                yield toks
            if fin:
                return

    # -- engine pump ---------------------------------------------------------
    async def _pump(self) -> None:
        loop = asyncio.get_running_loop()
        ses = self._ses
        while True:
            if self._staged:
                with ses.span("intake"):        # intake between rounds
                    while self._staged:
                        req, arrival, fut = self._staged.popleft()
                        rid = ses.submit(req, arrival_time=arrival)
                        self._queues[rid] = asyncio.Queue()
                        fut.set_result(rid)
            if not ses.has_work:
                if self._closing:
                    break
                self._wake.clear()              # idle: park until a submit
                await self._wake.wait()
                continue
            events = await loop.run_in_executor(None, ses.step)
            if not events:
                continue
            with ses.span("deliver"):
                for rid, toks, fin in events:
                    q = self._queues.get(rid)
                    if q is not None:
                        q.put_nowait((list(toks), fin))
                        if fin:
                            self._queues.pop(rid, None)
        self.stats = ses.finalize()


def _print_class_stats(stats: dict) -> None:
    """Per-class queue-wait lines.  Open-loop sessions (driven with
    arrival times) report WALL-CLOCK percentiles; closed-loop batches
    keep the admission-round numbers — rounds are meaningless as a
    latency unit when requests arrive over an open interval."""
    for cname, cs in stats["classes"].items():
        if "wait_wall_ms_p50" in cs:
            print(f"  class {cname}: {cs['admitted']} admitted, "
                  f"queue wait p50 {cs['wait_wall_ms_p50']:.1f} / "
                  f"p99 {cs['wait_wall_ms_p99']:.1f} ms wall, "
                  f"{cs['preemptions']} preemptions")
        else:
            print(f"  class {cname}: {cs['admitted']} admitted, "
                  f"queue wait p50 {cs['wait_p50']:.0f} / "
                  f"p99 {cs['wait_p99']:.0f} rounds, "
                  f"{cs['preemptions']} preemptions")


async def _serve_demo(eng: MultiTenantEngine, sc: ServeConfig, trace,
                      time_scale: float, tok: ByteTokenizer) -> dict:
    """Drive a synthetic open-loop trace through :class:`AsyncServer`:
    one client coroutine per trace entry sleeps until its scheduled
    arrival, submits, and consumes its stream; the server drains
    gracefully once every accepted request finishes."""
    t0 = time.monotonic()
    lat: Dict[int, Tuple[float, int, float]] = {}   # rid -> (ttft, n, span)
    async with AsyncServer(eng, sc) as srv:
        async def client(i, e):
            sched = e.arrival_s * time_scale
            await asyncio.sleep(max(0.0, sched - (time.monotonic() - t0)))
            rid = await srv.submit(e.request())
            first = last = None
            n = 0
            async for toks in srv.stream(rid):
                now = time.monotonic() - t0
                first = now if first is None else first
                last, n = now, n + len(toks)
            lat[i] = (first - sched, n, (last - first) if n > 1 else 0.0)

        await asyncio.gather(*(client(i, e) for i, e in enumerate(trace)))
        elapsed = time.monotonic() - t0
    ttfts = sorted(v[0] for v in lat.values())
    total = sum(v[1] for v in lat.values())
    print(f"open-loop serve: {len(trace)} requests, {total} tokens in "
          f"{elapsed:.2f}s ({total / elapsed:.1f} tok/s goodput incl. "
          f"compile); TTFT p50 "
          f"{1e3 * float(np.percentile(ttfts, 50)):.1f} / p99 "
          f"{1e3 * float(np.percentile(ttfts, 99)):.1f} ms "
          f"[overlap={'on' if sc.overlap else 'off'}]")
    _print_class_stats(srv.stats)
    return srv.stats


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b", choices=ALL_ARCHS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=256)
    ap.add_argument("--adapters", default="", help="npz checkpoint to load")
    ap.add_argument("--dual", action="store_true",
                    help="demo: fuse two random adapter sets via Eq.7")
    ap.add_argument("--tenants", type=int, default=0,
                    help="multi-tenant demo: N resident client adapters, "
                         "one engine, mixed-client batch")
    ap.add_argument("--continuous", action="store_true",
                    help="with --tenants: serve a ragged request stream "
                         "through the slot scheduler + paged KV cache")
    ap.add_argument("--requests", type=int, default=0,
                    help="continuous mode: queued requests (default 3x batch)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="continuous mode: KV block size (tokens)")
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="continuous mode: prompt tokens per prefill "
                         "dispatch (1 = legacy one-token-per-step)")
    ap.add_argument("--stream", action="store_true",
                    help="continuous mode: print per-request token "
                         "increments as chunks complete (generate_stream)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="continuous mode: content-addressed shared KV "
                         "blocks — shared prompt prefixes skip re-prefill "
                         "within and across calls (runs the stream twice "
                         "to show the warm-cache hit rate)")
    ap.add_argument("--sched-policy", default="sla",
                    choices=["sla", "fcfs"],
                    help="continuous mode: 'sla' = priority-class admission "
                         "with aging + prefix-aware preemption victims; "
                         "'fcfs' = legacy arrival order + newest-first")
    ap.add_argument("--priority-mix", default="",
                    help="continuous mode: comma list of classes "
                         "(interactive,batch,background) cycled over the "
                         "request stream, e.g. 'batch,batch,interactive'; "
                         "empty = all batch")
    ap.add_argument("--spec-decode", action="store_true",
                    help="continuous mode: speculative greedy decoding — "
                         "prompt-lookup drafts verified through the paged "
                         "prefill path (bitwise-identical tokens, fewer "
                         "model evaluations on repetitive output)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="with --spec-decode: max drafted tokens per slot "
                         "per verify round")
    ap.add_argument("--shards", type=int, default=1,
                    help="continuous mode: partition the paged KV pool and "
                         "adapter bank into N shards with placement-aware "
                         "admission (slots and blocks split evenly; outputs "
                         "stay bitwise-identical to --shards 1)")
    ap.add_argument("--kv-dtype", default="f32",
                    choices=["f32", "int8"],
                    help="continuous mode: paged KV block storage — 'f32' "
                         "= the unquantized pools, 'int8' = quantized "
                         "blocks with per-block scales (~1.78x blocks per "
                         "HBM byte; error-bound, not bitwise, vs f32)")
    ap.add_argument("--paged-backend", default=None,
                    choices=list(runtime.PAGED_BACKENDS),
                    help="continuous mode: paged-attention implementation — "
                         "'jnp' gather oracle or 'pallas' kernels; default "
                         "the platform's (pallas on TPU, jnp on CPU)")
    ap.add_argument("--serve", action="store_true",
                    help="with --tenants: open-loop asyncio serving — "
                         "requests arrive on a synthetic trace at wall-"
                         "clock times, tokens stream back per request, "
                         "graceful drain; reports TTFT percentiles and "
                         "WALL-CLOCK per-class queue waits")
    ap.add_argument("--trace-requests", type=int, default=24,
                    help="--serve: trace length (requests)")
    ap.add_argument("--trace-arrival", default="bursty",
                    choices=["poisson", "bursty"],
                    help="--serve: arrival process (same long-run rate)")
    ap.add_argument("--trace-rate", type=float, default=20.0,
                    help="--serve: mean arrival rate, requests/second")
    ap.add_argument("--trace-seed", type=int, default=0,
                    help="--serve: workload generator seed")
    ap.add_argument("--time-scale", type=float, default=1.0,
                    help="--serve: multiply trace arrival times (<1 "
                         "compresses the trace = higher load)")
    ap.add_argument("--no-overlap", action="store_true",
                    help="disable async overlapped dispatch (run the "
                         "synchronous reference loop; tokens are bitwise "
                         "identical either way)")
    ap.add_argument("--ranks", default="",
                    help="with --tenants: comma list of rank buckets (e.g. "
                         "'2,4,8') — the bank splits into one bucket per "
                         "rank and client i registers at ranks[i %% len], "
                         "padded into its bucket (small-rank clients stop "
                         "paying max-rank HBM; outputs stay bitwise equal "
                         "to each client's native-rank adapter)")
    ap.add_argument("--update-every", type=int, default=0,
                    help="continuous mode: every N stream events, re-"
                         "register one client's fused adapter mid-serve "
                         "(round-robin) — the FDLoRA continual loop; the "
                         "live session hot-swaps the bank at its next "
                         "round boundary and the updated client's prefix-"
                         "cache scope is invalidated by the version bump")
    args = ap.parse_args(argv)
    runtime.enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.is_encdec:
        raise SystemExit("enc-dec serving needs audio embeds; use tests/"
                         "test_models.py::test_whisper_prefill_cross for the path")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    tok = ByteTokenizer()
    prompt = tok.encode("logs: job start | net link up anomaly? ")[:32]
    prompt = np.array(prompt, np.int32) % cfg.vocab_size
    sc = ServeConfig(batch_size=args.batch, max_new_tokens=args.new_tokens,
                     cache_len=args.cache_len)

    if (args.continuous or args.serve) and args.tenants <= 0:
        raise SystemExit("--continuous/--serve need --tenants N (the "
                         "continuous scheduler serves the multi-tenant "
                         "engine)")
    sc.overlap = not args.no_overlap
    if args.tenants > 0:
        if args.adapters or args.dual:
            raise SystemExit("--tenants is a self-contained demo (random "
                             "fused adapters per tenant); it cannot combine "
                             "with --adapters/--dual")
        if args.update_every and args.prefix_cache:
            raise SystemExit("--update-every re-registers adapters mid-"
                             "serve, so the --prefix-cache warm-call "
                             "bitwise check cannot hold; pick one")
        # FDLoRA end state: every client registered one Eq.7-fused adapter;
        # a single engine serves a batch that mixes all of them.
        rank_list = [int(r) for r in args.ranks.split(",") if r.strip()]
        # ragged banks split capacity across rank buckets; give the demo 2x
        # slack so round-robin client ranks never churn a full bucket
        cap = args.tenants if not rank_list else 2 * args.tenants
        cap = max(cap, args.shards * max(1, len(set(rank_list))))
        if args.shards > 1:
            cap = -(-cap // args.shards) * args.shards
            registry = ShardedAdapterRegistry(cfg, capacity=cap,
                                              num_shards=args.shards,
                                              ranks=rank_list or None)
        else:
            registry = AdapterRegistry(cfg, capacity=cap,
                                       ranks=rank_list or None)

        def _client_rank(i: int):
            return rank_list[i % len(rank_list)] if rank_list else None

        for i in range(args.tenants):
            rk = _client_rank(i)
            ad_p = init_adapters(jax.random.PRNGKey(10 + 2 * i), cfg, rank=rk)
            ad_s = init_adapters(jax.random.PRNGKey(11 + 2 * i), cfg, rank=rk)
            registry.register_dual(f"client{i}", ad_p, ad_s,
                                   jnp.array([0.6, 0.6]))
        if rank_list:
            print(f"ragged adapter bank: buckets {registry.bucket_ranks}, "
                  f"per-slot effective ranks "
                  f"{registry.slot_ranks().tolist()}")
        eng = MultiTenantEngine(model, cfg, params, registry)
        if args.serve:
            from repro.serving.trace import synth_trace
            sc.block_size = args.block_size
            sc.prefill_chunk = args.prefill_chunk
            sc.sched_policy = args.sched_policy
            sc.paged_backend = args.paged_backend
            sc.kv_dtype = args.kv_dtype
            sc.num_shards = args.shards
            # open-loop sessions need the pool pinned up front: size it for
            # batch_size concurrent worst-case spans (prompt_max + out_max)
            prompt_max, out_max = 32, args.new_tokens
            bp = blocks_needed(prompt_max + out_max, sc.block_size)
            nb = args.batch * bp
            if args.shards > 1:
                nb = -(-nb // args.shards) * args.shards
            sc.num_blocks = 1 + nb
            sc.max_blocks_per_slot = bp
            trace = synth_trace(
                args.trace_seed, args.trace_requests,
                arrival=args.trace_arrival, rate=args.trace_rate,
                prompt_max=prompt_max, out_max=out_max,
                clients=tuple(f"client{i}" for i in range(args.tenants)),
                vocab_size=cfg.vocab_size)
            asyncio.run(_serve_demo(eng, sc, trace, args.time_scale, tok))
            return
        if args.continuous:
            # ragged stream: varied prompt lengths AND per-request budgets;
            # the scheduler admits queued requests as slots free up.
            n_req = args.requests or 3 * args.batch
            sc.block_size = args.block_size
            sc.prefill_chunk = args.prefill_chunk
            sc.prefix_cache = args.prefix_cache
            sc.sched_policy = args.sched_policy
            sc.paged_backend = args.paged_backend
            sc.kv_dtype = args.kv_dtype
            sc.spec_decode = args.spec_decode
            sc.spec_k = args.spec_k
            sc.num_shards = args.shards
            mix = [c.strip() for c in args.priority_mix.split(",")
                   if c.strip()]
            reqs = [Request(f"client{i % args.tenants}",
                            prompt[: 8 + (5 * i) % (len(prompt) - 7)],
                            max_new_tokens=4 + (7 * i) % args.new_tokens,
                            priority=mix[i % len(mix)] if mix else "batch")
                    for i in range(n_req)]
            t0 = time.time()
            updates = 0
            if args.stream or args.update_every > 0:
                outs = [np.zeros((0,), np.int32)] * n_req
                events = 0
                for rid, toks, finished in eng.generate_stream(reqs, sc):
                    outs[rid] = np.concatenate(
                        [outs[rid], np.asarray(toks, np.int32)])
                    if args.stream:
                        tag = " <done>" if finished else ""
                        print(f"  [stream] req{rid} +{len(toks)} "
                              f"({outs[rid].size} total){tag}: "
                              f"{tok.decode(np.asarray(toks))[:24]!r}")
                    events += 1
                    if args.update_every and events % args.update_every == 0:
                        # the FDLoRA continual loop: a finished stage-2
                        # round publishes one client's refreshed fused
                        # adapter into the LIVE registry; the session
                        # hot-swaps the bank at its next round boundary
                        i = updates % args.tenants
                        rk = _client_rank(i)
                        registry.register_dual(
                            f"client{i}",
                            init_adapters(jax.random.PRNGKey(
                                1000 + 2 * updates), cfg, rank=rk),
                            init_adapters(jax.random.PRNGKey(
                                1001 + 2 * updates), cfg, rank=rk),
                            jnp.array([0.6, 0.6]))
                        updates += 1
            else:
                outs = eng.generate(reqs, sc)
            dt = time.time() - t0
            total = sum(o.size for o in outs)
            stats = eng.last_stats
            print(f"{args.tenants} tenants, {n_req} ragged requests over "
                  f"{args.batch} slots (block={sc.block_size}, "
                  f"prefill_chunk={sc.prefill_chunk}): {total} tokens in "
                  f"{dt:.2f}s ({total/dt:.1f} tok/s incl. compile); "
                  f"{stats['prefill_dispatches']} prefill + "
                  f"{stats['decode_dispatches']} decode dispatches, "
                  f"{stats['preemptions']} preemptions "
                  f"[{stats['sched_policy']}, "
                  f"backend={stats['paged_backend']}, "
                  f"kv={sc.kv_dtype}]")
            if args.shards > 1:
                print(f"  {args.shards} shards: placements "
                      f"{stats['shard_placements']} "
                      f"(prefix-affinity > adapter home > least-loaded)")
            if args.update_every:
                print(f"  online updates: {updates} mid-serve "
                      f"re-registrations, "
                      f"{stats['adapter_bank_refreshes']} bank hot-swaps")
            if args.spec_decode:
                print(f"  spec decode (k={sc.spec_k}): "
                      f"{stats['accepted_tokens']}/{stats['drafted_tokens']} "
                      f"drafted tokens accepted "
                      f"({stats['acceptance_rate']:.0%}) over "
                      f"{stats['verify_dispatches']} verify dispatches; "
                      f"{stats['rollback_tokens']} tokens / "
                      f"{stats['rollback_blocks']} blocks rolled back")
            _print_class_stats(stats)
            if args.prefix_cache:
                print(f"  prefix cache (cold call): "
                      f"{stats['prefix_hit_tokens']}/"
                      f"{stats['prompt_tokens']} prompt tokens cached "
                      f"({stats['prefix_hit_rate']:.0%}); "
                      f"{stats['prefix_cached_blocks']} blocks retained")
                outs2 = eng.generate(reqs, sc)     # warm: prefixes re-match
                warm = eng.last_stats
                if sc.temperature == 0:            # bitwise claim is greedy-only
                    for a, b in zip(outs, outs2):
                        assert (np.asarray(a) == np.asarray(b)).all(), \
                            "warm cache diverged from cold run"
                print(f"  prefix cache (warm call): "
                      f"{warm['prefix_hit_tokens']}/"
                      f"{warm['prompt_tokens']} prompt tokens cached "
                      f"({warm['prefix_hit_rate']:.0%}); bitwise-equal, "
                      f"{warm['prefill_dispatches']} prefill dispatches vs "
                      f"{stats['prefill_dispatches']} cold")
            for r, o in list(zip(reqs, outs))[:args.tenants]:
                print(f"  {r.client_id} (S={len(r.prompt)}, "
                      f"budget={r.max_new_tokens}):", tok.decode(o)[:40])
            return
        reqs = [Request(f"client{b % args.tenants}", prompt)
                for b in range(args.batch)]
        t0 = time.time()
        out = eng.generate_fixed(reqs, sc)
        dt = time.time() - t0
        total = args.batch * args.new_tokens
        print(f"{args.tenants} tenants resident, mixed batch of {args.batch}: "
              f"{total} tokens in {dt:.2f}s ({total/dt:.1f} tok/s incl. compile)")
        for b in range(min(args.batch, args.tenants)):
            print(f"  {reqs[b].client_id}:",
                  tok.decode(np.asarray(out)[b])[:48])
        return

    adapters = None
    if args.adapters:
        adapters = load_checkpoint(args.adapters)
    elif args.dual:
        ad_p = init_adapters(jax.random.PRNGKey(1), cfg)
        ad_s = init_adapters(jax.random.PRNGKey(2), cfg)
        adapters = merge(ad_p, ad_s, jnp.array([0.6, 0.6]))

    eng = Engine(model, cfg, params, adapters)
    prompts = jnp.asarray(np.tile(prompt, (args.batch, 1)))
    t0 = time.time()
    out = eng.generate(prompts, sc)
    dt = time.time() - t0
    total = args.batch * args.new_tokens
    print(f"generated {total} tokens in {dt:.2f}s "
          f"({total/dt:.1f} tok/s incl. compile)")
    print("sample:", tok.decode(np.asarray(out)[0])[:60])


if __name__ == "__main__":
    main()
