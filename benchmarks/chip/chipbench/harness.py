"""One run of one cell: load, warm up, measure, check, report.

The system under test is the program's serving path as a user drives it:
``repro.launch.serve.AsyncServer`` over an open-loop ``StreamSession`` of
``MultiTenantEngine``, with an ``AdapterRegistry`` of Eq. 7-fused clients
(``register_dual``).  This module is the only one that imports the
program; everything specific to the model's architecture comes from the
module its configuration file names (:func:`chipbench.spec.arch_of`).

:func:`run_cell` does everything after the look for a chip, so the tests
drive it on the CPU at a tiny size (with faults planted through
``patch``).
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import math
import shutil
import sys
import tempfile
import time
from collections import deque
from pathlib import Path
from typing import Callable, List, Optional

import jax
import numpy as np

from chipbench import reference as R
from chipbench import serve_trace, spec, traces
from chipbench import traffic as TR
from chipbench import work

sys.path.insert(0, str(spec.ROOT / "src"))

# a request due in the window that has not finished this long after the
# window closed has failed
GRACE_S = 60.0
# the traced span: this share of the window, centred, once the slots fill
TRACE_SPAN = (0.4, 0.6)
# the program's (XLA modules') names
PROGRAMS = {"prefill": "_prefill_chunk_impl", "decode": "_decode_chunk_impl"}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Rec:
    """What a client saw of one request (host clock, seconds)."""
    client: int
    prompt_len: int
    budget: int
    due: float
    submitted: Optional[float] = None
    events: List[tuple] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    prompt: Optional[np.ndarray] = None
    ended: bool = False              # its stream finished

    @property
    def done(self) -> bool:
        return len(self.tokens) == self.budget

    @property
    def ttft(self) -> float:
        return self.events[0][0] - self.due if self.done else math.inf


class CompileWatch:
    """Backend compilations (and persistent-cache loads) JAX reports."""

    def __init__(self):
        self.count, self.secs = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.secs += duration


def program_config(config: dict):
    """The program's ModelConfig for a configuration file, as its
    architecture module maps it."""
    from repro.configs.base import ModelConfig

    return ModelConfig(**spec.arch_of(config).program_config_kwargs(config))


def build(config: dict, traffic: dict, seed: int):
    """Seeded weights, the registry of fused clients, the engine and the
    serving configuration."""
    from repro.models.api import get_model
    from repro.serving.engine import MultiTenantEngine, ServeConfig
    from repro.serving.registry import AdapterRegistry

    arch = spec.arch_of(config)
    cfg = program_config(config)
    params = arch.init_params(config, seed)
    n = TR.n_clients(traffic)
    reg = AdapterRegistry(cfg, capacity=n)
    for c in range(n):
        per, glo, w = arch.client_adapters(config, seed, c)
        reg.register_dual(f"c{c}", per, glo, w)
    eng = MultiTenantEngine(get_model(cfg), cfg, params, reg)
    srv = config["serving"]
    sc = ServeConfig(batch_size=traffic["slots"],
                     block_size=srv["block_size"],
                     num_blocks=srv["kv_pool_blocks"],
                     max_blocks_per_slot=-(-traffic["context_limit"]
                                           // srv["block_size"]),
                     prefill_chunk=traffic["prefill_chunk"],
                     scan_chunk=traffic["scan_chunk"])
    return eng, sc


async def _serve_one(srv, rec: Rec, client: int, prompt, budget):
    from repro.serving.engine import Request

    rec.submitted = time.perf_counter()
    rid = await srv.submit(Request(f"c{client}", prompt,
                                   max_new_tokens=budget))
    async for toks in srv.stream(rid):
        rec.events.append((time.perf_counter(), len(toks)))
        rec.tokens.extend(toks)
    rec.ended = True


async def warm_up(eng, sc, traffic: dict) -> None:
    """Every slot takes a prompt of two prefill chunks less a few tokens
    and decodes past one decode chunk: the cell's prefill and decode
    programs, with rows riding prefill dispatches and deferred decode
    observation, all run before the window."""
    from repro.launch.serve import AsyncServer

    T, cap = traffic["prefill_chunk"], traffic["scan_chunk"]
    n = TR.n_clients(traffic)
    rng = np.random.default_rng(0)
    async with AsyncServer(eng, sc) as srv:
        await asyncio.gather(*[
            _serve_one(srv, Rec(c, 0, 0, 0.0), c % n,
                       rng.integers(1, eng.cfg.vocab_size,
                                    2 * T - 3 - c % 3).astype(np.int32),
                       cap + 3)
            for c in range(sc.batch_size)])


async def _trace_span(srv, t0: float, seconds: float, out: dict):
    """Profile [t0 + a*seconds, t0 + b*seconds) of the window, and take
    the difference of the session's counters over it."""
    a, b = TRACE_SPAN
    await asyncio.sleep(max(0.0, t0 + a * seconds - time.perf_counter()))
    out["dir"] = tempfile.mkdtemp(prefix="chipbench-trace-")
    jax.profiler.start_trace(out["dir"])
    out["t0"] = time.perf_counter()
    before = srv._ses.counters()
    with jax.profiler.TraceAnnotation(traces.HOST_PREFIX + "window"):
        await asyncio.sleep(max(0.0, t0 + b * seconds - time.perf_counter()))
    out["counters"] = serve_trace.counter_delta(before, srv._ses.counters())
    out["t1"] = time.perf_counter()
    jax.profiler.stop_trace()


async def _resident_ready(recs: List[Rec], tasks) -> None:
    """Wait until every resident session has its first token: its
    history is in the cache and it decodes."""
    while not all(r.events for r in recs):
        for t in tasks:
            if t.done():
                raise RuntimeError(f"a resident session ended before the "
                                   f"window: {t.exception()!r}")
        await asyncio.sleep(0.002)


async def serve_window(eng, sc, traffic: dict, items, seconds: float,
                       trace: bool, t_start: float):
    """Drive the window; returns (records, window start, stats, setup_s,
    trace info).  Open loop: every item is due at its time.  Closed loop:
    users draw items in order until the window closes.  Resident: every
    item is submitted at once and the window opens when each has its
    first token (building their caches is set-up)."""
    from repro.launch.serve import AsyncServer

    srv = AsyncServer(eng, sc).start()
    tr: dict = {}
    recs: List[Rec] = []
    tasks = []
    loop = traffic["loop"]
    if loop == "resident":
        for it in items:
            rec = Rec(it.client, len(it.prompt), it.max_new_tokens,
                      time.perf_counter(), prompt=it.prompt)
            recs.append(rec)
            tasks.append(asyncio.ensure_future(_serve_one(
                srv, rec, it.client, it.prompt, it.max_new_tokens)))
        await _resident_ready(recs, tasks)
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    t_end = t0 + seconds
    if loop == "open":
        for it in items:
            rec = Rec(it.client, len(it.prompt), it.max_new_tokens,
                      t0 + it.due_s, prompt=it.prompt)
            recs.append(rec)

            async def client(rec=rec, it=it):
                await asyncio.sleep(max(0.0, rec.due - time.perf_counter()))
                await _serve_one(srv, rec, it.client, it.prompt,
                                 it.max_new_tokens)
            tasks.append(asyncio.ensure_future(client()))
    elif loop == "closed":
        queue = deque(items)

        async def user(u):
            await asyncio.sleep(u * traffic["stagger_s"])
            while time.perf_counter() < t_end:
                it = queue.popleft()
                rec = Rec(u, len(it.prompt), it.max_new_tokens,
                          time.perf_counter(), prompt=it.prompt)
                recs.append(rec)
                await _serve_one(srv, rec, u, it.prompt, it.max_new_tokens)
        tasks = [asyncio.ensure_future(user(u))
                 for u in range(traffic["users"])]
    if trace:
        tasks.append(asyncio.ensure_future(_trace_span(srv, t0, seconds,
                                                       tr)))
    if loop != "open":
        # the close ends a closed-loop or resident run: requests still in
        # flight are cut off, not waited for (the session is abandoned,
        # its pump stopped; the step under way finishes before the loop
        # closes)
        await asyncio.sleep(max(0.0, t_end - time.perf_counter()))
        if trace:
            await tasks.pop()
        for t in tasks:
            t.cancel()
        srv._pump_task.cancel()
        for e in await asyncio.gather(*tasks, srv._pump_task,
                                      return_exceptions=True):
            if e is not None and not isinstance(e, asyncio.CancelledError):
                log(f"a client failed: {e!r}")
        return recs, t0, None, setup_s, tr
    done, pending = await asyncio.wait(tasks, timeout=seconds + GRACE_S)
    for t in done:
        if t.exception() is not None:
            log(f"a client failed: {t.exception()!r}")
    if pending:
        for t in pending:
            t.cancel()
        log(f"{len(pending)} clients still waiting {GRACE_S} s after "
            f"the window closed")
        stats = None
    else:
        stats = await srv.drain()
    return recs, t0, stats, setup_s, tr


def _sample(recs: List[Rec], k: int, seed: int,
            resident: bool = False) -> List[Rec]:
    """k requests drawn from the seed, the one with most served tokens
    among them: of the finished requests, or for resident sessions, which
    the close cuts off, of every session that was served."""
    done = [r for r in recs if (r.tokens if resident else r.done)]
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: (len(done[i].tokens), done[i].prompt_len))
    rest = [i for i in range(len(done)) if i != longest]
    pick = TR.rng_for(seed, 3).permutation(rest)[: max(0, k - 1)]
    return [done[longest]] + [done[i] for i in sorted(pick)]


def decide(checks: dict, sampled_tokens: int) -> bool:
    """``correct``: every number compared within its limit, and some
    served tokens compared."""
    return all(v["value"] <= v["limit"] for v in checks.values()) \
        and sampled_tokens > 0


def check(config: dict, traffic: dict, seed: int, recs: List[Rec],
          control: bool = False) -> dict:
    """Widest gap of a sample of served tokens below the reference's best
    (and, with ``control``, of the control's top tokens at the same
    positions)."""
    sample = _sample(recs, traffic["check_sample"], seed,
                     traffic["loop"] == "resident")
    length = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    seqs = [(r.client, np.concatenate([r.prompt,
                                       np.asarray(r.tokens[:-1], np.int32)]))
            for r in sample]
    arch = spec.arch_of(config)
    ref = arch.Reference(config, seed)
    hs = ref.hidden(seqs, length)
    head = ref.head()
    gaps = [R.served_gaps(h, head, r.prompt_len, np.asarray(r.tokens))
            for h, r in zip(hs, sample)]
    out = {"sampled_requests": len(sample),
           "sampled_tokens": int(sum(len(g) for g in gaps)),
           "max_logit_gap": float(max((g.max() for g in gaps),
                                      default=math.inf))}
    if control:
        ctl = arch.Reference(config, seed, quant=True).hidden(seqs, length)
        cg = [R.control_gaps(h, c, head, r.prompt_len, len(r.tokens))
              for h, c, r in zip(hs, ctl, sample)]
        out["control_max_logit_gap"] = float(max((g.max() for g in cg),
                                                 default=math.inf))
    return out


def _memory(what: str) -> Optional[int]:
    """Log the device's bytes in use and peak; return the peak."""
    stats = jax.devices()[0].memory_stats() or {}
    if stats:
        log(f"memory {what}: in use {stats.get('bytes_in_use')}, peak "
            f"{stats.get('peak_bytes_in_use')}, limit "
            f"{stats.get('bytes_limit')}")
    return stats.get("peak_bytes_in_use")


def run_cell(bench: dict, cell: dict, seed: int, seconds: float,
             trace: bool, t_start: float,
             patch: Optional[Callable] = None,
             config: Optional[dict] = None,
             traffic: Optional[dict] = None,
             control: bool = False, save: str = "",
             keep: Optional[dict] = None) -> dict:
    """One run: the result line's object (see run.py).  ``config`` and
    ``traffic`` stand in for the cell's files, ``patch(engine)`` plants a
    fault (both for tests); ``control`` also reads the control's gap on
    the same sample (for setting the limit); ``save`` is a file to write
    the trace to as :func:`chipbench.serve_trace.read_xspace` keeps it,
    and ``keep`` a dict to copy the readers' context into (both for
    ``trace_layers.py``)."""
    config = config or spec.load_config(cell["config"])
    traffic = traffic or spec.load_traffic(cell["traffic"])
    arch = spec.arch_of(config)
    watch = CompileWatch()
    eng, sc = build(config, traffic, seed)
    _memory("after weights and bank")
    if patch is not None:
        patch(eng)
    asyncio.run(warm_up(eng, sc, traffic))
    gc.collect()
    _memory("after warm-up")
    items = TR.items(traffic, seed, seconds, config["vocab_size"])
    n_compiles = watch.count
    recs, t0, stats, setup_s, tr = asyncio.run(
        serve_window(eng, sc, traffic, items, seconds, trace, t_start))
    compiles = watch.count - n_compiles
    peak = _memory("after the window")
    window_end = t0 + seconds
    if traffic["loop"] == "open":
        failed = sum(not r.done for r in recs)
        late = [r.submitted - r.due for r in recs if r.submitted]
        log(f"generator lateness: max {max(late, default=0) * 1e3:.3f} ms "
            f"over {len(late)} requests")
    else:
        failed = sum(r.ended and not r.done for r in recs)
        log(f"{sum(not r.ended for r in recs)} requests in flight at the "
            f"close were cut off")
    log(f"window: {len(recs)} requests attempted, {failed} failed, "
        f"{compiles} compiles in the window, setup {setup_s:.3f} s")

    reduced = None
    if trace and "dir" in tr:
        reduced = _reduce_trace(tr, arch, save)
        shutil.rmtree(tr["dir"], ignore_errors=True)
    dev = jax.devices()[0]
    del eng, sc
    gc.collect()
    _memory("with the program's state freed")

    chk = check(config, traffic, seed, recs, control=control)
    limit = config["check"]["max_logit_gap"]
    checks = {"max_logit_gap": {"value": chk["max_logit_gap"],
                                "limit": limit},
              "failed_requests": {"value": failed, "limit": 0}}
    correct = decide(checks, chk["sampled_tokens"])

    ctx = {"records": recs, "window": (t0, window_end),
           "seconds": seconds, "setup_s": setup_s, "stats": stats,
           "config": config, "arch": arch, "traffic": traffic,
           "trace": reduced, "span": (tr.get("t0"), tr.get("t1")),
           "counters": tr.get("counters"),
           "peak": work.peak(dev.device_kind) if dev.platform == "tpu"
           else None}
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in spec.cell_metrics(bench, cell["name"], kind):
        v = spec.load_reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if keep is not None:
        keep.update(ctx)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": len(recs),
              "failed": failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    log(f"check: {chk['sampled_requests']} requests, "
        f"{chk['sampled_tokens']} served tokens compared")
    for k, v in checks.items():
        log(f"check {k}: {v['value']!r} (limit {v['limit']!r})")
    if control:
        # the control in the program's place: its gaps through the same
        # decision
        ctl = dict(checks, max_logit_gap={
            "value": chk["control_max_logit_gap"], "limit": limit})
        result["control_max_logit_gap"] = chk["control_max_logit_gap"]
        result["control_correct"] = decide(ctl, chk["sampled_tokens"])
    result["checks"] = checks
    return result


def _reduce_trace(tr: dict, arch, save: str = "") -> dict:
    """Reduce the traced span, bounded by the ``bench:window`` host span
    on the trace's own clock, with the architecture's kernels and scopes
    (and write what was read to ``save``, where given)."""
    raw = serve_trace.read_xspace(
        str(next(Path(tr["dir"]).rglob("*.xplane.pb"))))
    if save:
        Path(save).parent.mkdir(parents=True, exist_ok=True)
        traces.save(raw, save)
    win = next(h for h in raw["host"]
               if h[0] == traces.HOST_PREFIX + "window")
    red = serve_trace.reduce(raw, win[1], win[1] + win[2], arch.KERNELS,
                             PROGRAMS, arch.SCOPES)
    log(f"trace: busy {red['busy_s']:.4f} s of {red['window_s']:.4f} s; "
        f"programs {red['programs']}; kernels {red['kernels']}; "
        f"idle by span {red['idle_by_span']}")
    return red
