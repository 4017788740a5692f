"""One traced run of a cell that also reads the serving path's own spans,
scopes and counters.

    python3 benchmarks/chip/trace_layers.py --workload <cell> --seed <n> \
        --seconds <s> [--save <dir>]

The run is ``run.py --trace 1``'s (same window, same traced span, same
check), with three additions: the session's counters are snapshotted at
the traced span's ends, the trace is reduced by
:func:`chipbench.serve_trace.reduce`, and the end-to-end metrics are
reported too (what they read with the profiler on).  The last line of
stdout is ``run.py``'s result with ``"serve"`` added: the occupancy of the decode
slots, the live share of the decode kernel's grid, the LoRA share of the
decode program, the counters' difference over the span, the decode
program's time by scope and the idle time by host span.  ``--save``
writes the trace as :func:`chipbench.serve_trace.read_xspace` keeps it
(``<dir>/<cell>.<seed>.json.gz``), for recording a slice.

It wraps three functions of :mod:`chipbench.harness` for the one run
(the span hooks, the traced span, the trace reduction) in place of
editing them, so ``run.py``'s runs stay as they are; ``PERF.md`` (open
question 4) lists the edits that would make these readings part of every
traced run.  Needs a TPU; exits 1 without one.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))


def counters(srv):
    """A snapshot of the session's counters, or None where the program
    keeps none."""
    read = getattr(srv._ses, "counters", None)
    return read() if read is not None else None


def run_traced(bench: dict, cell: dict, seed: int, seconds: float,
               t_start: float, save: str = "", config=None,
               traffic=None) -> dict:
    """``harness.run_cell`` with ``trace`` on, reading the serving path's
    spans, scopes and counters too; returns the result with ``"serve"``.
    ``config`` and ``traffic`` stand in for the cell's files (tests)."""
    import jax

    from chipbench import harness, serve_trace, spec, traces

    config = config or spec.load_config(cell["config"])
    traffic = traffic or spec.load_traffic(cell["traffic"])
    seen: dict = {}
    originals = (harness._span_hooks, harness._trace_span,
                 harness._reduce_trace)

    def hooks(srv):
        seen["srv"] = srv
        originals[0](srv)

    async def trace_span(t0: float, seconds: float, out: dict):
        # harness._trace_span, with the counters read at the span's ends
        a, b = harness.TRACE_SPAN
        await asyncio.sleep(max(0.0, t0 + a * seconds - time.perf_counter()))
        out["dir"] = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(out["dir"])
        out["t0"] = time.perf_counter()
        before = counters(seen["srv"])
        with jax.profiler.TraceAnnotation(traces.HOST_PREFIX + "window"):
            await asyncio.sleep(max(0.0, t0 + b * seconds
                                    - time.perf_counter()))
        seen["counters"] = serve_trace.counter_delta(
            before, counters(seen["srv"]))
        out["t1"] = time.perf_counter()
        jax.profiler.stop_trace()

    def reduce_trace(tr: dict) -> dict:
        raw = serve_trace.read_xspace(
            str(next(Path(tr["dir"]).rglob("*.xplane.pb"))))
        if save:
            Path(save).mkdir(parents=True, exist_ok=True)
            traces.save(raw, f"{save}/{cell['name']}.{seed}.json.gz")
        win = next(h for h in raw["host"]
                   if h[0] == traces.HOST_PREFIX + "window")
        red = serve_trace.reduce(raw, win[1], win[1] + win[2],
                                 harness.KERNELS, harness.PROGRAMS)
        harness.log(f"trace: idle by span {red['idle_by_span']}")
        seen["trace"] = red
        return red

    def both_kinds(bench, name, kind):
        # the end-to-end metrics too, to weigh what tracing costs them
        return (cell_metrics(bench, name, "end_to_end")
                + cell_metrics(bench, name, "per_layer"))

    cell_metrics = spec.cell_metrics
    harness._span_hooks, harness._trace_span, harness._reduce_trace = (
        hooks, trace_span, reduce_trace)
    spec.cell_metrics = both_kinds
    try:
        result = harness.run_cell(bench, cell, seed, seconds, True, t_start,
                                  config=config, traffic=traffic)
    finally:
        (harness._span_hooks, harness._trace_span,
         harness._reduce_trace) = originals
        spec.cell_metrics = cell_metrics
    ctx = {"trace": seen.get("trace"), "counters": seen.get("counters"),
           "traffic": traffic}
    tr = ctx["trace"] or {}
    kern = tr.get("kernels", {}).get("paged_attention", {})
    result["serve"] = {
        "occupancy": serve_trace.occupancy(ctx),
        "kv_live_share": serve_trace.kv_live_share(ctx),
        "lora_share": serve_trace.lora_share(ctx),
        "counters": ctx["counters"],
        "kernel_decode_steps": (kern.get("count", 0)
                                / config["num_hidden_layers"]),
        "kernel_seconds": kern.get("seconds"),
        "scopes": tr.get("scopes"),
        "idle_by_span": tr.get("idle_by_span")}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--save", default="")
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_compilation_cache_dir",
                      str(HERE.parents[1] / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from chipbench import spec

    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    if jax.devices()[0].platform != "tpu":
        print("trace_layers.py: needs a TPU", file=sys.stderr)
        return 1
    result = run_traced(bench, cell, args.seed, args.seconds, T_START,
                        args.save)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
