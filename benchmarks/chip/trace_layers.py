"""One traced run of a cell that also reports what the serving path's own
spans, scopes and counters read, beyond the cell's metrics.

    python3 benchmarks/chip/trace_layers.py --workload <cell> --seed <n> \
        --seconds <s> [--save <dir>]

The run is ``run.py --trace 1``'s (``harness.run_cell``: same window,
same traced span, same check), with the end-to-end metrics reported too
(what they read with the profiler on).  The last line of stdout is
``run.py``'s result with ``"serve"`` added: the occupancy of the decode
slots, the live share of the decode kernel's grid, the LoRA share of the
decode program, the counters' difference over the span, the decode
kernel's calls per layer that calls it and its seconds, the decode
program's time by scope and the idle time by host span.  ``--save``
writes the trace as :func:`chipbench.serve_trace.read_xspace` keeps it
(``<dir>/<cell>.<seed>.json.gz``), for recording a slice.  Needs a TPU;
exits 1 without one.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))


def run_traced(bench: dict, cell: dict, seed: int, seconds: float,
               t_start: float, save: str = "", config=None,
               traffic=None) -> dict:
    """``harness.run_cell`` with ``trace`` on, the end-to-end metrics and
    ``"serve"`` added.  ``config`` and ``traffic`` stand in for the cell's
    files (tests)."""
    from chipbench import harness, readers, serve_trace, spec

    ctx: dict = {}
    result = harness.run_cell(
        bench, cell, seed, seconds, True, t_start, config=config,
        traffic=traffic, keep=ctx,
        save=save and f"{save}/{cell['name']}.{seed}.json.gz")
    for m in spec.cell_metrics(bench, cell["name"], "end_to_end"):
        v = spec.load_reader(m["name"])(ctx)
        if v is not None:
            result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    tr = ctx["trace"] or {}
    result["serve"] = {
        "occupancy": serve_trace.occupancy(ctx),
        "kv_live_share": serve_trace.kv_live_share(ctx),
        "lora_share": serve_trace.lora_share(ctx),
        "counters": ctx["counters"],
        "kernel_decode_steps": readers.decode_steps(ctx),
        "kernels": tr.get("kernels"),
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
