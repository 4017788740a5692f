"""One run of one benchmark cell on the chip.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell, its model configuration, its traffic and its metrics are named
in ``BENCHMARK.json`` and found as files under ``benchmarks/chip``.  The
run loads, warms up the cell's own programs, measures for ``--seconds``,
checks what the window served against a float32 reference, and prints
one JSON object as the last line of stdout.  ``--trace 0`` reports the
end-to-end metrics, ``--trace 1`` the per-layer ones read from a profiler
trace of part of the window.  The numbers compared for ``correct`` are
printed, each beside its limit, as the last lines of stderr and under
``checks`` in the result.

Without a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result.  JAX's persistent compilation cache lives in
``<checkout>/.jax_cache``.
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
CACHE = HERE.parents[1] / ".jax_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from chipbench import harness, spec

    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"run.py: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 1
    result = harness.run_cell(bench, cell, args.seed, args.seconds,
                              bool(args.trace), T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
