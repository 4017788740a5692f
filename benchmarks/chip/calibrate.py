"""Readings for a cell's correctness limit, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 11,12,13 --seconds 15

For each seed, in one process: a run of the cell at its own load for
``--seconds`` (every request due in it finished), then on the same
sample of served tokens the widest gap below the float32 reference's
best of (a) the tokens the program served and (b) the tokens the control
puts first, the control being the reference with float8 e4m3 matmul
inputs; each passes through the run's own decision, so the control's
line has to read ``"control_correct": false``.  One JSON line per seed.  The limit in the configuration file
lies between the largest program reading over a dozen seeds or more and
the smallest control reading (PERF.md gives both).
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_compilation_cache_dir",
                      str(HERE.parents[1] / ".jax_cache"))
    from chipbench import harness, spec

    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: needs a TPU", file=sys.stderr)
        return 1
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    for seed in [int(s) for s in args.seeds.split(",")]:
        r = harness.run_cell(bench, cell, seed, args.seconds, False,
                             time.perf_counter(), control=True)
        print(json.dumps({
            "seed": seed, "attempted": r["attempted"], "failed": r["failed"],
            "program_max_logit_gap": r["checks"]["max_logit_gap"]["value"],
            "control_max_logit_gap": r["control_max_logit_gap"],
            "program_correct": r["correct"],
            "control_correct": r["control_correct"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
