"""Find the highest arrival rate an open-loop cell sustains, on the chip.

    python3 benchmarks/chip/sweep.py --workload <cell> --rates 0.5,1,1.5 \
        --seconds 51 [--seed 7]

One process loads the cell once and serves its traffic at each rate in
turn, each for ``--seconds`` on a fresh session, and prints one JSON line
per rate: requests due and finished, TTFT p50/p90, and the median of
TTFT per prefill dispatch its prompt needs (which takes out the prompt's
own length) over the first and the last third of the requests by due
time.  A rate is sustained when every request finishes and the last
third's median is at most ``GROWTH`` times the first third's (no growing
backlog); the sweep stops after the first rate that is not.  The last
line gives the highest sustained rate (the knee) and 0.8 of it, the rate
the cell's traffic file states.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
# the last third may read this much above the first before the rate
# counts as growing a backlog (about the spread of TTFT p50 between runs)
GROWTH = 1.10


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    jax.config.update("jax_compilation_cache_dir",
                      str(HERE.parents[1] / ".jax_cache"))
    from chipbench import harness, spec
    from chipbench import traffic as TR

    if jax.devices()[0].platform != "tpu":
        print("sweep.py: needs a TPU", file=sys.stderr)
        return 1
    cell = spec.find_cell(spec.load_benchmark(), args.workload)
    config = spec.load_config(cell["config"])
    traffic = spec.load_traffic(cell["traffic"])
    eng, sc = harness.build(config, traffic, args.seed)
    asyncio.run(harness.warm_up(eng, sc, traffic))
    knee = None
    for rate in [float(r) for r in args.rates.split(",")]:
        traffic["rate_per_s"] = rate
        items = TR.open_loop(traffic, args.seed, args.seconds,
                             config["vocab_size"])
        recs, *_ = asyncio.run(harness.serve_window(
            eng, sc, traffic, items, args.seconds, False, T_START))
        ttft = [r.ttft for r in recs]
        per = [r.ttft / -(-r.prompt_len // traffic["prefill_chunk"])
               for r in recs]
        third = max(1, len(recs) // 3)
        first = float(np.median(per[:third]))
        last = float(np.median(per[-third:]))
        done = sum(r.done for r in recs)
        ok = done == len(recs) and last <= GROWTH * first
        print(json.dumps({
            "rate_per_s": rate, "due": len(recs), "finished": done,
            "ttft_p50_ms": float(np.percentile(ttft, 50)) * 1e3,
            "ttft_p90_ms": float(np.percentile(ttft, 90)) * 1e3,
            "first_third_ms_per_dispatch": first * 1e3,
            "last_third_ms_per_dispatch": last * 1e3,
            "sustained": bool(ok)}), flush=True)
        if not ok:
            break
        knee = rate
    print(json.dumps({"knee_per_s": knee,
                      "cell_rate_per_s": None if knee is None
                      else 0.8 * knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
