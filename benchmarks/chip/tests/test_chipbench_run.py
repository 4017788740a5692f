"""A whole run on the CPU at a tiny size: everything after the look for
a chip, with the timed path broken underneath for each fault a serving
cell can have, and the float8 control."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import subprocess
import time

import pytest

from chipbench import harness, spec  # noqa: E402
from chipbench_tiny import TINY_CONFIG, TINY_TRAFFIC  # noqa: E402

RUN = Path(__file__).resolve().parents[1] / "run.py"


def _token_altered(eng):
    """Every decoded token comes out one id higher than the model chose."""
    orig, V = eng._decode_chunk, eng.cfg.vocab_size

    def bad(*a, **k):
        out, *rest = orig(*a, **k)
        return ((out + 1) % V, *rest)
    eng._decode_chunk = bad


def _state_unchanged(eng):
    """Decode steps hand back the K/V cache they were given."""
    orig = eng._decode_chunk

    def bad(params, bank, ids, cache, *a, **k):
        out, _, *rest = orig(params, bank, ids, cache, *a, **k)
        return (out, cache, *rest)
    eng._decode_chunk = bad


def _half_batch(eng):
    """Half of the rows are left out: they return the tokens decoded for
    the other half."""
    orig = eng._decode_chunk

    def bad(*a, **k):
        out, *rest = orig(*a, **k)
        half = out.shape[1] // 2
        return (out.at[:, half:].set(out[:, :half]), *rest)
    eng._decode_chunk = bad


def _wrong_adapter(eng):
    """Half of the rows are served with the next client's adapter."""
    for name in ("_prefill_chunk", "_decode_chunk"):
        orig = getattr(eng, name)

        def bad(params, bank, ids, *a, _orig=orig, **k):
            half = ids.shape[0] // 2
            ids = ids.at[half:].set((ids[half:] + 1) % 4)
            return _orig(params, bank, ids, *a, **k)
        setattr(eng, name, bad)


FAULTS = {"sound": None, "token_altered": _token_altered,
          "state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "wrong_adapter": _wrong_adapter}


RESIDENT = dict(TINY_TRAFFIC, loop="resident", sessions=4)
# cell: (traffic, seconds, the end-to-end metrics it reports)
LOOPS = {"tiny.open": (TINY_TRAFFIC, 0.016,
                       {"ttft_p50_ms", "ttft_p90_ms", "setup_s"}),
         "tiny.resident": (RESIDENT, 0.5, {"tpot_p90_ms", "setup_s"})}


def _bench(cell_name: str, metrics: set) -> dict:
    """BENCHMARK.json with one more cell that reports ``metrics``."""
    bench = spec.load_benchmark()
    bench["workloads"].append({"name": cell_name, "config": "tiny",
                               "traffic": "tiny", "chips": 1, "why": "test"})
    have = {m["name"]: m for m in bench["end_to_end"]}
    for name in metrics:
        m = have.get(name)
        if m is None:
            bench["end_to_end"].append({
                "name": name, "unit": "ms", "better": "lower", "bound": 0.25,
                "source": "host_clock", "workloads": [cell_name]})
        elif "workloads" in m:
            m["workloads"].append(cell_name)
    return bench


def _run(cell_name, seed, patch=None, control=False):
    traffic, seconds, metrics = LOOPS[cell_name]
    bench = _bench(cell_name, metrics)
    cell = spec.find_cell(bench, cell_name)
    return harness.run_cell(bench, cell, seed, seconds, False,
                            time.perf_counter(), patch=patch,
                            config=dict(TINY_CONFIG), traffic=dict(traffic),
                            control=control)


@pytest.mark.parametrize("fault", list(FAULTS))
def test_correct_only_when_sound(fault):
    # open loop: all 16 requests due within 16 ms, so every slot fills
    res = _run("tiny.open", 2**31 + 77, FAULTS[fault])
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] == 16 and res["failed"] == 0
    assert set(res["metrics"]) == LOOPS["tiny.open"][2]
    gap = res["checks"]["max_logit_gap"]
    assert res["correct"] is (fault == "sound"), gap
    if fault != "sound":
        assert gap["value"] > 3 * gap["limit"]


@pytest.mark.parametrize("fault", list(FAULTS))
def test_resident_correct_only_when_sound(fault):
    """Resident sessions decode through the window and are cut off at its
    close; what they were served is checked."""
    res = _run("tiny.resident", 2**31 + 78, FAULTS[fault])
    assert res["attempted"] == 4 and res["failed"] == 0
    assert set(res["metrics"]) == LOOPS["tiny.resident"][2]
    assert 0 < res["metrics"]["tpot_p90_ms"]["value"] < 500
    gap = res["checks"]["max_logit_gap"]
    assert res["correct"] is (fault == "sound"), gap
    if fault != "sound":
        assert gap["value"] > 3 * gap["limit"]


@pytest.mark.parametrize("cell_name", list(LOOPS))
def test_control_fails_the_limit(cell_name):
    """The reference computed with float8 matmul inputs, in the program's
    place on the tokens a sound run served, reads a gap above the limit
    that run passes, and the same decision calls it not correct."""
    res = _run(cell_name, 5, control=True)
    limit = res["checks"]["max_logit_gap"]["limit"]
    assert res["correct"] is True
    assert res["control_max_logit_gap"] > limit
    assert res["control_correct"] is False


def test_no_tpu_no_result():
    """Without a TPU the run exits non-zero and prints no result."""
    p = subprocess.run([sys.executable, str(RUN), "--workload",
                        "yi-6b-l16.gen-closed", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], capture_output=True, text=True,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU" in p.stderr


def test_closed_loop():
    """Users send again as each reply completes; the close cuts off what
    is in flight, and what finished is checked."""
    traffic = dict(TINY_TRAFFIC, loop="closed", users=4, stagger_s=0.001)
    bench = spec.load_benchmark()
    cell = dict(spec.find_cell(bench, "yi-6b-l16.gen-closed"))
    res = harness.run_cell(bench, cell, 3, 1.0, False, time.perf_counter(),
                           config=dict(TINY_CONFIG), traffic=traffic)
    assert res["correct"] and res["failed"] == 0
    assert set(res["metrics"]) == {"out_tok_s", "setup_s"}
    assert res["metrics"]["out_tok_s"]["value"] > 0


def test_nothing_finished_is_not_correct():
    """A window too short for any reply to finish compares nothing: the
    run and its control both read not correct, and nothing raises."""
    traffic = dict(TINY_TRAFFIC, loop="closed", users=4, stagger_s=0.001)
    bench = spec.load_benchmark()
    cell = dict(spec.find_cell(bench, "yi-6b-l16.gen-closed"))
    res = harness.run_cell(bench, cell, 3, 0.001, False, time.perf_counter(),
                           config=dict(TINY_CONFIG), traffic=traffic,
                           control=True)
    assert res["correct"] is False and res["control_correct"] is False
    assert res["checks"]["max_logit_gap"]["value"] == float("inf")
