"""The trace reduction, on hand-made events and on a recorded slice of a
v5e trace of OLMo-1B serving long prompts (75 ms across the end of a
prefill dispatch and the start of a decode dispatch)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


import pytest

from chipbench import harness, serve_trace, spec, traces

SLICE = Path(__file__).resolve().parents[1] / "testdata" / \
    "olmo_trace_slice.json.gz"


def _op(name, start, dur):
    return [f"%{name} = op", start, dur]


def test_busy_gaps_and_attribution_by_hand():
    tr = {"ops": [_op("fusion.1", 0, 10), _op("fusion.2", 5, 10),
                  _op("paged_attention.3", 20, 10),
                  _op("while.4", 0, 30)],
          "modules": [["jit__decode_chunk_impl(1)", 0, 30]],
          "host": []}
    r = traces.reduce(tr, 0, 40, {"attn": {"module": "_decode_chunk_impl",
                                           "op": "%paged_attention."}},
                      {"decode": "_decode_chunk_impl"})
    assert r["busy_s"] == pytest.approx(30e-9)       # [0, 30) covered
    assert r["window_s"] == pytest.approx(40e-9)
    assert r["kernels"]["attn"] == {"count": 1, "seconds": pytest.approx(1e-8)}
    assert r["programs"]["decode"]["count"] == 1
    assert r["programs"]["decode"]["seconds"] == pytest.approx(30e-9)
    # loops hold other ops and are left out of the per-op time
    assert [n for n, _ in r["device_ops"]] == [
        "_decode_chunk_impl/%fusion", "_decode_chunk_impl/%paged_attention"]
    assert r["device_ops"][0][1] == pytest.approx(20e-9)
    # the one idle gap; naming it is serve_trace.reduce's
    assert serve_trace.idle_intervals(tr["ops"], 0, 40) == [(30, 40)]
    assert "idle_gaps" not in r


def test_clipping_to_the_span():
    tr = {"ops": [_op("fusion.1", 0, 100)],
          "modules": [["jit__decode_chunk_impl(1)", 0, 100],
                      ["jit__decode_chunk_impl(1)", 40, 10]],
          "host": []}
    r = traces.reduce(tr, 20, 60, {}, {"decode": "_decode_chunk_impl"})
    assert r["busy_s"] == pytest.approx(40e-9)
    # only the execution wholly inside counts per dispatch
    assert r["programs"]["decode"]["count"] == 1
    assert r["programs"]["decode"]["seconds_each"] == [pytest.approx(1e-8)]
    assert r["programs"]["decode"]["seconds"] == pytest.approx(50e-9)


def _busy_by_counting(ops, lo, hi, step=1000):
    """Busy time on a 1 us grid: an independent check of the union."""
    n = 0
    for t in range(int(lo), int(hi), step):
        if any(e[1] <= t < e[1] + e[2] for e in ops):
            n += step
    return n * 1e-9


def test_recorded_slice():
    tr = traces.load(str(SLICE))
    win = [h for h in tr["host"] if h[0] == "bench:window"][0]
    lo, hi = win[1], win[1] + win[2]
    r = traces.reduce(tr, lo, hi, spec.load_arch("dense").KERNELS,
                      harness.PROGRAMS)
    assert r["window_s"] == pytest.approx(0.075)
    assert r["busy_s"] == pytest.approx(_busy_by_counting(tr["ops"], lo, hi),
                                        rel=2e-3)
    assert 0.05 < r["busy_s"] < 0.075
    # two decode-kernel calls of 15.85 ms each start inside the slice
    k = r["kernels"]["paged_attention"]
    assert k["count"] == 2
    assert k["seconds"] == pytest.approx(
        sum(min(e[1] + e[2], hi) - e[1] for e in tr["ops"]
            if e[0].startswith("%paged_attention.")) * 1e-9)
    assert r["kernels"]["paged_prefill"]["count"] == 0
    # the prefill dispatch began before the slice and the decode one ends
    # after it: neither is wholly inside
    assert r["programs"]["prefill"]["count"] == 0
    assert r["programs"]["decode"]["count"] == 0
    assert r["device_ops"][0][0] == "_decode_chunk_impl/%paged_attention"
    gaps = serve_trace.idle_intervals(tr["ops"], lo, hi)
    assert len(gaps) >= 10
    assert sum(b - a for a, b in gaps) * 1e-9 == pytest.approx(
        r["window_s"] - r["busy_s"])
