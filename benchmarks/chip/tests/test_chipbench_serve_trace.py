"""The readers of the serving path's own spans, scopes and counters
(``chipbench.serve_trace``): on hand-made events, on the recorded slices
of the first benchmark and of a chip run with the spans and scopes in
place, on a profile of a tiny server on the CPU, and through a whole
traced run at a tiny size."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import asyncio  # noqa: E402
import struct  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import harness, serve_trace, spec, traces  # noqa: E402
from chipbench_tiny import TINY_CONFIG, TINY_TRAFFIC  # noqa: E402

DENSE = spec.load_arch("dense")

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"
DECODE = "jit(_decode_chunk_impl)/while/body"


def _op(name, start, dur, scope=""):
    return [f"%{name} = op", start, dur, scope]


def test_scope_of_takes_the_innermost_named_scope():
    def scope(path):
        return serve_trace.scope_of(path, DENSE.SCOPES)

    assert scope(f"{DECODE}/attn/lora/dot_general") == "lora"
    assert scope(f"{DECODE}/mlp/lora/gather") == "lora"
    assert scope(f"{DECODE}/attn/dot_general") == "attn"
    assert scope(f"{DECODE}/mlp/mul") == "mlp"
    assert scope(f"{DECODE}/argmax") == "other"
    assert scope("") == "other"
    # a scope name must be a whole path component
    assert scope(f"{DECODE}/attn_out/lorax") == "other"


def _varint(n):
    out = b""
    while True:
        out += bytes([n & 0x7F | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _pb(*fields):
    """A serialized protobuf message of ``(field number, value)`` pairs:
    ints as varints, floats as fixed64, str and bytes length-delimited."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        elif isinstance(v, float):
            out += _varint(num << 3 | 1) + struct.pack("<d", v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def test_op_scopes_decodes_the_event_metadata(tmp_path):
    """An ``XSpace`` built by hand: the device plane's op metadata carries
    the scope path as a string stat or as a reference to a stat
    metadata's name; other stats and other planes are passed over."""
    def stat_md(i, name):
        return (5, _pb((1, i), (2, _pb((1, i), (2, name)))))

    def event_md(i, name, *stats):
        return (4, _pb((1, i), (2, _pb((1, i), (2, name),
                                       *[(5, st) for st in stats]))))

    path = f"{DECODE}/attn/lora/gather:"
    device = _pb((1, 7), (2, "/device:TPU:0"),
                 stat_md(1, "flops"), stat_md(2, "tf_op"),
                 stat_md(300, f"{DECODE}/mlp/dot_general:"),
                 event_md(1, "%fusion.1 = f32[2] fusion()",
                          _pb((1, 1), (2, 3.5)), _pb((1, 2), (5, path))),
                 event_md(2, "%fusion.2 = f32[2] fusion()",
                          _pb((1, 2), (7, 300))),
                 event_md(3, "%copy.3 = f32[2] copy()", _pb((1, 1), (3, 9))))
    host = _pb((2, "/host:CPU"), stat_md(2, "tf_op"),
               event_md(1, "serve:step", _pb((1, 2), (5, "x"))))
    f = tmp_path / "t.xplane.pb"
    f.write_bytes(_pb((1, host), (1, device), (4, "hostname")))
    assert serve_trace.op_scopes(str(f), "/device:TPU:0") == {
        "%fusion.1 = f32[2] fusion()": path,
        "%fusion.2 = f32[2] fusion()": f"{DECODE}/mlp/dot_general:"}
    assert serve_trace.op_scopes(str(f), "/device:TPU:1") == {}


def test_gaps_go_to_the_innermost_serve_span():
    tr = {"ops": [_op("fusion.1", 0, 10, f"{DECODE}/attn/lora/dot"),
                  _op("fusion.2", 20, 10, f"{DECODE}/attn/dot"),
                  _op("fusion.3", 40, 5, f"{DECODE}/mlp/dot"),
                  _op("while.4", 40, 5, DECODE),
                  _op("fusion.5", 60, 10, "jit(_prefill_chunk_impl)/lora")],
          "modules": [["jit__decode_chunk_impl(1)", 0, 45],
                      ["jit__prefill_chunk_impl(2)", 60, 10]],
          "host": [["bench:session.step", 8, 40],
                   ["bench:scheduler.admit", 50, 5]],
          "serve": [["serve:step", 8, 40, "t1", 1],
                    ["serve:flush", 9, 6, "t1", 1],
                    ["serve:sync", 10, 4, "t1", 1],
                    ["serve:plan", 16, 6, "t1", 1],
                    ["serve:deliver", 31, 3, "t2", 1],
                    ["serve:observe", 32, 8, "t1", 1]]}
    r = serve_trace.reduce(tr, 0, 80, {}, harness.PROGRAMS, DENSE.SCOPES)
    # gaps: [10, 20) in step > flush > sync (4) and plan (4): the first
    # child overlapping most wins, then its child; [30, 40) overlaps
    # observe (8) more than deliver (3); [45, 60) overlaps step (3) and
    # the bench span admit (5), which names nothing; [70, 80) none
    assert r["idle_gaps"] == [
        ["serve:step", pytest.approx(15e-9)],
        ["serve:sync", pytest.approx(10e-9)],
        ["serve:observe", pytest.approx(10e-9)],
        [serve_trace.NO_SPAN, pytest.approx(10e-9)]]
    assert r["idle_by_span"] == {
        "serve:step": pytest.approx(15e-9),
        "serve:sync": pytest.approx(10e-9),
        "serve:observe": pytest.approx(10e-9),
        serve_trace.NO_SPAN: pytest.approx(10e-9)}
    # the decode program's op time by scope; the loop and the prefill op
    # are not counted
    assert r["scopes"] == {"lora": pytest.approx(10e-9),
                           "attn": pytest.approx(10e-9),
                           "mlp": pytest.approx(5e-9), "other": 0.0}
    assert serve_trace.lora_share({"trace": r}) == pytest.approx(40.0)


def test_bench_spans_name_no_gap():
    tr = {"ops": [_op("fusion.1", 0, 10, DECODE), _op("fusion.2", 30, 10,
                                                     DECODE)],
          "modules": [["jit__decode_chunk_impl(1)", 0, 40]],
          "host": [["bench:window", 0, 50], ["bench:session.step", 10, 10]],
          "serve": [["serve:step", 40, 10, "t1", 1]]}
    r = serve_trace.reduce(tr, 0, 50, {}, harness.PROGRAMS, DENSE.SCOPES)
    assert r["idle_gaps"] == [[serve_trace.NO_SPAN, pytest.approx(20e-9)],
                              ["serve:step", pytest.approx(10e-9)]]
    assert serve_trace.SpanNamer([])(10, 20) == serve_trace.NO_SPAN


def test_spans_of_other_threads_do_not_nest():
    name = serve_trace.SpanNamer(
        [["serve:step", 0, 100, "a", 1], ["serve:plan", 0, 30, "a", 1],
         ["serve:intake", 0, 100, "b", 1]])
    # intake (thread b) is no child of step: both are top-level, and the
    # first of equal overlaps is taken before descending
    assert name(5, 20) == "serve:plan"
    assert name(40, 50) == "serve:step"


def test_recorded_slice_keeps_every_other_output():
    tr = traces.load(str(TESTDATA / "olmo_trace_slice.json.gz"))
    win = [h for h in tr["host"] if h[0] == "bench:window"][0]
    lo, hi = win[1], win[1] + win[2]
    old = traces.reduce(tr, lo, hi, DENSE.KERNELS, harness.PROGRAMS)
    new = serve_trace.reduce(tr, lo, hi, DENSE.KERNELS, harness.PROGRAMS,
                             DENSE.SCOPES)
    for key in old:
        assert new[key] == old[key], key
    # without serve: spans no gap has a name; the ten longest are kept
    gaps = sorted(b - a for a, b in serve_trace.idle_intervals(
        [e for e in tr["ops"] if e[1] < hi and e[1] + e[2] > lo], lo, hi))
    assert new["idle_gaps"] == [
        [serve_trace.NO_SPAN, pytest.approx(g * 1e-9)]
        for g in gaps[::-1][:10]]
    assert sum(new["idle_by_span"].values()) == pytest.approx(
        new["window_s"] - new["busy_s"])
    # ops recorded without a scope count as "other"
    assert new["scopes"]["lora"] == 0.0 and new["scopes"]["other"] > 0


def test_recorded_chip_slice_with_spans_and_scopes():
    """60 ms of a traced OLMo-1B ``decode-long`` run on a v5e, with the
    serving path's spans and scopes: the end of one decode dispatch, the
    host's gap before the next, and its start."""
    tr = traces.load(str(TESTDATA / "olmo_serve_slice.json.gz"))
    win = [h for h in tr["host"] if h[0] == "bench:window"][0]
    lo, hi = win[1], win[1] + win[2]
    r = serve_trace.reduce(tr, lo, hi, DENSE.KERNELS, harness.PROGRAMS,
                           DENSE.SCOPES)
    # the scope path reached the ops as the chip wrote it: every named
    # scope holds time, and the decode kernel runs under attn
    sc = r["scopes"]
    assert sc["lora"] > 0 and sc["attn"] > 0 and sc["mlp"] > 0
    kernel = r["kernels"]["paged_attention"]
    assert kernel["count"] == 2
    assert sc["attn"] >= kernel["seconds"]
    assert all(serve_trace.scope_of(e[3], DENSE.SCOPES) == "attn"
               for e in tr["ops"]
               if e[0].startswith("%paged_attention."))
    # the scopes split the decode program's op time, loops left out
    ops = [e for e in tr["ops"] if e[1] < hi and e[1] + e[2] > lo]
    total = sum(min(e[1] + e[2], hi) - max(e[1], lo)
                for e in traces._in_module(ops, tr["modules"],
                                           "_decode_chunk_impl")
                if traces.op_name(e) != "%while") * 1e-9
    assert sum(sc.values()) == pytest.approx(total)
    # the host was marshalling the next dispatch's tables through the
    # slice's one long gap (block growth had flushed the pipeline)
    assert r["idle_gaps"][0][0] == "serve:tables"
    assert r["idle_gaps"][0][1] == pytest.approx(9.68e-3, rel=1e-3)
    assert {s[0] for s in tr["serve"]} >= {
        "serve:step", "serve:flush", "serve:sync", "serve:admit",
        "serve:plan", "serve:tables", "serve:dispatch", "serve:observe",
        "serve:deliver"}


def test_counter_readers():
    c = {"steps": 10, "decode_row_steps": 150, "kv_grid_blocks": 1000,
         "kv_live_blocks": 400}
    ctx = {"counters": c, "traffic": {"slots": 20}}
    assert serve_trace.occupancy(ctx) == pytest.approx(75.0)
    assert serve_trace.kv_live_share(ctx) == pytest.approx(40.0)
    # a program without counters, or a span with no decode step
    assert serve_trace.occupancy({"counters": None}) is None
    assert serve_trace.kv_live_share(
        {"counters": dict(c, kv_grid_blocks=0)}) is None
    assert serve_trace.counter_delta(None, c) is None
    assert serve_trace.counter_delta(
        dict.fromkeys(c, 1), c) == {k: v - 1 for k, v in c.items()}
    assert serve_trace.lora_share({"trace": None}) is None


def _tiny_engine():
    sys.path.insert(0, str(spec.ROOT / "src"))
    from repro.serving.engine import ServeConfig

    eng, _ = harness.build(TINY_CONFIG, TINY_TRAFFIC, 3)
    return eng, ServeConfig(batch_size=2, block_size=8, num_blocks=33,
                            max_blocks_per_slot=7, prefill_chunk=8,
                            scan_chunk=4)


def test_profiled_server_spans_nest_in_steps(tmp_path):
    """Every phase span of a profiled tiny server lies inside a
    ``serve:step`` of its thread with the same ``step``; the pump's own
    spans carry a step too."""
    import jax

    from repro.launch.serve import AsyncServer
    from repro.serving.engine import Request

    eng, sc = _tiny_engine()

    async def serve():
        async with AsyncServer(eng, sc) as srv:
            async def one(c):
                rid = await srv.submit(Request(
                    f"c{c}", np.arange(3, 14, dtype=np.int32),
                    max_new_tokens=9))
                async for _ in srv.stream(rid):
                    pass
            await asyncio.gather(one(0), one(1))

    asyncio.run(serve())                       # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    asyncio.run(serve())
    jax.profiler.stop_trace()
    raw = serve_trace.read_xspace(str(next(tmp_path.rglob("*.xplane.pb"))))
    spans = raw["serve"]
    names = {s[0] for s in spans}
    assert {"serve:step", "serve:bank_swap", "serve:admit", "serve:plan",
            "serve:tables", "serve:dispatch", "serve:sync", "serve:observe",
            "serve:intake", "serve:deliver"} <= names
    steps = [s for s in spans if s[0] == "serve:step"]
    # intake before the first step is tagged step 0
    assert all(isinstance(s[4], int) and s[4] >= 0 for s in spans)
    for s in spans:
        if s[0] in ("serve:step", "serve:intake", "serve:deliver"):
            continue
        assert any(p[3] == s[3] and p[4] == s[4] and p[1] <= s[1]
                   and s[1] + s[2] <= p[1] + p[2] for p in steps), s
    # the pump runs on another thread than the steps
    assert {s[3] for s in spans if s[0] == "serve:intake"}.isdisjoint(
        {s[3] for s in steps})


def test_traced_run_reads_the_counters():
    """A whole traced run at a tiny size, closed loop, more users than
    slots: the counters read over the traced span give occupancy and the
    live share of the decode grid, in the cell's per-layer metrics and in
    ``trace_layers.py``'s additions; the CPU trace holds no device op, so
    the LoRA share reads nothing."""
    import trace_layers

    traffic = dict(TINY_TRAFFIC, loop="closed", users=16, stagger_s=0.0,
                   context_limit=128,
                   output_len={"median": 70, "sigma": 0.1, "min": 60,
                               "max": 80})
    config = dict(TINY_CONFIG, serving={"block_size": 8,
                                        "kv_pool_blocks": 65})
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, "yi-6b-l16.gen-closed")
    r = trace_layers.run_traced(bench, cell, 2**31 + 5, 2.0,
                                time.perf_counter(), config=config,
                                traffic=traffic)
    assert r["correct"]
    s = r["serve"]
    c = s["counters"]
    assert c["steps"] > 0 and c["decode_dispatches"] > 0
    assert 0 < s["occupancy"] <= 100
    assert 0 < s["kv_live_share"] <= 100
    assert s["lora_share"] is None
    m = r["metrics"]
    assert m["occupancy.tok"]["value"] == s["occupancy"]
    assert m["kv_live_share.tok"]["value"] == s["kv_live_share"]
    assert "lora_share.tok" not in m
    assert "setup_s" in m and "out_tok_s" in m
    # the harness is left as it was
    assert harness._trace_span.__module__ == "chipbench.harness"
