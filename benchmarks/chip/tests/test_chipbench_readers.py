"""The readers take what is specific to an architecture from its module:
on hand-made contexts of an architecture with attention in only some of
its layers, a kernel that attention does not bound, and a named scope
that only it lists."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from types import SimpleNamespace  # noqa: E402

import pytest  # noqa: E402

from chipbench import readers, serve_trace, spec  # noqa: E402
from chipbench.harness import Rec  # noqa: E402

DENSE = spec.load_arch("dense")
PEAK = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}

# 40 layers, 4 of them attention; an SSM kernel bounded by its own work
HYBRID = SimpleNamespace(
    KERNELS=dict(DENSE.KERNELS, ssm_scan={
        "module": "_decode_chunk_impl", "op": "%ssm_scan.", "work": "ssm"}),
    SCOPES=("lora", "ssm", "attn", "mlp"),
    attention_layers=lambda c: 4,
    prompt_work=lambda c, p, chunk: {"flops": 0},
    token_work=lambda c, ctx: {"flops": 50.0, "attn_flops": 20.0,
                               "attn_bytes": 4.0, "ssm_flops": 1.0,
                               "ssm_bytes": 30.0})


def _ctx(kernels, decode_s=0.5):
    r = Rec(client=0, prompt_len=3, budget=5, due=0.0)
    r.events = [(0.5, 1), (1.5, 4)]            # 4 output tokens in [1, 2)
    return {"arch": HYBRID, "config": {"num_hidden_layers": 40},
            "records": [r], "span": (1.0, 2.0), "peak": PEAK,
            "traffic": {"prefill_chunk": 16},
            "trace": {"kernels": kernels,
                      "programs": {"decode": {"seconds": decode_s}}}}


def _kernels(attn_s, ssm_s, calls=40):
    return {"paged_prefill": {"count": 0, "seconds": 0.0},
            "paged_attention": {"count": calls, "seconds": attn_s},
            "ssm_scan": {"count": 360, "seconds": ssm_s}}


def test_decode_step_counts_kernel_calls_per_attention_layer():
    # 40 decode-kernel calls over 4 attention layers are 10 steps, not 1
    ctx = _ctx(_kernels(2.0, 20.0))
    assert readers.decode_step_ms(ctx) == pytest.approx(50.0)
    # no attention layer, no decode kernel: nothing to read
    assert readers.decode_step_ms(
        dict(ctx, arch=SimpleNamespace(KERNELS=HYBRID.KERNELS,
                                       attention_layers=lambda c: 0))) is None
    assert readers.decode_step_ms(
        dict(ctx, trace={"kernels": {}, "programs": ctx["trace"]["programs"]}
             )) is None


def test_decode_steps_follow_the_kernel_the_architecture_marks():
    # an architecture whose decode kernel has another name: its calls
    # count the steps, and an unmarked kernel's do not
    arch = SimpleNamespace(
        KERNELS={"ragged_decode": {"module": "_decode_chunk_impl",
                                   "op": "%ragged_decode.", "work": "attn",
                                   "per_step": True},
                 "paged_attention": {"module": "_decode_chunk_impl",
                                     "op": "%paged_attention.",
                                     "work": "attn"}},
        attention_layers=lambda c: 4)
    ctx = dict(_ctx({"ragged_decode": {"count": 20, "seconds": 1.0},
                     "paged_attention": {"count": 400, "seconds": 1.0}}),
               arch=arch)
    assert readers.decode_steps(ctx) == pytest.approx(5.0)
    assert readers.decode_step_ms(ctx) == pytest.approx(100.0)
    # no kernel marked: nothing counts the steps
    unmarked = SimpleNamespace(
        KERNELS={"paged_attention": arch.KERNELS["paged_attention"]},
        attention_layers=lambda c: 4)
    assert readers.decode_steps(dict(ctx, arch=unmarked)) is None
    assert readers.decode_step_ms(dict(ctx, arch=unmarked)) is None


def test_roofline_sums_only_the_kernels_of_its_work():
    # attention: 4 tokens x max(20 / 100, 4 / 10) = 1.6 s of 2.0 + 0.0 s;
    # the SSM kernel's 20 s is not attention's
    ctx = _ctx(_kernels(2.0, 20.0))
    assert readers.roofline(ctx, "attn") == pytest.approx(80.0)
    # the SSM kernel against its own work: 4 x max(1 / 100, 30 / 10) = 12 s
    assert readers.roofline(ctx, "ssm") == pytest.approx(60.0)
    # with no time in the attention kernels there is nothing to read
    assert readers.roofline(_ctx(_kernels(0.0, 20.0)), "attn") is None


def test_scope_of_takes_a_scope_only_the_architecture_lists():
    path = "jit(_decode_chunk_impl)/while/body/ssm/dot_general"
    assert serve_trace.scope_of(path, HYBRID.SCOPES) == "ssm"
    assert serve_trace.scope_of(path, DENSE.SCOPES) == "other"
    assert serve_trace.scope_of(path.replace("/ssm/", "/ssm/lora/"),
                                HYBRID.SCOPES) == "lora"
    ops = [["%fusion.1 = op", 0, 10, path],
           ["%fusion.2 = op", 10, 5, "jit(_decode_chunk_impl)/attn/dot"]]
    got = serve_trace.decode_scopes(
        ops, [["jit__decode_chunk_impl(1)", 0, 20]], 0, 20,
        "_decode_chunk_impl", HYBRID.SCOPES)
    assert got == {"lora": 0.0, "ssm": pytest.approx(10e-9),
                   "attn": pytest.approx(5e-9), "mlp": 0.0, "other": 0.0}
