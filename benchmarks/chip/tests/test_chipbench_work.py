"""Work counts (the dense architecture's, added up over a span by
``chipbench.work``) and peaks against numbers worked out by hand."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import pytest

from chipbench import spec, work
from chipbench.harness import Rec

DENSE = spec.load_arch("dense")
OLMO = spec.load_config("olmo-1b")
YI = spec.load_config("yi-6b-l16")


def test_matmul_params():
    # 16 layers x (4 x 2048^2 + 3 x 2048 x 8192) + 2048 x 50304
    assert DENSE.matmul_params(OLMO) == 1_176_764_416
    # 16 x (2 x 4096^2 + 2 x 4096 x 512 + 3 x 4096 x 11008) + 4096 x 64000
    assert DENSE.matmul_params(YI) == 16 * 173_015_040 + 262_144_000


def test_lora_flops():
    # 2 r (d_in + d_out): 4 x (2048+2048) + 3 x (2048+8192) = 47,104 per
    # layer, times 2 x 16, times 16 layers
    assert DENSE.lora_flops_per_token(OLMO) == 24_117_248
    # Yi: (4096+4096) x 2 + (4096+512) x 2 + 3 x (4096+11008) = 70,912
    # per layer (1,134,592 adapter parameters at r 16), x 2 x 16 x 16
    assert DENSE.lora_flops_per_token(YI) == 36_306_944


def test_attention_counts():
    assert DENSE.attn_flops(OLMO, 100) == 4 * 100 * 16 * 128 * 16
    assert DENSE.kv_bytes(OLMO, 1) == 128 * 1024      # 128 KiB a token
    assert DENSE.kv_bytes(YI, 1) == 32 * 1024         # 32 KiB a token


def test_prompt_work_small():
    w = DENSE.prompt_work(OLMO, 3, 2)
    one = DENSE.attn_flops(OLMO, 1)
    assert w["attn_flops"] == one * (1 + 2 + 3)
    assert w["flops"] == 3 * (2 * DENSE.matmul_params(OLMO)
                              + DENSE.lora_flops_per_token(OLMO)) \
        + w["attn_flops"]
    assert w["attn_bytes"] == DENSE.kv_bytes(OLMO, 2) \
        + DENSE.kv_bytes(OLMO, 3)


def test_span_work_counts_tokens_where_they_arrive():
    r = Rec(client=0, prompt_len=3, budget=3, due=0.0)
    r.events = [(1.0, 1), (2.0, 2)]            # first token, then two more
    in_first = work.span_work(DENSE, OLMO, [r], 0.5, 1.5, 2)
    assert in_first["flops"] == DENSE.prompt_work(OLMO, 3, 2)["flops"]
    in_second = work.span_work(DENSE, OLMO, [r], 1.5, 2.5, 2)
    assert in_second["flops"] == (DENSE.token_flops(OLMO, 4)
                                  + DENSE.token_flops(OLMO, 5))
    assert in_second["attn_bytes"] == (DENSE.kv_bytes(OLMO, 4)
                                       + DENSE.kv_bytes(OLMO, 5))


def test_peaks_keyed_by_device_kind():
    p = work.peak("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peak("TPU v9 imaginary")
