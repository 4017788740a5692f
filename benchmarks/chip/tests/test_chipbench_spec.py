"""Every configuration, traffic mix and metric file under benchmarks/chip
resolves the way a run resolves it, at the files' own sizes (nothing is
built)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import pytest

from chipbench import harness, spec
from chipbench import traffic as TR

CONFIGS = sorted(p.stem for p in (spec.BENCH_DIR / "configs").glob("*.json"))
TRAFFIC = sorted(p.stem for p in (spec.BENCH_DIR / "traffic").glob("*.json"))
BENCH = spec.load_benchmark()


@pytest.mark.parametrize("name", CONFIGS)
def test_config_file_maps_to_the_program(name):
    c = spec.load_config(name)
    cfg = harness.program_config(c)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.d_ff, cfg.vocab_size) == (
        c["num_hidden_layers"], c["hidden_size"], c["num_attention_heads"],
        c["num_key_value_heads"], c["intermediate_size"], c["vocab_size"])
    arch = spec.arch_of(c)
    assert 0 < arch.attention_layers(c) <= c["num_hidden_layers"]
    assert set(arch.SCOPES) >= {"lora"}
    assert all(k["work"] for k in arch.KERNELS.values())
    assert spec.as_run(c, "norm_eps") == 1e-6


def test_a_file_without_an_arch_is_an_error():
    c = spec.load_config("olmo-1b")
    del c["arch"]
    with pytest.raises(KeyError, match="names no 'arch'"):
        harness.program_config(c)


def test_a_key_the_source_leaves_out_takes_its_default():
    c = spec.load_config("yi-6b-l16")
    assert "embed_multiplier" not in c
    assert spec.as_run(c, "embed_multiplier") == 1.0
    dep = dict(c, program_departures={"embed_multiplier": {"run": 3.0}})
    assert spec.as_run(dep, "embed_multiplier") == 3.0


@pytest.mark.parametrize("name", TRAFFIC)
def test_traffic_file_generates(name):
    t = spec.load_traffic(name)
    items = TR.items(t, 2**31 + 17, 2.0, 1000)
    assert items and all(len(i.prompt) + i.max_new_tokens
                         <= t["context_limit"] for i in items)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves(cell):
    w = spec.find_cell(BENCH, cell)
    harness.program_config(spec.load_config(w["config"]))
    spec.load_traffic(w["traffic"])
    for kind in ("end_to_end", "per_layer"):
        for m in spec.cell_metrics(BENCH, cell, kind):
            assert callable(spec.load_reader(m["name"]))
