"""The traffic generator and finding a cell's files by name."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import json
import shutil

import numpy as np
import pytest

from chipbench import spec
from chipbench import traffic as TR


def _sig(items):
    return [(i.client, i.prompt.tobytes(), i.max_new_tokens, i.due_s)
            for i in items]


TRAFFIC = ["decode-long", "long-prompt", "gen-closed"]


@pytest.mark.parametrize("name", TRAFFIC)
def test_same_seed_same_trace(name):
    t = spec.load_traffic(name)

    def gen(seed):
        return TR.items(t, seed, 20.0, 1000)
    big = 2**31 + 12345
    assert _sig(gen(big)) == _sig(gen(big))
    assert _sig(gen(big)) != _sig(gen(big + 1))


@pytest.mark.parametrize("name", TRAFFIC)
def test_seeds_reorder_one_multiset(name):
    """Every seed gets the same lengths and clients in another order, so
    the amount of work in a run does not depend on the seed."""
    t = spec.load_traffic(name)
    a, b = TR.items(t, 1, 30.0, 500), TR.items(t, 2, 30.0, 500)
    assert len(a) == len(b)
    if t["loop"] != "closed":
        assert sorted(i.client for i in a) == sorted(i.client for i in b)
    for f in (lambda i: len(i.prompt), lambda i: i.max_new_tokens):
        assert sorted(map(f, a)) == sorted(map(f, b))
    assert [x.prompt.tolist() for x in a] != [x.prompt.tolist() for x in b]


def test_open_loop_window_and_limits():
    t = spec.load_traffic("long-prompt")
    items = TR.open_loop(t, 9, 51.0, 50304)
    assert len(items) == round(t["rate_per_s"] * 51)
    due = [i.due_s for i in items]
    assert due[0] >= 0.0 and due == sorted(due) and due[-1] < 51.0
    for i in items:
        assert t["prompt_len"]["min"] <= len(i.prompt) <= t["prompt_len"]["max"]
        assert len(i.prompt) + i.max_new_tokens <= t["context_limit"]
        assert i.prompt.min() >= 1 and i.prompt.max() < 50304


def test_stratified_lengths_follow_the_lognormal():
    spec_ = {"median": 100, "sigma": 0.5, "min": 1, "max": 10**6}
    x = TR.stratified_lengths(spec_, 1001)
    assert np.median(x) == 100
    assert np.all(np.diff(x) >= 0)


def test_zipf_counts():
    c = TR.zipf_counts(4, 1.0, 25)
    assert c.sum() == 25
    assert list(c) == [12, 6, 4, 3]       # 25 * (1, 1/2, 1/3, 1/4) / 2.083


def test_new_cell_from_data_alone(tmp_path, monkeypatch):
    """A cell defined by a new traffic file and a workloads entry loads
    and generates with no change to any code."""
    root = tmp_path / "repo"
    shutil.copytree(spec.BENCH_DIR, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = spec.load_benchmark()
    new = dict(spec.load_traffic("long-prompt"), rate_per_s=0.5,
               prompt_len={"median": 256, "sigma": 0.2, "min": 128,
                           "max": 512})
    with open(root / "benchmarks/chip/traffic/short-chat.json", "w") as f:
        json.dump(new, f)
    bench["workloads"].append({"name": "olmo-1b.short-chat",
                               "config": "olmo-1b", "traffic": "short-chat",
                               "chips": 1, "why": "test"})
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    monkeypatch.setattr(spec, "BENCH_DIR", root / "benchmarks" / "chip")
    b = spec.load_benchmark(root)
    cell = spec.find_cell(b, "olmo-1b.short-chat")
    t = spec.load_traffic(cell["traffic"])
    c = spec.load_config(cell["config"])
    items = TR.open_loop(t, 3, 10.0, c["vocab_size"])
    assert len(items) == 5
    assert all(128 <= len(i.prompt) <= 512 for i in items)
    names = [m["name"] for m in spec.cell_metrics(b, cell["name"],
                                                  "end_to_end")]
    assert names == ["setup_s"]


def test_open_loop_arrivals_are_poisson():
    """Gaps between due times are exponential at the file's rate (mean
    1 / rate, coefficient of variation 1, a third of them under 0.4 of the
    mean), and every seed draws its own."""
    t = dict(spec.load_traffic("long-prompt"), rate_per_s=20.0)
    due = np.asarray([i.due_s for i in TR.open_loop(t, 2**33 + 5, 500.0,
                                                    100)])
    gaps = np.diff(due)
    mean = 1 / t["rate_per_s"]
    assert abs(gaps.mean() / mean - 1) < 0.03
    assert abs(gaps.std() / gaps.mean() - 1) < 0.05
    assert abs((gaps < 0.4 * mean).mean() - (1 - np.exp(-0.4))) < 0.02
    other = [i.due_s for i in TR.open_loop(t, 2**33 + 6, 500.0, 100)]
    assert not np.allclose(due, other)


@pytest.mark.parametrize("arrival", [None, "on_off", "uniform"])
def test_unknown_arrival_is_refused(arrival):
    t = dict(spec.load_traffic("long-prompt"), arrival=arrival)
    with pytest.raises(ValueError, match="not implemented"):
        TR.open_loop(t, 1, 10.0, 100)


def test_resident_sessions_fit_slots_and_pool():
    """Resident sessions are all due at once, fit the slots, and their
    whole spans fit the configuration's pool, so none is preempted."""
    t = spec.load_traffic("decode-long")
    c = spec.load_config("olmo-1b")
    items = TR.resident(t, 2**31 + 9, c["vocab_size"])
    assert len(items) == t["sessions"] <= t["slots"]
    assert all(i.due_s == 0.0 for i in items)
    bs = c["serving"]["block_size"]
    blocks = sum(-(-(len(i.prompt) + i.max_new_tokens) // bs) for i in items)
    assert blocks <= c["serving"]["kv_pool_blocks"]
    with pytest.raises(ValueError, match="do not fit"):
        TR.resident(dict(t, sessions=t["slots"] + 1), 1, 100)
