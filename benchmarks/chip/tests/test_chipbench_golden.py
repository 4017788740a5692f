"""The dense architecture module reproduces, bit for bit, what the
benchmark made and counted before it moved there: seeded weights,
adapters, reference and control hidden states at the tiny sizes, and the
work counts of a fixed record list at every size
(``testdata/dense_golden.json``)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import functools  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
from chipbench_tiny import TINY_CONFIG, TINY_TIED  # noqa: E402

from chipbench import spec, work  # noqa: E402
from chipbench.harness import Rec  # noqa: E402

DENSE = spec.load_arch("dense")
GOLDEN = json.loads((Path(__file__).resolve().parents[1] / "testdata"
                     / "dense_golden.json").read_text())
SEED = GOLDEN["seed"]
TINY = {c["name"]: c for c in (TINY_CONFIG, TINY_TIED)}


def digest(tree) -> dict:
    """{leaf path: sha256 of its dtype, shape and bytes (16 digits)}."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h = hashlib.sha256(f"{a.dtype.name}{a.shape}".encode() + a.tobytes())
        out[jax.tree_util.keystr(path)] = h.hexdigest()[:16]
    return out


def _seqs(config):
    rng = np.random.default_rng(7)
    return [(c, rng.integers(1, config["vocab_size"], n).astype(np.int32))
            for c, n in ((0, 20), (1, 29))]


@functools.lru_cache(maxsize=None)
def _made(name: str) -> dict:
    c = TINY[name]
    return {
        "init_params": digest(DENSE.init_params(c, SEED)),
        "client_adapters": [digest(DENSE.client_adapters(c, SEED, k))
                            for k in (0, 1)],
        "layer_params_1": digest(DENSE.layer_params(c, SEED, 1)),
        "embed_head": digest(DENSE.embed_head(c, SEED)),
        "hidden": digest(DENSE.Reference(c, SEED).hidden(_seqs(c), 32)),
        "hidden_control": digest(DENSE.Reference(c, SEED, quant=True)
                                 .hidden(_seqs(c), 32)),
        "head": digest(DENSE.Reference(c, SEED).head())}


@pytest.mark.parametrize("name", sorted(TINY))
@pytest.mark.parametrize("what", ["init_params", "client_adapters",
                                  "layer_params_1", "embed_head", "hidden",
                                  "hidden_control", "head"])
def test_seeded_trees_are_bitwise_unchanged(name, what):
    assert _made(name)[what] == GOLDEN["tiny"][name][what]


def _rec(prompt_len, events):
    r = Rec(client=0, prompt_len=prompt_len, budget=0, due=0.0)
    r.events = events
    return r


RECS = [_rec(20, [(1.0, 1), (1.2, 3), (2.5, 2)]),
        _rec(7, [(0.4, 1), (0.9, 1), (1.1, 4)]),
        _rec(33, []), _rec(12, [(3.0, 1)]),
        _rec(40, [(1.9, 2), (1.95, 5)])]


@pytest.mark.parametrize("name,chunk", [("tiny", 16), ("tiny-tied", 16),
                                        ("olmo-1b", 64), ("yi-6b-l16", 64)])
def test_work_counts_are_unchanged(name, chunk):
    c = TINY.get(name) or spec.load_config(name)
    want = GOLDEN["work"][name]
    got = work.span_work(DENSE, c, RECS, 0.5, 2.0, chunk)
    assert dict(got) == want["span_work"]
    assert all(type(v) is float for v in got.values())
    pw = DENSE.prompt_work(c, 37, chunk)
    assert [pw["flops"], pw["attn_flops"], pw["attn_bytes"]] == \
        want["prompt_work"]
