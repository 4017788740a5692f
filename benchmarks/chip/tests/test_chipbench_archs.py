"""A second architecture comes in as files alone: a copy of the benchmark
gains an architecture module, a configuration naming it, a traffic mix
and entries in ``BENCHMARK.json``, and a whole run of the new cell on
the CPU is correct with no file of ``chipbench/`` changed."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import filecmp  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402

from chipbench import spec  # noqa: E402
from chipbench_tiny import TINY_CONFIG, TINY_TRAFFIC  # noqa: E402

# the dense layers served as a period of two attn+mlp blocks: layer 2p + i
# is period p of block b<i>
PAIR_ARCH = '''"""Dense layers served as periods of two attn+mlp blocks."""
import jax

from chipbench import spec

dense = spec.load_arch("dense")
KERNELS, SCOPES = dense.KERNELS, dense.SCOPES
attention_layers, prompt_work, token_work = (
    dense.attention_layers, dense.prompt_work, dense.token_work)
layer_params, embed_head, Reference = (
    dense.layer_params, dense.embed_head, dense.Reference)


def program_config_kwargs(c):
    return dict(dense.program_config_kwargs(c),
                layer_pattern=("attn+mlp", "attn+mlp"))


def _pair(blocks):
    return {f"b{i}": jax.tree.map(lambda x: x[i::2], blocks["b0"])
            for i in (0, 1)}


def init_params(c, seed):
    p = dense.init_params(c, seed)
    return dict(p, blocks=_pair(p["blocks"]))


def client_adapters(c, seed, client):
    per, glo, w = dense.client_adapters(c, seed, client)
    return ({"blocks": _pair(per["blocks"])},
            {"blocks": _pair(glo["blocks"])}, w)
'''

RUN = '''import json, sys, time
sys.path.insert(0, sys.argv[1])
from chipbench import harness, spec
bench = spec.load_benchmark()
cell = spec.find_cell(bench, "tiny-pair.closed")
config = spec.load_config(cell["config"])
r = harness.run_cell(bench, cell, 2**31 + 19, 1.0, False, time.perf_counter())
r["layer_pattern"] = harness.program_config(config).layer_pattern
r["blocks"] = sorted(spec.arch_of(config).init_params(config, 1)["blocks"])
print(json.dumps(r))
'''


def _add_cell(root: Path) -> None:
    """The new files and entries, as a later change would add them."""
    bench_dir = root / "benchmarks" / "chip"
    (bench_dir / "archs" / "dense_pair.py").write_text(PAIR_ARCH)
    config = dict(TINY_CONFIG, name="tiny-pair", arch="dense_pair",
                  num_hidden_layers=4)
    (bench_dir / "configs" / "tiny-pair.json").write_text(json.dumps(config))
    traffic = dict(TINY_TRAFFIC, loop="closed", users=4, stagger_s=0.001)
    (bench_dir / "traffic" / "closed.json").write_text(json.dumps(traffic))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny-pair", "source": "test", "reduced": [], "why": "test",
        "file": "benchmarks/chip/configs/tiny-pair.json"})
    bench["workloads"].append({
        "name": "tiny-pair.closed", "config": "tiny-pair",
        "traffic": "closed", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "out_tok_s":
            m["workloads"].append("tiny-pair.closed")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_a_new_architecture_is_files_alone(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH_DIR, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    _add_cell(root)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(spec.ROOT / "src"))
    p = subprocess.run(
        [sys.executable, "-c", RUN, str(root / "benchmarks" / "chip")],
        capture_output=True, text=True, env=env, cwd=root, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["layer_pattern"] == ["attn+mlp", "attn+mlp"]
    assert r["blocks"] == ["b0", "b1"]
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0
    assert set(r["metrics"]) == {"out_tok_s", "setup_s"}
    # nothing of the yardstick changed
    mine, theirs = spec.BENCH_DIR / "chipbench", \
        root / "benchmarks" / "chip" / "chipbench"
    names = sorted(f.name for f in mine.glob("*.py"))
    assert names == sorted(f.name for f in theirs.glob("*.py"))
    match, mismatch, errors = filecmp.cmpfiles(mine, theirs, names,
                                               shallow=False)
    assert (mismatch, errors) == ([], [])
