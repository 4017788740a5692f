"""Finding a cell's pieces by name.

``BENCHMARK.json`` (at the checkout's root) names each cell's
configuration and traffic mix, and each metric.  Every piece is a file of
its own under ``benchmarks/chip``:

* ``configs/<config>.json`` — the model as it is run;
* ``traffic/<traffic>.json`` — the parameters the traffic generator reads;
* ``metrics/<metric>.py`` — a reader with ``read(ctx) -> float | None``.

A new cell or metric is added with new files and entries alone.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (have "
                   f"{[w['name'] for w in bench['workloads']]})")


def _json(kind: str, name: str) -> dict:
    with open(BENCH_DIR / kind / f"{name}.json") as f:
        return json.load(f)


def load_config(name: str) -> dict:
    return _json("configs", name)


def load_traffic(name: str) -> dict:
    return _json("traffic", name)


# what a published configuration means when it leaves a key out
SOURCE_DEFAULTS = {"embed_multiplier": 1.0}


def as_run(config: dict, key: str):
    """The value of ``key`` that the program runs with: the file's (or the
    source's default where the file leaves the key out), or, where
    ``program_departures`` lists the key, the value the program uses in
    place of the source's."""
    dep = config.get("program_departures", {})
    if key in dep:
        return dep[key]["run"]
    return config[key] if key in config else SOURCE_DEFAULTS[key]


def cell_metrics(bench: dict, cell: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that ``cell`` reports:
    those whose ``workloads`` list names it, and those without one."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


_READERS: Dict[str, Callable] = {}


def load_reader(metric: str) -> Callable[[dict], Optional[float]]:
    """``read`` of ``metrics/<metric>.py`` (names may hold dots, so the
    file is loaded by path, not imported by module name)."""
    if metric not in _READERS:
        path = BENCH_DIR / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            "chipbench_metric_" + metric.replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _READERS[metric] = mod.read
    return _READERS[metric]
