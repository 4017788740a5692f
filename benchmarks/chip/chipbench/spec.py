"""Finding a cell's pieces by name.

``BENCHMARK.json`` (at the checkout's root) names each cell's
configuration and traffic mix, and each metric.  Every piece is a file of
its own under ``benchmarks/chip``:

* ``configs/<config>.json`` — the model as it is run, with ``"arch"``
  naming its architecture;
* ``archs/<arch>.py`` — everything specific to an architecture
  (:func:`load_arch`);
* ``traffic/<traffic>.json`` — the parameters the traffic generator reads;
* ``metrics/<metric>.py`` — a reader with ``read(ctx) -> float | None``.

A new cell, metric or architecture is added with new files and entries
alone.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
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


def _load(kind: str, name: str) -> ModuleType:
    """``<kind>/<name>.py``, loaded by path (names may hold dots and
    dashes, so it is not imported by module name)."""
    modname = f"chipbench_{kind}_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(
        modname, BENCH_DIR / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


_READERS: Dict[str, Callable] = {}
_ARCHS: Dict[str, ModuleType] = {}


def load_reader(metric: str) -> Callable[[dict], Optional[float]]:
    """``read`` of ``metrics/<metric>.py``."""
    if metric not in _READERS:
        _READERS[metric] = _load("metrics", metric).read
    return _READERS[metric]


def load_arch(name: str) -> ModuleType:
    """``archs/<name>.py``: everything specific to one architecture.  It
    provides

    * ``program_config_kwargs(config)`` — the program's ``ModelConfig``
      keywords, refusing a file whose values the program does not run;
    * ``init_params(config, seed)``, ``layer_params(config, seed, layer)``,
      ``embed_head(config, seed)`` and ``client_adapters(config, seed,
      client)`` — seeded weights and (personalised, global, [w1, w2])
      adapters, trees keyed by the program's block names ``b0..bN``;
    * ``Reference(config, seed, quant=False)`` with ``hidden(seqs,
      length)`` and ``head()`` — the plain float32 reference (``quant``:
      its float8 control);
    * ``prompt_work(config, prompt, chunk)`` and ``token_work(config,
      ctx)`` — work counts for :func:`chipbench.work.span_work`;
    * ``KERNELS`` — the Pallas calls to time: name -> ``{"module", "op",
      "work"}``, ``work`` naming the ``<work>_flops`` and ``<work>_bytes``
      that bound the kernel, and ``"per_step": True`` on the decode
      kernel whose calls count the decode steps (once a step in each
      attention layer);
    * ``SCOPES`` — the program's named scopes on its layers, innermost
      first;
    * ``attention_layers(config)`` — the layers that call the paged
      attention kernels."""
    if name not in _ARCHS:
        _ARCHS[name] = _load("archs", name)
    return _ARCHS[name]


def arch_of(config: dict) -> ModuleType:
    """The architecture module a configuration file names."""
    if "arch" not in config:
        raise KeyError(f"configuration {config.get('name')!r} names no "
                       f"'arch' (a module under {BENCH_DIR / 'archs'})")
    return load_arch(config["arch"])
