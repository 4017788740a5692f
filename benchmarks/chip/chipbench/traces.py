"""Reduction of a profiler trace to device time, idle gaps and the time of
programs and kernels.

A trace is kept as plain lists (:func:`chipbench.serve_trace.read_xspace`
reads them from an ``.xplane.pb``): the device's ops and modules as
``[name, start_ns, dur_ns, ...]``, and the benchmark's own host span
``bench:window``, which bounds the traced span.  :func:`save` and
:func:`load` write and read them as JSON, so a small recorded trace can
be checked without a chip.  :func:`reduce` works on that alone; the idle
gaps are named by the program's spans, in
:func:`chipbench.serve_trace.reduce`.

Kernels are found by a key: the program (XLA module) whose name holds
``module`` and, inside it, the ops whose HLO instruction name starts with
``op`` (a Pallas call's instruction is named after its wrapper, as in
``%paged_attention.9``).
"""
from __future__ import annotations

import bisect
import gzip
import json
from typing import Dict, List, Sequence, Tuple

HOST_PREFIX = "bench:"
OPS, MODULES = "XLA Ops", "XLA Modules"


def save(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _clip(ivs: Sequence[Tuple[float, float]], lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in ivs if b > lo and a < hi]


def union(ivs: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted disjoint cover of the intervals."""
    out: List[List[float]] = []
    for a, b in sorted(ivs):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def op_name(ev) -> str:
    """An op's HLO instruction name without its number: the event name is
    the instruction's text, ``%fusion.12 = bf16[...] fusion(...)``."""
    return ev[0].split(" = ", 1)[0].rsplit(".", 1)[0]


def _matches(ev, op: str) -> bool:
    return ev[0].startswith(op)


def _module(name: str) -> str:
    """``jit__decode_chunk_impl(1234)`` -> ``_decode_chunk_impl``."""
    return name.split("(", 1)[0].removeprefix("jit_")


def _in_module(ops, modules, module: str):
    """The ops that run inside an execution of a module named ``module``."""
    spans = union([(m[1], m[1] + m[2]) for m in modules if module in m[0]])
    out, i = [], 0
    for ev in sorted(ops, key=lambda e: e[1]):
        while i < len(spans) and spans[i][1] < ev[1]:
            i += 1
        if i < len(spans) and spans[i][0] <= ev[1] <= spans[i][1]:
            out.append(ev)
    return out


def reduce(trace: dict, start_ns: float, end_ns: float,
           kernels: Dict[str, dict], programs: Dict[str, str],
           top: int = 10) -> dict:
    """Busy time of the device in [start_ns, end_ns), the time of each
    program and kernel, and the ops that took most time.

    ``kernels``: name -> {"module": ..., "op": ...}; ``programs``: name ->
    module-name fragment.  Times in seconds."""
    ops = [e for e in trace["ops"] if e[1] < end_ns and e[1] + e[2] > start_ns]
    busy_ivs = union(_clip([(e[1], e[1] + e[2]) for e in ops],
                           start_ns, end_ns))
    busy = sum(b - a for a, b in busy_ivs)

    # time by program and op, leaving out loops, which hold other ops
    per_op: Dict[str, float] = {}
    mods = sorted((m[1], m[1] + m[2], _module(m[0]))
                  for m in trace["modules"])
    starts = [m[0] for m in mods]
    for e in ops:
        name = op_name(e)
        if name == "%while":
            continue
        i = bisect.bisect_right(starts, e[1]) - 1
        mod = mods[i][2] if i >= 0 and e[1] <= mods[i][1] else "?"
        a, b = max(e[1], start_ns), min(e[1] + e[2], end_ns)
        key = f"{mod}/{name}"
        per_op[key] = per_op.get(key, 0.0) + (b - a)

    # per program: executions wholly inside the span (for time per
    # dispatch) and the time of all of them clipped to the span
    prog = {}
    for name, frag in programs.items():
        evs = [m for m in trace["modules"] if frag in m[0]]
        whole = [m for m in evs if start_ns <= m[1] and m[1] + m[2] <= end_ns]
        prog[name] = {"count": len(whole),
                      "seconds_each": [m[2] * 1e-9 for m in whole],
                      "seconds": sum(min(m[1] + m[2], end_ns)
                                     - max(m[1], start_ns) for m in evs
                                     if m[1] < end_ns
                                     and m[1] + m[2] > start_ns) * 1e-9}

    kern = {}
    for name, key in kernels.items():
        evs = [e for e in _in_module(ops, trace["modules"], key["module"])
               if _matches(e, key["op"])]
        kern[name] = {"count": len(evs),
                      "seconds": sum(min(e[1] + e[2], end_ns)
                                     - max(e[1], start_ns)
                                     for e in evs) * 1e-9}

    return {
        "window_s": (end_ns - start_ns) * 1e-9,
        "busy_s": busy * 1e-9,
        "programs": prog,
        "kernels": kern,
        "device_ops": [[n, s * 1e-9] for n, s in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
    }
