"""The serving path's own instruments, read from a profiler trace and from
its counters.

The program writes three things that :mod:`chipbench.traces` does not
read:

* host spans named ``serve:<phase>`` (``serve:step`` and the phases inside
  it, ``serve:intake`` and ``serve:deliver`` in the front end's pump), each
  with the session's step index as its ``step`` argument;
* a ``jax.named_scope`` on each part of a layer (for a dense layer: the
  LoRA update ``lora``, attention ``attn`` and the MLP ``mlp``; the
  architecture module lists them as ``SCOPES``), which reaches each
  device op's scope path in the trace;
* cumulative counters, ``StreamSession.counters()``.

:func:`read_xspace` keeps the device's ops (each with its scope path as a
fourth field) and modules, the benchmark's ``bench:window`` span and
the program's ``serve:`` spans.  :func:`reduce` adds to
:func:`chipbench.traces.reduce` the decode program's device time by scope
and the idle time by the host span that was running, and names each
long idle gap by the innermost ``serve:`` span in it (the one reducer
that names gaps).  :func:`occupancy`,
:func:`lora_share` and :func:`kv_live_share` read a run's context as the
metric readers do, with ``ctx["counters"]`` the difference of two
counter snapshots taken at the traced span's ends.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Sequence

from chipbench import traces

SERVE_PREFIX = "serve:"
# the stat of a device op's event metadata that holds its scope path
SCOPE_STAT = "tf_op"
NO_SPAN = "no serve span"


def scope_of(path: str, scopes: Sequence[str]) -> str:
    """The innermost of ``scopes`` (listed innermost first) in an op's
    scope path, else ``other``."""
    parts = set(path.split("/"))
    return next((s for s in scopes if s in parts), "other")


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of a serialized protobuf
    message: varints as ints, everything else as bytes."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, v


def op_scopes(path: str, device: str) -> Dict[str, str]:
    """{device op event name: its scope path}.  The path is the
    ``SCOPE_STAT`` stat of the op's event metadata in the ``.xplane.pb``
    (an ``XSpace`` message), which ``jax.profiler.ProfileData`` does not
    expose, so the few messages that hold it are decoded here: the
    device plane (``XSpace`` field 1; its name is field 2) maps stat ids
    to names (field 5: ``XStatMetadata`` id 1, name 2) and holds one
    ``XEventMetadata`` per op (field 4: name 2, stats 5, each an
    ``XStat`` of metadata id 1 and a string 5 or a reference 7 to a stat
    metadata whose name is the string)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for num, plane in _fields(space):
        if num != 1:
            continue
        fields = list(_fields(plane))
        if bytes(next((v for n, v in fields if n == 2), b"")) != \
                device.encode():
            continue
        names = {}
        for n, entry in fields:
            if n == 5:
                md = dict(_fields(dict(_fields(entry)).get(2, b"")))
                names[md.get(1, 0)] = bytes(md.get(2, b"")).decode()
        out = {}
        for n, entry in fields:
            if n != 4:
                continue
            md = list(_fields(dict(_fields(entry)).get(2, b"")))
            stats = [dict(_fields(v)) for k, v in md if k == 5]
            for st in stats:
                if names.get(st.get(1, 0)) != SCOPE_STAT:
                    continue
                val = (bytes(st[5]).decode() if 5 in st
                       else names.get(st.get(7), ""))
                out[bytes(next(v for k, v in md if k == 2)).decode()] = val
        return out
    return {}


def read_xspace(path: str, device: str = "/device:TPU:0") -> dict:
    """{"ops": [[name, start_ns, dur_ns, scope_path]], "modules": [[name,
    start_ns, dur_ns]], "host": [...], "serve": [...]} of one device
    (scope paths from :func:`op_scopes`), the benchmark's ``bench:`` host
    span (``bench:window``), and the program's spans as ``[name,
    start_ns, dur_ns, thread, step]`` (``thread``: the host line's index
    in its plane)."""
    from jax.profiler import ProfileData

    scopes = op_scopes(path, device)
    out: dict = {"ops": [], "modules": [], "host": [], "serve": []}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == device:
            for line in plane.lines:
                if line.name == traces.MODULES:
                    out["modules"] += [[e.name, e.start_ns, e.duration_ns]
                                       for e in line.events]
                elif line.name == traces.OPS:
                    out["ops"] += [[e.name, e.start_ns, e.duration_ns,
                                    scopes.get(e.name, "")]
                                   for e in line.events]
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(traces.HOST_PREFIX):
                        out["host"].append([e.name, e.start_ns,
                                            e.duration_ns])
                    elif e.name.startswith(SERVE_PREFIX):
                        out["serve"].append(
                            [e.name, e.start_ns, e.duration_ns,
                             f"{plane.name}#{i}",
                             dict(e.stats).get("step")])
    return out


def idle_intervals(ops, start_ns: float, end_ns: float):
    """The gaps in [start_ns, end_ns) in which no op ran on the device."""
    busy = traces.union(traces._clip([(e[1], e[1] + e[2]) for e in ops],
                                     start_ns, end_ns))
    edges = [start_ns] + [x for iv in busy for x in iv] + [end_ns]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


class SpanNamer:
    """Names an interval by the host span that was running in it: the
    innermost ``serve:`` span on the path of most overlap (the top-level
    span that overlaps it most, then its child that does, and so on), else
    :data:`NO_SPAN`."""

    def __init__(self, serve: Sequence):
        order = sorted(range(len(serve)),
                       key=lambda i: (serve[i][1], -serve[i][2]))
        self.spans = [(serve[i][0], serve[i][1], serve[i][1] + serve[i][2],
                       serve[i][3]) for i in order]
        self.starts = [s[1] for s in self.spans]
        self.longest = max((s[2] - s[1] for s in self.spans), default=0)
        # parent: the innermost earlier span of the same thread enclosing
        # the span (spans of one thread nest)
        self.parent: List[Optional[int]] = []
        open_: Dict[str, List[int]] = {}
        for i, (_, s, e, th) in enumerate(self.spans):
            stack = open_.setdefault(th, [])
            while stack and self.spans[stack[-1]][2] < e:
                stack.pop()
            self.parent.append(stack[-1] if stack else None)
            stack.append(i)

    def __call__(self, a: float, b: float) -> str:
        lo = bisect.bisect_left(self.starts, a - self.longest)
        hi = bisect.bisect_left(self.starts, b)
        over = {i: min(b, self.spans[i][2]) - max(a, self.spans[i][1])
                for i in range(lo, hi)}
        over = {i: v for i, v in over.items() if v > 0}
        cur = None
        while True:
            kids = [i for i in over if self.parent[i] == cur]
            if not kids:
                break
            cur = max(kids, key=lambda i: (over[i], -i))
        return self.spans[cur][0] if cur is not None else NO_SPAN


def decode_scopes(ops, modules, start_ns: float, end_ns: float,
                  module: str, scopes: Sequence[str]) -> Dict[str, float]:
    """Seconds of the device ``ops``' time in [start_ns, end_ns) inside
    executions of ``module``, by :func:`scope_of` (loops, which hold other
    ops, left out)."""
    out = {s: 0.0 for s in (*scopes, "other")}
    for e in traces._in_module(ops, modules, module):
        if traces.op_name(e) == "%while":
            continue
        a, b = max(e[1], start_ns), min(e[1] + e[2], end_ns)
        out[scope_of(e[3] if len(e) > 3 else "", scopes)] += (b - a) * 1e-9
    return out


def reduce(trace: dict, start_ns: float, end_ns: float,
           kernels: Dict[str, dict], programs: Dict[str, str],
           scopes: Sequence[str], top: int = 10) -> dict:
    """:func:`chipbench.traces.reduce`, and three more entries:
    ``idle_gaps``, the ``top`` longest idle gaps, each named by
    :class:`SpanNamer`; ``idle_by_span``, the idle seconds in the span by
    the host span that was running (longest first); and ``scopes``, the
    decode program's op seconds by each of ``scopes``."""
    out = traces.reduce(trace, start_ns, end_ns, kernels, programs, top)
    ops = [e for e in trace["ops"] if e[1] < end_ns and e[1] + e[2] > start_ns]
    name = SpanNamer(trace.get("serve", []))
    gaps = [(name(a, b), (b - a) * 1e-9)
            for a, b in idle_intervals(ops, start_ns, end_ns)]
    by: Dict[str, float] = {}
    for n, s in gaps:
        by[n] = by.get(n, 0.0) + s
    gaps.sort(key=lambda g: -g[1])
    out["idle_gaps"] = [list(g) for g in gaps[:top]]
    out["idle_by_span"] = dict(sorted(by.items(), key=lambda kv: -kv[1]))
    out["scopes"] = decode_scopes(ops, trace["modules"], start_ns, end_ns,
                                  programs["decode"], scopes)
    return out


def counter_delta(before: Optional[dict], after: Optional[dict]
                  ) -> Optional[dict]:
    """What the session counted between two snapshots (None where the
    program has no counters)."""
    if before is None or after is None:
        return None
    return {k: after[k] - before[k] for k in after}


def occupancy(ctx) -> Optional[float]:
    """Per cent of the decode steps' slots that held an active row."""
    c = ctx.get("counters")
    if not c or c["steps"] <= 0:
        return None
    return 100.0 * c["decode_row_steps"] / (c["steps"]
                                            * ctx["traffic"]["slots"])


def kv_live_share(ctx) -> Optional[float]:
    """Per cent of the decode kernel's grid over table blocks that held
    live K/V."""
    c = ctx.get("counters")
    if not c or c["kv_grid_blocks"] <= 0:
        return None
    return 100.0 * c["kv_live_blocks"] / c["kv_grid_blocks"]


def lora_share(ctx) -> Optional[float]:
    """Per cent of the decode program's op time in the traced span that
    ran under the ``lora`` scope."""
    tr = ctx.get("trace")
    if not tr or "scopes" not in tr:
        return None
    total, lora = sum(tr["scopes"].values()), tr["scopes"].get("lora", 0.0)
    if total <= 0 or lora <= 0:
        return None
    return 100.0 * lora / total
