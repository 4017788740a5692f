"""Roofline-term extraction from the compiled dry-run artifact.

Three terms per (arch × shape × mesh), in seconds (EXPERIMENTS.md §Roofline):

    compute    = HLO_FLOPs / (chips × peak_FLOP/s)
    memory     = HLO_bytes / (chips × HBM_bw)
    collective = collective_bytes / (chips × link_bw)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()``. Collective
bytes are NOT in cost_analysis: we parse the optimized HLO and sum the bytes
each chip moves per collective, using standard ring-algorithm factors on the
op's *output* shape (g = collective group size):

    all-reduce       2·S·(g-1)/g      (reduce-scatter + all-gather phases)
    all-gather       S_out·(g-1)/g    (each chip receives the other shards)
    reduce-scatter   S_out·(g-1)     (input = g·S_out, each chip sends all but its shard)
    all-to-all       S·(g-1)/g
    collective-permute  S

Peak rates come from :data:`PEAKS`, keyed by ``jax.Device.device_kind``;
a kind missing from the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float                # bf16 FLOP/s per chip
    hbm_bw: float               # HBM bytes/s per chip
    ici_bw: float               # interconnect bytes/s per link


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect over four links
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def peaks(device_kind: str) -> Peaks:
    """The peak rates of one chip of ``device_kind``."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|(\w+\[[^\]]*\]))\S*\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"(\w+?)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([^}]*)\}")
_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class Collective:
    op: str
    out_bytes: int
    group_size: int
    per_chip_bytes: float


def parse_collectives(hlo_text: str) -> List[Collective]:
    out: List[Collective] = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_str = m.group(1) or m.group(2)
        op = m.group(3)
        size = _shape_bytes(shape_str)
        g = 1
        gm = _GROUPS_V2_RE.search(line)
        if gm:  # iota format [num_groups,group_size]
            g = int(gm.group(2))
        else:
            gm = _GROUPS_RE.search(line)
            if gm:
                g = len([x for x in gm.group(1).split(",") if x.strip() != ""])
        g = max(g, 1)
        if op == "all-reduce":
            per_chip = 2 * size * (g - 1) / g
        elif op == "all-gather":
            per_chip = size * (g - 1) / g
        elif op == "reduce-scatter":
            per_chip = size * (g - 1)
        elif op == "all-to-all":
            per_chip = size * (g - 1) / g
        else:  # collective-permute
            per_chip = size
        out.append(Collective(op, size, g, per_chip))
    return out


@dataclasses.dataclass
class Roofline:
    flops: float                # global HLO flops
    hbm_bytes: float            # global bytes accessed
    collective_bytes: float     # per-chip bytes moved over ICI
    chips: int
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float = 0.0
    useful_ratio: float = 0.0
    n_collectives: int = 0
    coll_by_op: Optional[Dict[str, float]] = None

    def to_dict(self):
        return dataclasses.asdict(self)


def analyze(cost: Dict, hlo_text: str, chips: int, model_flops: float = 0.0,
            *, device_kind: str) -> Roofline:
    """``cost`` comes from ``compiled.cost_analysis()``, which reports the
    SPMD-partitioned (per-device) module — flops/bytes are PER CHIP (verified
    against a hand-computed matmul; tests/test_roofline.py).  Times are
    against the peaks of one ``device_kind`` chip."""
    peak = peaks(device_kind)
    flops = float(cost.get("flops", 0.0))          # per chip
    hbm = float(cost.get("bytes accessed", 0.0))   # per chip
    colls = parse_collectives(hlo_text)
    per_chip_coll = sum(c.per_chip_bytes for c in colls)
    by_op: Dict[str, float] = {}
    for c in colls:
        by_op[c.op] = by_op.get(c.op, 0.0) + c.per_chip_bytes

    compute_s = flops / peak.flops
    memory_s = hbm / peak.hbm_bw
    coll_s = per_chip_coll / peak.ici_bw
    dominant = max(
        (("compute", compute_s), ("memory", memory_s), ("collective", coll_s)),
        key=lambda kv: kv[1])[0]
    global_flops = flops * chips
    return Roofline(
        flops=flops, hbm_bytes=hbm, collective_bytes=per_chip_coll,
        chips=chips, compute_s=compute_s, memory_s=memory_s,
        collective_s=coll_s, dominant=dominant, model_flops=model_flops,
        useful_ratio=(model_flops / global_flops if global_flops else 0.0),
        n_collectives=len(colls), coll_by_op=by_op)


def model_flops_train(cfg, tokens: int) -> float:
    """6·N_active·D for a train step (fwd+bwd)."""
    return 6.0 * cfg.count_active_params() * tokens


def model_flops_decode(cfg, tokens: int) -> float:
    """2·N_active·D for forward-only decode."""
    return 2.0 * cfg.count_active_params() * tokens
