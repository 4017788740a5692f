"""The work of the tokens a span served, and the chip's peaks.

An architecture module counts the work of one prompt and of one output
token from its configuration's shapes (``prompt_work``, ``token_work``:
``flops`` and, for each kind of kernel, ``<kind>_flops`` and
``<kind>_bytes``); :func:`span_work` adds them up over the token records
of a span.
"""
from __future__ import annotations

import collections
from typing import Dict, Iterable

# Published peaks of one chip, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM at 819 GB/s.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; an unknown kind is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (have {sorted(PEAKS)})")
    return PEAKS[device_kind]


def span_work(arch, c: dict, records: Iterable, t0: float, t1: float,
              chunk: int) -> Dict[str, float]:
    """Work of the tokens a span [t0, t1) served, from token records, as
    ``arch`` counts it (a key nothing added to reads 0).

    A record has ``prompt_len`` and ``events`` [(time, n_tokens), ...].
    A prompt counts where its first token arrived (its prefill ends
    there); output token i counts at the event that delivered it."""
    out: Dict[str, float] = collections.defaultdict(float)

    def add(work):
        for k, v in work.items():
            out[k] += v

    for r in records:
        if not r.events:
            continue
        p = r.prompt_len
        if t0 <= r.events[0][0] < t1:
            add(arch.prompt_work(c, p, chunk))
        i = 0
        for t, n in r.events:
            for j in range(i, i + n):
                if j >= 1 and t0 <= t < t1:
                    add(arch.token_work(c, p + j))
            i += n
    return out
