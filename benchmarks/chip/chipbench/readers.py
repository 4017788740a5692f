"""What the metric readers under ``metrics/`` share.

A reader gets the run's context and returns a number, or ``None`` where
it finds nothing to read.  The context holds the token records
(``records``), the window (``window``, ``seconds``, ``setup_s``), the
session's ``stats``, the configuration and its architecture module
(``config``, ``arch``), the ``traffic``, the chip's ``peak``, and in a
``--trace 1`` run the traced span's ends (``span``), its reduced trace
(``trace``, :func:`chipbench.serve_trace.reduce`) and the session's
counters over it (``counters``).
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import numpy as np

from chipbench import work


def percentile(xs: Sequence[float], q: float, scale: float = 1.0
               ) -> Optional[float]:
    """The q-th percentile (linear interpolation) times ``scale``; a
    failed request is +inf, and any percentile that reaches one is
    inf."""
    if not xs:
        return None
    v = float(np.percentile(np.asarray(xs, float), q))
    return math.inf if math.isnan(v) else v * scale


def window_tpot(rec, t0: float, t1: float, loop: str) -> Optional[float]:
    """Seconds per token between a request's first and last delivery
    inside [t0, t1); inf if it failed (an open-loop request that never
    finished, or any stream that ended short of its budget); None with
    fewer than two deliveries in the window."""
    if not rec.done and (rec.ended or loop == "open"):
        return math.inf
    ev = [(t, k) for t, k in rec.events if t0 <= t < t1]
    if len(ev) < 2:
        return None
    return (ev[-1][0] - ev[0][0]) / sum(k for _, k in ev[1:])


def idle_share(ctx) -> Optional[float]:
    """Per cent of the traced span in which no op ran on the device."""
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def _span_work(ctx):
    t0, t1 = ctx["span"]
    if t0 is None or ctx["peak"] is None:
        return None
    w = work.span_work(ctx["arch"], ctx["config"], ctx["records"], t0, t1,
                       ctx["traffic"]["prefill_chunk"])
    return w, t1 - t0


def mfu(ctx) -> Optional[float]:
    """Per cent of the chip's bf16 peak that the FLOPs of the tokens
    served in the traced span need (prompt tokens where their first token
    arrived, output tokens where delivered)."""
    sw = _span_work(ctx)
    if sw is None or sw[0]["flops"] == 0:
        return None
    w, secs = sw
    return 100.0 * w["flops"] / (secs * ctx["peak"]["bf16_flops"])


def roofline(ctx, kind: str) -> Optional[float]:
    """Per cent: the least time the chip needs for the ``kind`` work of the
    tokens served in the traced span (the larger of their ``<kind>_flops``
    at peak and their ``<kind>_bytes`` at peak bandwidth) over the device
    time in the span of the kernels that the architecture bounds by that
    work."""
    sw = _span_work(ctx)
    tr = ctx["trace"]
    if sw is None or not tr:
        return None
    kernels = ctx["arch"].KERNELS
    secs = sum(k["seconds"] for name, k in tr["kernels"].items()
               if kernels[name]["work"] == kind)
    w = sw[0]
    if secs <= 0 or w[f"{kind}_flops"] == 0:
        return None
    pk = ctx["peak"]
    least = max(w[f"{kind}_flops"] / pk["bf16_flops"],
                w[f"{kind}_bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * least / secs


def program_ms(ctx, name: str) -> Optional[float]:
    """Mean device milliseconds of the dispatches of program ``name`` that
    ran wholly inside the traced span."""
    tr = ctx["trace"]
    if not tr or not tr["programs"][name]["count"]:
        return None
    each = tr["programs"][name]["seconds_each"]
    return 1e3 * sum(each) / len(each)


def decode_steps(ctx) -> Optional[float]:
    """Decode steps in the traced span: the calls of the kernels that the
    architecture marks ``"per_step"`` (called once a decode step in each
    attention layer) over its attention layers."""
    tr = ctx["trace"]
    arch = ctx["arch"]
    layers = arch.attention_layers(ctx["config"])
    names = [n for n, k in arch.KERNELS.items() if k.get("per_step")]
    if not tr or not names or layers <= 0:
        return None
    return sum(tr["kernels"].get(n, {}).get("count", 0)
               for n in names) / layers


def decode_step_ms(ctx) -> Optional[float]:
    """Device milliseconds of the decode program per decode step: its
    time in the traced span over :func:`decode_steps`."""
    steps = decode_steps(ctx)
    if not steps:
        return None
    return 1e3 * ctx["trace"]["programs"]["decode"]["seconds"] / steps


def queue_wait_p50_ms(ctx) -> Optional[float]:
    """Median wall-clock wait from arrival to admission that the
    scheduler recorded for the window's session (its busiest class)."""
    st = ctx["stats"]
    if not st or not st["classes"]:
        return None
    cls = max(st["classes"].values(), key=lambda c: c["admitted"])
    return cls.get("wait_wall_ms_p50")
