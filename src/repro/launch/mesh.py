"""Production mesh factory (TPU v5e target).

Single pod: 16×16 = 256 chips, axes ("data", "model").
Multi-pod:  2×16×16 = 512 chips, axes ("pod", "data", "model") — the "pod"
axis carries FDLoRA clients (client == pod slice; DESIGN.md §4).

Defined as functions so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = 512 if multi_pod else 256
    devices = jax.devices()
    if len(devices) < need:
        raise ValueError(
            f"make_production_mesh(multi_pod={multi_pod}) needs {need} "
            f"devices, found {len(devices)}; use make_host_mesh() for "
            f"local runs")
    import numpy as np
    return jax.sharding.Mesh(np.asarray(devices[:need]).reshape(shape), axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over the real local devices (CPU tests / examples).

    ``model`` splits the devices into a ("data", "model") grid; the device
    count must be divisible by it (force extra host devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
    """
    import numpy as np
    n = len(jax.devices())
    if model < 1:
        raise ValueError(f"model axis must be >= 1, got {model}")
    if n % model != 0:
        raise ValueError(
            f"make_host_mesh(model={model}): {n} local devices are not "
            f"divisible by the model axis; force a compatible count with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count")
    return jax.sharding.Mesh(
        np.asarray(jax.devices()).reshape(n // model, model),
        ("data", "model"))
