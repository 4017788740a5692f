"""What the program derives from the platform it runs on.

* :func:`interpret_mode` — every Pallas kernel runs compiled on TPU and
  interpreted on CPU; any other backend is refused rather than silently
  interpreted.
* :func:`paged_backend` — the paged-attention implementation the serving
  path uses when no caller pinned one: the Pallas kernels on TPU, the jnp
  gather on CPU (where the kernels would only be interpreted).
* :func:`enable_compile_cache` — JAX's persistent compilation cache, at
  ``$JAX_COMPILATION_CACHE_DIR`` when that is set and otherwise at one
  fixed directory inside the checkout.

Nothing here runs at import: the backend is read when a kernel is traced,
so importing the package never initialises a device.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

PAGED_BACKENDS = ("jnp", "pallas")

# <checkout>/.jax_cache — fixed, so a later process finds what an earlier
# one compiled (the path is part of the cache key); listed in .gitignore
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def interpret_mode() -> bool:
    """``interpret=`` for ``pallas_call``: False on TPU, True on CPU."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default JAX backend is {backend!r}")


def paged_backend(choice: Optional[str] = None) -> str:
    """``choice`` when a caller pinned one, else the platform's: "pallas"
    on TPU, "jnp" elsewhere."""
    if choice is None:
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    if choice not in PAGED_BACKENDS:
        raise ValueError(f"unknown paged_backend {choice!r}; "
                         f"expected one of {PAGED_BACKENDS}")
    return choice


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache before the first compile
    and return its directory.  A ``JAX_COMPILATION_CACHE_DIR`` set by the
    environment wins and JAX reads it itself; otherwise the cache goes to
    :data:`CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
