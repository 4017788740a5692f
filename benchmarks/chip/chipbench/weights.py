"""The key that every architecture's seeded weights and adapters come from.

Weights and adapters are made on the device from ``--seed``.  An
architecture module (``archs/<arch>.py``) gives every leaf its own key,
``fold_in`` of :func:`seed_key` by a fixed path, so a layer made alone
equals the same layer made inside the stacked call, and its reference
regenerates any layer from the seed without reading what the program
holds.
"""
from __future__ import annotations

import jax


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits (PRNGKey keeps only 32)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)
