"""What every architecture's reference shares: float32 products at
``Precision.HIGHEST``, the control's float8 rounding, and the comparison
of served tokens with the reference's logits.

An architecture module (``archs/<arch>.py``) writes its layers' equations
in float32 with :func:`mm` for every product; with ``quant`` every
matmul input is first rounded to float8 e4m3 (per-row scales for
activations, per-column for weights), the next precision below the
configurations' bf16: that is the control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def q8(x, axis):
    """x rounded to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x, w, quant):
    """x (..., k) @ w (k, n) in float32 at HIGHEST precision; with
    ``quant`` both inputs first go to float8."""
    if quant:
        x, w = q8(x, -1), q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


@jax.jit
def gap_to_best(h, head, tokens):
    """Per position p: best logit minus the logit of ``tokens[p]``, with
    logits h @ head at HIGHEST precision."""
    lg = jnp.matmul(h, head, precision=HI)
    return jnp.max(lg, -1) - jnp.take_along_axis(lg, tokens[:, None], -1)[:, 0]


@functools.partial(jax.jit, static_argnames=("quant",))
def top_token(h, head, quant):
    """argmax over the vocabulary of h @ head (float8 inputs with quant)."""
    return jnp.argmax(mm(h, head, quant), -1).astype(jnp.int32)


def served_gaps(ref_h, head, prompt_len: int, served: np.ndarray
                ) -> np.ndarray:
    """Gap of each served token below the reference's best, at the
    position that predicts it (the prompt's last token predicts the
    first)."""
    L = ref_h.shape[0]
    nxt = np.zeros(L, np.int32)
    nxt[prompt_len - 1: prompt_len - 1 + len(served)] = served
    g = np.asarray(gap_to_best(ref_h, head, jnp.asarray(nxt)))
    return g[prompt_len - 1: prompt_len - 1 + len(served)]


def control_gaps(ref_h, ctl_h, head, prompt_len: int, n: int
                 ) -> np.ndarray:
    """Gap below the reference's best of the token the control puts first,
    at the same positions as :func:`served_gaps`."""
    top = top_token(ctl_h, head, quant=True)
    g = np.asarray(gap_to_best(ref_h, head, top))
    return g[prompt_len - 1: prompt_len - 1 + n]
