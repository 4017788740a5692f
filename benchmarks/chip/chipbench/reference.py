"""The plain float32 reference of the served decoder, and its control.

A dense pre-norm decoder as the configuration file states it: token
embedding (times ``embed_multiplier`` as the program runs it), per layer a norm, causal
multi-head attention with rotary positions (rotate-half, as the published
checkpoints use) and grouped K/V heads, a residual add, a norm, a SwiGLU
MLP and a residual add; a final norm and the unembedding.  Every one of
the seven projections carries the client's LoRA, fused from its
personalised and global modules by Eq. 7 of FDLoRA (the factors merged
element-wise with the client's two weights) and applied densely:
``y = x W + (alpha / r) (x A) B``.

Weights are the bf16 values :mod:`chipbench.weights` makes from the seed,
taken to float32; every activation stays float32 and every product runs
at ``Precision.HIGHEST``.  Layers are made and applied one at a time, so
the reference fits on the chip once the program's state is freed.

The control computes the same thing with every matmul input rounded to
float8 e4m3 (per-row scales for activations, per-column for weights),
the next precision below the configuration's bf16.
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import spec
from chipbench import weights as W

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _q8(x, axis):
    """x rounded to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    """x (..., k) @ w (k, n) in float32 at HIGHEST precision; with
    ``quant`` both inputs first go to float8."""
    if quant:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _norm(x, p, kind, eps):
    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * p["scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _rope(x, theta):
    """x (S, heads, hd) at positions 0..S-1, rotate-half convention."""
    S, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv      # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _block(x, lw, ad, *, H, Kv, hd, kind, eps, theta, scale, quant):
    """One decoder layer over x (S, d) float32."""
    S = x.shape[0]

    def lin(h, part, name):
        y = _mm(h, lw[part][name].astype(jnp.float32), quant)
        if name in ad:
            a, b = ad[name]
            y = y + scale * _mm(_mm(h, a, quant), b, quant)
        return y

    h = _norm(x, lw["norm1"], kind, eps)
    q = _rope(lin(h, "mixer", "wq").reshape(S, H, hd), theta)
    k = _rope(lin(h, "mixer", "wk").reshape(S, Kv, hd), theta)
    v = lin(h, "mixer", "wv").reshape(S, Kv, hd)
    G = H // Kv
    q = q.reshape(S, Kv, G, hd)
    if quant:
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, 0)
    s = jnp.einsum("qkgd,pkd->kgqp", q, k, precision=HI) * hd ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if quant:
        p = _q8(p, -1)
    o = jnp.einsum("kgqp,pkd->qkgd", p, v, precision=HI).reshape(S, H * hd)
    x = x + lin(o, "mixer", "wo")
    h = _norm(x, lw["norm2"], kind, eps)
    g = lin(h, "mlp", "w_gate")
    u = lin(h, "mlp", "w_up")
    return x + lin(jax.nn.silu(g) * u, "mlp", "w_out")


class Reference:
    """The reference (``quant=False``) or its control (``quant=True``)
    for one configuration and seed."""

    def __init__(self, config: dict, seed: int, quant: bool = False):
        self.c, self.seed, self.quant = config, seed, quant
        self.d = W.dims(config)
        lo = config["lora"]
        self.block = jax.jit(functools.partial(
            _block, H=config["num_attention_heads"],
            Kv=config["num_key_value_heads"], hd=config["head_dim"],
            kind=config["norm"], eps=spec.as_run(config, "norm_eps"),
            theta=config["rope_theta"], scale=lo["alpha"] / lo["rank"],
            quant=quant))

    def _fused(self, client: int):
        """The client's Eq. 7 adapter: per target (A, B), each
        (layers, ...) float32."""
        per, glo, w = W.client_adapters(self.d, self.seed, client)
        out = {}
        for part in ("mixer", "mlp"):
            for t, pg in per["blocks"]["b0"].get(part, {}).items():
                gg = glo["blocks"]["b0"][part][t]
                out[t] = (w[0] * pg["a"] + w[1] * gg["a"],
                          w[0] * pg["b"] + w[1] * gg["b"])
        return out

    def hidden(self, seqs: Sequence[Tuple[int, np.ndarray]], length: int
               ) -> List[jax.Array]:
        """Final-normed hidden states (length, d) of each (client, tokens)
        sequence, zero-padded to ``length`` tokens (causal attention keeps
        the padding out of every real position)."""
        emb, _, fnorm = W.embed_head(self.d, self.seed)
        mult = spec.as_run(self.c, "embed_multiplier")
        xs, ads = [], []
        for client, toks in seqs:
            t = np.zeros(length, np.int32)
            t[: len(toks)] = toks
            xs.append(emb[jnp.asarray(t)].astype(jnp.float32) * mult)
            ads.append(self._fused(client))
        del emb
        for l in range(self.c["num_hidden_layers"]):
            lw = W.layer_params(self.d, self.seed, l)
            xs = [self.block(x, lw, {t: (a[l], b[l])
                                     for t, (a, b) in ad.items()})
                  for x, ad in zip(xs, ads)]
        kind, eps = self.c["norm"], spec.as_run(self.c, "norm_eps")
        return [_norm(x, fnorm, kind, eps) for x in xs]

    def head(self) -> jax.Array:
        return W.embed_head(self.d, self.seed)[1].astype(jnp.float32)


@jax.jit
def gap_to_best(h, head, tokens):
    """Per position p: best logit minus the logit of ``tokens[p]``, with
    logits h @ head at HIGHEST precision."""
    lg = jnp.matmul(h, head, precision=HI)
    return jnp.max(lg, -1) - jnp.take_along_axis(lg, tokens[:, None], -1)[:, 0]


@functools.partial(jax.jit, static_argnames=("quant",))
def top_token(h, head, quant):
    """argmax over the vocabulary of h @ head (float8 inputs with quant)."""
    return jnp.argmax(_mm(h, head, quant), -1).astype(jnp.int32)


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
