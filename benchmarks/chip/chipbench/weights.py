"""Seeded weights and adapters, made on the device from ``--seed``.

The trees are laid out as the serving program takes them (one stacked
period of ``attn+mlp`` blocks under ``blocks/b0``; adapter factors
``(layers, d_in, r)`` and ``(layers, r, d_out)``), but nothing here
imports the program: the reference regenerates any layer from the same
seed with the same functions, so it never reads what the program holds.

Every leaf has its own key, ``fold_in`` of the run's key by a fixed path,
so a layer made alone equals the same layer made inside the stacked call.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

ATTN = ("wq", "wk", "wv", "wo")
MLP = ("w_gate", "w_up", "w_out")


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits (PRNGKey keeps only 32)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


class Dims(NamedTuple):
    """The sizes the generators need (hashable, so jitted makers cache)."""
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    layers: int
    tied: bool
    norm: str
    rank: int
    targets: Tuple[str, ...]
    embed_std: float
    lora_b_std: float


def dims(c: dict) -> Dims:
    """The generators' sizes from a configuration file."""
    return Dims(c["hidden_size"], c["num_attention_heads"],
                c["num_key_value_heads"], c["head_dim"],
                c["intermediate_size"], c["vocab_size"],
                c["num_hidden_layers"], bool(c["tie_word_embeddings"]),
                c["norm"], c["lora"]["rank"], tuple(c["lora"]["targets"]),
                c["init"]["embed_std"], c["init"]["lora_b_std"])


def target_shapes(d: Dims) -> Dict[str, Tuple[int, int]]:
    dm, H, Kv, hd, ff = d.d_model, d.heads, d.kv_heads, d.head_dim, d.d_ff
    return {"wq": (dm, H * hd), "wk": (dm, Kv * hd), "wv": (dm, Kv * hd),
            "wo": (H * hd, dm), "w_gate": (dm, ff), "w_up": (dm, ff),
            "w_out": (ff, dm)}


def _norm(key, d: Dims):
    if d.norm == "rmsnorm":
        return {"scale": 1.0 + 0.1 * jax.random.normal(key, (d.d_model,))}
    return {}


def _layer(d, key):
    """One layer's bf16 matrices (std 1/sqrt(d_in)) and f32 norm scales."""
    shapes = target_shapes(d)
    w = {n: (jax.random.normal(jax.random.fold_in(key, i), shapes[n])
             * shapes[n][0] ** -0.5).astype(jnp.bfloat16)
         for i, n in enumerate(ATTN + MLP)}
    return {"norm1": _norm(jax.random.fold_in(key, 100), d),
            "mixer": {n: w[n] for n in ATTN},
            "norm2": _norm(jax.random.fold_in(key, 101), d),
            "mlp": {n: w[n] for n in MLP}}


def _layer_key(base, layer):
    return jax.random.fold_in(jax.random.fold_in(base, 4), layer)


def _embed(d, base):
    return (jax.random.normal(jax.random.fold_in(base, 1),
                              (d.vocab, d.d_model))
            * d.embed_std).astype(jnp.bfloat16)


def _head(d, base):
    return (jax.random.normal(jax.random.fold_in(base, 2),
                              (d.d_model, d.vocab))
            * d.d_model ** -0.5).astype(jnp.bfloat16)


@functools.lru_cache(maxsize=None)
def _params_fn(d):
    def make(base):
        keys = jax.vmap(lambda l: _layer_key(base, l))(jnp.arange(d.layers))
        p = {"embed": _embed(d, base),
             "final_norm": _norm(jax.random.fold_in(base, 3), d),
             "blocks": {"b0": jax.vmap(lambda k: _layer(d, k))(keys)}}
        if not d.tied:
            p["lm_head"] = _head(d, base)
        return p
    return jax.jit(make)


def init_params(d, seed: int):
    """The whole base model in one jitted call on the default device."""
    return _params_fn(d)(seed_key(seed))


@functools.lru_cache(maxsize=None)
def _layer_fn(d):
    return jax.jit(lambda base, l: _layer(d, _layer_key(base, l)))


def layer_params(d, seed: int, layer: int):
    """Layer ``layer`` alone, equal to its slice of :func:`init_params`."""
    return _layer_fn(d)(seed_key(seed), jnp.int32(layer))


@functools.lru_cache(maxsize=None)
def _ends_fn(d):
    def make(base):
        head = _embed(d, base).T if d.tied else _head(d, base)
        return (_embed(d, base), head, _norm(jax.random.fold_in(base, 3), d))
    return jax.jit(make)


def embed_head(d, seed: int):
    """(embedding (V, d), unembedding (d, V), final norm) — the tied
    unembedding is the embedding's transpose."""
    return _ends_fn(d)(seed_key(seed))


def _factors(d, key):
    """One adapter module per target: A ~ N(0, 1/d_in) and
    B ~ N(0, s^2/r) with s = ``init.lora_b_std``, so (x A) B has about s
    times the RMS of x (at s = 0.05 and a LoRA scale of 2 the delta is
    about a tenth of the base output)."""
    r, L = d.rank, d.layers
    out = {}
    for i, t in enumerate(d.targets):
        din, dout = target_shapes(d)[t]
        k = jax.random.fold_in(key, i)
        out[t] = {"a": jax.random.normal(jax.random.fold_in(k, 0),
                                         (L, din, r)) * din ** -0.5,
                  "b": jax.random.normal(jax.random.fold_in(k, 1),
                                         (L, r, dout)) * d.lora_b_std * r ** -0.5}
    tree = {"mixer": {t: out[t] for t in ATTN if t in out},
            "mlp": {t: out[t] for t in MLP if t in out}}
    return {"blocks": {"b0": {k: v for k, v in tree.items() if v}}}


@functools.lru_cache(maxsize=None)
def _client_fn(d):
    def make(base, c):
        k = jax.random.fold_in(jax.random.fold_in(base, 5), c)
        w1 = jax.random.uniform(jax.random.fold_in(k, 2), (),
                                minval=0.3, maxval=0.7)
        return (_factors(d, jax.random.fold_in(k, 0)),
                _factors(d, jax.random.fold_in(k, 1)),
                jnp.stack([w1, 1.0 - w1]))
    return jax.jit(make)


def client_adapters(d, seed: int, client: int):
    """Client ``client``'s dual LoRA: (personalised, global, [w1, w2])."""
    return _client_fn(d)(seed_key(seed), jnp.int32(client))
