"""The dense pre-norm decoder: every layer ``attn+mlp``.

A configuration file with ``"arch": "dense"`` is served as the program's
``family="dense"`` model with SwiGLU and rotary positions, one stacked
period of ``attn+mlp`` blocks under ``blocks/b0``.  This module holds
everything about that architecture that the benchmark needs (see
:func:`chipbench.spec.load_arch` for the interface):

* the program's ``ModelConfig`` keywords, with the check of the program's
  departures from the source;
* the seeded weights and client adapters, made on the device;
* the plain float32 reference and its float8 control;
* the work the served tokens need, and the kernels and scopes that a
  trace of the program shows.

Nothing here imports the program.
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import spec
from chipbench.reference import HI, mm, q8
from chipbench.weights import seed_key

ATTN = ("wq", "wk", "wv", "wo")
MLP = ("w_gate", "w_up", "w_out")

# the Pallas calls to time (the HLO instruction of each is named after its
# wrapper), each with the work whose roofline bounds it: ``attn`` is
# ``attn_flops`` and ``attn_bytes``; the decode kernel, called once a step
# in every layer, counts the decode steps (``per_step``)
KERNELS = {"paged_prefill": {"module": "_prefill_chunk_impl",
                             "op": "%paged_prefill_attention.",
                             "work": "attn"},
           "paged_attention": {"module": "_decode_chunk_impl",
                               "op": "%paged_attention.", "work": "attn",
                               "per_step": True}}
# the program's named scopes on a dense layer, innermost first: the LoRA
# update runs inside the projections of attention and of the MLP
SCOPES = ("lora", "attn", "mlp")


def attention_layers(c: dict) -> int:
    """Layers that call the paged attention kernels: all of them."""
    return c["num_hidden_layers"]


def program_config_kwargs(c: dict) -> dict:
    """The program's ModelConfig keywords for a configuration file.  The
    program scales a tied embedding by sqrt(d_model) and normalises with
    eps 1e-6 whatever the model; where the source differs, the file lists
    the key under ``program_departures`` with the value the program runs."""
    tied = bool(c["tie_word_embeddings"])
    want = c["hidden_size"] ** 0.5 if tied else 1.0
    mult, eps = spec.as_run(c, "embed_multiplier"), spec.as_run(c, "norm_eps")
    if abs(mult - want) > 1e-9 * want or eps != 1e-6:
        raise ValueError(f"{c['name']}: the program runs embed multiplier "
                         f"{want} and norm eps 1e-6; the file says "
                         f"{mult} and {eps}")
    return dict(
        name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], head_dim=c["head_dim"],
        norm_type={"rmsnorm": "rmsnorm",
                   "layernorm_nonparametric": "nonparametric"}[c["norm"]],
        mlp_type="swiglu", tie_embeddings=tied, rope_theta=c["rope_theta"],
        max_seq_len=c["max_position_embeddings"],
        lora_rank=c["lora"]["rank"], lora_alpha=c["lora"]["alpha"],
        lora_targets=tuple(c["lora"]["targets"]))


# ---------------------------------------------------------------------------
# Seeded weights and adapters.  The trees are laid out as the serving
# program takes them (adapter factors ``(layers, d_in, r)`` and
# ``(layers, r, d_out)``).  Every leaf has its own key, ``fold_in`` of the
# run's key by a fixed path, so a layer made alone equals the same layer
# made inside the stacked call, and the reference regenerates any layer
# from the seed without reading what the program holds.
# ---------------------------------------------------------------------------

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


def _shapes(dm, H, Kv, hd, ff) -> Dict[str, Tuple[int, int]]:
    """(d_in, d_out) of each of the seven projections."""
    return {"wq": (dm, H * hd), "wk": (dm, Kv * hd), "wv": (dm, Kv * hd),
            "wo": (H * hd, dm), "w_gate": (dm, ff), "w_up": (dm, ff),
            "w_out": (ff, dm)}


def target_shapes(c: dict) -> Dict[str, Tuple[int, int]]:
    return _shapes(c["hidden_size"], c["num_attention_heads"],
                   c["num_key_value_heads"], c["head_dim"],
                   c["intermediate_size"])


def _norm_params(key, d: Dims):
    if d.norm == "rmsnorm":
        return {"scale": 1.0 + 0.1 * jax.random.normal(key, (d.d_model,))}
    return {}


def _layer(d, key):
    """One layer's bf16 matrices (std 1/sqrt(d_in)) and f32 norm scales."""
    shapes = _shapes(d.d_model, d.heads, d.kv_heads, d.head_dim, d.d_ff)
    w = {n: (jax.random.normal(jax.random.fold_in(key, i), shapes[n])
             * shapes[n][0] ** -0.5).astype(jnp.bfloat16)
         for i, n in enumerate(ATTN + MLP)}
    return {"norm1": _norm_params(jax.random.fold_in(key, 100), d),
            "mixer": {n: w[n] for n in ATTN},
            "norm2": _norm_params(jax.random.fold_in(key, 101), d),
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
             "final_norm": _norm_params(jax.random.fold_in(base, 3), d),
             "blocks": {"b0": jax.vmap(lambda k: _layer(d, k))(keys)}}
        if not d.tied:
            p["lm_head"] = _head(d, base)
        return p
    return jax.jit(make)


def init_params(c: dict, seed: int):
    """The whole base model in one jitted call on the default device."""
    return _params_fn(dims(c))(seed_key(seed))


@functools.lru_cache(maxsize=None)
def _layer_fn(d):
    return jax.jit(lambda base, l: _layer(d, _layer_key(base, l)))


def layer_params(c: dict, seed: int, layer: int):
    """Layer ``layer`` alone, equal to its slice of :func:`init_params`."""
    return _layer_fn(dims(c))(seed_key(seed), jnp.int32(layer))


@functools.lru_cache(maxsize=None)
def _ends_fn(d):
    def make(base):
        head = _embed(d, base).T if d.tied else _head(d, base)
        return (_embed(d, base), head,
                _norm_params(jax.random.fold_in(base, 3), d))
    return jax.jit(make)


def embed_head(c: dict, seed: int):
    """(embedding (V, d), unembedding (d, V), final norm) — the tied
    unembedding is the embedding's transpose."""
    return _ends_fn(dims(c))(seed_key(seed))


def _factors(d, key):
    """One adapter module per target: A ~ N(0, 1/d_in) and
    B ~ N(0, s^2/r) with s = ``init.lora_b_std``, so (x A) B has about s
    times the RMS of x (at s = 0.05 and a LoRA scale of 2 the delta is
    about a tenth of the base output)."""
    r, L = d.rank, d.layers
    shapes = _shapes(d.d_model, d.heads, d.kv_heads, d.head_dim, d.d_ff)
    out = {}
    for i, t in enumerate(d.targets):
        din, dout = shapes[t]
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


def client_adapters(c: dict, seed: int, client: int):
    """Client ``client``'s dual LoRA: (personalised, global, [w1, w2])."""
    return _client_fn(dims(c))(seed_key(seed), jnp.int32(client))


# ---------------------------------------------------------------------------
# The plain float32 reference, and its control.
#
# Token embedding (times ``embed_multiplier`` as the program runs it), per
# layer a norm, causal multi-head attention with rotary positions
# (rotate-half, as the published checkpoints use) and grouped K/V heads, a
# residual add, a norm, a SwiGLU MLP and a residual add; a final norm and
# the unembedding.  Every one of the seven projections carries the
# client's LoRA, fused from its personalised and global modules by Eq. 7
# of FDLoRA (the factors merged element-wise with the client's two
# weights) and applied densely: ``y = x W + (alpha / r) (x A) B``.
#
# Weights are the bf16 values made above, taken to float32; every
# activation stays float32 and every product runs at
# ``Precision.HIGHEST``.  Layers are made and applied one at a time, so
# the reference fits on the chip once the program's state is freed.  The
# control rounds every matmul input to float8 (``chipbench.reference``).
# ---------------------------------------------------------------------------

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
        y = mm(h, lw[part][name].astype(jnp.float32), quant)
        if name in ad:
            a, b = ad[name]
            y = y + scale * mm(mm(h, a, quant), b, quant)
        return y

    h = _norm(x, lw["norm1"], kind, eps)
    q = _rope(lin(h, "mixer", "wq").reshape(S, H, hd), theta)
    k = _rope(lin(h, "mixer", "wk").reshape(S, Kv, hd), theta)
    v = lin(h, "mixer", "wv").reshape(S, Kv, hd)
    G = H // Kv
    q = q.reshape(S, Kv, G, hd)
    if quant:
        q, k, v = q8(q, -1), q8(k, -1), q8(v, 0)
    s = jnp.einsum("qkgd,pkd->kgqp", q, k, precision=HI) * hd ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if quant:
        p = q8(p, -1)
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
        per, glo, w = client_adapters(self.c, self.seed, client)
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
        emb, _, fnorm = embed_head(self.c, self.seed)
        mult = spec.as_run(self.c, "embed_multiplier")
        xs, ads = [], []
        for client, toks in seqs:
            t = np.zeros(length, np.int32)
            t[: len(toks)] = toks
            xs.append(emb[jnp.asarray(t)].astype(jnp.float32) * mult)
            ads.append(self._fused(client))
        del emb
        for l in range(self.c["num_hidden_layers"]):
            lw = layer_params(self.c, self.seed, l)
            xs = [self.block(x, lw, {t: (a[l], b[l])
                                     for t, (a, b) in ad.items()})
                  for x, ad in zip(xs, ads)]
        kind, eps = self.c["norm"], spec.as_run(self.c, "norm_eps")
        return [_norm(x, fnorm, kind, eps) for x in xs]

    def head(self) -> jax.Array:
        return embed_head(self.c, self.seed)[1].astype(jnp.float32)


# ---------------------------------------------------------------------------
# Work: operations and bytes counted from the shapes.  Counts are of the
# algorithm, not of what the program happens to compute: padded rows,
# logits nobody reads and recomputation do not count.  A multiply-add is
# two operations.
# ---------------------------------------------------------------------------

def matmul_params(c: dict) -> int:
    """Weights a token multiplies: the seven projections of every layer
    and the unembedding (the embedding is a lookup)."""
    per_layer = sum(i * o for i, o in target_shapes(c).values())
    return c["num_hidden_layers"] * per_layer \
        + c["hidden_size"] * c["vocab_size"]


def lora_flops_per_token(c: dict) -> int:
    """2 r (d_in + d_out) for each adapted projection of every layer."""
    r = c["lora"]["rank"]
    return c["num_hidden_layers"] * sum(
        2 * r * (i + o) for t, (i, o) in target_shapes(c).items()
        if t in c["lora"]["targets"])


def attn_flops(c: dict, ctx: int) -> int:
    """Scores and weighted sum of one query over ``ctx`` keys, all layers:
    4 ctx H hd per layer."""
    return 4 * ctx * c["num_attention_heads"] * c["head_dim"] \
        * attention_layers(c)


def kv_bytes(c: dict, ctx: int, itemsize: int = 2) -> int:
    """K and V of ``ctx`` positions, all layers, at ``itemsize`` bytes."""
    return 2 * ctx * c["num_key_value_heads"] * c["head_dim"] \
        * attention_layers(c) * itemsize


def token_flops(c: dict, ctx: int) -> int:
    """Everything one token needs at context ``ctx`` (itself included):
    the matmuls, its LoRA deltas and its attention."""
    return 2 * matmul_params(c) + lora_flops_per_token(c) \
        + attn_flops(c, ctx)


def token_work(c: dict, ctx: int) -> Dict[str, int]:
    """The work of one output token at context ``ctx``: all its FLOPs, its
    attention FLOPs and the least bytes its attention reads."""
    return {"flops": token_flops(c, ctx), "attn_flops": attn_flops(c, ctx),
            "attn_bytes": kv_bytes(c, ctx)}


def prompt_work(c: dict, prompt: int, chunk: int) -> Dict[str, int]:
    """The work of prefilling a ``prompt``-token prompt in chunks of
    ``chunk``, as :func:`token_work` counts it: token t attends t + 1
    positions; each chunk reads the K/V of its context once (its own
    included)."""
    attn = attn_flops(c, 1) * prompt * (prompt + 1) // 2
    total = prompt * (2 * matmul_params(c) + lora_flops_per_token(c)) + attn
    nbytes = sum(kv_bytes(c, min(prompt, s + chunk))
                 for s in range(0, prompt, chunk))
    return {"flops": total, "attn_flops": attn, "attn_bytes": nbytes}
