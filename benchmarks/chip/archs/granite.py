"""The Granite-4.0-H hybrid: Mamba-2 and NoPE GQA layers, each with a
SwiGLU MLP (``model_type`` ``granitemoehybrid`` without experts).

A configuration file with ``"arch": "granite"`` is served as the
program's ``family="hybrid"`` model: one period of the file's
``layer_types`` (``mamba`` -> ``mamba+mlp``, ``attention`` ->
``attn+mlp``) as blocks ``b0..b{p-1}``, each stacked over the periods, so
layer ``l`` is period ``l // p`` of block ``b{l % p}``.  The published
scalars come from the file: embeddings times ``embedding_multiplier``,
each sublayer's output times ``residual_multiplier`` before its residual
add, softmax scale ``attention_multiplier``, logits divided by
``logits_scaling``.  This module holds everything about the architecture
that the benchmark needs (see :func:`chipbench.spec.load_arch`): the
program's ``ModelConfig`` keywords, seeded weights and client adapters,
the plain float32 reference (its Mamba-2 the token-by-token recurrence)
and its float8 control, the work counts, and the kernels and scopes of a
trace.

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
SSM = ("in_proj", "out_proj")
MLP = ("w_gate", "w_up", "w_out")
MIXERS = {"mamba": "mamba+mlp", "attention": "attn+mlp"}

# the Pallas calls to time, as for the dense decoder: both serve the
# attention layers alone (the Mamba-2 layers run no kernel of their own)
KERNELS = {"paged_prefill": {"module": "_prefill_chunk_impl",
                             "op": "%paged_prefill_attention.",
                             "work": "attn"},
           "paged_attention": {"module": "_decode_chunk_impl",
                               "op": "%paged_attention.", "work": "attn",
                               "per_step": True}}
# the program's named scopes, innermost first: the LoRA update runs inside
# the projections; ``ssm`` is a Mamba-2 mixer between its projections (conv,
# scan or recurrent step, gated norm)
SCOPES = ("lora", "ssm", "attn", "mlp")


def period(c: dict) -> Tuple[str, ...]:
    """The shortest period of ``layer_types`` that repeats to the depth."""
    types = c["layer_types"]
    n = len(types)
    assert n == c["num_hidden_layers"], "layer_types must name every layer"
    p = next(p for p in range(1, n + 1)
             if n % p == 0 and types == types[:p] * (n // p))
    return tuple(types[:p])


def attention_layers(c: dict) -> int:
    """Layers that call the paged attention kernels."""
    return c["layer_types"].count("attention")


def mamba_layers(c: dict) -> int:
    return c["layer_types"].count("mamba")


def program_config_kwargs(c: dict) -> dict:
    """The program's ModelConfig keywords for a configuration file,
    refusing what the program does not run."""
    want = {"position_embedding_type": "nope", "hidden_act": "silu",
            "normalization_function": "rmsnorm", "attention_bias": False,
            "mamba_proj_bias": False, "num_local_experts": 0,
            "tie_word_embeddings": True}
    for k, v in want.items():
        if c[k] != v:
            raise ValueError(f"{c['name']}: the program runs {k} = {v!r}; "
                             f"the file says {c[k]!r}")
    if c["mamba_n_heads"] * c["mamba_d_head"] != \
            c["mamba_expand"] * c["hidden_size"]:
        raise ValueError(f"{c['name']}: mamba heads x head size must be "
                         f"expand x hidden size")
    if c["shared_intermediate_size"] != c["intermediate_size"]:
        raise ValueError(f"{c['name']}: the dense MLP's width is "
                         f"shared_intermediate_size")
    hd = c["hidden_size"] // c["num_attention_heads"]
    return dict(
        name=c["name"], family="hybrid", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        d_ff=c["shared_intermediate_size"], vocab_size=c["vocab_size"],
        head_dim=hd, layer_pattern=tuple(MIXERS[t] for t in period(c)),
        norm_type="rmsnorm", norm_eps=spec.as_run(c, "norm_eps"),
        mlp_type="swiglu", tie_embeddings=True, use_rope=False,
        embedding_multiplier=float(c["embedding_multiplier"]),
        residual_multiplier=float(c["residual_multiplier"]),
        attention_multiplier=float(c["attention_multiplier"]),
        logits_scaling=float(c["logits_scaling"]),
        ssm_d_state=c["mamba_d_state"], ssm_d_conv=c["mamba_d_conv"],
        ssm_expand=c["mamba_expand"], ssm_head_dim=c["mamba_d_head"],
        ssm_n_groups=c["mamba_n_groups"],
        ssm_chunk=c["serving"]["ssd_chunk"],
        ssm_conv_bias=bool(c["mamba_conv_bias"]),
        max_seq_len=c["max_position_embeddings"],
        lora_rank=c["lora"]["rank"], lora_alpha=c["lora"]["alpha"],
        lora_targets=tuple(c["lora"]["targets"]))


# ---------------------------------------------------------------------------
# Seeded weights and adapters, laid out as the serving program takes them
# (block ``b{i}`` stacked over the periods).  Every leaf has its own key,
# ``fold_in`` of the run's key by a fixed path (layer, then leaf), so a
# layer made alone equals the same layer inside the stacked call, and the
# reference regenerates any layer from the seed.
# ---------------------------------------------------------------------------

class Dims(NamedTuple):
    """The sizes the generators need (hashable, so jitted makers cache)."""
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    types: Tuple[str, ...]            # every layer's kind
    pattern: Tuple[str, ...]          # one period of it
    ssm_heads: int
    ssm_head_dim: int
    d_state: int
    groups: int
    d_conv: int
    rank: int
    targets: Tuple[str, ...]
    embed_std: float
    lora_b_std: float

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.groups * self.d_state

    @property
    def proj_dim(self) -> int:
        return self.conv_dim + self.d_inner + self.ssm_heads


def dims(c: dict) -> Dims:
    """The generators' sizes from a configuration file."""
    return Dims(c["hidden_size"], c["num_attention_heads"],
                c["num_key_value_heads"],
                c["hidden_size"] // c["num_attention_heads"],
                c["shared_intermediate_size"], c["vocab_size"],
                tuple(c["layer_types"]), period(c), c["mamba_n_heads"],
                c["mamba_d_head"], c["mamba_d_state"], c["mamba_n_groups"],
                c["mamba_d_conv"], c["lora"]["rank"],
                tuple(c["lora"]["targets"]), c["init"]["embed_std"],
                c["init"]["lora_b_std"])


def _shapes(d: Dims, kind: str) -> Dict[str, Tuple[int, int]]:
    """(d_in, d_out) of each projection of a ``kind`` layer."""
    dm, hd = d.d_model, d.head_dim
    mix = ({"wq": (dm, d.heads * hd), "wk": (dm, d.kv_heads * hd),
            "wv": (dm, d.kv_heads * hd), "wo": (d.heads * hd, dm)}
           if kind == "attention" else
           {"in_proj": (dm, d.proj_dim), "out_proj": (d.d_inner, dm)})
    return dict(mix, w_gate=(dm, d.d_ff), w_up=(dm, d.d_ff),
                w_out=(d.d_ff, dm))


def _scale_vec(key, n):
    return 1.0 + 0.1 * jax.random.normal(key, (n,))


def _layer(d: Dims, kind: str, key):
    """One layer: bf16 matrices (std 1/sqrt(d_in)) and f32 norm scales;
    a Mamba-2 mixer's conv (std 1/sqrt(K), bias std 0.1, bf16), A from
    ``a_log`` = log(1..H), dt bias the inverse softplus of dt drawn
    log-uniform in [1e-3, 0.1], D = 1, gated-norm scales 1 + 0.1 N(0, 1)."""
    shapes = _shapes(d, kind)
    w = {n: (jax.random.normal(jax.random.fold_in(key, i), shapes[n])
             * shapes[n][0] ** -0.5).astype(jnp.bfloat16)
         for i, n in enumerate(shapes)}
    names = ATTN if kind == "attention" else SSM
    mixer = {n: w[n] for n in names}
    if kind == "mamba":
        k = jax.random.fold_in(key, 50)
        H = d.ssm_heads
        dt = jnp.exp(jax.random.uniform(
            jax.random.fold_in(k, 3), (H,), minval=np.log(1e-3),
            maxval=np.log(0.1)))
        mixer.update(
            conv_w=(jax.random.normal(jax.random.fold_in(k, 0),
                                      (d.d_conv, d.conv_dim))
                    * d.d_conv ** -0.5).astype(jnp.bfloat16),
            conv_b=(0.1 * jax.random.normal(jax.random.fold_in(k, 1),
                                            (d.conv_dim,))
                    ).astype(jnp.bfloat16),
            a_log=jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
            dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
            d_skip=jnp.ones((H,), jnp.float32),
            norm_scale=_scale_vec(jax.random.fold_in(k, 2), d.d_inner))
    return {"norm1": {"scale": _scale_vec(jax.random.fold_in(key, 100),
                                          d.d_model)},
            "mixer": mixer,
            "norm2": {"scale": _scale_vec(jax.random.fold_in(key, 101),
                                          d.d_model)},
            "mlp": {n: w[n] for n in MLP}}


def _layer_key(base, layer):
    return jax.random.fold_in(jax.random.fold_in(base, 4), layer)


def _embed(d, base):
    return (jax.random.normal(jax.random.fold_in(base, 1),
                              (d.vocab, d.d_model))
            * d.embed_std).astype(jnp.bfloat16)


def _periods(d: Dims, i: int) -> List[int]:
    """The layers that block ``b{i}`` stacks: i, i + p, i + 2p, ..."""
    return list(range(i, len(d.types), len(d.pattern)))


@functools.lru_cache(maxsize=None)
def _params_fn(d):
    def make(base):
        blocks = {}
        for i, kind in enumerate(d.pattern):
            keys = jax.vmap(lambda l: _layer_key(base, l))(
                jnp.asarray(_periods(d, i)))
            blocks[f"b{i}"] = jax.vmap(
                lambda k, kind=kind: _layer(d, kind, k))(keys)
        return {"embed": _embed(d, base),
                "final_norm": {"scale": _scale_vec(
                    jax.random.fold_in(base, 3), d.d_model)},
                "blocks": blocks}
    return jax.jit(make)


def init_params(c: dict, seed: int):
    """The whole base model in one jitted call on the default device."""
    return _params_fn(dims(c))(seed_key(seed))


@functools.lru_cache(maxsize=None)
def _layer_fn(d, kind):
    return jax.jit(lambda base, l: _layer(d, kind, _layer_key(base, l)))


def layer_params(c: dict, seed: int, layer: int):
    """Layer ``layer`` alone, equal to its slice of :func:`init_params`."""
    d = dims(c)
    return _layer_fn(d, d.types[layer])(seed_key(seed), jnp.int32(layer))


@functools.lru_cache(maxsize=None)
def _ends_fn(d):
    def make(base):
        emb = _embed(d, base)
        return (emb, emb.T,
                {"scale": _scale_vec(jax.random.fold_in(base, 3), d.d_model)})
    return jax.jit(make)


def embed_head(c: dict, seed: int):
    """(embedding (V, d), unembedding (d, V) = its transpose, final
    norm)."""
    return _ends_fn(dims(c))(seed_key(seed))


def _factors(d: Dims, key, layer: int, kind: str):
    """One layer's adapter modules: A ~ N(0, 1/d_in), B ~ N(0, s^2/r) with
    s = ``init.lora_b_std`` (the delta is about s times the RMS of x)."""
    r = d.rank
    out = {}
    for i, (t, (din, dout)) in enumerate(_shapes(d, kind).items()):
        if t not in d.targets:
            continue
        k = jax.random.fold_in(jax.random.fold_in(key, layer), i)
        out[t] = {"a": jax.random.normal(jax.random.fold_in(k, 0),
                                         (din, r)) * din ** -0.5,
                  "b": jax.random.normal(jax.random.fold_in(k, 1),
                                         (r, dout)) * d.lora_b_std * r ** -0.5}
    names = ATTN if kind == "attention" else SSM
    tree = {"mixer": {t: out[t] for t in names if t in out},
            "mlp": {t: out[t] for t in MLP if t in out}}
    return {k: v for k, v in tree.items() if v}


def _stacked_factors(d: Dims, key):
    """Every layer's modules, stacked by block as the program takes them
    (each block's layers made in one batched call)."""
    return {"blocks": {
        f"b{i}": jax.vmap(lambda l, kind=kind: _factors(d, key, l, kind))(
            jnp.asarray(_periods(d, i)))
        for i, kind in enumerate(d.pattern)}}


def _client_key(base, c):
    return jax.random.fold_in(jax.random.fold_in(base, 5), c)


@functools.lru_cache(maxsize=None)
def _client_fn(d):
    def make(base, c):
        k = _client_key(base, c)
        w1 = jax.random.uniform(jax.random.fold_in(k, 2), (),
                                minval=0.3, maxval=0.7)
        return (_stacked_factors(d, jax.random.fold_in(k, 0)),
                _stacked_factors(d, jax.random.fold_in(k, 1)),
                jnp.stack([w1, 1.0 - w1]))
    return jax.jit(make)


def client_adapters(c: dict, seed: int, client: int):
    """Client ``client``'s dual LoRA: (personalised, global, [w1, w2])."""
    return _client_fn(dims(c))(seed_key(seed), jnp.int32(client))


@functools.lru_cache(maxsize=None)
def _fused_fn(d, kind):
    def make(base, c, layer):
        k = _client_key(base, c)
        w1 = jax.random.uniform(jax.random.fold_in(k, 2), (),
                                minval=0.3, maxval=0.7)
        per = _factors(d, jax.random.fold_in(k, 0), layer, kind)
        glo = _factors(d, jax.random.fold_in(k, 1), layer, kind)
        return {t: (w1 * per[part][t]["a"] + (1 - w1) * glo[part][t]["a"],
                    w1 * per[part][t]["b"] + (1 - w1) * glo[part][t]["b"])
                for part in per for t in per[part]}
    return jax.jit(make)


def fused_adapter(c: dict, seed: int, client: int, layer: int):
    """Client ``client``'s Eq. 7 adapter at one layer: per target (A, B),
    made from the seed alone."""
    d = dims(c)
    return _fused_fn(d, d.types[layer])(seed_key(seed), jnp.int32(client),
                                        jnp.int32(layer))


# ---------------------------------------------------------------------------
# The plain float32 reference, and its control.
#
# Token embedding times ``embedding_multiplier``; per layer an RMSNorm,
# the mixer, its output times ``residual_multiplier`` added to the
# residual, an RMSNorm, a SwiGLU MLP (gate and up, as Granite's shared
# MLP splits its input projection) likewise scaled and added; a final
# RMSNorm, and the tied unembedding with the logits divided by
# ``logits_scaling``.  Attention: causal GQA with no position embedding
# and softmax scale ``attention_multiplier``.  Mamba-2 (Granite's mixer):
# ``in_proj`` to (z, xBC, dt); a causal depthwise conv of K with bias and
# SiLU over xBC, split into x (H heads of P), B and C (G groups of N);
# dt = softplus(dt + dt_bias), A = -exp(a_log); then token by token
# h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T and y_t = C_t h_t + D x_t;
# y times SiLU(z), RMS-normalised, times the norm scale; ``out_proj``.
# Every projection carries the client's Eq. 7-fused LoRA, applied densely:
# ``y = x W + (alpha / r) (x A) B``.
#
# Weights are the bf16 values made above, taken to float32; every
# activation and the state stay float32 and every product runs at
# ``Precision.HIGHEST``.  Layers are made and applied one at a time.  The
# control rounds every matmul input (and x, B, C of the recurrence) to
# float8 (``chipbench.reference``).
# ---------------------------------------------------------------------------

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _lin(h, w, ad, name, lscale, quant):
    y = mm(h, w.astype(jnp.float32), quant)
    if name in ad:
        a, b = ad[name]
        y = y + lscale * mm(mm(h, a, quant), b, quant)
    return y


def _mlp(h, lw, ad, lscale, quant):
    g = _lin(h, lw["mlp"]["w_gate"], ad, "w_gate", lscale, quant)
    u = _lin(h, lw["mlp"]["w_up"], ad, "w_up", lscale, quant)
    return _lin(jax.nn.silu(g) * u, lw["mlp"]["w_out"], ad, "w_out",
                lscale, quant)


def _attention(h, lw, ad, *, H, Kv, hd, amult, lscale, quant):
    S = h.shape[0]
    mx = lw["mixer"]
    q = _lin(h, mx["wq"], ad, "wq", lscale, quant).reshape(S, Kv, H // Kv,
                                                           hd)
    k = _lin(h, mx["wk"], ad, "wk", lscale, quant).reshape(S, Kv, hd)
    v = _lin(h, mx["wv"], ad, "wv", lscale, quant).reshape(S, Kv, hd)
    if quant:
        q, k, v = q8(q, -1), q8(k, -1), q8(v, 0)
    s = jnp.einsum("qkgd,pkd->kgqp", q, k, precision=HI) * amult
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    if quant:
        p = q8(p, -1)
    o = jnp.einsum("kgqp,pkd->qkgd", p, v, precision=HI).reshape(S, H * hd)
    return _lin(o, mx["wo"], ad, "wo", lscale, quant)


def _mamba(h, lw, ad, *, Hs, P, N, G, K, eps, lscale, quant):
    S = h.shape[0]
    mx = lw["mixer"]
    d_in = Hs * P
    zxbcdt = _lin(h, mx["in_proj"], ad, "in_proj", lscale, quant)
    z, xbc, dt = (zxbcdt[:, :d_in], zxbcdt[:, d_in:-Hs], zxbcdt[:, -Hs:])
    w = mx["conv_w"].astype(jnp.float32)                   # (K, C)
    xp = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc], 0)
    conv = sum(xp[k:k + S] * w[k] for k in range(K))
    xbc = jax.nn.silu(conv + mx["conv_b"].astype(jnp.float32))
    x = xbc[:, :d_in].reshape(S, Hs, P)
    Bm = xbc[:, d_in:d_in + G * N].reshape(S, G, N)
    Cm = xbc[:, d_in + G * N:].reshape(S, G, N)
    if quant:
        x, Bm, Cm = q8(x, -1), q8(Bm, -1), q8(Cm, -1)
    dt = jax.nn.softplus(dt + mx["dt_bias"])               # (S, Hs)
    A = -jnp.exp(mx["a_log"])
    grp = jnp.arange(Hs) // (Hs // G)                      # head -> group

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = state * jnp.exp(dt_t * A)[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[grp][:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t[grp],
                                 precision=HI)

    _, y = jax.lax.scan(step, jnp.zeros((Hs, P, N), jnp.float32),
                        (x, Bm, Cm, dt))
    y = (y + x * mx["d_skip"][:, None]).reshape(S, d_in)
    y = _rms(y * jax.nn.silu(z), mx["norm_scale"], eps)
    return _lin(y, mx["out_proj"], ad, "out_proj", lscale, quant)


def _block(x, lw, ad, *, mixer, eps, rmult, lscale, quant, attn, ssm):
    """One layer over x (S, d) float32; ``attn`` and ``ssm`` are the
    mixers' sizes."""
    h = _rms(x, lw["norm1"]["scale"], eps)
    if mixer == "attention":
        out = _attention(h, lw, ad, lscale=lscale, quant=quant, **attn)
    else:
        out = _mamba(h, lw, ad, eps=eps, lscale=lscale, quant=quant, **ssm)
    x = x + rmult * out
    h = _rms(x, lw["norm2"]["scale"], eps)
    return x + rmult * _mlp(h, lw, ad, lscale, quant)


class Reference:
    """The reference (``quant=False``) or its control (``quant=True``)
    for one configuration and seed."""

    def __init__(self, config: dict, seed: int, quant: bool = False):
        self.c, self.seed, self.quant = config, seed, quant
        d = dims(config)
        lo = config["lora"]
        self.eps = spec.as_run(config, "norm_eps")
        common = dict(
            eps=self.eps, rmult=float(config["residual_multiplier"]),
            lscale=lo["alpha"] / lo["rank"], quant=quant,
            attn=dict(H=d.heads, Kv=d.kv_heads, hd=d.head_dim,
                      amult=float(config["attention_multiplier"])),
            ssm=dict(Hs=d.ssm_heads, P=d.ssm_head_dim, N=d.d_state,
                     G=d.groups, K=d.d_conv))
        self.block = {m: jax.jit(functools.partial(_block, mixer=m, **common))
                      for m in MIXERS}

    def hidden(self, seqs: Sequence[Tuple[int, np.ndarray]], length: int
               ) -> List[jax.Array]:
        """Final-normed hidden states (length, d) of each (client, tokens)
        sequence, zero-padded to ``length`` tokens (every layer is causal,
        so the padding stays out of every real position)."""
        emb, _, fnorm = embed_head(self.c, self.seed)
        mult = float(self.c["embedding_multiplier"])
        xs = []
        for _, toks in seqs:
            t = np.zeros(length, np.int32)
            t[: len(toks)] = toks
            xs.append(emb[jnp.asarray(t)].astype(jnp.float32) * mult)
        del emb
        with jax.default_matmul_precision("highest"):
            for l, kind in enumerate(self.c["layer_types"]):
                lw = layer_params(self.c, self.seed, l)
                xs = [self.block[kind](x, lw, fused_adapter(
                          self.c, self.seed, client, l))
                      for x, (client, _) in zip(xs, seqs)]
        return [_rms(x, fnorm["scale"], self.eps) for x in xs]

    def head(self) -> jax.Array:
        """The unembedding, divided by ``logits_scaling``: the logits the
        gaps are taken of."""
        return embed_head(self.c, self.seed)[1].astype(jnp.float32) \
            / float(self.c["logits_scaling"])


# ---------------------------------------------------------------------------
# Work: operations and bytes counted from the shapes.  Counts are of the
# algorithm, not of what the program happens to compute: padded rows,
# logits nobody reads and recomputation do not count; a Mamba-2 layer's
# token costs what its recurrence needs, however the program blocks it.  A
# multiply-add is two operations.
# ---------------------------------------------------------------------------

def matmul_params(c: dict) -> int:
    """Weights a token multiplies: every layer's projections and MLP, and
    the unembedding (the embedding is a lookup)."""
    d = dims(c)
    return sum(i * o for kind in d.types
               for i, o in _shapes(d, kind).values()) \
        + d.d_model * d.vocab


def lora_flops_per_token(c: dict) -> int:
    """2 r (d_in + d_out) for each adapted projection of every layer."""
    d = dims(c)
    return sum(2 * d.rank * (i + o) for kind in d.types
               for t, (i, o) in _shapes(d, kind).items() if t in d.targets)


def attn_flops(c: dict, ctx: int) -> int:
    """Scores and weighted sum of one query over ``ctx`` keys, all
    attention layers: 4 ctx H hd per layer."""
    d = dims(c)
    return 4 * ctx * d.heads * d.head_dim * attention_layers(c)


def kv_bytes(c: dict, ctx: int, itemsize: int = 2) -> int:
    """K and V of ``ctx`` positions, all attention layers."""
    d = dims(c)
    return 2 * ctx * d.kv_heads * d.head_dim * attention_layers(c) \
        * itemsize


def ssm_flops(c: dict) -> int:
    """One token's state update in every Mamba-2 layer: per state element
    a decay multiply, the input's multiply-add and the output's
    multiply-add (5 H P N), and the conv's K multiply-adds per channel."""
    d = dims(c)
    per = 5 * d.ssm_heads * d.ssm_head_dim * d.d_state \
        + 2 * d.d_conv * d.conv_dim
    return mamba_layers(c) * per


def ssm_bytes(c: dict) -> int:
    """One token's state read and written in every Mamba-2 layer: the
    float32 state (H P N) and the bf16 conv window ((K - 1) channels)."""
    d = dims(c)
    per = 4 * d.ssm_heads * d.ssm_head_dim * d.d_state \
        + 2 * (d.d_conv - 1) * d.conv_dim
    return mamba_layers(c) * 2 * per


def token_work(c: dict, ctx: int) -> Dict[str, int]:
    """The work of one output token at context ``ctx``: all its FLOPs, its
    attention's FLOPs and least K/V bytes, and its state update's FLOPs
    and state bytes."""
    flops = 2 * matmul_params(c) + lora_flops_per_token(c) \
        + attn_flops(c, ctx) + ssm_flops(c)
    return {"flops": flops, "attn_flops": attn_flops(c, ctx),
            "attn_bytes": kv_bytes(c, ctx), "ssm_flops": ssm_flops(c),
            "ssm_bytes": ssm_bytes(c)}


def prompt_work(c: dict, prompt: int, chunk: int) -> Dict[str, int]:
    """The work of prefilling a ``prompt``-token prompt in chunks of
    ``chunk``, as :func:`token_work` counts it: token t attends t + 1
    positions; each chunk reads the K/V of its context once.  The state
    updates count in ``flops`` only: ``ssm_*`` is decode work."""
    attn = attn_flops(c, 1) * prompt * (prompt + 1) // 2
    total = prompt * (2 * matmul_params(c) + lora_flops_per_token(c)
                      + ssm_flops(c)) + attn
    nbytes = sum(kv_bytes(c, min(prompt, s + chunk))
                 for s in range(0, prompt, chunk))
    return {"flops": total, "attn_flops": attn, "attn_bytes": nbytes}
