"""Model/architecture configuration system.

Every assigned architecture gets one module in ``repro/configs`` exporting a
``CONFIG`` (full-size, exercised only via the dry-run) and a ``SMOKE_CONFIG``
(reduced variant of the same family for CPU tests). Configs are registered by
id in ``repro.configs.registry``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# Block descriptors: a model is ``n_layers`` layers arranged as repetitions of
# a ``layer_pattern`` (a period).  Each entry is "<mixer>+<mlp>" where mixer is
# one of {"attn", "mamba"} and mlp one of {"mlp", "moe", "none"}.
# Dense models use a period of 1 (["attn+mlp"]); Jamba uses a period of 8.
# ---------------------------------------------------------------------------

VALID_MIXERS = ("attn", "mamba")
VALID_MLPS = ("mlp", "moe", "none")


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Unified configuration covering all supported families."""

    name: str
    family: str  # dense | moe | ssm | hybrid | encdec | vlm

    # Transformer trunk
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // n_heads

    # Layer arrangement (period pattern). Default: dense attn+mlp.
    layer_pattern: Tuple[str, ...] = ("attn+mlp",)

    # Attention flavour
    rope_theta: float = 10000.0
    use_rope: bool = True
    sliding_window: int = 0          # 0 = full attention
    attn_logit_softcap: float = 0.0
    # beyond-paper perf knobs (EXPERIMENTS.md §Perf):
    attn_impl: str = "repeat"        # repeat | grouped (no KV materialization)
    attn_softmax_dtype: str = "float32"  # float32 | bfloat16 logits/probs
    # serving paged-attention backend: "jnp" materialises the block-table
    # gather (the reference); "pallas" routes the paged branch of
    # layers.multihead_attention through kernels/paged_attention.py +
    # kernels/paged_prefill.py (full attention only — no sliding window /
    # logit softcap).  None = the platform's choice (repro.runtime:
    # pallas on TPU, jnp on CPU); ServeConfig.paged_backend overrides it
    # per stream.
    paged_backend: Optional[str] = None   # None | jnp | pallas

    # Norm / activation flavour
    norm_type: str = "rmsnorm"       # rmsnorm | layernorm | nonparametric
    mlp_type: str = "swiglu"         # swiglu | geglu | gelu
    tie_embeddings: bool = False

    # MoE
    n_experts: int = 0
    n_experts_per_tok: int = 0
    d_ff_moe: int = 0                # 0 -> d_ff
    moe_capacity_factor: float = 1.25
    router_aux_loss_coef: float = 0.01

    # SSM (Mamba2 / SSD)
    ssm_d_state: int = 0
    ssm_d_conv: int = 4
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_n_groups: int = 1
    ssm_chunk: int = 128

    # Encoder-decoder (whisper): encoder is attn+mlp, full attention,
    # learned positions, consumes stubbed frame embeddings.
    n_encoder_layers: int = 0
    encoder_seq_len: int = 0         # e.g. 1500 audio frames
    # VLM: number of stubbed image-patch embeddings prepended to text.
    n_patch_tokens: int = 0

    # Context
    max_seq_len: int = 8192

    # LoRA defaults for this arch (which linears get adapters)
    lora_rank: int = 16
    lora_alpha: float = 32.0
    lora_targets: Tuple[str, ...] = ("wq", "wk", "wv", "wo", "w_up", "w_gate", "w_out")

    # dtype policy
    dtype: str = "bfloat16"          # activations/frozen params
    param_dtype: str = "bfloat16"    # base (frozen) param storage

    # rematerialisation of the per-period body under the layer scan
    # (training memory ~O(sqrt) of depth; standard for big models)
    remat: bool = True
    # remat policy: "full" recomputes everything (min memory, recomputes the
    # TP all-reduces in backward); "dots" saves matmul/collective outputs
    # (§Perf: trades peak memory for ~1/3 of the collective term)
    remat_policy: str = "full"

    # unroll factor for the layer scan. 1 = rolled while-loop (fast compile,
    # production default). The dry-run fully unrolls because XLA's
    # cost_analysis counts a while body ONCE, not × trip count — full unroll
    # makes HLO_FLOPs/bytes exact for the roofline (tests/test_roofline.py).
    scan_unroll: int = 1

    # Source citation for the config values.
    citation: str = ""

    def __post_init__(self):
        assert self.paged_backend in (None, "jnp", "pallas"), (
            f"{self.name}: unknown paged_backend {self.paged_backend!r}")
        for p in self.layer_pattern:
            mixer, _, mlp = p.partition("+")
            assert mixer in VALID_MIXERS and mlp in VALID_MLPS, p
        assert self.n_layers % len(self.layer_pattern) == 0, (
            f"{self.name}: n_layers={self.n_layers} not a multiple of the "
            f"pattern period {len(self.layer_pattern)}")

    # -- derived ----------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def n_periods(self) -> int:
        return self.n_layers // len(self.layer_pattern)

    @property
    def resolved_d_ff_moe(self) -> int:
        return self.d_ff_moe or self.d_ff

    @property
    def ssm_d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_n_heads(self) -> int:
        return self.ssm_d_inner // self.ssm_head_dim

    @property
    def is_encdec(self) -> bool:
        return self.family == "encdec"

    def has_mixer(self, mixer: str) -> bool:
        return any(p.startswith(mixer + "+") or p == mixer for p in self.layer_pattern)

    def has_moe(self) -> bool:
        return any(p.endswith("+moe") for p in self.layer_pattern)

    def with_overrides(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # -- parameter accounting (for Fig-4 style reporting & rooflines) ------
    def count_params(self) -> int:
        """Total base parameters (approximate, exact for our impl)."""
        d, ff, V = self.d_model, self.d_ff, self.vocab_size
        hd = self.resolved_head_dim
        per = {
            "attn": d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd
                    + self.n_heads * hd * d,
            "mamba": (d * (2 * self.ssm_d_inner + 2 * self.ssm_n_groups * self.ssm_d_state
                           + self.ssm_n_heads)
                      + self.ssm_d_inner * d
                      + self.ssm_d_conv * (self.ssm_d_inner + 2 * self.ssm_n_groups * self.ssm_d_state)
                      + 2 * self.ssm_n_heads),
            "mlp": (3 if self.mlp_type in ("swiglu", "geglu") else 2) * d * ff,
            "moe": self.n_experts * (3 if self.mlp_type in ("swiglu", "geglu") else 2)
                   * d * self.resolved_d_ff_moe + d * self.n_experts,
            "none": 0,
        }
        total = 0
        for i in range(self.n_layers):
            mixer, _, mlp = self.layer_pattern[i % len(self.layer_pattern)].partition("+")
            total += per[mixer] + per[mlp] + 2 * d  # + norms
        total += V * d  # embed
        if not self.tie_embeddings:
            total += V * d
        if self.is_encdec:
            enc_layer = per["attn"] + per["mlp"] + 2 * d
            total += self.n_encoder_layers * (enc_layer + per["attn"] + d)  # + cross-attn
            total += self.encoder_seq_len * d + self.max_seq_len * d  # learned pos
        return total

    def count_active_params(self) -> int:
        """Active parameters per token (MoE: only routed experts)."""
        if not self.has_moe():
            return self.count_params()
        d = self.d_model
        moe_full = self.n_experts * 3 * d * self.resolved_d_ff_moe
        moe_active = self.n_experts_per_tok * 3 * d * self.resolved_d_ff_moe
        n_moe_layers = sum(1 for i in range(self.n_layers)
                           if self.layer_pattern[i % len(self.layer_pattern)].endswith("+moe"))
        return self.count_params() - n_moe_layers * (moe_full - moe_active)

    def count_lora_params(self, rank: Optional[int] = None) -> int:
        """Trainable parameters of one LoRA adapter set."""
        r = rank or self.lora_rank
        from repro.core.lora import lora_target_shapes
        return sum(din * r + r * dout for (din, dout) in lora_target_shapes(self))


# ---------------------------------------------------------------------------
# Input shapes (assigned): name -> (seq_len, global_batch, kind)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}
