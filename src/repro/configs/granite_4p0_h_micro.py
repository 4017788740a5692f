"""IBM Granite-4.0-H-Micro [huggingface.co/ibm-granite/granite-4.0-h-micro,
config.json; model_type granitemoehybrid] — a hybrid of 36 Mamba-2 layers
and 4 GQA layers without position embeddings, in a period of 10 (five
Mamba-2, one attention, four Mamba-2), every layer with a SwiGLU MLP of
8192.  Its published scalars: embeddings times 12, each sublayer's output
times 0.22 before the residual add, softmax scale 1/64 (not 1/sqrt(64)),
logits divided by 8, RMSNorm eps 1e-5.  The Mamba-2 mixer: 64 heads of
64, d_state 128, one group, a conv of 4 with bias, no projection bias.

Serving keeps each Mamba-2 layer's state in float32 per slot (64 x 64 x
128 floats, 2 MiB a sequence a layer) beside the attention layers' paged
K/V."""
from repro.configs.base import ModelConfig

PATTERN = ("mamba+mlp",) * 5 + ("attn+mlp",) + ("mamba+mlp",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-micro", family="hybrid",
    n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8, d_ff=8192,
    vocab_size=100352, head_dim=64,
    layer_pattern=PATTERN,
    norm_type="rmsnorm", norm_eps=1e-5, mlp_type="swiglu",
    tie_embeddings=True, use_rope=False,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.015625, logits_scaling=8.0,
    ssm_d_state=128, ssm_d_conv=4, ssm_expand=2, ssm_head_dim=64,
    ssm_n_groups=1, ssm_chunk=256, ssm_conv_bias=True,
    max_seq_len=131072,
    lora_targets=("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_out",
                  "in_proj", "out_proj"),
    citation="https://huggingface.co/ibm-granite/granite-4.0-h-micro",
)

SMOKE_CONFIG = CONFIG.with_overrides(
    name="granite-smoke", n_layers=10, d_model=128, n_heads=4, n_kv_heads=2,
    head_dim=32, d_ff=256, vocab_size=512, ssm_d_state=16, ssm_head_dim=16,
    ssm_chunk=8, max_seq_len=64)
