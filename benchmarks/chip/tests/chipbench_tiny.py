"""A tiny configuration and traffic that a CPU run can hold."""

TINY_CONFIG = {
    "name": "tiny", "arch": "dense", "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 2, "vocab_size": 256,
    "max_position_embeddings": 128, "rope_theta": 10000.0,
    "tie_word_embeddings": False, "norm": "rmsnorm", "norm_eps": 1e-6,
    "embed_multiplier": 1.0, "init": {"embed_std": 0.02, "lora_b_std": 0.5},
    "lora": {"rank": 4, "alpha": 8.0,
             "targets": ["wq", "wk", "wv", "wo", "w_gate", "w_up", "w_out"]},
    "serving": {"block_size": 8, "kv_pool_blocks": 33},
    "check": {"max_logit_gap": 0.25}}

TINY_TIED = dict(TINY_CONFIG, name="tiny-tied", tie_word_embeddings=True,
                 norm="layernorm_nonparametric", embed_multiplier=8.0,
                 num_key_value_heads=4)

TINY_TRAFFIC = {
    "loop": "open", "arrival": "poisson", "rate_per_s": 1000.0,
    "prompt_len": {"median": 24, "sigma": 0.3, "min": 12, "max": 40},
    "output_len": {"median": 8, "sigma": 0.4, "min": 4, "max": 16},
    "context_limit": 56, "clients": 4, "client_zipf_s": 1.0, "slots": 4,
    "prefill_chunk": 16, "scan_chunk": 8, "check_sample": 16}
