"""What the served tokens need: operations and bytes counted from the
configuration's shapes and the harness's token records, and the chip's
peaks.

Counts are of the algorithm, not of what the program happens to compute:
padded rows, logits nobody reads and recomputation do not count.  A
multiply-add is two operations.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

# Published peaks of one chip, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM at 819 GB/s.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; an unknown kind is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (have {sorted(PEAKS)})")
    return PEAKS[device_kind]


def _targets(c: dict) -> Dict[str, Tuple[int, int]]:
    d, H, Kv, hd, ff = (c["hidden_size"], c["num_attention_heads"],
                        c["num_key_value_heads"], c["head_dim"],
                        c["intermediate_size"])
    return {"wq": (d, H * hd), "wk": (d, Kv * hd), "wv": (d, Kv * hd),
            "wo": (H * hd, d), "w_gate": (d, ff), "w_up": (d, ff),
            "w_out": (ff, d)}


def matmul_params(c: dict) -> int:
    """Weights a token multiplies: the seven projections of every layer
    and the unembedding (the embedding is a lookup)."""
    per_layer = sum(i * o for i, o in _targets(c).values())
    return c["num_hidden_layers"] * per_layer \
        + c["hidden_size"] * c["vocab_size"]


def lora_flops_per_token(c: dict) -> int:
    """2 r (d_in + d_out) for each adapted projection of every layer."""
    r = c["lora"]["rank"]
    return c["num_hidden_layers"] * sum(
        2 * r * (i + o) for t, (i, o) in _targets(c).items()
        if t in c["lora"]["targets"])


def attn_flops(c: dict, ctx: int) -> int:
    """Scores and weighted sum of one query over ``ctx`` keys, all layers:
    4 ctx H hd per layer."""
    return 4 * ctx * c["num_attention_heads"] * c["head_dim"] \
        * c["num_hidden_layers"]


def kv_bytes(c: dict, ctx: int, itemsize: int = 2) -> int:
    """K and V of ``ctx`` positions, all layers, at ``itemsize`` bytes."""
    return 2 * ctx * c["num_key_value_heads"] * c["head_dim"] \
        * c["num_hidden_layers"] * itemsize


def token_flops(c: dict, ctx: int) -> int:
    """Everything one token needs at context ``ctx`` (itself included):
    the matmuls, its LoRA deltas and its attention."""
    return 2 * matmul_params(c) + lora_flops_per_token(c) \
        + attn_flops(c, ctx)


def prompt_work(c: dict, prompt: int, chunk: int) -> Tuple[int, int, int]:
    """(all FLOPs, attention FLOPs, least attention bytes) of prefilling a
    ``prompt``-token prompt in chunks of ``chunk``: token t attends t + 1
    positions; each chunk reads the K/V of its context once (its own
    included)."""
    attn = attn_flops(c, 1) * prompt * (prompt + 1) // 2
    total = prompt * (2 * matmul_params(c) + lora_flops_per_token(c)) + attn
    nbytes = sum(kv_bytes(c, min(prompt, s + chunk))
                 for s in range(0, prompt, chunk))
    return total, attn, nbytes


def span_work(c: dict, records: Iterable, t0: float, t1: float,
              chunk: int) -> Dict[str, float]:
    """Work of the tokens a span [t0, t1) served, from token records.

    A record has ``prompt_len`` and ``events`` [(time, n_tokens), ...].
    A prompt counts where its first token arrived (its prefill ends
    there); output token i counts at the event that delivered it."""
    flops = attn = nbytes = 0.0
    for r in records:
        if not r.events:
            continue
        p = r.prompt_len
        if t0 <= r.events[0][0] < t1:
            f, a, b = prompt_work(c, p, chunk)
            flops, attn, nbytes = flops + f, attn + a, nbytes + b
        i = 0
        for t, n in r.events:
            for j in range(i, i + n):
                if j >= 1 and t0 <= t < t1:
                    flops += token_flops(c, p + j)
                    attn += attn_flops(c, p + j)
                    nbytes += kv_bytes(c, p + j)
            i += n
    return {"flops": flops, "attn_flops": attn, "attn_bytes": nbytes}
