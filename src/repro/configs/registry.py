"""Architecture registry: ``--arch <id>`` resolution for every launcher."""
from __future__ import annotations

import importlib
from typing import Dict, List, Optional

from repro.configs.base import INPUT_SHAPES, InputShape, ModelConfig

_MODULES = {
    "starcoder2-15b": "repro.configs.starcoder2_15b",
    "whisper-small": "repro.configs.whisper_small",
    "dbrx-132b": "repro.configs.dbrx_132b",
    "internvl2-26b": "repro.configs.internvl2_26b",
    "gemma-2b": "repro.configs.gemma_2b",
    "yi-6b": "repro.configs.yi_6b",
    "mamba2-2.7b": "repro.configs.mamba2_2p7b",
    "olmo-1b": "repro.configs.olmo_1b",
    "kimi-k2-1t-a32b": "repro.configs.kimi_k2_1t_a32b",
    "jamba-v0.1-52b": "repro.configs.jamba_v0p1_52b",
    "llama2-7b": "repro.configs.llama2_7b",  # the paper's own backbone
    "granite-4.0-h-micro": "repro.configs.granite_4p0_h_micro",  # chip bench
}

# the assignment's architectures: all but the paper's backbone and the
# chip benchmark's hybrid
ASSIGNED_ARCHS: List[str] = [k for k in _MODULES
                             if k not in ("llama2-7b", "granite-4.0-h-micro")]
ALL_ARCHS: List[str] = list(_MODULES)


def get_config(arch: str, smoke: bool = False) -> ModelConfig:
    mod = importlib.import_module(_MODULES[arch])
    return mod.SMOKE_CONFIG if smoke else mod.CONFIG


def get_shape(name: str) -> InputShape:
    return INPUT_SHAPES[name]


# ---------------------------------------------------------------------------
# arch × shape applicability + per-shape config adjustment
# ---------------------------------------------------------------------------

def shape_supported(arch: str, shape: str) -> bool:
    """DESIGN.md §5: the only skip is whisper × long_500k (enc-dec with an
    architecturally bounded decoder context)."""
    if arch == "whisper-small" and shape == "long_500k":
        return False
    return True


def config_for_shape(arch: str, shape: str, smoke: bool = False) -> ModelConfig:
    """Per-shape variant: dense archs take a 4k sliding window for long_500k
    (the sub-quadratic variant the task spec requires); everything else runs
    its base config."""
    cfg = get_config(arch, smoke)
    if shape == "long_500k" and cfg.family in ("dense", "moe", "vlm") \
            and cfg.sliding_window == 0:
        cfg = cfg.with_overrides(sliding_window=4096)
    if shape in ("decode_32k", "long_500k", "prefill_32k"):
        need = INPUT_SHAPES[shape].seq_len
        if cfg.max_seq_len < need:
            cfg = cfg.with_overrides(max_seq_len=need)
    return cfg
