"""Architecture registry: ``--arch <id>`` resolves here.

Each module exposes ``FULL`` (the exact assigned config) and ``SMOKE``
(reduced variant: ≤2-3 layers, d_model ≤ 512, ≤4 experts) of the same family.
"""
from typing import Optional

from repro.configs.base import (
    ArchConfig,
    AttentionConfig,
    MoEConfig,
    SSMConfig,
    RGLRUConfig,
    ShapeConfig,
    TrainConfig,
    SHAPES,
)

from repro.configs import (
    minicpm3_4b,
    musicgen_medium,
    qwen3_14b,
    deepseek_v2_236b,
    internvl2_2b,
    gemma3_12b,
    phi35_moe,
    gemma2_27b,
    recurrentgemma_2b,
    mamba2_2_7b,
)

ARCHS = {
    "minicpm3-4b": minicpm3_4b,
    "musicgen-medium": musicgen_medium,
    "qwen3-14b": qwen3_14b,
    "deepseek-v2-236b": deepseek_v2_236b,
    "internvl2-2b": internvl2_2b,
    "gemma3-12b": gemma3_12b,
    "phi3.5-moe-42b-a6.6b": phi35_moe,
    "gemma2-27b": gemma2_27b,
    "recurrentgemma-2b": recurrentgemma_2b,
    "mamba2-2.7b": mamba2_2_7b,
}

# Archs whose base attention is quadratic-full: long_500k runs their
# sliding-window VARIANT (ring-buffer KV, window=8192). See DESIGN.md §5.
SWA_VARIANT_FOR_LONG = {
    "minicpm3-4b",
    "musicgen-medium",
    "qwen3-14b",
    "deepseek-v2-236b",
    "internvl2-2b",
    "phi3.5-moe-42b-a6.6b",
}
LONG_WINDOW = 8192


def get_arch(name: str, smoke: bool = False,
             layers: Optional[int] = None) -> ArchConfig:
    """The registered config; ``layers`` cuts its depth to that many
    layers and keeps every width (the cut a chip's share of a model
    takes)."""
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; choose from {sorted(ARCHS)}")
    mod = ARCHS[name]
    cfg = mod.SMOKE if smoke else mod.FULL
    if layers is None:
        return cfg
    if not 1 <= layers <= cfg.n_layers:
        raise ValueError(f"layers={layers} outside 1..{cfg.n_layers} "
                         f"for {cfg.name}")
    return cfg.replace(n_layers=layers)


def arch_for_shape(name: str, shape: str, smoke: bool = False) -> ArchConfig:
    """Resolve the arch config to use for a given input shape.

    long_500k on full-attention archs swaps in the sliding-window variant so
    decode state stays O(window) instead of O(seq_len).
    """
    cfg = get_arch(name, smoke=smoke)
    if shape == "long_500k" and name in SWA_VARIANT_FOR_LONG:
        att = cfg.attention
        assert att is not None
        cfg = cfg.replace(
            name=cfg.name + "+swa",
            attention=AttentionConfig(
                **{**att.__dict__, "window": LONG_WINDOW},
            ),
            block_pattern=("L",),
        )
    return cfg


__all__ = [
    "ArchConfig",
    "AttentionConfig",
    "MoEConfig",
    "SSMConfig",
    "RGLRUConfig",
    "ShapeConfig",
    "TrainConfig",
    "SHAPES",
    "ARCHS",
    "get_arch",
    "arch_for_shape",
    "SWA_VARIANT_FOR_LONG",
    "LONG_WINDOW",
]
