"""Config registry: --arch <id> resolution."""
from repro.configs.base import (ArchConfig, ShapeConfig, DIT_SHAPES,
                                DIT_TRAIN_SHAPES, SHAPES, SMOKE_SHAPES)

_ARCH_MODULES = {
    "h2o-danube-3-4b": "h2o_danube_3_4b",
    "gemma3-1b": "gemma3_1b",
    "mistral-large-123b": "mistral_large_123b",
    "qwen3-1.7b": "qwen3_1_7b",
    "zamba2-1.2b": "zamba2_1_2b",
    "rwkv6-7b": "rwkv6_7b",
    "whisper-small": "whisper_small",
    "moonshot-v1-16b-a3b": "moonshot_v1_16b_a3b",
    "llama4-maverick-400b-a17b": "llama4_maverick_400b_a17b",
    "internvl2-1b": "internvl2_1b",
    "wan2_1_1_3b": "wan2_1_1_3b",
    "lightningdit_1b": "lightningdit_1b",
}

ASSIGNED_ARCHS = list(_ARCH_MODULES)[:10]  # the 10 assigned (x4 shapes)
PAPER_ARCHS = list(_ARCH_MODULES)[10:]  # the paper's own models


def get_arch(name: str) -> ArchConfig:
    import importlib
    mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[name]}")
    return mod.CONFIG


def get_shape(name: str, smoke: bool = False) -> ShapeConfig:
    """Shape by name: the assigned SHAPES (or their SMOKE_SHAPES
    reductions), else a DiT shape by its ShapeConfig name."""
    if smoke:
        return SMOKE_SHAPES[name]
    if name in SHAPES:
        return SHAPES[name]
    return {s.name: s for s in (*DIT_SHAPES.values(),
                                *DIT_TRAIN_SHAPES)}[name]


__all__ = ["ArchConfig", "ShapeConfig", "SHAPES", "SMOKE_SHAPES",
           "ASSIGNED_ARCHS", "PAPER_ARCHS", "get_arch", "get_shape"]
