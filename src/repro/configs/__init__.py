"""Config registry: one module per assigned architecture.

`get_config(name)` returns the full published config; `get_config(name,
reduced=True)` the CPU smoke-test derivative.
"""

from __future__ import annotations

from repro.configs.base import ModelConfig, MoEConfig, SSMConfig, SHAPES, get_shape

from repro.configs import (
    whisper_tiny, mixtral_8x22b, arctic_480b, qwen2_vl_2b, qwen3_0_6b,
    qwen1_5_32b, granite_20b, granite_3_8b, zamba2_1_2b, mamba2_2_7b,
    granite_4_0_h_micro, paper_gemm,
)

_REGISTRY = {
    m.CONFIG.name: m.CONFIG
    for m in (
        whisper_tiny, mixtral_8x22b, arctic_480b, qwen2_vl_2b, qwen3_0_6b,
        qwen1_5_32b, granite_20b, granite_3_8b, zamba2_1_2b, mamba2_2_7b,
        granite_4_0_h_micro,
    )
}

ARCH_NAMES = tuple(sorted(_REGISTRY))


def get_config(name: str, reduced: bool = False) -> ModelConfig:
    cfg = _REGISTRY[name]
    return cfg.reduced() if reduced else cfg
