"""Config registry: ``get_config(name)`` / ``get_smoke(name)`` / ``ARCHS``."""
from repro.configs import (
    deepseek_67b,
    deepseek_coder_33b,
    gemma3_1b,
    jamba_v0_1_52b,
    kimi_k2_1t_a32b,
    llama31_8b,
    mamba2_1_3b,
    mixtral_8x22b,
    moonlight_16b_a3b,
    musicgen_medium,
    olmo_1b,
    pixtral_12b,
)
from repro.configs.base import (
    CURConfig,
    MeshConfig,
    ModelConfig,
    OptimizerConfig,
    ShapeConfig,
    SHAPES,
    TrainConfig,
    shape_applicable,
)

_MODULES = {
    "deepseek-67b": deepseek_67b,
    "gemma3-1b": gemma3_1b,
    "olmo-1b": olmo_1b,
    "deepseek-coder-33b": deepseek_coder_33b,
    "musicgen-medium": musicgen_medium,
    "mamba2-1.3b": mamba2_1_3b,
    "mixtral-8x22b": mixtral_8x22b,
    "kimi-k2-1t-a32b": kimi_k2_1t_a32b,
    "pixtral-12b": pixtral_12b,
    "jamba-v0.1-52b": jamba_v0_1_52b,
    "llama3.1-8b": llama31_8b,
    "moonlight-16b-a3b": moonlight_16b_a3b,
}

# the 10 assigned architectures and moonlight-16b-a3b (the paper's own
# model is extra)
ARCHS = tuple(k for k in _MODULES if k != "llama3.1-8b")


def get_config(name: str) -> ModelConfig:
    return _MODULES[name].CONFIG


def get_smoke(name: str) -> ModelConfig:
    return _MODULES[name].SMOKE


def get_repro() -> ModelConfig:
    """The CPU-scale llama-family model used for quality experiments."""
    return llama31_8b.REPRO
