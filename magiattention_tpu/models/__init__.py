"""Model families built on the framework."""

from .dit import (
    DiTConfig,
    MagiDiT,
    build_magi_dit,
    chunk_causal_mask,
    init_dit_params,
)
from .llama import LlamaConfig, MagiLlama, build_magi_llama, init_params
from .pattern import (
    MagiPattern,
    PatternConfig,
    afmoe_config,
    build_magi_pattern,
    glm4_moe_lite_config,
    init_pattern_params,
    llama_pattern,
    ouro_config,
)
from .llama_pp import (
    MagiLlamaPP,
    build_magi_llama_pp,
    init_pp_params,
    stack_layer_params,
)

__all__ = [
    "DiTConfig",
    "LlamaConfig",
    "MagiDiT",
    "MagiLlama",
    "MagiLlamaPP",
    "MagiPattern",
    "PatternConfig",
    "afmoe_config",
    "build_magi_dit",
    "build_magi_llama",
    "build_magi_llama_pp",
    "build_magi_pattern",
    "chunk_causal_mask",
    "glm4_moe_lite_config",
    "init_dit_params",
    "init_params",
    "init_pattern_params",
    "llama_pattern",
    "ouro_config",
    "init_pp_params",
    "stack_layer_params",
]
