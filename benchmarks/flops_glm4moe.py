"""Operation counts of the GLM-4.7-Flash decoder
(``benchmarks/configs/glm-4.7-flash.json``), ``flops_afmoe``'s rules.

A step's *model* FLOPs count no recomputed operation: 6 x tokens x the
parameters every token is multiplied by, 6 x (token-expert pairs computed
here) x one expert's parameters, and each layer's attention forward +
backward on the exact area of the mask at ``num_attention_heads`` heads
of ``qk_nope_head_dim + qk_rope_head_dim`` (the expanded form the flex
kernels run; ``v_head_dim`` is the same width). The MTP module, where the
configuration has one, is one more layer of each, its ``eh_proj``, and a
second pass through the shared output head. The pairs are read from a
step, not assumed. For the kernels' roofline only, the attention FLOPs a
step *executes*: under remat a layer's forward runs twice, so
1 + 1 + 2.5 = 4.5 x forward.
"""

from __future__ import annotations

from . import flops
from .flops_afmoe import EXECUTED_OVER_FWD, expert_params  # a SwiGLU expert


def head_dim(cfg: dict) -> int:
    """The width of a head as the flex kernels see it."""
    return cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]


def mtp_modules(cfg: dict) -> int:
    return int(cfg.get("num_nextn_predict_layers", 0))


def attn_layers(cfg: dict) -> int:
    """Layers whose attention a step runs: the trunk's and the MTP
    modules' one each."""
    return cfg["num_hidden_layers"] + mtp_modules(cfg)


def attn_params(cfg: dict) -> int:
    """One layer's latent attention: the q down- and up-projection, the
    key-value down-projection with the shared rotary key's columns, the
    up-projection to every head's k_nope and v, and the output."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd = cfg["v_head_dim"]
    return (
        d * cfg["q_lora_rank"]
        + cfg["q_lora_rank"] * heads * (nope + rope)
        + d * (cfg["kv_lora_rank"] + rope)
        + cfg["kv_lora_rank"] * heads * (nope + vd)
        + heads * vd * d
    )


def expert_layer_token_params(cfg: dict) -> int:
    """What every token meets in an expert layer: the attention, the
    shared experts and the router."""
    return (
        attn_params(cfg)
        + cfg["n_shared_experts"] * expert_params(cfg)
        + cfg["hidden_size"] * cfg["n_routed_experts"]
    )


def per_token_params(cfg: dict) -> int:
    """Parameters every token is multiplied by on this rank. The
    embedding is a lookup and the norms are vectors: neither counts. The
    MTP module shares the output head and goes through it again."""
    d = cfg["hidden_size"]
    head = d * cfg["vocab_here"]
    n_dense = cfg["first_k_dense_replace"]
    total = head
    total += n_dense * (attn_params(cfg) + 3 * d * cfg["intermediate_size"])
    n_expert = cfg["num_hidden_layers"] - n_dense
    total += n_expert * expert_layer_token_params(cfg)
    total += mtp_modules(cfg) * (
        expert_layer_token_params(cfg) + 2 * d * d + head
    )
    return total


def attn_executed_flops(cfg: dict, area: int) -> float:
    """Attention FLOPs the flex kernels execute in one step under
    remat, all layers' (one attention kind: every layer is full)."""
    return attn_layers(cfg) * EXECUTED_OVER_FWD * flops.attn_fwd_flops(
        area, cfg["num_attention_heads"], head_dim(cfg)
    )


def train_step_flops(cfg: dict, tokens: int, area: int,
                     pairs_here: float) -> float:
    """Forward + backward of one packed sequence; ``area`` the exact
    area of the documents' causal mask, ``pairs_here`` the token-expert
    pairs all expert layers (the MTP module's too) computed on this rank
    in the step."""
    attn = attn_layers(cfg) * flops.attn_fwdbwd_flops(
        area, cfg["num_attention_heads"], head_dim(cfg)
    )
    return (
        6.0 * per_token_params(cfg) * tokens
        + 6.0 * pairs_here * expert_params(cfg)
        + attn
    )
