"""Operation counts of the AFMoE decoder (``benchmarks/configs/trinity-mini.json``).

A step's *model* FLOPs count no recomputed operation: 6 x tokens x the
parameters every token is multiplied by, 6 x (token-expert pairs computed
here) x one expert's parameters, and each layer's attention forward +
backward on the exact area of its kind's mask (``flops.attn_fwdbwd_flops``).
The pairs are read from a step, not assumed. For the kernels' rooflines
only, the attention FLOPs a step *executes* a kind: under remat a layer's
forward runs twice, so 1 + 1 + 2.5 = 4.5 x forward.
"""

from __future__ import annotations

from . import flops

EXECUTED_OVER_FWD = 2.0 + flops.BWD_OVER_FWD  # forward, remat's, backward


def layer_kinds(cfg: dict) -> list[tuple[str, bool]]:
    """(attention kind, FFN is dense) a layer."""
    return [
        (kind, i < cfg["num_dense_layers"])
        for i, kind in enumerate(cfg["layer_types"])
    ]


def attn_params(cfg: dict) -> int:
    """q, k, v, o and the output gate of one layer."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * hd * (2 * hq + 2 * hk) + d * hq * hd


def expert_params(cfg: dict) -> int:
    """One expert: a SwiGLU at ``moe_intermediate_size``."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def per_token_params(cfg: dict) -> int:
    """Parameters every token is multiplied by on this rank: every
    layer's attention, the dense FFN or the shared experts and the
    router, and the vocabulary slice's output head. The embedding is a
    lookup and the norms are vectors: neither counts."""
    d = cfg["hidden_size"]
    total = d * cfg["vocab_here"]
    for _kind, dense in layer_kinds(cfg):
        total += attn_params(cfg)
        if dense:
            total += 3 * d * cfg["intermediate_size"]
        else:
            total += cfg["num_shared_experts"] * expert_params(cfg)
            total += d * cfg["num_experts"]
    return total


def kind_layers(cfg: dict, kind: str) -> int:
    return sum(k == kind for k in cfg["layer_types"])


def attn_executed_flops(cfg: dict, kind: str, area: int) -> float:
    """Attention FLOPs the flex kernels of ``kind``'s layers execute in
    one step under remat."""
    return kind_layers(cfg, kind) * EXECUTED_OVER_FWD * flops.attn_fwd_flops(
        area, cfg["num_attention_heads"], cfg["head_dim"]
    )


def train_step_flops(cfg: dict, tokens: int, areas: dict[str, int],
                     pairs_here: float) -> float:
    """Forward + backward of one packed sequence; ``areas`` the exact
    area of each attention kind's mask, ``pairs_here`` the token-expert
    pairs all expert layers computed on this rank in the step."""
    attn = sum(
        kind_layers(cfg, kind) * flops.attn_fwdbwd_flops(
            area, cfg["num_attention_heads"], cfg["head_dim"]
        )
        for kind, area in areas.items()
    )
    return (
        6.0 * per_token_params(cfg) * tokens
        + 6.0 * pairs_here * expert_params(cfg)
        + attn
    )
