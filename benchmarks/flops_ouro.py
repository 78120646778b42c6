"""Operation counts of the looped Ouro decoder
(``benchmarks/configs/ouro-2.6b.json``), ``flops_afmoe``'s rules.

A step's *model* FLOPs count no recomputed operation: 6 x tokens x the
parameters every token is multiplied by, and a layer's attention forward
+ backward on the exact area of the mask. The layer stack runs
``total_ut_steps`` times on shared weights and every pass ends in the
shared head and the exit gate, so a token is multiplied by a layer's
parameters, the head's and the gate's once a pass: ``num_hidden_layers x
total_ut_steps`` layer applications and ``total_ut_steps`` passes through
the head. For the kernels' roofline only, the attention FLOPs a step
*executes*: under remat a layer application's forward runs twice, so
1 + 1 + 2.5 = 4.5 x forward.
"""

from __future__ import annotations

from . import flops
from .flops_afmoe import EXECUTED_OVER_FWD


def layer_applications(cfg: dict) -> int:
    return cfg["num_hidden_layers"] * cfg["total_ut_steps"]


def layer_params(cfg: dict) -> int:
    """One layer's matrices: q, k, v, o and the SwiGLU's three."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * hd * (2 * hq + 2 * hk) + 3 * d * cfg["intermediate_size"]


def per_token_params(cfg: dict) -> int:
    """Parameters a token is multiplied by in one step, a pass counted
    each time it runs. The embedding is a lookup and the norms are
    vectors: neither counts."""
    d = cfg["hidden_size"]
    exit_params = d * cfg["vocab_size"] + d  # the head and the gate
    return (
        layer_applications(cfg) * layer_params(cfg)
        + cfg["total_ut_steps"] * exit_params
    )


def attn_executed_flops(cfg: dict, area: int) -> float:
    """Attention FLOPs the flex kernels execute in one step under
    remat, all layer applications' (one attention kind: every layer is
    full)."""
    return layer_applications(cfg) * EXECUTED_OVER_FWD * flops.attn_fwd_flops(
        area, cfg["num_attention_heads"], cfg["head_dim"]
    )


def train_step_flops(cfg: dict, tokens: int, area: int) -> float:
    """Forward + backward of one packed sequence; ``area`` the exact
    area of the documents' causal mask."""
    attn = layer_applications(cfg) * flops.attn_fwdbwd_flops(
        area, cfg["num_attention_heads"], cfg["head_dim"]
    )
    return 6.0 * per_token_params(cfg) * tokens + attn
