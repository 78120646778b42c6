"""Operation counts of the ZAYA1 decoder
(``benchmarks/configs/zaya1-8b.json``), ``flops_afmoe``'s rules.

A step's *model* FLOPs count no recomputed operation: 6 x tokens x the
parameters every token is multiplied by, 6 x (tokens the experts held
here compute: read from a step, not assumed, and not the grouped
matmul's capacity) x one expert's parameters, and each layer's attention
forward + backward on the exact area of the mask at
``num_attention_heads`` query heads of ``head_dim`` (the attention runs
in the projections' latent: no up-projection on either side of the
kernels). A convolution counts as the matrix it is: the depthwise one a
weight a channel a tap, the grouped one a ``head_dim`` square a head a
tap. The embedding is tied: its rows are the output head's, counted once
(the lookup multiplies nothing). For the kernels' roofline only, the
attention FLOPs a step *executes* (under remat a layer's forward runs
twice, so 1 + 1 + 2.5 = 4.5 x forward) and the bytes those launches
cannot avoid moving.
"""

from __future__ import annotations

from . import flops
from .flops_afmoe import EXECUTED_OVER_FWD, expert_params  # a SwiGLU expert


def latent_widths(cfg: dict) -> tuple[int, int]:
    """(query latent, key / value latent) in elements a token."""
    hd = cfg["head_dim"]
    return cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd


def attn_params(cfg: dict) -> int:
    """One layer's attention half: q, k, v, o and the two convolutions on
    ``[q | k]``."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = latent_widths(cfg)
    heads = cfg["num_attention_heads"] + cfg["num_key_value_heads"]
    convs = cfg["cca_time0"] * (q + kv) + cfg["cca_time1"] * heads * hd * hd
    return d * (2 * q + 2 * kv) + convs


def router_params(cfg: dict) -> int:
    """The MLP router: down, two hidden layers, the output."""
    rh = cfg["router_hidden_size"]
    return cfg["hidden_size"] * rh + 2 * rh * rh + rh * cfg["num_experts"]


def per_token_params(cfg: dict) -> int:
    """Parameters every token is multiplied by on this rank: every
    layer's attention half and router, and the tied embedding's slice as
    the output head. The norms, the biases, the temperatures and the
    router's per-channel state weight are vectors and do not count."""
    return (
        cfg["num_hidden_layers"] * (attn_params(cfg) + router_params(cfg))
        + cfg["hidden_size"] * cfg["vocab_here"]
    )


def attn_executed_flops(cfg: dict, area: int) -> float:
    """Attention FLOPs the flex kernels execute in one step under remat,
    all layers' (one attention kind: every layer is full)."""
    return (
        cfg["num_hidden_layers"] * EXECUTED_OVER_FWD
        * flops.attn_fwd_flops(
            area, cfg["num_attention_heads"], cfg["head_dim"]
        )
    )


def attn_executed_bytes(cfg: dict, tokens: int, itemsize: int = 2) -> float:
    """Bytes those kernels cannot avoid moving in one step, every operand
    once a launch: the forward (twice under remat) reads q, k, v and
    writes out; dq reads q, k, v, out's cotangent and writes dq; dkv
    reads the same and writes dk, dv. The float32 statistics a row (lse,
    delta) are left out: under 1% of q's bytes."""
    q, kv = latent_widths(cfg)
    fwd = 2 * q + 2 * kv
    dq = 3 * q + 2 * kv
    dkv = 2 * q + 4 * kv
    return (
        cfg["num_hidden_layers"] * tokens * itemsize * (2 * fwd + dq + dkv)
    )


def train_step_flops(cfg: dict, tokens: int, area: int,
                     pairs_here: float) -> float:
    """Forward + backward of one packed sequence; ``area`` the exact
    area of the documents' causal mask, ``pairs_here`` the tokens all
    layers' held experts computed on this rank in the step."""
    attn = cfg["num_hidden_layers"] * flops.attn_fwdbwd_flops(
        area, cfg["num_attention_heads"], cfg["head_dim"]
    )
    return (
        6.0 * per_token_params(cfg) * tokens
        + 6.0 * pairs_here * expert_params(cfg)
        + attn
    )
