"""Operation and byte counts of the granite-4.0-h-micro decoder
(``benchmarks/configs/granite-4.0-h-micro.json``), ``flops_phi4flash``'s
rules.

A step's *model* FLOPs count no recomputed operation and no padding: 6 x
tokens x the parameters every token is multiplied by (a layer's by its
kind; the depthwise convolution as the matrix it is, a weight a channel a
tap), the attention layer's forward + backward on the exact area of its
mask at 32 heads of 64, and the scans in their chunked form at the
PUBLISHED chunk (``mamba_chunk_size`` 256), whatever tile the kernel
takes: a row of a forward pass is the chunk's ``C B^T`` (2 x 256 x 128),
the product with the chunk's ``delta x`` (2 x 256 x 4,096), and the
state read and written (2 x 2 x 4,096 x 128); a backward pass twice that
(each product has two transposes). The embedding is tied: its rows are
the output head's, counted once.

For the kernels' rooflines only: what a step *executes*. Since PR 48 a
remat layer keeps its attention call's out and lse, so a step launches
the attention layer's forward kernel once and its backward once (1 + 2.5
= 3.5 x forward); the scan keeps nothing across remat, so its forward
kernel runs twice and its backward once (:data:`LAUNCHES`, which
``tests/test_benchmarks/test_ssd_check.py`` counts in the step's
gradient). The backward kernel's own recomputation of a chunk's forward
products is no work; nor are the zeroed lanes of two heads that share a
tile. The bytes are what the scans cannot avoid moving.
"""

from __future__ import annotations

from . import flops
from .reference_granite import ATTENTION, MAMBA, layer_kinds

# kernel launches of one layer in one step, by kernel
LAUNCHES = {
    "magi_flex_fwd_kernel": 1, "magi_flex_bwd_kernel": 1,
    "magi_ssd_scan_fwd_kernel": 2, "magi_ssd_scan_bwd_kernel": 1,
}
ATTN_EXECUTED_OVER_FWD = (
    LAUNCHES["magi_flex_fwd_kernel"]
    + LAUNCHES["magi_flex_bwd_kernel"] * flops.BWD_OVER_FWD
)
SCAN_BWD_OVER_FWD = 2.0


def sizes(cfg: dict) -> dict:
    """The widths the counts read: the file's keys and its assumed sizes."""
    heads, width = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    return {
        "d": cfg["hidden_size"], "hq": cfg["num_attention_heads"],
        "hk": cfg["num_key_value_heads"],
        "hd": cfg["assumed"]["sizes"]["head_dim"],
        "h": heads, "e": heads * width, "n": cfg["mamba_d_state"],
        "taps": cfg["mamba_d_conv"], "chunk": cfg["mamba_chunk_size"],
        "ffn": cfg["shared_intermediate_size"],
    }


def mixer_params(cfg: dict, kind: str) -> int:
    """The matrices of one layer's mixer half, by its kind (vectors,
    the norms, biases, A and D, count as nothing)."""
    z = sizes(cfg)
    d, e, n = z["d"], z["e"], z["n"]
    return {
        MAMBA: d * (2 * e + 2 * n + z["h"]) + z["taps"] * (e + 2 * n) + e * d,
        ATTENTION: d * 2 * (z["hq"] + z["hk"]) * z["hd"],
    }[kind]


def per_token_params(cfg: dict) -> int:
    """Parameters every token is multiplied by on this rank: every kept
    layer's mixer and its dense SwiGLU, and the tied embedding's slice as
    the output head."""
    z = sizes(cfg)
    return (
        sum(mixer_params(cfg, k) + 3 * z["d"] * z["ffn"]
            for k in layer_kinds(cfg))
        + z["d"] * cfg["vocab_here"]
    )


def attn_fwd_flops(cfg: dict, area: int) -> float:
    z = sizes(cfg)
    return flops.attn_fwd_flops(area, z["hq"], z["hd"])


def attn_executed_flops(cfg: dict, area: int) -> float:
    """Attention FLOPs the flex kernels execute for the model in one
    step, over its attention layers (:data:`LAUNCHES`)."""
    return (
        layer_kinds(cfg).count(ATTENTION) * ATTN_EXECUTED_OVER_FWD
        * attn_fwd_flops(cfg, area)
    )


def ssd_scan_fwd_flops(cfg: dict, tokens: int) -> float:
    """One layer's scan, one forward pass, in the chunked form at the
    published chunk."""
    z = sizes(cfg)
    q, e, n = z["chunk"], z["e"], z["n"]
    return float(tokens) * (2 * q * n + 2 * q * e + 2 * 2 * e * n)


def ssd_scan_flops(cfg: dict, tokens: int) -> float:
    """The scans' FLOPs of one step as launched, all Mamba-2 layers."""
    passes = (
        LAUNCHES["magi_ssd_scan_fwd_kernel"]
        + LAUNCHES["magi_ssd_scan_bwd_kernel"] * SCAN_BWD_OVER_FWD
    )
    return (
        layer_kinds(cfg).count(MAMBA) * passes * ssd_scan_fwd_flops(cfg, tokens)
    )


def ssd_scan_bytes(cfg: dict, tokens: int) -> float:
    """Bytes the scans of one step cannot avoid moving, all Mamba-2
    layers', every operand once a pass in the model's dtypes: a forward
    pass reads ``x`` (bf16), the step ``delta`` (float32 a head), ``B``
    and ``C`` (bf16) and writes ``y`` (bf16); the backward reads those
    four and ``y``'s cotangent and writes the cotangents of ``x``,
    ``delta``, ``B`` and ``C``. ``A`` and ``D``, a head's, are nothing
    beside them."""
    z = sizes(cfg)
    e, h, n = z["e"], z["h"], z["n"]
    fwd = e * (2 + 2) + h * 4 + 2 * n * 2
    bwd = e * (2 + 2 + 2) + 2 * h * 4 + 4 * n * 2
    return float(
        layer_kinds(cfg).count(MAMBA) * tokens * (
            LAUNCHES["magi_ssd_scan_fwd_kernel"] * fwd
            + LAUNCHES["magi_ssd_scan_bwd_kernel"] * bwd
        )
    )


def train_step_flops(cfg: dict, tokens: int, area: int) -> float:
    """Forward + backward of one packed sequence; ``area`` the exact area
    of the documents' causal mask."""
    kinds = layer_kinds(cfg)
    attn = kinds.count(ATTENTION) * (1.0 + flops.BWD_OVER_FWD) * attn_fwd_flops(
        cfg, area
    )
    scans = kinds.count(MAMBA) * (1.0 + SCAN_BWD_OVER_FWD) * ssd_scan_fwd_flops(
        cfg, tokens
    )
    return 6.0 * per_token_params(cfg) * tokens + attn + scans
