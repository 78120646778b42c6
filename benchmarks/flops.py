"""Operation counts and the table of peaks.

Attention FLOPs follow the reference's benchmark arithmetic
(``cp_benchmark.md:38-61``): forward = 4 * area * heads_q * head_dim
(QK^T and PV, two FLOPs a multiply-add), backward = 2.5 x forward (five
matmuls against the forward's two), so forward+backward = 3.5 x forward.
``area`` is the exact number of allowed (query, key) pairs of the mask
(``masks.Mask.area``); recomputation is never counted.
"""

from __future__ import annotations

import json
import os

BWD_OVER_FWD = 2.5

_PEAKS_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "peaks.json"
)


def attn_fwd_flops(area: int, heads_q: int, head_dim: int) -> float:
    return 4.0 * area * heads_q * head_dim


def attn_bwd_flops(area: int, heads_q: int, head_dim: int) -> float:
    return BWD_OVER_FWD * attn_fwd_flops(area, heads_q, head_dim)


def attn_fwdbwd_flops(area: int, heads_q: int, head_dim: int) -> float:
    return (1.0 + BWD_OVER_FWD) * attn_fwd_flops(area, heads_q, head_dim)


ATTN_FLOPS = {
    "attn_fwd": attn_fwd_flops,
    "attn_bwd": attn_bwd_flops,
    "attn_fwdbwd": attn_fwdbwd_flops,
}


def decoder_matmul_params(cfg: dict) -> int:
    """Parameters of a dense decoder that a token is multiplied by: the
    per-layer projections and FFN, and the output head. The embedding is
    a lookup and the norms are vectors: neither counts."""
    d = cfg["hidden_size"]
    hd = cfg["head_dim"]
    attn = d * hd * (2 * cfg["num_attention_heads"]
                     + 2 * cfg["num_key_value_heads"])
    ffn = 3 * d * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (attn + ffn) + d * cfg["vocab_size"]


def train_step_flops(cfg: dict, tokens: int, area: int) -> float:
    """Forward + backward of one packed sequence: 6 * params * tokens for
    the matmuls, plus every layer's attention forward+backward on the
    mask's exact area. No recomputed operation is counted."""
    attn = attn_fwdbwd_flops(
        area, cfg["num_attention_heads"], cfg["head_dim"]
    )
    return (
        6.0 * decoder_matmul_params(cfg) * tokens
        + cfg["num_hidden_layers"] * attn
    )


def load_peaks(device_kind: str) -> dict:
    """The peaks of one device kind; an unknown kind is an error."""
    with open(_PEAKS_PATH) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        known = sorted(k for k in table if not k.startswith("_"))
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {_PEAKS_PATH}; "
            f"known: {known}"
        )
    return table[device_kind]


def roofline_pct(flops: float, seconds: float, peak_tflops: float) -> float:
    """Share of the compute roofline: the least time the chip could take
    for ``flops`` over the time it took. (The flex kernels are
    compute-bound at these shapes: at head_dim 128 a tile's bytes are
    two orders under its FLOPs over the machine balance.)"""
    return 100.0 * flops / (peak_tflops * 1e12) / seconds
