"""Operation and byte counts of the Phi-4-mini-flash-reasoning decoder
(``benchmarks/configs/phi-4-mini-flash-reasoning.json``), ``flops_afmoe``'s
rules.

A step's *model* FLOPs count no recomputed operation and no padding: 6 x
tokens x the parameters every token is multiplied by (a layer's by its
kind; the depthwise convolution as the matrix it is, a weight a channel a
tap), each attention layer's forward + backward on the exact area of its
mask, and the scans. Differential attention a query pair is two score
matrices on 64-wide heads and two products with a 128-wide value pair:
per allowed (query, key) pair ``2 x 40 x 64 + 2 x 40 x 128`` FLOPs
forward, whatever form the kernels run it in (today a 64-wide head rides
a 128-wide kernel head, zeros in the other half: those lanes count as no
work). The scan a token a channel a state: the decay's product and
exponential, the state's update (two products, a sum) and the read-out
(a product, a sum), 7 FLOPs forward, twice that backward. The embedding
is tied: its rows are the output head's, counted once. For the kernels'
rooflines only: the attention FLOPs a step *executes* under remat (1 + 1
+ 2.5 = 4.5 x forward) and the bytes the scan cannot avoid moving.
"""

from __future__ import annotations

from . import flops
from .flops_afmoe import EXECUTED_OVER_FWD
from .reference_phi4flash import CROSS, FULL, GMU, SLIDING, SSM, layer_kinds

SCAN_FLOPS_FWD = 7  # a token a channel a state
SCAN_BWD_OVER_FWD = 2.0


def sizes(cfg: dict) -> dict:
    """The widths the counts read: the file's keys and its assumed sizes."""
    s = cfg["assumed"]["sizes"]
    d = cfg["hidden_size"]
    return {
        "d": d, "hq": cfg["num_attention_heads"],
        "hk": cfg["num_key_value_heads"], "hd": s["head_dim"],
        "e": s["expand"] * d, "n": s["d_state"], "r": s["dt_rank"],
        "taps": s["d_conv"], "ffn": cfg["intermediate_size"],
    }


def mixer_params(cfg: dict, kind: str) -> int:
    """The matrices of one layer's mixer half, by its kind (vectors,
    the norms, biases, lambda, A and D, count as nothing)."""
    z = sizes(cfg)
    d, e = z["d"], z["e"]
    q, kv = z["hq"] * z["hd"], z["hk"] * z["hd"]
    return {
        SSM: d * 2 * e + z["taps"] * e + e * (z["r"] + 2 * z["n"])
        + z["r"] * e + e * d,
        GMU: 2 * d * e,
        SLIDING: d * (2 * q + 2 * kv),
        FULL: d * (2 * q + 2 * kv),
        CROSS: d * 2 * q,
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
    """One attention layer's forward on ``area`` allowed pairs: the
    scores at ``head_dim``, the value product at twice that."""
    z = sizes(cfg)
    return 2.0 * area * z["hq"] * (z["hd"] + 2 * z["hd"])


def attn_layers(cfg: dict) -> dict[str, int]:
    """Attention layers by the plan they run on: the window's, and the
    documents' whole mask (the full layer and the cross layers)."""
    kinds = layer_kinds(cfg)
    return {
        SLIDING: kinds.count(SLIDING),
        FULL: kinds.count(FULL) + kinds.count(CROSS),
    }


def attn_executed_flops(cfg: dict, kind: str, area: int) -> float:
    """Attention FLOPs the flex kernels of one plan (``SLIDING``,
    ``FULL``) execute for the model in one step under remat, over all
    its layers; the padded lanes are no work."""
    return attn_layers(cfg)[kind] * EXECUTED_OVER_FWD * attn_fwd_flops(cfg, area)


def scan_fwd_flops(cfg: dict, tokens: int) -> float:
    z = sizes(cfg)
    return float(SCAN_FLOPS_FWD) * tokens * z["e"] * z["n"]


def ssm_scan_bytes(cfg: dict, tokens: int) -> float:
    """Bytes the scans of one step cannot avoid moving, all state-space
    layers', every operand once a pass: a forward pass reads ``u`` (bf16),
    the step ``delta`` (float32), ``B`` and ``C`` (bf16) and writes ``y``
    (bf16); under remat it runs twice; the backward reads those four and
    ``y``'s cotangent and writes the cotangents of ``u`` (bf16),
    ``delta`` (float32), ``B`` and ``C``. ``A`` and ``D``, a channel's,
    are nothing beside them."""
    z = sizes(cfg)
    fwd = z["e"] * (2 + 4 + 2) + 2 * z["n"] * 2
    bwd = z["e"] * (2 + 4 + 2 + 2 + 4) + 4 * z["n"] * 2
    return float(layer_kinds(cfg).count(SSM) * tokens * (2 * fwd + bwd))


def train_step_flops(cfg: dict, tokens: int, areas: dict[str, int]) -> float:
    """Forward + backward of one packed sequence; ``areas`` the exact
    area of the documents' causal mask (``FULL``) and of the same under
    the window (``SLIDING``)."""
    attn = sum(
        n * (1.0 + flops.BWD_OVER_FWD) * attn_fwd_flops(cfg, areas[kind])
        for kind, n in attn_layers(cfg).items()
    )
    scans = (
        layer_kinds(cfg).count(SSM) * (1.0 + SCAN_BWD_OVER_FWD)
        * scan_fwd_flops(cfg, tokens)
    )
    return 6.0 * per_token_params(cfg) * tokens + attn + scans
