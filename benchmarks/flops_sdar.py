"""Operation counts of the SDAR sparse-expert decoder trained by
diffusion over blocks (``benchmarks/configs/sdar-30b-a3b-chat.json``).

A step feeds 2L rows for L data tokens: every layer's projections,
router and chosen experts run on all 2L; the head on the noisy half's L
alone. A step's *model* FLOPs count no recomputed operation: 6 x rows x
the parameters a row is multiplied by, 6 x (row-expert pairs computed
here) x one expert's parameters, 6 x L x the head's, and each layer's
attention forward + backward on the doubled mask's exact area
(``masks_blockdiff.blockdiff_area``: n^2 + n B a document). For the
kernels' roofline only, the attention FLOPs a step *executes*: under
remat a layer's forward runs twice, so 1 + 1 + 2.5 = 4.5 x forward.
"""

from __future__ import annotations

from . import flops

EXECUTED_OVER_FWD = 2.0 + flops.BWD_OVER_FWD  # forward, remat's, backward


def attn_params(cfg: dict) -> int:
    """q, k, v and o of one layer (no gate, no bias)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return d * hd * (
        2 * cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"]
    )


def expert_params(cfg: dict) -> int:
    """One expert: a SwiGLU at ``moe_intermediate_size``."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def per_row_params(cfg: dict) -> int:
    """Parameters every one of the 2L rows is multiplied by on this
    rank: every layer's attention and router. The embedding is a lookup
    and the norms are vectors: neither counts."""
    return cfg["num_hidden_layers"] * (
        attn_params(cfg) + cfg["hidden_size"] * cfg["num_experts"]
    )


def head_params(cfg: dict) -> int:
    """The vocabulary slice's output head: the noisy half's rows only."""
    return cfg["hidden_size"] * cfg["vocab_here"]


def attn_executed_flops(cfg: dict, area: int) -> float:
    """Attention FLOPs the flex kernels execute in one step under remat."""
    return (
        cfg["num_hidden_layers"] * EXECUTED_OVER_FWD
        * flops.attn_fwd_flops(
            area, cfg["num_attention_heads"], cfg["head_dim"]
        )
    )


def train_step_flops(cfg: dict, data_tokens: int, area: int,
                     pairs_here: float) -> float:
    """Forward + backward of one packed sequence of ``data_tokens``
    tokens fed as twice as many rows; ``area`` the doubled mask's exact
    area, ``pairs_here`` the row-expert pairs all layers computed on
    this rank in the step."""
    attn = cfg["num_hidden_layers"] * flops.attn_fwdbwd_flops(
        area, cfg["num_attention_heads"], cfg["head_dim"]
    )
    return (
        6.0 * per_row_params(cfg) * 2 * data_tokens
        + 6.0 * head_params(cfg) * data_tokens
        + 6.0 * pairs_here * expert_params(cfg)
        + attn
    )
