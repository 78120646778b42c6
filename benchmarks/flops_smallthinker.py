"""Operation counts of the SmallThinker sparse-expert decoder
(``benchmarks/configs/smallthinker-21b-a3b.json``), ``flops_sdar``'s rules.

A step's *model* FLOPs count no recomputed operation and no padding: 6 x
rows x the parameters every row is multiplied by (a layer's four
attention matrices and its router), 6 x (row-expert pairs computed here)
x one expert's parameters (the zero rows a whole chunk's matmuls also
take are no work), 6 x rows x the head's, and each layer's attention
forward + backward on its own kind's exact area: the documents' causal
mask in the layers whose ``sliding_window_layout`` entry is 0, the same
under ``sliding_window_size`` in the others.

For the kernels' rooflines only, the attention FLOPs a step *executes*:
since PR 48 a remat layer keeps its attention call's out and lse, so a
step launches a layer's forward kernel once and its backward once: 1 +
2.5 = 3.5 x forward (``tests/test_benchmarks/test_prerouted_check.py``
counts the launches of the step's gradient against ``LAUNCHES``).
"""

from __future__ import annotations

from . import flops
from .flops_sdar import attn_params  # q, k, v and o, no gate, no bias

SLIDING, FULL = "sliding_attention", "full_attention"
# the flex kernels a layer's attention launches in a step: the forward
# once (kept across remat), the one backward kernel once
LAUNCHES = {"fwd": 1, "bwd": 1}
EXECUTED_OVER_FWD = LAUNCHES["fwd"] + LAUNCHES["bwd"] * flops.BWD_OVER_FWD


def kind_layers(cfg: dict, kind: str) -> int:
    """The kept layers of one attention kind."""
    layout = cfg["sliding_window_layout"][: cfg["num_hidden_layers"]]
    return sum(bool(w) == (kind == SLIDING) for w in layout)


def expert_params(cfg: dict) -> int:
    """One expert: a gated FFN at ``moe_ffn_hidden_size``."""
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def per_row_params(cfg: dict) -> int:
    """Parameters every row is multiplied by on this rank but the head's:
    every layer's attention and router. The embedding is a lookup and the
    norms are vectors: neither counts."""
    return cfg["num_hidden_layers"] * (
        attn_params(cfg)
        + cfg["hidden_size"] * cfg["moe_num_primary_experts"]
    )


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_here"]


def attn_executed_flops(cfg: dict, kind: str, area: int) -> float:
    """Attention FLOPs the flex kernels of ``kind``'s layers execute in
    one step (:data:`LAUNCHES`)."""
    return kind_layers(cfg, kind) * EXECUTED_OVER_FWD * flops.attn_fwd_flops(
        area, cfg["num_attention_heads"], cfg["head_dim"]
    )


def train_step_flops(cfg: dict, tokens: int, areas: dict[str, int],
                     pairs_here: float) -> float:
    """Forward + backward of one packed sequence of ``tokens`` rows;
    ``areas`` each kind's exact mask area, ``pairs_here`` the row-expert
    pairs all layers computed on this rank in the step."""
    attn = sum(
        kind_layers(cfg, kind) * flops.attn_fwdbwd_flops(
            areas[kind], cfg["num_attention_heads"], cfg["head_dim"]
        )
        for kind in (FULL, SLIDING)
    )
    return (
        6.0 * (per_row_params(cfg) + head_params(cfg)) * tokens
        + 6.0 * pairs_here * expert_params(cfg)
        + attn
    )
