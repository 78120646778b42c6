"""Operation and byte counts of the Xing4.0 decoder
(``benchmarks/configs/xing4.0-29b-a4b.json``), ``flops_glm4moe``'s rules.

A step's *model* FLOPs count no recomputed operation and no padding: 6 x
tokens x the parameters every token is multiplied by (latent attention's
five matrices, a half-layer's ``phi``, the dense SwiGLU or the router and
the shared expert, the head), 6 x (token-expert pairs computed here) x one
expert's parameters, the stream mix's own multiplications (a read and a
write a half-layer), and each layer's attention forward + backward on the
exact area of the mask: per allowed (query, key) pair a head's score is
``qk_nope_head_dim + qk_rope_head_dim`` deep and its value product
``v_head_dim`` wide, ``2 x heads x (192 + 128)`` FLOPs forward; the 64
lanes of zeros that q and k ride into the kernels with are no work.

For the kernels' roofline only, the attention FLOPs a step *executes*:
since PR 48 a remat layer keeps its attention's out and lse, so a step
launches the forward kernel once a layer and the backward once. At one
head width that is 1 + 2.5 = 3.5 x forward; here the backward's five
products are three at the key width (S again, dK, dQ) and two at the value
width (dP, dV), 832 against the forward's 320: 3.6 x forward. For the stream mix's roofline, the bytes a step cannot avoid
moving (:func:`mhc_stream_bytes`).
"""

from __future__ import annotations

from .flops_afmoe import expert_params  # a SwiGLU expert
from .flops_glm4moe import attn_params, mtp_modules


def dense_layers(cfg: dict) -> int:
    """The kept layers that are dense: those of ``layers_kept`` under the
    published ``first_k_dense_replace``."""
    kept = cfg.get("layers_kept", range(cfg["num_hidden_layers"]))
    return sum(i < cfg["first_k_dense_replace"] for i in kept)


def attn_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] + mtp_modules(cfg)


def mixer_params(cfg: dict) -> int:
    """A layer's two stream mixers' ``phi``."""
    n = cfg["hc_mult"]
    return 2 * n * cfg["hidden_size"] * (n * n + 2 * n)


def expert_layer_token_params(cfg: dict) -> int:
    return (
        attn_params(cfg) + mixer_params(cfg)
        + cfg["n_shared_experts"] * expert_params(cfg)
        + cfg["hidden_size"] * cfg["n_routed_experts"]
    )


def per_token_params(cfg: dict) -> int:
    """Parameters every token is multiplied by on this rank (the embedding
    is a lookup, the norms are vectors; an MTP module shares the head and
    goes through it again)."""
    d = cfg["hidden_size"]
    head = d * cfg["vocab_here"]
    n_dense = dense_layers(cfg)
    return (
        head
        + n_dense * (
            attn_params(cfg) + mixer_params(cfg)
            + 3 * d * cfg["intermediate_size"]
        )
        + (cfg["num_hidden_layers"] - n_dense) * expert_layer_token_params(cfg)
        + mtp_modules(cfg) * (expert_layer_token_params(cfg) + 2 * d * d + head)
    )


def attn_fwd_flops(cfg: dict, area: int) -> float:
    """One attention layer's forward on ``area`` allowed pairs."""
    return 2.0 * area * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    )


def attn_bwd_flops(cfg: dict, area: int) -> float:
    """One attention layer's backward: S again, dK and dQ at the key
    width, dP and dV at the value width."""
    return 2.0 * area * cfg["num_attention_heads"] * (
        3 * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])
        + 2 * cfg["v_head_dim"]
    )


def attn_executed_flops(cfg: dict, area: int) -> float:
    """Attention FLOPs the flex kernels execute in one step, all layers':
    the forward kernel once (its out and lse are kept across remat), the
    backward once."""
    return attn_layers(cfg) * (
        attn_fwd_flops(cfg, area) + attn_bwd_flops(cfg, area)
    )


def mix_fwd_flops(cfg: dict, tokens: int) -> float:
    """The stream mix's own multiply-adds forward, all half-layers: the
    read (n x C), the write's n x n mix of streams and its n x C spread."""
    n, c = cfg["hc_mult"], cfg["hidden_size"]
    return 2.0 * attn_layers(cfg) * tokens * 2.0 * c * (2 * n + n * n)


def mhc_stream_bytes(cfg: dict, tokens: int, itemsize: int = 2,
                     remat: bool = True) -> float:
    """Bytes the stream mix of one step cannot avoid moving, all
    half-layers'. A state is ``tokens x hc_mult x hidden_size`` in the
    model's dtype (bf16), a hidden state 1 / ``hc_mult`` of it. Forward a
    half-layer reads the state twice (the coefficients need a token's
    whole row before the read can weigh it, so the write reads it again),
    writes the new state, writes ``u`` and reads ``y``: 3 states + 2
    hidden. Backward it reads the state and the new state's cotangent,
    writes the state's, reads ``u``'s cotangent and writes ``y``'s: the
    same count. Under remat the forward runs twice. The coefficients, 24
    numbers a token, are nothing beside them."""
    n = cfg["hc_mult"]
    state = tokens * n * cfg["hidden_size"] * itemsize
    one_pass = 3 * state + 2 * state // n
    return float(2 * attn_layers(cfg) * one_pass * (3 if remat else 2))


def train_step_flops(cfg: dict, tokens: int, area: int,
                     pairs_here: float) -> float:
    """Forward + backward of one packed sequence; ``area`` the exact area
    of the documents' causal mask, ``pairs_here`` the token-expert pairs
    all expert layers computed on this rank in the step."""
    attn = attn_executed_flops(cfg, area)  # nothing of it is recomputed
    return (
        6.0 * per_token_params(cfg) * tokens
        + 6.0 * pairs_here * expert_params(cfg)
        + 3.0 * mix_fwd_flops(cfg, tokens)
        + attn
    )
