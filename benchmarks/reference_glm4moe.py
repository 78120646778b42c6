"""The plain float32 GLM-4.7-Flash decoder (``model_type``
``glm4_moe_lite``), independent of ``magiattention_tpu/``.

Straightforward ``jax.numpy``: dense boolean masks, no kernels, no
sorting, no cache, no planner. Callers run it under
``jax.default_matmul_precision("highest")``. ``cfg`` is the configuration
file's keys (``benchmarks/configs/glm-4.7-flash.json``); ``params`` is a
pytree with the names ``models/pattern.py`` documents, which is all the
two share. Everything ``config.json`` does not itself state is listed in
the configuration file under ``assumed``.

A layer, token ``i`` at position ``p_i``, hidden ``x``, no biases::

    h   = norm(x; attn_norm)
    c_q = norm(h W_dq; q_a_norm)
    q   = c_q W_uq                 -> heads x (nope | rope)
    [c_kv | k_r] = h W_dkv         # kv_lora_rank | rope
    kv  = norm(c_kv; kv_a_norm) W_ukv   -> heads x (nope k | v_head_dim v)
    q_h = [q_nope_h ; rope(q_rope_h, p_i)]
    k_h = [k_nope_h ; rope(k_r, p_i)]   # one rotary key, every head's
    o_h = softmax(q_h k_h^T / sqrt(nope + rope), allowed keys) v_h
    x   = x + concat_h(o_h) W_o
    g   = norm(x; mlp_norm)
    x   = x + SwiGLU(g)                                  # dense layers
    x   = x + shared(g) + sum_{k in top-k} w_k expert_k(g)    # the others

and the multi-token-prediction module after the last layer's ``x``
(before the final norm), sharing ``embed`` and ``lm_head``::

    x'  = [norm(embed(t_{i+1}); embed_norm) ; norm(x_i; hidden_norm)] W_eh
    x'' = layer_mtp(x')            # same mask, same positions
    loss = CE(head(norm(x; final_norm)), t_{i+1})
           + mtp_loss_weight CE(head(norm(x''; mtp final_norm)), t_{i+2})

One rank's share of the deployment, as the system under test is given it
(``reference_afmoe``'s rule): the router is ``n_routed_experts`` wide and
chooses ``num_experts_per_tok``; of the chosen only ``experts_here`` =
[first, last) are computed; the vocabulary is the slice the parameters
hold.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .reference import _rope
from .reference_afmoe import _hashable, _rms_norm, _swiglu


def router(h, w, cfg: dict, forced=None):
    """(chosen experts [t, k], their weights [t, k], margins [t, k]):
    ``noaux_tc`` at one group. Sigmoid scores; the top
    ``num_experts_per_tok`` of score + ``expert_bias``; the scores at the
    chosen experts over their sum (``norm_topk_prob``), times
    ``routed_scaling_factor``. ``forced`` [t, k] takes the place of the
    router's own choice (the weights are still this router's scores
    there); a margin is how far under the k-th best score + bias a chosen
    expert's lies: 0 for the router's own choice, the size of the tie a
    forced one broke."""
    s = jax.nn.sigmoid(h @ w["w_router"])
    biased = s + w["expert_bias"]
    best, idx = jax.lax.top_k(biased, cfg["num_experts_per_tok"])
    if forced is not None:
        idx = forced
    margins = best[:, -1:] - jnp.take_along_axis(biased, idx, axis=1)
    wts = jnp.take_along_axis(s, idx, axis=1)
    if cfg["norm_topk_prob"]:
        wts = wts / wts.sum(axis=1, keepdims=True)
    return idx, wts * cfg["routed_scaling_factor"], jnp.maximum(margins, 0.0)


def expert_ffn(h, w, cfg: dict, forced=None):
    """shared(h) + sum over the chosen experts held here of weight x
    expert(h): every held expert on every token, then masked."""
    idx, wts, margins = router(h, w, cfg, forced)
    first, last = cfg["experts_here"]
    y = jnp.zeros_like(h)
    for e in range(first, last):
        w_e = jnp.where(idx == e, wts, 0.0).sum(axis=1)  # 0 where not chosen
        j = e - first
        y = y + w_e[:, None] * _swiglu(
            h, w["we_gate"][j], w["we_up"][j], w["we_down"][j]
        )
    if cfg["n_shared_experts"]:
        y = y + _swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"])
    return y, (idx, margins)


def latent_attention(h, w, allow, pos, cfg: dict):
    """The attention half of a layer on the normed hidden state ``h``
    [t, hidden], before the output projection: [t, heads x v_head_dim]."""
    eps = cfg["rms_norm_eps"]
    t = h.shape[0]
    heads = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    theta = float(cfg["rope_theta"])
    q = (_rms_norm(h @ w["wq_a"], w["q_a_norm"], eps) @ w["wq_b"]).reshape(
        t, heads, nope + rope
    )
    q = jnp.concatenate(
        [q[..., :nope], _rope(q[..., nope:], pos, theta)], axis=-1
    )
    c = h @ w["wkv_a"]
    kv = (_rms_norm(c[:, :rank], w["kv_a_norm"], eps) @ w["wkv_b"]).reshape(
        t, heads, nope + vd
    )
    k_rope = _rope(c[:, None, rank:], pos, theta)  # [t, 1, rope]: one head
    s = jnp.einsum("rhd,chd->hrc", q[..., :nope], kv[..., :nope])
    s = s + jnp.einsum("rhd,cd->hrc", q[..., nope:], k_rope[:, 0])
    s = jnp.where(allow[None], s * (nope + rope) ** -0.5, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hrc,chd->rhd", p, kv[..., nope:]).reshape(t, heads * vd)


def _layer(x, w, allow, pos, forced, cfg_items, is_dense):
    cfg = dict(cfg_items)
    eps = cfg["rms_norm_eps"]
    h = _rms_norm(x, w["attn_norm"], eps)
    x = x + latent_attention(h, w, allow, pos, cfg) @ w["wo"]
    g = _rms_norm(x, w["mlp_norm"], eps)
    if is_dense:
        return x + _swiglu(g, w["w_gate"], w["w_up"], w["w_down"]), None
    y, routed = expert_ffn(g, w, cfg, forced)
    return x + y, routed


def _mean_ce(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()


def glm4moe_loss(params, tokens, labels, labels2, allow, cfg: dict, *,
                 with_routing: bool = False, forced_routing=None):
    """The training loss of one packed sequence, float32 throughout:
    mean next-token cross-entropy, plus ``mtp_loss_weight`` x the MTP
    module's mean cross-entropy on ``labels2`` (token i + 2) where
    ``params`` holds a module. ``allow`` [t, t] is the documents' causal
    mask. With ``with_routing`` also the expert layers' chosen experts
    and their margins (``router``), each [layers, t, k], the trunk's
    layers and then the module's; ``forced_routing`` [layers, t, k] hands
    every expert layer its choice. A layer is recomputed in the backward,
    which changes no value."""
    f32 = jnp.float32
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(tokens.shape[0])
    layer_fn = jax.checkpoint(_layer, static_argnums=(5, 6))
    embed = params["embed"].astype(f32)
    head = params["lm_head"].astype(f32)
    chosen = []

    def run_layer(x, layer, dense):
        w = {n: a.astype(f32) for n, a in layer.items()}
        forced = None
        if forced_routing is not None and not dense:
            forced = forced_routing[len(chosen)]
        x, routed = layer_fn(x, w, allow, pos, forced, _hashable(cfg), dense)
        if routed is not None:
            chosen.append(routed)
        return x

    x = embed[tokens]
    for i, layer in enumerate(params["layers"]):
        x = run_layer(x, layer, i < cfg["first_k_dense_replace"])
    loss = _mean_ce(
        _rms_norm(x, params["final_norm"].astype(f32), eps) @ head, labels
    )
    modules = params.get("mtp", ())
    if len(modules) > 1:
        raise ValueError("one MTP module (GLM-4.7-Flash's) is written down")
    for mod in modules:
        m = {n: a.astype(f32) for n, a in mod.items() if n != "layer"}
        x = jnp.concatenate(
            [_rms_norm(embed[labels], m["embed_norm"], eps),
             _rms_norm(x, m["hidden_norm"], eps)], axis=-1,
        ) @ m["eh_proj"]
        # a layer of the kind the last trunk layer is
        x = run_layer(
            x, mod["layer"],
            cfg["first_k_dense_replace"] >= len(params["layers"]),
        )
        loss = loss + cfg["mtp_loss_weight"] * _mean_ce(
            _rms_norm(x, m["final_norm"], eps) @ head, labels2
        )
    if not with_routing:
        return loss
    return loss, tuple(jnp.stack(a) for a in zip(*chosen))
