"""The plain float32 AFMoE decoder (Trinity), independent of
``magiattention_tpu/``.

Straightforward ``jax.numpy``: dense boolean masks, no kernels, no
sorting, no cache, no planner. Callers run it under
``jax.default_matmul_precision("highest")``. ``cfg`` is the configuration
file's keys (``benchmarks/configs/trinity-mini.json``); ``params`` is a
pytree with the names ``models/pattern.py`` documents, which is all the
two share. Everything ``config.json`` does not itself state is listed in
the configuration file under ``assumed``.

One rank's share of the deployment, as the system under test is given
it: the router is ``num_experts`` wide and chooses ``num_experts_per_tok``
of them; of the chosen, only the experts ``experts_here`` = [first, last)
are computed (the others' terms arrive from other ranks in a deployment);
the vocabulary is the slice the parameters hold.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .reference import _rope


def window_allowed(allow, window: int):
    """``allow`` [t, t] cut to the last ``window`` keys of every row, the
    row's own included: k > q - window."""
    t = allow.shape[0]
    q = jnp.arange(t)[:, None]
    k = jnp.arange(t)[None, :]
    return allow & (k > q - window)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def router(h, w, cfg: dict, forced=None):
    """(chosen experts [t, k], their weights [t, k], margins [t, k]).
    ``forced`` [t, k] takes the place of the router's own choice (the
    weights are still this router's scores there); a margin is how far
    under the k-th best score + bias a chosen expert's lies: 0 for the
    router's own choice, the size of the tie a forced one broke."""
    s = jax.nn.sigmoid(h @ w["w_router"])
    biased = s + w["expert_bias"]
    best, idx = jax.lax.top_k(biased, cfg["num_experts_per_tok"])
    if forced is not None:
        idx = forced
    margins = best[:, -1:] - jnp.take_along_axis(biased, idx, axis=1)
    wts = jnp.take_along_axis(s, idx, axis=1)
    if cfg["route_norm"]:
        wts = wts / wts.sum(axis=1, keepdims=True)
    return idx, wts * cfg["route_scale"], jnp.maximum(margins, 0.0)


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
    if cfg["num_shared_experts"]:
        y = y + _swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"])
    return y, (idx, margins)


def _layer(x, w, allow, pos, forced, cfg_items, layer_type, is_dense):
    cfg = dict(cfg_items)
    eps = cfg["rms_norm_eps"]
    t = x.shape[0]
    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    g = hq // hk
    h = _rms_norm(x, w["attn_norm"], eps)
    q = _rms_norm((h @ w["wq"]).reshape(t, hq, hd), w["q_norm"], eps)
    k = _rms_norm((h @ w["wk"]).reshape(t, hk, hd), w["k_norm"], eps)
    v = (h @ w["wv"]).reshape(t, hk, hd)
    if layer_type == "sliding_attention":  # global layers carry no position
        q = _rope(q, pos, float(cfg["rope_theta"]))
        k = _rope(k, pos, float(cfg["rope_theta"]))
    s = jnp.einsum("rkgd,ckd->kgrc", q.reshape(t, hk, g, hd), k)
    s = jnp.where(allow[None, None], s * hd ** -0.5, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    attn = jnp.einsum("kgrc,ckd->rkgd", p, v).reshape(t, hq * hd)
    attn = (attn * jax.nn.sigmoid(h @ w["w_attn_gate"])) @ w["wo"]
    x = x + _rms_norm(attn, w["post_attn_norm"], eps)
    h = _rms_norm(x, w["mlp_norm"], eps)
    if is_dense:
        y, routed = _swiglu(h, w["w_gate"], w["w_up"], w["w_down"]), None
    else:
        y, routed = expert_ffn(h, w, cfg, forced)
    return x + _rms_norm(y, w["post_mlp_norm"], eps), routed


def _hashable(cfg: dict):
    return tuple(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in cfg.items()
        if isinstance(v, (int, float, bool, str, list))
    )


def afmoe_loss(params, tokens, labels, allow_full, cfg: dict, *,
               with_routing: bool = False, forced_routing=None):
    """Mean next-token cross-entropy of one packed sequence, float32
    throughout. ``allow_full`` [t, t] is the documents' causal mask;
    sliding layers see it under ``window_allowed(.., sliding_window)``.
    With ``with_routing`` also the expert layers' chosen experts and
    their margins (``router``), each [layers, t, k]; ``forced_routing``
    [layers, t, k] hands every expert layer its choice. A layer is
    recomputed in the backward, which changes no value."""
    f32 = jnp.float32
    allow = {
        "full_attention": allow_full,
        "sliding_attention": window_allowed(
            allow_full, cfg["sliding_window"]
        ),
    }
    pos = jnp.arange(tokens.shape[0])
    layer_fn = jax.checkpoint(_layer, static_argnums=(5, 6, 7))
    x = params["embed"].astype(f32)[tokens]
    if cfg["mup_enabled"]:
        x = x * cfg["hidden_size"] ** 0.5
    chosen = []
    for i, layer in enumerate(params["layers"]):
        w = {n: a.astype(f32) for n, a in layer.items()}
        kind = cfg["layer_types"][i]
        dense = i < cfg["num_dense_layers"]
        forced = None
        if forced_routing is not None and not dense:
            forced = forced_routing[len(chosen)]
        x, routed = layer_fn(
            x, w, allow[kind], pos, forced, _hashable(cfg), kind, dense
        )
        if routed is not None:
            chosen.append(routed)
    logits = _rms_norm(
        x, params["final_norm"].astype(f32), cfg["rms_norm_eps"]
    ) @ params["lm_head"].astype(f32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    loss = -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()
    if not with_routing:
        return loss
    return loss, tuple(jnp.stack(a) for a in zip(*chosen))
