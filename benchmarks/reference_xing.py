"""The plain float32 Xing4.0 decoder (``model_type`` ``xing4_0``),
independent of ``magiattention_tpu/``.

Straightforward ``jax.numpy``: a dense boolean mask from document ids, no
kernels, no sorting, no padding, no cache, no planner; Sinkhorn's rounds a
Python loop. Callers run it under
``jax.default_matmul_precision("highest")``. ``cfg`` is the configuration
file's keys (``benchmarks/configs/xing4.0-29b-a4b.json``); ``params`` is a
pytree with the names ``models/pattern.py`` documents, which is all the
two share.

The residual state is ``n = hc_mult`` streams, ``X`` [t, n, C], every
stream the token's embedding at first. A layer is two half-layers, ``F``
the attention and then ``F`` the feed-forward, each with a mixer of its
own (``phi`` [n C, n^2 + 2n], ``b``, ``alpha`` [3]), token by token::

    x_hat = vec(X)                                     # [n C]
    m     = (x_hat phi) rsqrt(mean(x_hat^2) + rms_norm_eps)
    H_pre  = sigmoid(alpha_1 m[0:n]  + b[0:n])
    H_post = 2 sigmoid(alpha_2 m[n:2n] + b[n:2n])
    M      = exp(clip(alpha_3 mat(m[2n:]) + mat(b[2n:]), clamp))
    hc_sinkhorn_iters times:  M <- M / (rowsum(M) + hc_eps)
                              M <- M / (colsum(M) + hc_eps)
    u   = sum_i H_pre[i] X[i]
    y   = F(norm(u))
    X'[i] = sum_j M[i, j] X[j] + H_post[i] y

``F`` attention is DeepSeek-V3's latent attention, heads of ``nope | rope``
keys beside ``v_head_dim`` values::

    c_q = norm(h W_dq; q_a_norm);  q = c_q W_uq   -> heads x (nope | rope)
    [c_kv | k_r] = h W_dkv                         # kv_lora_rank | rope
    kv  = norm(c_kv; kv_a_norm) W_ukv   -> heads x (nope k | v_head_dim v)
    s   = ([q_nope ; rot(q_rope)] . [k_nope ; rot(k_r)]) scale
    scale = (nope + rope)^-1/2 (0.1 mscale_all_dim ln(factor) + 1)^2
    o   = softmax(s, allowed keys) v;   y = concat_h(o_h) W_o

``rot`` is the half-split rotation at YaRN's frequencies
(:func:`yarn_inv_freq`); ``F`` feed-forward is ``reference_glm4moe``'s
(SwiGLU, or the shared expert plus the chosen experts held here). The
read-out is ``x = sum_i X[i]``, the final norm, the untied head; the MTP
module is ``reference_glm4moe``'s on that ``x``, its layer on streams of its
own (its input replicated, its output their sum).

Departures from what the published ``config.json`` states, each also under
``assumed`` in the configuration's file (from memory of the three papers;
there is no network here): where ``hc_eps`` enters (under each Sinkhorn
sum); ``phi`` applied to the raw state and the norm's factor after, the
norm's weight folded into ``phi``; rows before columns; the replicated
input and the summed read-out (arXiv:2409.19606 section 3); the MTP
module's streams; ``expert_bias`` a zero buffer; ``mtp_loss_weight`` 0.3;
YaRN's ramp between ``floor`` and ``ceil`` of the two pair indices;
``mscale == mscale_all_dim`` so cos and sin are unscaled.

One rank's share of the deployment, as the system under test is given it
(``reference_glm4moe``'s rule): the router is ``n_routed_experts`` wide and
chooses ``num_experts_per_tok``; of the chosen only ``experts_here`` are
computed; the vocabulary is the slice the parameters hold.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .reference_afmoe import _rms_norm, _swiglu
from .reference_glm4moe import _mean_ce, expert_ffn


def _hashable(cfg: dict):
    """``cfg``'s numbers, lists and the ``rope_scaling`` group as a static
    argument of ``jax.checkpoint`` (:func:`_layer` makes the dict again;
    ``reference_afmoe._hashable`` would drop the group)."""

    def static(v):
        if isinstance(v, dict):
            return tuple(sorted(v.items()))
        return tuple(v) if isinstance(v, list) else v

    return tuple(
        (k, static(v)) for k, v in cfg.items()
        if isinstance(v, (int, float, bool, str, list)) or k == "rope_scaling"
    )


HEADS_AT_A_TIME = 4  # a block's [heads, t, t] scores: 1.07 GB at 8,192 rows


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """The rotary pairs' angular frequencies [rope / 2], float32:
    ``f_j = theta^(-2j / rope)`` stretched by ``rope_scaling`` (YaRN,
    arXiv:2309.00071): ``d(beta) = rope ln(original / (2 pi beta)) /
    (2 ln theta)`` is the pair that turns ``beta`` times in the original
    context; pairs below ``floor(d(beta_fast))`` keep ``f_j``, pairs above
    ``ceil(d(beta_slow))`` take ``f_j / factor``, a linear ramp between."""
    rope, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    j = np.arange(rope // 2, dtype=np.float64)
    f = theta ** (-2.0 * j / rope)
    sc = cfg.get("rope_scaling")
    if not sc:
        return f.astype(np.float32)

    def d(beta):
        return rope * math.log(
            sc["original_max_position_embeddings"] / (2 * math.pi * beta)
        ) / (2 * math.log(theta))

    lo = max(math.floor(d(sc["beta_fast"])), 0)
    hi = min(math.ceil(d(sc["beta_slow"])), rope - 1)
    r = np.clip((j - lo) / (hi - lo), 0.0, 1.0)
    return (f * (1 - r) + f / sc["factor"] * r).astype(np.float32)


def softmax_scale(cfg: dict) -> float:
    sc = cfg.get("rope_scaling") or {}
    factor, all_dim = sc.get("factor", 1.0), sc.get("mscale_all_dim", 0.0)
    if sc and sc.get("mscale", 1.0) != all_dim:
        raise ValueError("mscale != mscale_all_dim is not written down")
    m = 0.1 * all_dim * math.log(factor) + 1.0 if factor > 1 else 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _rot(x, pos, freqs):
    """x [t, h, rope] rotated, half-split (``reference._rope``'s layout)."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def latent_attention(h, w, doc, pos, cfg: dict):
    """The attention on the normed hidden state ``h`` [t, hidden], before
    the output projection: [t, heads x v_head_dim]. ``doc`` [t]: a row's
    document; a query sees the keys of its document at or before it.
    ``HEADS_AT_A_TIME`` heads' scores at once."""
    eps = cfg["rms_norm_eps"]
    t = h.shape[0]
    heads = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    freqs = jnp.asarray(yarn_inv_freq(cfg))
    scale = softmax_scale(cfg)
    rows = jnp.arange(t)
    allow = (doc[:, None] == doc[None, :]) & (rows[:, None] >= rows[None, :])
    q = (_rms_norm(h @ w["wq_a"], w["q_a_norm"], eps) @ w["wq_b"]).reshape(
        t, heads, nope + rope
    )
    q = jnp.concatenate(
        [q[..., :nope], _rot(q[..., nope:], pos, freqs)], axis=-1
    )
    c = h @ w["wkv_a"]
    kv = (_rms_norm(c[:, :rank], w["kv_a_norm"], eps) @ w["wkv_b"]).reshape(
        t, heads, nope + vd
    )
    k_rope = _rot(c[:, None, rank:], pos, freqs)[:, 0]  # [t, rope]: one head

    def some_heads(args):
        qb, kvb = args  # [t, hb, nope + rope], [t, hb, nope + vd]
        s = jnp.einsum("rhd,chd->hrc", qb[..., :nope], kvb[..., :nope])
        s = s + jnp.einsum("rhd,cd->hrc", qb[..., nope:], k_rope)
        p = jax.nn.softmax(jnp.where(allow[None], s * scale, -jnp.inf), axis=-1)
        return jnp.einsum("hrc,chd->rhd", p, kvb[..., nope:])

    hb = HEADS_AT_A_TIME if heads % HEADS_AT_A_TIME == 0 else heads

    def blocks(x):  # [t, heads, d] -> [heads / hb, t, hb, d]
        return jnp.moveaxis(x.reshape(t, heads // hb, hb, x.shape[-1]), 1, 0)

    o = jax.lax.map(jax.checkpoint(some_heads), (blocks(q), blocks(kv)))
    return jnp.moveaxis(o, 0, 1).reshape(t, heads * vd)


def mixer_coefficients(X, w, cfg: dict):
    """(H_pre [t, n], H_post [t, n], H_res [t, n, n]) of a half-layer from
    the streams' state ``X`` [t, n, C] and its mixer ``w``."""
    t, n, _c = X.shape
    x_hat = X.reshape(t, -1)
    m = (x_hat @ w["phi"]) * jax.lax.rsqrt(
        jnp.mean(x_hat * x_hat, axis=-1, keepdims=True) + cfg["rms_norm_eps"]
    )
    a, b = w["alpha"], w["b"]
    h_pre = jax.nn.sigmoid(a[0] * m[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * m[:, n : 2 * n] + b[n : 2 * n])
    h = a[2] * m[:, 2 * n :].reshape(t, n, n) + b[2 * n :].reshape(n, n)
    mat = jnp.exp(
        jnp.clip(h, cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"])
    )
    for _ in range(cfg["hc_sinkhorn_iters"]):
        mat = mat / (mat.sum(axis=2, keepdims=True) + cfg["hc_eps"])  # rows
        mat = mat / (mat.sum(axis=1, keepdims=True) + cfg["hc_eps"])  # columns
    return h_pre, h_post, mat


def half_layer(X, w, cfg: dict, fn):
    """``X'`` of one half-layer: read, ``fn`` on the read state, write."""
    h_pre, h_post, h_res = mixer_coefficients(X, w, cfg)
    y = fn(jnp.einsum("tn,tnc->tc", h_pre, X))
    return jnp.einsum("tij,tjc->tic", h_res, X) + h_post[:, :, None] * y[:, None]


def _layer(X, w, doc, pos, forced, cfg_items, is_dense):
    cfg = dict(cfg_items)
    if cfg.get("rope_scaling"):
        cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    eps = cfg["rms_norm_eps"]
    routed = []

    def attention(u):
        h = _rms_norm(u, w["attn_norm"], eps)
        return latent_attention(h, w, doc, pos, cfg) @ w["wo"]

    def ffn(u):
        g = _rms_norm(u, w["mlp_norm"], eps)
        if is_dense:
            return _swiglu(g, w["w_gate"], w["w_up"], w["w_down"])
        y, chosen = expert_ffn(g, w, cfg, forced)
        routed.append(chosen)
        return y

    X = half_layer(X, w["hc_attn"], cfg, attention)
    X = half_layer(X, w["hc_ffn"], cfg, ffn)
    return X, (routed[0] if routed else None)


def xing_loss(params, tokens, labels, labels2, doc, cfg: dict, *,
              with_routing: bool = False, forced_routing=None):
    """The training loss of one packed sequence, float32 throughout: mean
    next-token cross-entropy, plus ``mtp_loss_weight`` x the MTP module's
    mean cross-entropy on ``labels2`` (token i + 2) where ``params`` holds
    a module. ``doc`` [t] int: a row's document. With ``with_routing`` also
    the expert layers' chosen experts and their margins
    (``reference_glm4moe.router``), each [layers, t, k], the trunk's layers
    and then the module's; ``forced_routing`` [layers, t, k] hands every
    expert layer its choice. A layer is recomputed in the backward, which
    changes no value."""
    f32 = jnp.float32
    eps = cfg["rms_norm_eps"]
    n = cfg["hc_mult"]
    pos = jnp.arange(tokens.shape[0])
    layer_fn = jax.checkpoint(_layer, static_argnums=(5, 6))
    embed = params["embed"].astype(f32)
    head = params["lm_head"].astype(f32)
    chosen = []

    def run_layer(x, layer, dense):
        """One layer on streams that start as copies of ``x`` [t, C] where
        it is no state yet: -> the state [t, n, C]."""
        w = jax.tree.map(lambda a: a.astype(f32), layer)
        forced = None
        if forced_routing is not None and not dense:
            forced = forced_routing[len(chosen)]
        if x.ndim == 2:
            x = jnp.broadcast_to(x[:, None, :], (x.shape[0], n, x.shape[1]))
        x, routed = layer_fn(x, w, doc, pos, forced, _hashable(cfg), dense)
        if routed is not None:
            chosen.append(routed)
        return x

    x = embed[tokens]
    for i, layer in enumerate(params["layers"]):
        x = run_layer(x, layer, i < cfg["first_k_dense_replace"])
    x = x.sum(axis=1)
    loss = _mean_ce(
        _rms_norm(x, params["final_norm"].astype(f32), eps) @ head, labels
    )
    modules = params.get("mtp", ())
    if len(modules) > 1:
        raise ValueError("one MTP module is written down")
    for mod in modules:
        m = {k: a.astype(f32) for k, a in mod.items() if k != "layer"}
        x = jnp.concatenate(
            [_rms_norm(embed[labels], m["embed_norm"], eps),
             _rms_norm(x, m["hidden_norm"], eps)], axis=-1,
        ) @ m["eh_proj"]
        x = run_layer(
            x, mod["layer"],
            cfg["first_k_dense_replace"] >= len(params["layers"]),
        ).sum(axis=1)
        loss = loss + cfg["mtp_loss_weight"] * _mean_ce(
            _rms_norm(x, m["final_norm"], eps) @ head, labels2
        )
    if not with_routing:
        return loss
    return loss, tuple(jnp.stack(a) for a in zip(*chosen))
