"""The plain float32 ZAYA1 decoder (``model_type`` ``zaya``), independent
of ``magiattention_tpu/``.

Straightforward ``jax.numpy``: dense boolean masks, dense shift matrices,
no kernels, no sorting, no dispatch, no planner. Callers run it under
``jax.default_matmul_precision("highest")``. ``cfg`` is the configuration
file's keys (``benchmarks/configs/zaya1-8b.json``); ``params`` is a
pytree with the names ``models/pattern.py`` documents, which is all the
two share. Everything ``config.json`` does not itself state is listed in
the configuration file under ``assumed``.

A layer, token ``t`` of a document (``u_{t-j} = 0`` where ``t - j`` falls
before the document's first token; ``S_j`` is that shift as a matrix),
hidden ``x``, ``H`` = 128 the head width, 8 query heads on 2 key-value
heads::

    h    = norm(x; attn_norm)
    q~   = h W_q  (8 heads),  k~ = h W_k  (2 heads),  c = [q~ | k~]
    c1   = b1 + sum_{j < cca_time0} a_j * S_j c          # a weight a channel
    c2   = b2 + sum_{j < cca_time1} G_j S_j c1           # a block a head
    q    = c2_q + (q~ + rep(k~)) / 2     # rep: a key head to its 4 query heads
    k    = c2_k + (grp(q~) + k~) / 2     # grp: the mean of a group's 4
    q    = sqrt(H) q / |q|,  k = sqrt(H) tau_head k / |k|    # a head
    q, k = rotary on the first partial_rotary_factor x H dimensions
    v    = [ h W_v (first key-value head) | S_1 h W_v (second) ]
    o_h  = softmax(q_h k_h^T / sqrt(H), allowed keys) v_h
    x    = x + concat_h(o_h) W_o
    g    = norm(x; mlp_norm)
    r_l  = g W_down + gamma_l * r_{l-1}                   # r_0 = 0; handed on
    s    = softmax(W_3 gelu(W_2 gelu(W_1 norm(r_l; router_norm))))
    e    = argmax(s + b)          # b: expert_bias, a zero buffer
    x    = x + s_e SwiGLU_e(g)

then the final norm, logits on the tied embedding, mean next-token
cross-entropy.

One rank's share of the deployment, as the system under test is given it
(``reference_afmoe``'s rule): the router is ``num_experts`` wide and
chooses ``num_experts_per_tok``; of the chosen only ``experts_here`` =
[first, last) are computed; the vocabulary is the slice the parameters
hold.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .reference import _rope
from .reference_afmoe import _hashable, _rms_norm, _swiglu


def shift_matrices(allow, n: int):
    """``S_0 .. S_{n-1}`` [n, t, t] float32 from the documents' causal
    mask: ``S_j[p, p - j] = 1`` where ``p - j`` is a key ``p`` may see
    (its own document's, not after it), every other entry 0."""
    t = allow.shape[0]
    rows = jnp.arange(t)[:, None]
    cols = jnp.arange(t)[None, :]
    return jnp.stack([
        (allow & (cols == rows - j)).astype(jnp.float32) for j in range(n)
    ])


def cca_attention(h, w, allow, pos, cfg: dict):
    """The attention half of a layer on the normed hidden state ``h``
    [t, hidden], before the output projection: [t, heads x head_dim]."""
    t = h.shape[0]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, group = cfg["head_dim"], hq // hk
    t0, t1 = cfg["cca_time0"], cfg["cca_time1"]
    eps = cfg["rms_norm_eps"]
    rope = cfg["rope_parameters"]["hybrid"]
    rot = int(hd * rope["partial_rotary_factor"])
    shifts = shift_matrices(allow, max(t0, t1, 2))

    q0, k0 = h @ w["wq"], h @ w["wk"]
    c = jnp.concatenate([q0, k0], axis=-1)
    c1 = w["cca_conv1_b"] + sum(
        w["cca_conv1_w"][j] * (shifts[j] @ c) for j in range(t0)
    )
    c2 = w["cca_conv2_b"].reshape(hq + hk, hd) + sum(
        jnp.einsum(
            "thd,hde->the", (shifts[j] @ c1).reshape(t, hq + hk, hd),
            w["cca_conv2_w"][j],
        )
        for j in range(t1)
    )
    q0, k0 = q0.reshape(t, hk, group, hd), k0.reshape(t, hk, hd)
    q = c2[:, :hq].reshape(t, hk, group, hd) + (q0 + k0[:, :, None]) / 2
    k = c2[:, hq:] + (q0.mean(axis=2) + k0) / 2

    def unit(x):  # sqrt(head_dim) x / |x|, the norms' epsilon under the root
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)

    def rotary(x):
        theta = float(rope["rope_theta"])
        return jnp.concatenate(
            [_rope(x[..., :rot], pos, theta), x[..., rot:]], axis=-1
        )

    q = rotary(unit(q).reshape(t, hq, hd)).reshape(t, hk, group, hd)
    k = rotary(unit(k) * w["cca_temp"][:, None])
    v = (h @ w["wv"]).reshape(t, hk, hd)
    v = jnp.concatenate(
        [v[:, : hk // 2],
         jnp.einsum("pc,chd->phd", shifts[1], v[:, hk // 2 :])], axis=1,
    )
    out = []
    for g in range(hk):  # a key-value head at a time: [group, t, t] scores
        s = jnp.einsum("rgd,cd->grc", q[:, g], k[:, g]) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(allow[None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("grc,cd->rgd", p, v[:, g]))
    return jnp.stack(out, axis=1).reshape(t, hq * hd)


def router(g, r_prev, w, cfg: dict, forced=None):
    """(chosen experts [t, k], their weights [t, k], margins [t, k], the
    state for the next layer): the MLP router on the hidden state's
    down-projection plus ``router_gamma`` x the layer before's state.
    Softmax scores; the top ``num_experts_per_tok`` of score +
    ``expert_bias``; the scores at the chosen experts, not renormalised.
    ``forced`` and the margins as in ``reference_glm4moe.router``."""
    r = g @ w["w_router_down"] + w["router_gamma"] * r_prev
    z = _rms_norm(r, w["router_norm"], cfg["rms_norm_eps"])
    z = jax.nn.gelu(z @ w["w_router_mlp1"], approximate=False)
    z = jax.nn.gelu(z @ w["w_router_mlp2"], approximate=False)
    s = jax.nn.softmax(z @ w["w_router"], axis=-1)
    biased = s + w["expert_bias"]
    best, idx = jax.lax.top_k(biased, cfg["num_experts_per_tok"])
    if forced is not None:
        idx = forced
    margins = best[:, -1:] - jnp.take_along_axis(biased, idx, axis=1)
    wts = jnp.take_along_axis(s, idx, axis=1)
    return idx, wts, jnp.maximum(margins, 0.0), r


def expert_ffn(g, r_prev, w, cfg: dict, forced=None):
    """(sum over the chosen experts held here of score x expert(g): every
    held expert on every token, then masked; the router's state; (chosen
    experts, margins))."""
    idx, wts, margins, r = router(g, r_prev, w, cfg, forced)
    first, last = cfg["experts_here"]
    y = jnp.zeros_like(g)
    for e in range(first, last):
        w_e = jnp.where(idx == e, wts, 0.0).sum(axis=1)  # 0 where not chosen
        j = e - first
        y = y + w_e[:, None] * _swiglu(
            g, w["we_gate"][j], w["we_up"][j], w["we_down"][j]
        )
    return y, r, (idx, margins)


def _layer(x, r, w, allow, pos, forced, cfg_items):
    cfg = dict(cfg_items)
    cfg["rope_parameters"] = {"hybrid": dict(cfg.pop("_rope"))}
    eps = cfg["rms_norm_eps"]
    h = _rms_norm(x, w["attn_norm"], eps)
    x = x + cca_attention(h, w, allow, pos, cfg) @ w["wo"]
    g = _rms_norm(x, w["mlp_norm"], eps)
    y, r_next, routed = expert_ffn(g, r, w, cfg, forced)
    return x + y, r_next, routed + (g, r)


def zaya_loss(params, tokens, labels, allow, cfg: dict, *,
              with_routing: bool = False, forced_routing=None):
    """The training loss of one packed sequence, float32 throughout: mean
    next-token cross-entropy of the logits on the tied embedding.
    ``allow`` [t, t] is the documents' causal mask. With ``with_routing``
    also, a layer each: the chosen experts and their margins (``router``)
    [layers, t, k], and what the layer's router read, the normed hidden
    state [layers, t, hidden] and the layer before's state [layers, t,
    router_hidden_size]; ``forced_routing`` [layers, t, k] hands every
    layer its choice. A layer is recomputed in the backward, which
    changes no value."""
    f32 = jnp.float32
    pos = jnp.arange(tokens.shape[0])
    layer_fn = jax.checkpoint(_layer, static_argnums=(6,))
    static = _hashable(cfg) + (
        ("_rope", tuple(cfg["rope_parameters"]["hybrid"].items())),
    )
    embed = params["embed"].astype(f32)
    x = embed[tokens]
    r = jnp.zeros((tokens.shape[0], cfg["router_hidden_size"]), f32)
    chosen = []
    for i, layer in enumerate(params["layers"]):
        w = {n: a.astype(f32) for n, a in layer.items()}
        forced = None if forced_routing is None else forced_routing[i]
        x, r, routed = layer_fn(x, r, w, allow, pos, forced, static)
        chosen.append(routed)
    logits = _rms_norm(
        x, params["final_norm"].astype(f32), cfg["rms_norm_eps"]
    ) @ embed.T
    logp = jax.nn.log_softmax(logits, axis=-1)
    loss = -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()
    if not with_routing:
        return loss
    return loss, tuple(jnp.stack(a) for a in zip(*chosen))
