"""The plain float32 Ouro decoder (``model_type`` ``ouro``, a looped
language model: arXiv:2510.25741), independent of ``magiattention_tpu/``.

Straightforward ``jax.numpy``: a dense boolean mask, a Python loop over
passes and layers, no kernels, no scan, no planner. Callers run it under
``jax.default_matmul_precision("highest")``. ``cfg`` is the configuration
file's keys (``benchmarks/configs/ouro-2.6b.json``); ``params`` is a
pytree with the names ``models/pattern.py`` documents, which is all the
two share. Everything ``config.json`` does not itself state is listed in
the configuration file under ``assumed``.

A layer (sandwich norm), token ``i`` at position ``p_i``, no biases::

    a = Attn(norm(x; attn_norm))        x = x + norm(a; post_attn_norm)
    m = SwiGLU(norm(x; mlp_norm))       x = x + norm(m; post_mlp_norm)
    Attn(h): q, k, v = h W_q, h W_k, h W_v; rotary on all of a head of q
             and k; softmax(q k^T / sqrt(head_dim), allowed keys) v; W_o

The trunk runs the SAME layers ``T = total_ut_steps`` times, the one
final norm inside the loop, and every pass ends in the shared head and
the exit gate::

    x_0 = embed[tokens]
    x_t = norm(layers(x_{t-1}); final_norm)              t = 1..T
    logits_t = x_t W_head         lambda_t = sigmoid(x_t w_g + b_g)
    p_t = lambda_t prod_{j<t} (1 - lambda_j)  (t < T)
    p_T = prod_{j<T} (1 - lambda_j)
    loss = mean_i [ sum_t p_t CE_t - beta H(p) ],  H(p) = -sum_t p_t log p_t

with ``CE_t`` the next-token cross-entropy of ``logits_t`` a position and
``beta`` the file's ``exit_entropy_weight`` (the paper's
entropy-regularised objective under a uniform prior over exits).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .reference import _rope
from .reference_afmoe import _rms_norm, _swiglu


def attention(h, w, allow, pos, cfg: dict):
    """The attention half of a layer on the normed hidden state ``h``
    [t, hidden], output projection included."""
    t = h.shape[0]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, theta = cfg["head_dim"], float(cfg["rope_theta"])
    g = hq // hk
    q = _rope((h @ w["wq"]).reshape(t, hq, hd), pos, theta)
    k = _rope((h @ w["wk"]).reshape(t, hk, hd), pos, theta)
    v = (h @ w["wv"]).reshape(t, hk, hd)
    s = jnp.einsum("rkgd,ckd->kgrc", q.reshape(t, hk, g, hd), k)
    s = jnp.where(allow[None, None], s * hd ** -0.5, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("kgrc,ckd->rkgd", p, v).reshape(t, hq * hd) @ w["wo"]


def layer(x, w, allow, pos, cfg: dict):
    eps = cfg["rms_norm_eps"]
    a = attention(_rms_norm(x, w["attn_norm"], eps), w, allow, pos, cfg)
    x = x + _rms_norm(a, w["post_attn_norm"], eps)
    m = _swiglu(
        _rms_norm(x, w["mlp_norm"], eps), w["w_gate"], w["w_up"], w["w_down"]
    )
    return x + _rms_norm(m, w["post_mlp_norm"], eps)


def exit_distribution(lam):
    """p [T, t] from the gates' lambda [T, t]: exit ``t`` is taken with
    ``lambda_t`` if no earlier one was; the last takes what is left."""
    stay, p = jnp.ones_like(lam[0]), []
    for lam_t in lam[:-1]:
        p.append(lam_t * stay)
        stay = stay * (1.0 - lam_t)
    return jnp.stack(p + [stay])


def exit_ce_and_gate(x, head, w_g, b_g, labels):
    """One exit: the next-token cross-entropy a position [t] and the
    gate's lambda [t]."""
    logp = jax.nn.log_softmax(x @ head, axis=-1)
    ce = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    return ce, jax.nn.sigmoid((x @ w_g)[:, 0] + b_g[0])


def ouro_loss(params, tokens, labels, allow, cfg: dict, *,
              recompute: bool = False, with_exits: bool = False):
    """The training loss of one packed sequence, float32 throughout.
    ``allow`` [t, t] is the documents' causal mask. ``recompute`` runs a
    layer application and an exit again in the backward instead of
    keeping them (at 4,096 tokens a layer's scores are 1 GB and there
    are layers x passes of them), which changes no value. With
    ``with_exits`` also (p [T, t], CE [T, t])."""
    f32 = jnp.float32
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(tokens.shape[0])

    def layer_fn(x, w):
        return layer(x, w, allow, pos, cfg)

    exit_fn = exit_ce_and_gate
    if recompute:
        layer_fn, exit_fn = jax.checkpoint(layer_fn), jax.checkpoint(exit_fn)
    layers = [
        {n: a.astype(f32) for n, a in w.items()} for w in params["layers"]
    ]
    head = params["lm_head"].astype(f32)
    final_norm = params["final_norm"].astype(f32)
    gate = params["exit_gate"]
    x = params["embed"].astype(f32)[tokens]
    ce, lam = [], []
    for _t in range(cfg["total_ut_steps"]):
        for w in layers:
            x = layer_fn(x, w)
        x = _rms_norm(x, final_norm, eps)
        ce_t, lam_t = exit_fn(
            x, head, gate["w"].astype(f32), gate["b"].astype(f32), labels
        )
        ce.append(ce_t)
        lam.append(lam_t)
    ce, p = jnp.stack(ce), exit_distribution(jnp.stack(lam))
    entropy = -(p * jnp.log(p)).sum(axis=0)
    loss = ((p * ce).sum(axis=0) - cfg["exit_entropy_weight"] * entropy).mean()
    return (loss, (p, ce)) if with_exits else loss
