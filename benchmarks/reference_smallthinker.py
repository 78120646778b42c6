"""The plain float32 SmallThinker sparse-expert decoder and its training
loss, independent of ``magiattention_tpu/``.

Straightforward ``jax.numpy``: a dense boolean mask from each row's
document and place, no kernels, no sorting, no planner. Callers run it
under ``jax.default_matmul_precision("highest")``. ``cfg`` is the
configuration file's keys
(``benchmarks/configs/smallthinker-21b-a3b.json``); ``params`` is a pytree
with the names ``models/pattern.py`` documents, which is all the two
share. Everything ``config.json`` does not itself state is listed in the
configuration file under ``assumed``.

The layer (SmallThinker-21BA3B-Instruct, arXiv:2507.20984; token rows
``x``, layer ``l``, no bias anywhere)::

    h      = RMSNorm(x; attn_norm)
    z      = h W_r                     # the router reads h, BEFORE attention
    E      = top-k of z;  w = softmax(z_E)         (norm_topk_prob)
    q,k,v  = h W_q, h W_k, h W_v       # 28 query / 4 key-value heads of 128
    rope_layout[l] == 1:  q, k = rotary(q, k; rope_theta, half-split)
    mask   = causal inside the document; sliding_window_layout[l] == 1:
             a query sees itself and the sliding_window_size - 1 keys before
    x1     = x + softmax(q k^T / sqrt(head_dim) + mask) v W_o
    g      = RMSNorm(x1; mlp_norm)
    x2     = x1 + sum_{e in E} w_e (relu(g W_gate,e) * (g W_up,e)) W_down,e

then a final RMSNorm and an untied head; the loss is the mean
cross-entropy of the next token over the rows whose next token lies in
their own document.

Departures from the published description, each noted where it is made:
the mask and the softmax are computed a block of ``ROW_BLOCK`` query rows
at a time (a value changes nowhere: 28 heads x 16,384^2 float32 scores do
not fit); the layers run as one ``lax.scan`` over their stacked weights
with the layer's two layout bits as data (a layer at 0 takes the
un-rotated q, k and the un-windowed mask through ``jnp.where``: the
values of the published branch); a layer is recomputed in the backward.

One rank's share of the deployment, as the system under test is given
it: the router is ``moe_num_primary_experts`` wide and chooses
``moe_num_active_primary_experts``; of the chosen, only the experts
``experts_here`` = [first, last) are computed; the vocabulary is the
slice the parameters hold.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .reference import _rope
from .reference_sdar import _hashable, _rms_norm, attention

ROW_BLOCK = 512  # query rows a block of the mask and the softmax


def router(h, w, cfg: dict, forced=None):
    """(chosen experts [t, k], their weights [t, k], margins [t, k]) of
    the router on ``h``, the ATTENTION half's normed input. ``forced``
    [t, k] takes the place of the router's own choice (the weights are
    still this router's logits there); a margin is how far under the k-th
    best a chosen expert lies, in the softmax over all experts (the scale
    ``reference_sdar`` and ``reference_afmoe`` read theirs in): 0 for the
    router's own choice, the size of the tie a forced one broke."""
    z = h @ w["w_router"]
    s = jax.nn.softmax(z, axis=-1)  # monotone in z: one top-k serves both
    best, idx = jax.lax.top_k(s, cfg["moe_num_active_primary_experts"])
    if forced is not None:
        idx = forced
    margins = jnp.maximum(
        best[:, -1:] - jnp.take_along_axis(s, idx, axis=1), 0.0
    )
    if cfg["norm_topk_prob"]:  # the softmax over the chosen logits alone
        wts = jax.nn.softmax(jnp.take_along_axis(z, idx, axis=1), axis=-1)
    else:
        wts = jnp.take_along_axis(s, idx, axis=1)
    return idx, wts, margins


def expert_ffn(g, routed, w, cfg: dict, experts_here=None):
    """sum over the chosen experts held here of weight x expert(g), the
    gate through ReLU: every held expert on every row, the rows that did
    not choose it weighted 0. ``routed``: :func:`router`'s (ids, weights).
    ``experts_here`` [first, last) overrides the configuration's (the
    tests add up the ranks' parts)."""
    idx, wts = routed
    first, last = experts_here or cfg["experts_here"]
    held = first + jnp.arange(last - first)
    # [t, held]: a row's weight on each held expert, 0 where not chosen
    w_te = jnp.where(idx[:, :, None] == held, wts[:, :, None], 0.0).sum(axis=1)
    a = jax.nn.relu(jnp.einsum("td,edh->teh", g, w["we_gate"]))
    a = a * jnp.einsum("td,edh->teh", g, w["we_up"])
    return jnp.einsum("teh,ehd->td", a * w_te[:, :, None], w["we_down"])


def allowed(doc, place, rows, windowed, window: int):
    """[len(rows), t] boolean: row ``i`` sees key ``j`` of its own
    document at or before it, and where ``windowed`` only the ``window``
    keys up to itself (``i - j < window``)."""
    keys = jnp.arange(doc.shape[0])
    same = doc[rows][:, None] == doc[None, :]
    back = place[rows][:, None] - place[None, :]  # both of one document
    seen = same & (keys[None, :] <= rows[:, None])
    return seen & (~windowed | (back < window))


def _layer(x, w, bits, forced, doc, place, cfg_items, *, row_block):
    cfg = dict(cfg_items)
    eps = cfg["rms_norm_eps"]
    t = x.shape[0]
    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    rotary, windowed = bits[0] == 1, bits[1] == 1
    h = _rms_norm(x, w["attn_norm"], eps)
    idx, wts, margins = router(h, w, cfg, forced)  # before attention
    q = (h @ w["wq"]).reshape(t, hq, hd)
    k = (h @ w["wk"]).reshape(t, hk, hd)
    v = (h @ w["wv"]).reshape(t, hk, hd)
    # a token's position in its document; a layer at rope_layout 0 none
    theta = float(cfg["rope_theta"])
    q = jnp.where(rotary, _rope(q, place, theta), q)
    k = jnp.where(rotary, _rope(k, place, theta), k)
    attn = attention(
        q, k, v,
        lambda r: allowed(doc, place, r, windowed, cfg["sliding_window_size"]),
        row_block,
    )
    x = x + attn @ w["wo"]
    g = _rms_norm(x, w["mlp_norm"], eps)
    return x + expert_ffn(g, (idx, wts), w, cfg), (idx, margins)


def places(doc):
    """A row's place in its document, from the rows' document ids
    (non-decreasing: the documents are packed one after the other)."""
    rows = jnp.arange(doc.shape[0])
    starts = jnp.where(jnp.r_[True, doc[1:] != doc[:-1]], rows, 0)
    return rows - jax.lax.cummax(starts)


def smallthinker_loss(params, tokens, doc, cfg: dict, *,
                      with_routing: bool = False, forced_routing=None,
                      row_block: int = ROW_BLOCK):
    """Mean next-token cross-entropy of one packed sequence, float32
    throughout: ``tokens`` [t] the ids, ``doc`` [t] a row's document; a
    row whose next token is another document's (or none) has no label.
    With ``with_routing`` also every layer's chosen experts and their
    margins (:func:`router`), each [layers, t, k]; ``forced_routing``
    [layers, t, k] hands every layer its choice. ``params["layers"]`` is
    the list of the layers' dicts, or those dicts stacked along a leading
    layer axis already (the benchmark's check at the published widths
    stacks them outside: the gradients then come out stacked, and no
    second copy of the weights and of their gradients is made inside)."""
    f32 = jnp.float32
    stacked = params["layers"]
    if not isinstance(stacked, dict):
        stacked = jax.tree.map(lambda *a: jnp.stack(a), *stacked)
    stacked = jax.tree.map(lambda a: a.astype(f32), stacked)
    n = stacked["wq"].shape[0]
    place = places(doc)
    layer_fn = jax.checkpoint(
        functools.partial(_layer, row_block=row_block), static_argnums=(6,)
    )
    x = params["embed"].astype(f32)[tokens]  # unscaled
    bits = jnp.stack([
        jnp.asarray(cfg["rope_layout"][:n], jnp.int32),
        jnp.asarray(cfg["sliding_window_layout"][:n], jnp.int32),
    ], axis=1)

    def body(x, layer):
        w, layer_bits, forced = layer
        return layer_fn(x, w, layer_bits, forced, doc, place, _hashable(cfg))

    x, chosen = jax.lax.scan(body, x, (stacked, bits, forced_routing))
    logits = _rms_norm(
        x, params["final_norm"].astype(f32), cfg["rms_norm_eps"]
    ) @ params["lm_head"].astype(f32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    labels = jnp.roll(tokens, -1)
    valid = jnp.r_[doc[1:] == doc[:-1], False]
    ce = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    loss = jnp.where(valid, ce, 0.0).sum() / valid.sum()
    return (loss, chosen) if with_routing else loss
