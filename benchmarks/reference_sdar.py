"""The plain float32 SDAR sparse-expert decoder and its block-diffusion
training loss, independent of ``magiattention_tpu/``.

Straightforward ``jax.numpy``: a dense boolean mask from the definition
(``masks_blockdiff.allowed``: each row's and key's half, document and
block index, never a slice list), no kernels, no sorting, no planner.
Callers run it under ``jax.default_matmul_precision("highest")``.
``cfg`` is the configuration file's keys
(``benchmarks/configs/sdar-30b-a3b-chat.json``); ``params`` is a pytree
with the names ``models/pattern.py`` documents, which is all the two
share. Everything ``config.json`` does not itself state is listed in the
configuration file under ``assumed``.

The layer is the published one of a qk-normed GQA sparse-expert decoder
(``model_type: sdar_moe``; Qwen3-MoE's equations): two RMSNorms a layer,
a per-head RMSNorm on q and k before a half-split rotary at
``rope_theta`` in every layer, no bias, no gate; the router a float32
softmax over ``num_experts``, the top ``num_experts_per_tok``,
renormalised (``norm_topk_prob``); SwiGLU experts, none shared; an untied
head. What is SDAR's own is the training (arXiv:2510.06303; the mask of
BD3-LM, arXiv:2503.09573): the model is fed ``[noisy ; clean]``, 2L rows
that both carry the token's own position, under the block-diffusion
mask; the head runs on the noisy half; the loss is the cross-entropy of
a masked row against its OWN clean token (no shift), times the row's
weight 1/t, summed and divided by L.

Departures from the sources, each noted where it is made: the mask and
the softmax are computed a block of ``ROW_BLOCK`` query rows at a time
(a value changes nowhere: 32 heads x 4,096^2 float32 scores and their
gradient do not fit beside three copies of the weights); the layers, all
alike, run as one ``lax.scan`` over their stacked weights (the program
of six unrolled float32 layers took the chip's compiler 200 s and did
not fit the compile cache: my chip run, PR 42); a layer is recomputed in
the backward.

One rank's share of the deployment, as the system under test is given
it: the router is ``num_experts`` wide and chooses
``num_experts_per_tok``; of the chosen, only the experts ``experts_here``
= [first, last) are computed; the vocabulary is the slice the parameters
hold.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import masks_blockdiff
from .reference import _rope

ROW_BLOCK = 512  # query rows a block of the mask and the softmax


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def router(h, w, cfg: dict, forced=None):
    """(chosen experts [t, k], their weights [t, k], margins [t, k]).
    ``forced`` [t, k] takes the place of the router's own choice (the
    weights are still this router's scores there); a margin is how far
    under the k-th best score a chosen expert's lies: 0 for the router's
    own choice, the size of the tie a forced one broke."""
    s = jax.nn.softmax(h @ w["w_router"], axis=-1)
    best, idx = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    if forced is not None:
        idx = forced
    wts = jnp.take_along_axis(s, idx, axis=1)
    margins = best[:, -1:] - wts
    if cfg["norm_topk_prob"]:
        wts = wts / wts.sum(axis=1, keepdims=True)
    return idx, wts, jnp.maximum(margins, 0.0)


def expert_ffn(h, w, cfg: dict, forced=None, experts_here=None):
    """sum over the chosen experts held here of weight x expert(h): every
    held expert on every row, the rows that did not choose it weighted 0.
    ``experts_here`` [first, last) overrides the configuration's (the
    tests add up the ranks' parts)."""
    idx, wts, margins = router(h, w, cfg, forced)
    first, last = experts_here or cfg["experts_here"]
    held = first + jnp.arange(last - first)
    # [t, held]: a row's weight on each held expert, 0 where not chosen
    w_te = jnp.where(idx[:, :, None] == held, wts[:, :, None], 0.0).sum(axis=1)
    a = jax.nn.silu(jnp.einsum("td,edh->teh", h, w["we_gate"]))
    a = a * jnp.einsum("td,edh->teh", h, w["we_up"])
    y = jnp.einsum("teh,ehd->td", a * w_te[:, :, None], w["we_down"])
    return y, (idx, margins)


def attention(q, k, v, allow_rows, row_block: int):
    """softmax(q k^T / sqrt(hd)) v under the mask, ``row_block`` query
    rows at a time; ``allow_rows(rows) -> [len(rows), t]`` boolean."""
    t, hq, hd = q.shape
    hk = k.shape[1]
    g = hq // hk
    block = min(row_block, t)
    assert t % block == 0, (t, block)

    @jax.checkpoint
    def rows(r0):
        idx = r0 + jnp.arange(block)
        qb = jax.lax.dynamic_slice_in_dim(q, r0, block).reshape(block, hk, g, hd)
        s = jnp.einsum("rkgd,ckd->kgrc", qb, k) * hd ** -0.5
        s = jnp.where(allow_rows(idx)[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgrc,ckd->rkgd", p, v).reshape(block, hq * hd)

    out = jax.lax.map(rows, jnp.arange(0, t, block))
    return out.reshape(t, hq * hd)


def _layer(x, w, pos, forced, cfg_items, *, mask, row_block):
    cfg = dict(cfg_items)
    eps = cfg["rms_norm_eps"]
    t = x.shape[0]
    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    h = _rms_norm(x, w["attn_norm"], eps)
    q = _rms_norm((h @ w["wq"]).reshape(t, hq, hd), w["q_norm"], eps)
    k = _rms_norm((h @ w["wk"]).reshape(t, hk, hd), w["k_norm"], eps)
    v = (h @ w["wv"]).reshape(t, hk, hd)
    q = _rope(q, pos, float(cfg["rope_theta"]))
    k = _rope(k, pos, float(cfg["rope_theta"]))
    keys = jnp.arange(t)
    attn = attention(
        q, k, v, lambda r: masks_blockdiff.allowed(mask, r, keys), row_block
    )
    x = x + attn @ w["wo"]
    h = _rms_norm(x, w["mlp_norm"], eps)
    y, routed = expert_ffn(h, w, cfg, forced)
    return x + y, routed


def _hashable(cfg: dict):
    return tuple(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in cfg.items()
        if isinstance(v, (int, float, bool, str, list))
    )


def sdar_loss(params, noisy, clean, labels, weights, mask, cfg: dict, *,
              with_routing: bool = False, forced_routing=None,
              row_block: int = ROW_BLOCK):
    """The block-diffusion loss of one packed sequence of L tokens,
    float32 throughout: ``noisy`` / ``clean`` [L] the noised and the
    clean ids, ``labels`` [L] a masked token's own clean id and -1 where
    it is not masked, ``weights`` [L] a token's weight (1/t of its
    block), ``mask`` the ``masks_blockdiff.BlockDiffMask`` of the
    documents. The sum of weight x cross-entropy over the masked rows of
    the noisy half, over L. With ``with_routing`` also every layer's
    chosen experts and their margins (``router``), each [layers, 2L, k];
    ``forced_routing`` [layers, 2L, k] hands every layer its choice."""
    f32 = jnp.float32
    n = noisy.shape[0]
    tokens = jnp.concatenate([noisy, clean])
    pos = jnp.concatenate([jnp.arange(n), jnp.arange(n)])  # its own, twice
    layer_fn = jax.checkpoint(
        functools.partial(_layer, mask=mask, row_block=row_block),
        static_argnums=(4,),
    )
    x = params["embed"].astype(f32)[tokens]
    # the layers are alike: one scan over their stacked weights (a value
    # changes nowhere; the program holds a layer once, not six times)
    stacked = jax.tree.map(
        lambda *a: jnp.stack(a).astype(f32), *params["layers"]
    )

    def body(x, layer):
        w, forced = layer
        return layer_fn(x, w, pos, forced, _hashable(cfg))

    x, chosen = jax.lax.scan(body, x, (stacked, forced_routing))
    logits = _rms_norm(
        x[:n], params["final_norm"].astype(f32), cfg["rms_norm_eps"]
    ) @ params["lm_head"].astype(f32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    masked = labels >= 0
    ce = -jnp.take_along_axis(
        logp, jnp.where(masked, labels, 0)[:, None], axis=1
    )[:, 0]
    loss = jnp.where(masked, ce * weights, 0.0).sum() / n
    return (loss, chosen) if with_routing else loss
