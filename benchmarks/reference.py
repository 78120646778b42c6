"""Plain float32 references, independent of ``magiattention_tpu/ops``.

Straightforward ``jax.numpy``: no kernels, no cache, no planner. Callers
run them under ``jax.default_matmul_precision("highest")`` — on the TPU a
default float32 matmul is a single bf16 pass, which would make the
reference no better than the system under test.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def attention_rows(q, k, v, allow, d_out, d_lse):
    """Attention of ``R`` query rows over ``C`` context rows with its
    gradients, one key-value head at a time.

    q, d_out [R, hq, d]; k, v [C, hk, d]; allow [R, C] bool; d_lse
    [R, hq]. Returns float32 (out [R, hq, d], lse [R, hq], dq, dk, dv)
    for the loss ``sum(out * d_out) + sum(lse * d_lse)``. A query row
    that may see nothing gives out 0 and lse -inf, and no gradient.
    """
    r, hq, d = q.shape
    c, hk, _ = k.shape
    g = hq // hk
    scale = d ** -0.5
    f32 = jnp.float32
    any_allowed = allow.any(axis=1)

    def fwd(qg, kg, vg):  # [R, g, d], [C, d], [C, d]
        s = jnp.einsum("rgd,cd->grc", qg, kg) * scale
        s = jnp.where(allow[None], s, -jnp.inf)
        m = jnp.where(any_allowed, s.max(axis=-1), 0.0)  # [g, R]
        p = jnp.exp(s - m[..., None])
        l = p.sum(axis=-1)
        out = jnp.einsum("grc,cd->rgd", p, vg) / jnp.where(
            any_allowed, l, 1.0
        ).T[..., None]
        lse = jnp.where(any_allowed, m + jnp.log(l), -jnp.inf).T  # [R, g]
        return out, lse

    def loss(qg, kg, vg, dog, dlg):
        out, lse = fwd(qg, kg, vg)
        # -inf * 0 is nan: rows that see nothing carry no cotangent
        lse_term = jnp.where(any_allowed[:, None], lse, 0.0) * dlg
        return (out * dog).sum() + lse_term.sum()

    def one_head(args):
        qg, kg, vg, dog, dlg = args
        out, lse = fwd(qg, kg, vg)
        dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(qg, kg, vg, dog, dlg)
        return out, lse, dq, dk, dv

    def by_head(x):  # [R, hq, ...] -> [hk, R, g, ...]
        return jnp.moveaxis(
            x.astype(f32).reshape(r, hk, g, *x.shape[2:]), 1, 0
        )

    out, lse, dq, dk, dv = jax.lax.map(
        one_head,
        (
            by_head(q),
            jnp.moveaxis(k.astype(f32), 1, 0),
            jnp.moveaxis(v.astype(f32), 1, 0),
            by_head(d_out),
            by_head(d_lse),
        ),
    )

    def to_rows(x):  # [hk, R, g, ...] -> [R, hq, ...]
        return jnp.moveaxis(x, 0, 1).reshape(r, hq, *x.shape[3:])

    return (
        to_rows(out), to_rows(lse), to_rows(dq),
        jnp.moveaxis(dk, 0, 1), jnp.moveaxis(dv, 0, 1),
    )


# ---------------------------------------------------------------------------
# the plain decoder (Llama / Mistral family)
# ---------------------------------------------------------------------------

RMS_EPS = 1e-5  # models/llama.py's constant; Mistral-7B-v0.3 publishes 1e-5


def _rms_norm(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + RMS_EPS) * w


def _rope(x, pos, theta):
    """Half-split rotary embedding, x [t, h, hd] (the layout
    ``models/llama.py`` trains in; HF checkpoints permute to it)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _decoder_layer(x, w, allow, pos, dims, theta):
    t = x.shape[0]
    hq, hk, hd = dims
    g = hq // hk
    h = _rms_norm(x, w["attn_norm"])
    q = _rope((h @ w["wq"]).reshape(t, hq, hd), pos, theta)
    k = _rope((h @ w["wk"]).reshape(t, hk, hd), pos, theta)
    v = (h @ w["wv"]).reshape(t, hk, hd)
    s = jnp.einsum("rkgd,ckd->kgrc", q.reshape(t, hk, g, hd), k)
    s = jnp.where(allow[None, None], s * hd ** -0.5, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    attn = jnp.einsum("kgrc,ckd->rkgd", p, v).reshape(t, hq * hd)
    x = x + attn @ w["wo"]
    h = _rms_norm(x, w["mlp_norm"])
    return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def decoder_loss(params, tokens, labels, allow, cfg: dict):
    """Mean next-token cross-entropy of one packed sequence through a
    dense pre-norm decoder: RMSNorm, rotary GQA attention under the
    boolean mask ``allow`` [t, t], SwiGLU, untied output head. float32
    throughout. ``params`` is the pytree ``models.init_params`` makes;
    ``cfg`` the configuration file's keys. A layer is recomputed in the
    backward (its [heads, t, t] scores are 2 GB at 4,096 tokens), which
    changes no value."""
    f32 = jnp.float32
    dims = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"])
    pos = jnp.arange(tokens.shape[0])
    layer_fn = jax.checkpoint(_decoder_layer, static_argnums=(4, 5))
    x = params["embed"].astype(f32)[tokens]
    for layer in params["layers"]:
        w = {n: a.astype(f32) for n, a in layer.items()}
        x = layer_fn(x, w, allow, pos, dims, float(cfg["rope_theta"]))
    logits = _rms_norm(x, params["final_norm"].astype(f32)) @ params[
        "lm_head"
    ].astype(f32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()
