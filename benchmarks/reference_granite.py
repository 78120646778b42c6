"""The plain float32 granite-4.0-h-micro decoder (``model_type``
``granitemoehybrid``; Mamba-2 / state-space duality, arXiv:2405.21060),
independent of ``magiattention_tpu/``.

Straightforward ``jax.numpy``: the recurrence a ``lax.scan`` over single
tokens on a ``[heads, head channels, states]`` state, zeroed at a
document's first row (NOT the chunked form the system runs), the
convolution an explicit sum over taps inside a document, the attention's
mask a dense boolean from document ids, no kernels, no dispatch, no
planner. Callers run it under ``jax.default_matmul_precision("highest")``
and take gradients with ``jax.grad``. ``cfg`` is the configuration file's
keys (``benchmarks/configs/granite-4.0-h-micro.json``) with its
``assumed.sizes`` beside them; ``params`` is a pytree with the names
``models/pattern.py`` and ``models/ssm.py`` document, which is all the
two share.

The equations, from the published keys (``m_e`` = ``embedding_multiplier``
12, ``m_r`` = ``residual_multiplier`` 0.22, ``m_a`` =
``attention_multiplier`` 1/64, ``m_l`` = ``logits_scaling`` 8)::

    h_0 = m_e E[ids]
    h  <- h + m_r mix(rmsnorm(h))            # by layer_types[l]
    h  <- h + m_r W_down (silu(g) * u),  [g | u] = rmsnorm(h) [W_gate | W_up]
    logits = rmsnorm(h_L) E^T / m_l;  mean next-token cross-entropy

``mix`` of an ``attention`` layer: q = x W_q (32 heads of 64), k = x W_k,
v = x W_v (8 heads of 64: a key-value head serves 4 query heads), no
bias, no position encoding (``position_embedding_type`` nope),
``softmax(m_a q k^T + M) v`` with ``M`` causal inside the document, then
``W_o``.

``mix`` of a ``mamba`` layer, H = ``mamba_n_heads`` 64 heads of P =
``mamba_d_head`` 64 channels (E = H P = 4,096), N = ``mamba_d_state`` 128,
one group::

    [z | xBC | dl] = x W_in                   # E | E + 2 N | H
    xBC <- silu(sum_j w_j xBC_{t-j} + b_c)    # mamba_d_conv taps, depthwise,
                                              # inside the document
    [x | B | C] = xBC                         # E | N | N
    dt  = softplus(dl + b_dt)                 # a head
    S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t   # S [H, P, N]; a = -exp(A_log)
                                              # a head; S = 0 before a
                                              # document's first row
    y_t = S_t C_t + D x_t
    out = (rmsnorm(y * silu(z)) w_n) W_out    # over the E channels

Departures from ``modeling_granitemoehybrid.py``, each also under
``assumed`` in the configuration file (no network here: the modelling
file is from memory of it and of the Mamba-2 paper):

- the fused ``in_proj`` is one matrix with the columns ``[z | x | B | C
  | dt]`` in that order; the fused ``shared_mlp.input_linear`` is held as
  its two halves (the same arithmetic);
- the gated norm is ``rmsnorm(y * silu(z))`` over all E channels with one
  weight (``mamba_n_groups`` 1: the norm's group is the whole width), its
  epsilon ``rms_norm_eps``;
- no clamp on ``dt`` (``time_step_limit`` (0, inf), the default);
- the packed sequence's documents reset the state and stop the
  convolution and the attention (the published model packs nothing: one
  sequence a row);
- ``head_dim`` = ``hidden_size / num_attention_heads`` = 64 (no key
  states it);
- the seed's initialisation, not a checkpoint;
- labels are the packed sequence rolled by -1, running across document
  boundaries and wrapping at its end, as every training kind here rolls
  them;
- one rank's share of the vocabulary: the logits are over the rows the
  parameters hold.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .reference_afmoe import _hashable
from .reference_phi4flash import attend

MAMBA, ATTENTION = "mamba", "attention"


def layer_kinds(cfg: dict) -> list[str]:
    """The kinds of the layers the file keeps: the published list up to
    ``num_hidden_layers``."""
    return list(cfg["layer_types"][: cfg["num_hidden_layers"]])


def rms_norm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(axis=-1, keepdims=True) + eps) * w


ROWS_KEPT_APART = 64  # rows between two states the backward keeps


def token_recurrence(x, dt, a, b, c, d, start):
    """``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) b_t``, ``S = 0`` before a
    row of ``start``; ``y_t = S_t c_t + d x_t``: a token a step. x [t, H,
    P]; dt [t, H]; a, d [H]; b, c [t, N]; start [t] bool. The backward
    keeps the state every ``ROWS_KEPT_APART`` rows and makes the rows
    between again: a state a row is 2 MB at the published widths, 8.6 GB a
    layer at 4,096 rows (memory, not mathematics: every row is still one
    step of the recurrence)."""

    def token(s, row):
        xt, dtt, bt, ct, first = row
        s = jnp.where(first, 0.0, s)
        s = (
            jnp.exp(dtt * a)[:, None, None] * s
            + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :]
        )
        return s, s @ ct + d[:, None] * xt

    @jax.checkpoint
    def rows(s, block):
        return jax.lax.scan(token, s, block)

    t, heads, width = x.shape
    apart = ROWS_KEPT_APART if t % ROWS_KEPT_APART == 0 else t
    _, y = jax.lax.scan(
        rows, jnp.zeros((heads, width, b.shape[1])),
        jax.tree.map(
            lambda v: v.reshape(t // apart, apart, *v.shape[1:]),
            (x, dt, b, c, start),
        ),
    )
    return y.reshape(t, heads, width)


def mamba2(h, w, doc, start, cfg: dict):
    t = h.shape[0]
    heads, width, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    e = heads * width
    zxd = h @ w["ssd_in"]
    z, xbc, dl = zxd[:, :e], zxd[:, e : 2 * e + 2 * n], zxd[:, 2 * e + 2 * n :]
    conv = w["ssd_conv_b"] + w["ssd_conv_w"][0] * xbc
    for j in range(1, cfg["mamba_d_conv"]):
        # the token j before, where it is of the same document
        back = jnp.concatenate([jnp.zeros((j, e + 2 * n)), xbc[: t - j]])
        same = jnp.concatenate([jnp.zeros((j,), bool), doc[j:] == doc[: t - j]])
        conv = conv + w["ssd_conv_w"][j] * jnp.where(same[:, None], back, 0.0)
    xbc = jax.nn.silu(conv)
    y = token_recurrence(
        xbc[:, :e].reshape(t, heads, width),
        jax.nn.softplus(dl + w["ssd_dt_b"]), -jnp.exp(w["ssd_a_log"]),
        xbc[:, e : e + n], xbc[:, e + n :], w["ssd_d"], start,
    ).reshape(t, e)
    gated = rms_norm(y * jax.nn.silu(z), w["ssd_norm"], cfg["rms_norm_eps"])
    return gated @ w["ssd_out"]


def attention(h, w, doc, cfg: dict):
    t = h.shape[0]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // hq
    rows, cols = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    allow = (doc[:, None] == doc[None, :]) & (cols <= rows)
    q = (h @ w["wq"]).reshape(t, hq, d).transpose(1, 0, 2)
    k = (h @ w["wk"]).reshape(t, hk, d).transpose(1, 0, 2)
    v = (h @ w["wv"]).reshape(t, hk, d).transpose(1, 0, 2)
    group = hq // hk  # query head i reads key-value head i // group
    # ``attend`` divides by sqrt(d): the published scale rides on q
    q = q * (cfg["attention_multiplier"] * d ** 0.5)
    out = attend(q, jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0), allow)
    return out.transpose(1, 0, 2).reshape(t, hq * d) @ w["wo"]


def _layer(x, w, doc, kind: str, cfg):
    cfg = dict(cfg)
    eps, m_r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    start = jnp.concatenate([jnp.ones((1,), bool), doc[1:] != doc[:-1]])
    h = rms_norm(x, w["attn_norm"], eps)
    if kind == MAMBA:
        out = mamba2(h, w, doc, start, cfg)
    else:
        out = attention(h, w, doc, cfg)
    x = x + m_r * out
    h = rms_norm(x, w["mlp_norm"], eps)
    mlp = (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
    return x + m_r * mlp


def hidden_states(params, tokens, doc, cfg: dict):
    """The last layer's output [t, hidden], before the final norm."""
    # a layer keeps its inputs alone for the backward (memory, not maths)
    layer_fn = jax.checkpoint(_layer, static_argnums=(3, 4))
    x = cfg["embedding_multiplier"] * params["embed"][tokens]
    for w, kind in zip(params["layers"], layer_kinds(cfg)):
        x = layer_fn(x, w, doc, kind, _hashable(cfg))
    return x


def granite_loss(params, tokens, labels, doc, cfg: dict):
    """Mean next-token cross-entropy of one packed sequence: ``tokens``,
    ``labels`` [t] int32, ``doc`` [t] the rows' document ids."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = hidden_states(params, tokens, doc, cfg)
    x = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
    logits = x @ params["embed"].T / cfg["logits_scaling"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()
