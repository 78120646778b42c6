"""The plain float32 Phi-4-mini-flash-reasoning decoder (``model_type``
``phi4flash``; the SambaY decoder-hybrid-decoder, arXiv:2507.06607),
independent of ``magiattention_tpu/``.

Straightforward ``jax.numpy``: the scan a ``lax.scan`` over single
tokens with the reset written as ``where(start, 0, s)``, the convolution
an explicit sum over taps inside a document, the masks dense booleans
from document ids, a differential pair two plain softmaxes, no kernels,
no dispatch, no planner. Callers run it under
``jax.default_matmul_precision("highest")`` and take gradients with
``jax.grad``. ``cfg`` is the configuration file's keys
(``benchmarks/configs/phi-4-mini-flash-reasoning.json``) with its
``assumed.sizes`` beside them; ``params`` is a pytree with the names
``models/pattern.py`` and ``models/ssm.py`` document, which is all the
two share.

``LN`` is LayerNorm with weight and bias; no layer encodes a position;
``l`` is a layer's published index (``layers_kept``). Every layer::

    x <- x + mixer(LN1(x));   x <- x + W_down (silu(h W_gate) * h W_up), h = LN2(x)

by :func:`layer_kinds`: Mamba-1 (l even, l <= 16), window attention (l
odd, l < 16), full attention (l = 17), gated memory (l even, l > 16),
cross attention (l odd, l > 17). Then ``LN_f``, logits on the tied
embedding's rows, mean next-token cross-entropy.

- Mamba-1: ``[u | z] = h W_in``; ``u <- silu(sum_j w_j u_{t-j} + b_c)``
  over ``d_conv`` taps inside the document; ``[dl | B | C] = u W_x``;
  ``dt = softplus(dl W_dt + b_dt)``; ``s_t = exp(dt_t A) s_{t-1} + dt_t
  B_t u_t`` with ``s = 0`` before a document's first token, ``A =
  -exp(A_log)``; ``y_t = C_t . s_t + D u_t``; ``out = (y * silu(z))
  W_out``. The Mamba layer at ``l = 16`` hands ``y``, before the gate,
  on as the memory ``m``.
- Gated memory: ``out = (m * silu(h W_1)) W_2``.
- Differential attention: ``q = h W_q + b_q`` (heads of 64), ``k``,
  ``v`` likewise. Query pair ``j`` = heads ``(2j, 2j+1)``, key pair ``i =
  j // (n_q / n_kv)`` = key heads ``(2i, 2i+1)``, ``V_i = [v_2i |
  v_2i+1]``. ``a1 = softmax(q_2j k_2i^T / 8 + M) V_i``, ``a2`` the same
  of the pair's second heads; ``o_j = (1 - lam0) RMSNorm(a1 - lam a2)``
  with a weight of its own, ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) +
  lam0``, ``lam0 = 0.8 - 0.6 exp(-0.3 l)``; ``out = [o_0 ..] W_o +
  b_o``. ``M``: causal inside the document, in window layers the
  ``sliding_window`` newest keys, the token itself among them. The full
  layer's ``k``, ``v`` are the shared pair.
- Cross attention: ``q`` alone, the same form with its own ``lam``
  vectors, norm and ``W_o``, on the shared pair, causal inside the
  document.

Departures from the published model, each also under ``assumed`` in the
configuration file (no network here: the modelling file is from memory of
the SambaY and Differential Transformer papers):

- the fused ``gate_up`` and ``in_proj`` matrices are held as their two
  halves (the same arithmetic);
- adjacent-head pairing and ``V_i`` as the concatenation of the pair's
  two value heads; the window counts the token itself (512 keys);
- the sub-norm's epsilon is ``layer_norm_eps``;
- the seed's initialisation, not a checkpoint;
- labels are the packed sequence rolled by -1, running across document
  boundaries and wrapping at its end, as every training kind here rolls
  them (the published loss masks nothing either: documents are packed);
- one rank's share of the vocabulary: the logits are over the rows the
  parameters hold.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .reference_afmoe import _hashable

SSM, GMU = "state_space", "gated_memory"
SLIDING, FULL, CROSS = "sliding_attention", "full_attention", "cross_attention"


def layer_kinds(cfg: dict) -> list[str]:
    """The kinds of the layers the file keeps (``layers_kept``: published
    indices), from the published depth and ``mb_per_layer``."""
    depth = cfg.get("num_hidden_layers_published", cfg["num_hidden_layers"])
    half, mb = depth // 2, cfg["mb_per_layer"]

    def kind(i):
        if i % mb == 0:
            return SSM if i <= half else GMU
        return SLIDING if i < half else FULL if i == half + 1 else CROSS

    return [kind(i) for i in cfg.get("layers_kept", range(depth))]


def layer_norm(x, w, b, eps):
    x = x - x.mean(axis=-1, keepdims=True)
    return x / jnp.sqrt((x * x).mean(axis=-1, keepdims=True) + eps) * w + b


def masks_of(doc, window: int):
    """(causal inside a document [t, t] bool, the same under the window,
    which rows start a document [t] bool) from the rows' document ids."""
    t = doc.shape[0]
    rows, cols = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    full = (doc[:, None] == doc[None, :]) & (cols <= rows)
    start = jnp.concatenate([jnp.ones((1,), bool), doc[1:] != doc[:-1]])
    return full, full & (rows - cols < window), start


def selective_scan(u, dt, a, b, c, d, start):
    """``s_t = exp(dt_t a) s_{t-1} + dt_t b_t u_t``, ``s = 0`` before a
    row of ``start``; ``y_t = c_t . s_t + d u_t``: a token a step. u, dt
    [t, e]; a [e, n]; b, c [t, n]; d [e]; start [t] bool."""

    def token(s, row):
        ut, dtt, bt, ct, first = row
        s = jnp.where(first, 0.0, s)
        s = jnp.exp(dtt[:, None] * a) * s + (dtt * ut)[:, None] * bt[None, :]
        return s, s @ ct + d * ut

    _, y = jax.lax.scan(token, jnp.zeros(a.shape), (u, dt, b, c, start))
    return y


def mamba(h, w, doc, start, cfg: dict):
    """(the mixer's output, its scan's output before the gate)."""
    t = h.shape[0]
    e = cfg["expand"] * cfg["hidden_size"]
    n, r = cfg["d_state"], cfg["dt_rank"]
    uz = h @ w["ssm_in"]
    u, z = uz[:, :e], uz[:, e:]
    conv = w["ssm_conv_b"] + w["ssm_conv_w"][0] * u
    for j in range(1, cfg["d_conv"]):
        # the token j before, where it is of the same document
        back = jnp.concatenate([jnp.zeros((j, e)), u[: t - j]])
        same = jnp.concatenate([jnp.zeros((j,), bool), doc[j:] == doc[: t - j]])
        conv = conv + w["ssm_conv_w"][j] * jnp.where(same[:, None], back, 0.0)
    u = jax.nn.silu(conv)
    x = u @ w["ssm_x"]
    dt = jax.nn.softplus(x[:, :r] @ w["ssm_dt_w"] + w["ssm_dt_b"])
    y = selective_scan(
        u, dt, -jnp.exp(w["ssm_a_log"]), x[:, r : r + n], x[:, r + n :],
        w["ssm_d"], start,
    )
    return (y * jax.nn.silu(z)) @ w["ssm_out"], y


def lambda_init(index: int) -> float:
    return 0.8 - 0.6 * float(np.exp(-0.3 * index))


ROWS_AT_A_TIME = 2048  # query rows of one head whose scores are alive


def attend(q, k, v, allow):
    """``softmax(q k^T / sqrt(d) + mask) v`` a head: q, k [heads, t, d],
    v [heads, t, dv], ``allow`` [t, t] bool -> [heads, t, dv]. A head and
    ``ROWS_AT_A_TIME`` query rows at a time, each made again in the
    backward: [t, t] float32 scores of every head at once are 43 GB at
    16,384 rows (memory, not mathematics)."""
    t, d = q.shape[1:]
    rows = ROWS_AT_A_TIME if t % ROWS_AT_A_TIME == 0 else t

    @jax.checkpoint
    def block(qb, kh, vh, ab):
        s = jnp.where(ab, qb @ kh.T / np.sqrt(d), -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ vh

    def head(args):
        qh, kh, vh = args
        out = jax.lax.map(
            lambda b: block(b[0], kh, vh, b[1]),
            (qh.reshape(-1, rows, d), allow.reshape(-1, rows, t)),
        )
        return out.reshape(t, -1)

    return jax.lax.map(head, (q, k, v))


def diff_attention(q, k, v, w, allow, index: int, cfg: dict):
    """The differential pairs on q [t, hq, d], k and v [t, hk, d] ->
    [t, hq / 2 x 2d], before the output projection."""
    t, hq, d = q.shape
    hk = k.shape[1]
    per_key_pair = (hq // 2) // (hk // 2)
    lam0 = lambda_init(index)
    lam = (
        jnp.exp(w["lambda_q1"] @ w["lambda_k1"])
        - jnp.exp(w["lambda_q2"] @ w["lambda_k2"]) + lam0
    )
    # query head 2j + s reads key head 2i + s and the value pair i
    key_of = [2 * ((h // 2) // per_key_pair) + h % 2 for h in range(hq)]
    pair_of = [(h // 2) // per_key_pair for h in range(hq)]
    pairs = v.reshape(t, hk // 2, 2 * d)  # [v_2i | v_2i+1]
    a = attend(
        q.transpose(1, 0, 2), k[:, key_of].transpose(1, 0, 2),
        pairs[:, pair_of].transpose(1, 0, 2), allow,
    )
    x = a[0::2] - lam * a[1::2]  # [pairs, t, 2d]
    x = x / jnp.sqrt((x * x).mean(axis=-1, keepdims=True)
                     + cfg["layer_norm_eps"])
    x = (1.0 - lam0) * x * w["diff_norm"]
    return x.transpose(1, 0, 2).reshape(t, -1)


def _layer(x, w, handed, doc, kind: str, index: int, hands_on: bool, cfg):
    """One layer -> (x, what it hands on: its scan output or its keys and
    values where ``hands_on``, else what it was handed)."""
    cfg = dict(cfg)
    eps = cfg["layer_norm_eps"]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // hq
    t = x.shape[0]
    full, window, start = masks_of(doc, cfg["sliding_window"])
    memory, shared = handed
    h = layer_norm(x, w["attn_norm"], w["attn_norm_b"], eps)
    if kind == SSM:
        out, y = mamba(h, w, doc, start, cfg)
        if hands_on:
            memory = y
    elif kind == GMU:
        out = (memory * jax.nn.silu(h @ w["gmu_in"])) @ w["gmu_out"]
    else:
        q = (h @ w["wq"] + w["bq"]).reshape(t, hq, d)
        if kind == CROSS:
            k, v = shared
        else:
            k = (h @ w["wk"] + w["bk"]).reshape(t, hk, d)
            v = (h @ w["wv"] + w["bv"]).reshape(t, hk, d)
            if hands_on:
                shared = (k, v)
        allow = window if kind == SLIDING else full
        out = diff_attention(q, k, v, w, allow, index, cfg)
        out = out @ w["wo"] + w["bo"]
    x = x + out
    h = layer_norm(x, w["mlp_norm"], w["mlp_norm_b"], eps)
    x = x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
    return x, (memory, shared)


def hidden_states(params, tokens, doc, cfg: dict):
    """The last layer's output [t, hidden], before the final norm."""
    kinds = layer_kinds(cfg)
    index = list(cfg.get("layers_kept", range(len(kinds))))
    # who hands on: the last mixer before the first memory unit, the last
    # full layer before the first cross layer
    hands_on = {
        max((i for i in range(kinds.index(reader)) if kinds[i] == maker),
            default=None)
        for maker, reader in ((SSM, GMU), (FULL, CROSS)) if reader in kinds
    }
    # a layer keeps its inputs alone for the backward (memory, not maths)
    layer_fn = jax.checkpoint(_layer, static_argnums=(4, 5, 6, 7))
    x = params["embed"][tokens]
    t, hk = tokens.shape[0], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // cfg["num_attention_heads"]
    handed = (
        jnp.zeros((t, cfg["expand"] * cfg["hidden_size"])),
        (jnp.zeros((t, hk, d)), jnp.zeros((t, hk, d))),
    )
    for i, (w, kind) in enumerate(zip(params["layers"], kinds)):
        x, handed = layer_fn(
            x, w, handed, doc, kind, index[i], i in hands_on, _hashable(cfg)
        )
    return x


def phi4flash_loss(params, tokens, labels, doc, cfg: dict):
    """Mean next-token cross-entropy of one packed sequence: ``tokens``,
    ``labels`` [t] int32, ``doc`` [t] the rows' document ids."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = hidden_states(params, tokens, doc, cfg)
    x = layer_norm(
        x, params["final_norm"], params["final_norm_b"], cfg["layer_norm_eps"]
    )
    logp = jax.nn.log_softmax(x @ params["embed"].T, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()
