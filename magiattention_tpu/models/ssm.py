"""The state-space half of a decoder-hybrid-decoder (SambaY,
arXiv:2507.06607; Phi-4-mini-flash-reasoning): the Mamba-1 mixer
(arXiv:2312.00752) and the gated memory unit that reads the last mixer's
scan output. ``models/pattern.py`` calls both on the normed hidden state
of one rank's rows; the norms, the residual and the FFN are its own.

Mamba-1 on ``h`` [t, dim], ``E`` = ``cfg.ssm_inner`` channels, ``N`` =
``cfg.ssm_state``, ``R`` = ``cfg.ssm_dt_rank``::

    [u | z] = h W_in                                   # magi_proj
    u   = silu(conv(u) + b_c)      # depthwise, causal, cfg.ssm_conv taps
                                   # inside the document  (magi_ssm_mix)
    [dl | B | C] = u W_x           # R + N + N
    dt  = softplus(dl W_dt + b_dt)                     # float32
    y   = selective_scan(u, dt, -exp(A_log), B, C, D)  # magi_ssm_scan
    out = (y * silu(z)) W_out      # the gate: magi_ssm_mix; W_out: magi_proj

``y``, before the gate, is what the last mixer hands on as the memory
``m``; a gated memory unit is ``(m * silu(h W_1)) W_2``: no scan, no
convolution.

The convolution reads a token's ``cfg.ssm_conv - 1`` predecessors through
``parallel/dispatch.shift_local`` (zero before a document's first token,
wherever dispatch put them); the scan runs on rows in sequence order, so
a state-space layer needs cp = 1 until a rank hands its state to the next
(ROADMAP R8).

Mamba-2 (arXiv:2405.21060; granite-4.0-h-micro's ``mamba`` layers) is the
second mixer, :func:`mamba2_mixer`: ``H`` = ``cfg.ssm_heads`` heads of
``P`` = ``E / H`` channels, one group of ``N`` states, a scalar decay a
head, so the scan is the chunked state-space-dual form of
``ops/ssd_scan.py`` (matmuls; imported where the mixer runs, not with the
package)::

    [z | xBC | dl] = h W_in         # E | E + 2 N | H        (magi_proj)
    xBC = silu(conv(xBC) + b_c)     # depthwise, causal, inside the document
    [x | B | C] = xBC               # E | N | N             (magi_ssm_mix)
    dt  = softplus(dl + b_dt)                               # float32
    y   = ssd_scan(x, dt, -exp(A_log), B, C, D)             # magi_ssd_scan
    out = (rmsnorm(y * silu(z)) w_n) W_out   # the gated norm: magi_ssm_mix
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.selective_scan import selective_scan
from ..utils.instrument import named_scope

F32 = jnp.float32


def _dense(key, shape):
    return jax.random.normal(key, shape, F32) / np.sqrt(shape[-2])


def init_mamba(key: jax.Array, cfg) -> dict:
    """The mixer's parameters. ``A_log`` is log 1..N a channel (the S4D
    start Mamba-1 uses), ``b_dt`` the inverse softplus of a step drawn
    log-uniform in [1e-3, 1e-1], so the decay differs a channel and a
    state and both carry gradient; ``D`` ones."""
    e, n, r, taps = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_dt_rank, cfg.ssm_conv
    k = jax.random.split(key, 7)
    step = jnp.exp(
        jax.random.uniform(k[5], (e,), F32) * (np.log(1e-1) - np.log(1e-3))
        + np.log(1e-3)
    )
    return {
        "ssm_in": _dense(k[0], (cfg.dim, 2 * e)),
        # tap j reads the token j before: a weight a channel a tap
        "ssm_conv_w": _dense(k[1], (taps, e)),
        "ssm_conv_b": 0.02 * jax.random.normal(k[2], (e,), F32),
        "ssm_x": _dense(k[3], (e, r + 2 * n)),
        "ssm_dt_w": _dense(k[4], (r, e)),
        "ssm_dt_b": step + jnp.log(-jnp.expm1(-step)),
        "ssm_a_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=F32)), (e, n)
        ),
        "ssm_d": jnp.ones((e,), F32),
        "ssm_out": _dense(k[6], (e, cfg.dim)),
    }


def init_mamba2(key: jax.Array, cfg) -> dict:
    """The Mamba-2 mixer's parameters, the seed's draws as
    :func:`init_mamba`'s: ``A_log`` the log of a draw in [1, 16] a head,
    ``b_dt`` the inverse softplus of a step log-uniform in [1e-3, 1e-1],
    ``D`` and the gated norm's weight ones."""
    e, n, h, taps = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_conv
    k = jax.random.split(key, 6)
    step = jnp.exp(
        jax.random.uniform(k[4], (h,), F32) * (np.log(1e-1) - np.log(1e-3))
        + np.log(1e-3)
    )
    return {
        # z's columns, then x's, B's and C's, then the step's a head
        "ssd_in": _dense(k[0], (cfg.dim, 2 * e + 2 * n + h)),
        # tap j reads the token j before: a weight a channel a tap
        "ssd_conv_w": _dense(k[1], (taps, e + 2 * n)),
        "ssd_conv_b": 0.02 * jax.random.normal(k[2], (e + 2 * n,), F32),
        "ssd_dt_b": step + jnp.log(-jnp.expm1(-step)),
        "ssd_a_log": jnp.log(
            jax.random.uniform(k[3], (h,), F32, minval=1.0, maxval=16.0)
        ),
        "ssd_d": jnp.ones((h,), F32),
        "ssd_norm": jnp.ones((e,), F32),
        "ssd_out": _dense(k[5], (e, cfg.dim)),
    }


def init_gmu(key: jax.Array, cfg) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "gmu_in": _dense(k1, (cfg.dim, cfg.ssm_inner)),
        "gmu_out": _dense(k2, (cfg.ssm_inner, cfg.dim)),
    }


def mamba_mixer(h, layer: dict, cfg, shift, start, *, interpret=None):
    """(the mixer's output [t, dim], the scan's output before the gate
    [t, E]). ``shift(u)`` -> ``u`` shifted by 1 .. ``cfg.ssm_conv - 1``
    along the document, zeros before its first token; ``start`` [t] bool,
    the rows at which a document starts; ``interpret``: the scan's
    kernels in interpret mode (None: off the TPU)."""
    dt = cfg.jnp_dtype
    e, n, r = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_dt_rank
    with named_scope("magi_proj"):
        uz = h @ layer["ssm_in"].astype(dt)
        u, z = uz[:, :e], uz[:, e:]
    with named_scope("magi_ssm_mix"):
        w = layer["ssm_conv_w"]
        conv = layer["ssm_conv_b"] + w[0] * u.astype(F32)
        for j, back in enumerate(shift(u), start=1):
            conv = conv + w[j] * back.astype(F32)
        u = jax.nn.silu(conv).astype(dt)
        x = u @ layer["ssm_x"].astype(dt)
        delta = jax.nn.softplus(
            jnp.dot(
                x[:, :r], layer["ssm_dt_w"].astype(dt),
                preferred_element_type=F32,
            ) + layer["ssm_dt_b"]
        )
        a = -jnp.exp(layer["ssm_a_log"])
    with named_scope("magi_ssm_scan"):
        y = selective_scan(
            u, delta, a, x[:, r : r + n], x[:, r + n :], layer["ssm_d"],
            start, state_dtype=cfg.scan_state_dtype, interpret=interpret,
        )
    with named_scope("magi_ssm_mix"):
        gated = y * jax.nn.silu(z)
    with named_scope("magi_proj"):
        return gated @ layer["ssm_out"].astype(dt), y


def mamba2_mixer(h, layer: dict, cfg, shift, start, *, interpret=None):
    """The Mamba-2 mixer's output [t, dim] (module docstring); ``shift``,
    ``start`` and ``interpret`` as :func:`mamba_mixer`'s."""
    from ..ops.ssd_scan import ssd_scan

    dt = cfg.jnp_dtype
    e, n, heads = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_heads
    t = h.shape[0]
    with named_scope("magi_proj"):
        zxd = h @ layer["ssd_in"].astype(dt)
        z, xbc, dl = zxd[:, :e], zxd[:, e : 2 * e + 2 * n], zxd[:, 2 * e + 2 * n :]
    with named_scope("magi_ssm_mix"):
        w = layer["ssd_conv_w"]
        conv = layer["ssd_conv_b"] + w[0] * xbc.astype(F32)
        for j, back in enumerate(shift(xbc), start=1):
            conv = conv + w[j] * back.astype(F32)
        xbc = jax.nn.silu(conv).astype(dt)
        delta = jax.nn.softplus(dl.astype(F32) + layer["ssd_dt_b"])
        a = -jnp.exp(layer["ssd_a_log"])
    with named_scope("magi_ssd_scan"):
        y = ssd_scan(
            xbc[:, :e].reshape(t, heads, e // heads), delta, a,
            xbc[:, e : e + n], xbc[:, e + n :], layer["ssd_d"], start,
            chunk=cfg.ssm_chunk or None, state_dtype=cfg.scan_state_dtype,
            interpret=interpret,
        ).reshape(t, e)
    with named_scope("magi_ssm_mix"):
        g = (y * jax.nn.silu(z)).astype(F32)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + cfg.rms_eps)
        gated = (g * layer["ssd_norm"]).astype(dt)
    with named_scope("magi_proj"):
        return gated @ layer["ssd_out"].astype(dt)


def gmu(h, m, layer: dict, cfg):
    """The gated memory unit on the normed hidden state ``h`` and the
    memory ``m`` [t, E] a mixer handed on."""
    dt = cfg.jnp_dtype
    with named_scope("magi_gmu"):
        return (
            m * jax.nn.silu(h @ layer["gmu_in"].astype(dt))
        ) @ layer["gmu_out"].astype(dt)
