"""Seeded faults of the latent-attention decoder (``models/pattern.py``)
that no configuration field expresses, planted for the length of a
``with``: each is a wrong model the comparison with
``reference_glm4moe`` has to refuse. The model is traced inside the
``with`` (jit caches by function identity, and the tests build a new
closure a reading)."""

from __future__ import annotations

import contextlib

import jax.numpy as jnp

from magiattention_tpu.models import pattern


def _rope_on_every_dimension(real):
    """The rotary applied to a head's whole width, not its last
    ``rope_head_dim``: the position-free part is rotated too."""

    def qkv(h, pos, layer, cfg):
        q, k, v = real(h, pos, layer, cfg)
        nope = cfg.head_dim - cfg.rope_head_dim

        def rot(x):
            return jnp.concatenate(
                [pattern._rope(x[..., :nope], pos, cfg.rope_theta, nope),
                 x[..., nope:]], axis=-1,
            )

        return rot(q), rot(k), v

    return qkv


def _rope_key_a_head(real):
    """The rotary key not shared: every head its own (here the shared
    one scaled by the head's number, as a per-head projection would
    differ)."""

    def qkv(h, pos, layer, cfg):
        q, k, v = real(h, pos, layer, cfg)
        nope = cfg.head_dim - cfg.rope_head_dim
        per_head = 1.0 + jnp.arange(cfg.n_heads, dtype=k.dtype)[None, :, None]
        k = jnp.concatenate(
            [k[..., :nope], k[..., nope:] * per_head / cfg.n_heads], axis=-1
        )
        return q, k, v

    return qkv


class _Skip:
    """Marks a norm weight whose normalisation a fault leaves out."""

    def __init__(self, weight):
        self.weight = weight


@contextlib.contextmanager
def planted(fault: str):
    """``fault``: ``rotary on every dimension``, ``rotary key not
    shared``, ``latent norm left out``, ``mtp target rolled by -1``."""
    saved = {
        n: getattr(pattern, n) for n in ("_latent_qkv", "_rms_norm", "roll")
    }
    real_qkv, real_norm, real_roll = saved.values()
    if fault == "rotary on every dimension":
        pattern._latent_qkv = _rope_on_every_dimension(real_qkv)
    elif fault == "rotary key not shared":
        pattern._latent_qkv = _rope_key_a_head(real_qkv)
    elif fault == "latent norm left out":
        # the key-value latent's norm is the one whose weight is named so
        def qkv(h, pos, layer, cfg):
            marked = dict(layer, kv_a_norm=_Skip(layer["kv_a_norm"]))
            return real_qkv(h, pos, marked, cfg)

        def norm(x, w, eps=1e-5):
            if isinstance(w, _Skip):
                return x * w.weight.astype(x.dtype)
            return real_norm(x, w, eps)

        pattern._latent_qkv, pattern._rms_norm = qkv, norm
    elif fault == "mtp target rolled by -1":
        pattern.roll = lambda x, meta, shift, **kw: real_roll(
            x, meta, shift + 1, **kw
        )
    else:
        raise ValueError(fault)
    try:
        yield
    finally:
        for name, value in saved.items():
            setattr(pattern, name, value)


PLANTED = (
    "rotary on every dimension", "rotary key not shared",
    "latent norm left out", "mtp target rolled by -1",
)
