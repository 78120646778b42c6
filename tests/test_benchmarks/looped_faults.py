"""Seeded faults of the looped decoder (``models/pattern.py`` at
``n_loops > 1``) that no configuration field expresses, planted for the
length of a ``with``: each is a wrong model the comparison with
``reference_ouro`` has to refuse. The model is traced inside the ``with``
(jit caches by function identity, and the tests build a new closure a
reading)."""

from __future__ import annotations

import contextlib

import jax.numpy as jnp

from magiattention_tpu.models import pattern


def unrolled_trunk(params, tokens, pos, cfg, tables, plans, attn_params,
                   axis_name, *, shared: bool = True,
                   norm_inside: bool = True):
    """``pattern._looped_trunk_local`` as a Python loop over passes and
    layers; as a fault, with a pass's weights its own (pass ``t`` runs
    layer ``i`` on the weights of layer ``i + t``) or the final norm left
    out of the loop (a pass starts from the state before the norm; the
    exits still read the normed one)."""
    n = cfg.n_layers
    x = pattern._embed(params, tokens, cfg)
    states = []
    for t in range(cfg.n_loops):
        for i, (layer_type, ffn_type) in enumerate(
            zip(cfg.layer_types, cfg.ffn_types)
        ):
            layer = params["layers"][i if shared else (i + t) % n]
            x, _s = pattern._one_layer(
                cfg, layer_type, ffn_type, tables, plans, attn_params,
                axis_name,
            )(x, pos, layer)
        normed = pattern._rms_norm(x, params["final_norm"], cfg.rms_eps)
        states.append(normed)
        if norm_inside:
            x = normed
    return jnp.stack(states)


@contextlib.contextmanager
def planted(fault: str):
    """``fault``: ``weights not shared between passes``, ``the final norm
    outside the loop``."""
    real = pattern._looped_trunk_local
    if fault == "weights not shared between passes":
        kw = dict(shared=False, norm_inside=True)
    elif fault == "the final norm outside the loop":
        kw = dict(shared=True, norm_inside=False)
    else:
        raise ValueError(fault)
    pattern._looped_trunk_local = lambda *a: unrolled_trunk(*a, **kw)
    try:
        yield
    finally:
        pattern._looped_trunk_local = real


PLANTED = (
    "weights not shared between passes", "the final norm outside the loop",
)
