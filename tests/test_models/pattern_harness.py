"""What the pattern decoder's test files share (not collected): the toy
documents, the mesh, the model's loss and gradients through the normal
path, the comparison's norm, the program census, and the memo that makes a
form's float32 reference (and an unfaulted model run) once a module
(ISSUE 45: a reference is computed once, not once a case)."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from magiattention_tpu.models import pattern
from magiattention_tpu.models.pattern import build_magi_pattern
from magiattention_tpu.parallel import dispatch, roll

# one document longer than the window (so the BICAUSAL band exists), one
# shorter, one that ends off the chunk grid
DOCS = [150, 40, 66]
TOTAL, CHUNK, WINDOW = sum(DOCS), 32, 48
CU = [0, *np.cumsum(DOCS).tolist()]




def computed_once(fn):
    """Memoise ``fn`` over a test module's cases. A tree of arrays (the
    parameters: the module's fixture hands every case the same tree) is
    keyed by identity, an array of tokens by its bytes, everything else (a
    configuration, a dict of published fields, a flag) by ``repr``. Only
    for calls under which nothing is patched or planted: a fault's run is
    made where the fault is."""
    cache = {}

    def key_of(a):
        if isinstance(a, np.ndarray):
            return ("array", a.shape, a.tobytes())
        if any(isinstance(x, jax.Array) for x in jax.tree.leaves(a)):
            return ("tree", id(a))
        return repr(a)

    def memoised(*args, **kwargs):
        key = (
            tuple(key_of(a) for a in args),
            tuple((k, key_of(v)) for k, v in sorted(kwargs.items())),
        )
        if key not in cache:
            cache[key] = (fn(*args, **kwargs), args)  # args: ids stay taken
        return cache[key][0]

    return memoised


def _mesh(cp):
    return Mesh(np.array(jax.devices()[:cp]).reshape(1, cp), ("dp", "cp"))


def _allow_full():
    pos = np.arange(TOTAL)
    doc = np.searchsorted(np.asarray(CU[1:]), pos, side="right")
    return jnp.asarray(
        (pos[None, :] <= pos[:, None]) & (doc[:, None] == doc[None, :])
    )


def _model_loss_and_grads(cfg, cp, params):
    mesh = _mesh(cp)
    model, meta = build_magi_pattern(cfg, mesh, CU, chunk_size=CHUNK)
    tokens_g = np.random.default_rng(3).integers(0, 64, (1, TOTAL))
    tokens = jax.vmap(lambda x: dispatch(x, meta))(
        jnp.asarray(tokens_g, jnp.int32)
    )
    labels = roll(tokens, meta, -1, axis=1, mesh=mesh, cp_axis="cp")
    pos = jnp.asarray(meta.perm_idx)[None]
    loss, grads = jax.jit(jax.value_and_grad(model.loss_fn))(
        params, tokens, labels, pos, model.sharded_tables()
    )
    return float(loss), grads, tokens_g[0], model, meta


# the same run, made once a module: where a test patches or plants nothing
unfaulted_loss_and_grads = computed_once(_model_loss_and_grads)


def _worst(got, want):
    errs = jax.tree.map(
        lambda a, b: float(
            jnp.linalg.norm((a - b).ravel())
            / jnp.maximum(jnp.linalg.norm(b.ravel()), 1e-30)
        ),
        got, want,
    )
    return max(jax.tree.leaves(errs))


def _census(jaxpr, into=None):
    """Equations by primitive, through every nested jaxpr."""
    into = collections.Counter() if into is None else into
    for eqn in jaxpr.eqns:
        into[eqn.primitive.name] += 1
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _census(sub, into)
    return into


def _kernels_by_name(jaxpr, into=None):
    """``pallas_call`` equations by the kernel's name, through every nested
    jaxpr but the kernels' own bodies: a scan's body counts once."""
    into = collections.Counter() if into is None else into
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            into[eqn.params["name"]] += 1
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernels_by_name(sub, into)
    return into


def _gradient_jaxpr(model, params):
    """The loss-and-gradient program of a built model, traced once."""
    batch = jnp.zeros((1, TOTAL), jnp.int32)
    return jax.make_jaxpr(jax.value_and_grad(model.loss_fn))(
        params, batch, batch, batch, model.sharded_tables()
    ).jaxpr


def _pin(cfg, params, monkeypatch):
    """(parameter names and shapes, the loss-and-gradient program's
    equations by primitive with the attention call stubbed out: what
    ``models/pattern.py`` itself traces, apart from the kernels and the
    runtime under it)."""
    monkeypatch.setattr(
        pattern, "dist_attn_local",
        lambda q, k, v, *a, **kw: (
            q + (k.sum() + v.sum()).astype(q.dtype), None, None
        ),
    )
    with jax.enable_x64(False):
        model, _meta = build_magi_pattern(cfg, _mesh(1), CU, chunk_size=CHUNK)
        jaxpr = _gradient_jaxpr(model, params)
    shapes = {
        jax.tree_util.keystr(k): tuple(v.shape)
        for k, v in jax.tree_util.tree_leaves_with_path(params)
    }
    return shapes, dict(_census(jaxpr))
