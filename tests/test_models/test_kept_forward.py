"""A layer the trunk applies once a step keeps its attention call's out and
compact lse across its ``jax.checkpoint`` (ISSUE 48,
``models/_common.layer_under_remat``): the gradient's program holds the
forward kernel once a layer, what a layer saves is its inputs and those
two, the gradients are the plain checkpoint's to the bit, and the looped
trunk, whose layers a scan applies ``n_loops`` times, keeps today's form."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals  # not exported in 0.9.0

from magiattention_tpu import telemetry
from magiattention_tpu.api import infer_attn_mask_from_cu_seqlens
from magiattention_tpu.models import (
    LlamaConfig, _common, build_magi_llama, init_params, llama, pattern,
)
from magiattention_tpu.models.pattern import (
    FULL, SLIDING, afmoe_config, build_magi_pattern, init_pattern_params,
)
from magiattention_tpu.ops.flex_attn import KEPT_NAMES
from tests.test_models.pattern_harness import (
    CHUNK, CU, TOTAL, _gradient_jaxpr, _kernels_by_name, _mesh,
    _model_loss_and_grads,
)
from tests.test_models.test_pattern import HF
from tests.test_models.test_pattern_looped import _ouro

# two layers, one of each attention kind over the packed documents: a
# window mask and a packed mask in one model (dense, then experts)
CFG = afmoe_config(
    dict(HF, num_hidden_layers=2, layer_types=[SLIDING, FULL]),
    dtype="float32", remat=True, expert_range=(2, 6), vocab_size=64,
)
LLAMA = LlamaConfig(
    vocab_size=64, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=16,
    ffn_hidden=96, dtype="float32", remat=True,
)
FWD, BWD = "magi_flex_fwd_kernel", "magi_flex_bwd_kernel"
COUNTER = "magi_flex_forward_kept_total"


@pytest.fixture(scope="module")
def params():
    with jax.enable_x64(False):
        return init_pattern_params(jax.random.PRNGKey(7), CFG)


def _build_llama():
    qr, kr, ts = infer_attn_mask_from_cu_seqlens(CU)
    model, _meta = build_magi_llama(
        LLAMA, _mesh(1), TOTAL, qr, kr, ts, chunk_size=CHUNK,
        block_q=32, block_k=32,
    )
    return model, init_params(jax.random.PRNGKey(0), LLAMA)


def _todays_form(monkeypatch):
    """Every trunk's layers under the plain ``jax.checkpoint``, nothing
    named and nothing kept: the form of before ISSUE 48, which the looped
    trunk still asks for."""
    plain = functools.partial(_common.layer_under_remat, applied_once=False)
    for module in (pattern, llama):
        monkeypatch.setattr(
            module, "layer_under_remat",
            lambda make, attn_params, kinds, *, remat, applied_once=True:
            plain(make, attn_params, kinds, remat=remat),
        )


@pytest.fixture
def counter():
    telemetry.set_enabled(True)
    reg = telemetry.get_registry()
    reg.clear_metric(COUNTER)
    try:
        yield lambda: {
            kind: int(reg.counter_value(COUNTER, kind=kind))
            for kind in ("sliding", "full")
        }
    finally:
        reg.clear_metric(COUNTER)
        telemetry.set_enabled(False)


def test_a_pattern_layers_forward_kernel_is_in_the_gradient_once(
    params, counter, monkeypatch
):
    with jax.enable_x64(False):
        model, _meta = build_magi_pattern(CFG, _mesh(1), CU, chunk_size=CHUNK)
        kept = _kernels_by_name(_gradient_jaxpr(model, params))
        assert counter() == {"sliding": 1, "full": 1}  # a layer and kind
        _todays_form(monkeypatch)
        plain = _kernels_by_name(_gradient_jaxpr(model, params))
    assert kept == {FWD: 2, BWD: 2}
    assert plain == {FWD: 4, BWD: 2}  # forward, remat's forward, backward
    assert counter() == {"sliding": 1, "full": 1}  # the plain form: no more


def test_the_llama_decoders_forward_kernel_is_in_the_gradient_once(counter):
    with jax.enable_x64(False):
        model, weights = _build_llama()
        kept = _kernels_by_name(_gradient_jaxpr(model, weights))
    assert kept == {FWD: LLAMA.n_layers, BWD: LLAMA.n_layers}
    # the decoder's layers are one function, which jax traces once for
    # them all: the rule is traced once, not once a layer
    assert counter() == {"sliding": 0, "full": 1}


def _saved(fn, *args):
    """(shape, reason less its source line) of what differentiating ``fn``
    saves that is neither an argument nor a constant."""
    return [
        (aval.shape, why.split(" from ")[0])
        for aval, why in saved_residuals(fn, *args)
        if not why.startswith(("from the argument", "from a constant"))
    ]


@pytest.mark.parametrize("index", [0, 1], ids=["sliding", "full"])
def test_a_pattern_layer_saves_its_inputs_and_the_two_names(params, index):
    """out [hq, t, d] and lse [hq, t] as the kernel hands them back, and no
    q, k, v, lane-replicated statistic or activation of the FFN. (Behind a
    saved float that the layer reads on, as out is, jax puts a
    ``reduce_precision`` to the array's own precision: that is the reason
    its line gives, where the lse's gives its name.)"""
    with jax.enable_x64(False):
        model, _meta = build_magi_pattern(CFG, _mesh(1), CU, chunk_size=CHUNK)
        layer_fn = pattern._one_layer(
            CFG, CFG.layer_types[index], CFG.ffn_types[index],
            model.sharded_tables(), model.plans, model.attn_params, "cp",
            None, index,
        )
        x = jnp.ones((TOTAL, CFG.dim), jnp.float32)
        pos = jnp.arange(TOTAL, dtype=jnp.int32)
        saved = _saved(
            lambda x, layer: layer_fn(x, pos, layer)[0], x,
            params["layers"][index],
        )
    assert sorted(saved) == [
        ((CFG.n_heads, TOTAL), f"named '{KEPT_NAMES[1]}'"),
        ((CFG.n_heads, TOTAL, CFG.head_dim), "output of reduce_precision"),
    ]


def test_the_llama_decoder_saves_two_arrays_of_the_call_a_layer():
    with jax.enable_x64(False):
        model, weights = _build_llama()
        tok = jnp.zeros((TOTAL,), jnp.int32)
        saved = _saved(
            lambda p: llama.forward_local(
                p, tok, tok, LLAMA, model.sharded_tables(), model.plan,
                model.attn_params, "cp",
            ).sum(),
            weights,
        )
    h, d = LLAMA.n_heads, LLAMA.head_dim
    kept = [
        ((h, TOTAL), f"named '{KEPT_NAMES[1]}'"),
        ((h, TOTAL, d), "output of reduce_precision"),
    ]
    assert sorted(x for x in saved if x in kept) == sorted(kept * LLAMA.n_layers)
    # and beside them a layer's input, nothing head-major or lane-wide
    assert all(len(shape) <= 2 for shape, why in saved if (shape, why) not in kept)


@pytest.mark.parametrize("cp", [1, 2])
def test_kept_gradients_are_the_plain_checkpoints_to_the_bit(
    params, cp, monkeypatch
):
    """The same kernels on the same inputs: the backward reads the out and
    the lse the second forward would have remade. One model holds a window
    layer and a packed full layer; at cp 2 every stage's call is kept."""
    with jax.enable_x64(False):
        loss, grads, *_ = _model_loss_and_grads(CFG, cp, params)
        _todays_form(monkeypatch)
        want, want_grads, *_ = _model_loss_and_grads(CFG, cp, params)
    assert loss == want
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want_grads)
    ):
        assert np.array_equal(np.asarray(a), np.asarray(b)), path


def test_the_looped_trunk_keeps_nothing(counter):
    """A layer inside the pass scan is applied ``n_loops`` times: what it
    kept would be held once a pass. Its gradient still holds two forwards
    an application (the scanned pass, remat's in the transpose)."""
    with jax.enable_x64(False):
        _hf, cfg = _ouro()
        weights = init_pattern_params(jax.random.PRNGKey(7), cfg)
        model, _meta = build_magi_pattern(cfg, _mesh(1), CU, chunk_size=CHUNK)
        launches = _kernels_by_name(_gradient_jaxpr(model, weights))
    layers = len(cfg.layer_types)
    assert launches == {FWD: 2 * layers, BWD: layers}
    assert counter() == {"sliding": 0, "full": 0}
