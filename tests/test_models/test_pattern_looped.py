"""The looped decoder (``models/pattern.py`` at ``n_loops > 1``: Ouro's
layer stack run several times on shared weights inside one ``lax.scan``,
an exit through the shared head and a gate after every pass) against the
plain float32 reference of ``benchmarks/`` on seeded random weights, at
toy size on the CPU, through the normal path; and what ``n_loops == 1``
may not move."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_ouro
from magiattention_tpu import telemetry
from magiattention_tpu.models import _common, pattern
from magiattention_tpu.models.pattern import (
    DENSE, EXPERTS, FULL, build_magi_pattern, exit_log_probs,
    init_pattern_params, ouro_config,
)
from tests.test_benchmarks import looped_faults
from tests.test_models.pattern_harness import (
    CHUNK, CU, TOTAL, _allow_full, _census, _gradient_jaxpr, _mesh,
    _model_loss_and_grads, _pin, _worst, computed_once,
    unfaulted_loss_and_grads,
)
from tests.test_models.test_pattern import CFG
from tests.test_models.test_pattern_latent import _glm

# the published widths in ratio: 4 query = 4 key-value heads, a SwiGLU of
# 2.75 x hidden, 4 passes through 2 layers
HF = dict(
    hidden_size=64, intermediate_size=176, num_attention_heads=4,
    num_key_value_heads=4, head_dim=16, num_hidden_layers=2,
    rope_theta=1e6, rms_norm_eps=1e-6, vocab_size=64, total_ut_steps=4,
    sliding_window=None, exit_entropy_weight=0.05,
)


def _ouro(dtype="float32", **keys):
    hf = dict(HF, **keys)
    return hf, ouro_config(hf, dtype=dtype, remat=True)


@computed_once
def _reference(hf, params, tokens_g, **kw):
    toks = jnp.asarray(tokens_g, jnp.int32)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: reference_ouro.ouro_loss(
                p, toks, jnp.roll(toks, -1), _allow_full(), hf, **kw
            )
        )(params)


@pytest.fixture(scope="module")
def params():
    with jax.enable_x64(False):
        return init_pattern_params(jax.random.PRNGKey(7), _ouro()[1])


# float32 against float32, tolerances as test_pattern.py's: what is left
# is the order of the sums (the kernels' online softmax, the scan's sum
# of the weights' gradient over passes)
@pytest.mark.parametrize("cp", [1, 2, 4])
def test_looped_loss_and_every_gradient_match_the_reference(params, cp):
    hf, cfg = _ouro()
    assert set(params["exit_gate"]) == {"w", "b"}
    with jax.enable_x64(False):
        loss, grads, tokens_g, _model, _meta = unfaulted_loss_and_grads(
            cfg, cp, params
        )
        want, want_grads = _reference(hf, params, tokens_g)
    assert abs(loss - float(want)) <= 2e-5 * abs(float(want))
    assert _worst(grads, want_grads) <= 2e-4
    # the gate's and the shared head's gradients are live
    for leaf in (grads["exit_gate"]["w"], grads["exit_gate"]["b"],
                 grads["lm_head"], grads["final_norm"]):
        assert float(jnp.abs(leaf).max()) > 0


def test_the_reference_recomputed_is_the_reference(params):
    """``recompute`` (the chip's check: a layer application and an exit
    run again in the backward) changes no value."""
    hf, _cfg = _ouro()
    tokens_g = np.random.default_rng(3).integers(0, 64, TOTAL)
    with jax.enable_x64(False):
        a, ga = _reference(hf, params, tokens_g)
        b, gb = _reference(hf, params, tokens_g, recompute=True)
    assert float(a) == float(b)
    assert _worst(gb, ga) <= 1e-6


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "no-remat"])
def test_the_scanned_pass_is_the_unrolled_loop(params, remat, monkeypatch):
    _hf, cfg = _ouro()
    cfg = dataclasses.replace(cfg, remat=remat)
    with jax.enable_x64(False):
        loss, grads, *_ = unfaulted_loss_and_grads(cfg, 2, params)
        monkeypatch.setattr(
            pattern, "_looped_trunk_local", looped_faults.unrolled_trunk
        )
        want, want_grads, *_ = _model_loss_and_grads(cfg, 2, params)
    assert abs(loss - want) <= 1e-6 * abs(want)
    assert _worst(grads, want_grads) <= 2e-6


def _pallas_calls(cfg, params):
    model, _meta = build_magi_pattern(cfg, _mesh(1), CU, chunk_size=CHUNK)
    return _census(_gradient_jaxpr(model, params))


def test_the_program_does_not_grow_with_the_passes(params):
    """A layer's kernels are in the loss-and-gradient program once a
    direction whatever ``n_loops`` is (the forward in the scanned pass;
    remat's forward and the one backward kernel in its transpose), and so
    is every other equation: two passes and four trace the same program
    but for the scans' lengths. (The step the chip's compiler makes of the
    published widths holds 3 x layers ``tpu_custom_call``s:
    tests/test_aot_compile_tpu.py.)"""
    with jax.enable_x64(False):
        two, four = (
            _pallas_calls(_ouro(total_ut_steps=n)[1], params) for n in (2, 4)
        )
    assert four["pallas_call"] == 3 * 2
    assert two == four


def _ce_sums_as_they_were(logits, labels):
    """``_common.masked_ce_sums`` before it became the per-token form's
    sum, written out."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    valid = labels >= 0
    safe = jnp.where(valid, labels, 0)
    tok_loss = -jnp.take_along_axis(logp, safe[:, None], axis=1)[:, 0]
    return (
        jnp.where(valid, tok_loss, 0.0).sum(),
        valid.sum().astype(jnp.float32),
    )


def test_one_pass_gives_the_loss_it_gave(monkeypatch):
    """``n_loops == 1`` takes the path it took: no gate among the
    parameters, and the loss and every gradient of the Trinity-shaped toy
    model bit for bit what the old ``masked_ce_sums`` gives in the same
    program."""
    assert CFG.n_loops == 1
    with jax.enable_x64(False):
        params = init_pattern_params(jax.random.PRNGKey(7), CFG)
        assert "exit_gate" not in params
        loss, grads, *_ = _model_loss_and_grads(CFG, 2, params)
        monkeypatch.setattr(pattern, "masked_ce_sums", _ce_sums_as_they_were)
        was, was_grads, *_ = _model_loss_and_grads(CFG, 2, params)
    assert loss == was
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: bool((a == b).all()), grads, was_grads
    )))


def test_the_sums_are_the_per_token_form_summed():
    """``masked_ce_sums`` is ``masked_ce_tokens`` summed."""
    rng = np.random.default_rng(5)
    logits = jnp.asarray(rng.standard_normal((96, 64)) * 3, jnp.float32)
    labels = jnp.asarray(rng.integers(-1, 64, 96), jnp.int32)
    was = _ce_sums_as_they_were(logits, labels)
    got = _common.masked_ce_sums(logits, labels)
    tok, ok = _common.masked_ce_tokens(logits, labels)
    assert float(got[0]) == float(was[0]) == float(tok.sum())
    assert float(got[1]) == float(was[1]) == float(ok.sum()) < 96
    assert not np.asarray(tok)[np.asarray(labels) < 0].any()


# equations by primitive of the GLM-shaped toy model's loss-and-gradient
# program with the attention call stubbed (``test_pattern._pin``), read
# from the commit before the looped path (PR 31's tree)
GLM_CENSUS = {
    "add": 165, "add_any": 116, "and": 12, "broadcast_in_dim": 531,
    "concatenate": 35, "convert_element_type": 115, "cos": 16, "cumsum": 6,
    "div": 129, "dot_general": 145, "dynamic_slice": 6, "exp": 2, "gather":
    26, "ge": 9, "integer_pow": 3, "iota": 28, "jit": 161, "log": 2,
    "logistic": 20, "lt": 47, "max": 23, "min": 12, "mul": 484, "ne": 12,
    "neg": 17, "pad": 48, "pow": 16, "psum": 79, "pvary": 2,
    "ragged_dot_general": 36, "reduce_max": 2, "reduce_sum": 178, "rem": 6,
    "remat2": 10, "reshape": 107, "reshard": 1, "rsqrt": 36, "scatter-add":
    22, "select_n": 108, "shard_map": 3, "sign": 12, "sin": 16, "slice": 93,
    "sort": 6, "split": 17, "squeeze": 16, "stop_gradient": 8, "sub": 54,
    "top_k": 6, "transpose": 49,
}


def test_glms_tree_and_program_are_what_they_were(monkeypatch):
    """Beside test_pattern.py's Trinity and Llama pins (which hold the
    same of the GQA form): the latent form with its MTP module."""
    _hf, cfg = _glm(1)
    with jax.enable_x64(False):
        glm_params = init_pattern_params(jax.random.PRNGKey(7), cfg)
    shapes, census = _pin(cfg, glm_params, monkeypatch)
    assert len(shapes) == 70 and "['exit_gate']['w']" not in shapes
    assert census == GLM_CENSUS


def test_one_exit_is_certain():
    """T = 1: p_1 = 1 and H = 0, in the model's form and the
    reference's; and the exits' probabilities add up to 1 at any T."""
    g = jnp.asarray(np.random.default_rng(0).standard_normal((4, 33)) * 4)
    logp = exit_log_probs(g[:1])
    assert logp.shape == (1, 33) and not np.asarray(logp).any()
    assert (np.asarray(reference_ouro.exit_distribution(
        jax.nn.sigmoid(g[:1])
    )) == 1.0).all()
    p = jnp.exp(exit_log_probs(g))
    np.testing.assert_allclose(p.sum(axis=0), 1.0, rtol=1e-6)
    np.testing.assert_allclose(
        p, reference_ouro.exit_distribution(jax.nn.sigmoid(g)), rtol=1e-5,
        atol=1e-12,
    )


def test_the_reference_at_one_pass_is_the_mean_cross_entropy(params):
    hf, _cfg = _ouro(total_ut_steps=1)
    toks = jnp.asarray(np.random.default_rng(3).integers(0, 64, TOTAL))
    with jax.enable_x64(False), jax.default_matmul_precision("highest"):
        loss, (p, ce) = reference_ouro.ouro_loss(
            params, toks, jnp.roll(toks, -1), _allow_full(), hf,
            with_exits=True,
        )
    assert (np.asarray(p) == 1.0).all() and p.shape == (1, TOTAL)
    assert float(loss) == pytest.approx(float(ce.mean()), rel=1e-6)


LOOP_FAULTS = {
    "one pass fewer": {"n_loops": 3},
    "beta 0.055 for 0.05": {"exit_entropy_weight": 0.055},
    "the entropy's sign flipped": {"exit_entropy_weight": -0.05},
    "no norm after each half": {"post_norms": False},
}


@pytest.mark.parametrize("fault", sorted(LOOP_FAULTS))
def test_a_looped_fault_moves_a_gradient(params, fault):
    """Float32 on both sides: each wrong model fails the comparison's
    2e-4 by orders."""
    hf, cfg = _ouro()
    with jax.enable_x64(False):
        _loss, grads, tokens_g, _m, _meta = _model_loss_and_grads(
            dataclasses.replace(cfg, **LOOP_FAULTS[fault]), 1, params
        )
        _want, want_grads = _reference(hf, params, tokens_g)
    assert _worst(grads, want_grads) > 0.02, fault


def test_the_loop_scopes_and_gauges(params):
    _hf, cfg = _ouro()
    telemetry.set_enabled(True)
    reg = telemetry.get_registry()
    names = ("magi_model_loop_steps", "magi_model_layer_applications")
    try:
        with jax.enable_x64(False):
            model, _meta = build_magi_pattern(cfg, _mesh(1), CU, chunk_size=CHUNK)
            batch = jnp.zeros((1, TOTAL), jnp.int32)
            text = jax.jit(model.loss_fn).lower(
                params, batch, batch, batch, model.sharded_tables()
            ).as_text(debug_info=True)
        looped = [reg.gauge_value(n) for n in names]
        span = [
            e["args"] for e in telemetry.get_event_buffer().events()
            if e["name"] == "plan_flex_attn"
        ][-1]
        build_magi_pattern(CFG, _mesh(1), CU, chunk_size=CHUNK)
        once = [reg.gauge_value(n) for n in names]
    finally:
        for n in names:
            reg.clear_metric(n)
        telemetry.set_enabled(None)
    for scope in ("magi_loop", "magi_exit_head", "magi_attn_full"):
        assert scope in text, scope
    assert looped == [4.0, 8.0] and once == [1.0, 3.0]
    assert (span["heads_q"], span["heads_kv"], span["head_dim"]) == (4, 4, 16)


def test_looped_config_rejects_what_is_not_built():
    _hf, cfg = _ouro()
    assert (cfg.n_loops, cfg.exit_entropy_weight, cfg.post_norms) == (
        4, 0.05, True
    )
    assert cfg.layer_types == (FULL,) * 2 and cfg.ffn_types == (DENSE,) * 2
    assert (cfg.qk_norm, cfg.attn_gate, cfg.rope_kinds, cfg.rms_eps) == (
        False, False, (FULL,), 1e-6
    )
    with pytest.raises(ValueError, match="at least one pass"):
        dataclasses.replace(cfg, n_loops=0)
    with pytest.raises(ValueError, match="looped decoder with experts"):
        dataclasses.replace(cfg, ffn_types=(DENSE, EXPERTS))
    with pytest.raises(ValueError, match="looped decoder with experts"):
        dataclasses.replace(_glm(1)[1], n_loops=2)
    with pytest.raises(ValueError, match="window"):
        ouro_config(dict(HF, sliding_window=128))
