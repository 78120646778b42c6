"""The Mamba-2 hybrid (``models/pattern.py`` with the ``state_space_dual``
layer kind and ``models/ssm.mamba2_mixer``: granite-4.0-h-micro's two
layer kinds under the family's four scalars) against the plain float32
reference of ``benchmarks/`` on seeded random weights, at toy size on the
CPU, through the normal path: dispatch, shard_map, the documents' forward
shift, the state-space-dual scan's kernels in interpret mode,
``dist_attn_local`` on the documents' plan.

The toy has the published structure (Mamba, attention, Mamba, Mamba; a
dense SwiGLU in every layer; no position; the embedding tied and sliced)
at 4 query / 2 key-value heads of 16, 4 scan heads of 32 channels and 16
states, a scan chunk of 32, on ``pattern_harness``'s three documents (150
/ 40 / 66 tokens: both boundaries inside a scan chunk, the first document
longer than four chunks)."""

import collections
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks import reference_granite
from magiattention_tpu import telemetry
from magiattention_tpu.models import pattern
from magiattention_tpu.models.pattern import (
    DENSE, FULL, GQA, SSD, PatternConfig, build_magi_pattern,
    granitemoehybrid_config, init_pattern_params,
)
from tests.test_models import pattern_harness as toy
from tests.test_models.pattern_harness import (
    CU, TOTAL, _model_loss_and_grads, computed_once,
    unfaulted_loss_and_grads,
)

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
HF = dict(
    model_type="granitemoehybrid", hidden_size=64, num_attention_heads=4,
    num_key_value_heads=2, intermediate_size=96, shared_intermediate_size=96,
    rms_norm_eps=1e-5, num_hidden_layers=4,
    layer_types=["mamba", "attention", "mamba", "mamba", "mamba", "attention"],
    embedding_multiplier=12, residual_multiplier=0.22,
    attention_multiplier=0.0625, logits_scaling=8,
    mamba_n_heads=4, mamba_d_head=32, mamba_expand=2, mamba_d_state=16,
    mamba_n_groups=1, mamba_d_conv=4, mamba_conv_bias=True,
    mamba_proj_bias=False, mamba_chunk_size=32, attention_bias=False,
    position_embedding_type="nope", rope_scaling=None,
    normalization_function="rmsnorm", num_local_experts=0,
    num_experts_per_tok=0, tie_word_embeddings=True,
    vocab_size=256, vocab_here=64,
)
LOSS_TOL, GRAD_TOL = 2e-5, 2e-4


def _granite(dtype="float32", remat=True, **keys):
    hf = dict(HF, **keys)
    return hf, granitemoehybrid_config(
        hf, dtype=dtype, remat=remat, vocab_size=hf["vocab_here"]
    )


@computed_once
def _reference(hf, params, tokens_g):
    toks = jnp.asarray(tokens_g, jnp.int32)
    doc = jnp.asarray(
        np.searchsorted(np.asarray(CU[1:]), np.arange(TOTAL), side="right")
    )
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: reference_granite.granite_loss(
                p, toks, jnp.roll(toks, -1), doc, hf
            )
        )(params)


@pytest.fixture(scope="module")
def params():
    with jax.enable_x64(False):
        return init_pattern_params(jax.random.PRNGKey(7), _granite()[1])


def _errors(grads, want):
    """{name: the worst layer's relative L2 error}."""
    errs = {}
    grads, want = jax.tree.map(np.asarray, (grads, want))  # off their meshes
    for got, ref in zip(grads["layers"], want["layers"]):
        for name in ref:
            e = float(
                np.linalg.norm(got[name] - ref[name]) / np.linalg.norm(ref[name])
            )
            errs[name] = max(errs.get(name, 0.0), e)
    for name in want:
        if name != "layers":
            errs[name] = float(
                np.linalg.norm(grads[name] - want[name])
                / np.linalg.norm(want[name])
            )
    return errs


def _agrees(loss, grads, want, want_grads) -> bool:
    return (
        abs(loss - float(want)) <= LOSS_TOL * abs(float(want))
        and max(_errors(grads, want_grads).values()) <= GRAD_TOL
    )


def test_the_pattern_the_toy_builds(params):
    hf, cfg = _granite()
    assert cfg.layer_types == (SSD, FULL, SSD, SSD)
    assert cfg.ffn_types == (DENSE,) * 4 and cfg.attn_form == GQA
    assert cfg.plan_kinds == (FULL,)
    assert [cfg.plan_kind(t) for t in cfg.layer_types] == [
        None, FULL, None, None
    ]
    assert cfg.rope_kinds == () and cfg.shift_taps == (1, 2, 3)
    assert (cfg.ssm_inner, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_conv,
            cfg.ssm_chunk) == (128, 4, 16, 4, 32)
    assert (cfg.embed_scale, cfg.residual_scale, cfg.softmax_scale,
            cfg.logits_scaling) == (12.0, 0.22, 0.0625, 8.0)
    # the kernels are planned for the model's own heads and scale
    assert cfg.kernel_heads is cfg and cfg.head_dim == 16
    # the vocabulary slice: this rank's rows of the tied embedding
    assert cfg.tie_embeddings and "lm_head" not in params
    assert params["embed"].shape == (hf["vocab_here"], 64)
    names = [sorted(layer) for layer in params["layers"]]
    assert names[0] == names[2] == names[3] and "ssd_a_log" in names[0]
    assert {"wq", "wk", "wv", "wo"} <= set(names[1])
    assert not {"wq", "ssm_in", "w_attn_gate", "q_norm"} & set(names[0])
    shapes = {k: v.shape for k, v in params["layers"][0].items()}
    assert shapes["ssd_in"] == (64, 2 * 128 + 2 * 16 + 4)
    assert shapes["ssd_conv_w"] == (4, 128 + 32)
    assert shapes["ssd_conv_b"] == (160,) and shapes["ssd_norm"] == (128,)
    assert shapes["ssd_a_log"] == shapes["ssd_dt_b"] == shapes["ssd_d"] == (4,)
    assert shapes["ssd_out"] == (128, 64)


def test_loss_and_every_gradient_match_the_reference(params):
    hf, cfg = _granite()
    with jax.enable_x64(False):
        loss, grads, tokens_g, model, _meta = unfaulted_loss_and_grads(
            cfg, 1, params
        )
        want, want_grads = _reference(hf, params, tokens_g)
    assert tokens_g.max() < hf["vocab_here"]  # ids drawn inside the slice
    assert abs(loss - float(want)) <= LOSS_TOL * abs(float(want))
    errs = _errors(grads, want_grads)
    assert max(errs.values()) <= GRAD_TOL, errs
    for i, layer in enumerate(grads["layers"]):  # every parameter is live
        for name, g in layer.items():
            assert float(jnp.abs(g).max()) > 0.0, (i, name)
    assert model.shift_plan.taps == (1, 2, 3) and set(model.plans) == {FULL}


@pytest.mark.parametrize("field,plain", [
    ("embed_scale", 1.0), ("residual_scale", 1.0), ("softmax_scale", None),
    ("logits_scaling", 1.0),
])
def test_a_multiplier_left_out_of_the_model_fails_it(params, field, plain):
    """Each of Granite's four scalars at what every other configuration
    has (``softmax_scale`` None: ``head_dim ** -0.5``, 1/4 not 1/16), in
    the model alone: the comparison that passes above refuses it."""
    hf, cfg = _granite()
    with jax.enable_x64(False):
        loss, grads, tokens_g, *_ = _model_loss_and_grads(
            dataclasses.replace(cfg, **{field: plain}), 1, params
        )
        assert not _agrees(loss, grads, *_reference(hf, params, tokens_g))


def _across_documents(q, k, v, _tables, _plan, attn_params, **_):
    """Causal attention that ignores the documents (cp = 1: rows in
    order)."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(a, group, axis=1) for a in (k, v))
    s = jnp.einsum("ihd,jhd->hij", q, k) * attn_params.scale
    t = q.shape[0]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("hij,jhd->ihd", jax.nn.softmax(s, axis=-1), v), None, None


def _shift_across_documents(u, _tables, plan, _axis):
    return [
        jnp.concatenate([jnp.zeros_like(u[:j]), u[:-j]]) for j in plan.taps
    ]


@pytest.mark.parametrize("name,fault", [
    ("dist_attn_local", _across_documents),
    ("shift_local", _shift_across_documents),
])
def test_reading_across_a_document_boundary_fails_it(
    params, monkeypatch, name, fault
):
    """Attention that sees the document before, and a convolution whose
    taps do not stop at a boundary: each alone is refused (the scan's own
    reset is ``tests/test_ops/test_ssd_scan.py``'s)."""
    hf, cfg = _granite()
    monkeypatch.setattr(pattern, name, fault)
    with jax.enable_x64(False):
        loss, grads, tokens_g, *_ = _model_loss_and_grads(cfg, 1, params)
        assert not _agrees(loss, grads, *_reference(hf, params, tokens_g))


def test_remat_on_equals_remat_off(params):
    """The scan's kernels run again under remat from the layer's inputs:
    the same loss to the bit, the same gradients to float32's rounding
    (XLA fuses the recomputed forward with the backward)."""
    with jax.enable_x64(False):
        on = unfaulted_loss_and_grads(_granite()[1], 1, params)
        off = _model_loss_and_grads(_granite(remat=False)[1], 1, params)
    assert on[0] == off[0]
    assert max(_errors(on[1], off[1]).values()) <= GRAD_TOL / 10


def test_a_state_space_dual_layer_past_cp_one_raises_by_name(params):
    with pytest.raises(NotImplementedError, match="R8"):
        _model_loss_and_grads(_granite()[1], 2, params)


@pytest.mark.parametrize("change,match", [
    (dict(attn_form=pattern.DIFF), "plain GQA"),
    (dict(ssm_heads=3), "whole heads"),
    (dict(ssm_state=0), "mixer's sizes"),
    (dict(hc_mult=4), "plain GQA"),
])
def test_what_no_reference_states_is_refused(change, match):
    _hf, cfg = _granite()
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(cfg, **change)


@pytest.mark.parametrize("key,value,match", [
    ("num_local_experts", 8, "experts"),
    ("position_embedding_type", "rope", "rotary"),
    ("mamba_n_groups", 2, "one group"),
    ("layer_types", ["mamba", "moe", "mamba", "mamba"], "layer_types"),
    ("mamba_d_head", 16, "heads' width"),
])
def test_a_published_key_that_is_not_built_is_refused(key, value, match):
    with pytest.raises(ValueError, match=match):
        _granite(**{key: value})


def _toy_step_text(cfg) -> str:
    model, _ = build_magi_pattern(cfg, toy._mesh(1), CU, chunk_size=toy.CHUNK)
    params = init_pattern_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adamw(1e-3)
    batch = jnp.zeros((1, TOTAL), jnp.int32)
    return model.make_train_step(opt).lower(
        params, opt.init(params), batch, batch, batch
    ).as_text()


def test_at_their_defaults_the_scalars_add_nothing_to_a_step(monkeypatch):
    """An existing pattern cell's toy step (Trinity's form: window and
    full layers, experts) lowers to the same text under the code that
    reads ``residual_scale`` and ``logits_scaling`` as under the forms
    those two replaced."""
    from tests.test_models.test_pattern import CFG as afmoe

    assert (afmoe.residual_scale, afmoe.logits_scaling) == (1.0, 1.0)
    with jax.enable_x64(False):
        now = _toy_step_text(afmoe)
        monkeypatch.setattr(pattern, "_residual", lambda x, out, cfg: x + out)

        def logits(x, params, cfg):
            head = (
                params["embed"].astype(cfg.jnp_dtype).T if cfg.tie_embeddings
                else params["lm_head"].astype(cfg.jnp_dtype)
            )
            return (x @ head).astype(jnp.float32)

        monkeypatch.setattr(pattern, "_logits", logits)
        before = _toy_step_text(afmoe)
    assert now == before


def test_the_published_depth_is_nine_mamba_to_one_attention():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(
            r for r in map(json.loads, f) if r["name"] == "granite-4.0-h-micro"
        )
    cfg = granitemoehybrid_config(row["config"])
    assert cfg.n_layers == 40
    assert collections.Counter(cfg.layer_types) == {SSD: 36, FULL: 4}
    assert [i for i, k in enumerate(cfg.layer_types) if k == FULL] == [
        5, 15, 25, 35
    ]
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        2048, 32, 8, 64
    )
    assert (cfg.ssm_inner, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_conv,
            cfg.ssm_chunk) == (4096, 64, 128, 4, 256)
    assert (cfg.embed_scale, cfg.residual_scale, cfg.softmax_scale,
            cfg.logits_scaling) == (12.0, 0.22, 0.015625, 8.0)
    assert (cfg.ffn_hidden, cfg.vocab_size) == (8192, 100352)
    shapes = jax.eval_shape(
        lambda r: init_pattern_params(r, cfg), jax.random.PRNGKey(0)
    )
    n = sum(v.size for v in jax.tree.leaves(shapes))
    assert n == 3_191_396_096  # the published 3 B
    one = dataclasses.replace(
        cfg, layer_types=cfg.layer_types[:10], ffn_types=(DENSE,) * 10,
        vocab_size=12544,
    )
    shapes = jax.eval_shape(
        lambda r: init_pattern_params(r, one), jax.random.PRNGKey(0)
    )
    assert sum(v.size for v in jax.tree.leaves(shapes)) == 772_160_448


def test_the_build_counts_the_documents_and_the_reset_chunks(params):
    _hf, cfg = _granite()
    telemetry.set_enabled(True)
    reg = telemetry.get_registry()
    try:
        with jax.enable_x64(False):
            build_magi_pattern(cfg, toy._mesh(1), CU, chunk_size=toy.CHUNK)
        got = {
            name: reg.gauge_value(name)
            for name in ("magi_ssm_documents", "magi_ssd_reset_chunks")
        }
        got.update({
            name: reg.gauge_value("magi_model_multipliers", multiplier=name)
            for name in ("embed", "residual", "softmax", "logits")
        })
    finally:
        telemetry.set_enabled(None)
    # 150 and 190 both lie inside a chunk of 32, in two chunks
    assert got == {
        "magi_ssm_documents": 3.0, "magi_ssd_reset_chunks": 2.0,
        "embed": 12.0, "residual": 0.22, "softmax": 0.0625, "logits": 8.0,
    }
