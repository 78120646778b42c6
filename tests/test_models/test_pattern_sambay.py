"""The decoder-hybrid-decoder (``models/pattern.py`` at
``attn_form="diff"`` with ``models/ssm.py``: Phi-4-mini-flash-reasoning's
six layer kinds) against the plain float32 reference of ``benchmarks/`` on
seeded random weights, at toy size on the CPU, through the normal path:
dispatch, shard_map, the documents' forward shift, the selective scan's
kernels in interpret mode, ``dist_attn_local`` on a window plan and a
full plan, the memory and the shared keys and values from layer to layer.

The toy has the published structure (layers 14-19: Mamba, window
attention, the Mamba that hands on its scan, the full attention that hands
on its k and v, a gated memory unit, a cross attention) at 4 query / 2
key-value heads of 16 and 4 scan states, on ``pattern_harness``'s three
documents (150 / 40 / 66 tokens: one shorter than the window of 48, two
longer, both boundaries off the chunk grid of 32)."""

import collections
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_phi4flash
from magiattention_tpu import telemetry
from magiattention_tpu.models import pattern
from magiattention_tpu.models.pattern import (
    CROSS, DENSE, DIFF, FULL, GMU, LAYER, SLIDING, SSM, PatternConfig,
    init_pattern_params, phi4flash_config, phi4flash_kinds,
)
from tests.test_models.pattern_harness import (
    CU, TOTAL, WINDOW, _model_loss_and_grads, computed_once,
    unfaulted_loss_and_grads,
)

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
HF = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=96, sliding_window=WINDOW, layer_norm_eps=1e-5,
    mb_per_layer=2, num_hidden_layers=6, num_hidden_layers_published=32,
    layers_kept=[14, 15, 16, 17, 18, 19], tie_word_embeddings=True,
    vocab_size=256, vocab_here=64,
    d_state=4, d_conv=4, expand=2, dt_rank=4,
)
# a key's bias moves no score: its gradient is zero, both sides read noise
DEAD = ("bk",)


def _sambay(dtype="float32", remat=True, **keys):
    hf = dict(HF, **keys)
    return hf, phi4flash_config(
        hf, dtype=dtype, remat=remat, vocab_size=hf["vocab_here"],
        layers=hf["layers_kept"],
    )


@computed_once
def _reference(hf, params, tokens_g):
    toks = jnp.asarray(tokens_g, jnp.int32)
    doc = jnp.asarray(
        np.searchsorted(np.asarray(CU[1:]), np.arange(TOTAL), side="right")
    )
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: reference_phi4flash.phi4flash_loss(
                p, toks, jnp.roll(toks, -1), doc, hf
            )
        )(params)


@pytest.fixture(scope="module")
def params():
    with jax.enable_x64(False):
        return init_pattern_params(jax.random.PRNGKey(7), _sambay()[1])


def _errors(grads, want):
    """{name: the worst layer's relative L2 error}; a dead parameter's
    against the norm of the live one beside it."""
    errs = {}
    grads, want = jax.tree.map(np.asarray, (grads, want))  # off their meshes
    for i, (got, ref) in enumerate(zip(grads["layers"], want["layers"])):
        for name in ref:
            held_to = ref["bq"] if name in DEAD else ref[name]
            e = float(
                np.linalg.norm(got[name] - ref[name]) / np.linalg.norm(held_to)
            )
            errs[name] = max(errs.get(name, 0.0), e)
    for name in want:
        if name != "layers":
            errs[name] = float(
                np.linalg.norm(grads[name] - want[name])
                / np.linalg.norm(want[name])
            )
    return errs


def test_the_pattern_the_toy_builds(params):
    hf, cfg = _sambay()
    assert cfg.layer_types == (SSM, SLIDING, SSM, FULL, GMU, CROSS)
    assert cfg.layer_index == (14, 15, 16, 17, 18, 19)
    assert (cfg.memory_layer, cfg.kv_layer) == (2, 3)
    assert cfg.plan_kinds == (FULL, SLIDING)
    assert [cfg.plan_kind(t) for t in cfg.layer_types] == [
        None, SLIDING, None, FULL, None, FULL
    ]
    assert (cfg.attn_form, cfg.norm_form, cfg.rope_kinds) == (DIFF, LAYER, ())
    assert cfg.shift_taps == (1, 2, 3) and cfg.ffn_types == (DENSE,) * 6
    assert (cfg.ssm_inner, cfg.ssm_state, cfg.ssm_conv, cfg.ssm_dt_rank) == (
        128, 4, 4, 4
    )
    heads = cfg.kernel_heads
    assert (heads.n_heads, heads.n_kv_heads, heads.head_dim) == (4, 2, 32)
    assert heads.softmax_scale == 0.25  # the published head's 1 / sqrt(16)
    assert cfg.tie_embeddings and "lm_head" not in params
    names = [sorted(layer) for layer in params["layers"]]
    assert names[0] == names[2] and "ssm_a_log" in names[0]
    assert {"wq", "wk", "wv", "wo", "bq", "bk", "bv", "bo", "lambda_q1",
            "diff_norm"} <= set(names[1]) and names[1] == names[3]
    assert {"gmu_in", "gmu_out"} <= set(names[4]) and "wq" not in names[4]
    assert {"wq", "wo", "bq", "bo", "lambda_k2"} <= set(names[5])
    assert not {"wk", "wv", "bk", "bv"} & set(names[5])  # a query alone
    assert all("attn_norm_b" in n and "mlp_norm_b" in n for n in names)
    assert "final_norm_b" in params
    shapes = {k: v.shape for k, v in params["layers"][0].items()}
    assert shapes["ssm_in"] == (64, 256) and shapes["ssm_conv_w"] == (4, 128)
    assert shapes["ssm_x"] == (128, 12) and shapes["ssm_a_log"] == (128, 4)


def test_loss_and_every_gradient_match_the_reference(params):
    hf, cfg = _sambay()
    with jax.enable_x64(False):
        loss, grads, tokens_g, model, _meta = unfaulted_loss_and_grads(
            cfg, 1, params
        )
        want, want_grads = _reference(hf, params, tokens_g)
    assert abs(loss - float(want)) <= 2e-5 * abs(float(want))
    errs = _errors(grads, want_grads)
    assert max(errs.values()) <= 2e-4, errs
    # every parameter is live but the keys' bias
    for i, layer in enumerate(grads["layers"]):
        for name, g in layer.items():
            scale = float(jnp.abs(want_grads["layers"][i]["bq"]).max()) if (
                name in DEAD
            ) else 0.0
            assert (float(jnp.abs(g).max()) > 1e-3 * scale) != (
                name in DEAD
            ), (i, name)
    assert model.shift_plan.taps == (1, 2, 3)
    assert set(model.plans) == {FULL, SLIDING}


def test_what_is_handed_on_crosses_checkpoint_to_the_bit(params):
    """``m`` and ``(k, v)`` reach every reader through ``checkpoint``:
    remat on equals remat off, loss and every gradient, to the bit on the
    CPU (a carry that were recomputed, dropped or counted twice would
    not)."""
    with jax.enable_x64(False):
        on = unfaulted_loss_and_grads(_sambay()[1], 1, params)
        off = _model_loss_and_grads(_sambay(remat=False)[1], 1, params)
    assert on[0] == off[0]
    for a, b in zip(jax.tree.leaves(on[1]), jax.tree.leaves(off[1])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_wider_query_group_pairs_the_same_way():
    """8 query heads on 2 key-value heads: four query pairs read one key
    pair (``i = j // 2`` is the published case of ``j // (n_q / n_kv)``)."""
    hf, cfg = _sambay(num_attention_heads=8)
    assert (cfg.n_heads, cfg.head_dim) == (8, 8)
    with jax.enable_x64(False):
        params = init_pattern_params(jax.random.PRNGKey(3), cfg)
        loss, grads, tokens_g, _model, _meta = _model_loss_and_grads(
            cfg, 1, params
        )
        want, want_grads = _reference(hf, params, tokens_g)
    assert abs(loss - float(want)) <= 2e-5 * abs(float(want))
    assert max(_errors(grads, want_grads).values()) <= 2e-4


def _cross_only():
    """A full layer and two cross layers that read its keys and values:
    no state-space layer, so any cp."""
    _hf, cfg = _sambay()
    return dataclasses.replace(
        cfg, layer_types=(FULL, CROSS, CROSS), ffn_types=(DENSE,) * 3,
        layer_index=(17, 19, 21),
    )


@pytest.mark.parametrize("cp", [2, 4])
def test_cross_layers_at_cp_equal_cp_one(cp):
    cfg = _cross_only()
    assert (cfg.kv_layer, cfg.memory_layer, cfg.shift_taps) == (0, None, ())
    telemetry.set_enabled(True)
    reg = telemetry.get_registry()
    try:
        with jax.enable_x64(False):
            params = init_pattern_params(jax.random.PRNGKey(5), cfg)
            one = unfaulted_loss_and_grads(cfg, 1, params)
            assert reg.counter_value("magi_shared_kv_recast_rows_total") == 0
            many = _model_loss_and_grads(cfg, cp, params)
        recast = reg.counter_value("magi_shared_kv_recast_rows_total")
        readers = reg.gauge_value("magi_shared_kv_readers")
        padded = reg.gauge_value("magi_flex_pad_lane_share")
    finally:
        reg.clear_metric("magi_shared_kv_recast_rows_total")
        telemetry.set_enabled(None)
    assert abs(many[0] - one[0]) <= 2e-5 * abs(one[0])
    errs = _errors(many[1], one[1])
    assert max(errs.values()) <= 2e-4, errs
    # both readers' calls cast the handed-on pair again: the follow-up's
    # number (ROADMAP R11)
    assert (readers, padded) == (2.0, 0.5)
    assert recast > 0 and recast % 2 == 0


def test_a_state_space_layer_past_cp_one_raises_by_name(params):
    _hf, cfg = _sambay()
    with pytest.raises(NotImplementedError, match="R8"):
        _model_loss_and_grads(cfg, 2, params)


@pytest.mark.parametrize("kinds,match", [
    ((SSM, CROSS, FULL), "no full_attention layer before it"),
    ((GMU, SSM, FULL), "no state-space layer before it"),
])
def test_a_reader_before_its_maker_is_refused(kinds, match):
    _hf, cfg = _sambay()
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(
            cfg, layer_types=kinds, ffn_types=(DENSE,) * 3, layer_index=()
        )


def test_the_new_kinds_go_with_differential_attention_alone():
    with pytest.raises(ValueError, match="attn_form 'diff'"):
        PatternConfig(
            vocab_size=64, dim=64, n_heads=4, n_kv_heads=2, head_dim=16,
            layer_types=(SSM, FULL), ffn_types=(DENSE,) * 2, ffn_hidden=96,
        )


@pytest.mark.parametrize("spread,closest", [
    (pattern.DIFF_LAMBDA_STD, "above 0.08"), (0.1, "under 0.02"),
])
def test_the_seeds_lambda_stays_off_one(monkeypatch, spread, closest):
    """``DIFF_LAMBDA_STD``: at heads of 64 the seed's lambda leaves
    lambda_init by a draw of spread 11.3 x its square, and 1 - lambda stays
    above 0.08 on every one of 512 seeds' three layers; at the
    Differential Transformer's 0.1 some seed comes within 0.02 of 1, where
    bf16 loses a document's first tokens (the refused check of PR 46)."""
    monkeypatch.setattr(pattern, "DIFF_LAMBDA_STD", spread)
    _, cfg = _sambay(hidden_size=256)
    assert cfg.head_dim == 64

    def lambdas(rng):
        return jnp.stack([
            jnp.exp(w["lambda_q1"] @ w["lambda_k1"])
            - jnp.exp(w["lambda_q2"] @ w["lambda_k2"])
            + pattern.diff_lambda_init(i)
            for w, i in zip(
                init_pattern_params(rng, cfg)["layers"], cfg.layer_index
            ) if "lambda_q1" in w
        ])

    got = np.asarray(jax.jit(jax.vmap(lambdas))(
        jax.random.split(jax.random.PRNGKey(46), 512)
    ))
    assert got.shape == (512, 3)
    np.testing.assert_allclose(
        got[:, 0].std(), 2 ** 0.5 * 8 * spread ** 2, rtol=0.1
    )
    assert got.std() > 0.02  # lambda leaves its constant: it carries gradient
    gap = np.abs(1.0 - got).min()
    assert (gap > 0.08) if closest == "above 0.08" else (gap < 0.02), gap


def test_the_published_depth_is_nine_eight_one_seven_seven():
    assert collections.Counter(phi4flash_kinds(32, 2)) == {
        SSM: 9, SLIDING: 8, FULL: 1, GMU: 7, CROSS: 7
    }
    kinds = phi4flash_kinds(32, 2)
    assert kinds[14:20] == (SSM, SLIDING, SSM, FULL, GMU, CROSS)
    assert [i for i, k in enumerate(kinds) if k == SSM] == list(range(0, 17, 2))
    assert kinds.index(FULL) == 17 and kinds[31] == CROSS
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(
            r for r in map(json.loads, f)
            if r["name"] == "Phi-4-mini-flash-reasoning"
        )
    cfg = phi4flash_config(row["config"])
    assert cfg.n_layers == 32 and cfg.layer_types == kinds
    assert (cfg.memory_layer, cfg.kv_layer) == (16, 17)
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        2560, 40, 20, 64
    )
    assert (cfg.ssm_inner, cfg.ssm_state, cfg.ssm_conv, cfg.ssm_dt_rank) == (
        5120, 16, 4, 160
    )
    assert (cfg.sliding_window, cfg.ffn_hidden, cfg.vocab_size) == (
        512, 10240, 200064
    )
    shapes = jax.eval_shape(
        lambda r: init_pattern_params(r, cfg), jax.random.PRNGKey(0)
    )
    n = sum(v.size for v in jax.tree.leaves(shapes))
    assert n == 3_852_562_944  # the published 3.8 B


def test_the_build_counts_what_is_handed_on(params):
    _hf, cfg = _sambay()
    telemetry.set_enabled(True)
    reg = telemetry.get_registry()
    try:
        with jax.enable_x64(False):
            pattern.build_magi_pattern(
                cfg, _mesh_one(), CU, chunk_size=32
            )
        got = {
            name: reg.gauge_value(name) for name in (
                "magi_ssm_documents", "magi_shared_kv_readers",
                "magi_flex_pad_lane_share",
            )
        }
    finally:
        telemetry.set_enabled(None)
    assert got == {
        "magi_ssm_documents": 3.0, "magi_shared_kv_readers": 1.0,
        "magi_flex_pad_lane_share": 0.5,
    }


def _mesh_one():
    from tests.test_models.pattern_harness import _mesh

    return _mesh(1)
