"""``models/pattern.py`` with the route made before attention
(SmallThinker, ``smallthinker_config``): a softmax router on the attention
half's normed input, ReLU-gated experts, one full-attention layer with no
position encoding to rotary window layers at a GQA group of 7, against
the plain ``benchmarks/reference_smallthinker.py`` (float32 on both
sides); the share of an 8-rank deployment; and that the route is one call
a layer wherever it is made."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_smallthinker
from magiattention_tpu import telemetry
from magiattention_tpu.models import pattern
from magiattention_tpu.models.pattern import (
    EXPERTS, FULL, GQA, SLIDING, SOFTMAX, build_magi_pattern,
    init_pattern_params, smallthinker_config,
)
from magiattention_tpu.parallel import dispatch
from tests.test_models.pattern_harness import (
    _census, _kernels_by_name, _mesh, _worst,
)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the published widths in ratio: 14 query heads on 2 key-value heads of 16
# (group 7), 16 experts top-3, eight of them here; one full layer to one
# window layer (the published period's two kinds)
HF = dict(
    hidden_size=64, head_dim=16, num_attention_heads=14,
    num_key_value_heads=2, num_hidden_layers=2, max_position_embeddings=128,
    moe_ffn_hidden_size=32, moe_num_primary_experts=16,
    moe_num_active_primary_experts=3, moe_primary_router_apply_softmax=True,
    norm_topk_prob=True, rms_norm_eps=1e-6, rope_layout=[0, 1, 1, 1],
    sliding_window_layout=[0, 1, 1, 1], sliding_window_size=32,
    rope_scaling=None, rope_theta=1.5e6, tie_word_embeddings=False,
    vocab_size=512, experts_here=[4, 12], vocab_here=64,
    flat_expert_rows=True,
)
# a document 2.5 windows long, one exactly a window, one short
DOCS, CHUNK = (80, 32, 16), 32
TOKENS = sum(DOCS)
CU = [0, *np.cumsum(DOCS).tolist()]
DOC = np.repeat(np.arange(len(DOCS)), DOCS).astype(np.int32)
TOKEN_IDS = np.random.default_rng(3).integers(0, HF["vocab_here"], TOKENS)


def _toy(dtype="float32", **keys):
    hf = dict(HF, **keys)
    return hf, smallthinker_config(
        hf, dtype=dtype, remat=True, expert_range=tuple(hf["experts_here"]),
        vocab_size=hf["vocab_here"],
    )


def _model_loss_and_grads(cfg, cp, params, with_stats=False):
    model, meta = build_magi_pattern(cfg, _mesh(cp), CU, chunk_size=CHUNK)
    labels_g = np.roll(TOKEN_IDS, -1)
    labels_g[np.asarray(CU[1:]) - 1] = -1  # a document's last row: no label
    tokens, labels = (
        dispatch(jnp.asarray(a, jnp.int32), meta)[None]
        for a in (TOKEN_IDS, labels_g)
    )
    pos = jnp.asarray(meta.perm_idx)[None]
    (loss, stats), grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn(
            p, tokens, labels, pos, model.sharded_tables(), with_stats=True
        ), has_aux=True,
    ))(params)
    return (float(loss), grads) + ((stats, meta) if with_stats else ())


def _reference(hf, params, **kw):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: reference_smallthinker.smallthinker_loss(
                p, jnp.asarray(TOKEN_IDS, jnp.int32), jnp.asarray(DOC), hf,
                row_block=64, **kw
            )
        )(params)


@pytest.fixture(scope="module")
def params():
    with jax.enable_x64(False):
        return init_pattern_params(jax.random.PRNGKey(7), _toy()[1])


@pytest.fixture(scope="module")
def reference(params):
    with jax.enable_x64(False):
        return _reference(_toy()[0], params)


def test_the_pattern_the_toy_builds(params):
    _hf, cfg = _toy()
    assert (cfg.attn_form, cfg.router_form, cfg.top_k) == (GQA, SOFTMAX, 3)
    assert cfg.layer_types == (FULL, SLIDING) and cfg.rope_kinds == (SLIDING,)
    assert cfg.plan_kinds == (FULL, SLIDING) and cfg.sliding_window == 32
    assert cfg.ffn_types == (EXPERTS,) * 2 and cfg.route_norm
    assert (cfg.router_input, cfg.expert_act) == ("attn", "relu")
    assert (cfg.n_heads, cfg.n_kv_heads) == (14, 2)  # a group of 7
    assert not (cfg.qk_norm or cfg.attn_gate or cfg.post_norms)
    assert not (cfg.n_shared_experts or cfg.tie_embeddings)
    assert cfg.flat_expert_rows and cfg.router_dtype == "float32"
    assert set(params["layers"][0]) == {
        "wq", "wk", "wv", "wo", "attn_norm", "mlp_norm", "w_router",
        "expert_bias", "we_gate", "we_up", "we_down",
    }


# float32 against float32, tolerances as test_pattern_blockdiff.py's: what
# is left is the order of the sums
@pytest.mark.parametrize("cp", [1, 2, 4])
def test_loss_and_every_gradient_match_the_reference(params, reference, cp):
    _hf, cfg = _toy()
    want, want_grads = reference
    with jax.enable_x64(False):
        loss, grads = _model_loss_and_grads(cfg, cp, params)
    assert abs(loss - float(want)) <= 2e-5 * abs(float(want))
    assert _worst(grads, want_grads) <= 2e-4
    for layer in grads["layers"]:
        for name, g in layer.items():
            assert (float(jnp.abs(g).max()) > 0) != (name == "expert_bias"), name


FAULTS = {
    "the window ignored": {"sliding_window": None},
    "rotary on the full layers": {"rope_kinds": (SLIDING, FULL)},
    "the router fed the FFN half's norm": {"router_input": "ffn"},
    "SiLU for ReLU": {"expert_act": "silu"},
    "the softmax not renormalised over the chosen": {"route_norm": False},
    "a bfloat16 router": {"router_dtype": "bfloat16"},
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_seeded_fault_moves_the_loss_or_a_gradient(params, reference, fault):
    """Wrong models of this layer's own kind, each refused by the
    comparison the sound model passes above, by fifty times its limits
    (the structural faults read thousands of times; a bfloat16 router in a
    float32 model flips near-ties and reads 0.12 on a gradient)."""
    _hf, cfg = _toy()
    want, want_grads = reference
    with jax.enable_x64(False):
        loss, grads = _model_loss_and_grads(
            dataclasses.replace(cfg, **FAULTS[fault]), 1, params
        )
    moved = max(
        abs(loss - float(want)) / abs(float(want)) / 2e-5,
        _worst(grads, want_grads) / 2e-4,
    )
    assert moved > 50.0, (fault, moved)


def test_the_route_is_the_references_and_is_made_on_the_attention_input(params):
    """``route`` under SOFTMAX with ``route_norm`` == the reference's
    router (top-k of the logits, the softmax over the chosen); the model's
    choices of a whole forward are the reference's, which routes on the
    attention half's normed input: layer 1's could not be otherwise."""
    hf, cfg = _toy()
    layer = params["layers"][1]
    rng = np.random.default_rng(11)
    with jax.enable_x64(False):
        h = jnp.asarray(rng.standard_normal((96, cfg.dim)), jnp.float32)
        idx, w, r = pattern.route(h, layer, cfg)
        with jax.default_matmul_precision("highest"):
            want_idx, want_w, margins = reference_smallthinker.router(
                h, layer, hf
            )
            _l, (ref_idx, _m) = reference_smallthinker.smallthinker_loss(
                params, jnp.asarray(TOKEN_IDS, jnp.int32), jnp.asarray(DOC),
                hf, with_routing=True, row_block=64,
            )
        np.testing.assert_array_equal(idx, want_idx)
        np.testing.assert_allclose(w, want_w, rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(w.sum(axis=1), 1.0, rtol=1e-5)
        assert r is None and float(margins.max()) == 0.0
        # a forced choice breaks a tie of the size the margin says
        _i, _w, m = reference_smallthinker.router(
            h, layer, hf, jnp.roll(want_idx, 1, axis=0)
        )
        assert float(m.max()) > 0
        _loss, _g, stats, meta = _model_loss_and_grads(
            cfg, 2, params, with_stats=True
        )
    got = np.zeros((2, TOKENS, 3), np.int64)
    got[:, np.asarray(meta.perm_idx)] = np.asarray(stats["expert_idx"])[0]
    assert (np.sort(got, -1) == np.sort(np.asarray(ref_idx), -1)).all()


def test_the_eight_shares_add_up_to_the_uncut_layer(params):
    """Eight ranks of two experts each: their expert outputs sum to the
    uncut layer's (no shared expert to count once), in the program and in
    the reference, and every routed pair is computed on exactly one."""
    hf, cfg = _toy()
    whole = params["layers"][0]
    rng = np.random.default_rng(5)
    with jax.enable_x64(False):
        h = jnp.asarray(rng.standard_normal((64, cfg.dim)), jnp.float32)
        whole = dict(whole, **{
            n: jnp.asarray(
                rng.standard_normal((16, *whole[n].shape[1:])) * 0.1,
                jnp.float32,
            ) for n in ("we_gate", "we_up", "we_down")
        })
        with jax.default_matmul_precision("highest"):
            idx, wts, _m = reference_smallthinker.router(h, whole, hf)
            want = reference_smallthinker.expert_ffn(
                h, (idx, wts), whole, hf, experts_here=(0, 16)
            )
        total, counted = jnp.zeros_like(h), 0
        for first in range(0, 16, 2):
            share_cfg = dataclasses.replace(
                cfg, expert_range=(first, first + 2)
            )
            share = {
                k: v[first:first + 2] if k.startswith("we_") else v
                for k, v in whole.items()
            }
            y, stats = pattern._expert_ffn(
                h, share, share_cfg, pattern._route(h, share, share_cfg)
            )
            counted += int(stats["expert_counts"].sum())
            with jax.default_matmul_precision("highest"):
                part = reference_smallthinker.expert_ffn(
                    h, (idx, wts), share, hf, experts_here=(first, first + 2)
                )
            np.testing.assert_allclose(y, part, rtol=2e-4, atol=2e-5)
            total = total + y
    assert counted == 64 * 3
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("router_input", ["ffn", "attn"])
def test_one_route_a_layer_wherever_it_is_made(params, router_input):
    """The loss program holds one top-k an expert layer in either setting
    (a second would be a second route), the route never leaves its layer,
    and made ahead it stands before the layer's attention kernel."""
    _hf, cfg = _toy()
    cfg = dataclasses.replace(cfg, router_input=router_input, remat=False)
    with jax.enable_x64(False):
        model, _meta = build_magi_pattern(cfg, _mesh(1), CU, chunk_size=CHUNK)
        batch = jnp.zeros((1, TOKENS), jnp.int32)
        jaxpr = jax.make_jaxpr(model.loss_fn)(
            params, batch, batch, batch, model.sharded_tables()
        ).jaxpr
    assert _census(jaxpr)["top_k"] == cfg.n_layers
    assert sum(_kernels_by_name(jaxpr).values()) == cfg.n_layers

    def order(jaxpr, seen):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in ("top_k", "pallas_call"):
                seen.append(eqn.primitive.name)
            else:
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    order(sub, seen)
        return seen

    first = "top_k" if router_input == "attn" else "pallas_call"
    second = "pallas_call" if first == "top_k" else "top_k"
    assert order(jaxpr, []) == [first, second] * cfg.n_layers
    # the layer hands nothing on: no carry among its stats
    x = jnp.zeros((TOKENS, cfg.dim), jnp.float32)
    stats = jax.eval_shape(
        lambda x, layer: pattern._layer_local(
            x, jnp.arange(TOKENS), layer, None, cfg=cfg, layer_type=FULL,
            ffn_type=EXPERTS, tables=model.sharded_tables(),
            plans=model.plans, attn_params=model.attn_params, axis_name="cp",
        )[1], x, params["layers"][0],
    )
    assert set(stats) == {"expert_idx", "expert_counts"}


def test_the_gauge_counts_the_layers_routed_ahead():
    name = "magi_moe_route_ahead_layers"
    _hf, cfg = _toy()
    telemetry.set_enabled(True)
    try:
        with jax.enable_x64(False):
            build_magi_pattern(cfg, _mesh(1), CU, chunk_size=CHUNK)
            ahead = telemetry.snapshot()["gauges"][name]
            build_magi_pattern(
                dataclasses.replace(cfg, router_input="ffn"), _mesh(1), CU,
                chunk_size=CHUNK,
            )
            behind = telemetry.snapshot()["gauges"][name]
    finally:
        telemetry.set_enabled(None)
    assert (ahead, behind) == (2.0, 0.0)


def test_what_the_configuration_does_not_run_is_refused_by_name():
    _hf, cfg = _toy()
    for keys, match in (
        ({"router_input": "before"}, "unknown layer kinds"),
        ({"expert_act": "gelu"}, "unknown layer kinds"),
        ({"router_input": "attn", "router_form": pattern.MLP,
          "router_hidden": 8}, "router_input 'attn'"),
    ):
        with pytest.raises(ValueError, match=match):
            dataclasses.replace(cfg, **keys)
    for keys, match in (
        ({"rope_layout": [1, 1, 1, 1]}, "rope_layout and"),
        ({"sliding_window_layout": [1, 1, 1, 1]}, "rope_layout and"),
        ({"moe_primary_router_apply_softmax": False}, "apply_softmax"),
        ({"num_hidden_layers": 5}, "shorter than"),
    ):
        with pytest.raises(ValueError, match=match):
            _toy(**keys)


def test_the_published_keys_give_the_pattern_and_the_parameters_of_the_cut():
    """``benchmarks/configs/smallthinker-21b-a3b.json`` through
    ``smallthinker_config``: the pattern ISSUE 53 states and its count."""
    with open(os.path.join(
        REPO, "benchmarks", "configs", "smallthinker-21b-a3b.json"
    )) as f:
        hf = json.load(f)
    p = smallthinker_config(
        hf, remat=True, expert_range=tuple(hf["experts_here"]),
        vocab_size=hf["vocab_here"],
    )
    assert (p.dim, p.n_heads, p.n_kv_heads, p.head_dim) == (2560, 28, 4, 128)
    assert p.layer_types == (FULL, SLIDING, SLIDING, SLIDING) * 2
    assert p.plan_kinds == (FULL, SLIDING) and p.rope_kinds == (SLIDING,)
    assert (p.sliding_window, p.rope_theta, p.rms_eps) == (4096, 1.5e6, 1e-6)
    assert p.ffn_types == (EXPERTS,) * 8
    assert (p.n_experts, p.top_k, p.expert_hidden, p.held_experts) == (
        64, 6, 768, (0, 8)
    )
    assert (p.router_form, p.router_input, p.expert_act, p.router_dtype) == (
        SOFTMAX, "attn", "relu", "float32"
    )
    assert (p.n_shared_experts, p.route_norm, p.route_scale) == (0, True, 1.0)
    assert not (p.qk_norm or p.attn_gate or p.post_norms or p.tie_embeddings)
    assert p.flat_expert_rows and p.embed_scale == 1.0
    assert p.vocab_size == 18992
    shapes = jax.eval_shape(
        lambda r: init_pattern_params(r, p), jax.random.PRNGKey(0)
    )
    sizes = {
        jax.tree_util.keystr(k): v.size
        for k, v in jax.tree_util.tree_leaves_with_path(shapes)
    }
    buffers = sum(n for k, n in sizes.items() if "expert_bias" in k)
    layer0 = sum(n for k, n in sizes.items() if k.startswith("['layers'][0]"))
    assert layer0 - 64 == 68_326_400 == (
        2560 * 128 * (2 * 28 + 2 * 4) + 2560 * 64 + 2 * 2560
        + 8 * 3 * 2560 * 768
    )
    # ISSUE 53: 643,852,800 parameters = 10.30 GB at 16 bytes; the
    # selection-bias buffer (64 a layer, no gradient) rides along
    assert sum(sizes.values()) - buffers == 643_852_800 and buffers == 512
    assert "643,852,800" in hf["reduced"]["num_hidden_layers"]
