"""``models/pattern.py`` under diffusion over blocks (SDAR,
``sdar_moe_config``): the doubled sequence ``[noisy ; clean]`` under the
three-slice stepped mask, the softmax router, the head on the noisy half
and the weighted loss, against the plain ``benchmarks/reference_sdar.py``
(float32 on both sides); and the share of an 8-rank deployment."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import masks_blockdiff, reference_sdar
from magiattention_tpu import telemetry
from magiattention_tpu.models import pattern
from magiattention_tpu.models.pattern import (
    EXPERTS, FULL, GQA, SOFTMAX, build_magi_pattern, init_pattern_params,
    sdar_moe_config,
)
from magiattention_tpu.parallel import dispatch
from tests.test_models.pattern_harness import _mesh, _worst

# the published widths in ratio: 8 query heads on 2 key-value heads of 16,
# 16 experts top-4, eight of them here, blocks of 4
HF = dict(
    model_type="sdar_moe", hidden_size=64, intermediate_size=192,
    num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    num_hidden_layers=2, decoder_sparse_step=1, mlp_only_layers=[],
    rope_theta=1e6, rms_norm_eps=1e-6, num_experts=16, num_experts_per_tok=4,
    moe_intermediate_size=32, norm_topk_prob=True, sliding_window=None,
    use_sliding_window=False, tie_word_embeddings=False, vocab_size=512,
    block_length=4, mask_token_id=63, experts_here=[4, 12], vocab_here=64,
)
DOCS, CHUNK = (72, 36, 20), 32  # 128 tokens: 256 rows; 72 and 108 off the grid
TOKENS = sum(DOCS)


def _sdar(dtype="float32", **keys):
    hf = dict(HF, **keys)
    return hf, sdar_moe_config(
        hf, dtype=dtype, remat=True, expert_range=tuple(hf["experts_here"]),
        vocab_size=hf["vocab_here"],
    )


def _mask(block=4):
    return masks_blockdiff.build_mask(
        {"type": "varlen_block_causal", "lengths": list(DOCS)}, TOKENS, block
    )


def _draw(seed=3, t_min=1e-3):
    """A sequence's clean ids, every block's t, the masked tokens, their
    labels and every token's weight 1/t."""
    rng = np.random.default_rng(seed)
    clean = rng.integers(0, HF["mask_token_id"], TOKENS)
    t = np.repeat(rng.uniform(t_min, 1.0, TOKENS // 4), 4)
    masked = rng.uniform(size=TOKENS) < t
    return {
        "clean": clean, "noisy": np.where(masked, HF["mask_token_id"], clean),
        "labels": np.where(masked, clean, -1),
        "weights": (1.0 / t).astype(np.float32),
    }


def _model_batch(meta, d):
    n = TOKENS
    doubled = (
        (np.concatenate([d["noisy"], d["clean"]]), jnp.int32),
        (np.concatenate([d["labels"], np.full(n, -1)]), jnp.int32),
        (np.concatenate([np.arange(n), np.arange(n)]), jnp.int32),
        (np.concatenate([d["weights"], np.zeros(n, np.float32)]), jnp.float32),
    )
    return tuple(
        jax.vmap(lambda x: dispatch(x, meta))(jnp.asarray(a[None], dt))
        for a, dt in doubled
    )


def _reference(hf, params, d, **kw):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: reference_sdar.sdar_loss(
                p, jnp.asarray(d["noisy"], jnp.int32),
                jnp.asarray(d["clean"], jnp.int32),
                jnp.asarray(d["labels"], jnp.int32),
                jnp.asarray(d["weights"]), _mask(), hf, row_block=64, **kw
            )
        )(params)


@pytest.fixture(scope="module")
def params():
    with jax.enable_x64(False):
        return init_pattern_params(jax.random.PRNGKey(7), _sdar()[1])


@pytest.fixture(scope="module")
def reference(params):
    """The plain decoder's loss and gradients on ``_draw()``."""
    with jax.enable_x64(False):
        return _reference(_sdar()[0], params, _draw())


def test_the_pattern_the_toy_builds(params):
    _hf, cfg = _sdar()
    assert (cfg.attn_form, cfg.router_form, cfg.top_k) == (GQA, SOFTMAX, 4)
    assert cfg.layer_types == (FULL,) * 2 and cfg.ffn_types == (EXPERTS,) * 2
    assert cfg.plan_kinds == (FULL,) and cfg.rope_kinds == (FULL,)
    assert (cfg.diffusion_block, cfg.qk_norm, cfg.route_norm) == (4, True, True)
    assert not (cfg.attn_gate or cfg.post_norms or cfg.n_shared_experts)
    assert not cfg.tie_embeddings and params["lm_head"].shape == (64, 64)
    # whole chunks in the held experts' matmuls (ISSUE 42); the embedding
    # at the initializer's 0.02, as every pattern's
    assert cfg.flat_expert_rows
    assert 0.018 < float(jnp.std(params["embed"])) < 0.022
    shapes = {k: v.shape for k, v in params["layers"][0].items()}
    assert shapes["w_router"] == (64, 16) and shapes["we_gate"] == (8, 64, 32)
    assert set(shapes) == {
        "wq", "wk", "wv", "wo", "attn_norm", "mlp_norm", "q_norm", "k_norm",
        "w_router", "expert_bias", "we_gate", "we_up", "we_down",
    }


# float32 against float32, tolerances as test_pattern.py's: what is left
# is the order of the sums (the kernels' online softmax, the grouped
# matmul's row order)
@pytest.mark.parametrize("cp", [1, 2])
def test_loss_and_every_gradient_match_the_reference(params, reference, cp):
    hf, cfg = _sdar()
    d = _draw()
    want, want_grads = reference
    telemetry.set_enabled(True)
    try:
        with jax.enable_x64(False):
            seen = len(telemetry.get_event_buffer().events())
            model, meta = build_magi_pattern(
                cfg, _mesh(cp), list(_mask().cu_seqlens), chunk_size=CHUNK
            )
            (span,) = [
                ev["args"]
                for ev in telemetry.get_event_buffer().events()[seen:]
                if ev["name"] == "plan_flex_attn"
            ]
            tokens, labels, pos, weights = _model_batch(meta, d)
            loss, grads = jax.jit(jax.value_and_grad(
                lambda p: model.loss_fn(
                    p, tokens, labels, pos, model.sharded_tables(), weights
                )
            ))(params)
        gauges = telemetry.snapshot()["gauges"]
    finally:
        telemetry.set_enabled(None)
    assert abs(float(loss) - float(want)) <= 2e-5 * abs(float(want))
    assert _worst(grads, want_grads) <= 2e-4
    for layer in grads["layers"]:
        for name, g in layer.items():
            assert (float(jnp.abs(g).max()) > 0) != (name == "expert_bias"), name
    # the plan is the doubled sequence's, three slices a document
    (plan,), (p,) = model.plans.values(), model.attn_params.values()
    assert meta.total_seqlen == 2 * TOKENS and plan.total_area == _mask().area
    assert p.mask_step == 4
    # a rank's noisy rows: the first half's chunks it was dealt
    rows = model.noisy_rows.rows
    assert rows.shape[0] == cp and (rows >= 0).sum() == TOKENS
    for r in range(cp):
        here = rows[r][rows[r] >= 0]
        assert (np.asarray(meta.position_ids(r))[here] < TOKENS).all()
    # what the plan's span and the gauges say of the mask
    assert (span["mask_step"], span["slices"], span["kind"]) == (4, 9, "full")
    assert span["rectangles"] == 3 * TOKENS // 4 - 3
    assert gauges["magi_mask_step{kind=full}"] == 4.0
    assert 0.0 < gauges["magi_flex_stepped_tile_share"] <= 100.0


@pytest.mark.parametrize("fault", ["every step set to 1", "the logits shifted",
                                   "weights left out", "clean rows see noisy"])
def test_a_seeded_fault_moves_the_loss_or_a_gradient(
    params, reference, fault, monkeypatch
):
    """Wrong models of this layer's own kind, each refused by the
    comparison: a causal mask in place of the staircase; a masked row
    held to the NEXT token; an unweighted loss; one document."""
    from magiattention_tpu.api import functools as api_functools

    hf, cfg = _sdar()
    d = _draw()
    real = api_functools.infer_block_diffusion_mask
    cu = list(_mask().cu_seqlens)
    if fault == "every step set to 1":
        def unstepped(*a, **k):
            q, kk, t = real(*a, **k)
            return q, kk, [x.base for x in t]
        monkeypatch.setattr(api_functools, "infer_block_diffusion_mask", unstepped)
    with jax.enable_x64(False):
        model, meta = build_magi_pattern(cfg, _mesh(1), cu, chunk_size=CHUNK)
        if fault == "clean rows see noisy":
            # the doubled sequence planned as one causal document
            one = dataclasses.replace(cfg, diffusion_block=0)
            other, _ = build_magi_pattern(
                one, _mesh(1), [0, 2 * TOKENS], chunk_size=CHUNK
            )
            model = dataclasses.replace(
                model, plans=other.plans, attn_params=other.attn_params
            )
        wrong = dict(d)
        if fault == "the logits shifted":
            wrong["labels"] = np.where(
                d["labels"] >= 0, np.roll(d["clean"], -1), -1
            )
        if fault == "weights left out":
            wrong["weights"] = np.ones_like(d["weights"])
        tokens, labels, pos, weights = _model_batch(meta, wrong)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: model.loss_fn(
                p, tokens, labels, pos, model.sharded_tables(), weights
            )
        ))(params)
    want, want_grads = reference
    moved = max(
        abs(float(loss) - float(want)) / abs(float(want)) / 1e-3,
        _worst(grads, want_grads) / 6e-2,
    )
    assert moved > 2.0, (fault, moved)


def test_the_softmax_router_alone(params):
    """``route`` under the third ``router_form`` == the reference's
    router: one matrix, a float32 softmax over all experts, the top k,
    renormalised; and it is no sigmoid."""
    hf, cfg = _sdar()
    layer = params["layers"][1]
    rng = np.random.default_rng(11)
    with jax.enable_x64(False):
        h = jnp.asarray(rng.standard_normal((96, cfg.dim)), jnp.float32)
        idx, w, r = pattern.route(h, layer, cfg)
        with jax.default_matmul_precision("highest"):
            want_idx, want_w, margins = reference_sdar.router(h, layer, hf)
        np.testing.assert_array_equal(idx, want_idx)
        np.testing.assert_allclose(w, want_w, rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(w.sum(axis=1), 1.0, rtol=1e-5)
        assert r is None and float(margins.max()) == 0.0
        _i, unnormed, _ = pattern.route(
            h, layer, dataclasses.replace(cfg, route_norm=False)
        )
        scores = jax.nn.softmax(h @ layer["w_router"], axis=-1)
        np.testing.assert_allclose(
            unnormed, jnp.take_along_axis(scores, idx, axis=1), rtol=1e-5
        )
        _i, sig, _ = pattern.route(
            h, layer, dataclasses.replace(cfg, router_form=pattern.SIGMOID)
        )
        assert float(jnp.abs(sig - w).max()) > 1e-2
        # a forced choice breaks a tie of the size the margin says
        forced = jnp.roll(want_idx, 1, axis=0)
        _idx, _w, m = reference_sdar.router(h, layer, hf, forced)
        assert float(m.max()) > 0


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The guide's share test, tied to the model: the expert half as each
    of the 8 ranks that split 128 experts computes it (the model, 16 a
    rank, the router 128 wide and top-8 on all), added up, is the uncut
    reference's layer output; every pair is some rank's, once."""
    hf, cfg = _sdar(
        num_experts=128, num_experts_per_tok=8, experts_here=[0, 128]
    )
    t = 64
    rng = np.random.default_rng(5)
    with jax.enable_x64(False):
        whole = init_pattern_params(jax.random.PRNGKey(2), cfg)["layers"][1]
        h = jnp.asarray(rng.standard_normal((t, cfg.dim)), jnp.float32)
        with jax.default_matmul_precision("highest"):
            want, _routed = reference_sdar.expert_ffn(h, whole, hf)
        total, counted = jnp.zeros_like(want), 0
        for first in range(0, 128, 16):
            share_cfg = dataclasses.replace(cfg, expert_range=(first, first + 16))
            share = {
                k: v[first:first + 16] if k.startswith("we_") else v
                for k, v in whole.items()
            }
            y, stats = pattern._expert_ffn(
                h, share, share_cfg, pattern._route(h, share, share_cfg)
            )
            counted += int(stats["expert_counts"].sum())
            # the reference's own share, the same rank
            part, _ = reference_sdar.expert_ffn(
                h, share, hf, experts_here=(first, first + 16)
            )
            np.testing.assert_allclose(y, part, rtol=2e-4, atol=2e-5)
            total = total + y
    assert counted == t * 8
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


def _scatter_adds_into(jaxpr, shape) -> int:
    """The ``scatter-add`` equations of ``jaxpr``, its sub-jaxprs too,
    whose operand has ``shape``."""
    found = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scatter-add":
            found += eqn.invars[0].aval.shape == shape
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _scatter_adds_into(sub, shape)
    return found


@pytest.mark.parametrize("to_held", [0, 1, 4], ids="held{}of4".format)
def test_every_chunk_runs_whatever_the_router_sends_here(
    params, to_held, monkeypatch
):
    """Under ``flat_expert_rows`` past top-2 (top-4 here: two chunks of
    ``2 t`` pair rows) a step's work does not follow the router: the
    program has no ``cond``, every grouped matmul of both chunks takes
    ``2 t`` rows whether the rows choose no held expert, one or four, and
    the output and the gradients are those of the form that follows the
    pairs (which skips the second chunk unless a pair reaches it). The
    rows here move by the sort's permutation: no ``[t, dim]`` operand is
    scatter-added into, forward or backward, where the other form's every
    chunk scatter-adds into two."""
    _hf, flat = _sdar()
    plain = dataclasses.replace(flat, flat_expert_rows=False)
    t = 48
    rng = np.random.default_rng(13)
    first, last = flat.held_experts
    # every row chooses ``to_held`` held experts and the rest elsewhere
    ids = np.r_[first:first + to_held, 0:4 - to_held]
    idx = jnp.asarray(np.tile(ids, (t, 1)), jnp.int32)
    seen = []
    ragged_dot = jax.lax.ragged_dot

    def spy(x, w, sizes, **kw):
        jax.debug.callback(lambda n: seen.append(int(n)), sizes.sum())
        return ragged_dot(x, w, sizes, **kw)

    with jax.enable_x64(False):
        layer = params["layers"][1]
        h = jnp.asarray(rng.standard_normal((t, flat.dim)), jnp.float32)
        w = jnp.asarray(rng.uniform(0.1, 1.0, (t, 4)), jnp.float32)

        def out(cfg, h, layer):
            y, counts = pattern.held_expert_ffn(h, idx, w, layer, cfg)
            return jnp.sum(y * jnp.cos(y)), (y, counts)

        got = jax.value_and_grad(out, (1, 2), has_aux=True)(flat, h, layer)
        want = jax.value_and_grad(out, (1, 2), has_aux=True)(plain, h, layer)
        text = {
            cfg.flat_expert_rows: str(jax.make_jaxpr(
                lambda h: pattern.held_expert_ffn(h, idx, w, layer, cfg)[0]
            )(h))
            for cfg in (flat, plain)
        }
        assert " cond[" not in text[True] and " cond[" in text[False]
        into_rows = {
            cfg.flat_expert_rows: _scatter_adds_into(
                jax.make_jaxpr(
                    jax.grad(lambda h: out(cfg, h, layer)[0])
                )(h).jaxpr,
                (t, flat.dim),
            )
            for cfg in (flat, plain)
        }
        assert into_rows == {True: 0, False: 4}  # y and dh, two chunks
        monkeypatch.setattr(jax.lax, "ragged_dot", spy)
        _, (_, counts) = out(flat, h, layer)
        jax.effects_barrier()
    assert int(counts.sum()) == t * to_held
    assert seen == [2 * t] * 6  # gate, up and down of two chunks
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_the_scopes_the_doubled_input_sets(params):
    _hf, cfg = _sdar()
    with jax.enable_x64(False):
        model, _meta = build_magi_pattern(
            cfg, _mesh(1), list(_mask().cu_seqlens), chunk_size=CHUNK
        )
        batch = jnp.zeros((1, 2 * TOKENS), jnp.int32)
        text = jax.jit(model.loss_fn).lower(
            params, batch, batch, batch, model.sharded_tables(),
            jnp.zeros((1, 2 * TOKENS), jnp.float32),
        ).as_text(debug_info=True)
    # a cross-cut over the embedding and the head, as magi_mtp is
    for scope in ("magi_diffusion_io/magi_embed", "magi_diffusion_io/magi_head",
                  "magi_head/magi_diffusion_io", "magi_moe_router",
                  "magi_attn_full", "magi_proj"):
        assert scope in text, scope
    assert not [
        line for line in text.splitlines()
        if "magi_diffusion_io" in line and "pallas_call" in line
    ]


def test_what_the_configuration_and_the_builder_refuse(params):
    _hf, cfg = _sdar()
    for field in ({"sliding_window": 64}, {"n_mtp": 1}, {"n_loops": 2}):
        with pytest.raises(ValueError, match="diffusion over blocks under"):
            dataclasses.replace(cfg, **field)
    with pytest.raises(ValueError, match="dense layers is not built"):
        _sdar(mlp_only_layers=[0])
    with pytest.raises(ValueError, match="window is not built"):
        _sdar(use_sliding_window=True)
    with jax.enable_x64(False):
        with pytest.raises(ValueError, match="no whole number of chunks"):
            build_magi_pattern(cfg, _mesh(1), [0, 72, 108, 128], chunk_size=48)
        with pytest.raises(ValueError, match="whole number of blocks of 4"):
            build_magi_pattern(cfg, _mesh(1), [0, 70, 128], chunk_size=CHUNK)
        model, meta = build_magi_pattern(
            cfg, _mesh(1), list(_mask().cu_seqlens), chunk_size=CHUNK
        )
        batch = jnp.zeros((1, 2 * TOKENS), jnp.int32)
        with pytest.raises(ValueError, match="per-row weights go with diffusion"):
            model.loss_fn(params, batch, batch, batch, model.sharded_tables())
