"""The pattern-driven decoder (models/pattern.py) against the plain
float32 AFMoE reference of ``benchmarks/`` on seeded random weights, at
toy size on the CPU: loss and every parameter's gradient through the
normal path (dispatch, shard_map, dist_attn_local, a plan per attention
kind on one dispatch), at cp=1 and at cp=4 on emulated devices."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_afmoe
from magiattention_tpu import telemetry
from magiattention_tpu.models import LlamaConfig, build_magi_llama, init_params
from magiattention_tpu.models import pattern
from magiattention_tpu.models.pattern import (
    DENSE, EXPERTS, FULL, SLIDING, afmoe_config,
    build_magi_pattern, held_expert_ffn, init_pattern_params, llama_pattern,
)
from magiattention_tpu.parallel import dispatch, roll
from tests.test_models.pattern_harness import (
    CHUNK, CU, DOCS, TOTAL, WINDOW, _allow_full, _mesh, _model_loss_and_grads,
    _pin, _worst, computed_once, unfaulted_loss_and_grads,
)

HF = dict(
    hidden_size=64, intermediate_size=96, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, num_hidden_layers=3,
    layer_types=[SLIDING, SLIDING, FULL], num_dense_layers=1,
    sliding_window=WINDOW, rope_theta=10000.0, rms_norm_eps=1e-5,
    mup_enabled=True, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=32, num_shared_experts=1, route_norm=True,
    route_scale=2.826, vocab_size=256, experts_here=[2, 6], vocab_here=64,
)
CFG = afmoe_config(
    HF, dtype="float32", remat=True, expert_range=(2, 6), vocab_size=64
)


@computed_once
def _reference(params, tokens_g):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: reference_afmoe.afmoe_loss(
                p, jnp.asarray(tokens_g, jnp.int32),
                jnp.asarray(np.roll(tokens_g, -1), jnp.int32),
                _allow_full(), HF,
            )
        )(params)



@pytest.fixture(scope="module")
def params():
    with jax.enable_x64(False):
        return init_pattern_params(jax.random.PRNGKey(7), CFG)


# float32 against float32: what is left is the order of the sums (the
# kernels' online softmax, the grouped matmul's row order); 2e-4 is two
# orders over what the runs read (1e-6) and three under any fault
@pytest.mark.parametrize("cp", [1, 4])
def test_loss_and_every_gradient_match_the_reference(params, cp):
    with jax.enable_x64(False):
        loss, grads, tokens_g, _model, _meta = unfaulted_loss_and_grads(
            CFG, cp, params
        )
        want, want_grads = _reference(params, tokens_g)
    assert abs(loss - float(want)) <= 2e-5 * abs(float(want))
    assert _worst(grads, want_grads) <= 2e-4


FAULTS = {
    "sliding layers given the global mask": {"sliding_window": None},
    "global layers given rotary": {"rope_kinds": (SLIDING, FULL)},
    "the gate left out": {"attn_gate": False},
    "route_norm left out": {"route_norm": False},
    "every layer sliding": {"layer_types": (SLIDING,) * 3},
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_seeded_fault_moves_a_gradient(params, fault):
    """The same comparison fails by orders for a wrong model (float32
    on both sides, so nothing but the fault differs)."""
    with jax.enable_x64(False):
        cfg = dataclasses.replace(CFG, **FAULTS[fault])
        _loss, grads, tokens_g, _model, _meta = _model_loss_and_grads(
            cfg, 1, params
        )
        _want, want_grads = _reference(params, tokens_g)
    assert _worst(grads, want_grads) > 0.1, fault


def test_one_dispatch_and_a_plan_per_kind(monkeypatch):
    from magiattention_tpu.meta import dispatch_meta

    solves = []
    real = dispatch_meta.make_dispatch_meta_from_qk_ranges
    monkeypatch.setattr(
        dispatch_meta, "make_dispatch_meta_from_qk_ranges",
        lambda *a, **k: solves.append(1) or real(*a, **k),
    )
    telemetry.set_enabled(True)
    reg = telemetry.get_registry()
    reg.clear_metric("magi_model_attn_plans_total")
    try:
        model, meta = build_magi_pattern(CFG, _mesh(4), CU, chunk_size=CHUNK)
        counted = {
            k: reg.counter_value("magi_model_attn_plans_total", kind=k)
            for k in ("full", "sliding")
        }
        spans = [
            e["args"].get("kind") for e in telemetry.get_event_buffer().events()
            if e["name"] == "plan_flex_attn"
        ]
    finally:
        reg.clear_metric("magi_model_attn_plans_total")
        telemetry.set_enabled(None)
    assert len(solves) == 1
    assert counted == {"full": 1.0, "sliding": 1.0}
    assert spans[-2:] == ["full", "sliding"]
    assert set(model.plans) == {FULL, SLIDING}
    full, sliding = model.plans[FULL], model.plans[SLIDING]
    # one dispatch: the same shard, the same permutation for both plans
    assert (full.cp_size, full.shard_q_len) == (4, sliding.shard_q_len)
    assert sorted(np.asarray(meta.perm_idx).tolist()) == list(range(TOTAL))
    # and each plan's area is its own mask's
    tri = lambda n: n * (n + 1) // 2  # noqa: E731
    assert full.total_area == sum(tri(n) for n in DOCS)
    assert sliding.total_area == sum(
        tri(n) if n <= WINDOW else tri(WINDOW) + (n - WINDOW) * WINDOW
        for n in DOCS
    )


@pytest.mark.parametrize(
    "fields,kinds",
    [({"layer_types": (FULL,) * 3}, {FULL}),
     ({"layer_types": (SLIDING,) * 3}, {SLIDING}),
     ({"sliding_window": None}, {FULL})],
)
def test_a_pattern_of_one_kind_builds_one_plan(fields, kinds):
    cfg = dataclasses.replace(CFG, **fields)
    model, _meta = build_magi_pattern(cfg, _mesh(1), CU, chunk_size=CHUNK)
    assert set(model.plans) == kinds == set(model.attn_params)


def _dense_experts(h, idx, w, layer, e0, e1):
    y = jnp.zeros((h.shape[0], layer["we_down"].shape[-1]), jnp.float32)
    for e in range(e0, e1):
        w_e = jnp.where(idx == e, w, 0.0).sum(axis=1)
        j = e - e0
        y = y + w_e[:, None] * reference_afmoe._swiglu(
            h, layer["we_gate"][j], layer["we_up"][j], layer["we_down"][j]
        )
    return y


ROWS_PERMUTED = "magi_moe_rows_permuted_total"


@pytest.mark.parametrize("flat", [False, True], ids=["follows", "flat"])
@pytest.mark.parametrize(
    "held,k,case",
    [((2, 6), 2, "uneven"), ((0, 8), 2, "every pair here"),
     ((2, 6), 2, "no pair here"), ((0, 8), 5, "a second chunk runs"),
     ((2, 5), 5, "a second chunk is skipped")],
)
def test_the_expert_layer_alone(held, k, case, flat):
    """Against a plain loop over the held experts, value and gradients,
    with one expert taking most pairs and one none; with every pair held
    here; with none; and at top-5, where the pairs fill three chunks of
    2 t rows (the last one padded) and the held ones reach the second
    (every expert held) or stop in the first. In both forms of row
    movement: a chunk's own gather and scatter-add where the chunks
    follow the pairs, the sort's permutation and its inverse where every
    chunk runs (``flat_expert_rows``)."""
    t = 96
    cfg = dataclasses.replace(CFG, expert_range=held, flat_expert_rows=flat)
    rng = np.random.default_rng(11)
    with jax.enable_x64(False):
        layer = init_pattern_params(jax.random.PRNGKey(1), cfg)["layers"][1]
        h = jnp.asarray(rng.standard_normal((t, cfg.dim)), jnp.float32)
        if case == "no pair here":
            idx = np.tile([0, 7], (t, 1))
        elif k == 5:
            idx = np.stack([rng.permutation(8)[:k] for _ in range(t)])
        else:
            # expert 3 takes most tokens, expert 4 none
            first = np.where(rng.random(t) < 0.8, 3, rng.choice([0, 2, 5, 6, 7], t))
            second = np.where(first == 1, 0, 1)
            idx = np.stack([first, second], axis=1)
        idx = jnp.asarray(idx, jnp.int32)
        w = jnp.asarray(rng.random((t, k)), jnp.float32)

        def got(h, w, layer):
            return held_expert_ffn(h, idx, w, layer, cfg)[0]

        def want(h, w, layer):
            return _dense_experts(h, idx, w, layer, *held)

        probe = jnp.asarray(rng.standard_normal((t, cfg.dim)), jnp.float32)
        outs = []
        reg = telemetry.get_registry()
        telemetry.set_enabled(True)
        reg.clear_metric(ROWS_PERMUTED)
        try:
            for fn in (got, want):
                y, grads = jax.value_and_grad(
                    lambda *a: (fn(*a) * probe).sum(), argnums=(0, 1, 2)
                )(h, w, layer)
                outs.append((fn(h, w, layer), grads))
            permuted = {
                end: int(reg.counter_value(ROWS_PERMUTED, end=end))
                for end in ("dispatch", "combine")
            }
        finally:
            reg.clear_metric(ROWS_PERMUTED)
            telemetry.set_enabled(False)
        counts = np.asarray(held_expert_ffn(h, idx, w, layer, cfg)[1])
    # one expert layer differentiated once: each end of the path counted
    # where the rule is traced, and only where every chunk runs
    assert permuted == dict.fromkeys(("dispatch", "combine"), int(flat))
    chosen = np.asarray(idx).ravel()
    assert counts.tolist() == [
        int((chosen == e).sum()) for e in range(*held)
    ]
    if case == "uneven":
        assert counts.max() > 4 * max(counts.mean(), 1) * 0.5 and 0 in counts
    if case == "every pair here":
        assert counts.sum() == t * k > t
    if k == 5:
        assert (counts.sum() > 2 * t) == (case == "a second chunk runs")
    np.testing.assert_allclose(outs[0][0], outs[1][0], rtol=1e-4, atol=1e-5)
    assert _worst(outs[0][1], outs[1][1]) <= 1e-4 or case == "no pair here"
    if case == "no pair here":
        assert not np.asarray(outs[0][0]).any()


@pytest.mark.parametrize("k,rows", [(2, 24), (5, 24), (1, 12)])
def test_the_two_row_movements_are_each_others_transpose(k, rows):
    """``_token_rows`` and ``_slot_sums``, the two ends of the expert path
    where every chunk runs, on a random permutation of ``t k`` pairs in
    whole chunks of ``rows`` (a padded tail at top-5): the second sums
    back, ``top_k`` times over, what the first laid out, each is jax's own
    transpose of the other (what ``_experts_on_sorted_rows``'s backward
    relies on), and a token's number a pair (the weights) goes the same
    way as its row."""
    t, dim = 12, 8
    n = t * k
    rng = np.random.default_rng(5)
    with jax.enable_x64(False):
        order = jnp.asarray(rng.permutation(n), jnp.int32)
        inv = jnp.argsort(order).astype(jnp.int32)
        order = jnp.pad(order, (0, -n % rows))
        tok = order // k
        n_rows = order.shape[0]
        assert (n_rows > n) == (k == 5)
        h = jnp.asarray(rng.standard_normal((t, dim)), jnp.float32)
        laid = jnp.asarray(rng.standard_normal((n_rows, dim)), jnp.float32)
        w = jnp.asarray(rng.standard_normal(n), jnp.float32)

        xs, out_vjp = jax.vjp(lambda h: pattern._token_rows(h, tok, n), h)
        back, back_vjp = jax.vjp(
            lambda r: pattern._slot_sums(r, inv, t), laid
        )
        np.testing.assert_array_equal(xs[:n], h[tok[:n]])
        assert xs.shape == laid.shape and not np.asarray(xs[n:]).any()
        np.testing.assert_allclose(
            back, laid[inv].reshape(t, k, dim).sum(1), rtol=1e-6, atol=1e-6
        )
        # the pairs' rows, summed back a token, are top_k times its row
        np.testing.assert_allclose(
            pattern._slot_sums(xs, inv, t), k * h, rtol=1e-6, atol=1e-6
        )
        g_xs = jnp.asarray(rng.standard_normal(xs.shape), jnp.float32)
        g_back = jnp.asarray(rng.standard_normal(back.shape), jnp.float32)
        np.testing.assert_allclose(
            pattern._slot_sums(g_xs, inv, t), out_vjp(g_xs)[0],
            rtol=1e-6, atol=1e-6,
        )
        np.testing.assert_array_equal(
            pattern._token_rows(g_back, tok, n), back_vjp(g_back)[0]
        )
        # a pair's weight: each pair its own token, one slot
        ws, ws_vjp = jax.vjp(lambda w: pattern._token_rows(w, order, n), w)
        np.testing.assert_array_equal(ws[:n], w[order[:n]])
        assert not np.asarray(ws[n:]).any()
        g_ws = jnp.asarray(rng.standard_normal(n_rows), jnp.float32)
        np.testing.assert_array_equal(ws_vjp(g_ws)[0], g_ws[inv])


def test_llama_is_a_pattern_with_the_extras_off():
    """(full, dense) with rotary everywhere and no AFMoE extra is
    models/llama.py's layer: the same loss on the same parameters."""
    lcfg = LlamaConfig(
        vocab_size=64, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=16, ffn_hidden=96, dtype="float32",
    )
    mesh = _mesh(2)
    with jax.enable_x64(False):
        params = init_params(jax.random.PRNGKey(0), lcfg)
        from magiattention_tpu.api import infer_attn_mask_from_cu_seqlens

        llama, meta = build_magi_llama(
            lcfg, mesh, TOTAL, *infer_attn_mask_from_cu_seqlens(CU),
            chunk_size=CHUNK,
        )
        pat, meta2 = build_magi_pattern(
            llama_pattern(lcfg), mesh, CU, chunk_size=CHUNK
        )
        assert meta.partitions == meta2.partitions
        tokens = jax.vmap(lambda x: dispatch(x, meta))(
            jnp.asarray(
                np.random.default_rng(0).integers(0, 64, (2, TOTAL)), jnp.int32
            )
        )
        labels = roll(tokens, meta, -1, axis=1, mesh=mesh, cp_axis="cp")
        pos = jnp.broadcast_to(jnp.asarray(meta.perm_idx)[None], tokens.shape)
        a = jax.jit(llama.loss_fn)(
            params, tokens, labels, pos, llama.sharded_tables()
        )
        b = jax.jit(pat.loss_fn)(
            params, tokens, labels, pos, pat.sharded_tables()
        )
    assert abs(float(a) - float(b)) <= 1e-6 * abs(float(a))


def test_the_scopes_name_the_kind_of_layer(params):
    """A device trace tells the sliding layers' kernels from the global
    layer's and from the experts by these scopes."""
    with jax.enable_x64(False):
        model, meta = build_magi_pattern(CFG, _mesh(1), CU, chunk_size=CHUNK)
        batch = jnp.zeros((1, TOTAL), jnp.int32)
        text = jax.jit(model.loss_fn).lower(
            params, batch, batch, batch, model.sharded_tables()
        ).as_text(debug_info=True)
    for scope in ("magi_attn_sliding", "magi_attn_full", "magi_moe_router",
                  "magi_moe_experts", "magi_moe_shared"):
        assert scope in text, scope


def test_expert_load_gauges():
    telemetry.set_enabled(True)
    reg = telemetry.get_registry()
    try:
        pattern.MagiPattern.record_expert_load(None, [[6, 2, 0, 0], [1, 1, 1, 1]])
        got = {
            (name, layer): reg.gauge_value(name, layer=layer)
            for name in ("magi_moe_pairs_here", "magi_moe_load_max_over_mean")
            for layer in (0, 1)
        }
    finally:
        reg.clear_metric("magi_moe_pairs_here")
        reg.clear_metric("magi_moe_load_max_over_mean")
        telemetry.set_enabled(None)
    assert got == {
        ("magi_moe_pairs_here", 0): 8.0, ("magi_moe_pairs_here", 1): 4.0,
        ("magi_moe_load_max_over_mean", 0): 3.0,
        ("magi_moe_load_max_over_mean", 1): 1.0,
    }


def test_config_rejects_unknown_kinds():
    with pytest.raises(ValueError, match="unknown layer kinds"):
        dataclasses.replace(CFG, layer_types=("window", SLIDING, FULL))
    with pytest.raises(ValueError, match="differ in length"):
        dataclasses.replace(CFG, ffn_types=(DENSE, EXPERTS))


# ---------------------------------------------------------------------------
# what the second attention form and the module may not move
# (the latent form itself: test_pattern_latent.py)
# ---------------------------------------------------------------------------


# equations by primitive of the two loss-and-gradient programs, read from
# the commit before the second attention form (PR 29's tree; ``_pin``)
TRINITY_CENSUS = {
    "add": 136, "add_any": 82, "and": 10, "broadcast_in_dim": 392,
    "concatenate": 10, "convert_element_type": 94, "cos": 8, "cumsum": 4,
    "div": 106, "dot_general": 107, "dynamic_slice": 4, "exp": 1, "gather":
    20, "ge": 5, "integer_pow": 2, "iota": 18, "jit": 125, "log": 1,
    "logistic": 22, "lt": 38, "max": 18, "min": 12, "mul": 413, "ne": 12,
    "neg": 9, "pad": 13, "pow": 8, "psum": 59, "ragged_dot_general": 30,
    "reduce_max": 1, "reduce_sum": 148, "rem": 6, "remat2": 7, "reshape": 94,
    "reshard": 1, "rsqrt": 37, "scatter-add": 16, "select_n": 89, "shard_map":
    2, "sign": 12, "sin": 8, "slice": 24, "sort": 4, "split": 4, "squeeze":
    10, "stop_gradient": 5, "sub": 40, "top_k": 4, "transpose": 33,
}
LLAMA_CENSUS = {
    "add": 21, "add_any": 35, "broadcast_in_dim": 126, "concatenate": 4,
    "convert_element_type": 8, "cos": 4, "div": 26, "dot_general": 45, "exp":
    1, "gather": 2, "ge": 1, "iota": 4, "jit": 16, "log": 1, "logistic": 2,
    "lt": 2, "max": 2, "mul": 108, "neg": 7, "pad": 9, "pow": 4, "psum": 27,
    "reduce_max": 1, "reduce_sum": 39, "reshape": 28, "reshard": 1, "rsqrt":
    5, "scatter-add": 2, "select_n": 5, "shard_map": 2, "sin": 4, "slice": 12,
    "split": 4, "squeeze": 5, "stop_gradient": 1, "sub": 8, "transpose": 15,
}


def test_trinitys_tree_and_program_are_what_they_were(params, monkeypatch):
    shapes, census = _pin(CFG, params, monkeypatch)
    expert = {
        "attn_norm": (64,), "mlp_norm": (64,), "post_attn_norm": (64,),
        "post_mlp_norm": (64,), "q_norm": (16,), "k_norm": (16,),
        "wq": (64, 64), "wk": (64, 32), "wv": (64, 32), "wo": (64, 64),
        "w_attn_gate": (64, 64), "w_router": (64, 8), "expert_bias": (8,),
        "we_gate": (4, 64, 32), "we_up": (4, 64, 32), "we_down": (4, 32, 64),
        "ws_gate": (64, 32), "ws_up": (64, 32), "ws_down": (32, 64),
    }
    assert {
        k.removeprefix("['layers'][2]"): v for k, v in shapes.items()
        if k.startswith("['layers'][2]")
    } == {f"['{k}']": v for k, v in expert.items()}
    assert {k: v for k, v in shapes.items() if "layers" not in k} == {
        "['embed']": (64, 64), "['final_norm']": (64,), "['lm_head']": (64, 64),
    }
    assert len(shapes) == 55
    assert census == TRINITY_CENSUS


def test_llama_patterns_tree_and_program_are_what_they_were(monkeypatch):
    lcfg = LlamaConfig(
        vocab_size=64, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=16, ffn_hidden=96, dtype="float32",
    )
    with jax.enable_x64(False):
        params = init_params(jax.random.PRNGKey(0), lcfg)
    shapes, census = _pin(llama_pattern(lcfg), params, monkeypatch)
    assert {
        k.removeprefix("['layers'][1]"): v for k, v in shapes.items()
        if k.startswith("['layers'][1]")
    } == {
        "['attn_norm']": (64,), "['mlp_norm']": (64,), "['wq']": (64, 64),
        "['wk']": (64, 32), "['wv']": (64, 32), "['wo']": (64, 64),
        "['w_gate']": (64, 96), "['w_up']": (64, 96), "['w_down']": (96, 64),
    }
    assert len(shapes) == 21
    assert census == LLAMA_CENSUS
