"""Compressed convolutional attention and the MLP router
(``models/pattern.py`` at ``attn_form="cca"``: ZAYA1's layer) against the
plain float32 reference of ``benchmarks/`` on seeded random weights, at
toy size on the CPU, through the normal path: dispatch, shard_map, the
documents' forward shift, ``dist_attn_local``, the router's state from
layer to layer."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from benchmarks import reference_zaya
from magiattention_tpu import telemetry
from magiattention_tpu.models import pattern
from magiattention_tpu.models.pattern import (
    CCA, EXPERTS, FULL, MLP, build_magi_pattern, init_pattern_params,
    zaya_config,
)
from magiattention_tpu.parallel import dispatch, undispatch
from magiattention_tpu.utils.compat import shard_map
from tests.test_models.pattern_harness import (
    CHUNK, CU, DOCS, TOTAL, _allow_full, _mesh, _model_loss_and_grads, _worst,
    computed_once,
)

# the published widths in ratio: 8 query heads on 2 key-value heads, both
# kernels 2, rotary on half a head, 8 experts top-1, four of them here
HF = dict(
    hidden_size=64, num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    num_hidden_layers=3, layer_types=["hybrid"] * 3, cca_time0=2, cca_time1=2,
    rms_norm_eps=1e-5, num_experts=8, num_experts_per_tok=1,
    moe_intermediate_size=32, router_hidden_size=24,
    rope_parameters={"hybrid": {
        "partial_rotary_factor": 0.5, "rope_theta": 5e6, "rope_type": "default",
    }},
    sliding_window=None, tie_word_embeddings=True, vocab_size=256,
    experts_here=[2, 6], vocab_here=64,
)


def _zaya(dtype="float32", **keys):
    hf = dict(HF, **keys)
    return hf, zaya_config(
        hf, dtype=dtype, remat=True, expert_range=tuple(hf["experts_here"]),
        vocab_size=hf["vocab_here"],
    )


@computed_once
def _reference(hf, params, tokens_g, **kw):
    toks = jnp.asarray(tokens_g, jnp.int32)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: reference_zaya.zaya_loss(
                p, toks, jnp.roll(toks, -1), _allow_full(), hf, **kw
            )
        )(params)


@pytest.fixture(scope="module")
def params():
    with jax.enable_x64(False):
        return init_pattern_params(jax.random.PRNGKey(7), _zaya()[1])


def test_the_pattern_the_toy_builds(params):
    _hf, cfg = _zaya()
    assert (cfg.attn_form, cfg.router_form, cfg.top_k) == (CCA, MLP, 1)
    assert cfg.layer_types == (FULL,) * 3 and cfg.ffn_types == (EXPERTS,) * 3
    assert (cfg.conv_taps, cfg.rope_head_dim, cfg.shift_taps) == (
        (2, 2), 8, (1, 2)
    )
    assert cfg.tie_embeddings and "lm_head" not in params
    shapes = {k: v.shape for k, v in params["layers"][0].items()}
    assert shapes["cca_conv1_w"] == (2, 160) and shapes["cca_temp"] == (2,)
    assert shapes["cca_conv2_w"] == (2, 10, 16, 16)
    assert shapes["w_router_down"] == (64, 24) and shapes["w_router"] == (24, 8)
    assert shapes["we_gate"] == (4, 64, 32) and shapes["wo"] == (128, 64)


# float32 against float32, tolerances as test_pattern.py's: what is left
# is the order of the sums (the kernels' online softmax, the grouped
# matmul's row order)
@pytest.mark.parametrize("cp", [1, 2, 4])
def test_loss_and_every_gradient_match_the_reference(params, cp):
    hf, cfg = _zaya()
    telemetry.set_enabled(True)
    reg = telemetry.get_registry()
    reg.clear_metric("magi_shift_remote_rows_total")
    try:
        with jax.enable_x64(False):
            loss, grads, tokens_g, model, meta = _model_loss_and_grads(
                cfg, cp, params  # run here: the counter counts this build
            )
            want, want_grads = _reference(hf, params, tokens_g)
        crossed = reg.counter_value("magi_shift_remote_rows_total")
    finally:
        reg.clear_metric("magi_shift_remote_rows_total")
        telemetry.set_enabled(None)
    assert abs(loss - float(want)) <= 2e-5 * abs(float(want))
    assert _worst(grads, want_grads) <= 2e-4
    # every parameter the layer brings is live but the first layer's
    # weight on the state before it, which is zero
    for i, layer in enumerate(grads["layers"]):
        for name, g in layer.items():
            live = float(jnp.abs(g).max()) > 0
            dead = name == "expert_bias" or (name == "router_gamma" and i == 0)
            assert live != dead, (i, name)
    # past cp = 1 the dispatch puts neighbouring chunks on different
    # ranks and the shift brings their last rows across
    chunk_rank = np.empty(meta.num_chunks, int)
    for rank, chunks in enumerate(meta.partitions):
        chunk_rank[list(chunks)] = rank
    assert (chunk_rank[1:] != chunk_rank[:-1]).any() == (cp > 1)
    assert crossed == model.shift_plan.remote_rows
    assert (crossed > 0) == (cp > 1)


def _leak_one_row(x, tables, plan, axis_name):
    """The shift with its documents forgotten: a plain roll."""
    return tuple(jnp.roll(x, j, axis=0) for j in plan.taps)


FAULTS = {
    "a shift that leaks a row across a document": "leak",
    "the value read from its own token": "value",
    "the router's state dropped between layers": "state",
    "rotary on the whole head": {"rope_head_dim": 16},
    "a bfloat16 router": {"router_dtype": "bfloat16"},
    "sigmoid scores for softmax": "sigmoid",
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_seeded_fault_moves_a_gradient(params, fault, monkeypatch):
    """The same comparison fails by orders for a wrong model (float32 on
    both sides but for the router's dtype, so nothing but the fault
    differs)."""
    hf, cfg = _zaya()
    what = FAULTS[fault]
    if isinstance(what, dict):
        cfg = dataclasses.replace(cfg, **what)
    elif what == "leak":
        monkeypatch.setattr(pattern, "shift_local", _leak_one_row)
    elif what == "value":
        real = pattern._cca_mix

        def own_token(q, k, v, layer, cfg, shift):
            got_q, got_k, _v = real(q, k, v, layer, cfg, shift)
            return got_q, got_k, v.reshape(_v.shape)

        monkeypatch.setattr(pattern, "_cca_mix", own_token)
    elif what == "state":
        real = pattern._router_scores
        monkeypatch.setattr(
            pattern, "_router_scores",
            lambda h, r, layer, cfg: real(h, jnp.zeros_like(r), layer, cfg),
        )
    elif what == "sigmoid":
        monkeypatch.setattr(jax.nn, "softmax", lambda x, axis=-1: jax.nn.sigmoid(x))
    with jax.enable_x64(False):
        _loss, grads, tokens_g, _model, _meta = _model_loss_and_grads(
            cfg, 1, params
        )
        monkeypatch.undo()
        _want, want_grads = _reference(hf, params, tokens_g)
    floor = 5e-3 if fault.startswith("a bfloat16") else 0.05
    assert _worst(grads, want_grads) > floor, fault


def _hidden(cfg, cp, params, tokens_g):
    """The trunk's output (before the final norm) in global order."""
    mesh = _mesh(cp)
    model, meta = build_magi_pattern(cfg, mesh, CU, chunk_size=CHUNK)
    tables = model.sharded_tables()
    batch = P("dp", "cp")

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(
            P(), batch, batch,
            {k: (P("cp"),) * len(v) for k, v in tables.items()},
        ),
        out_specs=batch, check_vma=False,
    )
    def local(params, tok, pos, tabs):
        x, _stats = pattern._trunk_local(
            params, tok[0], pos[0], cfg, tabs, model.plans,
            model.attn_params, "cp", model.shift_plan,
        )
        return x[None]

    tokens = dispatch(jnp.asarray(tokens_g, jnp.int32), meta)[None]
    pos = jnp.asarray(meta.perm_idx)[None]
    return np.asarray(
        undispatch(jax.jit(local)(params, tokens, pos, tables)[0], meta)
    )


@pytest.mark.parametrize("cp", [1, 4])
def test_no_document_reads_another(params, cp):
    """Every token of the middle document changed: the hidden states of
    the documents before and after it are bit-equal (the convolutions,
    the value's shift and the attention stay inside a document, through
    every layer)."""
    _hf, cfg = _zaya()
    rng = np.random.default_rng(11)
    tokens = rng.integers(0, 64, TOTAL)
    other = tokens.copy()
    lo, hi = CU[1], CU[2]
    other[lo:hi] = (tokens[lo:hi] + 1 + rng.integers(0, 62, hi - lo)) % 64
    assert (other[lo:hi] != tokens[lo:hi]).all()
    with jax.enable_x64(False):
        a = _hidden(cfg, cp, params, tokens)
        b = _hidden(cfg, cp, params, other)
    assert np.isfinite(a).all()
    np.testing.assert_array_equal(a[:lo], b[:lo])
    np.testing.assert_array_equal(a[hi:], b[hi:])
    assert (a[lo:hi] != b[lo:hi]).any(axis=1).all()


def test_the_two_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the expert half as each of the two ranks
    that split the experts computes it (the model, four experts a rank),
    added up, is the uncut reference's layer output; the router, which
    both compute alike, decides once and its state is the same on both."""
    hf, cfg = _zaya(experts_here=[0, 8])
    t = 96
    rng = np.random.default_rng(5)
    with jax.enable_x64(False):
        whole = init_pattern_params(jax.random.PRNGKey(2), cfg)["layers"][1]
        h = jnp.asarray(rng.standard_normal((t, cfg.dim)), jnp.float32)
        r = jnp.asarray(rng.standard_normal((t, cfg.router_hidden)), jnp.float32)
        with jax.default_matmul_precision("highest"):
            want, want_r, _routed = reference_zaya.expert_ffn(h, r, whole, hf)
        total, counted = jnp.zeros_like(want), 0
        for first in (0, 4):
            share_cfg = dataclasses.replace(cfg, expert_range=(first, first + 4))
            share = {
                k: v[first:first + 4] if k.startswith("we_") else v
                for k, v in whole.items()
            }
            y, stats = pattern._expert_ffn(
                h, share, share_cfg, pattern._route(h, share, share_cfg, r)
            )
            counted += int(stats["expert_counts"].sum())
            np.testing.assert_allclose(
                stats["router_state"], want_r, rtol=1e-5, atol=1e-6
            )
            total = total + y
    assert counted == t  # top-1: a token is some rank's, once
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("to", [3, 7], ids=["held", "elsewhere"])
def test_top_1_drops_no_token_under_a_skewed_router(to):
    """Every token to one expert (its selection bias raised): a held
    expert computes all of them in its one chunk of ``t`` rows, none
    dropped; one held elsewhere leaves nothing here."""
    hf, cfg = _zaya()
    t = 160
    with jax.enable_x64(False):
        layer = init_pattern_params(jax.random.PRNGKey(3), cfg)["layers"][0]
        layer = dict(
            layer, expert_bias=layer["expert_bias"].at[to].set(10.0)
        )
        h = jnp.asarray(
            np.random.default_rng(2).standard_normal((t, cfg.dim)), jnp.float32
        )
        r = jnp.zeros((t, cfg.router_hidden), jnp.float32)
        y, stats = pattern._expert_ffn(
            h, layer, cfg, pattern._route(h, layer, cfg, r)
        )
        with jax.default_matmul_precision("highest"):
            want, _r, (idx, _margins) = reference_zaya.expert_ffn(h, r, layer, hf)
    assert (np.asarray(stats["expert_idx"]) == to).all()
    assert (np.asarray(idx) == to).all()
    counts = np.asarray(stats["expert_counts"])
    first, last = hf["experts_here"]
    if first <= to < last:
        assert counts[to - first] == t == counts.sum()
        assert float(jnp.abs(want).min(axis=1).max()) > 0
    else:
        assert counts.sum() == 0 and not np.asarray(y).any()
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("held", [(2, 6), (0, 1), (7, 8)])
def test_flat_expert_rows_are_the_same_function_on_whole_chunks(
    held, monkeypatch
):
    """``flat_expert_rows`` (what ``zaya_config`` sets): every grouped
    matmul takes its chunk's ``t`` rows whatever share of the tokens has a
    held expert, the rows past the held pairs zeros in the last group, and
    the layer's output and every gradient are what the matmuls that follow
    the pairs give."""
    hf, flat = _zaya(experts_here=list(held))
    assert flat.flat_expert_rows
    plain = dataclasses.replace(flat, flat_expert_rows=False)
    t = 96
    rng = np.random.default_rng(11)
    seen = []
    ragged_dot = jax.lax.ragged_dot

    def spy(x, w, sizes, **kw):
        jax.debug.callback(lambda n: seen.append(int(n)), sizes.sum())
        return ragged_dot(x, w, sizes, **kw)

    with jax.enable_x64(False):
        layer = init_pattern_params(jax.random.PRNGKey(4), flat)["layers"][1]
        h = jnp.asarray(rng.standard_normal((t, flat.dim)), jnp.float32)
        r = jnp.asarray(rng.standard_normal((t, flat.router_hidden)), jnp.float32)

        def out(cfg, h, layer):
            y, stats = pattern._expert_ffn(
                h, layer, cfg, pattern._route(h, layer, cfg, r)
            )
            return jnp.sum(y * jnp.cos(y)), (y, stats["expert_counts"])

        got = jax.value_and_grad(out, (1, 2), has_aux=True)(flat, h, layer)
        monkeypatch.setattr(jax.lax, "ragged_dot", spy)
        _, (_, counts) = out(flat, h, layer)
        jax.effects_barrier()
        assert seen == [t] * 3
        assert 0 < int(counts.sum()) < t  # zero rows there were
        seen.clear()
        want = jax.value_and_grad(out, (1, 2), has_aux=True)(plain, h, layer)
        jax.effects_barrier()
        assert set(seen) == {int(counts.sum())}
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def _batch(meta, tokens_g):
    tokens = dispatch(jnp.asarray(tokens_g, jnp.int32), meta)[None]
    return tokens, jnp.roll(tokens, -1, 1), jnp.asarray(meta.perm_idx)[None]


@pytest.mark.parametrize("cp", [1, 4])
def test_a_tokens_expert_knows_no_later_token(params, cp):
    """Routing is causal and stays inside a document: with every token of
    the last document changed, the experts of every token before it, in
    every layer, are the ones they were (the choice is the argmax of the
    token's own scores plus a buffer no batch moves)."""
    _hf, cfg = _zaya()
    rng = np.random.default_rng(13)
    tokens = rng.integers(0, 64, TOTAL)
    other = tokens.copy()
    lo = CU[-2]
    other[lo:] = (tokens[lo:] + 1 + rng.integers(0, 62, TOTAL - lo)) % 64
    chosen = []
    with jax.enable_x64(False):
        model, meta = build_magi_pattern(cfg, _mesh(cp), CU, chunk_size=CHUNK)
        tables = model.sharded_tables()
        stats_of = jax.jit(
            lambda p, *b: model.loss_fn(p, *b, tables, with_stats=True)[1]
        )
        for toks in (tokens, other):
            idx = np.asarray(stats_of(params, *_batch(meta, toks))["expert_idx"])
            glob = np.zeros_like(idx[0])  # [layers, tokens, 1], global order
            glob[:, np.asarray(meta.perm_idx)] = idx[0]
            chosen.append(glob)
    np.testing.assert_array_equal(chosen[0][:, :lo], chosen[1][:, :lo])
    assert (chosen[0][:, lo:] != chosen[1][:, lo:]).any()


def test_a_train_step_leaves_the_bias_a_zero_buffer(params):
    """``expert_bias`` is a buffer: no gradient reaches it and a train
    step (AdamW with its weight decay) leaves it the zeros it was seeded
    with, while the router's own weights move."""
    import optax

    _hf, cfg = _zaya()
    opt = optax.adamw(3e-4)
    with jax.enable_x64(False):
        model, meta = build_magi_pattern(cfg, _mesh(1), CU, chunk_size=CHUNK)
        batch = _batch(meta, np.random.default_rng(5).integers(0, 64, TOTAL))
        copy = jax.tree.map(jnp.array, params)  # the step donates
        new, _state, loss = model.make_train_step(opt)(
            copy, opt.init(copy), *batch
        )
    assert np.isfinite(float(loss))
    for before, after in zip(params["layers"], new["layers"]):
        assert not np.asarray(after["expert_bias"]).any()
        assert not np.array_equal(before["w_router"], after["w_router"])


def test_the_scopes_the_layer_sets(params):
    _hf, cfg = _zaya()
    with jax.enable_x64(False):
        model, _meta = build_magi_pattern(cfg, _mesh(1), CU, chunk_size=CHUNK)
        batch = jnp.zeros((1, TOTAL), jnp.int32)
        text = jax.jit(model.loss_fn).lower(
            params, batch, batch, batch, model.sharded_tables()
        ).as_text(debug_info=True)
    for scope in ("magi_cca_mix", "magi_moe_router", "magi_moe_experts",
                  "magi_proj", "magi_attn_full", "magi_head"):
        assert scope in text, scope
    # the mix is a sibling of the projections, and no kernel lies under it
    assert "magi_proj/magi_cca_mix" not in text
    assert "magi_cca_mix/magi_proj" not in text
    assert not [
        line for line in text.splitlines()
        if "magi_cca_mix" in line and "pallas_call" in line
    ]


def test_what_the_configuration_refuses():
    hf, cfg = _zaya()
    with pytest.raises(ValueError, match="hybrid"):
        zaya_config(dict(hf, layer_types=["hybrid", "hybrid_sliding", "hybrid"]))
    with pytest.raises(ValueError, match="window"):
        zaya_config(dict(hf, sliding_window=4096))
    for fields in ({"n_kv_heads": 1, "n_heads": 8}, {"conv_taps": (2, 0)},
                   {"rope_head_dim": 0}, {"n_loops": 2, "ffn_types": ("dense",) * 3},
                   {"sliding_window": 48}):
        with pytest.raises(ValueError, match="cca"):
            dataclasses.replace(cfg, **fields)
    with pytest.raises(ValueError, match="router_hidden"):
        dataclasses.replace(cfg, router_hidden=0)
    # a cut in depth runs the first layers of the published list
    deep = zaya_config(dict(hf, layer_types=["hybrid"] * 40))
    assert deep.n_layers == 3 and DOCS == [150, 40, 66]
