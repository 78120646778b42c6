"""The plan's kernels by grid and by dq form, end to end at cp > 1 on the
virtual CPU mesh: the row-major and the compact grid agree bit for bit
over per-rank stacked, padded, traced tables, and a stage whose k-major
table leaves q blocks out fills its dq. (Split from ``test_pipeline.py``
in ISSUE 45, whose scenarios and mesh these use: a file a subject, none a
worker's whole run.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from magiattention_tpu.common import AttnMaskType, AttnRanges
from magiattention_tpu.meta import make_dispatch_meta_from_qk_ranges
from magiattention_tpu.parallel import (
    build_dist_attn_plan,
    dispatch,
    make_attn_params,
    make_dist_attn_fn,
    undispatch,
)
from magiattention_tpu.testing import assert_close, ref_attn_from_ranges

from .test_pipeline import SCENARIOS, _mesh


@pytest.mark.parametrize("cp", [2, 4])
def test_a_stage_that_names_only_some_q_blocks_fills_its_dq(cp):
    """ISSUE 44: under an overlap degree above 0 a remote stage's keys
    reach only some of a rank's q blocks, and the blocks its k-major table
    leaves out must come back as zeros from that stage's backward: the
    plan builder counts them on the host (the per-rank tables are traced),
    that stage's kernel takes its dq output aliased to a zero fill, and a
    stage (the host stage here) that names every block fills nothing. dq,
    dk, dv against the reference."""
    from magiattention_tpu import telemetry
    from magiattention_tpu.meta.solver.overlap_solver import OverlapConfig

    # packed documents over 32 q blocks: a remote stage brings a rank the
    # keys of a few documents' heads, which only those documents' rows see
    name, total = "documents", 2048
    qr = kr = [(0, 200), (200, 1100), (1100, 1300), (1300, 2048)]
    ts = [AttnMaskType.CAUSAL] * len(qr)
    hq, hk, d = 2, 2, 64
    mesh = _mesh(cp)
    mq, _, bucket = make_dispatch_meta_from_qk_ranges(
        AttnRanges.from_ranges(qr), AttnRanges.from_ranges(kr), ts, total,
        total, chunk_size=total // (4 * cp), cp_size=cp,
    )
    plan = build_dist_attn_plan(
        mq, bucket, block_q=64, block_k=64,
        overlap_config=OverlapConfig(degree=2, min_stage_rows=64),
    )
    assert plan.overlap_degree == 2
    sets = [plan.host_tables, *(sp.tables for sp in plan.stages)]
    unnamed = [t.q_visits()[2] for t in sets]
    assert unnamed[0] == 0 and min(unnamed[1:]) > 0, unnamed
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    try:
        with telemetry.span("attn_fn_build"):
            params = make_attn_params(plan, d, out_dtype="float32")
        (*_, said) = [
            ev["args"] for ev in telemetry.get_event_buffer().events()
            if ev["name"] == "attn_fn_build"
        ]
    finally:
        telemetry.set_enabled(was)
    # the plan's count, on the params and on the span; a call has its own
    assert params.bwd_unnamed_q == said["dq_unnamed_q_blocks"] == sum(unnamed)
    assert said["dq_visits_per_tile"] == pytest.approx(
        sum(t.q_visits()[0] for t in sets) / sum(t.q_visits()[1] for t in sets)
    )
    attn_fn = make_dist_attn_fn(plan, mesh, params)

    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((total, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((total, hk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((total, hk, d)), jnp.float32)
    do = jnp.asarray(rng.standard_normal((total, hq, d)), jnp.float32)

    def loss(q, k, v):
        out_d, _ = attn_fn(dispatch(q, mq), dispatch(k, mq), dispatch(v, mq))
        return (undispatch(out_d, mq) * do).sum()

    telemetry.set_enabled(True)
    reg = telemetry.get_registry()

    def built(form):
        return reg.counter_value(
            "magi_flex_kernel_build_total", kernel="bwd", grid=params.grid,
            heads_per_step=1, stats="compact", delta="xla", dq=form,
        )

    before = {form: built(form) for form in ("visits", "zero_filled")}
    try:
        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
        assert built("zero_filled") - before["zero_filled"] == sum(
            n > 0 for n in unnamed
        )
        assert built("visits") - before["visits"] == sum(n == 0 for n in unnamed)
    finally:
        telemetry.set_enabled(was)
    gr = jax.grad(
        lambda q, k, v: (ref_attn_from_ranges(q, k, v, qr, kr, ts)[0] * do).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b, nm in zip(g, gr, ["dq", "dk", "dv"]):
        assert_close(a, b, atol=1e-4, rtol=1e-4, msg=f"{name} cp{cp} {nm}")


@pytest.mark.parametrize("block_k", [64, 128])
@pytest.mark.parametrize("head_block", [1, 2], ids=["per-head", "hb=2"])
@pytest.mark.parametrize("degree", [0, 2])
def test_pipeline_agrees_between_the_two_grids(
    degree, head_block, block_k, monkeypatch
):
    """The plan's kernels on the row-major and on the compact grid
    (ISSUE 27: ``make_attn_params`` sets ``FlexAttnParams.grid``, here
    pinned each way by ``MAGI_ATTENTION_GRID``) over per-rank stacked,
    padded, traced tables at cp=4: same out, lse and gradients, and the
    oracle's; out and lse from the forward alone (``jax.jit(fwd)``: no
    residual is built), which the differentiated forward's equal bit for
    bit. Degree 2 merges two stages' partials on their lse
    (``ops/correction.py``), which needs ``lse = -inf`` on the rows a
    stage does not cover: the forward restores it where a q block is
    written (ISSUE 29; ``block_k`` 128 runs its per-lane row sum, 64 the
    narrow-tile form)."""
    from magiattention_tpu.meta.solver.overlap_solver import OverlapConfig

    name, total, qr, kr, ts = next(
        s for s in SCENARIOS if s[0] == "mixed_types_with_holes"
    )
    cp, hq, hk, d = 4, 4, 2, 64
    mesh = _mesh(cp)
    mq, _, bucket = make_dispatch_meta_from_qk_ranges(
        AttnRanges.from_ranges(qr), AttnRanges.from_ranges(kr), ts, total,
        total, chunk_size=total // (4 * cp), cp_size=cp,
    )
    plan = build_dist_attn_plan(
        mq, bucket, block_q=64, block_k=block_k,
        overlap_config=OverlapConfig(degree=degree, min_stage_rows=64),
    )
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((total, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((total, hk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((total, hk, d)), jnp.float32)
    do = jnp.asarray(rng.standard_normal((total, hq, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((total, hq)), jnp.float32)

    def results(grid):
        monkeypatch.setenv("MAGI_ATTENTION_GRID", grid)
        params = make_attn_params(
            plan, d, out_dtype="float32", head_block=head_block
        )
        assert params.grid == grid
        attn_fn = make_dist_attn_fn(plan, mesh, params)

        def fwd(q, k, v):
            out_d, lse_d = attn_fn(
                dispatch(q, mq), dispatch(k, mq), dispatch(v, mq)
            )
            return undispatch(out_d, mq), undispatch(lse_d, mq)

        def loss(q, k, v):
            out, lse = fwd(q, k, v)
            return (out * do).sum() + (
                jnp.where(jnp.isneginf(lse), 0.0, lse) * w
            ).sum(), (out, lse)

        # two programs a grid: the forward alone (the build without the
        # backward's residual: what serving and the benchmark's forward
        # phase run), and the differentiated one, whose forward hands out
        # and lse back as aux
        (_, with_residual), grads = jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
        )(q, k, v)
        alone = jax.jit(fwd)(q, k, v)
        for a, b, nm in zip(alone, with_residual, ["out", "lse"]):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"{nm} on {grid}: the forward alone is not the "
                "differentiated one",
            )
        return (*alone, *grads)

    row_major, compact = results("row_major"), results("sparse")
    ref_out, ref_lse, _ = ref_attn_from_ranges(q, k, v, qr, kr, ts)
    for a, b, nm in zip(row_major, compact, ["out", "lse", "dq", "dk", "dv"]):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=f"{nm}: the grids disagree"
        )
    assert_close(compact[0], ref_out, atol=3e-5, rtol=3e-5, msg="out")
    finite = ~np.isneginf(np.asarray(ref_lse))
    assert not finite.all()  # the mask has holes
    np.testing.assert_array_equal(np.isneginf(np.asarray(compact[1])), ~finite)
    assert_close(
        np.asarray(compact[1])[finite], np.asarray(ref_lse)[finite],
        atol=3e-5, rtol=3e-5, msg="lse",
    )
