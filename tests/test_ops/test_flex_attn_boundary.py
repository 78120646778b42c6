"""What crosses the flex kernels' boundary (ISSUE 40), CPU interpret mode.

A side operand crosses once, in the form its consumer wants: lse and the row
maximum leave the forward with rows along lanes, dk / dv leave in the
inputs' dtype. Since ISSUE 43 the backward is one k-major kernel: dq leaves
it in the inputs' dtype too (ISSUE 44), delta is made before the kernel (a q
block has no first step on that walk), and lse and delta enter it with rows
along lanes as one operand (ISSUE 58). Each against the form it replaced,
which stays here as the reference. Cases come from ``kernel_cases.run`` (mask
``edge``), which records what the backward kernel was handed; the side of a
comparison that patches the kernel module is traced here.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from magiattention_tpu.testing import assert_close

from .kernel_cases import (
    KernelCase, kernel_stats, launch_args, operands, run, trace,
    uncovered_rows,
)

_EDGE_T = 768
_EDGE_UNCOVERED = uncovered_rows("edge")
assert (_EDGE_UNCOVERED == np.r_[260:300, 500:768]).all()
# (hq, hk, head_block): per head; 5 heads a step at group 1 (the latent
# form's snap); 8 heads a step, two kv heads' groups of 4
_EDGE_HEADS = [(4, 2, 1), (5, 5, 5), (8, 2, 8)]
_EDGE_IDS = ["per-head", "hb=5", "hb=8"]


def _edge(hq, hk, head_block, grid, block_q, with_sink=True, **more):
    return KernelCase(
        "edge", hq=hq, hk=hk, d=32, block_q=block_q, block_k=128,
        head_block=head_block, grid=grid, sink=with_sink, **more,
    )


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("block_q", [128, 256])
@pytest.mark.parametrize("hq,hk,head_block", _EDGE_HEADS, ids=_EDGE_IDS)
@pytest.mark.parametrize("with_sink", [False, True], ids=["nosink", "sink"])
def test_compact_stats_are_lane_0_of_the_replicated_ones(
    with_sink, hq, hk, head_block, block_q, grid, monkeypatch
):
    """lse and the row maximum written with rows along lanes are lane 0 of
    the lane-replicated ones bit for bit, against the ``lanes`` form of
    the whole forward, and ``-inf`` (under a sink, the sink) stands on rows
    no entry covers. ISSUE 58, the backward's side of the same boundary:
    the launcher is handed that lse and delta as [hq, tqp] and its kernel
    reads ONE ``(hq / HBG, nq, 2, HBG, bq)`` operand that holds both bit
    for bit, blocked at the backward's own head block; nothing replicated
    over lanes is made for it."""
    from magiattention_tpu.ops import flex_attn as fa

    case = _edge(hq, hk, head_block, grid, block_q, with_sink, watch=True)
    q, k, v, sink, ftab, _btab, params = launch_args(case)
    sink2d = sink.reshape(hq, 1)
    assert fa.stats_form(block_q) == "compact"
    got, _, seen = run(case)  # the differentiated forward
    out, lse, rowmax = got["out"], got["lse"], got["rowmax"]
    assert len(jax.eval_shape(
        lambda: fa._fwd_pallas(q, k, v, sink2d, ftab, params)
    )) == 3
    (stats,) = seen["stats"]
    assert stats.shape == (
        hq // head_block, _EDGE_T // block_q, 2, head_block, block_q
    )
    assert seen["lse"].shape == seen["delta"].shape == (hq, _EDGE_T)
    np.testing.assert_array_equal(seen["lse"], lse)
    for nm, x in zip(("lse", "delta"), kernel_stats(case, seen)):
        np.testing.assert_array_equal(x, seen[nm], err_msg=nm)
    monkeypatch.setattr(fa, "stats_form", lambda block_q: "lanes")
    old = fa._fwd_pallas(q, k, v, sink2d, ftab, params)
    assert lse.shape == rowmax.shape == (hq, _EDGE_T)
    for a, b, nm in zip((out, lse, rowmax), old, ["out", "lse", "rowmax"]):
        np.testing.assert_array_equal(a, np.asarray(b), err_msg=nm)
    un = _EDGE_UNCOVERED
    assert np.isneginf(rowmax[:, un]).all()
    covered = np.setdiff1d(np.arange(_EDGE_T), un)
    assert np.isfinite(lse[:, covered]).all()
    assert np.isfinite(rowmax[:, covered]).all()
    if with_sink:
        np.testing.assert_array_equal(
            lse[:, un], np.broadcast_to(np.asarray(sink)[:, None], (hq, un.size))
        )
    else:
        assert np.isneginf(lse[:, un]).all()


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("block_q", [64, 128])
@pytest.mark.parametrize("hq,hk,head_block", _EDGE_HEADS, ids=_EDGE_IDS)
def test_grads_written_in_bf16_are_the_float32_ones_cast(
    hq, hk, head_block, block_q, grid, monkeypatch
):
    """dk, dv and (ISSUE 44) dq leave the kernel in the inputs' dtype: the
    float32 sums rounded once where they are stored (dk, dv: the VMEM
    accumulator at a k block's end; dq: the tile's float32 slot at the q
    block's last visit, into the staging slot its DMA writes from), bit
    for bit what float32 outputs rounded by XLA give, which for dq is what
    the parent's float32 buffer and rounding pass gave: the sums start
    from the first visit's zeros and add in table order as they did."""
    import copy

    from jax.experimental.pallas import tpu as pltpu

    from magiattention_tpu.ops import flex_attn as fa

    case = _edge(hq, hk, head_block, grid, block_q, dtype="bfloat16")
    got = run(case).got
    build = fa._flex_pallas_call
    widened = []

    def float32_then_cast(role, heads, grid_kind, body, form=None, **kwargs):
        if role == "fwd":
            return build(role, heads, grid_kind, body, form, **kwargs)
        shapes = kwargs["out_shape"]
        assert [s.dtype for s in shapes] == [  # dk, dv, dq, dq's scratch
            jnp.bfloat16, jnp.bfloat16, jnp.bfloat16, jnp.float32
        ]
        kwargs["out_shape"] = [
            jax.ShapeDtypeStruct(s.shape, jnp.float32) for s in shapes
        ]
        # dq's staging slots (and the zero fill its output is aliased to,
        # where the table leaves a q block out) widen with the output
        spec = kwargs["grid_spec"] = copy.copy(kwargs["grid_spec"])
        spec.scratch_shapes = tuple(
            pltpu.VMEM(s.shape, jnp.float32)
            if getattr(s, "dtype", None) == jnp.bfloat16 else s
            for s in spec.scratch_shapes
        )
        # (the result in dO's place, operand 10, where nothing is filled:
        # a float32 result cannot take it, and needs no place of its own;
        # float32 already: the sums' own unwritten buffer, the first
        # operand after the statistics)
        aliases = kwargs["input_output_aliases"]
        fills = {i for i, o in aliases.items() if i > 10 and o == 2}
        kwargs["input_output_aliases"] = {
            i: o for i, o in aliases.items() if i > 10
        }
        call = build(role, heads, grid_kind, body, form, **kwargs)
        widened.append(role)
        return lambda *args: [
            x.astype(s.dtype)
            for x, s in zip(
                call(*[
                    a.astype(jnp.float32) if i in fills else a
                    for i, a in enumerate(args)
                ]),
                shapes,
            )
        ]

    monkeypatch.setattr(fa, "_flex_pallas_call", float32_then_cast)
    old, _ = trace(case)
    assert widened == ["bwd"]
    for nm in ("dq", "dk", "dv"):
        assert got[nm].dtype == jnp.bfloat16, nm
        assert np.isfinite(np.asarray(got[nm], np.float32)).all(), nm
        np.testing.assert_array_equal(
            np.asarray(got[nm], np.float32), np.asarray(old[nm], np.float32),
            err_msg=nm,
        )
    assert not np.asarray(got["dq"], np.float32)[:, _EDGE_UNCOVERED].any()


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("block_q", [64, 128, 256])
@pytest.mark.parametrize("hq,hk,head_block", _EDGE_HEADS, ids=_EDGE_IDS)
@pytest.mark.parametrize("use_lse", [False, True], ids=["zero-dlse", "dlse"])
def test_delta_is_made_before_the_kernel(
    use_lse, hq, hk, head_block, block_q, grid
):
    """``delta = sum(dO * out) - dlse`` is made once, before the one
    backward kernel, [hq, tqp] float32, and the kernel reads it with rows
    along lanes at every ``block_q`` (``kernel_stats``) bit for bit: with an lse
    cotangent that is a symbolic zero (nothing is subtracted) and with one
    that is not; dq, dk, dv, dsink against the jnp backend."""
    case = _edge(
        hq, hk, head_block, grid, block_q, use_lse=use_lse, watch=True
    )
    got, ref, seen = run(case)
    x = operands(case)
    delta = seen["delta"]
    assert delta.shape == (hq, _EDGE_T) and delta.dtype == np.float32
    np.testing.assert_array_equal(kernel_stats(case, seen)[1], delta)
    want = np.sum(x["do"] * got["out"], axis=-1)
    if use_lse:
        np.testing.assert_array_equal(seen["dlse"], x["w"])
        want = want - x["w"]
    else:
        assert seen["dlse"] is None
    assert_close(delta, want, atol=1e-5, rtol=1e-5, msg="delta")
    if use_lse:  # rows with out = 0: delta is the cotangent alone, exactly
        np.testing.assert_array_equal(delta[:, 500:], -x["w"][:, 500:])
    for nm in ("dq", "dk", "dv", "dsink"):
        assert np.isfinite(got[nm]).all(), nm
        assert_close(got[nm], ref[nm], atol=5e-5, rtol=5e-5, msg=nm)
    assert not got["dq"][:, _EDGE_UNCOVERED].any()


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("block_q", [64, 128, 256])
@pytest.mark.parametrize("hq,hk,head_block", _EDGE_HEADS, ids=_EDGE_IDS)
@pytest.mark.parametrize("use_lse", [False, True], ids=["zero-dlse", "dlse"])
def test_the_transposed_tile_gives_the_plain_orientations_gradients(
    use_lse, hq, hk, head_block, block_q, grid
):
    """ISSUE 58: the backward's step computes ``K Q^T`` against statistics
    that lie with rows along lanes (``_bwd_tile``). The reference is the
    orientation it replaced, written out in numpy on the mask the tables
    describe: ``P = exp(Q K^T - lse)``, ``dS = P (dO V^T - delta)``, ``dq =
    dS K``, ``dk = dS^T Q``, ``dv = P^T dO``, from the very lse and delta
    the launcher was handed. Same mathematics, same dtypes: the two differ
    only in which operand of a contraction is transposed, so dq, dk and dv
    agree to float32 rounding, with an lse cotangent that is a symbolic
    zero and with one that is not, rows no key covers (exact zeros in dq)
    and q blocks no entry names."""
    from magiattention_tpu.ops import flex_attn as fa

    case = _edge(
        hq, hk, head_block, grid, block_q, use_lse=use_lse, watch=True
    )
    got, _, seen = run(case)
    q, k, v, _sink, ftab, _btab, params = launch_args(case)
    tq, tk = case.tokens
    group = hq // hk
    do = np.zeros(q.shape, np.float32)
    do[:, :tq] = operands(case)["do"]
    mask = np.asarray(fa._dense_mask_from_tables(
        ftab, q.shape[1], k.shape[1], block_q, params.block_k
    ))
    kf, vf = (np.repeat(np.asarray(x), group, axis=0) for x in (k, v))
    s = np.float32(params.scale) * np.einsum("hqd,hkd->hqk", q, kf)
    lse = np.where(np.isneginf(seen["lse"]), 0.0, seen["lse"])
    p = np.where(mask[None], np.exp(s - lse[..., None]), np.float32(0.0))
    ds = p * (np.einsum("hqd,hkd->hqk", do, vf) - seen["delta"][..., None])
    per_kv = lambda x: x.reshape(hk, group, *x.shape[1:]).sum(axis=1)  # noqa: E731
    want = dict(
        dq=np.float32(params.scale) * np.einsum("hqk,hkd->hqd", ds, kf),
        dk=per_kv(np.float32(params.scale) * np.einsum("hqk,hqd->hkd", ds, q)),
        dv=per_kv(np.einsum("hqk,hqd->hkd", p, do)),
    )
    np.testing.assert_array_equal(seen["dq_kernel"][:, :tq], got["dq"])
    for nm, rows in (("dq", tq), ("dk", tk), ("dv", tk)):
        assert np.isfinite(got[nm]).all(), nm
        assert_close(got[nm], want[nm][:, :rows], atol=2e-5, rtol=2e-5, msg=nm)
    assert not got["dq"][:, _EDGE_UNCOVERED].any()
