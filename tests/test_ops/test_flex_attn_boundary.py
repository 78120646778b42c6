"""What crosses the flex kernels' boundary (ISSUE 40), CPU interpret mode.

A side operand crosses once, in the form its consumer wants: lse and the row
maximum leave the forward with rows along lanes, dk / dv leave in the
inputs' dtype. Since ISSUE 43 the backward is one k-major kernel: dq leaves
it as the float32 buffer it was summed in and is rounded once, delta is made
before the kernel (a q block has no first step on that walk). Each against
the form it replaced, which stays here as the reference. (A file of its own: the driver hands a
test file to one worker, and ``test_flex_attn.py`` is the longest already.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from magiattention_tpu.common import AttnMaskType
from magiattention_tpu.ops import build_block_meta
from magiattention_tpu.testing import assert_close

F = AttnMaskType.FULL
C = AttnMaskType.CAUSAL


def _rand_qkv(tq, tk, hq, hk, d, seed):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((tq, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((tk, hk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((tk, hk, d)), jnp.float32)
    return q, k, v


#   rows   0..150  full against k [0, 300);
#   rows 150..260  causal against k [100, 512);
#   rows 260..300  no slice at all, in a q block that has entries;
#   rows 300..500  full against k [0, 512);
#   rows 500..768  nothing: the tail of a block, then blocks with no entry.
_EDGE_T, _EDGE_TK = 768, 512
_EDGE_MASK = ([(0, 150), (150, 260), (300, 500)],
              [(0, 300), (100, 512), (0, 512)], [F, C, F])
_EDGE_UNCOVERED = np.r_[260:300, 500:768]
# (hq, hk, head_block): per head; 5 heads a step at group 1 (the latent
# form's snap); 8 heads a step, two kv heads' groups of 4
_EDGE_HEADS = [(4, 2, 1), (5, 5, 5), (8, 2, 8)]
_EDGE_IDS = ["per-head", "hb=5", "hb=8"]


def _edge_operands(hq, hk, head_block, grid, block_q, with_sink, dtype, d=32):
    from magiattention_tpu.ops import flex_attn as fa

    qr, kr, ts = _EDGE_MASK
    q, k, v = _rand_qkv(_EDGE_T, _EDGE_TK, hq, hk, d, seed=41)
    meta = build_block_meta(
        qr, kr, [t.value for t in ts], _EDGE_T, _EDGE_TK,
        block_q=block_q, block_k=128,
    )
    params = fa.FlexAttnParams(
        block_q=block_q, block_k=128, scale=d**-0.5, softcap=0.0,
        has_sink=with_sink, out_dtype=str(jnp.dtype(dtype)), interpret=True,
        head_block=head_block, fwd_steps=meta.fwd_steps,
        bwd_steps=meta.bwd_steps, grid=grid,
    )
    rng = np.random.default_rng(43)
    sink = jnp.asarray(rng.standard_normal(hq), jnp.float32)
    qh, kh, vh = (
        jnp.transpose(x, (1, 0, 2)).astype(dtype) for x in (q, k, v)
    )
    return qh, kh, vh, sink, fa.fwd_tables(meta), fa.bwd_tables(meta), params


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("block_q", [128, 256])
@pytest.mark.parametrize("hq,hk,head_block", _EDGE_HEADS, ids=_EDGE_IDS)
@pytest.mark.parametrize("with_sink", [False, True], ids=["nosink", "sink"])
def test_compact_stats_are_lane_0_of_the_replicated_ones(
    with_sink, hq, hk, head_block, block_q, grid, monkeypatch
):
    """lse and the row maximum written with rows along lanes are lane 0 of
    the lane-replicated ones bit for bit: against the residual the same
    kernel writes for the backward, and against the ``lanes`` form of the
    whole forward. ``-inf`` (under a sink, the sink) stands on rows no
    entry covers; the undifferentiated forward writes no residual."""
    from magiattention_tpu.ops import flex_attn as fa

    qh, kh, vh, sink, ftab, _, params = _edge_operands(
        hq, hk, head_block, grid, block_q, with_sink, jnp.float32
    )
    sink2d = sink.reshape(hq, 1)
    assert fa.stats_form(block_q) == "compact"
    got = fa._fwd_pallas(qh, kh, vh, sink2d, ftab, params, residual=True)
    assert fa._fwd_pallas(qh, kh, vh, sink2d, ftab, params)[3] is None
    monkeypatch.setattr(fa, "stats_form", lambda block_q: "lanes")
    old = fa._fwd_pallas(qh, kh, vh, sink2d, ftab, params)
    out, lse, rowmax, lse_lanes = (np.asarray(x) for x in got)
    assert lse.shape == rowmax.shape == (hq, _EDGE_T)
    assert lse_lanes.shape == (hq, _EDGE_T, fa.LANES)
    np.testing.assert_array_equal(lse, lse_lanes[:, :, 0])
    for a, b, nm in zip(got, old, ["out", "lse", "rowmax", "lse_lanes"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=nm)
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


def _edge_grads(operands, use_lse, dtype):
    """(dq, dk, dv, dsink) of a loss on out (and, ``use_lse``, on lse)."""
    from magiattention_tpu.ops import flex_attn as fa

    qh, kh, vh, sink, ftab, btab, params = operands
    rng = np.random.default_rng(47)
    do = jnp.asarray(rng.standard_normal(qh.shape), dtype)
    w = jnp.asarray(rng.standard_normal(qh.shape[:2]), jnp.float32)

    def loss(q, k, v, sink):
        out, lse, _ = fa.flex_attn_headmajor(
            q, k, v, ftab, btab, params,
            sink=sink if params.has_sink else None,
        )
        res = (out.astype(jnp.float32) * do.astype(jnp.float32)).sum()
        if use_lse:
            res += (jnp.where(jnp.isneginf(lse), 0.0, lse) * w).sum()
        return res

    return jax.grad(loss, argnums=(0, 1, 2, 3))(qh, kh, vh, sink), do, w


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

    operands = _edge_operands(
        hq, hk, head_block, grid, block_q, True, jnp.bfloat16
    )
    got, _, _ = _edge_grads(operands, True, jnp.bfloat16)
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
        # float32 already: operand 13, the sums' own unwritten buffer)
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
    old, _, _ = _edge_grads(operands, True, jnp.bfloat16)
    assert widened == ["bwd"]
    for a, b, nm in zip(got[:3], old[:3], ["dq", "dk", "dv"]):
        assert a.dtype == jnp.bfloat16, nm
        assert np.isfinite(np.asarray(a, np.float32)).all(), nm
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32), err_msg=nm
        )
    assert not np.asarray(got[0], np.float32)[:, _EDGE_UNCOVERED].any()


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("block_q", [64, 128, 256])
@pytest.mark.parametrize("hq,hk,head_block", _EDGE_HEADS, ids=_EDGE_IDS)
@pytest.mark.parametrize("use_lse", [False, True], ids=["zero-dlse", "dlse"])
def test_delta_is_made_before_the_kernel(
    use_lse, hq, hk, head_block, block_q, grid, monkeypatch
):
    """``delta = sum(dO * out) - dlse`` is made once, before the one
    backward kernel, and handed to it replicated over lanes: with an lse
    cotangent that is a symbolic zero (nothing is subtracted) and with one
    that is not; dq, dk, dv, dsink against the jnp backend."""
    from magiattention_tpu.ops import flex_attn as fa

    operands = _edge_operands(
        hq, hk, head_block, grid, block_q, True, jnp.float32
    )
    seen = {}
    bwd_delta = fa._bwd_delta

    def spy(do, out, dlse):
        res = bwd_delta(do, out, dlse)
        seen.update(do=do, out=out, dlse=dlse, rows=np.asarray(res[0]),
                    delta=np.asarray(res[1]))
        return res

    monkeypatch.setattr(fa, "_bwd_delta", spy)
    got, do, w = _edge_grads(operands, use_lse, jnp.float32)
    delta = seen["delta"]
    assert delta.shape == (hq, _EDGE_T, fa.LANES) and delta.dtype == np.float32
    np.testing.assert_array_equal(
        delta, np.broadcast_to(delta[..., :1], delta.shape)
    )
    np.testing.assert_array_equal(delta[..., 0], seen["rows"])
    want = np.asarray(jnp.sum(seen["do"] * seen["out"], axis=-1))
    if use_lse:
        np.testing.assert_array_equal(np.asarray(seen["dlse"]), np.asarray(w))
        want = want - np.asarray(w)
    else:
        assert seen["dlse"] is None
    assert_close(delta[..., 0], want, atol=1e-5, rtol=1e-5, msg="delta")
    if use_lse:  # rows with out = 0: delta is the cotangent alone, exactly
        np.testing.assert_array_equal(
            delta[:, 500:, 0], -np.asarray(w)[:, 500:]
        )

    monkeypatch.setenv("MAGI_ATTENTION_KERNEL_BACKEND", "jnp")
    ref, _, _ = _edge_grads(operands, use_lse, jnp.float32)
    for a, b, nm in zip(got, ref, ["dq", "dk", "dv", "dsink"]):
        assert np.isfinite(np.asarray(a)).all(), nm
        assert_close(a, b, atol=5e-5, rtol=5e-5, msg=nm)
    assert not np.asarray(got[0])[:, _EDGE_UNCOVERED].any()
