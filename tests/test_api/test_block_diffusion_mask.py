"""``api.infer_block_diffusion_mask``: the ``[noisy ; clean]`` mask of
diffusion over blocks as three stepped slices a document == the
rectangle form of the unstepped types == the definition, and through
``dispatch`` / ``calc_attn`` / ``undispatch`` at cp = 1, 2, 4."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from magiattention_tpu.api import (
    calc_attn, dispatch, infer_block_diffusion_mask, magi_attn_flex_key,
    undispatch,
)
from magiattention_tpu.common import AttnMaskType as T, AttnRanges
from magiattention_tpu.common.mask import (
    make_attn_mask_from_ranges, unstepped_slice_count,
)
from magiattention_tpu.testing import assert_close, ref_attn_from_ranges
from magiattention_tpu.tuning.cost_model import exact_mask_area


def definition(cu, block, total):
    """Each row's and key's half, document and block index: a noisy row
    sees its own noisy block and the clean blocks before it, a clean row
    the clean blocks up to and with its own."""
    doc = np.full(total, -1)
    blk = np.zeros(total, int)
    for d, (a, b) in enumerate(zip(cu, cu[1:])):
        doc[a:b] = d
        blk[a:b] = (np.arange(a, b) - a) // block
    doc, blk = np.tile(doc, 2), np.tile(blk, 2)
    clean = np.arange(2 * total) >= total
    q, k = np.ix_(np.arange(2 * total), np.arange(2 * total))
    same = (doc[q] == doc[k]) & (doc[q] >= 0)
    return same & (
        (~clean[q] & ~clean[k] & (blk[q] == blk[k]))
        | (~clean[q] & clean[k] & (blk[k] < blk[q]))
        | (clean[q] & clean[k] & (blk[k] <= blk[q]))
    )


def rectangles(cu, block, total=None):
    """The same mask from the unstepped types: one FULL rectangle a block
    and kind, ``3 n / block - 1`` a document of n tokens."""
    total = cu[-1] if total is None else total
    qr, kr = [], []
    for c0, c1 in zip(cu, cu[1:]):
        for b0 in range(c0, c1, block):
            b1 = b0 + block
            qr.append((total + b0, total + b1))  # clean -> clean
            kr.append((total + c0, total + b1))
            if b0 > c0:  # noisy -> the clean blocks before
                qr.append((b0, b1))
                kr.append((total + c0, total + b0))
            qr.append((b0, b1))  # noisy -> its own noisy block
            kr.append((b0, b1))
    return (
        AttnRanges.from_ranges(qr), AttnRanges.from_ranges(kr),
        [T.FULL] * len(qr),
    )


PACKED = [
    pytest.param([0, 24, 32, 64], 4, 64, id="three-docs"),
    pytest.param([0, 8, 16], 8, 24, id="one-block-docs-padded"),
    pytest.param([0, 4, 20], 4, 20, id="a-single-block-doc"),
    pytest.param([0, 16], 2, 16, id="one-doc-block2"),
    pytest.param([0, 10, 10, 13], 1, 13, id="block1-and-an-empty-doc"),
]


@pytest.mark.parametrize("cu,block,total", PACKED)
def test_three_slices_rectangles_and_the_definition(cu, block, total):
    want = definition(cu, block, total)
    docs = [(a, b) for a, b in zip(cu, cu[1:]) if b > a]
    rows = 2 * total
    q, k, t = infer_block_diffusion_mask(cu, block, total_seqlen=total)
    assert (make_attn_mask_from_ranges(q, k, t, rows, rows) == want).all()
    assert len(t) == sum(3 if b - a > block else 2 for a, b in docs)
    assert {x.step for x in t} == {block}
    assert {x.base for x in t} == {T.CAUSAL, T.BICAUSAL}
    naive = q.to_naive_ranges(), k.to_naive_ranges(), [int(x) for x in t]
    assert exact_mask_area(*naive) == want.sum()
    assert want.sum() == sum((b - a) ** 2 + (b - a) * block for a, b in docs)
    fq, fk, ft = rectangles(cu, block, total)
    assert (make_attn_mask_from_ranges(fq, fk, ft, rows, rows) == want).all()
    assert set(ft) == {T.FULL}
    assert len(ft) == sum(3 * (b - a) // block - 1 for a, b in docs)
    if block > 1:  # what the span's ``rectangles`` reports
        assert unstepped_slice_count(*naive) == len(ft)


def test_the_cells_mask_by_the_numbers():
    """ISSUE 42: 8,192 tokens in documents of 6,144 / 1,536 / 512, block
    4: nine slices where the unstepped types take 6,141; area
    40,402,944."""
    q, k, t = infer_block_diffusion_mask([0, 6144, 7680, 8192], 4)
    naive = q.to_naive_ranges(), k.to_naive_ranges(), [int(x) for x in t]
    assert len(t) == 9 and unstepped_slice_count(*naive) == 6141
    assert exact_mask_area(*naive) == 40_402_944
    assert len(rectangles([0, 6144, 7680, 8192], 4)[2]) == 6141


def test_what_is_refused():
    with pytest.raises(ValueError, match=r"\(0, 6\).*whole number of blocks of 4"):
        infer_block_diffusion_mask([0, 6, 8], 4)
    with pytest.raises(ValueError, match="power of two"):
        infer_block_diffusion_mask([0, 6, 12], 3)
    with pytest.raises(ValueError, match="start at 0"):
        infer_block_diffusion_mask([4, 8], 4)
    with pytest.raises(ValueError, match="total_seqlen 4"):
        infer_block_diffusion_mask([0, 8], 4, total_seqlen=4)


@pytest.mark.parametrize("stepped", [True, False], ids=["stepped", "rectangles"])
@pytest.mark.parametrize("cp", [1, 2, 4])
def test_through_the_keyed_api(cp, stepped):
    """Documents whose ends are off the chunk grid, so a chunk's edge
    cuts the three slices mid-document: out and dq / dk / dv against the
    dense reference."""
    cu, block, total, chunk = [0, 152, 200, 256], 4, 256, 32
    hq, hk, d = 4, 2, 32
    mesh = Mesh(np.array(jax.devices()[:cp]), ("cp",))
    qr, kr, ts = (
        infer_block_diffusion_mask if stepped else rectangles
    )(cu, block)
    key = magi_attn_flex_key(
        qr, kr, ts, 2 * total, 2 * total, mesh, num_heads=(hq, hk),
        head_dim=d, chunk_size=chunk, out_dtype="float32",
    )
    rng = np.random.default_rng(cp)
    q = jnp.asarray(rng.standard_normal((2 * total, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2 * total, hk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2 * total, hk, d)), jnp.float32)

    def ours(q, k, v):
        qd, kd, vd = dispatch(q, key), dispatch(k, key), dispatch(v, key)
        return undispatch(calc_attn(qd, kd, vd, key)[0], key)

    def dense(q, k, v):
        return ref_attn_from_ranges(q, k, v, qr, kr, ts)[0]

    assert_close(jax.jit(ours)(q, k, v), dense(q, k, v), atol=3e-5, rtol=3e-5)
    got = jax.jit(jax.grad(lambda *a: (ours(*a) ** 2).sum(), argnums=(0, 1, 2)))(q, k, v)
    want = jax.grad(lambda *a: (dense(*a) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert_close(a, b, atol=1e-4, rtol=1e-4)


def test_a_chunk_of_noisy_rows_needs_its_own_keys_alone():
    """The dispatch's slices of a chunk of the noisy half: its own rows
    (the block diagonal) and the clean rows before it; no noisy row of
    another chunk is needed as a key."""
    from magiattention_tpu.common.ranges import AttnRanges
    from magiattention_tpu.meta.dispatch_meta import (
        make_global_bucket_from_qk_ranges,
    )

    cu, block, total, chunk = [0, 152, 200, 256], 4, 256, 32
    qr, kr, ts = infer_block_diffusion_mask(cu, block)
    bucket = make_global_bucket_from_qk_ranges(qr, kr, ts, 2 * total, chunk)
    area = 0
    for c in bucket.q_chunks:
        lo, hi = c.q_range.start, c.q_range.end
        for s in c.attn_slices:
            area += s.area
            if hi <= total and s.k_range.start < total:  # noisy -> noisy
                assert lo <= s.k_range.start and s.k_range.end <= hi
            if lo >= total:  # clean rows see no noisy key
                assert s.k_range.start >= total
    assert area == definition(cu, block, total).sum()


def test_the_dynamic_solver_cuts_a_stepped_mask():
    """qo-comm's rectangles under cuts that fall inside a block."""
    from magiattention_tpu.common.rectangle import AttnRectangles
    from magiattention_tpu.meta.solver.dynamic_attn_solver import (
        DynamicAttnSolver,
    )

    cu, block, total = [0, 152, 200, 256], 4, 256
    qr, kr, ts = infer_block_diffusion_mask(cu, block)
    rects = AttnRectangles.from_ranges(
        qr.to_naive_ranges(), kr.to_naive_ranges(), ts
    )
    want = definition(cu, block, total)
    assert rects.area == want.sum()
    for pos in (101, 258, 407):
        top, bottom = rects.cut_q(pos)
        left, right = rects.cut_k(pos)
        assert top.area == want[:pos].sum() == rects.area_left_of_q(pos)
        assert left.area == want[:, :pos].sum() == rects.area_left_of_k(pos)
        assert top.area + bottom.area == left.area + right.area == want.sum()
    assert DynamicAttnSolver is not None
