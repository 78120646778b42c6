"""The fused backward (ISSUE 43): one kernel over the k-major table that
keeps dk / dv in VMEM and adds dq into a float32 buffer in HBM, a tile's
read started a step ahead. CPU, interpret mode: dq, dk, dv and the sink's
gradient against the jnp reference backward over mask type, GQA group,
head block, head_dim and both grids, with a non-zero lse cotangent and a
sink; and the orderings the step keeps, each by name."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from magiattention_tpu.common import AttnMaskType
from magiattention_tpu.ops import build_block_meta, flex_flash_attn_func
from magiattention_tpu.ops import flex_attn as fa
from magiattention_tpu.ops.block_meta import pad_block_meta
from magiattention_tpu.testing import assert_close, ref_attn_from_ranges

T = AttnMaskType
TOKENS = 256
# one slice of each bounded type on ranges that are no multiple of a block,
# and two slices at a step of 4 that share q rows with the first
MASKS = {
    "full": ([(0, 250)], [(6, 256)], [T.FULL]),
    "causal": ([(0, 250)], [(0, 250)], [T.CAUSAL]),
    "invcausal": ([(3, 200)], [(0, 256)], [T.INVCAUSAL]),
    "bicausal": ([(0, 180)], [(10, 256)], [T.BICAUSAL]),
    "stepped": (
        [(0, 128), (128, 256), (16, 80)],
        [(0, 128), (0, 256), (128, 200)],
        [T.CAUSAL.with_step(4), T.CAUSAL.with_step(4), T.FULL],
    ),
}


def _operands(hq, hk, d, seed=5):
    rng = np.random.default_rng(seed)
    make = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape), jnp.float32
    )
    return dict(
        q=make(TOKENS, hq, d), k=make(TOKENS, hk, d), v=make(TOKENS, hk, d),
        sink=make(hq), do=make(TOKENS, hq, d), w=make(TOKENS, hq),
    )


def _grads(attn, x):
    """dq, dk, dv, dsink of a loss that reads out and lse (a non-zero lse
    cotangent) through ``attn(q, k, v, sink) -> (out, lse)``."""

    def loss(q, k, v, sink):
        out, lse = attn(q, k, v, sink)
        lse = jnp.where(jnp.isneginf(lse), 0.0, lse)
        return (out * x["do"]).sum() + (lse * x["w"]).sum()

    return jax.grad(loss, argnums=(0, 1, 2, 3))(
        x["q"], x["k"], x["v"], x["sink"]
    )


@functools.lru_cache(maxsize=None)
def _reference(mask, hq, hk, d):
    qr, kr, ts = MASKS[mask]
    return _grads(
        lambda q, k, v, sink: ref_attn_from_ranges(
            q, k, v, qr, kr, ts, sink=sink
        )[:2],
        _operands(hq, hk, d),
    )


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("d", [128, 256])
@pytest.mark.parametrize("heads", ["per-head", "batched"])
@pytest.mark.parametrize("group", [1, 4, 8])
@pytest.mark.parametrize("mask", list(MASKS))
def test_fused_backward_matches_the_reference(mask, group, heads, d, grid):
    hk = 2
    hq = hk * group
    head_block = 1 if heads == "per-head" else max(group, 2)
    qr, kr, ts = MASKS[mask]
    got = _grads(
        lambda q, k, v, sink: flex_flash_attn_func(
            q, k, v, qr, kr, ts, sink=sink, block_q=64, block_k=64,
            head_block=head_block, grid=grid, interpret=True,
        ),
        _operands(hq, hk, d),
    )
    for a, b, nm in zip(got, _reference(mask, hq, hk, d), ["dq", "dk", "dv", "dsink"]):
        assert np.isfinite(np.asarray(a)).all(), nm
        assert_close(a, b, atol=1e-4, rtol=1e-4, msg=nm)


def _walk(meta):
    """(k block, q block) of the backward table's entries, in the order
    the kernel walks them."""
    return list(zip(meta.bwd_k_block.tolist(), meta.bwd_q_block.tolist()))


def _table_grads(meta, hq, hk, head_block, grid, d=32):
    """The same gradients through ``flex_attn_headmajor`` on the tables of
    ``meta`` as jit arguments (traced, as a plan's are)."""
    x = _operands(hq, hk, d)
    params = fa.FlexAttnParams(
        block_q=meta.block_q, block_k=meta.block_k, scale=d**-0.5,
        softcap=0.0, has_sink=True, out_dtype="float32", interpret=True,
        head_block=head_block, fwd_steps=meta.fwd_steps,
        bwd_steps=meta.bwd_steps, grid=grid,
    )

    def grads(ftab, btab):
        def attn(q, k, v, sink):
            out, lse, _ = fa.flex_attn_headmajor(
                jnp.transpose(q, (1, 0, 2)), jnp.transpose(k, (1, 0, 2)),
                jnp.transpose(v, (1, 0, 2)), ftab, btab, params, sink=sink,
            )
            return jnp.transpose(out, (1, 0, 2)), lse.T

        return _grads(attn, x)

    return jax.jit(grads)(fa.fwd_tables(meta), fa.bwd_tables(meta))


def _check(meta, mask, hq, hk, head_block, grid):
    qr, kr, ts = mask
    x = _operands(hq, hk, 32)
    want = _grads(
        lambda q, k, v, sink: ref_attn_from_ranges(
            q, k, v, qr, kr, ts, sink=sink
        )[:2],
        x,
    )
    got = _table_grads(meta, hq, hk, head_block, grid)
    for a, b, nm in zip(got, want, ["dq", "dk", "dv", "dsink"]):
        assert_close(a, b, atol=1e-4, rtol=1e-4, msg=nm)


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("hq,hk,head_block", [(2, 2, 1), (4, 1, 4), (2, 2, 2)])
def test_one_q_block_every_step_revisits_the_tile(hq, hk, head_block, grid):
    """A mask with one q block: every entry of the k-major walk names the
    same dq tile, so (head-batched, and per head at group 1) the tile
    stays in its VMEM slot from the first step to the last and makes one
    round trip."""
    mask = ([(0, 60)], [(0, 256)], [T.FULL])
    meta = build_block_meta(
        *mask[:2], [t.value for t in mask[2]], 64, TOKENS, block_q=64,
        block_k=64,
    )
    assert {q for _k, q in _walk(meta)} == {0} and len(_walk(meta)) >= 4
    x = _operands(hq, hk, 32)
    short = {n: (a[:64] if n in ("q", "do", "w") else a) for n, a in x.items()}
    qr, kr, ts = mask
    want = _grads(
        lambda q, k, v, sink: ref_attn_from_ranges(
            q, k, v, qr, kr, ts, sink=sink
        )[:2],
        short,
    )
    got = _grads(
        lambda q, k, v, sink: flex_flash_attn_func(
            q, k, v, qr, kr, ts, sink=sink, block_q=64, block_k=64,
            head_block=head_block, grid=grid, interpret=True,
        ),
        short,
    )
    for a, b, nm in zip(got, want, ["dq", "dk", "dv", "dsink"]):
        assert_close(a, b, atol=1e-4, rtol=1e-4, msg=nm)


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("hq,hk,head_block", [(2, 2, 1), (8, 2, 4), (2, 2, 2)])
def test_a_column_boundary_where_the_next_entry_names_the_same_q_block(
    hq, hk, head_block, grid
):
    """Two slices that split q block 0 against every key, and one of the
    other q blocks against the first k block: column 0 walks every q
    block, the columns after it q block 0 alone, twice. So the walk holds
    a column boundary where ``qblk[e + 1] == qblk[e]`` and, inside a
    column, two entries on one tile. The tile is kept in VMEM, not read
    while its write is in flight."""
    mask = (
        [(0, 30), (30, 64), (64, 256)],
        [(0, 256), (0, 256), (0, 64)],
        [T.FULL, T.FULL, T.FULL],
    )
    meta = build_block_meta(
        *mask[:2], [t.value for t in mask[2]], TOKENS, TOKENS, block_q=64,
        block_k=64,
    )
    walk = _walk(meta)
    same_q = [a for a, b in zip(walk, walk[1:]) if a[1] == b[1]]
    assert any(a[0] != b[0] and a[1] == b[1] for a, b in zip(walk, walk[1:]))
    assert any(a == b for a, b in zip(walk, walk[1:])) and same_q
    _check(meta, mask, hq, hk, head_block, grid)


@pytest.mark.parametrize("hq,hk,head_block", [(2, 2, 1), (8, 2, 4), (2, 2, 2)])
def test_a_row_major_plan_with_dead_steps_touches_nothing_in_them(
    hq, hk, head_block
):
    """Columns of 1 to 4 entries on the row-major grid, padded as a rank's
    tables are (levelled sentinel entries): a dead step starts and waits
    for no copy, the tile read ahead in a column's last live step is the
    next column's first, and the padded entries add zero to q block 0."""
    mask = ([(0, 250)], [(0, 250)], [T.CAUSAL])
    meta = build_block_meta(
        *mask[:2], [t.value for t in mask[2]], TOKENS, TOKENS, block_q=64,
        block_k=64, entry_pad=1,
    )
    counts = np.bincount(meta.bwd_k_block)
    assert counts.min() < meta.bwd_steps  # the grid has dead steps
    padded = pad_block_meta(
        meta, meta.num_fwd_entries + 3, meta.num_bwd_entries + 3,
        meta.num_slices + 1,
    )
    for tables in (meta, padded):
        _check(tables, mask, hq, hk, head_block, "row_major")


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
def test_dq_leaves_the_kernel_in_float32_and_is_rounded_once(grid, monkeypatch):
    """dq is the kernel's third output, float32 [hq, tqp, d], aliased to a
    zero-filled operand; rows no entry covers stay exactly zero; the
    caller gets it in q's dtype."""
    seen = {}
    bwd_pallas = fa._bwd_pallas

    def spy(q, k, v, do, lse, delta, tables, params):
        dk, dv, dq = bwd_pallas(q, k, v, do, lse, delta, tables, params)
        seen.update(dq=dq, delta=delta, lse=lse)
        return dk, dv, dq

    monkeypatch.setattr(fa, "_bwd_pallas", spy)
    qr, kr, ts = [(0, 100)], [(0, 100)], [T.CAUSAL]
    x = _operands(4, 2, 32)
    x = {n: a.astype(jnp.bfloat16) if n in "qkv" else a for n, a in x.items()}
    dq, _dk, _dv, _ds = _grads(
        lambda q, k, v, sink: flex_flash_attn_func(
            q, k, v, qr, kr, ts, sink=sink, block_q=64, block_k=64,
            head_block=2, grid=grid, interpret=True,
        ),
        x,
    )
    assert seen["dq"].dtype == jnp.float32 and dq.dtype == jnp.bfloat16
    assert seen["dq"].shape == (4, TOKENS, 32)
    np.testing.assert_array_equal(
        np.asarray(dq, np.float32),
        np.asarray(
            jnp.transpose(seen["dq"], (1, 0, 2)).astype(jnp.bfloat16),
            np.float32,
        ),
    )
    assert not np.asarray(seen["dq"])[:, 100:].any()
    for nm in ("lse", "delta"):  # what _bwd_p_ds reads at that shape
        stat = np.asarray(seen[nm])
        assert stat.shape == (4, TOKENS, fa.LANES) and stat.dtype == np.float32
        np.testing.assert_array_equal(
            stat, np.broadcast_to(stat[..., :1], stat.shape), err_msg=nm
        )
