"""Pallas flex-flash-attention vs jnp oracle (fwd + bwd), CPU interpret mode.

Model: reference tests/test_attn/test_flex_flash_attn.py — kernel vs oracle
over a grid of mask scenarios × head configs × features.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from magiattention_tpu.common import AttnMaskType
from magiattention_tpu.ops import build_block_meta, flex_flash_attn_func
from magiattention_tpu.ops.block_meta import SLICE_FIELDS, pad_block_meta
from magiattention_tpu.testing import assert_close, ref_attn_from_ranges

F = AttnMaskType.FULL
C = AttnMaskType.CAUSAL
I = AttnMaskType.INVCAUSAL
B = AttnMaskType.BICAUSAL

# mask scenarios: (name, tq, tk, q_ranges, k_ranges, types)
SCENARIOS = [
    ("dense_full_256", 256, 256, [(0, 256)], [(0, 256)], [F]),
    ("dense_causal_256", 256, 256, [(0, 256)], [(0, 256)], [C]),
    ("unaligned_causal", 200, 200, [(0, 200)], [(0, 200)], [C]),
    (
        "varlen_causal",
        320,
        320,
        [(0, 100), (100, 256), (256, 320)],
        [(0, 100), (100, 256), (256, 320)],
        [C, C, C],
    ),
    (
        "varlen_full",
        256,
        256,
        [(0, 96), (96, 256)],
        [(0, 96), (96, 256)],
        [F, F],
    ),
    (
        "mixed_types",
        256,
        256,
        [(0, 64), (64, 128), (128, 192), (192, 256)],
        [(0, 128), (0, 64), (64, 200), (100, 256)],
        [C, F, I, B],
    ),
    (
        "q_overlap",  # two slices share q rows (multi-k attention)
        128,
        256,
        [(0, 128), (32, 96)],
        [(0, 128), (128, 256)],
        [C, F],
    ),
    ("uncovered_rows", 256, 256, [(0, 100)], [(0, 100)], [C]),
    ("cross_attn_rect", 128, 384, [(0, 128)], [(0, 384)], [C]),
    (
        "sliding_window_ish",
        256,
        256,
        [(0, 64), (64, 128), (128, 192), (192, 256)],
        [(0, 64), (32, 128), (96, 192), (160, 256)],
        [C, C, C, C],
    ),
]


def _rand_qkv(tq, tk, hq, hk, d, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((tq, hq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((tk, hk, d)), dtype)
    v = jnp.asarray(rng.standard_normal((tk, hk, d)), dtype)
    return q, k, v


@pytest.mark.parametrize("name,tq,tk,qr,kr,ts", SCENARIOS, ids=[s[0] for s in SCENARIOS])
@pytest.mark.parametrize("hq,hk", [(2, 2), (4, 2)])
def test_fwd_matches_oracle(name, tq, tk, qr, kr, ts, hq, hk):
    d = 128
    q, k, v = _rand_qkv(tq, tk, hq, hk, d)
    out, lse = flex_flash_attn_func(q, k, v, qr, kr, ts, block_q=64, block_k=64)
    ref_out, ref_lse, _ = ref_attn_from_ranges(q, k, v, qr, kr, ts)
    assert_close(out, ref_out, atol=2e-5, rtol=2e-5, msg=f"{name} out")
    # lse: compare only finite entries; -inf rows must agree exactly
    np.testing.assert_array_equal(
        np.isneginf(np.asarray(lse)), np.isneginf(np.asarray(ref_lse))
    )
    finite = ~np.isneginf(np.asarray(ref_lse))
    assert_close(
        np.asarray(lse)[finite], np.asarray(ref_lse)[finite], atol=2e-5, rtol=2e-5,
        msg=f"{name} lse",
    )


@pytest.mark.parametrize(
    "name,tq,tk,qr,kr,ts",
    [s for s in SCENARIOS if s[0] in (
        "dense_causal_256", "varlen_causal", "mixed_types", "q_overlap",
        "uncovered_rows", "unaligned_causal",
    )],
    ids=lambda s: s if isinstance(s, str) else "",
)
def test_bwd_matches_oracle(name, tq, tk, qr, kr, ts):
    hq, hk, d = 4, 2, 64
    q, k, v = _rand_qkv(tq, tk, hq, hk, d, seed=1)
    do = jnp.asarray(
        np.random.default_rng(2).standard_normal((tq, hq, d)), jnp.float32
    )

    def f(q, k, v):
        out, _ = flex_flash_attn_func(q, k, v, qr, kr, ts, block_q=64, block_k=64)
        return (out * do).sum()

    def f_ref(q, k, v):
        out, _, _ = ref_attn_from_ranges(q, k, v, qr, kr, ts)
        return (out * do).sum()

    dq, dk, dv = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    rq, rk, rv = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    assert_close(dq, rq, atol=5e-5, rtol=5e-5, msg=f"{name} dq")
    assert_close(dk, rk, atol=5e-5, rtol=5e-5, msg=f"{name} dk")
    assert_close(dv, rv, atol=5e-5, rtol=5e-5, msg=f"{name} dv")


def test_softcap_fwd_bwd():
    qr, kr, ts = [(0, 128)], [(0, 128)], [C]
    q, k, v = _rand_qkv(128, 128, 2, 2, 64, seed=3)
    do = jnp.asarray(np.random.default_rng(4).standard_normal((128, 2, 64)), jnp.float32)
    out, lse = flex_flash_attn_func(q, k, v, qr, kr, ts, softcap=30.0, block_q=64, block_k=64)
    ref_out, ref_lse, _ = ref_attn_from_ranges(q, k, v, qr, kr, ts, softcap=30.0)
    assert_close(out, ref_out, atol=2e-5, rtol=2e-5)

    g = jax.grad(
        lambda q, k, v: (
            flex_flash_attn_func(q, k, v, qr, kr, ts, softcap=30.0, block_q=64, block_k=64)[0] * do
        ).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    gr = jax.grad(
        lambda q, k, v: (
            ref_attn_from_ranges(q, k, v, qr, kr, ts, softcap=30.0)[0] * do
        ).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b, n in zip(g, gr, "qkv"):
        assert_close(a, b, atol=5e-5, rtol=5e-5, msg=f"softcap d{n}")


def test_sink_fwd_bwd():
    qr, kr, ts = [(0, 128)], [(0, 128)], [C]
    hq = 4
    q, k, v = _rand_qkv(128, 128, hq, 2, 64, seed=5)
    sink = jnp.asarray([0.5, -0.3, 1.2, 0.0], jnp.float32)
    out, lse = flex_flash_attn_func(q, k, v, qr, kr, ts, sink=sink, block_q=64, block_k=64)
    ref_out, ref_lse, _ = ref_attn_from_ranges(q, k, v, qr, kr, ts, sink=sink)
    assert_close(out, ref_out, atol=2e-5, rtol=2e-5)
    assert_close(lse, ref_lse, atol=2e-5, rtol=2e-5)

    do = jnp.asarray(np.random.default_rng(6).standard_normal((128, hq, 64)), jnp.float32)
    g = jax.grad(
        lambda q, k, v, s: (
            flex_flash_attn_func(q, k, v, qr, kr, ts, sink=s, block_q=64, block_k=64)[0] * do
        ).sum(),
        argnums=(0, 1, 2, 3),
    )(q, k, v, sink)
    gr = jax.grad(
        lambda q, k, v, s: (
            ref_attn_from_ranges(q, k, v, qr, kr, ts, sink=s)[0] * do
        ).sum(),
        argnums=(0, 1, 2, 3),
    )(q, k, v, sink)
    for a, b, n in zip(g, gr, ["dq", "dk", "dv", "dsink"]):
        assert_close(a, b, atol=5e-5, rtol=5e-5, msg=f"sink {n}")


def test_max_logits():
    qr, kr, ts = [(0, 128)], [(0, 128)], [C]
    q, k, v = _rand_qkv(128, 128, 2, 2, 64, seed=7)
    out, lse, ml = flex_flash_attn_func(
        q, k, v, qr, kr, ts, return_max_logits=True, block_q=64, block_k=64
    )
    _, _, ref_ml = ref_attn_from_ranges(q, k, v, qr, kr, ts)
    assert_close(ml, ref_ml, atol=2e-5, rtol=2e-5)


def test_block_meta_tables():
    meta = build_block_meta([(0, 256)], [(0, 256)], [C.value], 256, 256, block_q=64, block_k=64)
    # causal 4x4 blocks → lower-triangular block pattern: 4+3+2+1 = 10 entries
    assert meta.num_fwd_entries >= 10
    real = meta.fwd_slice_id < meta.num_slices
    assert int(real.sum()) == 10
    # every q block covered, monotone q-major order
    assert set(meta.fwd_q_block.tolist()) == {0, 1, 2, 3}
    assert (np.diff(meta.fwd_q_block) >= 0).all()
    assert (np.diff(meta.bwd_k_block) >= 0).all()
    assert meta.total_area == 256 * 257 // 2
    assert meta.slice_bounds.shape[0] == 2 * SLICE_FIELDS


def test_bf16_reasonable():
    qr, kr, ts = [(0, 256)], [(0, 256)], [C]
    q, k, v = _rand_qkv(256, 256, 2, 2, 64, seed=8)
    out16, _ = flex_flash_attn_func(
        q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v.astype(jnp.bfloat16),
        qr, kr, ts, block_q=64, block_k=64,
    )
    ref_out, _, _ = ref_attn_from_ranges(q, k, v, qr, kr, ts)
    assert_close(out16.astype(jnp.float32), ref_out, atol=3e-2, rtol=3e-2)


# the all-8 shape re-tiered slow for the 870s tier-1 budget (ISSUE 16);
# (8,2,4) keeps GQA head-batching live and (4,4,2) the partial block
@pytest.mark.parametrize(
    "hq,hk,hb",
    [pytest.param(8, 8, 8, marks=pytest.mark.slow), (8, 2, 4), (4, 4, 2)],
)
def test_head_batched_kernel(hq, hk, hb):
    """head_block>1 path (batched MXU calls) vs oracle, incl. bwd."""
    tq = 256
    d = 64
    q, k, v = _rand_qkv(tq, tq, hq, hk, d, seed=9)
    qr, kr, ts = [(0, 100), (100, 256)], [(0, 100), (100, 256)], [C, C]
    out, lse = flex_flash_attn_func(
        q, k, v, qr, kr, ts, block_q=64, block_k=64, head_block=hb
    )
    ref_out, ref_lse, _ = ref_attn_from_ranges(q, k, v, qr, kr, ts)
    assert_close(out, ref_out, atol=2e-5, rtol=2e-5, msg=f"hb{hb}")
    finite = ~np.isneginf(np.asarray(ref_lse))
    assert_close(
        np.asarray(lse)[finite], np.asarray(ref_lse)[finite],
        atol=2e-5, rtol=2e-5,
    )
    do = jnp.asarray(
        np.random.default_rng(10).standard_normal((tq, hq, d)), jnp.float32
    )
    g = jax.grad(
        lambda q, k, v: (
            flex_flash_attn_func(
                q, k, v, qr, kr, ts, block_q=64, block_k=64, head_block=hb
            )[0] * do
        ).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    gr = jax.grad(
        lambda q, k, v: (ref_attn_from_ranges(q, k, v, qr, kr, ts)[0] * do).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b, nm in zip(g, gr, "qkv"):
        assert_close(a, b, atol=5e-5, rtol=5e-5, msg=f"hb{hb} d{nm}")


def test_large_block_escalation_config():
    """The (512, 2048) escalation rung (128k-dense smem fit) computes the
    same results as default blocking."""
    t, hq, hk, d = 4096, 2, 2, 32
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((t, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((t, hk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((t, hk, d)), jnp.float32)
    qr, kr, ts = [(0, t)], [(0, t)], [C]
    out, lse = flex_flash_attn_func(
        q, k, v, qr, kr, ts, block_q=512, block_k=2048, head_block=1
    )[:2]
    ref, ref_lse, _ = ref_attn_from_ranges(q, k, v, qr, kr, ts)
    assert_close(out, ref, atol=3e-5, rtol=3e-5)
    assert_close(lse, ref_lse, atol=3e-5, rtol=3e-5)


def test_auto_block_config_prefers_large_blocks_at_long_seq():
    """>= 16k tokens: the (1024, 1024) square rung is preferred (round-5
    chained on-chip winner for fwd AND fwd+bwd at 64k causal on the
    row-major grid); below 16k the low-latency (128, 512) rung stays
    first wherever its steps do not stream K and V at the HBM's pace (at
    GQA group 1 they do, and the next rung is given: ISSUE 35); oversized
    masks still escalate to (512, 2048)."""
    from magiattention_tpu.ops.flex_attn import auto_block_config

    # short dense causal -> small rung
    assert auto_block_config([(0, 8192)], [(0, 8192)], 64, 8) == (128, 512, 8)
    assert auto_block_config([(0, 8192)], [(0, 8192)], 8, 8) == (256, 512, 8)
    # long dense causal -> measured winner
    assert auto_block_config([(0, 32768)], [(0, 32768)], 8, 8)[:2] == (
        1024,
        1024,
    )
    # 256k dense: only the k-wide escalation rung fits the entry budget
    assert auto_block_config([(0, 262144)], [(0, 262144)], 8, 8)[:2] == (
        512,
        2048,
    )
    # fixed blocks are always honored
    assert auto_block_config(
        [(0, 32768)], [(0, 32768)], 8, 8, fixed_block_q=128, fixed_block_k=512
    )[:2] == (128, 512)


def test_auto_block_config_fixed_blocks_keep_their_head_block():
    """Caller-fixed small blocks at long seqlen keep the hb measured for
    that blocking (8), not the long-seq rung's hb."""
    from magiattention_tpu.ops.flex_attn import auto_block_config

    assert auto_block_config(
        [(0, 32768)], [(0, 32768)], 8, 8,
        fixed_block_q=128, fixed_block_k=512,
    ) == (128, 512, 8)


def test_auto_block_config_partially_fixed_blocks_key_hb_on_block_k():
    """When only one block dimension is fixed, the mixed (bq, bk) pair is
    not a measured rung; head_block falls back to the hb measured for the
    effective block_k (the K/V double-buffer width the hb values are
    sized against)."""
    from magiattention_tpu.ops.flex_attn import auto_block_config

    # fixed small block_k at long seqlen: bq iterates to 1024 (square
    # rung first); (1024, 512) is unmeasured, so hb keys on block_k -> 4
    assert auto_block_config(
        [(0, 32768)], [(0, 32768)], 8, 8, fixed_block_k=512
    ) == (1024, 512, 4)
    # a mixed pair no rung measures (bq=512 fixed, bk=512): hb keys on
    # block_k alone -> 4, not the iterating wide rung's 2/1
    assert auto_block_config(
        [(0, 32768)], [(0, 32768)], 8, 8, fixed_block_q=512, fixed_block_k=512
    )[2] == 4
    # fixed small block_q at long seqlen: bk iterates to 1024; the
    # (128, 1024) pair is unmeasured, so hb keys on block_k -> the most
    # conservative measured hb for bk=1024 (min of 2 and 1 = 1)
    assert auto_block_config(
        [(0, 32768)], [(0, 32768)], 8, 8, fixed_block_q=128
    ) == (128, 1024, 1)


def test_auto_block_config_long_keys_short_queries():
    """Cross-attn mask: 4k queries over 128k keys is in the grid-bound
    regime and must use a wide rung."""
    from magiattention_tpu.ops.flex_attn import auto_block_config

    assert auto_block_config([(0, 4096)], [(0, 131072)], 8, 8)[:2] == (
        1024,
        1024,
    )


# -- the head-batched backward (ISSUE 25) -----------------------------------
# Four documents, one of each mask type, none aligned to the 64-token
# blocks; rows 300..384 attend to nothing, so q block 5 has no entry at all.
_HB_T = 384
_HB_MASK = (
    [(0, 90), (90, 170), (170, 250), (250, 300)],
    [(0, 90), (90, 170), (150, 250), (230, 300)],
    [F, C, I, B],
)


@functools.lru_cache(maxsize=None)  # the references repeat across cases
def _hb_bwd_grads(
    hq, hk, head_block, softcap, traced, grid="row_major", pad=0, d=32
):
    """dq, dk, dv, dsink of a loss that reads out AND lse (a non-zero lse
    cotangent) through the Pallas kernels at ``head_block`` on ``grid``.
    ``traced``: the tables are jit arguments and the grid extents come from
    ``FlexAttnParams.fwd_steps``/``bwd_steps``, as on the keyed path;
    ``pad`` more entries a table, as ``StageTables.from_rank_metas`` pads
    the ranks' tables to the longest (``pad_block_meta``)."""
    from magiattention_tpu.ops import flex_attn as fa

    qr, kr, ts = _HB_MASK
    q, k, v = _rand_qkv(_HB_T, _HB_T, hq, hk, d, seed=11)
    rng = np.random.default_rng(12)
    do = jnp.asarray(rng.standard_normal((_HB_T, hq, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((_HB_T, hq)), jnp.float32)
    sink = jnp.asarray(rng.standard_normal(hq), jnp.float32)

    def loss_of(out, lse):
        return (out * do).sum() + (jnp.where(jnp.isneginf(lse), 0.0, lse) * w).sum()

    if head_block is None:  # the jnp oracle

        def loss(q, k, v, sink):
            out, lse, _ = ref_attn_from_ranges(
                q, k, v, qr, kr, ts, sink=sink, softcap=softcap
            )
            return loss_of(out, lse)

        return jax.grad(loss, argnums=(0, 1, 2, 3))(q, k, v, sink)

    if not traced:

        def loss(q, k, v, sink):
            out, lse = flex_flash_attn_func(
                q, k, v, qr, kr, ts, sink=sink, softcap=softcap,
                block_q=64, block_k=64, head_block=head_block, grid=grid,
                interpret=True,
            )
            return loss_of(out, lse)

        return jax.grad(loss, argnums=(0, 1, 2, 3))(q, k, v, sink)

    meta = build_block_meta(
        qr, kr, [t.value for t in ts], _HB_T, _HB_T, block_q=64, block_k=64
    )
    if pad:
        meta = pad_block_meta(
            meta, meta.num_fwd_entries + pad, meta.num_bwd_entries + pad,
            meta.num_slices + 2,
        )
    params = fa.FlexAttnParams(
        block_q=64, block_k=64, scale=d**-0.5, softcap=float(softcap),
        has_sink=True, out_dtype="float32", interpret=True,
        head_block=head_block, fwd_steps=meta.fwd_steps,
        bwd_steps=meta.bwd_steps, grid=grid,
    )

    def loss(q, k, v, sink, ftab, btab):
        out_h, lse_h, _ = fa.flex_attn_headmajor(
            jnp.transpose(q, (1, 0, 2)), jnp.transpose(k, (1, 0, 2)),
            jnp.transpose(v, (1, 0, 2)), ftab, btab, params, sink=sink,
        )
        return loss_of(jnp.transpose(out_h, (1, 0, 2)), lse_h.T)

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))(
        q, k, v, sink, fa.fwd_tables(meta), fa.bwd_tables(meta)
    )


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("traced", [False, True], ids=["concrete", "traced"])
@pytest.mark.parametrize("softcap", [0.0, 8.0], ids=["nocap", "softcap"])
@pytest.mark.parametrize("heads", [1, 2], ids=["hb=g", "hb=2g"])
@pytest.mark.parametrize("group", [1, 4, 8])
def test_head_batched_bwd_matches_per_head_and_oracle(
    group, heads, softcap, traced, grid
):
    """dq, dk, dv, dsink of the head-batched dq / dkv kernels, on the
    row-major and on the compact grid, against the per-head kernels and
    against the jnp oracle."""
    hk = 2
    hq = hk * group
    got = _hb_bwd_grads(hq, hk, heads * group, softcap, traced, grid)
    per_head = _hb_bwd_grads(hq, hk, 1, softcap, traced, grid)
    oracle = _hb_bwd_grads(hq, hk, None, softcap, False)
    for a, b, c, nm in zip(got, per_head, oracle, ["dq", "dk", "dv", "dsink"]):
        assert np.isfinite(np.asarray(a)).all(), nm
        assert_close(a, b, atol=2e-5, rtol=2e-5, msg=f"{nm} vs per-head")
        assert_close(a, c, atol=5e-5, rtol=5e-5, msg=f"{nm} vs oracle")
    # rows 300.. attend to nothing: their dq is exactly zero
    assert not np.asarray(got[0])[300:].any()


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("group,heads", [(1, 2), (4, 1), (8, 1)])
def test_head_batched_kernels_on_per_rank_padded_tables(group, heads, grid):
    """Tables padded as the ranks' tables are stacked (``pad_block_meta``:
    sentinel-slice entries levelled over the blocks): a padded entry is a
    live step of the compact grid, and its empty mask adds nothing."""
    hk = 2
    hq = hk * group
    got = _hb_bwd_grads(hq, hk, heads * group, 0.0, True, grid, pad=13)
    unpadded = _hb_bwd_grads(hq, hk, heads * group, 0.0, True, grid)
    oracle = _hb_bwd_grads(hq, hk, None, 0.0, False)
    for a, b, c, nm in zip(got, unpadded, oracle, ["dq", "dk", "dv", "dsink"]):
        assert_close(a, b, atol=2e-6, rtol=2e-6, msg=f"{nm} vs unpadded")
        assert_close(a, c, atol=5e-5, rtol=5e-5, msg=f"{nm} vs oracle")


# -- the forward's softmax state (ISSUE 29) ---------------------------------
# ``_fwd_update`` keeps no -inf inside a step (a finite mask value, a lazy
# per-lane row sum) and ``_fwd_finalize`` restores the public convention.
# The mask puts every kind of row into one q block of 64 (blocks of 64 x
# 128 and 64 x 256, so the row sum's lane-aligned path runs too):
#   rows   0..32   live in k block 0, fully masked in every later entry;
#   rows  32..64   fully masked in their first entry (entries), live in k
#                  [256, 384) only: what they gathered before is garbage
#                  and must be multiplied by exactly 0;
#   rows  64..100  no slice at all, in a q block that has entries;
#   rows 100..128  causal against k [384, 512);
#   rows 128..192  a q block with no entry of its own.
_STATE_T, _STATE_TK = 192, 512
_STATE_MASK = (
    [(0, 32), (32, 64), (100, 128)],
    [(0, 128), (256, 384), (384, 512)],
    [F, F, C],
)
_STATE_UNCOVERED = np.r_[64:100, 128:192]


def _state_case(
    head_block, grid, block_k, with_sink, softcap, amp=1.0, sign=0, d=32,
    with_oracle=True,
):
    """(kernel results, ``_fwd_jnp``'s) as dicts of out, lse, rowmax, dq,
    dk, dv (and dsink): the Pallas forward and backward in interpret mode
    against the dense jnp backend on the same tables. ``amp`` scales q and
    k; ``sign`` -1 makes every logit negative; ``with_oracle=False`` leaves the
    second dict out (None)."""
    from magiattention_tpu.ops import flex_attn as fa

    hq, hk = 4, 2
    qr, kr, ts = _STATE_MASK
    q, k, v = _rand_qkv(_STATE_T, _STATE_TK, hq, hk, d, seed=29)
    if sign:
        q, k = jnp.abs(q), sign * jnp.abs(k)
    q, k = q * amp, k * amp
    rng = np.random.default_rng(30)
    do = jnp.asarray(rng.standard_normal((hq, _STATE_T, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((hq, _STATE_T)), jnp.float32)
    sink = jnp.asarray(rng.standard_normal(hq), jnp.float32)
    meta = build_block_meta(
        qr, kr, [t.value for t in ts], _STATE_T, _STATE_TK,
        block_q=64, block_k=block_k,
    )
    params = fa.FlexAttnParams(
        block_q=64, block_k=block_k, scale=d**-0.5, softcap=float(softcap),
        has_sink=with_sink, out_dtype="float32", interpret=True,
        head_block=head_block, fwd_steps=meta.fwd_steps,
        bwd_steps=meta.bwd_steps, grid=grid,
    )
    ftab, btab = fa.fwd_tables(meta), fa.bwd_tables(meta)
    qh, kh, vh = (jnp.transpose(x, (1, 0, 2)) for x in (q, k, v))

    def kernel(q, k, v, sink):
        return fa.flex_attn_headmajor(
            q, k, v, ftab, btab, params, sink=sink if with_sink else None
        )

    def oracle(q, k, v, sink):
        return fa._fwd_jnp(q, k, v, sink.reshape(hq, 1), ftab, params)

    def run(fn):
        def loss(q, k, v, sink):
            out, lse, rowmax = fn(q, k, v, sink)
            return (out * do).sum() + (
                jnp.where(jnp.isneginf(lse), 0.0, lse) * w
            ).sum(), (out, lse, rowmax)

        (_, (out, lse, rowmax)), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3), has_aux=True
        )(qh, kh, vh, sink)
        res = dict(
            out=out, lse=lse, rowmax=rowmax,
            dq=grads[0], dk=grads[1], dv=grads[2],
        )
        if with_sink:
            res["dsink"] = grads[3]
        return {n: np.asarray(x) for n, x in res.items()}

    return (
        run(kernel), run(oracle) if with_oracle else None, np.asarray(sink)
    )


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("head_block", [1, 4], ids=["per-head", "hb=4"])
@pytest.mark.parametrize("block_k", [128, 256])
@pytest.mark.parametrize("softcap", [0.0, 8.0], ids=["nocap", "softcap"])
@pytest.mark.parametrize("with_sink", [False, True], ids=["nosink", "sink"])
def test_fwd_softmax_state_rows(with_sink, softcap, block_k, head_block, grid):
    """Rows that are masked first and live later, live first and masked
    later, covered by nothing inside a block that has entries, and in a
    block with none: out, lse, rowmax and every gradient are
    ``_fwd_jnp``'s, and -inf stands exactly where the oracle has it."""
    got, ref, sink = _state_case(head_block, grid, block_k, with_sink, softcap)
    for nm in ref:
        assert np.isfinite(got[nm][np.isfinite(ref[nm])]).all(), nm
        np.testing.assert_array_equal(
            np.isneginf(got[nm]), np.isneginf(ref[nm]), err_msg=nm
        )
        fin = np.isfinite(ref[nm])
        assert_close(got[nm][fin], ref[nm][fin], atol=5e-5, rtol=5e-5, msg=nm)
    un = _STATE_UNCOVERED
    assert not got["out"][:, un].any() and not got["dq"][:, un].any()
    assert np.isneginf(got["rowmax"][:, un]).all()
    if with_sink:  # a row that attends to nothing but the sink
        np.testing.assert_array_equal(
            got["lse"][:, un], np.broadcast_to(sink[:, None], (4, un.size))
        )
    else:
        assert np.isneginf(got["lse"][:, un]).all()


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("head_block", [1, 4], ids=["per-head", "hb=4"])
@pytest.mark.parametrize("sign", [0, -1], ids=["mixed", "all-negative"])
def test_fwd_finite_mask_value_never_meets_a_logit(sign, head_block, grid):
    """Logits of about +-1e4 after the scale, the useful edge of float32
    for a softmax: the finite in-step mask value (-2.4e38) stays far under
    them, so a row whose every live logit is hugely negative still counts
    as covered, and masked columns weigh exactly nothing."""
    got, ref, _ = _state_case(head_block, grid, 256, False, 0.0, amp=50.0, sign=sign)
    assert 3e3 < np.abs(ref["rowmax"][np.isfinite(ref["rowmax"])]).max() < 1e5
    for nm in ("out", "lse", "rowmax"):
        np.testing.assert_array_equal(
            np.isneginf(got[nm]), np.isneginf(ref[nm]), err_msg=nm
        )
        fin = np.isfinite(ref[nm])
        assert_close(got[nm][fin], ref[nm][fin], atol=1e-4, rtol=2e-5, msg=nm)
    np.testing.assert_array_equal(  # the running maximum stays exact
        got["rowmax"], ref["rowmax"]
    )


# -- the backward's P/dS block on whole vregs (ISSUE 31) --------------------
# ``_bwd_p_ds`` uses lse and delta at the lane-replicated (rows, 128) shape
# they arrive in and takes the logit tile 128 lanes at a time. The same
# float32 operations on the same values as the column form it replaced,
# which stays here as the reference.


def _bwd_p_ds_column(s, lse_ref, do_ref, v_ref, delta_ref, params, hb=None):
    """The block as it was until PR 31: lane 0 of lse and of delta sliced
    to (rows, 1) columns, the guard on the column, both broadcast over the
    (rows, bk) tile."""
    from magiattention_tpu.ops.flex_attn import NEG_INF

    def rows(ref):
        if hb is None:
            return ref[0]
        return ref[...].reshape(hb, -1, ref.shape[2])

    nb = s.ndim - 2
    lse = rows(lse_ref)[..., :1]
    lse_safe = jnp.where(lse == NEG_INF, 0.0, lse)
    p = jnp.exp(s - lse_safe)
    dp = jax.lax.dot_general(
        rows(do_ref),
        v_ref[0] if hb is None else v_ref[...],
        dimension_numbers=(
            ((nb + 1,), (nb + 1,)),
            (tuple(range(nb)), tuple(range(nb))),
        ),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - rows(delta_ref)[..., :1])
    if params.softcap > 0.0:
        ds = ds * (1.0 - (s / jnp.float32(params.softcap)) ** 2)
        ds = jnp.where(jnp.isneginf(s), 0.0, ds)
    return p, ds


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("head_block", [1, 4], ids=["per-head", "hb=4"])
@pytest.mark.parametrize("d", [128, 256])
@pytest.mark.parametrize("block_k", [64, 128, 256])
@pytest.mark.parametrize("softcap", [0.0, 8.0], ids=["nocap", "softcap"])
@pytest.mark.parametrize("with_sink", [False, True], ids=["nosink", "sink"])
def test_bwd_block_on_whole_vregs_is_the_column_form(
    with_sink, softcap, block_k, d, head_block, grid, monkeypatch
):
    """dq, dk, dv, dsink of the dq and dkv bodies (per head and
    head-batched, both grids) bit for bit what the column form gives:
    block_k 128 and 256 run the 128-lane slices, 64 the narrow form. The
    loss reads lse too (``delta - dlse``), rows 64..100 and 128..192 have
    ``lse = -inf``: their dq is exactly zero and nothing is non-finite.
    And all of it within the oracle's tolerances."""
    from magiattention_tpu.ops import flex_attn as fa

    got, ref, _ = _state_case(head_block, grid, block_k, with_sink, softcap, d=d)
    traced = []

    def column_form(*args):
        traced.append(1)
        return _bwd_p_ds_column(*args)

    monkeypatch.setattr(fa, "_bwd_p_ds", column_form)
    old, _, _ = _state_case(
        head_block, grid, block_k, with_sink, softcap, d=d, with_oracle=False
    )
    assert traced  # the bodies did trace the reference, not a cached program
    grads = [nm for nm in got if nm.startswith("d")]
    assert grads == ["dq", "dk", "dv"] + ["dsink"] * with_sink
    for nm in grads:
        assert np.isfinite(got[nm]).all(), nm
        np.testing.assert_array_equal(got[nm], old[nm], err_msg=nm)
        assert_close(got[nm], ref[nm], atol=5e-5, rtol=5e-5, msg=nm)
    assert not got["dq"][:, _STATE_UNCOVERED].any()


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("head_block", [1, 4], ids=["per-head", "hb=4"])
@pytest.mark.parametrize("with_sink", [False, True], ids=["nosink", "sink"])
def test_lse_and_delta_arrive_replicated_over_the_lanes(
    with_sink, head_block, grid, monkeypatch
):
    """The contract ``_bwd_p_ds`` leans on: what the backward kernel is
    handed as lse (the differentiated forward's residual, from either
    forward body on either grid) and as delta (made before the kernel,
    ``_bwd_delta``, the lse cotangent folded in) is equal in all 128
    lanes, on covered rows and on rows no entry covers (``-inf``, or the
    sink)."""
    from magiattention_tpu.ops import flex_attn as fa

    seen = {}
    bwd_pallas = fa._bwd_pallas

    def spy(q, k, v, do, lse, delta, tables, params):
        seen.update(lse=np.asarray(lse), delta=np.asarray(delta))
        return bwd_pallas(q, k, v, do, lse, delta, tables, params)

    monkeypatch.setattr(fa, "_bwd_pallas", spy)
    got, _, sink = _state_case(
        head_block, grid, 128, with_sink, 0.0, with_oracle=False
    )
    for nm in ("lse", "delta"):
        x = seen[nm]
        assert x.shape == (4, _STATE_T, fa.LANES) and x.dtype == np.float32
        np.testing.assert_array_equal(
            x, np.broadcast_to(x[..., :1], x.shape), err_msg=nm
        )
    np.testing.assert_array_equal(seen["lse"][..., 0], got["lse"])
    un = _STATE_UNCOVERED
    covered = np.setdiff1d(np.arange(_STATE_T), un)
    assert np.isfinite(seen["lse"][:, covered]).all()
    assert np.isfinite(seen["delta"]).all() and seen["delta"].any()
    np.testing.assert_array_equal(
        seen["lse"][:, un],
        np.broadcast_to(
            sink[:, None, None] if with_sink else -np.inf,
            (4, un.size, fa.LANES),
        ),
    )
