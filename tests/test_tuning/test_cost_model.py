"""Cost model: exact entry counting + canonical rung choices (ISSUE 2)."""

import numpy as np

from magiattention_tpu.ops.block_meta import (
    build_block_meta_general,
    identity_runs,
)
from magiattention_tpu.testing.workloads import mask_families
from magiattention_tpu.tuning import estimate_entries, rank_candidates


def _meta_counts(qr, kr, ts, total, bq, bk):
    """Ground truth from the real table builder (entry_pad=1: no leveled
    pad entries distorting row counts)."""
    slices = np.concatenate(
        [
            np.asarray(qr, np.int64),
            np.asarray(kr, np.int64),
            np.asarray(ts, np.int64)[:, None],
        ],
        axis=1,
    )
    meta = build_block_meta_general(
        slices,
        identity_runs(total),
        identity_runs(total),
        total,
        total,
        block_q=bq,
        block_k=bk,
        entry_pad=1,
    )
    return meta.num_fwd_entries, meta.fwd_steps


def test_estimate_matches_real_table_dense_causal():
    qr, kr, ts = [(0, 2048)], [(0, 2048)], [1]
    for bq, bk in [(128, 128), (128, 512), (256, 512), (512, 512)]:
        entries, steps, _nq = estimate_entries(qr, kr, ts, bq, bk)
        e_true, s_true = _meta_counts(qr, kr, ts, 2048, bq, bk)
        assert entries == e_true, (bq, bk)
        assert steps == s_true, (bq, bk)


def test_estimate_matches_real_table_varlen_mixed():
    qr = [(0, 700), (700, 1500), (1500, 2048)]
    kr = [(0, 700), (600, 1500), (1200, 2048)]
    ts = [1, 0, 2]  # causal, full, inv-causal
    for bq, bk in [(128, 128), (128, 256), (256, 128)]:
        entries, steps, _nq = estimate_entries(qr, kr, ts, bq, bk)
        e_true, s_true = _meta_counts(qr, kr, ts, 2048, bq, bk)
        assert entries == e_true, (bq, bk)
        assert steps == s_true, (bq, bk)


def test_estimate_counts_dummies_for_uncovered_blocks():
    qr, kr, ts = [(0, 128)], [(0, 512)], [0]
    entries, steps, nq = estimate_entries(qr, kr, ts, 128, 512)
    assert (entries, steps, nq) == (1, 1, 1)
    # degenerate slices contribute nothing and don't stretch the extent
    entries2, _, nq2 = estimate_entries(
        [(0, 128), (1024, 1024)], kr + [(0, 0)], [0, 0], 128, 512
    )
    assert (entries2, nq2) == (entries, nq)
    # gap between two live slices -> dummy entries for the hole blocks
    entries3, _, nq3 = estimate_entries(
        [(0, 128), (512, 640)], [(0, 512), (0, 512)], [0, 0], 128, 512
    )
    assert nq3 == 5 and entries3 == 2 + 3  # 2 live + 3 hole dummies


def test_canonical_64k_causal_keeps_square_rung():
    best = rank_candidates([(0, 65536)], [(0, 65536)], [1], 8, 8)[0]
    assert (best.block_q, best.block_k, best.head_block) == (1024, 1024, 1)


def test_regression_16k_varlen_block_causal_escapes_dense_rung():
    """THE ISSUE 2 regression: the static table ran this at 8.4 TF/s on a
    long-seq dense rung; the shape-aware model must select a small tile
    (narrow FULL slices waste most of a 1024-wide tile)."""
    qr, kr, ts = mask_families(16384)["varlen_block_causal"]
    ranked = rank_candidates(qr, kr, ts, 8, 8)
    best = ranked[0]
    assert best.block_q * best.block_k < 1024 * 1024, (
        f"picked dense rung {best.block_q}x{best.block_k}"
    )
    # and the dense rung must be priced strictly worse (beyond tie range)
    dense = next(s for s in ranked if (s.block_q, s.block_k) == (1024, 1024))
    assert dense.cost_seconds > best.cost_seconds * 1.15


def test_16k_swa_prefers_occupancy_over_preference():
    """VERDICT flagged 16k SWA slower in absolute ms than 32k SWA under
    the static long-seq rule; the model keeps SWA on small tiles."""
    qr, kr, ts = mask_families(16384)["swa_causal"]
    best = rank_candidates(qr, kr, ts, 8, 8)[0]
    assert best.block_q * best.block_k < 1024 * 1024


def test_smem_infeasible_masks_escalate_to_wide_rung():
    """Oversized dense masks (nothing fits the entry budget) keep the
    legacy escalation: the k-wide rung launches and the kernel's SMEM
    check owns the error message."""
    ranked = rank_candidates([(0, 262144)], [(0, 262144)], [1], 8, 8)
    assert not any(s.feasible for s in ranked)
    assert (ranked[0].block_q, ranked[0].block_k) == (512, 2048)


def test_shard_constraints_filter_candidates():
    ranked = rank_candidates(
        [(0, 16384)], [(0, 16384)], [1], 8, 8,
        max_block_q=256, max_block_k=512,
    )
    assert ranked
    assert all(s.block_q <= 256 and s.block_k <= 512 for s in ranked)
    # tighter than every rung -> empty
    assert (
        rank_candidates(
            [(0, 16384)], [(0, 16384)], [1], 8, 8, max_block_k=64
        )
        == []
    )


def test_gqa_head_block_snaps_to_group():
    """hb must stay a multiple of the GQA group that divides hq."""
    for s in rank_candidates([(0, 8192)], [(0, 8192)], [1], 8, 2):
        group = 4
        assert s.head_block == 1 or (
            s.head_block % group == 0 and 8 % s.head_block == 0
        )


def test_sparse_rungs_have_zero_dead_slots():
    """ISSUE 15: every sparse-grid candidate prices zero dead steps —
    the compact grid's extent IS the entry count."""
    qr, kr, ts = _varlen_16k()
    ranked = rank_candidates(qr, kr, ts, 8, 8)
    sparse = [s for s in ranked if s.grid == "sparse"]
    assert sparse, "sparse rungs missing from the ranking"
    for s in sparse:
        assert s.dead_slots == 0
        assert s.grid_slots == s.live_slots


def test_heterogeneous_headline_resolves_to_sparse_grid():
    """The 16k varlen block-causal headline (the 8.44 TF/s regression)
    must pick a sparse rung with >= 6x fewer grid slots than the best
    row-major candidate, and dense 64k causal must NOT."""
    qr, kr, ts = _varlen_16k()
    best = rank_candidates(qr, kr, ts, 8, 8, generation="v5e")[0]
    rm = rank_candidates(
        qr, kr, ts, 8, 8, generation="v5e", include_sparse=False
    )[0]
    assert best.grid == "sparse"
    assert best.dead_slots == 0
    assert rm.grid_slots >= 6 * best.grid_slots
    dense = rank_candidates(
        [(0, 65536)], [(0, 65536)], [1], 8, 8, generation="v5e"
    )[0]
    assert dense.grid == "row_major"
    assert (dense.block_q, dense.block_k) == (1024, 1024)


def test_include_sparse_false_restores_row_major_only_ranking():
    qr, kr, ts = _varlen_16k()
    ranked = rank_candidates(qr, kr, ts, 8, 8, include_sparse=False)
    assert ranked and all(s.grid == "row_major" for s in ranked)


def _varlen_16k():
    from magiattention_tpu.testing.workloads import varlen_block_causal

    sl = varlen_block_causal(16384)
    return (
        [(a, b) for a, b, *_ in sl],
        [(s[2], s[3]) for s in sl],
        [s[4] for s in sl],
    )
