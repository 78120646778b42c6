"""Cost model: exact entry counting + canonical rung choices (ISSUE 2)."""

import functools
import os

import numpy as np
import pytest

from magiattention_tpu.ops.block_meta import (
    build_block_meta,
    build_block_meta_general,
    identity_runs,
)
from magiattention_tpu.ops.flex_attn import (
    _AUTO_BLOCK_CONFIGS,
    _MAX_SMEM_ENTRIES,
    _est_entries,
)
from magiattention_tpu.testing.workloads import mask_families
from magiattention_tpu.tuning import (
    estimate_entries,
    rank_candidates,
    reset_tuning_cache,
    select_block_config,
)
from magiattention_tpu.tuning.cost_model import (
    HBM_PRICE_SHARE,
    smem_entries,
    step_bytes,
)
from magiattention_tpu.utils.cost import TPU_PEAK_SPECS

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


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
    must pick a sparse rung with >= 6x fewer grid slots than the row-major
    (128, 512, 8) the 8.44 TF/s was measured on (at 8 q = 8 kv heads the
    row-major ranking itself now leaves that rung: its steps stream K and V
    at the HBM's pace, ISSUE 35), and dense 64k causal must NOT."""
    qr, kr, ts = _varlen_16k()
    best = rank_candidates(qr, kr, ts, 8, 8, generation="v5e")[0]
    rm = next(
        s
        for s in rank_candidates(
            qr, kr, ts, 8, 8, generation="v5e", include_sparse=False
        )
        if (s.block_q, s.block_k) == (128, 512)
    )
    assert rm.bound == "hbm"
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


# -- the SMEM test's entry count (ISSUE 33) --------------------------------
# the benchmark's four mask families at 65,536 tokens, and band masks of
# three widths; the cp=4 cells' masks at 262,144
_MASK_SPECS = {
    "packed": "magi64x8-attn-64k-varlen",
    "causal": "magi64x8-attn-64k-causal",
    "chunk_causal": "magi64x8-attn-64k-chunkcausal",
    "swa256": {"type": "swa_causal", "window": 256},
    "swa1024": "magi64x8-attn-64k-swa1024",
    "swa4096": {"type": "swa_causal", "window": 4096},
    "cp4_packed": "magi64x8-attn-cp4-256k-varlen",
    "cp4_causal": "magi64x8-attn-cp4-256k-causal",
    "packed16k": "glm47flash-train-16k-packed",  # Mistral's and Ouro's too
    "packed32k": "trinitymini-train-32k-packed",
}
# (exact entries, the old bounding-box bound) where the issue names them
_PINNED = {
    ("swa1024", 128, 512): (1524, 65172),
    ("swa1024", 256, 512): (762, 32652),
    ("swa1024", 1024, 1024): (127, 4164),
    ("causal", 128, 512): (33024, 66177),
}


@functools.lru_cache(maxsize=None)
def _bench_mask(family: str):
    from benchmarks import harness, masks

    spec, total = _MASK_SPECS[family], 65536
    if isinstance(spec, str):
        traffic = harness.load_cell(ROOT, spec).traffic
        spec, total = traffic["mask"], int(traffic["total_tokens"])
    m = masks.build_mask(spec, total)
    return m.q_ranges, m.k_ranges, m.types, total


@pytest.mark.parametrize(
    "bq,bk", list(dict.fromkeys(c[:2] for c in _AUTO_BLOCK_CONFIGS))
)
@pytest.mark.parametrize(
    "family",
    ["packed", "causal", "chunk_causal", "swa256", "swa1024", "swa4096"],
)
def test_at_cp1_the_smem_test_reads_the_table_build_block_meta_builds(
    family, bq, bk
):
    """Where the tables are the global ones the feasibility test counts
    what the builder builds, forward and backward (every q and k block of
    these masks is covered, so neither table has dummies) with its padding,
    and not every slice's bounding box, which for a band is 40x the table."""
    qr, kr, ts, total = _bench_mask(family)
    meta = build_block_meta(
        qr, kr, ts, total, total, block_q=bq, block_k=bk, entry_pad=1
    )
    exact = estimate_entries(qr, kr, ts, bq, bk)[0]
    assert exact == meta.num_fwd_entries == meta.num_bwd_entries
    built = _built_entries(qr, kr, ts, total, total, bq, bk)
    assert exact <= built < exact + 8
    assert smem_entries(qr, kr, ts, bq, bk) == (
        built, "exact", built <= _MAX_SMEM_ENTRIES,
    )
    bound = _est_entries(qr, kr, bq, bk)
    assert built <= bound
    # cp = 2's share of the box is the whole box: what cp = 1 read before
    assert smem_entries(qr, kr, ts, bq, bk, 2) == (
        bound, "bound", bound <= _MAX_SMEM_ENTRIES,
    )
    if (family, bq, bk) in _PINNED:
        assert (exact, bound) == _PINNED[family, bq, bk]


def _built_entries(qr, kr, ts, total_q, total_k, bq, bk):
    """What the launch guard reads: the longer of the two built tables."""
    meta = build_block_meta(qr, kr, ts, total_q, total_k, block_q=bq, block_k=bk)
    return max(meta.num_fwd_entries, meta.num_bwd_entries)


_DOCS = [(i * 1024, (i + 1) * 1024) for i in range(64)]
# masks that leave most k blocks bare: (q ranges, k ranges, types, rows, keys)
_SPARSE_KEYS = {
    # 64 documents of 1,024 rows that all read the last 512 keys
    "shared_suffix": (_DOCS, [(65024, 65536)] * 64, [0] * 64, 65536, 65536),
    # ... that all read the first 512, and the last one itself as well
    "shared_prefix": (
        _DOCS + [_DOCS[-1]], [(0, 512)] * 64 + [_DOCS[-1]], [0] * 64 + [1],
        65536, 65536,
    ),
    # keys in two islands with a gap between, rows with a gap too
    "islands": (
        [(0, 4096), (32768, 36864)], [(8192, 9216), (60000, 65536)], [1, 0],
        36864, 65536,
    ),
}


@pytest.mark.parametrize("bq,bk", [c[:2] for c in _AUTO_BLOCK_CONFIGS])
@pytest.mark.parametrize("name", sorted(_SPARSE_KEYS))
def test_the_exact_count_is_the_longer_table_with_its_dummies(name, bq, bk):
    """A mask that leaves most k blocks bare: the backward table is the
    long one (the live tiles and a dummy a bare k block), and the launch
    guard reads the longer table, padded. What the ranker calls feasible
    is what ``_check_smem_budget`` will see."""
    qr, kr, ts, rows, keys = _SPARSE_KEYS[name]
    fwd = estimate_entries(qr, kr, ts, bq, bk)[0]
    built = _built_entries(qr, kr, ts, rows, keys, bq, bk)
    got = smem_entries(qr, kr, ts, bq, bk)
    assert (got.entries, got.count) == (built, "exact")
    if name != "islands" and bk < 2048:  # islands: the q gap's dummies lead
        assert got.entries > fwd + 8  # the forward count alone read too few


def test_a_buffer_longer_than_its_mask_has_dummies_the_slices_do_not_show():
    """The one thing the count cannot see: blocks past the mask's extent
    (here 127 key blocks nothing reads, behind the 512 keys every row
    reads) get a dummy each in the built table. The launch guard's budget
    is 2,214 entries over ``_MAX_SMEM_ENTRIES`` for them."""
    qr, kr, ts = _DOCS, [(0, 512)] * 64, [0] * 64
    got = smem_entries(qr, kr, ts, 128, 512)
    built = _built_entries(qr, kr, ts, 65536, 65536, 128, 512)
    assert (got.entries, built) == (512, 512 + 127 + 1)


def test_a_band_mask_gets_a_rung_that_fits_the_band():
    """THE ISSUE 33 regression: window 1,024 at 65,536 on the keyed
    runtime's arguments. (128, 512)'s table is 1,524 entries; under the
    bound it read 65,172, every rung under a megalogit was thrown out
    before its price was looked at, and the rest tied on (1024, 1024, 1)."""
    qr, kr, ts, total = _bench_mask("swa1024")
    ranked = rank_candidates(
        qr, kr, ts, 64, 8, max_block_q=total, max_block_k=total,
        include_sparse=False,
    )
    assert all(s.feasible and s.smem_count == "exact" for s in ranked)
    # ISSUE 56: of the two small rungs the cheaper, (256, 512, 8), ahead of
    # the table's (128, 512, 8) of 1,524 entries
    best, second = ranked[:2]
    assert (best.block_q, best.block_k, best.head_block) == (256, 512, 8)
    assert (best.entries, best.smem_entries) == (762, 768)  # padded to 8
    assert (second.block_q, second.entries, second.smem_entries) == (
        128, 1524, 1528,
    )
    # the prices did not move: the small rungs were 21-25% cheaper all along
    cost = {(s.block_q, s.block_k): s.cost_seconds * 1e3 for s in ranked}
    assert cost[128, 512] == pytest.approx(36.89, abs=0.01)
    assert cost[256, 512] == pytest.approx(35.06, abs=0.01)
    assert cost[1024, 1024] == pytest.approx(46.74, abs=0.01)
    old = rank_candidates(
        qr, kr, ts, 64, 8, max_block_q=total, max_block_k=total,
        include_sparse=False, cp_size=2,  # the whole box, as cp = 1 read
    )
    assert [(s.block_q, s.block_k) for s in old if not s.feasible] == [
        (256, 512), (128, 512),
    ]
    # what was left tied on (1024, 1024, 1) by the long-sequence lead; since
    # ISSUE 54 a mask at 3% of the square does not get the lead either way
    assert (old[0].block_q, old[0].block_k) == (256, 1024)
    assert {s.tie_order for s in ranked[1:] + old} == {"measured"}
    assert best.tie_order == "priced_pair"


def test_a_table_that_really_passes_the_budget_stays_infeasible():
    qr, kr, ts, _ = _bench_mask("causal")
    assert smem_entries(qr, kr, ts, 128, 512) == (33024, "exact", False)
    ranked = rank_candidates(qr, kr, ts, 64, 8, include_sparse=False)
    assert [(s.block_q, s.block_k) for s in ranked if not s.feasible] == [
        (128, 512)
    ]
    assert (ranked[0].block_q, ranked[0].block_k) == (1024, 1024)


@pytest.mark.parametrize(
    "family,verdicts,first",
    [
        # ISSUE 54: at 3.7% of the square the table's own order breaks the
        # 15% tie, not the dense slice's lead: (1024, 1024, 1) until then
        ("cp4_packed", (False, True, True, True, True), (256, 512, 8)),
        # nothing fits: the all-infeasible escalation order's widest tile
        ("cp4_causal", (False,) * 5, (512, 2048, 1)),
    ],
)
def test_per_rank_tables_keep_the_bound_and_its_verdicts(
    family, verdicts, first
):
    """cp = 4 (the box times 2 / cp): the global slices cannot count a
    rank's table, so the estimate stays every slice's bounding box times
    the rank's share, and both cp=4 cells keep the verdicts they had, the
    dense one its rung too (whether it should is ROADMAP S6's, on four
    chips)."""
    qr, kr, ts, total = _bench_mask(family)
    assert tuple(
        smem_entries(qr, kr, ts, bq, bk, 4).feasible
        for bq, bk in dict.fromkeys(c[:2] for c in _AUTO_BLOCK_CONFIGS)
    ) == verdicts
    ranked = rank_candidates(
        qr, kr, ts, 64, 8, max_block_q=total // 4, max_block_k=total // 4,
        cp_size=4, include_sparse=False,
    )
    for s in ranked:
        bound = int(_est_entries(qr, kr, s.block_q, s.block_k) * 0.5)
        assert (s.smem_entries, s.smem_count) == (bound, "bound")
        assert s.feasible == (bound <= _MAX_SMEM_ENTRIES)
    best = ranked[0]
    assert (best.block_q, best.block_k, best.head_block) == first
    # the decision's record says which count chose, and how many it dropped
    reset_tuning_cache()
    decision = select_block_config(
        qr, kr, ts, 64, 8, max_block_q=total // 4, max_block_k=total // 4,
        cp_size=4, include_sparse=False, mode="model",
    )
    reset_tuning_cache()
    assert decision.config == first
    assert (decision.smem_entries, decision.smem_count) == (
        best.smem_entries, "bound",
    )
    assert decision.rejected_smem == verdicts.count(False)


# -- the bytes a step streams from HBM (ISSUE 35) ---------------------------
@pytest.mark.parametrize(
    "kernel,rung,group,d,itemsize,want",
    [
        # q-major: K and V of the key-value heads the step's q heads share
        ("fwd", (128, 512, 5), 1, 256, 2, 2 * 5 * 512 * 256 * 2),  # 2.62 MB
        ("dq", (128, 512, 5), 1, 256, 2, 2_621_440),
        ("fwd", (128, 512, 8), 1, 128, 2, 2_097_152),  # Ouro: 2.10 MB
        ("fwd", (256, 512, 4), 1, 128, 2, 1_048_576),
        ("fwd", (128, 512, 8), 4, 128, 2, 2 * 2 * 512 * 128 * 2),  # Mistral
        ("fwd", (128, 512, 8), 8, 128, 2, 262_144),  # one pair a group
        ("dq", (1024, 1024, 1), 8, 128, 2, 524_288),  # per head: one pair
        ("fwd", (512, 2048, 1), 1, 64, 4, 2 * 2048 * 64 * 4),  # float32
        # k-major: q, dO and the two float32 statistics, rows along lanes
        ("dkv", (128, 512, 5), 1, 256, 2, 5 * 128 * (1024 + 8)),
        ("dkv", (256, 512, 4), 1, 128, 2, 4 * 256 * (512 + 8)),
        ("dkv", (128, 512, 8), 8, 128, 2, 532_480),
        ("dkv", (1024, 1024, 1), 8, 128, 2, 1024 * 520),
    ],
)
def test_step_bytes_against_a_hand_count(kernel, rung, group, d, itemsize, want):
    assert step_bytes(kernel, *rung, group, d, itemsize) == want


# forward / dq / dkv kernel ms a call on the GLM cell's mask at 20 q = 20 kv
# heads of 256, compact grid, v5e: PR 30 and PR 31's chip runs (PERF.md
# section 5), and PR 35's probe of the same mask (section 6)
_CHIP_MS_PR30 = {
    (128, 512, 5): (6.145, 6.525, 7.125),
    (256, 512, 4): (4.453, 5.508, 7.005),
    (128, 1024, 5): (7.775, 8.303, 9.070),
    (128, 512, 1): (8.847, 8.546, 9.754),
}
_CHIP_MS_PR35 = {
    (128, 512, 5): (6.148, 6.524, 7.125),
    (256, 512, 4): (4.451, 5.509, 6.925),
    (256, 512, 5): (4.350, 5.443, 6.843),
    (512, 512, 4): (4.463, 5.652, 7.713),
}


def _glm_prices(rungs):
    qr, kr, ts, total = _bench_mask("packed16k")
    ranked = rank_candidates(
        qr, kr, ts, 20, 20, head_dim=256, max_block_q=total,
        max_block_k=total, include_sparse=False, generation="v5e",
        rungs=[(bq, bk, 8 if hb == 5 else hb) for bq, bk, hb in rungs],
    )
    got = {(s.block_q, s.block_k, s.head_block): s for s in ranked}
    assert sorted(got) == sorted(rungs)  # 8 snaps to 5 of 20 heads
    return got


@pytest.mark.parametrize(
    "cheaper,dearer",
    [
        ((256, 512, 4), (128, 512, 5)),
        ((128, 512, 5), (128, 1024, 5)),
        ((128, 512, 5), (128, 512, 1)),
    ],
)
def test_the_price_orders_the_measured_rungs_as_the_chip_does(cheaper, dearer):
    """ISSUE 35's bar: on PR 30's four measured rungs the model's order is
    the chip's (forward + dq + dkv)."""
    assert sum(_CHIP_MS_PR30[cheaper]) < sum(_CHIP_MS_PR30[dearer])
    price = _glm_prices(list(_CHIP_MS_PR30))
    assert price[cheaper].cost_seconds < price[dearer].cost_seconds


def test_the_price_ratio_of_the_two_rungs_is_the_chips():
    """(128, 512, 5) : (256, 512, 4) within 15% of the measured 1.12 (the
    forward+backward call, 25.83 / 23.00 ms; the three kernels alone 1.167
    in PR 30's readings and 1.172 in PR 35's); without the bytes the model
    has the order the other way round."""
    price = _glm_prices([(128, 512, 5), (256, 512, 4)])
    ratio = price[128, 512, 5].cost_seconds / price[256, 512, 4].cost_seconds
    assert ratio == pytest.approx(1.12, rel=0.15)
    for chip in (_CHIP_MS_PR30, _CHIP_MS_PR35):
        measured = sum(chip[128, 512, 5]) / sum(chip[256, 512, 4])
        assert ratio == pytest.approx(measured, rel=0.15)
    assert (
        price[128, 512, 5].compute_seconds < price[256, 512, 4].compute_seconds
    )


def test_the_probes_rungs_keep_the_chips_winner():
    """PR 35's probe: five heads a step beats four at block_q 256, and the
    block_q-512 rung beats neither; the model agrees on all three."""
    price = _glm_prices(list(_CHIP_MS_PR35))
    for chip_order in (
        [(256, 512, 5), (256, 512, 4), (512, 512, 4)],
        [(256, 512, 4), (128, 512, 5)],
    ):
        for a, b in zip(chip_order, chip_order[1:]):
            assert sum(_CHIP_MS_PR35[a]) < sum(_CHIP_MS_PR35[b])
            assert price[a].cost_seconds < price[b].cost_seconds


def _at_the_peak(s, mfu=TPU_PEAK_SPECS["v5e"].mfu):
    """(hbm, mxu) seconds of a score's forward at the chip's two peaks."""
    return s.hbm_seconds * HBM_PRICE_SHARE, s.mxu_seconds * mfu


@pytest.mark.parametrize(
    "hq,hk,d,rung",
    [(20, 20, 256, (128, 512, 5)), (16, 16, 128, (128, 512, 8))],
    ids=["glm", "ouro"],
)
def test_the_term_binds_for_the_parents_rung_at_group_one(hq, hk, d, rung):
    qr, kr, ts, total = _bench_mask("packed16k")
    ranked = rank_candidates(
        qr, kr, ts, hq, hk, head_dim=d, max_block_q=total, max_block_k=total,
        include_sparse=False, generation="v5e",
    )
    by = {(s.block_q, s.block_k, s.head_block): s for s in ranked}
    old, best = by[rung], ranked[0]
    hbm, mxu = _at_the_peak(old)
    assert hbm > mxu and old.bound == "hbm" and old.hbm_excess_seconds > 0
    # 128 FLOPs a byte against the chip's 240
    assert mxu / hbm == pytest.approx(128 / (197e12 / 819e9), rel=1e-6)
    # the parent's choice: cheapest by tiles and steps, first in preference
    assert old.compute_seconds == min(s.compute_seconds for s in ranked)
    assert (best.block_q, best.block_k) == (256, 512) and best.head_block > 4
    assert best.bound == "mxu" and best.hbm_excess_seconds == 0.0
    assert best.hbm_seconds < old.hbm_seconds
    hbm, mxu = _at_the_peak(best)
    assert mxu / hbm == pytest.approx(256 / 240.5, rel=1e-3)  # just over


@pytest.mark.parametrize(
    "family,hq,hk,cp",
    [
        ("packed", 64, 8, 1), ("causal", 64, 8, 1), ("chunk_causal", 64, 8, 1),
        ("swa1024", 64, 8, 1), ("cp4_packed", 64, 8, 4),
        ("cp4_causal", 64, 8, 4), ("packed16k", 32, 8, 1),
        ("packed32k", 32, 4, 1),
    ],
)
def test_the_term_is_slack_in_the_eight_other_cells(family, hq, hk, cp):
    """At GQA group 4 or 8 a step's K and V serve the whole group: no rung
    of the table streams, every price is what it was, and the winner's
    forward is at least twice the balance away from the HBM's roof."""
    qr, kr, ts, total = _bench_mask(family)
    ranked = rank_candidates(
        qr, kr, ts, hq, hk, max_block_q=total // cp, max_block_k=total // cp,
        cp_size=cp, include_sparse=False, generation="v5e",
    )
    for s in ranked:
        assert s.bound == "mxu" and s.hbm_excess_seconds == 0.0
        assert s.cost_seconds == s.compute_seconds
    hbm, mxu = _at_the_peak(ranked[0])
    assert 2 * 240.6 * hbm < 240.6 * mxu


@pytest.mark.parametrize("family", ["packed", "causal", "swa1024"])
@pytest.mark.parametrize("include_sparse", [False, True])
def test_a_group_one_mask_at_64k_gets_block_q_256_or_more(family, include_sparse):
    """MHA at head_dim 128, 16 q = 16 kv heads: whatever the mask and the
    grids ranked, the winner's forward clears the balance."""
    qr, kr, ts, total = _bench_mask(family)
    best = rank_candidates(
        qr, kr, ts, 16, 16, max_block_q=total, max_block_k=total,
        include_sparse=include_sparse, generation="v5e",
    )[0]
    assert best.feasible and best.bound == "mxu" and best.block_q >= 256


def test_an_hbm_bound_rung_is_no_tie_with_one_that_is_not():
    """The check's 4,096-token mask at 16 q = 16 kv heads: the three small
    rungs are within 8% of each other, and (128, 512, 8), first in the
    preference order, is passed over because its own price says it
    streams. At 64 / 8 heads the same mask's tie goes to it as before."""
    from benchmarks import harness, masks

    tr = harness.load_cell(ROOT, "ouro26b-train-16k-looped").traffic
    m = masks.build_mask(tr["mask"], 4096, index=0)
    args = dict(max_block_q=4096, max_block_k=4096, include_sparse=False,
                generation="v5e")
    ranked = rank_candidates(m.q_ranges, m.k_ranges, m.types, 16, 16, **args)
    small = [s for s in ranked if s.block_k == 512]
    assert max(s.cost_seconds for s in small) < 1.15 * min(
        s.cost_seconds for s in small
    )
    assert [(s.block_q, s.head_block, s.bound) for s in small] == [
        (256, 8, "mxu"), (256, 4, "mxu"), (128, 8, "hbm"),
    ]
    gqa = rank_candidates(m.q_ranges, m.k_ranges, m.types, 64, 8, **args)
    assert (gqa[0].block_q, gqa[0].block_k, gqa[0].head_block) == (128, 512, 8)


# -- the tie order's long-sequence lead (ISSUE 54) --------------------------
def _lengths_mask(lengths):
    from benchmarks import masks

    return masks.build_mask(
        {"type": "varlen_block_causal", "lengths": list(lengths)}, sum(lengths)
    )


def _first(ranked):
    return (ranked[0].block_q, ranked[0].block_k, ranked[0].head_block)


def test_a_few_long_documents_get_the_checks_rung_not_the_dense_cells():
    """SmallThinker's timed mask (four documents in 16,384 rows, 23.1% of
    the square) at 28 / 4 heads: (1024, 1024, 1) is 8% over the cheapest
    rung, inside the 15% tie, and led the tie order from an extent of
    16,384 on. The lead was measured on a dense 64k slice; under the
    ranker's own density line the table's order breaks the tie, and the
    timed full plan walks the rung the check's (8,192 rows: no lead at any
    density) does: (128, 512, 7) by the table until ISSUE 56, (256, 512, 7)
    since, the cheaper of the pair on both masks."""
    from magiattention_tpu.tuning.cost_model import SPARSE_DENSITY_THRESHOLD

    timed = _lengths_mask((10240, 4096, 1536, 512))
    check = _lengths_mask((6144, 1536, 512))
    assert timed.area / timed.total**2 < SPARSE_DENSITY_THRESHOLD
    ranked = {
        m.total: rank_candidates(
            m.q_ranges, m.k_ranges, m.types, 28, 4, max_block_q=m.total,
            max_block_k=m.total, include_sparse=False,
        )
        for m in (timed, check)
    }
    assert _first(ranked[16384]) == _first(ranked[8192]) == (256, 512, 7)
    assert {s.tie_order for r in ranked.values() for s in r[1:]} == {"measured"}
    assert {r[0].tie_order for r in ranked.values()} == {"priced_pair"}
    # the price did not move: the per-head rung is in the tie as it was
    cost = {(s.block_q, s.block_k): s.cost_seconds for s in ranked[16384]}
    assert 1.07 < cost[1024, 1024] / min(cost.values()) < 1.09
    assert min(cost, key=cost.get) == (256, 512)


@pytest.mark.parametrize("family", ["causal", "chunk_causal"])
def test_the_masks_the_lead_was_measured_on_keep_it(family):
    """Dense causal and chunk-causal at 65,536 rows and 64 / 8 heads (half
    the square and over): (1024, 1024, 1), by the long-sequence lead."""
    qr, kr, ts, total = _bench_mask(family)
    for include_sparse in (False, True):
        ranked = rank_candidates(
            qr, kr, ts, 64, 8, max_block_q=total, max_block_k=total,
            include_sparse=include_sparse,
        )
        assert _first(ranked) == (1024, 1024, 1)
        assert ranked[0].grid == "row_major"
        assert {s.tie_order for s in ranked} == {"long_seq"}


# mask -> (q ranges, k ranges, types, rows, cp); a dozen and more with the
# head geometries below: every family a cell runs, and masks either side of
# the density line at either side of 16,384 rows
_LEAD_MASKS = {
    "packed": lambda: (*_bench_mask("packed"), 1),
    "causal": lambda: (*_bench_mask("causal"), 1),
    "chunk_causal": lambda: (*_bench_mask("chunk_causal"), 1),
    "swa1024": lambda: (*_bench_mask("swa1024"), 1),
    "swa4096": lambda: (*_bench_mask("swa4096"), 1),
    "cp4_packed": lambda: (*_bench_mask("cp4_packed"), 4),
    "cp4_causal": lambda: (*_bench_mask("cp4_causal"), 4),
    "packed16k": lambda: (*_bench_mask("packed16k"), 1),
    "packed32k": lambda: (*_bench_mask("packed32k"), 1),
    "four_docs_16k": lambda: _of(_lengths_mask((10240, 4096, 1536, 512))),
    "three_docs_8k": lambda: _of(_lengths_mask((6144, 1536, 512))),
    "five_docs_16k": lambda: _of(_lengths_mask((8192, 4096, 2048, 1536, 512))),
    "two_docs_32k": lambda: _of(_lengths_mask((16384, 16384))),
    "one_doc_16k": lambda: _of(_lengths_mask((16384,))),
}


def _of(m):
    return m.q_ranges, m.k_ranges, m.types, m.total, 1


@pytest.mark.parametrize(
    "heads",
    [(64, 8, 128, None), (28, 4, 128, None), (16, 16, 128, None),
     (20, 20, 256, None), (32, 32, 192, 128)],
    ids=lambda h: "x".join(str(x) for x in h if x),
)
@pytest.mark.parametrize("mask", list(_LEAD_MASKS))
def test_the_lead_decides_only_a_plan_it_put_on_a_long_sequence_rung(
    mask, heads
):
    """The tied set is the same set whichever order breaks the tie, and the
    long-sequence rungs close the table's own order: a ranking whose winner
    WITH the lead is neither of them had neither in its tie, and keeps its
    winner without it. So taking the lead off a mask can move only a plan
    that sat on (1024, 1024, 1) or (512, 2048, 1). The ranker's own choice
    of order reads the mask's density and extent and nothing else."""
    from magiattention_tpu.ops.flex_attn import (
        _LONG_SEQ_BLOCK_THRESHOLD,
        _LONG_SEQ_CONFIGS,
    )
    from magiattention_tpu.tuning.cost_model import (
        SPARSE_DENSITY_THRESHOLD,
        _preference_order,
        exact_mask_area,
    )

    qr, kr, ts, total, cp = _LEAD_MASKS[mask]()
    hq, hk, d, dv = heads
    # both orders, whatever this mask would get: a dense long mask's, and
    # a sparse one's
    orders = dict(
        _preference_order(_LONG_SEQ_BLOCK_THRESHOLD, density)
        for density in (1.0, 0.0)
    )
    assert orders["measured"] == _AUTO_BLOCK_CONFIGS
    assert orders["long_seq"][:2] == _LONG_SEQ_CONFIGS
    dense = exact_mask_area(qr, kr, ts) / total**2 >= SPARSE_DENSITY_THRESHOLD
    want = (
        "long_seq" if dense and total >= _LONG_SEQ_BLOCK_THRESHOLD
        else "measured"
    )
    for include_sparse in (False, True):
        args = dict(
            head_dim=d, v_head_dim=dv, max_block_q=total // cp,
            max_block_k=total // cp, cp_size=cp,
            include_sparse=include_sparse,
        )
        given = {
            name: rank_candidates(qr, kr, ts, hq, hk, rungs=order, **args)
            for name, order in orders.items()
        }
        key = lambda s: (s.block_q, s.block_k, s.head_block, s.grid)  # noqa: E731
        # the same candidates at the same prices, in either order
        assert {key(s): s.cost_seconds for s in given["long_seq"]} == {
            key(s): s.cost_seconds for s in given["measured"]
        }
        led = given["long_seq"][0]
        if (led.block_q, led.block_k) not in {c[:2] for c in _LONG_SEQ_CONFIGS}:
            assert key(given["measured"][0]) == key(led)
        own = rank_candidates(qr, kr, ts, hq, hk, **args)
        # but for the rung its own price put ahead of its smaller block_q
        # (ISSUE 56), which never is a long-sequence rung
        led = [key(s) for s in own if s.tie_order == "priced_pair"]
        assert {s.tie_order for s in own} - {"priced_pair"} == {want}
        assert not {k[:2] for k in led} & {c[:2] for c in _LONG_SEQ_CONFIGS}
        assert [key(s) for s in own if key(s) not in led] == [
            key(s) for s in given[want] if key(s) not in led
        ]
        assert {s.tie_order for s in given[want]} == {"given"}


# -- the priced pair (ISSUE 56) ----------------------------------------------
def _pair(ranked, hb):
    """(the 128 rung, the 256 rung) of one ranking at one head_block."""
    by = {(s.block_q, s.block_k, s.head_block, s.grid): s for s in ranked}
    return by[128, 512, hb, "row_major"], by[256, 512, hb, "row_major"]


# mask -> (slices, heads, the winner, its tie_order, 256's price over 128's)
_PAIR_CASES = {
    # Mistral's 17 documents in 16,384 rows: 256 computes 8.1% more tile
    # area for half the steps, and the price says +1.1%: the table's order
    "many_short_documents": (
        lambda: _bench_mask("packed16k")[:3], (32, 8),
        (128, 512, 8), "measured", (1.005, 1.02),
    ),
    # SmallThinker's four documents: the same tiles to 0.1%, half the steps
    "a_few_long_documents": (
        lambda: _of(_lengths_mask((10240, 4096, 1536, 512)))[:3], (28, 4),
        (256, 512, 7), "priced_pair", (0.92, 0.94),
    ),
    # the packed 64k cell's 49 documents: 2.6% more tile area, half the steps
    "the_packed_cell": (
        lambda: _bench_mask("packed")[:3], (64, 8),
        (256, 512, 8), "priced_pair", (0.94, 0.95),
    ),
    # a band of 1,024 keys in 65,536 rows: 762 tiles of 256 for 1,524 of 128
    "a_band": (
        lambda: _bench_mask("swa1024")[:3], (64, 8),
        (256, 512, 8), "priced_pair", (0.94, 0.96),
    ),
    # GQA group 1: (128, 512, 8) streams K and V at the HBM's pace and is no
    # tie with a rung that does not, so the pair never meets in the pool and
    # the bytes' price decides as it did (ISSUE 35)
    "gqa_group_1": (
        lambda: _bench_mask("packed16k")[:3], (16, 16),
        (256, 512, 8), "measured", (0.80, 0.90),
    ),
}


@pytest.mark.parametrize("case", list(_PAIR_CASES))
def test_the_pair_that_differs_in_block_q_alone_stands_in_the_order_of_its_prices(
    case,
):
    """Inside the tie pool (128, 512, hb) and (256, 512, hb) are ordered by
    their own price where 256 is the cheaper by ``PAIR_PRICE_MARGIN``; every
    other rung keeps the place the preference order gave it."""
    from magiattention_tpu.tuning.cost_model import PAIR_PRICE_MARGIN

    slices, (hq, hk), want, order, (lo, hi) = _PAIR_CASES[case]
    qr, kr, ts = slices()
    args = dict(include_sparse=False, generation="v5e")
    ranked = rank_candidates(qr, kr, ts, hq, hk, **args)
    small, big = _pair(ranked, want[2])
    assert lo < big.cost_seconds / small.cost_seconds < hi
    assert (_first(ranked), ranked[0].tie_order) == (want, order)
    key = lambda s: (s.block_q, s.block_k, s.head_block)  # noqa: E731
    table = rank_candidates(
        qr, kr, ts, hq, hk, rungs=_AUTO_BLOCK_CONFIGS, **args
    )
    led = [key(s) for s in ranked if s.tie_order == "priced_pair"]
    if order == "priced_pair":
        assert led == [want]
        assert big.cost_seconds < small.cost_seconds * (1 - PAIR_PRICE_MARGIN)
        # 256 stands just ahead of 128, and nothing else moved
        at = [key(s) for s in ranked].index(want)
        assert key(ranked[at + 1]) == (128, 512, want[2])
    else:
        assert led == []
    assert [key(s) for s in ranked if key(s) not in led] == [
        key(s) for s in table if key(s) not in led
    ]
    # a ranking over rungs the caller gave keeps the order given
    assert {s.tie_order for s in table} == {"given"}


@pytest.mark.parametrize("margin,want", [(0.06, 256), (0.07, 128)])
def test_the_margins_edge(monkeypatch, margin, want):
    """SmallThinker's four documents price 256 at 6.8% under 128: a margin
    of 6% lets it lead, one of 7% leaves the table's order."""
    from magiattention_tpu.tuning import cost_model

    monkeypatch.setattr(cost_model, "PAIR_PRICE_MARGIN", margin)
    m = _lengths_mask((10240, 4096, 1536, 512))
    ranked = rank_candidates(
        m.q_ranges, m.k_ranges, m.types, 28, 4, include_sparse=False,
        generation="v5e",
    )
    small, big = _pair(ranked, 7)
    assert 0.931 < big.cost_seconds / small.cost_seconds < 0.933
    assert _first(ranked) == (want, 512, 7)
    assert ranked[0].tie_order == (
        "priced_pair" if want == 256 else "measured"
    )


def test_the_pair_rule_leaves_the_fewest_steps_order_alone():
    """Where sparse rungs are ranked and the mask is under the density line
    the tie goes to the compact grid with the fewest slots, as it did: the
    priced pair is a row-major rule and does not reorder that pool."""
    qr, kr, ts, _ = _bench_mask("packed16k")
    ranked = rank_candidates(qr, kr, ts, 32, 8, generation="v5e")
    assert ranked[0].grid == "sparse"
    assert "priced_pair" not in {s.tie_order for s in ranked}


def _disjoint_slices(seed, total=192):
    """Random varlen-style slices with DISJOINT q ranges — the kernel's
    no-(q,k)-overlap contract, under which per-slice area == the dense
    union mask's popcount."""
    rng = np.random.default_rng(seed)
    cuts = np.sort(
        rng.choice(np.arange(1, total), int(rng.integers(2, 6)),
                   replace=False)
    )
    bounds = [0, *[int(c) for c in cuts], total]
    qr, kr, ts = [], [], []
    for a, b in zip(bounds, bounds[1:]):
        c, d = sorted(rng.integers(0, total, 2).tolist())
        if c == d:
            continue
        qr.append((a, b))
        kr.append((c, d))
        ts.append(int(rng.choice([0, 1, 2])))
    return qr, kr, ts


@pytest.mark.parametrize("seed", [0, 2, 5, 9])
def test_exact_mask_area_matches_oracle(seed):
    from magiattention_tpu.testing.ref_attn import make_attn_mask_from_ranges
    from magiattention_tpu.tuning.cost_model import exact_mask_area

    total = 192
    qr, kr, ts = _disjoint_slices(seed, total)
    mask = np.asarray(make_attn_mask_from_ranges(qr, kr, ts, total, total))
    assert exact_mask_area(qr, kr, ts) == int(mask.sum())
