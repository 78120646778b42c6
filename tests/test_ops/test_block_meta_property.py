"""Randomized entry-table properties: for random masks, blockings, and
run-permuted buffers, the q-major and k-major tables must both describe
EXACTLY the local dense mask (reference block_meta.h / slice_maker
correctness, checked as a property instead of enumerated cases)."""

import numpy as np
import pytest

from magiattention_tpu.common.enum import AttnMaskType
from magiattention_tpu.common.mask import make_attn_mask_from_ranges
from magiattention_tpu.ops.block_meta import (
    RUN_FIELDS,
    SLICE_FIELDS,
    Run,
    build_block_meta_general,
    runs_from_position_ids,
)


def _rand_slices(rng, total):
    cuts = [0]
    while cuts[-1] < total:
        cuts.append(min(cuts[-1] + int(rng.integers(16, total // 2)), total))
    rows = []
    for a, b in zip(cuts, cuts[1:]):
        t = int(rng.choice([0, 1, 2, 3]))
        k0 = 0 if rng.random() < 0.3 else a
        rows.append((a, b, k0, b, t))
    return np.asarray(rows, dtype=np.int64)


def _dense_from_entries(qb, kb, sid, runs, bounds, nq_rows, nk_rows, bq, bk):
    """Re-evaluate every entry's tile mask on host — the numpy mirror of
    the kernel's _entry_mask — and OR into a dense local mask."""
    dense = np.zeros((nq_rows, nk_rows), dtype=bool)
    runs = runs.reshape(-1, RUN_FIELDS)
    bounds = bounds.reshape(-1, SLICE_FIELDS)
    for e in range(qb.shape[0]):
        row0, col0 = int(qb[e]) * bq, int(kb[e]) * bk
        ql0, ql1, kl0, kl1, qoff, koff, _nm = (int(x) for x in runs[e])
        q0, q1, k0, k1, typ = (int(x) for x in bounds[int(sid[e])])
        for rl in range(max(row0, ql0), min(row0 + bq, ql1, nq_rows)):
            gq = rl + qoff
            if not (q0 <= gq < q1):
                continue
            for cl in range(max(col0, kl0), min(col0 + bk, kl1, nk_rows)):
                gk = cl + koff
                if not (k0 <= gk < k1):
                    continue
                if (typ & 1) and not ((gk - k1) <= (gq - q1)):
                    continue
                if (typ & 2) and not ((gk - k0) >= (gq - q0)):
                    continue
                dense[rl, cl] = True
    return dense


@pytest.mark.parametrize("seed", range(8))
def test_tables_describe_exactly_the_local_mask(seed):
    rng = np.random.default_rng(seed)
    total = 256
    bq = int(rng.choice([16, 32, 64]))
    bk = int(rng.choice([16, 32, 64]))
    sl = _rand_slices(rng, total)

    # random permuted local buffers: shuffle chunk-sized groups (the shape
    # dispatch produces), keep a subset for K (remote-buffer shape)
    chunk = 32
    perm = rng.permutation(total // chunk)
    q_pos = np.concatenate(
        [np.arange(c * chunk, (c + 1) * chunk) for c in perm]
    )
    keep = sorted(
        rng.choice(total // chunk, size=total // chunk - 2, replace=False)
    )
    k_pos = np.concatenate(
        [np.arange(c * chunk, (c + 1) * chunk) for c in keep]
    )
    q_runs = runs_from_position_ids(q_pos)
    k_runs = runs_from_position_ids(k_pos)

    meta = build_block_meta_general(
        sl, q_runs, k_runs, len(q_pos), len(k_pos), block_q=bq, block_k=bk
    )

    # ground truth: global dense mask restricted to the local buffers
    g = np.asarray(
        make_attn_mask_from_ranges(
            [(int(r[0]), int(r[1])) for r in sl],
            [(int(r[2]), int(r[3])) for r in sl],
            [AttnMaskType(int(r[4])) for r in sl],
            total,
            total,
        )
    )
    want = g[np.ix_(q_pos, k_pos)]

    got_fwd = _dense_from_entries(
        meta.fwd_q_block, meta.fwd_k_block, meta.fwd_slice_id,
        meta.fwd_runs, meta.slice_bounds, len(q_pos), len(k_pos), bq, bk,
    )
    np.testing.assert_array_equal(got_fwd, want, err_msg="fwd table")

    got_bwd = _dense_from_entries(
        meta.bwd_q_block, meta.bwd_k_block, meta.bwd_slice_id,
        meta.bwd_runs, meta.slice_bounds, len(q_pos), len(k_pos), bq, bk,
    )
    np.testing.assert_array_equal(got_bwd, want, err_msg="bwd table")

    # the recorded exact area matches the ground truth popcount
    assert meta.total_area == int(want.sum())

    # q-major ordering invariant: same-q-block entries are consecutive
    # (what makes VMEM accumulation without atomics correct)
    qb = meta.fwd_q_block
    seen = set()
    prev = None
    for e in range(qb.shape[0]):
        cur = int(qb[e])
        if cur != prev:
            assert cur not in seen, "q-block entries not consecutive"
            seen.add(cur)
            prev = cur
    # every q block appears (dummy entries guarantee output coverage)
    assert seen == set(range(meta.num_q_blocks))


# ---------------------------------------------------------------------------
# ISSUE 44: the k-major table's visit bits
# ---------------------------------------------------------------------------


def _check_visit_bits(q_block, runs, num_q_blocks):
    """In table order every q block some entry names has exactly one
    FIRST_VISIT and one LAST_VISIT, on its first and on its last entry;
    no other entry carries either; nothing lies above bit 2."""
    from magiattention_tpu.ops.block_meta import (
        FIRST_VISIT, LAST_VISIT, q_visit_counts,
    )

    words = np.asarray(runs).reshape(-1, RUN_FIELDS)[:, 6]
    qb = np.asarray(q_block)
    assert words.shape == qb.shape and not (words >> 3).any()
    firsts, lasts = (words & FIRST_VISIT) != 0, (words & LAST_VISIT) != 0
    named = np.unique(qb)
    for b in named:
        at = np.flatnonzero(qb == b)
        assert np.flatnonzero(firsts & (qb == b)).tolist() == [at[0]]
        assert np.flatnonzero(lasts & (qb == b)).tolist() == [at[-1]]
        assert at[0] <= at[-1]
    assert firsts.sum() == lasts.sum() == named.size
    assert firsts[0] and lasts[-1]  # the walk starts and ends clean
    assert q_visit_counts(qb, num_q_blocks) == (
        named.size, num_q_blocks - named.size
    )


def _needs_mask_of(meta, major, minor, sid, runs, major_col):
    """Bit 0 as the planner computes it, from the table's own entries."""
    from magiattention_tpu.ops.block_meta import _needs_mask_flags

    cols = [major, minor] if major_col == 0 else [minor, major]
    entries = np.concatenate(
        [np.stack([*cols, sid], axis=1),
         np.asarray(runs).reshape(-1, RUN_FIELDS)[:, :6]], axis=1,
    ).astype(np.int64)
    slices = meta.slice_bounds.reshape(-1, SLICE_FIELDS)
    live = (slices[:, 1] > slices[:, 0]) | (slices[:, 3] > slices[:, 2])
    n = int(np.flatnonzero(live).max()) + 1 if live.any() else 0
    return _needs_mask_flags(entries, slices[:n].astype(np.int64),
                             meta.block_q, meta.block_k)


@pytest.mark.parametrize("builder", ["general", "single_device", "sparse_mask"])
@pytest.mark.parametrize("seed", range(6))
def test_every_visited_q_block_has_one_first_and_one_last_visit(seed, builder):
    """Random slice lists through each way a backward table is made (all
    end in ``assemble_block_meta``), ``pad_block_meta`` on top (the pads
    name q block 0: they move its last visit, and on a leveled k block
    before its first real entry its first), and the stacked per-rank
    tables of ``StageTables.from_rank_metas``: the bits are each rank's
    own; bit 0 is the planner's "needs mask" as before, on both tables,
    and the q-major table carries nothing else."""
    from magiattention_tpu.ops.block_meta import (
        NEEDS_MASK, build_block_meta, pad_block_meta,
    )
    from magiattention_tpu.parallel.dist_attn import StageTables

    rng = np.random.default_rng(100 + seed)
    total = 256
    bq = int(rng.choice([16, 32, 64]))
    bk = int(rng.choice([16, 32, 64]))
    sl = _rand_slices(rng, total)
    if builder == "sparse_mask":  # rows without a key: whole q blocks unnamed
        sl = sl[sl[:, 0] % 2 == 0]
    metas = []
    for rank in range(3):
        if builder == "general":
            chunk = 32
            q_pos = np.concatenate([
                np.arange(c * chunk, (c + 1) * chunk)
                for c in rng.permutation(total // chunk)[: 4 + rank]
            ])
            k_pos = np.concatenate([
                np.arange(c * chunk, (c + 1) * chunk)
                for c in sorted(rng.choice(total // chunk, 5, replace=False))
            ])
            # one q buffer length a stage: pad the shorter ranks' rows
            metas.append(build_block_meta_general(
                sl, runs_from_position_ids(q_pos), runs_from_position_ids(k_pos),
                (4 + 2) * chunk, len(k_pos), block_q=bq, block_k=bk,
            ))
        else:
            metas.append(build_block_meta(
                sl[rank:, :2], sl[rank:, 2:4], sl[rank:, 4], total, total,
                block_q=bq, block_k=bk,
            ))
    for meta in metas:
        _check_visit_bits(meta.bwd_q_block, meta.bwd_runs, meta.num_q_blocks)
        padded = pad_block_meta(
            meta, meta.num_fwd_entries + 5, meta.num_bwd_entries + 7,
            meta.num_slices + 2,
        )
        _check_visit_bits(padded.bwd_q_block, padded.bwd_runs, meta.num_q_blocks)
        for m in (meta, padded):
            fwd_words = m.fwd_runs.reshape(-1, RUN_FIELDS)[:, 6]
            np.testing.assert_array_equal(
                fwd_words,
                _needs_mask_of(m, m.fwd_q_block, m.fwd_k_block,
                               m.fwd_slice_id, m.fwd_runs, 0),
            )
            np.testing.assert_array_equal(
                m.bwd_runs.reshape(-1, RUN_FIELDS)[:, 6] & NEEDS_MASK,
                _needs_mask_of(m, m.bwd_k_block, m.bwd_q_block,
                               m.bwd_slice_id, m.bwd_runs, 1),
            )
    stacked = StageTables.from_rank_metas(metas, metas[0].total_k)
    total_unnamed = 0
    for rank in range(len(metas)):
        _check_visit_bits(
            stacked.bwd_qblk[rank], stacked.bwd_runs[rank], stacked.num_q_blocks
        )
        total_unnamed += stacked.num_q_blocks - np.unique(stacked.bwd_qblk[rank]).size
    entries, named, unnamed = stacked.q_visits()
    assert entries == stacked.bwd_qblk.size and unnamed == total_unnamed
    assert named + unnamed == len(metas) * stacked.num_q_blocks
    if builder == "sparse_mask":
        assert unnamed > 0


def test_stepped_tile_steps_reads_bit_0_alone():
    """``StageTables.stepped_tile_steps`` (the SDAR cell's
    ``flex_stepped_tile_share``) counts the entries of a stepped slice
    whose "needs mask" bit is set: the k-major word's visit bits do not
    reach it. On the cell's own mask at its rung: the count with the bits
    there is the count with the bits cleared, and is above zero."""
    import dataclasses

    from magiattention_tpu.api.functools import infer_block_diffusion_mask
    from magiattention_tpu.ops.block_meta import NEEDS_MASK, build_block_meta
    from magiattention_tpu.parallel.dist_attn import StageTables

    q, k, t = infer_block_diffusion_mask([0, 1024, 2048], 4)
    meta = build_block_meta(
        q.to_naive_ranges(), k.to_naive_ranges(), [int(x) for x in t],
        4096, 4096, block_q=128, block_k=512,
    )
    tables = StageTables.from_rank_metas([meta], 4096)
    words = tables.bwd_runs.reshape(1, -1, RUN_FIELDS)
    assert (words[..., 6] & ~NEEDS_MASK).any()  # the bits are there
    cleared = words.copy()
    cleared[..., 6] &= NEEDS_MASK
    bare = dataclasses.replace(tables, bwd_runs=cleared.reshape(1, -1))
    assert tables.stepped_tile_steps() == bare.stepped_tile_steps() > 0
