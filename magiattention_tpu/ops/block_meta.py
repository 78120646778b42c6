"""Host-side block metadata for the Pallas flex-flash-attention kernels.

Role of the reference's ``csrc/flexible_flash_attention/block_meta.h`` +
tile schedulers *and* of its ``meta/solver/slice_maker.py``: instead of a
persistent CUDA kernel walking (range, m-block) tiles with atomics — and
instead of host-side splitting of k-ranges into local sub-slices with
adjusted mask types — we precompute, per unique mask, a flattened *entry
table*: one entry per (q-block, k-block, slice, run-pair) tile that
intersects the mask. The Pallas kernel walks entries on a sequential grid
with scalar-prefetched indices (splash-attention style); entries of the same
q-block are consecutive so accumulation happens in VMEM scratch, no atomics.

The *run* generalization is what makes the distributed path trivial: a rank's
local Q/K buffers are permuted concatenations of global-coordinate segments
("runs": local_start -> global_start, length). Each entry carries its runs'
local windows + global offsets, and the kernel evaluates the ORIGINAL
global-coordinate mask semantics on (local + offset) indices. Arbitrary
sequence shards and remote-KV buffer layouts then need no mask rewriting at
all — the moral replacement for slice_maker.py's trapezoid case analysis.

Tables are built in both orientations:
- q-major (sorted by q-block): the forward kernel,
- k-major (sorted by k-block): the backward kernel (dk / dv per k block in
  VMEM; each entry marked as the first / last visit of its q block, whose
  dq sums the walk keeps in HBM: :func:`mark_q_visits`).

Every q-block (resp. k-block) has at least one entry — a dummy all-masked
entry pointing at the sentinel slice — so output tiles are always written
(out=0 / lse=-inf for uncovered rows, dk=dv=0 for uncovered keys).
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

# Fields per slice in the flattened bounds table (global coords).
# mask_type is the type word: bound bits 0-1, log2 of the step above them
# (common/enum.AttnMaskType), so a stepped slice costs no field.
SLICE_FIELDS = 5  # qs, qe, ks, ke, mask_type
# Fields per entry in the flattened runs table (local windows + offsets).
# The seventh word is a flag word no kernel's mask reads. Bit 0, in both
# tables: the planner did not find the tile whole ("needs mask", a plan
# diagnostic). Bits 1 and 2, in the k-major table alone: the entry is the
# first resp. the last visit of its q block in table order, which on the
# backward's walk is the order in time (:func:`mark_q_visits`; the
# backward's dq protocol acts on them, ``flex_attn._dq_accumulate``).
RUN_FIELDS = 7  # ql0, ql1, kl0, kl1, qoff, koff, flags
NEEDS_MASK = 1
FIRST_VISIT = 2
LAST_VISIT = 4


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _round_up(a: int, b: int) -> int:
    return _cdiv(a, b) * b


@dataclasses.dataclass(frozen=True)
class Run:
    """A contiguous segment: local rows [local_start, local_start+length)
    hold global positions [global_start, global_start+length)."""

    local_start: int
    global_start: int
    length: int

    @property
    def local_end(self) -> int:
        return self.local_start + self.length

    @property
    def global_end(self) -> int:
        return self.global_start + self.length

    @property
    def offset(self) -> int:
        return self.global_start - self.local_start


def runs_from_position_ids(position_ids: np.ndarray) -> list[Run]:
    """Compress a local->global id map into maximal contiguous runs.

    Vectorized: run boundaries are exactly the places where the id does
    not advance by 1 (a Python per-element scan dominated 1M-token plan
    builds at ~70 ms per call; this is O(n) numpy + O(runs) Python).
    """
    pos = np.asarray(position_ids, dtype=np.int64).reshape(-1)
    n = pos.shape[0]
    if n == 0:
        return []
    starts = np.concatenate(([0], np.flatnonzero(np.diff(pos) != 1) + 1))
    ends = np.concatenate((starts[1:], [n]))
    return [
        Run(local_start=int(s), global_start=int(pos[s]), length=int(e - s))
        for s, e in zip(starts, ends)
    ]


def identity_runs(total: int) -> list[Run]:
    return [Run(0, 0, total)] if total > 0 else []


@dataclasses.dataclass(frozen=True, eq=False)
class FlexAttnBlockMeta:
    """Immutable host-side kernel plan for one (mask, layout, blocking) combo.

    All arrays are numpy int32, becoming scalar-prefetch operands of the
    Pallas kernels (or, in the distributed runtime, stacked per-rank and fed
    as sharded device arrays). ``slice_bounds`` is [num_slices+1, SLICE_FIELDS]
    flattened; the last row is the all-zero sentinel used by dummy entries.
    """

    total_q: int  # local q rows (padded to block_q multiple by the wrapper)
    total_k: int
    block_q: int
    block_k: int
    num_q_blocks: int
    num_k_blocks: int
    num_slices: int
    total_area: int  # exact unmasked pair count within this rank's plan

    # q-major table (the forward)
    fwd_q_block: np.ndarray  # [E]
    fwd_k_block: np.ndarray  # [E]
    fwd_slice_id: np.ndarray  # [E]
    fwd_runs: np.ndarray  # [E * RUN_FIELDS]

    # k-major table (the backward)
    bwd_k_block: np.ndarray  # [E2]
    bwd_q_block: np.ndarray  # [E2]
    bwd_slice_id: np.ndarray  # [E2]
    bwd_runs: np.ndarray  # [E2 * RUN_FIELDS]

    slice_bounds: np.ndarray  # [(num_slices+1) * SLICE_FIELDS]

    @property
    def num_fwd_entries(self) -> int:
        return int(self.fwd_q_block.shape[0])

    @property
    def num_bwd_entries(self) -> int:
        return int(self.bwd_k_block.shape[0])

    @property
    def fwd_steps(self) -> int:
        """Max fwd entries on any q block: the kernel's inner grid extent."""
        return max_row_count(self.fwd_q_block, self.num_q_blocks)

    @property
    def bwd_steps(self) -> int:
        """Max bwd entries on any k block."""
        return max_row_count(self.bwd_k_block, self.num_k_blocks)


def max_row_count(major: np.ndarray, num_major: int) -> int:
    """Max entries sharing one major block (>= 1: dummies cover all majors).

    This is the static inner-grid extent S of the row-major kernels: the
    grid is (heads, num_major, S) and each major's entries occupy its
    first row_count steps, the rest clamped dead. Host-side only — the
    launchers recompute row starts/counts on-device from the (possibly
    traced, per-rank stacked) major array with searchsorted.
    """
    if num_major <= 0 or major.size == 0:
        return 1
    return int(np.bincount(np.asarray(major), minlength=num_major).max())


def check_type_word(sid: int, q_range, k_range, word: int) -> None:
    """A slice's type word holds two bound bits and, above them, log2 of
    the step (``common.enum.AttnMaskType``); anything else is no type."""
    from ..common.enum import MASK_TYPE_BITS, MAX_MASK_STEP_LOG2

    if not 0 <= word < (MAX_MASK_STEP_LOG2 + 1) << MASK_TYPE_BITS:
        raise ValueError(
            f"slice {sid} (q [{int(q_range[0])}, {int(q_range[1])}), "
            f"k [{int(k_range[0])}, {int(k_range[1])})): bad mask type word "
            f"{word} (type {word & 3}, step 2**{word >> MASK_TYPE_BITS}; a "
            f"step is at most 2**{MAX_MASK_STEP_LOG2})"
        )


def _slice_k_span(
    gq_lo: int, gq_hi: int, ks: int, ke: int, qs: int, qe: int, mask_type: int
) -> tuple[int, int]:
    """Global k interval attended by global q rows [gq_lo, gq_hi) of a slice.

    The bounds move ``1 << ls`` keys every as many rows, in blocks counted
    from the slice's aligned corner (ls = 0: one key a row)."""
    k_lo, k_hi = ks, ke
    ls = mask_type >> 2
    if mask_type & 1:  # causal: k - ke <= q - qe; max row gq_hi-1
        k_hi = min(k_hi, ke - ((qe - gq_hi) >> ls << ls))
    if mask_type & 2:  # inv-causal: k - ks >= q - qs; min row gq_lo
        k_lo = max(k_lo, ks + ((gq_lo - qs) >> ls << ls))
    return k_lo, k_hi


def _emit_entries(
    slices: np.ndarray,  # [S, 5] (qs, qe, ks, ke, type) global coords
    q_runs: Sequence[Run],
    k_runs: Sequence[Run],
    block_q: int,
    block_k: int,
) -> list[tuple]:
    """All (q_block, k_block, slice, runfields...) tiles intersecting the mask.

    Entry tuple: (qblk, kblk, sid, ql0, ql1, kl0, kl1, qoff, koff).
    """
    out: list[tuple] = []
    for sid in range(slices.shape[0]):
        qs, qe, ks, ke, mt = (int(x) for x in slices[sid])
        if qs >= qe or ks >= ke:
            continue
        for qr in q_runs:
            # global q rows of this run covered by the slice
            gq_lo = max(qs, qr.global_start)
            gq_hi = min(qe, qr.global_end)
            if gq_lo >= gq_hi:
                continue
            ql_lo = gq_lo - qr.offset  # local rows
            ql_hi = gq_hi - qr.offset
            for i in range(ql_lo // block_q, _cdiv(ql_hi, block_q)):
                bq_lo = max(ql_lo, i * block_q)
                bq_hi = min(ql_hi, (i + 1) * block_q)
                # k span needed by these global rows
                k_lo, k_hi = _slice_k_span(
                    bq_lo + qr.offset, bq_hi + qr.offset, ks, ke, qs, qe, mt
                )
                if k_hi <= k_lo:
                    continue
                for kr in k_runs:
                    gk_lo = max(k_lo, kr.global_start)
                    gk_hi = min(k_hi, kr.global_end)
                    if gk_lo >= gk_hi:
                        continue
                    kl_lo = gk_lo - kr.offset
                    kl_hi = gk_hi - kr.offset
                    for j in range(kl_lo // block_k, _cdiv(kl_hi, block_k)):
                        out.append(
                            (
                                i,
                                j,
                                sid,
                                bq_lo,
                                bq_hi,
                                max(kl_lo, j * block_k),
                                min(kl_hi, (j + 1) * block_k),
                                qr.offset,
                                kr.offset,
                            )
                        )
    return out


def _needs_mask_flags(
    entries: np.ndarray,  # [E, 9] sorted entries
    slices: np.ndarray | None,  # [S, 5]
    block_q: int,
    block_k: int,
) -> np.ndarray:
    """1 where the tile's mask constraints actually bind, 0 where it is
    provably fully unmasked (window covers the whole tile AND the slice
    constraints hold at the worst corners).

    DIAGNOSTIC ONLY since the round-5 kernel rewrite: the kernels apply
    the branch-free row-interval mask unconditionally (a per-entry
    lax.cond skip measured 37% SLOWER on dense-causal 64k — see
    flex_attn._entry_interval_mask), so this flag no longer gates any
    kernel work. It remains in the table (RUN_FIELDS slot 6) for plan
    diagnostics — interior-tile fraction is a useful mask statistic —
    and for table-ABI stability with the C++ planner parity tests."""
    e = entries.shape[0]
    from .. import env
    if (
        e == 0
        or slices is None
        or slices.shape[0] == 0  # rank/stage with no work: all dummies
        or env.mask_skip_disabled()
    ):
        return np.ones((e,), dtype=np.int64)
    qb = entries[:, 0]
    kb = entries[:, 1]
    sid = np.minimum(entries[:, 2], slices.shape[0] - 1)
    dummy = entries[:, 2] >= slices.shape[0]
    ql0, ql1 = entries[:, 3], entries[:, 4]
    kl0, kl1 = entries[:, 5], entries[:, 6]
    qoff, koff = entries[:, 7], entries[:, 8]
    r0 = qb * block_q
    c0 = kb * block_k
    # window covers the whole tile
    full = (ql0 <= r0) & (ql1 >= r0 + block_q) & (kl0 <= c0) & (
        kl1 >= c0 + block_k
    )
    qs, qe = slices[sid, 0], slices[sid, 1]
    ks, ke = slices[sid, 2], slices[sid, 3]
    mt = slices[sid, 4]
    gq_lo, gq_hi = r0 + qoff, r0 + block_q - 1 + qoff
    gk_lo, gk_hi = c0 + koff, c0 + block_k - 1 + koff
    full &= (gq_lo >= qs) & (gq_hi < qe) & (gk_lo >= ks) & (gk_hi < ke)
    causal = (mt & 1) != 0
    inv = (mt & 2) != 0
    ls = mt >> 2  # a stepped bound is whole on more tiles than a diagonal
    # causal worst corner: top row, rightmost col
    full &= ~causal | (gk_hi < ke - ((qe - 1 - gq_lo) >> ls << ls))
    # inv-causal worst corner: bottom row, leftmost col
    full &= ~inv | (gk_lo >= ks + ((gq_hi - qs) >> ls << ls))
    full &= ~dummy
    return (~full).astype(np.int64)


def mark_q_visits(q_block: np.ndarray, runs: np.ndarray) -> np.ndarray:
    """The k-major runs table with each entry's visit bits set from the
    table as it will be walked, i.e. after every padding: FIRST_VISIT on
    the first entry that names a q block, LAST_VISIT on the last one (an
    entry may be both). Every entry counts as a visit of the q block it
    names, pads and dummies too (they name q block 0 under the sentinel
    slice and add exact zeros), so the walk's first entry is always a
    first visit and the kernel needs no third kind of step. Bits already
    there (a meta that is padded again) are recomputed; bit 0 stays."""
    qb = np.asarray(q_block, dtype=np.int64)
    words = np.array(runs, dtype=np.int32).reshape(-1, RUN_FIELDS)
    n = qb.shape[0]
    first = np.zeros(n, bool)
    last = np.zeros(n, bool)
    # first occurrences; the last ones are the first of the reversal
    first[np.unique(qb, return_index=True)[1]] = True
    last[n - 1 - np.unique(qb[::-1], return_index=True)[1]] = True
    words[:, 6] = (
        (words[:, 6] & NEEDS_MASK) | first * FIRST_VISIT | last * LAST_VISIT
    )
    return words.reshape(-1)


def q_visit_counts(q_block: np.ndarray, num_q_blocks: int) -> tuple[int, int]:
    """(q blocks a k-major table names in some entry, q blocks it names in
    none) of one rank's table. A block no entry names is never written by
    the backward's walk: its dq has to come back as zeros from a fill."""
    named = np.unique(np.asarray(q_block)).size
    return named, max(int(num_q_blocks), named) - named


def _distribute_pad_majors(
    major: np.ndarray, extra: int, num_major: int
) -> np.ndarray:
    """Major-block values for ``extra`` inert pad entries, chosen to keep
    per-major row counts level (always the currently-shortest row).

    Appending all pads to one major — the old behavior — inflates that
    row's count and with it the kernels' static inner-grid extent
    S = max row count, turning cross-rank entry padding into dead grid
    steps multiplied across EVERY row of every rank.
    """
    import heapq

    counts = np.bincount(
        np.asarray(major, dtype=np.int64), minlength=max(num_major, 1)
    )
    heap = [(int(c), i) for i, c in enumerate(counts)]
    heapq.heapify(heap)
    out = np.empty(extra, np.int32)
    for n in range(extra):
        c, i = heapq.heappop(heap)
        out[n] = i
        heapq.heappush(heap, (c + 1, i))
    return out


def _append_pads_leveled(
    major: np.ndarray,
    minor: np.ndarray,
    sid: np.ndarray,
    runs: np.ndarray,
    extra: int,
    num_major: int,
    sentinel: int,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Append ``extra`` inert (sentinel-slice, all-masked) pad entries with
    leveled major assignment, then stable-resort by major so each major's
    entries stay contiguous (the row-major kernels require it)."""
    pad_major = _distribute_pad_majors(major, extra, num_major)
    pad_runs = np.zeros((extra, RUN_FIELDS), np.int32)
    pad_runs[:, 6] = 1  # diagnostic flag: sentinel-slice pads are all-masked
    major = np.concatenate([major, pad_major])
    minor = np.concatenate([minor, np.zeros(extra, np.int32)])
    sid = np.concatenate([sid, np.full(extra, sentinel, np.int32)])
    runs2 = np.concatenate(
        [runs.reshape(-1, RUN_FIELDS), pad_runs], axis=0
    )
    order = np.argsort(major, kind="stable")
    return (
        np.ascontiguousarray(major[order]),
        np.ascontiguousarray(minor[order]),
        np.ascontiguousarray(sid[order]),
        np.ascontiguousarray(runs2[order].reshape(-1)),
    )


def _build_table(
    entries: np.ndarray,  # [E, 9] entry tuples (major-first ordering applied)
    num_major_blocks: int,
    sentinel_slice: int,
    pad_to: int,
    major_col: int = 0,
    slices_for_flags: np.ndarray | None = None,
    block_q_f: int = 0,
    block_k_f: int = 0,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Sort by major block, add dummies for uncovered majors, pad length."""
    dummy = [0] * 9
    dummy[2] = sentinel_slice
    covered = np.zeros(num_major_blocks, dtype=bool)
    if entries.size:
        covered[entries[:, major_col]] = True
    dummies = []
    for i in range(num_major_blocks):
        if not covered[i]:
            row = list(dummy)
            row[major_col] = i
            dummies.append(row)
    if dummies:
        d = np.asarray(dummies, dtype=np.int64)
        entries = np.concatenate([entries, d], axis=0) if entries.size else d
    minor_col = 1 - major_col
    order = np.lexsort(
        (entries[:, 2], entries[:, minor_col], entries[:, major_col])
    )
    entries = entries[order]
    e = entries.shape[0]
    target = max(_round_up(e, max(pad_to, 1)), 1)
    if target > e:
        pad = np.tile(np.asarray([dummy], dtype=np.int64), (target - e, 1))
        pad[:, major_col] = _distribute_pad_majors(
            entries[:, major_col], target - e, num_major_blocks
        )
        entries = np.concatenate([entries, pad], axis=0)
        entries = entries[np.argsort(entries[:, major_col], kind="stable")]
    flags = _needs_mask_flags(entries, slices_for_flags, block_q_f, block_k_f)
    major = entries[:, major_col].astype(np.int32)
    minor = entries[:, minor_col].astype(np.int32)
    sid = entries[:, 2].astype(np.int32)
    runs = np.concatenate(
        [entries[:, 3:9], flags[:, None]], axis=1
    ).astype(np.int32).reshape(-1)
    return major, minor, sid, runs


def build_block_meta_general(
    slices: np.ndarray,  # [S, 5] global (qs, qe, ks, ke, type)
    q_runs: Sequence[Run],
    k_runs: Sequence[Run],
    total_q: int,  # local q rows
    total_k: int,  # local k rows
    *,
    block_q: int = 128,
    block_k: int = 128,
    entry_pad: int = 8,
    pad_entries_to: int | None = None,  # uniform E across ranks (SPMD)
    pad_bwd_entries_to: int | None = None,
    num_slices_padded: int | None = None,
) -> FlexAttnBlockMeta:
    """Build entry tables for one rank's local attention problem.

    Local buffers are described by runs (local<->global segment map); the
    mask slices stay in global coordinates.
    """
    slices = np.asarray(slices, dtype=np.int64).reshape(-1, SLICE_FIELDS)
    S = slices.shape[0]
    nq = max(_cdiv(total_q, block_q), 1)
    nk = max(_cdiv(total_k, block_k), 1)

    q_runs_arr = np.asarray(
        [(r.local_start, r.global_start, r.length) for r in q_runs],
        dtype=np.int64,
    ).reshape(-1, 3)
    k_runs_arr = np.asarray(
        [(r.local_start, r.global_start, r.length) for r in k_runs],
        dtype=np.int64,
    ).reshape(-1, 3)

    from ..csrc import emit_entries_native

    entries = emit_entries_native(
        slices, q_runs_arr, k_runs_arr, block_q, block_k
    )
    if entries is None:  # python fallback (also the parity oracle)
        ent = _emit_entries(
            slices, list(q_runs), list(k_runs), block_q, block_k
        )
        entries = (
            np.asarray(ent, dtype=np.int64)
            if ent
            else np.empty((0, 9), dtype=np.int64)
        )

    # exact area: intersect each slice with the runs (a slice may reference
    # global rows/cols this rank does not hold)
    from ..csrc import slice_area_runs_native

    area_native = slice_area_runs_native(slices, q_runs_arr, k_runs_arr)
    if area_native is not None:
        area = area_native
    else:
        area = 0
        for sid in range(S):
            qs, qe, ks, ke, mt = (int(x) for x in slices[sid])
            for qr in q_runs:
                a, b = max(qs, qr.global_start), min(qe, qr.global_end)
                if a >= b:
                    continue
                k_lo, k_hi = _slice_k_span(a, b, ks, ke, qs, qe, mt)
                for kr in k_runs:
                    c, d = max(k_lo, kr.global_start), min(k_hi, kr.global_end)
                    if c >= d:
                        continue
                    area += _sub_area(a, b, c, d, qs, qe, ks, ke, mt)

    return assemble_block_meta(
        entries,
        slices,
        total_q,
        total_k,
        block_q,
        block_k,
        int(area),
        entry_pad=entry_pad,
        pad_entries_to=pad_entries_to,
        pad_bwd_entries_to=pad_bwd_entries_to,
        num_slices_padded=num_slices_padded,
    )


def assemble_block_meta(
    entries: np.ndarray,  # [E, 9] (qblk, kblk, sid, ql0, ql1, kl0, kl1, qoff, koff)
    slices: np.ndarray,  # [S, SLICE_FIELDS]
    total_q: int,
    total_k: int,
    block_q: int,
    block_k: int,
    total_area: int,
    *,
    entry_pad: int = 8,
    pad_entries_to: int | None = None,
    pad_bwd_entries_to: int | None = None,
    num_slices_padded: int | None = None,
) -> FlexAttnBlockMeta:
    """Entries + slices -> FlexAttnBlockMeta: sort both orientations, add
    dummies/pads, assemble bounds. Shared by the general slice-emission
    builder and planners that emit entries directly (block-sparse), so
    table-ABI details live in exactly one place."""
    S = slices.shape[0]
    nq = max(_cdiv(total_q, block_q), 1)
    nk = max(_cdiv(total_k, block_k), 1)
    fwd = _build_table(
        entries.copy(), nq, S, entry_pad, major_col=0,
        slices_for_flags=slices, block_q_f=block_q, block_k_f=block_k,
    )
    bwd = _build_table(
        entries.copy(), nk, S, entry_pad, major_col=1,
        slices_for_flags=slices, block_q_f=block_q, block_k_f=block_k,
    )

    def _pad_table(table, target, num_major):
        major, minor, sid, runs = table
        e = major.shape[0]
        if target is None or target <= e:
            assert target is None or target == e, (
                f"table length {e} exceeds requested pad {target}"
            )
            return table
        return _append_pads_leveled(
            major, minor, sid, runs, target - e, num_major, S
        )

    fwd = _pad_table(fwd, pad_entries_to, nq)
    bwd = _pad_table(bwd, pad_bwd_entries_to, nk)
    bwd = (*bwd[:3], mark_q_visits(bwd[1], bwd[3]))

    n_slices_store = S if num_slices_padded is None else num_slices_padded
    assert n_slices_store >= S
    bounds = np.zeros((n_slices_store + 1, SLICE_FIELDS), dtype=np.int32)
    bounds[:S] = slices
    # rows S..n_slices_store stay all-zero (sentinels: empty range = all-masked)

    return FlexAttnBlockMeta(
        total_q=total_q,
        total_k=total_k,
        block_q=block_q,
        block_k=block_k,
        num_q_blocks=nq,
        num_k_blocks=nk,
        num_slices=n_slices_store,
        total_area=int(total_area),
        fwd_q_block=fwd[0],
        fwd_k_block=fwd[1],
        fwd_slice_id=fwd[2],
        fwd_runs=fwd[3],
        bwd_k_block=bwd[0],
        bwd_q_block=bwd[1],
        bwd_slice_id=bwd[2],
        bwd_runs=bwd[3],
        slice_bounds=bounds.reshape(-1),
    )


def _sub_area(a, b, c, d, qs, qe, ks, ke, mt) -> int:
    """Unmasked pairs in global sub-rectangle rows [a,b) x cols [c,d).

    Row q attends cols [lo(q), hi(q)) with lo = ks + (q - qs) under an
    inv-causal bound (else ks) and hi = ke - qe + q + 1 under a causal bound
    (else ke), either in blocks of the type word's step; vectorized over
    rows (host-side planning only).
    """
    from ..common.mask import row_key_bounds

    lo, hi = row_key_bounds(np.arange(a, b), qs, qe, ks, ke, mt)
    cnt = np.minimum(hi, d) - np.maximum(lo, c)
    return int(np.maximum(cnt, 0).sum())


def pad_block_meta(
    meta: FlexAttnBlockMeta,
    pad_entries_to: int,
    pad_bwd_entries_to: int,
    num_slices_padded: int,
) -> FlexAttnBlockMeta:
    """Pad a built meta's tables to uniform lengths (SPMD across ranks).

    Pad entries replicate the last major block with the sentinel slice
    (all-masked, inert); extra bounds rows are zeros (further sentinels).
    """
    S = meta.num_slices
    assert num_slices_padded >= S

    def pad_tab(major, minor, sid, runs, target, sentinel, num_major):
        e = major.shape[0]
        assert target >= e, f"table length {e} exceeds pad target {target}"
        if target == e:
            return major, minor, sid, runs
        return _append_pads_leveled(
            major, minor, sid, runs, target - e, num_major, sentinel
        )

    fq, fk, fs, fr = pad_tab(
        meta.fwd_q_block,
        meta.fwd_k_block,
        meta.fwd_slice_id,
        meta.fwd_runs,
        pad_entries_to,
        S,
        meta.num_q_blocks,
    )
    bk, bq, bs, br = pad_tab(
        meta.bwd_k_block,
        meta.bwd_q_block,
        meta.bwd_slice_id,
        meta.bwd_runs,
        pad_bwd_entries_to,
        S,
        meta.num_k_blocks,
    )
    br = mark_q_visits(bq, br)  # the pads changed who is first and last
    bounds = np.zeros(((num_slices_padded + 1) * SLICE_FIELDS,), np.int32)
    bounds[: meta.slice_bounds.shape[0]] = meta.slice_bounds
    return dataclasses.replace(
        meta,
        num_slices=num_slices_padded,
        fwd_q_block=fq,
        fwd_k_block=fk,
        fwd_slice_id=fs,
        fwd_runs=fr,
        bwd_k_block=bk,
        bwd_q_block=bq,
        bwd_slice_id=bs,
        bwd_runs=br,
        slice_bounds=bounds,
    )


def build_block_meta(
    q_ranges: np.ndarray | Sequence[Sequence[int]],
    k_ranges: np.ndarray | Sequence[Sequence[int]],
    attn_type_map: np.ndarray | Sequence[int],
    total_q: int,
    total_k: int,
    *,
    block_q: int = 128,
    block_k: int = 128,
    entry_pad: int = 8,
) -> FlexAttnBlockMeta:
    """Single-device plan: identity runs, slices given as range lists."""
    q_arr = np.asarray(q_ranges, dtype=np.int64).reshape(-1, 2)
    k_arr = np.asarray(k_ranges, dtype=np.int64).reshape(-1, 2)
    t_arr = np.asarray(attn_type_map, dtype=np.int64).reshape(-1)
    assert q_arr.shape[0] == k_arr.shape[0] == t_arr.shape[0]
    for s in range(t_arr.shape[0]):
        assert 0 <= q_arr[s, 0] <= q_arr[s, 1] <= total_q, (
            f"slice {s}: bad q_range [{q_arr[s,0]},{q_arr[s,1]})"
        )
        assert 0 <= k_arr[s, 0] <= k_arr[s, 1] <= total_k, (
            f"slice {s}: bad k_range [{k_arr[s,0]},{k_arr[s,1]})"
        )
        check_type_word(s, q_arr[s], k_arr[s], int(t_arr[s]))
    slices = np.concatenate(
        [q_arr, k_arr, t_arr[:, None]], axis=1
    )  # [S, 5]
    return build_block_meta_general(
        slices,
        identity_runs(total_q),
        identity_runs(total_k),
        total_q,
        total_k,
        block_q=block_q,
        block_k=block_k,
        entry_pad=entry_pad,
    )
