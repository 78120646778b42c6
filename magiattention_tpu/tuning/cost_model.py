"""Analytic cost model ranking kernel block configurations per workload.

The round-5 kernels run a row-major grid (heads/head_block, num_q_blocks,
steps) over a host-built entry table (one entry per (q-block, k-block,
slice) tile intersecting the mask — ``ops/block_meta.py``). Three costs
follow directly from that structure, and all three depend on the MASK
SHAPE, not just the total seqlen the old static table keyed on:

- **tile compute** — every emitted entry pays a full (block_q x block_k)
  MXU tile regardless of how much of it the mask covers, so narrow slices
  (SWA bands, short varlen blocks) waste most of a 1024-wide tile;
- **grid steps** — each live step carries fixed overhead (calibrated from
  the round-5 stock-flash control: (256,512) at 71.5 vs (1024,1024) at
  99.9 TF/s on 64k causal with near-identical tile FLOPs), and clamped
  dead steps (rows shorter than the static ``steps`` extent) still cost a
  reduced per-step fee;
- **operand bytes** — a step streams fresh tiles from HBM (K and V in the
  q-major forward and dq, q, dO and the row statistics in the k-major
  dkv), and a kernel takes the longer of its MXU and its HBM time: at GQA
  group 1 a ``block_q`` of 128 reads a byte per 128 FLOPs where the
  chip's balance is 240 (:func:`step_bytes`, :data:`HBM_PRICE_SHARE`);
- **SMEM pressure** — the scalar-prefetch entry table must fit the ~1 MB
  scalar core budget (``flex_attn._MAX_SMEM_ENTRIES``), which rules small
  tiles out for huge dense masks.

Entry/step counts are computed EXACTLY for identity-run layouts by
intersecting every slice with the candidate's q-block grid (vectorized
numpy, O(num_slices * num_q_blocks) — host planning scale). Feasibility
reads the tables those counts describe where the plan's tables are the
global ones (one device, cp = 1), and the legacy upper bound (every slice's
misalignment-padded bounding box, scaled to a rank) where they are
per-rank tables over fragmented runs: :func:`smem_entries`.
"""

from __future__ import annotations

import dataclasses
import re
from typing import NamedTuple

import numpy as np

from ..common.mask import row_key_bounds
from ..utils.cost import TPU_PEAK_SPECS

# Per-live-grid-step fixed overhead (seconds): calibrated so the modeled
# gap between the (256,512) and (1024,1024) rungs on 64k dense causal
# matches the measured 71.5 -> 99.9 TF/s spread (~34 ms over ~132k steps).
STEP_OVERHEAD_S = 3.0e-7
# Clamped dead steps skip compute and re-DMA nothing; they still occupy a
# grid slot. Measured indirectly (leveled-pad experiments, round 4).
DEAD_STEP_OVERHEAD_S = 5.0e-8
# Extra per-live-step fee of the compact sparse grid: its q-side index
# maps are dynamic (``qblk[e]``), so Mosaic cannot statically prove
# q-block residency across steps the way the row-major grid's static
# maps allow — the round-5 flat-grid experiment bounds the worst case
# (dynamic maps on FULLY dense 64k: 76 vs 132 TF/s) but the sparse walk
# keeps entries q-sorted (residency changes only at row boundaries), so
# the priced fee is a fraction of that bound. The asymmetry is the
# point: dense workloads (dead slots ~0 anyway) stay on the measured
# row-major rungs, heterogeneous masks (dead + partial-tile dominated)
# escape to the sparse grid. (An estimate from before the ledger: PR 27
# measured the fee at -0.015 to +0.07 us, COMPACT_STEP_EXTRA_S below, and
# a dead step at 0.21-0.30 us where DEAD_STEP_OVERHEAD_S has 0.05. These
# three rank the rungs of every mask and are ROADMAP D4's to correct.)
SPARSE_STEP_OVERHEAD_S = 1.5e-7
# -- the grid of a built plan (``parallel/dist_attn.make_attn_params``) ----
# Two per-step prices measured on the chip (v5e, my chip runs, PR 27;
# PERF.md section 6), from whole kernels of the benchmark's cells at 64 q /
# 8 kv heads, bf16, head_dim 128, traced runs of 38 s. They price the GRID
# of a plan whose rung is already chosen and enter no rung's ranking: the
# constants above pick the rung of every mask, and folding these in is the
# cost model's own PR (ROADMAP D4).
#
# A dead row-major step: a slot past its block's entry count, which skips
# compute. The packed 64k cell at (128, 512, 8) on both grids: forward
# 131.77 -> 109.04 ms, dq 107.59 -> 74.71, dkv 128.36 -> 96.29 with
# 110,144 / 110,144 / 107,104 dead steps of 8 heads gone: 0.206 / 0.299 /
# 0.299 us, 87.68 ms over 327,392 = 0.268 us. (Per head it is dearer where
# the dead step still moves data: the dense 64k cell's per-head dkv,
# grid (hk, nk, steps, group), lost 272.8 ms with 132,608 dead slots,
# 2.06 us each: a dead slot of another group member fetches that head's q
# and dO block again.)
DEAD_ROW_MAJOR_STEP_S = 2.7e-7
# What the compact walk adds to a live step (q-side index maps read from
# the table, init and write tests on table values). The window-1024 64k
# mask pinned to (128, 512, 8), 0.8% dead, on both grids: forward 48.19 ->
# 48.62 ms, dq 31.99 -> 31.80, dkv 40.34 -> 40.53 over 12,224 steps each:
# +0.035 / -0.015 / +0.015 us, +0.012 us over the three. Per head at
# (1024, 1024, 1), 8,128 steps: dq +0.069 us, dkv -0.006. Set to the
# largest head-batched reading, the forward's.
COMPACT_STEP_EXTRA_S = 3.5e-8
# The compact grid is chosen only where what it saves passes the error bar
# of those readings (the dead step's spread over the three kernels is
# +-20%, the fee's sign changes from kernel to kernel): its fee times this
# must stay under the dead steps' price. With the two prices above that is
# a plan whose dead steps are more than a quarter of its entries.
GRID_FLIP_MARGIN = 2.0


def price_grids(row_major_steps: int, compact_steps: int) -> tuple[float, float]:
    """Seconds a head group's forward, dq and dkv spend on what the two
    grids do NOT share: the row-major grid's dead steps, and the compact
    walk's fee on every step it launches. A padded entry is a step of both
    (it computes on an empty mask), so the steps the row-major grid
    launches beyond the compact one's are exactly its dead ones."""
    dead = max(int(row_major_steps) - int(compact_steps), 0)
    return dead * DEAD_ROW_MAJOR_STEP_S, compact_steps * COMPACT_STEP_EXTRA_S


def choose_grid(row_major_steps: int, compact_steps: int) -> str:
    """``"sparse"`` (the compact entry walk) where the dead steps cost more
    than the walk's fee by :data:`GRID_FLIP_MARGIN`, else ``"row_major"``."""
    row_major_s, compact_s = price_grids(row_major_steps, compact_steps)
    return "sparse" if row_major_s > GRID_FLIP_MARGIN * compact_s else "row_major"


# Candidates within this relative cost of the best are considered a tie
# and resolved by the measured preference order (the analytic model is
# deliberately not trusted below its own error bar — the static table's
# on-chip measurements are).
TIE_TOLERANCE = 0.15

# -- operand bytes (ISSUE 35) ------------------------------------------------
# The share of the HBM's peak at which a streamed byte is priced, on the
# scale on which ``mxu_seconds`` prices a FLOP at ``TpuPeakSpec.mfu`` (0.5)
# of the MXU's. From the chip (v5e; the GLM cell's 16k packed mask at 20 q =
# 20 kv heads of 256, compact grid; PR 30, PR 31 and PR 35's probe, PERF.md
# sections 5 and 6): the HBM-bound forward of (128, 512, 5) takes 6.148 ms
# for 1,600 steps of 2.62 MB, 5.12 ms at 819 GB/s: 0.83 of the peak. The
# MXU-bound kernels of (256, 512, 4) take 4.451 / 5.509 / 6.925 ms (forward /
# dq / dkv) for 2.943 / 4.415 / 5.887 ms of MXU time at 197 TFLOP/s: 0.78 of
# that peak, 1.57 x what ``mfu`` prices. 0.83 / 1.57 = 0.53; and the value
# at which the price ratio of the two rungs is the chip's forward + dq + dkv
# ratio (1.17; 1.13 for the whole forward+backward call) is 0.53-0.57.
HBM_PRICE_SHARE = 0.55
# FLOPs of one tile in units of the forward's two matmuls: the forward, the
# fused backward ``bwd`` (five: S, dP, dV, dK, dQ), and the q-major dq (three)
# and k-major dkv (four) it replaced (PR 43), which each computed S and dP
KERNEL_FLOP_WEIGHTS = {"fwd": 1.0, "dq": 1.5, "dkv": 2.0, "bwd": 2.5}
# What a rung's price sums (``rank_candidates``): the three kernels its
# constants were calibrated on. Pricing the fused backward in their place
# re-ranks every mask, which is the cost model's own PR (ROADMAP D4); the
# HBM-bound rungs are the same either way (a k-major step's bytes follow
# ``block_q`` x heads in ``dkv`` and in ``bwd`` alike).
RANKED_KERNELS = ("fwd", "dq", "dkv")


def step_bytes(
    kernel: str,
    block_q: int,
    block_k: int,
    head_block: int,
    group: int,
    head_dim: int,
    itemsize: int,
    v_head_dim: int | None = None,
) -> int:
    """Bytes one live step of ``kernel`` (``fwd`` | ``bwd``, and the
    ``dq`` | ``dkv`` the ranking was calibrated on) moves to and from HBM.
    The forward (and dq) walk q-major: a step brings the K and V tiles of
    the key-value heads its ``head_block`` query heads share (a per-head
    step, ``head_block`` 1, brings one pair whatever the group). The
    backward walks k-major: a step brings q, dO and the two float32
    statistics (lse, delta) of its query heads with rows along lanes, 8
    bytes a row and head (``dkv``), and ``bwd`` also reads and writes
    their float32 dq tile: at head_dim 128 in bf16 1,544 bytes a row and
    head, 0.83 x ``block_k`` FLOPs a byte whatever the GQA group.

    Not counted: the tiles that stay while a block's entries run (q in the
    forward; K, V, dk and dv in the backward). Each is brought or written
    once a block, so over a call they are the tensors' own size whatever
    the rung: they move no order between rungs. ``v_head_dim``: the width
    of V and dO where it is not that of K and q (``head_dim``)."""
    dv = head_dim if v_head_dim is None else v_head_dim
    if kernel in ("dkv", "bwd"):
        row = (head_dim + dv) * itemsize + 2 * 4
        if kernel == "bwd":
            row += 2 * head_dim * 4  # the float32 dq tile, in and out
        return head_block * block_q * row
    return max(head_block // group, 1) * block_k * (head_dim + dv) * itemsize

# Sparse-only blockings: smaller tiles than any row-major rung carries.
# On the row-major grid small tiles lose to grid-step overhead (the
# static ``steps`` extent multiplies every row), but the sparse walk
# pays only live entries — and small tiles are what kill the
# partial-tile/masked-entry overcompute on narrow varlen blocks (the
# 16k varlen headline's ~6x scheduled-vs-true FLOPs at (128, 512)).
# head_block preferences sized like the small row-major rungs (the K/V
# double-buffer footprint is smaller than (128, 512, 8)'s).
SPARSE_ONLY_CONFIGS: tuple[tuple[int, int, int], ...] = (
    (128, 256, 8),
    (256, 256, 8),
    (256, 512, 8),
    (256, 768, 8),
    (512, 512, 4),
    (512, 768, 4),
)

# Below this covered fraction (true mask area / dense extent) a workload
# is in the heterogeneous regime where the row-major grid's measured
# throughput collapses (16k varlen block-causal: 8.44 TF/s at ~0.20
# density vs 101-113 TF/s on >= 0.5-density dense causal) — per-step
# overheads the analytic model cannot price dominate. Ties are then
# resolved toward the sparse grid with the FEWEST total grid slots
# instead of the dense-measured preference order (where sparse candidates
# are ranked: no distributed plan, ``include_sparse=False``). Under it the
# preference order itself drops its long-sequence lead, for every caller
# (:func:`_preference_order`, ISSUE 54).
SPARSE_DENSITY_THRESHOLD = 0.25
# Tie band in that regime: the model's residual on the one measured
# heterogeneous workload is ~8x (8.44 TF/s measured vs ~70 modeled), so
# the dense-calibrated 15% band is false precision there; 30% still
# bounds the modeled regression a slot-minimizing rung may accept while
# letting coarse-tile sparse candidates (fewest grid steps) through.
SPARSE_TIE_TOLERANCE = 0.30

# -- the priced pair (ISSUE 56) ----------------------------------------------
# Inside the tie pool two head-batched row-major rungs that differ in
# ``block_q`` alone, (128, 512, hb) and (256, 512, hb) today, are ordered by
# their own price: the larger ``block_q`` leads where it is cheaper by this
# share, else the table's order stands. The price may be trusted for this
# pair and not for the pool at large because the two differ in exactly the
# terms it counts from the mask itself, the computed tiles and the steps.
# Set from the chip (v5e; my chip run, PR 56, PERF.md section 6), the pair
# pinned by ``MAGI_ATTENTION_BLOCK_Q`` / ``_BLOCK_K`` / ``_HEAD_BLOCK``
# against the tuner's (128, 512, 8), 256's price over 128's in brackets: the
# packed 64k cell [-5.3%] forward 67.66 -> 62.18 ms, forward+backward 199.78
# -> 189.06; the window-1024 cell [-5.0%] 35.93 -> 33.11 and 98.02 -> 92.20;
# and the two masks at the break-even: Mistral's 17 documents [+1.1%, 8.1%
# more tile area] 32,012.1 -> 32,020.2 tokens/s, every unit of six steps
# faster than the other side's fastest, and Trinity's 29 documents with both
# plans pinned [-0.2% global, +2.6% sliding] 33,455.7 -> 33,479.5, inside its
# units' spread. The chip sided with 256 even where the price calls a dead
# heat, so no margin holds 256 back: 0, and the pair stands in the order of
# its prices.
PAIR_PRICE_MARGIN = 0.0


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


@dataclasses.dataclass(frozen=True)
class CandidateScore:
    """One ranked rung: predicted cost plus the estimates behind it."""

    block_q: int
    block_k: int
    head_block: int  # snapped to the workload's GQA group / hq
    entries: int  # exact tile count (identity runs), incl. dummies
    steps: int  # max entries on any q block = static inner-grid extent
    smem_entries: int  # the count the SMEM test read (:func:`smem_entries`)
    feasible: bool
    mxu_seconds: float
    step_seconds: float
    # "row_major" (static steps grid) or "sparse" (compact entry walk);
    # sparse candidates have zero dead slots by construction
    grid: str = "row_major"
    live_slots: int = 0  # grid_rows * entries (slots that compute)
    dead_slots: int = 0  # clamped slots past a row's entry count
    smem_count: str = "bound"  # which count ``smem_entries`` is: exact | bound
    # the forward's K and V stream at the priced share of the HBM's peak:
    # the time ``mxu_seconds`` is held against (:attr:`bound`)
    hbm_seconds: float = 0.0
    # what forward, dq and dkv take beyond their MXU time where the HBM's is
    # the longer, forward-sized (0.0 wherever the bytes are slack)
    hbm_excess_seconds: float = 0.0
    # the order the ranking this rung came from breaks a tie by
    # (:func:`_preference_order`): ``long_seq`` | ``measured`` | ``given``;
    # ``priced_pair`` on a rung its own price put ahead of its smaller
    # ``block_q`` in the tie pool (:func:`_lead_pairs_by_price`)
    tie_order: str = ""

    @property
    def bound(self) -> str:
        """``hbm`` where the rung's own price says its forward steps run
        at the HBM's pace, else ``mxu``."""
        return "hbm" if self.hbm_seconds > self.mxu_seconds else "mxu"

    @property
    def compute_seconds(self) -> float:
        """The price before ISSUE 35: tiles and steps, no bytes."""
        return self.mxu_seconds + self.step_seconds

    @property
    def cost_seconds(self) -> float:
        # a kernel's time is the larger of its MXU and its HBM time:
        # mxu + max(hbm - mxu, 0), summed over the kernels
        return self.compute_seconds + self.hbm_excess_seconds

    @property
    def grid_slots(self) -> int:
        """Total grid slots the candidate launches (live + dead) — the
        step count the acceptance gate tracks on the headline workload."""
        return self.live_slots + self.dead_slots

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["cost_seconds"] = self.cost_seconds
        d["compute_seconds"] = self.compute_seconds
        d["grid_slots"] = self.grid_slots
        d["bound"] = self.bound
        return d


def _normalize_slices(q_ranges, k_ranges, attn_type_map):
    q = np.asarray(q_ranges, dtype=np.int64).reshape(-1, 2)
    k = np.asarray(k_ranges, dtype=np.int64).reshape(-1, 2)
    if attn_type_map is None:
        t = np.zeros(q.shape[0], dtype=np.int64)  # FULL: conservative
    else:
        t = np.asarray(
            [int(x) for x in np.asarray(attn_type_map).reshape(-1)],
            dtype=np.int64,
        )
    assert q.shape[0] == k.shape[0] == t.shape[0]
    # degenerate slices attend nothing and must not stretch the extent
    # (an empty (n, n) sentinel range would otherwise inflate the q-block
    # grid with dummy rows)
    live = (q[:, 1] > q[:, 0]) & (k[:, 1] > k[:, 0])
    return q[live], k[live], t[live]


def estimate_entries(
    q_ranges,
    k_ranges,
    attn_type_map,
    block_q: int,
    block_k: int,
) -> tuple[int, int, int]:
    """(entries, steps, num_q_blocks) for one candidate blocking.

    Exact for identity-run (single-device) layouts: per q block of each
    slice, the attended k interval is computed mask-type-aware (the same
    affine spans ``block_meta._slice_k_span`` emits) and counted in
    k-block units. Uncovered q blocks contribute one dummy entry each
    (the table invariant); ``steps`` is the max per-block entry count —
    the kernel's static inner-grid extent.

    Memoized on a digest of the canonical slice bytes (a digest, not the
    blobs themselves — large varlen range arrays must not be pinned as
    cache keys): the fingerprint's per-rung entry buckets and the ranker's
    scoring pass hit the same workload x rung pairs back to back and must
    not pay the count twice.
    """
    q, k, t = _normalize_slices(q_ranges, k_ranges, attn_type_map)
    return _counted_entries(q, k, t, block_q, block_k)[:3]


def _counted_entries(q, k, t, block_q: int, block_k: int):
    """:func:`_estimate_entries_impl`'s four counts, memoized."""
    key = (slices_digest(q, k, t), int(block_q), int(block_k))
    hit = _ENTRY_MEMO.get(key)
    if hit is None:
        if len(_ENTRY_MEMO) >= _ENTRY_MEMO_CAP:  # crude bound, never grows
            _ENTRY_MEMO.clear()
        hit = _ENTRY_MEMO[key] = _estimate_entries_impl(
            q, k, t, int(block_q), int(block_k)
        )
    return hit


def slices_digest(q, k, t) -> bytes:
    """Stable 32-byte identity of a normalized slice set (shared with the
    fingerprint memo)."""
    import hashlib

    h = hashlib.sha256()
    for a in (q, k, t):
        h.update(np.ascontiguousarray(a).tobytes())
        h.update(b"|")
    return h.digest()


_ENTRY_MEMO: dict = {}
_ENTRY_MEMO_CAP = 4096


def slice_block_k_spans(
    q0: int, q1: int, k0: int, k1: int, mt: int, block_q: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Per-q-block attended k-intervals of ONE slice: (q_block_idx,
    row_lo, row_hi, k_lo, k_hi) vectors, mask-type-aware — the same
    affine spans ``block_meta._slice_k_span`` emits. Blocks whose span is
    empty have ``k_hi <= k_lo``. The counting primitive of the
    autotuner's entry estimator."""
    idx = np.arange(q0 // block_q, _cdiv(q1, block_q), dtype=np.int64)
    lo = np.maximum(q0, idx * block_q)  # first row (inclusive)
    hi = np.minimum(q1, (idx + 1) * block_q)  # last row (exclusive)
    k_lo = np.full(idx.shape, k0, dtype=np.int64)
    k_hi = np.full(idx.shape, k1, dtype=np.int64)
    ls = mt >> 2  # log2 of the slice's step (common/enum.AttnMaskType)
    if mt & 1:  # causal: k - ke <= q - qe
        k_hi = np.minimum(k_hi, k1 - ((q1 - hi) >> ls << ls))
    if mt & 2:  # inv-causal: k - ks >= q - qs
        k_lo = np.maximum(k_lo, k0 + ((lo - q0) >> ls << ls))
    return idx, lo, hi, k_lo, k_hi


def _estimate_entries_impl(
    q: np.ndarray, k: np.ndarray, t: np.ndarray, block_q: int, block_k: int
) -> tuple[int, int, int, int]:
    """(entries, steps, num_q_blocks, bwd_entries). The backward table holds
    the same live tiles under the k blocks, with ITS dummies: one for every
    k block, up to the mask's k extent, that no tile touches."""
    extent_q = int(q[:, 1].max()) if q.size else 0
    nq = max(_cdiv(extent_q, block_q), 1)
    nk = max(_cdiv(int(k[:, 1].max()) if k.size else 0, block_k), 1)
    per_block = np.zeros(nq, dtype=np.int64)
    k_cover = np.zeros(nk + 1, dtype=np.int64)  # +1 / -1 at a run's ends
    for (q0, q1), (k0, k1), mt in zip(q.tolist(), k.tolist(), t.tolist()):
        if q1 <= q0 or k1 <= k0:
            continue
        idx, _, _, k_lo, k_hi = slice_block_k_spans(
            q0, q1, k0, k1, mt, block_q
        )
        covered = k_hi > k_lo
        first = k_lo // block_k
        last = (np.maximum(k_hi, k_lo + 1) - 1) // block_k
        per_block[idx] += np.where(covered, last - first + 1, 0)
        np.add.at(k_cover, first[covered], 1)
        np.add.at(k_cover, last[covered] + 1, -1)
    dummies = int((per_block == 0).sum())
    live = int(per_block.sum())
    entries = live + dummies
    steps = max(int(per_block.max()) if per_block.size else 0, 1)
    bwd_dummies = int((np.cumsum(k_cover[:nk]) == 0).sum())
    return entries, steps, nq, live + bwd_dummies


def exact_mask_area(q_ranges, k_ranges, attn_type_map) -> int:
    """EXACT valid-entry count of the mask (row-wise, vectorized numpy —
    O(total q rows) per slice, host planning scale). This is the area a
    mask's true FLOPs are counted from; memoized on the canonical
    slice digest like the entry counts (the ranker's density test hits
    the same workloads repeatedly).

    Summed PER SLICE — the kernel's own work convention (every slice's
    entries run through the softmax; the runtime rejects masks whose
    slices overlap in (q, k) coverage, see MAGI_ATTENTION_SANITY_CHECK),
    matching how plan ``total_area`` counts."""
    q, k, t = _normalize_slices(q_ranges, k_ranges, attn_type_map)
    key = ("area", slices_digest(q, k, t))
    hit = _ENTRY_MEMO.get(key)
    if hit is None:
        total = 0
        for (q0, q1), (k0, k1), mt in zip(q.tolist(), k.tolist(), t.tolist()):
            # row-exact: the interval of every row, under the slice's step
            r_lo, r_hi = row_key_bounds(
                np.arange(q0, q1), q0, q1, k0, k1, mt
            )
            total += int(np.maximum(r_hi - r_lo, 0).sum())
        if len(_ENTRY_MEMO) >= _ENTRY_MEMO_CAP:
            _ENTRY_MEMO.clear()
        _ENTRY_MEMO[key] = hit = total
    return hit


class SmemCount(NamedTuple):
    """What the SMEM feasibility test read for one rung."""

    entries: int
    count: str  # "exact": the global tables' own; "bound": see smem_entries
    feasible: bool  # entries <= flex_attn._MAX_SMEM_ENTRIES


_ENTRY_PAD = 8  # build_block_meta's default: a table's length is padded to it


def smem_entries(
    q_ranges,
    k_ranges,
    attn_type_map,
    block_q: int,
    block_k: int,
    cp_size: int = 1,
) -> SmemCount:
    """The entry count the SMEM test holds one rung's tables to on the
    EXACT workload, which count it is, and the verdict. THE one answer to
    "does this rung's table fit": the ranker, the cache-hit re-validation
    (``autotuner.select_block_config``) and :func:`any_feasible_rung` all
    read it.

    ``cp_size`` 1: the plan's tables are the global tables (one device, or
    cp = 1), so the count is what ``build_block_meta`` builds and the
    launch guard (``flex_attn._check_smem_budget``) reads (``"exact"``):
    the longer of the forward table (:func:`estimate_entries`' count: the
    live tiles and a dummy for every q block without one) and the backward
    table (the same tiles and a dummy for every k block without one),
    padded as the builder pads it. A buffer longer than its mask has a
    dummy more for every block past the mask's extent, which the slices do
    not show; the launch guard's budget is 2,214 entries over
    ``_MAX_SMEM_ENTRIES``, for those and for its fixed tables.

    ``cp_size`` > 1: the tables are per-rank ones over fragmented runs,
    which the global slices cannot count, so it is every slice's bounding
    box in tiles (``flex_attn._est_entries``) times ``2 / cp`` (a rank's
    share, doubled for run fragmentation; ``"bound"``). The box of a band
    slice (a sliding window's BICAUSAL strip: 65,536 rows against 65,536
    keys where a row attends 1,024) is 40 times its table.

    Memoized (digest keys): the cache-hit re-validation runs on EVERY
    hit, the keyed runtime's steady-state repeat-call path."""
    q, k, t = _normalize_slices(q_ranges, k_ranges, attn_type_map)
    key = (slices_digest(q, k, t), int(block_q), int(block_k), int(cp_size))
    hit = _SMEM_MEMO.get(key)
    if hit is None:
        from ..ops.flex_attn import _MAX_SMEM_ENTRIES, _est_entries

        if cp_size <= 1:
            fwd, _, _, bwd = _counted_entries(q, k, t, block_q, block_k)
            entries = -(-max(fwd, bwd) // _ENTRY_PAD) * _ENTRY_PAD
            which = "exact"
        else:
            box = _est_entries(q.tolist(), k.tolist(), block_q, block_k)
            entries, which = int(box * (2.0 / cp_size)), "bound"
        if len(_SMEM_MEMO) >= _ENTRY_MEMO_CAP:  # crude bound, never grows
            _SMEM_MEMO.clear()
        hit = _SMEM_MEMO[key] = SmemCount(
            entries, which, entries <= _MAX_SMEM_ENTRIES
        )
    return hit


_SMEM_MEMO: dict = {}


def any_feasible_rung(
    q_ranges,
    k_ranges,
    attn_type_map,
    *,
    max_block_q: int | None = None,
    max_block_k: int | None = None,
    cp_size: int = 1,
) -> bool:
    """True when at least one candidate rung fits the exact workload's
    SMEM budget — the re-rank-on-aliased-hit escape hatch: if nothing is
    feasible, a cached escalation winner is as good as re-ranking."""
    from ..ops.flex_attn import _AUTO_BLOCK_CONFIGS

    return any(
        smem_entries(
            q_ranges, k_ranges, attn_type_map, bq, bk, cp_size
        ).feasible
        for bq, bk, _hb in _AUTO_BLOCK_CONFIGS
        if (max_block_q is None or bq <= max_block_q)
        and (max_block_k is None or bk <= max_block_k)
    )


def _preference_order(extent: int, density: float):
    """``(name, rungs)``: the measured rung preference for this extent
    class and mask density — the old static table's ordering, reused as
    the tie-breaker (on-chip measurements outrank the model inside its
    error bar). The long-sequence lead was measured on one dense causal
    slice at 64k; a mask under :data:`SPARSE_DENSITY_THRESHOLD` is not the
    mask it was measured on and gets the table's own order whatever its
    extent. What the table's order still decides: every tie but the one
    between two head-batched rungs that differ in ``block_q`` alone, which
    the price breaks where it prefers the larger by
    :data:`PAIR_PRICE_MARGIN` (:func:`_lead_pairs_by_price`, ISSUE 56)."""
    from ..ops.flex_attn import (
        _AUTO_BLOCK_CONFIGS,
        _LONG_SEQ_BLOCK_THRESHOLD,
        _LONG_SEQ_CONFIGS,
    )

    if (
        extent >= _LONG_SEQ_BLOCK_THRESHOLD
        and density >= SPARSE_DENSITY_THRESHOLD
    ):
        rest = tuple(
            c for c in _AUTO_BLOCK_CONFIGS if c not in _LONG_SEQ_CONFIGS
        )
        return "long_seq", _LONG_SEQ_CONFIGS + rest
    return "measured", _AUTO_BLOCK_CONFIGS


def _lead_pairs_by_price(tied: list) -> list:
    """The tie pool with the priced pair applied (:data:`PAIR_PRICE_MARGIN`):
    a head-batched row-major rung moves just ahead of the earliest rung
    before it that shares its ``block_k`` and snapped ``head_block`` and has
    a smaller ``block_q``, where its own ``cost_seconds`` is lower by the
    margin; it then carries ``tie_order`` ``priced_pair``. Every other rung
    keeps its place."""

    def leads(big, small) -> bool:
        return (
            small.grid == big.grid == "row_major"
            and big.head_block > 1
            and small.block_k == big.block_k
            and small.head_block == big.head_block
            and small.block_q < big.block_q
            and big.cost_seconds
            < small.cost_seconds * (1.0 - PAIR_PRICE_MARGIN)
        )

    out = list(tied)
    for big in tied:
        at = out.index(big)
        ahead = next((i for i in range(at) if leads(big, out[i])), None)
        if ahead is not None:
            out.insert(
                ahead, dataclasses.replace(out.pop(at), tie_order="priced_pair")
            )
    return out


def rank_candidates(
    q_ranges,
    k_ranges,
    attn_type_map,
    hq: int,
    hk: int,
    *,
    head_dim: int = 128,
    dtype: str = "bfloat16",
    generation: str | None = None,
    max_block_q: int | None = None,
    max_block_k: int | None = None,
    cp_size: int = 1,
    include_sparse: bool = True,
    rungs=None,
    v_head_dim: int | None = None,
) -> list[CandidateScore]:
    """Score every candidate rung for the workload, best first.

    Each blocking is priced under BOTH grid layouts: the row-major grid
    pays calibrated live + dead step fees (dead = clamped slots past a
    row's entry count — the static ``steps`` extent is the max over q
    blocks, so skewed varlen rows burn dead slots), the sparse grid pays
    zero dead slots but a dynamic-index-map fee per live step
    (:data:`SPARSE_STEP_OVERHEAD_S`), plus the sparse-only small-tile
    blockings (:data:`SPARSE_ONLY_CONFIGS`) that only make sense without
    a steps extent. ``include_sparse=False`` restores the pre-sparse
    row-major-only ranking — the distributed plan builder's contract
    (its kernels run the row-major grid).

    On top of tiles and steps a candidate pays for the bytes its live steps
    stream from HBM (:func:`step_bytes`, ``dtype``'s width), as a roofline
    does: each of forward, dq and dkv takes the larger of its MXU and its
    HBM time, so the term is 0.0 wherever the bytes are slack (every rung
    at GQA group >= 4 or ``block_q`` >= 512 a head) and the price there is
    what it was.

    The returned order is cost-ascending EXCEPT that candidates within
    :data:`TIE_TOLERANCE` of the best are resolved by the measured
    preference order for the workload's extent and density
    (:func:`_preference_order`; which one, on :attr:`CandidateScore.tie_order`)
    — so dense workloads keep the on-chip-measured winners while
    shape-sensitive workloads (narrow varlen blocks, SWA bands, a few long
    packed documents) escape to occupancy-correct rungs. One tie in the
    pool is the price's own: two head-batched row-major rungs that share
    ``block_k`` and the snapped ``head_block`` and differ in ``block_q``
    alone ((128, 512, hb) and (256, 512, hb)) stand in the order of their
    prices where the larger ``block_q`` is cheaper by
    :data:`PAIR_PRICE_MARGIN`, and that rung's ``tie_order`` reads
    ``priced_pair`` (a mask of many short documents keeps 128, one of a
    few long documents or a band takes 256). A rung
    whose own price says its steps run at the HBM's pace
    (:attr:`CandidateScore.bound`) is no tie with one that does not: the
    preference order was measured where no rung streams, and is not asked.

    ``rungs``: the (block_q, block_k, head_block) table to rank in place of
    the mask's preference order (a probe's rungs beside the chip's
    readings of them), ties in the order given.

    ``max_block_q``/``max_block_k`` drop rungs larger than the caller's
    shard geometry (distributed plans: a tile wider than the per-rank
    buffer is pure padding). ``cp_size`` says which tables the SMEM test
    counts (:func:`smem_entries`): 1 the global ones, exactly; more the
    per-rank ones, by the bounding-box estimate times ``2 / cp``.

    Infeasible-everywhere masks return the legacy escalation order
    (wide-tile rungs first) with ``feasible=False`` throughout — callers
    keep the old behavior of launching the least-bad rung and letting the
    kernel's SMEM check raise a descriptive error.

    ``v_head_dim``: the value heads' width where it is not ``head_dim``:
    a tile's ``Q K^T`` is ``head_dim`` deep and its ``P V``
    ``v_head_dim`` wide, and V's bytes are its own (:func:`step_bytes`).
    """
    from .. import env
    from ..ops.flex_attn import _auto_head_block

    dv = head_dim if v_head_dim is None else int(v_head_dim)
    q, k, t = _normalize_slices(q_ranges, k_ranges, attn_type_map)
    sq = int(q[:, 1].max()) if q.size else 0
    sk = int(k[:, 1].max()) if k.size else 0
    density = exact_mask_area(q, k, t) / max(sq * sk, 1)
    if rungs:
        tie_order = "given"
    else:
        tie_order, rungs = _preference_order(max(sq, sk), density)
    gen = generation if generation is not None else env.tpu_generation()
    spec = TPU_PEAK_SPECS.get(gen) or TPU_PEAK_SPECS["v5e"]
    eff_flops = spec.bf16_tflops * 1e12 * spec.mfu
    hbm_rate = spec.hbm_gbps * 1e9 * HBM_PRICE_SHARE
    # ``dtype`` is the string of whatever the caller had (a dtype, its
    # name, a scalar type's repr): its width is the number in it
    bits = re.search(r"\d+", str(dtype))
    itemsize = int(bits.group()) // 8 if bits else 2
    group = max(hq // max(hk, 1), 1)

    def score_one(bq: int, bk: int, hb_pref: int, grid: str):
        hb = _auto_head_block(hb_pref, hq, group)
        entries, steps, nq, bwd_entries = _counted_entries(q, k, t, bq, bk)
        smem = smem_entries(q, k, t, bq, bk, cp_size)
        grid_rows = max(hq // max(hb, 1), 1)
        live = grid_rows * entries
        if grid == "sparse":
            dead = 0
            step_s = live * (STEP_OVERHEAD_S + SPARSE_STEP_OVERHEAD_S)
        else:
            dead = max(grid_rows * nq * steps - live, 0)
            step_s = live * STEP_OVERHEAD_S + dead * DEAD_STEP_OVERHEAD_S
        mxu_s = 2.0 * (head_dim + dv) * hq * entries * bq * bk / eff_flops
        streamed = {
            kern: grid_rows
            * (bwd_entries if kern == "dkv" else entries)
            * step_bytes(kern, bq, bk, hb, group, head_dim, itemsize, dv)
            / hbm_rate
            for kern in RANKED_KERNELS
        }
        excess = sum(
            max(streamed[kern] - KERNEL_FLOP_WEIGHTS[kern] * mxu_s, 0.0)
            for kern in RANKED_KERNELS
        ) / sum(KERNEL_FLOP_WEIGHTS[kern] for kern in RANKED_KERNELS)
        return CandidateScore(
            block_q=bq,
            block_k=bk,
            head_block=hb,
            entries=entries,
            steps=steps,
            smem_entries=smem.entries,
            feasible=smem.feasible,
            mxu_seconds=mxu_s,
            step_seconds=step_s,
            grid=grid,
            live_slots=live,
            dead_slots=dead,
            smem_count=smem.count,
            hbm_seconds=streamed["fwd"],
            hbm_excess_seconds=excess,
            tie_order=tie_order,
        )

    scores: list[CandidateScore] = []
    seen: set[tuple[int, int, int, str]] = set()

    def emit(bq: int, bk: int, hb_pref: int, grid: str) -> None:
        if max_block_q is not None and bq > max_block_q:
            return
        if max_block_k is not None and bk > max_block_k:
            return
        cand = score_one(bq, bk, hb_pref, grid)
        # _auto_head_block can collapse different hb preferences onto
        # one head_block (small hq / GQA snapping) — a value-equal
        # duplicate would waste a MEASURE_TOP_K microbenchmark slot
        key = (cand.block_q, cand.block_k, cand.head_block, cand.grid)
        if key in seen:
            return
        seen.add(key)
        scores.append(cand)

    for bq, bk, hb_pref in rungs:
        # row-major FIRST: tied candidates resolve by generation order,
        # and inside the model's error bar the on-chip-measured
        # row-major rungs outrank the unmeasured sparse pricing
        emit(bq, bk, hb_pref, "row_major")
        if include_sparse:
            emit(bq, bk, hb_pref, "sparse")
    if include_sparse:
        for bq, bk, hb_pref in SPARSE_ONLY_CONFIGS:
            emit(bq, bk, hb_pref, "sparse")

    feasible = [s for s in scores if s.feasible]
    if not feasible:
        # legacy escalation: biggest tiles first, k-widest on ties — the
        # static table's entry-budget escalation rung ((512, 2048) for
        # oversized dense masks), so the launch-time SMEM check is the
        # one to fail, with its descriptive error
        return sorted(
            scores,
            key=lambda s: (-s.block_q * s.block_k, -s.block_k, s.smem_entries),
        )
    best = min(s.cost_seconds for s in feasible)
    hetero = (
        include_sparse
        and density < SPARSE_DENSITY_THRESHOLD
        and any(s.grid == "sparse" for s in feasible)
    )
    tol = SPARSE_TIE_TOLERANCE if hetero else TIE_TOLERANCE
    tied = [s for s in feasible if s.cost_seconds <= best * (1.0 + tol)]
    if any(s.bound == "mxu" for s in tied):
        # a rung whose own price says it streams is no tie with one that
        # does not: the preference order starts at (128, 512, 8) and was
        # measured at GQA group 8, where nothing streams
        tied = [s for s in tied if s.bound == "mxu"]
    rest = sorted(
        (s for s in scores if s not in tied), key=lambda s: s.cost_seconds
    )
    if hetero and any(s.grid == "sparse" for s in tied):
        # heterogeneous regime: inside the model's error bar, minimize
        # grid steps on the sparse grid — the measured 8.44 TF/s
        # collapse is step-overhead-shaped, and dead-step-free compact
        # grids with the fewest slots are the fix ROADMAP item 1 names
        tied = sorted(
            tied,
            key=lambda s: (s.grid != "sparse", s.grid_slots, s.cost_seconds),
        )
    elif tie_order != "given":
        # the one tie the price breaks itself: the pair that differs in
        # block_q alone (ISSUE 56)
        tied = _lead_pairs_by_price(tied)
    # tied candidates keep the measured preference order they were
    # generated in, but for the priced pair (dense regime), or the sparse
    # slot-minimizing order (heterogeneous regime); clear winners sort
    # ahead of the tie-pool's losers
    return tied + rest
