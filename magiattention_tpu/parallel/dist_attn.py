"""Distributed context-parallel flex attention: plan builder + runtime.

Role of the reference's ``meta/solver/dist_attn_solver.py`` +
``functional/dist_attn.py`` (DistAttnRuntime/DistAttnFunc), re-designed
TPU-first. Per rank, on host (once per unique mask, cached under the runtime
key):

1. host q/k ranges from the dispatch partition (chunked permutable shard),
2. ``remote_k = needed_k \\ host_k`` (zero-redundancy exact remote set,
   the reference's find_hole_ranges step),
3. GroupCollectiveMeta(s) routing K/V rows owner->consumer (the reference's
   TransferTable -> GroupCastArg pipeline), one per overlap stage,
4. per-rank Pallas entry tables over the rank-local KV buffers, built
   directly in global mask coordinates via run translation
   (ops/block_meta.py) — replacing slice_maker's sub-mask case analysis.

Execution modes (reference OverlapConfig semantics, overlap_solver.py:71):
- degree 0 (no-overlap): ONE group_cast of all remote KV, concat with the
  own shard, ONE kernel call over the merged buffer — no LSE-merge
  precision loss (reference _no_overlap_forward, dist_attn.py:3197).
- degree D >= 1 (multi-stage overlap): the host stage attends the own
  shard while D group_casts are in flight; each remote stage's partial
  (out, lse) is LSE-merged in. XLA's latency-hiding scheduler overlaps the
  casts with the Pallas kernels — the role of the reference's sm_margin /
  KernelBarrier stream machinery.

Everything is differentiable: autodiff transposes the casts into the dKV
group-reduces of the reference backward automatically.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import env, telemetry
from ..common.ranges import AttnRanges
from ..comm.group_collective import (
    GroupCollectiveMeta,
    group_cast_m,
    predicted_volume_ratio,
)
from ..comm.hier import HierGroupCollectiveMeta, group_cast_hier
from ..meta.containers import AttnBucket
from ..meta.dispatch_meta import DispatchMeta
from ..meta.solver.overlap_solver import (
    OverlapConfig,
    OverlapSolver,
    OverlapStageCost,
    simulate_overlap_timeline,
)
from ..ops.block_meta import (
    NEEDS_MASK,
    RUN_FIELDS,
    SLICE_FIELDS,
    FlexAttnBlockMeta,
    Run,
    build_block_meta_general,
    pad_block_meta,
    q_visit_counts,
    runs_from_position_ids,
)
from ..ops.correction import correct_attn_out_lse
from ..ops.flex_attn import (
    BWD_FORM,
    FlexAttnParams,
    bounds_mask_step,
    flex_attn_headmajor,
    stats_form,
)
from ..utils.instrument import named_scope


def _round_up(a: int, b: int) -> int:
    return -(-a // b) * b


@dataclasses.dataclass(frozen=True, eq=False)
class StageTables:
    """Stacked per-rank kernel tables for one attention call (numpy int32,
    leading cp axis; sharded on the cp mesh axis at runtime)."""

    kv_pad: int  # padded local KV length this stage's kernel sees
    fwd_qblk: np.ndarray
    fwd_kblk: np.ndarray
    fwd_sid: np.ndarray
    fwd_runs: np.ndarray
    bwd_kblk: np.ndarray
    bwd_qblk: np.ndarray
    bwd_sid: np.ndarray
    bwd_runs: np.ndarray
    bounds: np.ndarray
    # major-block counts of the per-rank tables (identical across ranks:
    # every meta is built against the same shard_q_pad / kv_pad). 0 =
    # unknown (legacy construction); kernel_steps then falls back to 1,
    # which is harmless for the max — see kernel_steps.
    num_q_blocks: int = 0
    num_k_blocks: int = 0

    def arrays(self):
        return (
            self.fwd_qblk,
            self.fwd_kblk,
            self.fwd_sid,
            self.fwd_runs,
            self.bwd_kblk,
            self.bwd_qblk,
            self.bwd_sid,
            self.bwd_runs,
            self.bounds,
        )

    def kernel_steps(self) -> tuple[int, int]:
        """(fwd, bwd) static inner-grid extents across ranks: the max
        entries sharing one q block (fwd/dq) resp. k block (dkv). The
        kernels run row-major grids (see FlexAttnParams.fwd_steps) and the
        tables are traced per-rank slices at runtime, so these must be
        computed host-side and carried in the params.

        The real major-block counts are passed through to max_row_count
        for honest bincount sizing; note the MAX is provably insensitive
        to minlength here (every major block owns >= 1 entry — dummies
        guarantee it — so bincount's tail padding can only append zeros),
        which is why the legacy num_major=1 never miscounted."""
        from ..ops.block_meta import max_row_count

        nq = max(self.num_q_blocks, 1)
        nk = max(self.num_k_blocks, 1)
        fs = max(max_row_count(row, nq) for row in self.fwd_qblk)
        bs = max(max_row_count(row, nk) for row in self.bwd_kblk)
        return fs, bs

    def grid_steps(self, fwd_steps: int, bwd_steps: int) -> tuple[int, int, float]:
        """Steps one head group's kernels launch on the row-major grid
        (blocks x the params' static extents) and on the compact one (the
        padded entries), the forward table walked twice (before PR 43 the
        second walk was dq's; until PR 48 a training step under remat ran
        the forward twice, as the looped trunk's still does) and the
        backward table once: the weights the grid's two
        prices were measured with (``tuning/cost_model.py``), kept so that
        no plan's grid moves with the backward's form. And how many of
        either do work:
        entries of a non-empty slice, the mean over ranks. Padded and
        dummy entries name an all-masked sentinel slice: both grids
        launch them, and they count as dead."""
        nq = max(self.num_q_blocks, int(self.fwd_qblk.max()) + 1)
        nk = max(self.num_k_blocks, int(self.bwd_kblk.max()) + 1)
        row_major = 2 * nq * fwd_steps + nk * bwd_steps
        compact = 2 * self.fwd_qblk.shape[1] + self.bwd_kblk.shape[1]
        b = self.bounds.reshape(self.bounds.shape[0], -1, SLICE_FIELDS)
        works = (b[..., 1] > b[..., 0]) & (b[..., 3] > b[..., 2])
        live = sum(
            weight * np.take_along_axis(works, sid, axis=1).sum(axis=1).mean()
            for weight, sid in ((2, self.fwd_sid), (1, self.bwd_sid))
        )
        return row_major, compact, float(live)

    def q_visits(self) -> tuple[int, int, int]:
        """What the backward's dq protocol meets on these k-major tables
        (``ops/flex_attn._dq_accumulate``), summed over ranks: (entries,
        q blocks some entry names, q blocks none names). Every entry is a
        visit of the q block it names, and a block no entry of a rank's
        table names (a stage whose keys reach only some of the rank's
        rows) is never written by that rank's walk: one such block in
        any rank makes the stage's kernel take its dq output aliased to a
        zero fill (``FlexAttnParams.bwd_unnamed_q``)."""
        # (block counts unknown, a legacy construction: every rank fills)
        nq = self.num_q_blocks or int(self.bwd_qblk.max()) + 2
        counts = [q_visit_counts(row, nq) for row in self.bwd_qblk]
        return (
            int(self.bwd_qblk.size),
            sum(c[0] for c in counts),
            sum(c[1] for c in counts),
        )

    def stepped_tile_steps(self) -> float:
        """Of :meth:`grid_steps`' live steps (same weights, the mean over
        ranks), those whose tile a stepped bound crosses: entries of a
        slice with a step above 1 that the planner did not find whole
        (the runs table's needs-mask flag)."""
        b = self.bounds.reshape(self.bounds.shape[0], -1, SLICE_FIELDS)
        stepped = (b[..., 4] >> 2) > 0
        total = 0.0
        for weight, sid, runs in (
            (2, self.fwd_sid, self.fwd_runs), (1, self.bwd_sid, self.bwd_runs)
        ):
            words = runs.reshape(runs.shape[0], -1, RUN_FIELDS)[..., 6]
            binds = (words & NEEDS_MASK) != 0  # the k-major word has more bits
            crossed = np.take_along_axis(stepped, sid, axis=1) & binds
            total += weight * crossed.sum(axis=1).mean()
        return float(total)

    @staticmethod
    def from_rank_metas(metas: list[FlexAttnBlockMeta], kv_pad: int):
        e = max(m.num_fwd_entries for m in metas)
        e2 = max(m.num_bwd_entries for m in metas)
        s = max(m.num_slices for m in metas)
        metas = [pad_block_meta(m, e, e2, s) for m in metas]
        return StageTables(
            kv_pad=kv_pad,
            num_q_blocks=max(m.num_q_blocks for m in metas),
            num_k_blocks=max(m.num_k_blocks for m in metas),
            fwd_qblk=np.stack([m.fwd_q_block for m in metas]),
            fwd_kblk=np.stack([m.fwd_k_block for m in metas]),
            fwd_sid=np.stack([m.fwd_slice_id for m in metas]),
            fwd_runs=np.stack([m.fwd_runs for m in metas]),
            bwd_kblk=np.stack([m.bwd_k_block for m in metas]),
            bwd_qblk=np.stack([m.bwd_q_block for m in metas]),
            bwd_sid=np.stack([m.bwd_slice_id for m in metas]),
            bwd_runs=np.stack([m.bwd_runs for m in metas]),
            bounds=np.stack([m.slice_bounds for m in metas]),
        )


@dataclasses.dataclass(frozen=True, eq=False)
class StagePlan:
    comm: GroupCollectiveMeta
    tables: StageTables
    # mask area of the heaviest rank's kernel work in this stage (0 =
    # legacy construction). The plan sanitizer sums it over the stages
    # against the plan's total area (analysis/plan_sanity.py).
    max_rank_area: int = 0


@dataclasses.dataclass(frozen=True, eq=False)
class DistAttnPlan:
    """Host-side plan for one (mask, dispatch, blocking, overlap) combo."""

    cp_size: int
    shard_q_len: int
    shard_q_pad: int
    block_q: int
    block_k: int
    overlap_degree: int  # 0 = merged no-overlap path
    total_area: int
    max_rank_area: int

    # degree-0 (merged) path
    merged_comm: GroupCollectiveMeta | None
    merged_tables: StageTables | None

    # staged path (degree >= 1)
    host_tables: StageTables | None
    stages: tuple[StagePlan, ...]

    # hierarchical 2-level comm over a (inter, intra) cp mesh (reference
    # _group_collective_hier.py); None = flat single-axis group collectives
    hier: tuple[int, int] | None = None

    # heaviest rank's host-stage (own-shard) mask area; 0 on the merged
    # degree-0 path (where max_rank_area covers the single kernel call)
    # and on legacy constructions. The plan sanitizer's per-stage area
    # sum reads it (analysis/plan_sanity.py).
    host_max_rank_area: int = 0

    @property
    def comm(self) -> GroupCollectiveMeta:
        """Primary comm meta (diagnostics; degree-0 path or stage union)."""
        if self.merged_comm is not None:
            return self.merged_comm
        # staged: synthesize recv totals for diagnostics
        return self._union_comm()

    def _union_comm(self):
        rt = [0] * self.cp_size
        st = [0] * self.cp_size
        for sp in self.stages:
            for r in range(self.cp_size):
                rt[r] += sp.comm.recv_total[r]
                st[r] += sp.comm.send_total[r]
        if not self.stages:
            # degree>=1 plan whose stages were all filtered out (fully-local
            # mask, e.g. block-diagonal varlen): zero comm volume
            cp = self.cp_size
            return GroupCollectiveMeta(
                cp_size=cp,
                max_send=0,
                max_recv=0,
                send_total=tuple(st),
                recv_total=tuple(rt),
                send_idx=np.zeros((cp, cp, 0), np.int32),
                recv_sel=np.zeros((cp, 0), np.int32),
                recv_valid=np.zeros((cp, 0), bool),
                seg_ids=np.zeros((cp, cp, 0), np.int32),
            )
        return dataclasses.replace(
            self.stages[0].comm,
            recv_total=tuple(rt),
            send_total=tuple(st),
        )

    def memory_ledger(
        self,
        *,
        num_heads_q: int,
        num_heads_kv: int,
        head_dim: int,
        bytes_per_elt: int = 2,
        **kw,
    ):
        """Price this plan's per-rank HBM footprint (ISSUE 14): one
        :class:`~..telemetry.memory.MemoryLedger` with per-stage cast
        buffers taken from each stage's
        ``comm.scheduled_rows_per_rank`` — the same figure the overlap
        solver prices, so the byte accounting
        can never drift from the cost model's — plus kernel
        partial/LSE scratch and operand/table/output buffers.
        ``make memory-check`` gates it against XLA's compiled
        ``memory_analysis`` of the jitted program."""
        from ..telemetry.memory import plan_memory_ledger

        return plan_memory_ledger(
            self,
            num_heads_q=num_heads_q,
            num_heads_kv=num_heads_kv,
            head_dim=head_dim,
            bytes_per_elt=bytes_per_elt,
            **kw,
        )

    def describe(self) -> str:
        """Multi-line plan summary (role of the reference's detailed plan
        dump, dist_attn_runtime_mgr.py:655-1014)."""
        lines = [
            f"DistAttnPlan: cp={self.cp_size} shard_q={self.shard_q_len} "
            f"(pad {self.shard_q_pad}) blocks=({self.block_q},{self.block_k}) "
            f"overlap_degree={self.overlap_degree}",
            f"  mask area total={self.total_area} max_rank={self.max_rank_area} "
            f"imbalance={self.max_rank_area / max(self.total_area / self.cp_size, 1):.3f}",
        ]
        if self.overlap_degree == 0:
            c = self.merged_comm
            lines.append(
                f"  comm (merged, {c.impl}): recv_rows/rank={list(c.recv_total)} "
                f"send_rows/rank={list(c.send_total)} "
                f"scheduled_payload_rows={c.scheduled_rows_per_rank} "
                f"(legacy padded {c.padded_rows_per_rank})"
            )
            lines.append(
                f"  tables: E_fwd={self.merged_tables.fwd_qblk.shape[1]} "
                f"E_bwd={self.merged_tables.bwd_kblk.shape[1]} "
                f"kv_buf_pad={self.merged_tables.kv_pad}"
            )
        else:
            for i, sp in enumerate(self.stages):
                lines.append(
                    f"  stage {i} ({sp.comm.impl}): "
                    f"recv_rows/rank={list(sp.comm.recv_total)} "
                    f"scheduled_rows={sp.comm.scheduled_rows_per_rank} "
                    f"E_fwd={sp.tables.fwd_qblk.shape[1]} "
                    f"kv_pad={sp.tables.kv_pad}"
                )
        return "\n".join(lines)

    def _comm_arrays(self, comm):
        """Device arrays one cast needs — impl-dependent (the selected
        group-collective impl decides the layout; flat a2a ships 3
        arrays, hop scheduling 2 per active hop, hierarchical plans the
        inter level + the intra level's impl layout)."""
        return comm.cast_device_arrays()

    def device_tables(self):
        """Flattened sharded operands, deterministic order (see
        ``dist_attn_local`` for the consuming cursor)."""
        arrs: list[np.ndarray] = []
        if self.overlap_degree == 0:
            assert self.merged_tables is not None and self.merged_comm
            arrs.extend(self.merged_tables.arrays())
            arrs.extend(self._comm_arrays(self.merged_comm))
        else:
            assert self.host_tables is not None
            arrs.extend(self.host_tables.arrays())
            for sp in self.stages:
                arrs.extend(sp.tables.arrays())
                arrs.extend(self._comm_arrays(sp.comm))
        return tuple(jnp.asarray(a) for a in arrs)


# ---------------------------------------------------------------------------
# plan building
# ---------------------------------------------------------------------------


def _split_send_map_by_stage(
    send_map: list[list[np.ndarray]],
    stage_row_of: list[np.ndarray],  # per dst rank: stage id of each recv row
    num_stages: int,
    cp: int,
) -> list[list[list[np.ndarray]]]:
    """stage -> owner -> dst -> owner-local rows (subset of send_map)."""
    out = [
        [[np.empty(0, np.int64) for _ in range(cp)] for _ in range(cp)]
        for _ in range(num_stages)
    ]
    for d in range(cp):
        pos = 0
        for s in range(cp):
            rows = send_map[s][d]
            n = len(rows)
            if n:
                stages = stage_row_of[d][pos : pos + n]
                for st in range(num_stages):
                    sel = rows[stages == st]
                    out[st][s][d] = sel
            pos += n
    return out


def _stage_granularity(
    n_rows: int, config: OverlapConfig, block_k: int
) -> int:
    """Row-block granularity for stage assignment — shared by the staged
    builder and the auto-degree timeline model so the model prices exactly
    the split that will execute."""
    return max(
        config.min_stage_rows,
        block_k,
        -(-n_rows // config.max_num_chunks) if n_rows else 0,
    )


def _slice_area_within_k(
    qs: int, qe: int, ks: int, ke: int, mt: int, intervals
) -> int:
    """Exact unmasked area of one slice restricted to k in the interval
    union (mask-type-aware, via rectangle k-cuts)."""
    from ..common.enum import AttnMaskType
    from ..common.range import AttnRange
    from ..common.rectangle import AttnRectangle

    rect = AttnRectangle(
        AttnRange(qs, qe), AttnRange(ks, ke), AttnMaskType(mt)
    )
    total = 0
    for a, b in intervals:
        _, right = rect.cut_k_multi(a)
        for piece in right:
            left, _ = piece.cut_k_multi(b)
            total += sum(p.area for p in left)
    return total


def _choose_overlap_degree(
    cp: int,
    slices_per_rank,
    host_ranges,
    recv_rows,
    config: OverlapConfig,
    block_k: int,
    inter_frac: float | None = None,
    comm_volume_ratio: float = 1.0,
) -> int:
    """Auto overlap degree: simulate the staged pipeline per candidate
    degree with the config's cost factors and return the argmin over the
    slowest rank (ties -> fewer stages). Mirrors the UNIFORM contiguous
    row split the staged builder will actually apply.

    ``inter_frac``: for hierarchical plans, the fraction of recv rows that
    also cross the slow inter hop after dedup — comm is then priced as
    one intra hop per row plus inter_frac of an inter hop.

    ``comm_volume_ratio``: scheduled / true rows of the selected
    group-collective impl on the full send map
    (:func:`~..comm.group_collective.predicted_volume_ratio`) — stage
    comm is priced at the volume the wire will actually carry, not the
    true-row lower bound (the per-stage skew is approximated by the
    plan-level ratio; the built stages' metas record the exact figure)."""
    from ..common.mask import slice_area

    cf = config.calc_cost_factor
    cmf = config.comm_cost_factor * max(comm_volume_ratio, 1e-9)
    if inter_frac is not None and config.comm_cost_factor_inter is not None:
        cmf = cmf + inter_frac * config.comm_cost_factor_inter
    per_rank: list[tuple[float, float, int]] = []  # (host_s, remote_s, rows)
    for r in range(cp):
        own = [
            (rng.start, rng.end) for rng in host_ranges[r]
        ]
        area_total = 0
        area_host = 0
        for qs, qe, ks, ke, mt in slices_per_rank[r].tolist():
            area_total += slice_area(qs, qe, ks, ke, mt)
            area_host += _slice_area_within_k(qs, qe, ks, ke, mt, own)
        per_rank.append(
            (
                area_host * cf,
                max(area_total - area_host, 0) * cf,
                int(recv_rows[r]),
            )
        )

    max_d = max(1, config.dynamic_max_degree)
    best_d, best_t = 1, float("inf")
    for d in range(1, max_d + 1):
        t = 0.0
        for host_s, remote_s, rows in per_rank:
            if rows == 0:
                t = max(t, host_s)
                continue
            gran = _stage_granularity(rows, config, block_k)
            n_blocks = -(-rows // gran)
            per = -(-n_blocks // min(d, n_blocks))
            stage_rows = []
            done = 0
            for s in range(min(d, n_blocks)):
                blocks = min(per, n_blocks - s * per)
                if blocks <= 0:
                    break
                r_rows = min(blocks * gran, rows - done)
                stage_rows.append(r_rows)
                done += r_rows
            comm_s = [x * cmf for x in stage_rows]
            calc_s = [remote_s * (x / rows) for x in stage_rows]
            t = max(
                t,
                simulate_overlap_timeline(
                    host_s, comm_s, calc_s, config.stage_overhead_s
                ),
            )
        if t < best_t * (1.0 - 1e-9):
            best_d, best_t = d, t
    telemetry.record_overlap_choice(best_d, best_t)
    return best_d


def build_dist_attn_plan(
    dispatch_meta: DispatchMeta,
    bucket: AttnBucket,
    *,
    kv_dispatch_meta: DispatchMeta | None = None,
    block_q: int = 128,
    block_k: int = 128,
    overlap_config: OverlapConfig | None = None,
    cp_mesh_shape: tuple[int, int] | None = None,
) -> DistAttnPlan:
    """Plan the distributed attention for one dispatched mask.

    Self-attention by default (K/V follow the Q partition); pass a separate
    ``kv_dispatch_meta`` for cross-attention (reference dispatch_qo/kv:
    queries are balanced by mask area, keys dispatched by their own meta).

    ``cp_mesh_shape``: (n_inter, n_intra) for hierarchical 2-level comm over
    a 2-D cp mesh (rank = inter * n_intra + intra; reference
    _group_collective_hier.py): casts dedup rows across the inter hop.

    With telemetry enabled the build is timed (span + latency histogram)
    and the finished plan's comm/overlap/kernel-grid facts are recorded
    (``telemetry.record_plan``) — all host-side, nothing traced.
    """
    t0 = time.perf_counter()
    with telemetry.span(
        "build_dist_attn_plan", cp=dispatch_meta.cp_size
    ):
        try:
            plan = _build_dist_attn_plan(
                dispatch_meta,
                bucket,
                kv_dispatch_meta=kv_dispatch_meta,
                block_q=block_q,
                block_k=block_k,
                overlap_config=overlap_config,
                cp_mesh_shape=cp_mesh_shape,
            )
        except Exception as exc:  # noqa: BLE001 — degradation, recorded
            # graceful degradation (ISSUE 8): a solver/staged-build
            # failure falls back to the dense single-bucket degree-0
            # plan — one merged cast + one kernel call, no overlap
            # solver, no stage assignment. Never silent: the reason is
            # recorded as magi_degraded_path and logged.
            cfg = overlap_config or OverlapConfig()
            if cfg.degree == 0:
                raise  # the fallback IS the path that failed
            telemetry.record_degraded_path("plan_build_error")
            from ..telemetry.logger import get_logger

            get_logger("resilience").warning(
                "plan build failed (%s: %s) — degrading to the dense "
                "single-bucket degree-0 plan",
                type(exc).__name__,
                exc,
            )
            plan = _build_dist_attn_plan(
                dispatch_meta,
                bucket,
                kv_dispatch_meta=kv_dispatch_meta,
                block_q=block_q,
                block_k=block_k,
                overlap_config=dataclasses.replace(cfg, degree=0),
                cp_mesh_shape=cp_mesh_shape,
            )
    build_s = time.perf_counter() - t0
    telemetry.record_plan(plan, build_seconds=build_s)
    # host-solver cost attribution (ISSUE 16): a cold build IS the miss
    # path's solver time, and its measured mean prices each later
    # cache hit's ms-saved credit
    telemetry.record_plan_solver(build_s, cache_hit=False)
    mode = env.validate_mode()
    if mode != "off":
        from ..analysis.plan_sanity import validate_plan

        validate_plan(plan, total_area=bucket.area)
        if mode == "trace":
            from ..analysis.plan_sanity import PlanValidationError
            from ..analysis.trace_audit import audit_plan_collectives

            problems = audit_plan_collectives(plan)
            if problems:
                telemetry.record_validate(failed=True)
                raise PlanValidationError("; ".join(problems))
    return plan


def _build_dist_attn_plan(
    dispatch_meta: DispatchMeta,
    bucket: AttnBucket,
    *,
    kv_dispatch_meta: DispatchMeta | None = None,
    block_q: int = 128,
    block_k: int = 128,
    overlap_config: OverlapConfig | None = None,
    cp_mesh_shape: tuple[int, int] | None = None,
) -> DistAttnPlan:
    from ..resilience import chaos

    chaos.maybe_fail("plan_error")  # injectable solver/build failure
    cp = dispatch_meta.cp_size
    shard_len = dispatch_meta.shard_seqlen
    kv_meta = kv_dispatch_meta or dispatch_meta
    assert kv_meta.cp_size == cp
    shard_k_len = kv_meta.shard_seqlen
    overlap_config = overlap_config or OverlapConfig()
    degree = overlap_config.degree
    if cp_mesh_shape is not None:
        assert cp_mesh_shape[0] * cp_mesh_shape[1] == cp, (
            f"cp_mesh_shape {cp_mesh_shape} != cp {cp}"
        )

    pos_ids = [dispatch_meta.position_ids(r) for r in range(cp)]
    pos_ids_k = [kv_meta.position_ids(r) for r in range(cp)]
    host_ranges = kv_meta.host_ranges_per_rank()  # K-side ownership

    # per-rank slices (global coords) + needed K sets
    slices_per_rank: list[np.ndarray] = []
    needed_k: list[AttnRanges] = []
    for r in range(cp):
        rows = []
        ks = AttnRanges()
        for c in dispatch_meta.partitions[r]:
            for s in bucket.q_chunks[c].attn_slices:
                rows.append(
                    (
                        s.q_range.start,
                        s.q_range.end,
                        s.k_range.start,
                        s.k_range.end,
                        int(s.mask_type),
                    )
                )
                ks.append(s.k_range.clone())
        slices_per_rank.append(np.asarray(rows, dtype=np.int64).reshape(-1, 5))
        needed_k.append(ks.merge())

    remote_k = [needed_k[r].find_hole_ranges(host_ranges[r]) for r in range(cp)]
    send_map: list[list[np.ndarray]] = [
        [np.empty(0, np.int64) for _ in range(cp)] for _ in range(cp)
    ]
    recv_segments: list[list[tuple[int, np.ndarray]]] = [[] for _ in range(cp)]
    for d in range(cp):
        for s in range(cp):
            if s == d:
                continue
            inter = remote_k[d].find_overlap_ranges(host_ranges[s])
            if inter.is_empty():
                continue
            local = host_ranges[s].make_ranges_local(inter, is_self_merged=True)
            order = sorted(range(len(local)), key=lambda i: local[i].start)
            idx_parts = [
                np.arange(local[i].start, local[i].end, dtype=np.int64)
                for i in order
            ]
            send_map[s][d] = (
                np.concatenate(idx_parts) if idx_parts else np.empty(0, np.int64)
            )
            recv_segments[d].append((s, pos_ids_k[s][send_map[s][d]]))

    shard_q_pad = _round_up(shard_len, block_q)
    q_runs_per_rank = [runs_from_position_ids(pos_ids[r]) for r in range(cp)]
    k_own_runs_per_rank = [
        runs_from_position_ids(pos_ids_k[r]) for r in range(cp)
    ]
    total_area = bucket.area

    if degree is None:
        # auto-tune (reference OverlapConfig degree=None + dynamic_max_degree,
        # overlap_solver.py:71-157): pick the stage count minimizing the
        # pipelined timeline cost model over the critical rank
        recv_rows = [
            sum(len(g) for _, g in recv_segments[r]) for r in range(cp)
        ]
        inter_frac = None
        if cp_mesh_shape is not None:
            tot = sum(recv_rows)
            inter_frac = (
                HierGroupCollectiveMeta.inter_crossing_rows(
                    send_map, *cp_mesh_shape
                )
                / tot
                if tot
                else 0.0
            )
        # price comm at the volume the selected impl will schedule (the
        # a2a's global pad, or the hop sums — for hier plans the flat
        # ratio approximates the intra level's skew)
        vol_ratio, _ = predicted_volume_ratio(send_map)
        degree = _choose_overlap_degree(
            cp,
            slices_per_rank,
            host_ranges,
            recv_rows,
            overlap_config,
            block_k,
            inter_frac=inter_frac,
            comm_volume_ratio=vol_ratio,
        )

    def _build_comm(smap):
        """(comm meta, per-rank recv-order global k ids) for one send map —
        flat single-axis or hierarchical two-hop routing."""
        if cp_mesh_shape is None:
            comm = GroupCollectiveMeta.build(smap, [shard_k_len] * cp)
            sources = [
                [(s, smap[s][d]) for s in range(cp) if len(smap[s][d])]
                for d in range(cp)
            ]
        else:
            comm, sources = HierGroupCollectiveMeta.build(
                smap, [shard_k_len] * cp, cp_mesh_shape[0], cp_mesh_shape[1]
            )
        gids = []
        for d in range(cp):
            parts = [pos_ids_k[s][rows] for s, rows in sources[d]]
            gids.append(
                np.concatenate(parts) if parts else np.empty(0, np.int64)
            )
        return comm, gids

    def _runs_from_recv_rows(global_ids: np.ndarray, base: int) -> list[Run]:
        runs = []
        for run in runs_from_position_ids(global_ids):
            runs.append(
                Run(
                    local_start=base + run.local_start,
                    global_start=run.global_start,
                    length=run.length,
                )
            )
        return runs

    if degree == 0:
        comm, comm_gids = _build_comm(send_map)
        kv_buf_pad = _round_up(shard_k_len + comm.max_recv, block_k)
        metas = []
        for r in range(cp):
            k_runs = list(k_own_runs_per_rank[r])
            # received rows sit right after the own shard, in recv order
            k_runs += _runs_from_recv_rows(comm_gids[r], shard_k_len)
            metas.append(
                build_block_meta_general(
                    slices_per_rank[r],
                    q_runs_per_rank[r],
                    k_runs,
                    shard_q_pad,
                    kv_buf_pad,
                    block_q=block_q,
                    block_k=block_k,
                )
            )
        tables = StageTables.from_rank_metas(metas, kv_buf_pad)
        return DistAttnPlan(
            cp_size=cp,
            shard_q_len=shard_len,
            shard_q_pad=shard_q_pad,
            block_q=block_q,
            block_k=block_k,
            overlap_degree=0,
            total_area=total_area,
            max_rank_area=max(m.total_area for m in metas),
            merged_comm=comm,
            merged_tables=tables,
            host_tables=None,
            stages=(),
            hier=cp_mesh_shape,
        )

    # ---- staged path -----------------------------------------------------
    # host stage: own shard only
    host_kv_pad = _round_up(shard_k_len, block_k)
    host_metas = [
        build_block_meta_general(
            slices_per_rank[r],
            q_runs_per_rank[r],
            k_own_runs_per_rank[r],  # the rank's own K/V shard
            shard_q_pad,
            host_kv_pad,
            block_q=block_q,
            block_k=block_k,
        )
        for r in range(cp)
    ]
    host_tables = StageTables.from_rank_metas(host_metas, host_kv_pad)

    # assign each rank's remote recv rows to stages via the overlap solver,
    # at row-block granularity in recv order (granularity honors
    # min_stage_rows and the max_num_chunks cap, matching the auto-degree
    # timeline model)
    stage_row_of: list[np.ndarray] = []
    solver = OverlapSolver(overlap_config)
    for r in range(cp):
        n_rows = sum(len(g) for _, g in recv_segments[r])
        gran = _stage_granularity(n_rows, overlap_config, block_k)
        n_blocks = -(-n_rows // gran) if n_rows else 0
        costs = [
            OverlapStageCost(comm_cost=float(min(gran, n_rows - b * gran)), calc_cost=1.0)
            for b in range(n_blocks)
        ]
        sol = solver.solve(costs, degree=degree)
        row_stage = np.zeros(n_rows, dtype=np.int64)
        for b in range(n_blocks):
            row_stage[b * gran : (b + 1) * gran] = (
                sol.stage_of[b] if b < len(sol.stage_of) else 0
            )
        stage_row_of.append(row_stage)

    num_stages = degree
    staged_maps = _split_send_map_by_stage(
        send_map, stage_row_of, num_stages, cp
    )
    rank_area = [host_metas[r].total_area for r in range(cp)]
    stages: list[StagePlan] = []
    for st in range(num_stages):
        st_comm, st_gids = _build_comm(staged_maps[st])
        st_kv_pad = _round_up(max(st_comm.max_recv, block_k), block_k)
        st_metas = []
        for r in range(cp):
            k_runs = _runs_from_recv_rows(st_gids[r], 0)
            st_metas.append(
                build_block_meta_general(
                    slices_per_rank[r],
                    q_runs_per_rank[r],
                    k_runs,
                    shard_q_pad,
                    st_kv_pad,
                    block_q=block_q,
                    block_k=block_k,
                )
            )
        if all(t == 0 for t in st_comm.recv_total):
            continue  # globally empty stage: no collective, no kernel call
        for r in range(cp):
            rank_area[r] += st_metas[r].total_area
        stages.append(
            StagePlan(
                comm=st_comm,
                tables=StageTables.from_rank_metas(st_metas, st_kv_pad),
                max_rank_area=max(m.total_area for m in st_metas),
            )
        )

    return DistAttnPlan(
        cp_size=cp,
        shard_q_len=shard_len,
        shard_q_pad=shard_q_pad,
        block_q=block_q,
        block_k=block_k,
        overlap_degree=num_stages,
        hier=cp_mesh_shape,
        total_area=total_area,
        max_rank_area=max(rank_area),
        host_max_rank_area=max(m.total_area for m in host_metas),
        merged_comm=None,
        merged_tables=None,
        host_tables=host_tables,
        stages=tuple(stages),
    )


# ---------------------------------------------------------------------------
# runtime
# ---------------------------------------------------------------------------


def make_attn_params(
    plan: DistAttnPlan,
    head_dim: int,
    *,
    scale: float | None = None,
    softcap: float = 0.0,
    has_sink: bool = False,
    out_dtype="bfloat16",
    interpret: bool | None = None,
    head_block: int = 1,
    v_head_dim: int | None = None,
) -> FlexAttnParams:
    """A plan's kernel parameters. ``head_dim``: the width of q and k, which
    gives the default softmax scale; ``v_head_dim``: that of v and out where
    it is another (the kernels read both off their operands: it goes on the
    ``attn_fn_build`` span here and nowhere into the parameters, so a plan at
    one width has the parameters it had)."""
    if scale is None:
        scale = 1.0 / math.sqrt(head_dim)
    telemetry.annotate_span(v_head_dim=v_head_dim or head_dim)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # plan-wide static inner-grid extents: max over every table set the
    # plan can hand the kernels (merged / host / per-stage / qo-comm) —
    # the per-rank tables are traced at runtime, so the row-major grids
    # need these in the hashable params (FlexAttnParams.fwd_steps)
    tabs = [
        t
        for t in (
            getattr(plan, "merged_tables", None),
            getattr(plan, "host_tables", None),
            getattr(plan, "tables", None),
            *(sp.tables for sp in getattr(plan, "stages", ()) or ()),
        )
        if t is not None
    ]
    params = ensure_kernel_steps(
        FlexAttnParams(
            head_block=int(head_block),
            block_q=plan.block_q,
            block_k=plan.block_k,
            scale=float(scale),
            softcap=float(softcap),
            has_sink=has_sink,
            out_dtype=str(jnp.dtype(out_dtype)),
            interpret=bool(interpret),
        ),
        tabs,
    )
    return dataclasses.replace(params, grid=_choose_grid(params, tabs))


def _choose_grid(params: FlexAttnParams, tabs) -> str:
    """The grid both kernels of a plan walk, from the same table sets
    the static extents came from: the steps each grid would launch are
    counted (:meth:`StageTables.grid_steps`) and priced with the two
    per-step costs measured on the chip (``tuning/cost_model.py``).
    ``MAGI_ATTENTION_GRID`` pins it, as it pins ``auto_kernel_config``.
    The decision is recorded: the gauge ``magi_flex_dead_step_share`` and
    the two prices on the live span (``attn_fn_build``)."""
    from ..tuning.cost_model import choose_grid, price_grids

    counts = [t.grid_steps(params.fwd_steps, params.bwd_steps) for t in tabs]
    launched = {
        "row_major": sum(c[0] for c in counts),
        "sparse": sum(c[1] for c in counts),
    }
    live = sum(c[2] for c in counts)
    visits = [t.q_visits() for t in tabs]
    visited = sum(v[1] for v in visits)
    grid = env.grid_override() or choose_grid(*launched.values())
    row_major_s, compact_s = price_grids(*launched.values())
    telemetry.annotate_span(
        rung=(params.block_q, params.block_k, params.head_block),
        grid=grid,
        # the form lse and the row maximum leave the forward kernel in
        # (the backward reads its statistics compact at every block), and
        # the form of the backward: one k-major kernel, delta made before
        # it (the build counter's labels of the same names)
        stats=stats_form(params.block_q),
        delta="xla",
        bwd_form=BWD_FORM,
        # what the backward's dq protocol meets on the k-major tables
        # (ops/flex_attn._dq_accumulate): the visits a q tile gets in the
        # mean (each moves it once; the first reads nothing, the last
        # writes the result), and the q blocks that no entry of some
        # rank's table names, which make that call's output a zero fill
        dq_visits_per_tile=(
            sum(v[0] for v in visits) / visited if visited else 0.0
        ),
        dq_unnamed_q_blocks=sum(v[2] for v in visits),
        row_major_steps=launched["row_major"],
        compact_steps=launched["sparse"],
        live_steps=live,
        row_major_dead_us=1e6 * row_major_s,
        compact_fee_us=1e6 * compact_s,
    )
    telemetry.record_flex_bwd_form(BWD_FORM)
    if launched[grid]:
        telemetry.record_flex_dead_step_share(
            100.0 * (1.0 - live / launched[grid])
        )
    if live:
        telemetry.record_flex_stepped_tile_share(
            100.0 * sum(t.stepped_tile_steps() for t in tabs) / live
        )
    return grid


def _hm(x, target):
    """[t, h, d] -> head-major [h, t_pad, d]."""
    x = jnp.transpose(x, (1, 0, 2))
    pad = target - x.shape[1]
    if pad > 0:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x


def _headmajor_to_seq(out_h, lse_h, n):
    """Kernel head-major outputs ([h, t_pad, d] out, [h, t_pad] lse) ->
    ([n, h, d] out, [n, h] lse)."""
    out = jnp.transpose(out_h, (1, 0, 2))[:n]
    lse = jnp.transpose(lse_h, (1, 0))[:n]
    return out, lse


def ensure_kernel_steps(params: FlexAttnParams, tables) -> FlexAttnParams:
    """Raise ``FlexAttnParams.fwd_steps``/``bwd_steps`` to cover the given
    host-side :class:`StageTables`. At runtime the per-rank tables are
    traced shard_map operands, so the row-major kernel grids need these
    static extents in the params; callers that built params directly
    (tests, baselines) get them derived here from the plan they already
    hold. Always maxes against the tables — never trusts pre-set values
    alone — so params built for one plan cannot silently under-cover a
    different plan's tables (too-small steps would drop entries with no
    error under tracing)."""
    fs = bs = unnamed = 0
    step = 1
    for t in tables:
        if t is None:
            continue
        a, b = t.kernel_steps()
        fs = max(fs, a)
        bs = max(bs, b)
        step = max(step, bounds_mask_step(t.bounds))
        unnamed += t.q_visits()[2]
    if (
        params.fwd_steps >= fs
        and params.bwd_steps >= bs
        and params.mask_step >= step
        and params.bwd_unnamed_q is not None
        and params.bwd_unnamed_q >= unnamed
    ):
        return params
    # mask_step rides with the extents: it too is read off the tables the
    # kernels will walk, and too small a value would read a stepped slice
    # as a diagonal with no error. So does the count of q blocks that some
    # k-major table leaves out (ops/flex_attn.dq_form): at 0 the backward
    # fills nothing, and a block no visit writes would come back unset
    return dataclasses.replace(
        params,
        fwd_steps=max(params.fwd_steps, fs),
        bwd_steps=max(params.bwd_steps, bs),
        mask_step=max(params.mask_step, step),
        bwd_unnamed_q=max(params.bwd_unnamed_q or 0, unnamed),
    )


def _for_tables(params: FlexAttnParams, tables: StageTables) -> FlexAttnParams:
    """``params`` (whose extents cover the whole plan) for the one kernel
    call that walks ``tables``: the count of unnamed q blocks is this
    stage's own, so a stage that names every block fills nothing though
    another stage of the plan leaves some out."""
    return dataclasses.replace(params, bwd_unnamed_q=tables.q_visits()[2])


def _call_kernel(qh, k_buf, v_buf, tab_arrays, kv_pad, params, sink):
    with named_scope("magi_layout"):
        kh = _hm(k_buf, kv_pad)
        vh = _hm(v_buf, kv_pad)
        ftab = tuple(a[0] for a in tab_arrays[:4]) + (tab_arrays[8][0],)
        btab = tuple(a[0] for a in tab_arrays[4:8]) + (tab_arrays[8][0],)
    return flex_attn_headmajor(qh, kh, vh, ftab, btab, params, sink=sink)


def dist_attn_local(
    q: jax.Array,  # [shard_q_len, hq, d] rank-local dispatched q
    k: jax.Array,  # [shard_q_len, hk, d]
    v: jax.Array,
    tables,  # flattened per-rank table slices from plan.device_tables()
    plan: DistAttnPlan,
    params: FlexAttnParams,
    *,
    axis_name: str = "cp",
    sink: jax.Array | None = None,
    with_guard_code: bool = False,
    with_census: bool = False,
):
    """The SPMD hot path — call inside shard_map over the cp axis.

    Returns (out [shard_q_len, hq, d], lse [shard_q_len, hq], and the
    rank-local per-head max logit [hq] — pmax it across the cp axis for
    the global value).

    ``with_guard_code``: additionally return the rank-local int32 guard
    error code as a 4th output (ISSUE 8 — every stage partial is guarded
    when ``MAGI_ATTENTION_GUARD`` != off; the keyed runtime consumes the
    code at the jit boundary). Default False keeps the 3-tuple contract
    for direct callers (models, trace audit).

    ``with_census``: additionally return the rank-local packed value
    census (ISSUE 18 — f32 ``[len(numerics.census_keys(sites))]``, the
    per-guard-site summaries + final softmax-mass deviation in
    ``plan_guard_sites`` order) as the LAST output. Pure reductions
    over partials already in registers — no collectives.
    """
    from ..resilience import chaos, guards
    from ..telemetry import numerics

    gmode = guards.guard_mode()
    code = guards.new_error_code() if with_guard_code else None
    census_vals: list = []
    partial_lses: list = []

    def _resilient(out_p, lse_p, site, site_index, rowmax=None):
        # chaos upstream of the guard — injected faults must travel the
        # exact path an organic kernel NaN would
        nonlocal code
        if chaos.enabled():
            out_p, lse_p = chaos.corrupt_partial(
                out_p,
                lse_p,
                site,
                axis_name=axis_name if plan.hier is None else None,
            )
        if gmode != "off":
            out_p, lse_p, code = guards.guard_partial(
                out_p, lse_p, code, site_index, site
            )
        if with_census:
            # census downstream of chaos: an injected corruption must
            # be visible to the instruments built to catch it
            census_vals.extend(
                numerics.site_summary(out_p, lse_p, rowmax)
            )
            partial_lses.append(lse_p)
        return out_p, lse_p

    def _pack_census(final_lse):
        census_vals.append(
            numerics.mass_deviation(partial_lses, final_lse)
        )
        return numerics.pack_census(census_vals)

    params = ensure_kernel_steps(
        params,
        (plan.merged_tables, plan.host_tables,
         *(sp.tables for sp in plan.stages)),
    )
    # named scopes (utils/instrument.py): every operation of the call lies
    # under exactly one part scope that a metric reads (docs/observability.md,
    # "Device scopes"): the flex kernels by their own names, the casts,
    # reduces and merges magi_*_cast / magi_group_* / magi_*_lse_merge, and
    # all the rest of what runs round the kernels magi_layout
    with named_scope("magi_layout"):
        qh = _hm(q, plan.shard_q_pad)
        # one all_to_all payload for K and V: stacked where they are one
        # width (the program every plan had), else [k | v] along the last
        # axis, split on arrival (latent attention's keys of 192 or 256
        # lanes beside values of 128)
        one_width = k.shape[-1] == v.shape[-1]
        kv = (
            jnp.stack([k, v], axis=1) if one_width
            else jnp.concatenate([k, v], axis=-1)
        )
        if env.is_backward_high_precision_reduce():
            # fp32 payload -> the transposed dKV reduce accumulates in fp32
            # (2x comm; reference BACKWARD_HIGH_PRECISION_REDUCE)
            kv = kv.astype(jnp.float32)
    cur = 0

    def take(n):
        nonlocal cur
        out = tables[cur : cur + n]
        cur += n
        return out

    def cast(payload, comm, comm_arrays):
        if plan.hier is not None:
            inter_name, intra_name = axis_name
            return group_cast_hier(
                payload,
                comm_arrays,
                axis_inter=inter_name,
                axis_intra=intra_name,
                meta=comm,
            )
        return group_cast_m(payload, comm, comm_arrays, axis_name=axis_name)

    def cast_kv(comm):
        # downcast received KV to the kernel dtype; with the fp32 payload
        # the astype transpose upcasts each dKV cotangent before the
        # reduce, giving the high-precision accumulate
        return cast(kv, comm, take(len(plan._comm_arrays(comm)))).astype(
            k.dtype
        )

    def split_kv(recv):
        if one_width:
            return recv[:, 0], recv[:, 1]
        return recv[..., : k.shape[-1]], recv[..., k.shape[-1] :]

    def _head_max(rowmax):
        # per-head max of masked logits over this rank's rows (pads carry
        # -inf); callers pmax across ranks (reference reduce_max_logits,
        # dist_attn.py:532 + :3168 all_reduce MAX — Muon QK-Clip support)
        return jnp.max(rowmax, axis=1)

    if plan.overlap_degree == 0:
        tab = take(9)
        with named_scope("magi_merged_cast"):
            recv = cast_kv(plan.merged_comm)
        with named_scope("magi_layout"):
            k_recv, v_recv = split_kv(recv)
            k_full = jnp.concatenate([k, k_recv], axis=0)
            v_full = jnp.concatenate([v, v_recv], axis=0)
        with named_scope("magi_merged_kernel"):
            out_h, lse_h, rowmax = _call_kernel(
                qh, k_full, v_full, tab, plan.merged_tables.kv_pad, params,
                sink,
            )
        with named_scope("magi_layout"):
            out, lse = _headmajor_to_seq(out_h, lse_h, plan.shard_q_len)
        out, lse = _resilient(out, lse, "merged", 0, rowmax=rowmax)
        with named_scope("magi_layout"):
            res = (out, lse, _head_max(rowmax))
        if with_guard_code:
            res = res + (code,)
        if with_census:
            res = res + (_pack_census(lse),)
        return res

    # staged path: host stage + D lse-merged remote stages.
    # The sink joins the softmax denominator exactly once — in the host
    # stage; remote partials are sink-free. The running accumulator stays
    # fp32 across merges (reference fwd_out_lse_use_acc /
    # FORWARD_HIGH_PRECISION_REDUCE semantics, default on); a single
    # downcast happens at the end.
    acc_dtype = (
        "float32"
        if env.is_forward_high_precision_reduce()
        else params.out_dtype
    )
    host_params = dataclasses.replace(params, out_dtype=acc_dtype)
    host_tab = take(9)
    with named_scope("magi_host_stage_kernel"):
        out_h, lse_h, rowmax = _call_kernel(
            qh, k, v, host_tab, plan.host_tables.kv_pad,
            _for_tables(host_params, plan.host_tables), sink,
        )
    with named_scope("magi_layout"):
        out, lse = _headmajor_to_seq(out_h, lse_h, plan.shard_q_len)
    out, lse = _resilient(out, lse, "host", 0, rowmax=rowmax)
    with named_scope("magi_layout"):
        mx = _head_max(rowmax)

    stage_params = dataclasses.replace(
        params, has_sink=False, out_dtype=acc_dtype
    )
    for i, sp in enumerate(plan.stages):
        tab = take(9)
        with named_scope(f"magi_stage{i}_cast"):
            recv = cast_kv(sp.comm)
        with named_scope(f"magi_stage{i}_kernel"):
            out_i_h, lse_i_h, rowmax_i = _call_kernel(
                qh, *split_kv(recv), tab, sp.tables.kv_pad,
                _for_tables(stage_params, sp.tables), None,
            )
        with named_scope("magi_layout"):
            out_i, lse_i = _headmajor_to_seq(
                out_i_h, lse_i_h, plan.shard_q_len
            )
        out_i, lse_i = _resilient(
            out_i, lse_i, f"stage{i}", 1 + i, rowmax=rowmax_i
        )
        with named_scope(f"magi_stage{i}_lse_merge"):
            out, lse = correct_attn_out_lse(out, lse, out_i, lse_i)
        with named_scope("magi_layout"):
            mx = jnp.maximum(mx, _head_max(rowmax_i))
    with named_scope("magi_layout"):
        out = out.astype(params.out_jnp_dtype)
    res = (out, lse, mx)
    if with_guard_code:
        res = res + (code,)
    if with_census:
        res = res + (_pack_census(lse),)
    return res


def make_dist_attn_fn(
    plan: DistAttnPlan,
    mesh: jax.sharding.Mesh,
    params: FlexAttnParams,
    *,
    axis_name: str = "cp",
    sink: jax.Array | None = None,  # [hq] learned sink logits (replicated)
    with_max_logits: bool = False,
):
    """Convenience: a jittable fn over *dispatched global* arrays sharded
    P(axis_name) along tokens.

    ``with_max_logits``: also return the globally-reduced per-head max
    logit [hq] (pmax over the cp axis; reference reduce_max_logits) as a
    third output.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..resilience import guards
    from ..utils.compat import shard_map

    assert params.has_sink == (sink is not None), (
        "params.has_sink must match whether a sink array is provided"
    )
    # ISSUE 8: with guards on, the local body threads an int32 error
    # code out of the traced program; this wrapper consumes it at the
    # jit boundary (check mode raises NumericalGuardError naming the
    # failing stage; repair mode records the quarantines)
    from ..telemetry import numerics

    thread_code = guards.guards_active()
    guard_sites = guards.plan_guard_sites(plan) if thread_code else ()
    # ISSUE 18: census mode threads the packed value summaries out the
    # same way (one extra [1, S] per-rank output, consumed at the jit
    # boundary); off-mode traces NOTHING extra — proven bit-identical
    # by the numerics-check transparency pass
    thread_census = numerics.census_active()
    census_keys = (
        numerics.census_keys(guards.plan_guard_sites(plan))
        if thread_census
        else ()
    )
    tables = plan.device_tables()
    if all(d.process_index == jax.process_index() for d in mesh.devices.flat):
        tables = tuple(
            jax.device_put(t, NamedSharding(mesh, P(axis_name)))
            for t in tables
        )
    else:
        # AOT-compilation meshes (jax.experimental.topologies) have
        # non-addressable devices: keep the tables as host constants and
        # let jit embed them. Placement is a per-call-cost nicety only.
        tables = tuple(tables)
    n_tab = len(tables)
    sink_specs = (P(),) if sink is not None else ()
    out_specs = (P(axis_name), P(axis_name))
    if with_max_logits:
        # per-rank [1, hq] maxes, globally max-reduced OUTSIDE shard_map
        # (pmax has no differentiation rule; jnp.max over the gathered
        # axis is equivalent and transparently differentiable — the
        # kernel vjp drops rowmax cotangents anyway)
        out_specs = out_specs + (P(axis_name),)
    if thread_code:
        out_specs = out_specs + (P(axis_name),)  # per-rank guard codes
    if thread_census:
        out_specs = out_specs + (P(axis_name),)  # per-rank census [1, S]

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(axis_name))
        + (P(axis_name),) * n_tab
        + sink_specs,
        out_specs=out_specs,
        # pallas_call out_shapes carry no vma info; skip the static check
        check_vma=False,
    )
    def _local(q, k, v, *rest):
        tabs = rest[:n_tab]
        s = rest[n_tab] if len(rest) > n_tab else None
        res = dist_attn_local(
            q, k, v, tabs, plan, params, axis_name=axis_name, sink=s,
            with_guard_code=thread_code, with_census=thread_census,
        )
        out, lse, mx = res[:3]
        outs = (out, lse)
        if with_max_logits:
            outs = outs + (mx[None],)
        if thread_code:
            outs = outs + (res[3][None],)
        if thread_census:
            outs = outs + (res[-1][None],)
        return outs

    def fn(q, k, v, sink_override=None):
        # sink is a *traced* argument: callers may pass an updated (e.g.
        # trainable) sink array per call so gradients flow through it; the
        # array captured at plan time is only the default. The has-sink
        # structure itself is static (fixed at plan time).
        s = sink if sink_override is None else sink_override
        assert (s is None) == (sink is None), (
            "sink override requires a plan built with has_sink=True"
        )
        extra = (s,) if s is not None else ()
        res = _local(q, k, v, *tables, *extra)
        if thread_census:
            *res, census = res
            numerics.consume_census(census, census_keys, layer="parallel")
        if thread_code:
            *res, code = res
            guards.consume_error_code(code, guard_sites)
        if not with_max_logits:
            return res[0], res[1]
        out, lse, mxs = res
        with named_scope("magi_layout"):
            return out, lse, jnp.max(mxs, axis=0)

    return fn
