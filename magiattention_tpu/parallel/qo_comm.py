"""qo-comm runtime: execute a dynamic (attention-plane) partition.

Role of reference ``meta/solver/dynamic_attn_solver.py`` emit stages +
the qo-comm paths of ``functional/dist_attn.py`` (_fetch_remote_q,
_reduce_partial_out_lse with reduce_op='lse'): the generalized mode where
**both Q/O and KV move**. The DynamicAttnSolver cuts the attention plane
into cp equal-area regions; each region owner group-casts in the Q rows and
KV rows its region touches, computes partial attention, and the partial
(out, lse) rows are group-reduced (LSE op) back to the Q owners.

Everything is differentiable: the O-return reduce is the lse-weighted
segment merge (comm/group_collective.group_reduce_lse), the Q/KV casts
transpose into the dQ/dKV returns automatically, and the kernel vjp's
first-class lse cotangent makes the partial-merge backward exact.

Token ownership is the contiguous (sequential) shard by default, or —
when a ``dispatch_meta`` is passed to :func:`build_qo_comm_plan` — the
chunk-permuted load-balanced dispatch layout, composing qo-comm with
area-balanced sharding (reference _make_attn_meta.py:40-130).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..common.range import AttnRange
from ..common.ranges import AttnRanges
from ..common.rectangle import AttnRectangles
from ..comm.group_collective import (
    GroupCollectiveMeta,
    group_cast_m,
    group_reduce_lse_m,
)
from ..meta.solver.dynamic_attn_solver import (
    AutoDynamicSolver,
    DynamicAttnSolver,
)
from ..ops.block_meta import Run, build_block_meta_general, runs_from_position_ids
from ..ops.correction import correct_attn_out_lse_with_sink
from ..ops.flex_attn import FlexAttnParams
from .dist_attn import StageTables, _call_kernel, _headmajor_to_seq, _hm, _round_up


@dataclasses.dataclass(frozen=True, eq=False)
class QoCommPlan:
    cp_size: int
    shard_len: int  # contiguous token shard per rank (q == kv side)
    q_buf_pad: int  # padded received-Q buffer rows
    kv_buf_pad: int
    block_q: int
    block_k: int
    comm_q: GroupCollectiveMeta  # Q cast out / O lse-reduce back
    comm_kv: GroupCollectiveMeta
    tables: StageTables
    rank_areas: tuple[int, ...]

    def device_tables(self):
        arrs = list(self.tables.arrays())
        # comm arrays in the metas' impl-dependent layouts: the Q meta
        # ships the reduce superset (its cast comes back as the O
        # lse-reduce), the KV meta the cast layout only
        arrs += list(self.comm_q.reduce_device_arrays())
        arrs += list(self.comm_kv.cast_device_arrays())
        return tuple(jnp.asarray(a) for a in arrs)


def _ranges_to_send_map(
    need: list[AttnRanges],
    shard: int,
    cp: int,
    unperm: np.ndarray | None = None,
) -> tuple[list[list[np.ndarray]], list[list[tuple[int, np.ndarray]]]]:
    """send_map[s][d] = s-local rows of need[d] owned by s;
    recv_segments[d] = (src, global ids) in recv order.

    Ownership: global row g lives at dispatch slot ``unperm[g]`` =
    rank * shard + local. ``unperm=None`` is the contiguous identity
    (sequential shard) fast path; a chunk-permuted dispatch layout
    (balanced MinHeap etc.) routes through its own unperm_idx — the
    composition the reference gets from building the dynamic attn meta
    over the dispatch meta (_make_attn_meta.py:40-130)."""
    send_map = [
        [np.empty(0, np.int64) for _ in range(cp)] for _ in range(cp)
    ]
    recv_segments: list[list[tuple[int, np.ndarray]]] = [[] for _ in range(cp)]
    for d in range(cp):
        if need[d].is_empty():
            continue
        if unperm is None:
            # contiguous ownership: pure interval arithmetic, no
            # row-id materialization or sort (1M-token plans care)
            for s in range(cp):
                own = AttnRanges.from_ranges([(s * shard, (s + 1) * shard)])
                inter = need[d].find_overlap_ranges(own)
                if inter.is_empty():
                    continue
                rows = np.concatenate(
                    [
                        np.arange(
                            r.start - s * shard,
                            r.end - s * shard,
                            dtype=np.int64,
                        )
                        for r in inter
                    ]
                )
                send_map[s][d] = rows
                recv_segments[d].append((s, rows + s * shard))
            continue
        ids = np.concatenate(
            [np.arange(r.start, r.end, dtype=np.int64) for r in need[d]]
        )
        slots = unperm[ids]
        s_rank = slots // shard
        local = slots % shard
        # canonical (src, global id) order shared by sender and receiver:
        # ids are ascending (merged ranges), so a stable sort by src rank
        # keeps them ascending within each src group
        order = np.argsort(s_rank, kind="stable")
        s_sorted = s_rank[order]
        for s in np.unique(s_sorted):
            m = s_sorted == s
            send_map[int(s)][d] = local[order][m]
            recv_segments[d].append((int(s), ids[order][m]))
    return send_map, recv_segments


def _runs_from_segments(
    segments: list[tuple[int, np.ndarray]]
) -> list[Run]:
    runs: list[Run] = []
    base = 0
    for _, gids in segments:
        for r in runs_from_position_ids(gids):
            runs.append(
                Run(
                    local_start=base + r.local_start,
                    global_start=r.global_start,
                    length=r.length,
                )
            )
        base += len(gids)
    return runs


def build_qo_comm_plan(
    slices: np.ndarray,  # [S, 5] global (qs, qe, ks, ke, type)
    total_seqlen: int,
    cp_size: int,
    *,
    block_q: int = 128,
    block_k: int = 128,
    solver: DynamicAttnSolver | None = None,
    dispatch_meta=None,
) -> QoCommPlan:
    """Plan the dynamic (attention-plane) partition + its comm routing.

    ``dispatch_meta``: when given, token ownership is that (chunk-
    permuted, load-balanced) dispatch layout instead of the contiguous
    sequential shard — qo-comm then composes with area-balanced
    dispatching exactly as the reference does by selecting the dynamic
    solver over the dispatch meta (_make_attn_meta.py:40-130). The plane
    partition itself stays in global coordinates either way; only the
    cast/reduce routing follows the permuted ownership.
    """
    assert total_seqlen % cp_size == 0, (
        f"total_seqlen {total_seqlen} must be divisible by cp_size {cp_size}"
    )
    sl = np.asarray(slices, dtype=np.int64).reshape(-1, 5)
    assert (sl[:, :4] >= 0).all() and (
        sl[:, [1, 3]] <= total_seqlen
    ).all(), (
        f"slice ranges must lie within [0, {total_seqlen}): got "
        f"{sl[:, :4].min()}..{sl[:, [1, 3]].max()} (out-of-range tokens "
        "would silently never be cast)"
    )
    shard = total_seqlen // cp_size
    unperm = None
    if dispatch_meta is not None:
        assert dispatch_meta.cp_size == cp_size, (
            dispatch_meta.cp_size, cp_size,
        )
        assert not dispatch_meta.is_uneven, (
            "qo-comm x uneven shard is unsupported (check_flag_comb)"
        )
        assert dispatch_meta.shard_seqlen == shard, (
            f"dispatch meta shard {dispatch_meta.shard_seqlen} != "
            f"{shard} (pad the sequence to the dispatch layout first)"
        )
        unperm = dispatch_meta.unperm_idx.astype(np.int64)
    # default: best-of-family by the modeled step cost (the measured
    # recommendation, docs/dynamic_solver.md) — pass an explicit solver
    # to pin one algorithm
    solver = solver or AutoDynamicSolver()

    rects = AttnRectangles.from_ranges(
        [(int(s[0]), int(s[1])) for s in slices],
        [(int(s[2]), int(s[3])) for s in slices],
        [int(s[4]) for s in slices],
    )
    sol = solver.solve(rects, cp_size, total_seqlen=total_seqlen)
    from .. import telemetry

    telemetry.record_dynamic_solution(
        type(solver).__name__, sol.balance_ratio
    )

    import logging

    logger = logging.getLogger("magiattention_tpu")
    if logger.isEnabledFor(logging.DEBUG):
        # debug-only bucket plot (reference _make_attn_meta.py:96-101
        # writes dyn_solver_buckets.png at DEBUG level); the filename is
        # keyed on the mask so multi-key runs keep every plot, and any
        # I/O failure must never take planning down
        try:
            import hashlib

            from ..utils.vis import plot_dynamic_solution

            tag = hashlib.sha1(sl.tobytes()).hexdigest()[:8]
            path = plot_dynamic_solution(
                sol,
                total_seqlen,
                total_seqlen,
                f"./dyn_solver_buckets_cp{cp_size}_{tag}.png",
            )
            if path:
                logger.debug("dynamic-solver bucket plot saved to %s", path)
        except Exception as e:
            logger.debug("dynamic-solver bucket plot failed: %r", e)

    q_need: list[AttnRanges] = []
    k_need: list[AttnRanges] = []
    rank_slices: list[np.ndarray] = []
    for rr in sol.rank_rects:
        qs = AttnRanges()
        ks = AttnRanges()
        rows = []
        for rect in rr:
            qs.append(rect.q_range.clone())
            ks.append(rect.k_range.clone())
            rows.append(
                (
                    rect.q_range.start,
                    rect.q_range.end,
                    rect.k_range.start,
                    rect.k_range.end,
                    int(rect.mask_type),
                )
            )
        q_need.append(qs.merge())
        k_need.append(ks.merge())
        rank_slices.append(np.asarray(rows, dtype=np.int64).reshape(-1, 5))

    send_q, recv_q = _ranges_to_send_map(q_need, shard, cp_size, unperm)
    send_kv, recv_kv = _ranges_to_send_map(k_need, shard, cp_size, unperm)
    comm_q = GroupCollectiveMeta.build(send_q, [shard] * cp_size)
    comm_kv = GroupCollectiveMeta.build(send_kv, [shard] * cp_size)

    q_buf_pad = _round_up(max(comm_q.max_recv, block_q), block_q)
    kv_buf_pad = _round_up(max(comm_kv.max_recv, block_k), block_k)

    metas = []
    for r in range(cp_size):
        metas.append(
            build_block_meta_general(
                rank_slices[r],
                _runs_from_segments(recv_q[r]),
                _runs_from_segments(recv_kv[r]),
                q_buf_pad,
                kv_buf_pad,
                block_q=block_q,
                block_k=block_k,
            )
        )
    tables = StageTables.from_rank_metas(metas, kv_buf_pad)
    return QoCommPlan(
        cp_size=cp_size,
        shard_len=shard,
        q_buf_pad=q_buf_pad,
        kv_buf_pad=kv_buf_pad,
        block_q=block_q,
        block_k=block_k,
        comm_q=comm_q,
        comm_kv=comm_kv,
        tables=tables,
        rank_areas=sol.areas,
    )


def qo_comm_attn_local(
    q: jax.Array,  # [shard, hq, d] contiguous token shard
    k: jax.Array,
    v: jax.Array,
    tables,  # 9 kernel arrays + q-comm + kv-comm (per-rank slices; comm
    # array counts follow the metas' impl layouts)
    plan: QoCommPlan,
    params: FlexAttnParams,
    *,
    axis_name: str = "cp",
    sink: jax.Array | None = None,  # [hq] learned sink logits (replicated)
):
    """Inside shard_map: cast Q + KV to region owners, partial attn,
    lse-reduce O back to Q owners. Returns (out [shard, hq, d], lse).

    ``sink``: a q row's softmax is split across region partials on
    different ranks, so the sink cannot ride the kernel (it would join the
    denominator once per region). Instead the partials are lse-merged
    sink-free and the owner rank folds the sink in once afterwards via the
    rescale identity ``lse' = logaddexp(lse, sink)``,
    ``out' = out * exp(lse - lse')`` — exactly the reference's
    sink-once-per-row semantics (functional/utils.py:561-677), and
    differentiable in the sink by plain autodiff."""
    assert not params.has_sink, (
        "qo-comm applies the sink post-merge: build params with "
        "has_sink=False and pass the sink array to this function instead"
    )
    assert (
        params.block_q == plan.block_q and params.block_k == plan.block_k
    ), (
        f"params blocks ({params.block_q},{params.block_k}) != plan blocks "
        f"({plan.block_q},{plan.block_k}) — entry tables would be misread; "
        "derive params with make_attn_params(plan, head_dim)"
    )
    from .dist_attn import ensure_kernel_steps

    params = ensure_kernel_steps(params, (plan.tables,))
    kt = tables
    ktab = kt[:9]
    nq = plan.comm_q.num_reduce_arrays
    q_arrays = kt[9 : 9 + nq]
    kv_arrays = kt[9 + nq : 9 + nq + plan.comm_kv.num_cast_arrays]

    hq = q.shape[1]
    qb = group_cast_m(q, plan.comm_q, q_arrays, axis_name=axis_name)
    kv = jnp.stack([k, v], axis=1)
    kvb = group_cast_m(kv, plan.comm_kv, kv_arrays, axis_name=axis_name)

    fp32 = dataclasses.replace(params, out_dtype="float32")
    qh = _hm(qb, plan.q_buf_pad)
    out_h, lse_h, _ = _call_kernel(
        qh, kvb[:, 0], kvb[:, 1], ktab, plan.kv_buf_pad, fp32, None
    )
    out_p, lse_p = _headmajor_to_seq(out_h, lse_h, plan.comm_q.max_recv)

    out_acc = jnp.zeros((plan.shard_len, hq, q.shape[2]), jnp.float32)
    lse_acc = jnp.full((plan.shard_len, hq), -jnp.inf, jnp.float32)
    out, lse = group_reduce_lse_m(
        out_p,
        lse_p,
        out_acc,
        lse_acc,
        plan.comm_q,
        q_arrays,
        axis_name=axis_name,
    )
    if sink is not None:
        # rows with lse=-inf (uncovered) end at lse'=sink, out stays 0 —
        # the Pallas epilogue's uncovered-row-with-sink behavior
        out, lse = correct_attn_out_lse_with_sink(
            out, lse, sink.astype(jnp.float32)[None, :], "sh"
        )
    return out.astype(params.out_jnp_dtype), lse


def make_qo_comm_attn_fn(
    plan: QoCommPlan,
    mesh: jax.sharding.Mesh,
    params: FlexAttnParams,
    *,
    axis_name: str = "cp",
    sink: jax.Array | None = None,  # [hq] default sink (traceable override)
):
    """Jittable fn over contiguously sharded [total, h, d] arrays."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..utils.compat import shard_map

    tables = tuple(
        jax.device_put(t, NamedSharding(mesh, P(axis_name)))
        for t in plan.device_tables()
    )
    n_tab = len(tables)
    sink_specs = (P(),) if sink is not None else ()

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis_name),) * 3 + (P(axis_name),) * n_tab + sink_specs,
        out_specs=(P(axis_name), P(axis_name)),
        check_vma=False,
    )
    def _local(q, k, v, *rest):
        tabs = rest[:n_tab]
        s = rest[n_tab] if len(rest) > n_tab else None
        return qo_comm_attn_local(
            q, k, v, tabs, plan, params, axis_name=axis_name, sink=s
        )

    def fn(q, k, v, sink_override=None):
        s = sink if sink_override is None else sink_override
        if sink is None:
            assert sink_override is None, (
                "this qo attn fn was built without a sink: rebuild with "
                "make_qo_comm_attn_fn(..., sink=...)"
            )
            return _local(q, k, v, *tables)
        return _local(q, k, v, *tables, s)

    return fn
