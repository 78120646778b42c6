"""Measured stage timelines: re-execute a plan piece-by-piece and time it.

The overlap solver *predicts* a pipelined timeline
(``simulate_overlap_timeline``) from analytic cost factors and picks the
overlap degree from it — but until now nothing ever measured what the
hardware actually did, so a solver misprediction was invisible. This
module is the measuring half of that loop:

1. split a :class:`~..parallel.dist_attn.DistAttnPlan` into its
   executable pieces — host-stage kernel, per-stage group cast, per-stage
   kernel (merged cast + merged kernel on the degree-0 path) — as
   separate jitted shard_map programs over the same mesh/tables the real
   runtime uses;
2. time each piece AND the full pipelined path with the sync
   discipline of ``benchmarking/bench.py`` (``do_bench``: warmup, inner
   batching, ``block_until_ready`` on the whole result per timed
   region);
3. fold the numbers into a :class:`MeasuredTimeline`: per-stage comm/calc
   ms, serial sum vs measured end-to-end, the overlap efficiency (what
   fraction of hideable comm the XLA scheduler actually hid), and the
   predicted-vs-measured delta against the same
   ``simulate_overlap_timeline`` model the solver chose the degree with.

Everything is host-driven: the pieces are ordinary jitted functions,
fenced on the host between timings — nothing records from inside traced
code. Telemetry gauges (``magi_overlap_measured_*``) are written via
:func:`~.collectors.record_measured_timeline` when telemetry is enabled.

Caveats: the pieces run with ``has_sink=False`` (the sink joins the
softmax once in the host stage and does not move timing) and the
default-precision KV payload. Each piece mirrors its slice of
``dist_attn_local`` — kernel, head-major -> sequence layout, and (remote
stages) the lse merge, in the same accumulator dtype — so the serial sum
prices the same numeric work as the pipelined path; the residual bias is
the per-piece dispatch overhead, which over-counts the serial bound
slightly. ``overlap_efficiency`` divides by hideable *comm* only, the
quantity the paper's claim is about.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class StageTiming:
    """Measured (and modeled) cost of one pipeline piece. ``stage`` is
    ``"host"``, ``"merged"``, or the remote stage index as a string;
    ``comm_ms`` is 0 for pieces with no cast (the host stage)."""

    stage: str
    comm_ms: float
    calc_ms: float
    predicted_comm_ms: float | None = None
    predicted_calc_ms: float | None = None


@dataclasses.dataclass(frozen=True)
class HopTiming:
    """Measured cost of ONE hop of a hop-scheduled group cast (or one
    level of a hierarchical cast), timed as its own jitted program.
    ``hop`` is the ppermute shift as a string (``"0"`` = the local-copy
    self hop) or the level name (``"inter"``/``"intra"``) on
    hierarchical metas; ``axis`` is the mesh axis the hop rides — the
    label the DCN-aware two-axis pricing (ROADMAP item 3) keys on."""

    stage: str  # "merged" or the remote stage index as a string
    axis: str  # mesh axis name ("cp"; "dcn"/"ici" on hier meshes)
    hop: str
    rows: int  # padded payload rows per rank this hop ships
    ms: float


@dataclasses.dataclass(frozen=True)
class MeasuredTimeline:
    """One profiled plan: per-stage measurements plus the aggregate
    pipelined/serial/predicted comparison."""

    overlap_degree: int
    cp_size: int
    stages: tuple[StageTiming, ...]
    measured_total_ms: float  # full pipelined path, end to end
    serial_total_ms: float  # sum of the individually-fenced pieces
    hideable_comm_ms: float  # total stage-cast time overlap could hide
    overlap_efficiency: float  # hidden / hideable, clamped to [0, 1]
    predicted_total_ms: float | None  # simulate_overlap_timeline model
    prediction_error_ratio: float | None  # measured_total / predicted
    # per-hop attribution of hop-scheduled / hierarchical casts (empty
    # for pure a2a plans): each hop timed as its own program
    hops: tuple[HopTiming, ...] = ()

    def report(self) -> str:
        """Human-readable predicted-vs-measured table (the overlap
        audit); one line per stage, then the aggregate verdict."""

        def fmt(v, suffix=""):
            return "-" if v is None else f"{v:.3f}{suffix}"

        lines = [
            f"measured stage timeline: overlap_degree={self.overlap_degree} "
            f"cp={self.cp_size}",
            f"  {'stage':<8} {'comm ms (pred)':<20} {'calc ms (pred)':<20}",
        ]
        for st in self.stages:
            comm = f"{st.comm_ms:.3f} ({fmt(st.predicted_comm_ms)})"
            calc = f"{st.calc_ms:.3f} ({fmt(st.predicted_calc_ms)})"
            lines.append(f"  {st.stage:<8} {comm:<20} {calc:<20}")
        lines.append(
            f"  end-to-end measured {self.measured_total_ms:.3f} ms | "
            f"serial sum {self.serial_total_ms:.3f} ms | "
            f"predicted {fmt(self.predicted_total_ms, ' ms')}"
        )
        # clamp like overlap_efficiency does: serial over-counts each
        # piece's dispatch overhead, so raw serial-minus-measured can
        # exceed the hideable comm — never print >100% of it as hidden
        hidden = min(
            max(self.serial_total_ms - self.measured_total_ms, 0.0),
            self.hideable_comm_ms,
        )
        lines.append(
            f"  overlap efficiency {self.overlap_efficiency:.1%}: "
            f"{hidden:.3f} ms of {self.hideable_comm_ms:.3f} ms hideable "
            "comm hidden"
        )
        if self.prediction_error_ratio is not None:
            lines.append(
                "  solver model delta: measured/predicted = "
                f"{self.prediction_error_ratio:.2f}x "
                "(>1: hardware slower than the model priced)"
            )
        if self.hops:
            lines.append("  per-hop cast attribution:")
            by_stage: dict[str, float] = {}
            for h in self.hops:
                lines.append(
                    f"    stage {h.stage:<7} axis={h.axis} hop {h.hop}: "
                    f"{h.ms:.3f} ms ({h.rows} rows/rank)"
                )
                by_stage[h.stage] = by_stage.get(h.stage, 0.0) + h.ms
            cast_by_stage = {
                st.stage: st.comm_ms for st in self.stages if st.comm_ms
            }
            for stage, total in by_stage.items():
                cast = cast_by_stage.get(stage)
                if cast:
                    lines.append(
                        f"    stage {stage:<7} hop sum {total:.3f} ms vs "
                        f"whole cast {cast:.3f} ms (per-hop programs "
                        "re-pay dispatch overhead)"
                    )
        return "\n".join(lines)


def _predicted_costs(
    plan,
    *,
    num_heads_q: int,
    num_heads_kv: int,
    head_dim: int,
    bytes_per_elt: int,
    generation: str | None,
    calc_cost_factor: float | None = None,
    comm_cost_factor: float | None = None,
    stage_overhead_s: float = 30e-6,
):
    """(host_calc_s, [stage_comm_s], [stage_calc_s], predicted_total_s)
    from the same pricing the auto-degree search uses — or None when the
    cost factors cannot be resolved (unknown generation)."""
    from ..meta.solver.overlap_solver import simulate_overlap_timeline

    if calc_cost_factor is None or comm_cost_factor is None:
        from .. import env
        from ..utils.cost import get_calc_cost_factor, get_comm_cost_factor

        gen = generation or env.tpu_generation()
        try:
            calc_cost_factor = get_calc_cost_factor(
                num_heads_q, head_dim, gen
            )
            comm_cost_factor = get_comm_cost_factor(
                num_heads_kv, head_dim, gen, bytes_per_elt=bytes_per_elt
            )
        except ValueError:
            return None
    # comm is priced at the rows the SELECTED impl schedules on the wire
    # (a2a: the globally-padded buffer; hops: the per-hop padded sums) —
    # the volume the hardware will actually move, matching the
    # auto-degree search's volume-ratio pricing (ISSUE 5)
    if plan.overlap_degree == 0:
        comm_s = [
            plan.merged_comm.scheduled_rows_per_rank * comm_cost_factor
        ]
        calc_s = [plan.max_rank_area * calc_cost_factor]
        total = simulate_overlap_timeline(0.0, comm_s, calc_s, 0.0)
        return 0.0, comm_s, calc_s, total
    host_s = plan.host_max_rank_area * calc_cost_factor
    comm_s = [
        sp.comm.scheduled_rows_per_rank * comm_cost_factor
        for sp in plan.stages
    ]
    calc_s = [sp.max_rank_area * calc_cost_factor for sp in plan.stages]
    total = simulate_overlap_timeline(host_s, comm_s, calc_s, stage_overhead_s)
    return host_s, comm_s, calc_s, total


def profile_plan_timeline(
    plan,
    mesh,
    params,
    *,
    axis_name="cp",
    q=None,
    k=None,
    v=None,
    num_heads: tuple[int, int] | None = None,
    head_dim: int | None = None,
    dtype=None,
    shard_k_len: int | None = None,
    reps: int | None = None,
    inner: int | None = None,
    warmup: int = 1,
    seed: int = 0,
    generation: str | None = None,
    calc_cost_factor: float | None = None,
    comm_cost_factor: float | None = None,
    stage_overhead_s: float = 30e-6,
    use_mesh_barrier: bool = False,
    record: bool = True,
) -> MeasuredTimeline:
    """Measure a plan's stage timeline on the given mesh.

    ``q/k/v`` are *dispatched-layout* global arrays (``[cp * shard, h,
    d]``); omitted, random operands are synthesized from ``num_heads`` /
    ``head_dim`` / ``dtype`` (default ``params.out_dtype``), with
    ``shard_k_len`` sizing the K/V shard for cross-attention plans whose
    KV dispatch differs from the Q one (default: the Q shard length —
    self-attention). ``reps`` /
    ``inner`` default to the ``MAGI_ATTENTION_TIMELINE_REPS`` /
    ``_INNER`` env knobs. ``use_mesh_barrier`` rendezvouses every device
    before each timed rep (multi-chip meshes).

    With ``record=True`` (and telemetry enabled) the result is also
    written to the registry as ``magi_overlap_measured_*`` gauges.

    Works for staged (degree >= 1), merged (degree 0), flat and
    hierarchical self-attention plans; qo-comm plans have their own
    kernel geometry and are not supported.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .. import env
    from ..benchmarking.bench import do_bench
    from ..comm.group_collective import group_cast, group_cast_m, hop_cast
    from ..comm.hier import group_cast_hier
    from ..ops.correction import correct_attn_out_lse
    from ..parallel.dist_attn import (
        _call_kernel,
        _headmajor_to_seq,
        _hm,
        dist_attn_local,
        ensure_kernel_steps,
    )
    from ..utils.compat import shard_map

    if not hasattr(plan, "stages"):
        raise NotImplementedError(
            "profile_plan_timeline supports DistAttnPlan runtimes only "
            f"(got {type(plan).__name__}); qo-comm plans interleave comm "
            "and compute inside one program and have no stage split to "
            "re-execute"
        )
    reps = env.timeline_reps() if reps is None else reps
    inner = env.timeline_inner() if inner is None else inner
    if isinstance(axis_name, (tuple, list)):
        axis_name = tuple(axis_name)
    if plan.hier is not None and not (
        isinstance(axis_name, tuple) and len(axis_name) == 2
    ):
        raise ValueError(
            "hierarchical plan: axis_name must be the (inter, intra) mesh "
            f"axis pair the plan was built for, got {axis_name!r}"
        )
    spec = P(axis_name)
    shard = NamedSharding(mesh, spec)

    # ---- operands ---------------------------------------------------------
    if q is None:
        # typed error naming exactly what is missing (was a bare
        # assert, invisible under python -O and nameless when tripped)
        missing = [
            name
            for name, val in (
                ("num_heads", num_heads),
                ("head_dim", head_dim),
            )
            if val is None
        ]
        if missing:
            raise ValueError(
                "profile_plan_timeline: synthesizing operands (q=None) "
                f"needs num_heads=(hq, hkv) and head_dim; missing: "
                f"{', '.join(missing)}"
            )
        hq, hkv = num_heads
        dt = jnp.dtype(dtype if dtype is not None else params.out_dtype)
        total = plan.cp_size * plan.shard_q_len
        total_k = plan.cp_size * (
            shard_k_len if shard_k_len is not None else plan.shard_q_len
        )
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.standard_normal((total, hq, head_dim)), dt)
        k = jnp.asarray(rng.standard_normal((total_k, hkv, head_dim)), dt)
        v = jnp.asarray(rng.standard_normal((total_k, hkv, head_dim)), dt)
    hq, head_dim = int(q.shape[1]), int(q.shape[2])
    hkv = int(k.shape[1])
    q = jax.device_put(q, shard)
    k = jax.device_put(k, shard)
    v = jax.device_put(v, shard)

    params = ensure_kernel_steps(
        params,
        (plan.merged_tables, plan.host_tables,
         *(sp.tables for sp in plan.stages)),
    )
    calc_params = dataclasses.replace(params, has_sink=False)
    # the staged path accumulates out/lse in fp32 when forward
    # high-precision reduce is on (dist_attn_local's acc_dtype) — the
    # pieces mirror it so the serial sum prices the same numeric work
    acc_dtype = (
        "float32"
        if env.is_forward_high_precision_reduce()
        else calc_params.out_dtype
    )
    piece_params = dataclasses.replace(calc_params, out_dtype=acc_dtype)

    def put(arrs):
        return tuple(jax.device_put(jnp.asarray(a), shard) for a in arrs)

    def smap(n_in, body, n_out=1):
        f = shard_map(
            body,
            mesh=mesh,
            in_specs=(spec,) * n_in,
            out_specs=spec if n_out == 1 else (spec,) * n_out,
            check_vma=False,
        )
        return jax.jit(f)

    def cast_payload(payload, comm, comm_arrays):
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

    def make_cast_fn(comm):
        # arity follows the meta's impl layout (a2a vs per-hop arrays),
        # so each comm meta gets its own program
        nca = len(plan._comm_arrays(comm))

        def body(k_, v_, *cas):
            return cast_payload(jnp.stack([k_, v_], axis=1), comm, cas)

        return smap(2 + nca, body)

    bench_kw = dict(
        warmup=warmup, rep=reps, inner=inner,
        mesh=mesh if use_mesh_barrier else None,
    )

    def t_ms(fn, *args):
        return do_bench(fn, *args, **bench_kw).median_ms

    # ---- per-hop comm attribution ----------------------------------------
    # Each hop of a hop-scheduled cast (and each level of a hierarchical
    # one) re-traced as its OWN jitted program and timed with the same
    # do_bench discipline, so the stage cast time decomposes per hop /
    # per axis — spans land on per-hop Chrome-trace tracks and the
    # magi_hop_ms{hop=,axis=,stage=} gauges carry the numbers the
    # DCN-aware hop pricing (ROADMAP item 3) will calibrate against.
    import time as _time

    from .events import record_event

    hop_timings: list[HopTiming] = []

    # each probe is hop_cast itself with a ONE-hop list — the exact body
    # (recv layout, named scope, chaos straggler branch) the real cast
    # runs, so a slow or chaos-straggled hop shows up in ITS gauge
    def _one_hop_fn(comm, h):
        def body(k_, v_, sidx, rpos, _h=h):
            return hop_cast(
                jnp.stack([k_, v_], axis=1),
                [_h],
                (sidx, rpos),
                comm.max_recv,
                axis_name=axis_name,
                world=comm.cp_size,
            )

        return smap(4, body)

    def _one_intra_hop_fn(comm, h, intra_name):
        def body(gw_, sidx, rpos, _h=h):
            return hop_cast(
                gw_,
                [_h],
                (sidx, rpos),
                comm.max_recv,
                axis_name=intra_name,
                world=comm.n_intra,
            )

        return smap(3, body)

    def time_hops(comm, stage_label):
        # (hop label, axis label, rows/rank, fn, args) per timed program
        pieces = []
        if plan.hier is not None:
            inter_name, intra_name = axis_name
            arrays = comm.cast_device_arrays()
            inter_args = put(arrays[:3])

            def inter_body(k_, v_, sidx, rsel, rval):
                return group_cast(
                    jnp.stack([k_, v_], axis=1), sidx, rsel, rval,
                    axis_name=inter_name,
                )

            inter_fn = smap(5, inter_body)
            gw = inter_fn(k, v, *inter_args)
            pieces.append(
                ("inter", inter_name,
                 comm.n_inter * int(comm.inter_send_idx.shape[2]),
                 inter_fn, (k, v) + inter_args)
            )
            if comm.impl == "hops":
                for j, h in enumerate(comm.intra_hops):
                    hop_args = put(arrays[3 + 2 * j : 5 + 2 * j])
                    pieces.append(
                        (str(h.shift), intra_name, h.size,
                         _one_intra_hop_fn(comm, h, intra_name),
                         (gw,) + hop_args)
                    )
            else:
                intra_args = put(arrays[3:6])

                def intra_body(gw_, sidx, rsel, rval):
                    return group_cast(
                        gw_, sidx, rsel, rval, axis_name=intra_name
                    )

                pieces.append(
                    ("intra", intra_name,
                     comm.n_intra * int(comm.intra_send_idx.shape[2]),
                     smap(4, intra_body), (gw,) + intra_args)
                )
        elif comm.impl == "hops":
            for h in comm.hops:
                hop_args = put((h.send_idx, h.recv_pos))
                pieces.append(
                    (str(h.shift), str(axis_name), h.size,
                     _one_hop_fn(comm, h), (k, v) + hop_args)
                )
        for hop_label, ax, rows, fn, args in pieces:
            t0 = _time.perf_counter()
            ms = t_ms(fn, *args)
            if record:  # record=False must leave the ring buffer alone
                record_event(
                    "hop_cast",
                    t0,
                    ms * 1e-3,
                    {"stage": stage_label, "hop": hop_label, "axis": ax,
                     "rows_per_rank": rows, "ms": ms},
                    track=f"hop {hop_label} ({ax})",
                )
            hop_timings.append(
                HopTiming(
                    stage=stage_label, axis=ax, hop=hop_label,
                    rows=rows, ms=ms,
                )
            )

    predicted = _predicted_costs(
        plan,
        num_heads_q=hq,
        num_heads_kv=hkv,
        head_dim=head_dim,
        bytes_per_elt=jnp.dtype(k.dtype).itemsize,
        generation=generation,
        calc_cost_factor=calc_cost_factor,
        comm_cost_factor=comm_cost_factor,
        stage_overhead_s=stage_overhead_s,
    )
    p_host_ms = p_comm_ms = p_calc_ms = None
    predicted_total_ms = None
    if predicted is not None:
        host_s, comm_s, calc_s, total_s = predicted
        p_host_ms = host_s * 1e3
        p_comm_ms = [x * 1e3 for x in comm_s]
        p_calc_ms = [x * 1e3 for x in calc_s]
        predicted_total_ms = total_s * 1e3

    # every piece mirrors its slice of dist_attn_local exactly — kernel
    # plus the head-major -> sequence layout and (remote stages) the lse
    # merge — so the serial sum prices the same work the pipelined path
    # runs and the overlap efficiency isolates scheduling alone
    stages: list[StageTiming] = []
    if plan.overlap_degree == 0:
        comm_args = put(plan._comm_arrays(plan.merged_comm))
        tabs = put(plan.merged_tables.arrays())
        cast_fn = make_cast_fn(plan.merged_comm)

        def merged_body(q_, k_, v_, recv, *tt):
            qh = _hm(q_, plan.shard_q_pad)
            out_h, lse_h, _ = _call_kernel(
                qh,
                jnp.concatenate([k_, recv[:, 0]], axis=0),
                jnp.concatenate([v_, recv[:, 1]], axis=0),
                tt,
                plan.merged_tables.kv_pad,
                calc_params,
                None,
            )
            return _headmajor_to_seq(out_h, lse_h, plan.shard_q_len)

        calc_fn = smap(4 + 9, merged_body, n_out=2)
        recv = cast_fn(k, v, *comm_args)
        comm_ms = t_ms(cast_fn, k, v, *comm_args)
        time_hops(plan.merged_comm, "merged")
        calc_ms = t_ms(calc_fn, q, k, v, recv, *tabs)
        stages.append(
            StageTiming(
                stage="merged",
                comm_ms=comm_ms,
                calc_ms=calc_ms,
                predicted_comm_ms=p_comm_ms[0] if p_comm_ms else None,
                predicted_calc_ms=p_calc_ms[0] if p_calc_ms else None,
            )
        )
        serial_ms = comm_ms + calc_ms
        hideable_ms = comm_ms
    else:
        host_tabs = put(plan.host_tables.arrays())

        def host_body(q_, k_, v_, *tt):
            qh = _hm(q_, plan.shard_q_pad)
            out_h, lse_h, _ = _call_kernel(
                qh, k_, v_, tt, plan.host_tables.kv_pad, piece_params, None
            )
            return _headmajor_to_seq(out_h, lse_h, plan.shard_q_len)

        host_fn = smap(3 + 9, host_body, n_out=2)
        acc_out, acc_lse = host_fn(q, k, v, *host_tabs)
        host_ms = t_ms(host_fn, q, k, v, *host_tabs)
        stages.append(
            StageTiming(
                stage="host",
                comm_ms=0.0,
                calc_ms=host_ms,
                predicted_comm_ms=None,
                predicted_calc_ms=p_host_ms,
            )
        )
        serial_ms = host_ms
        hideable_ms = 0.0
        for i, sp in enumerate(plan.stages):
            comm_args = put(plan._comm_arrays(sp.comm))
            tabs = put(sp.tables.arrays())
            cast_fn = make_cast_fn(sp.comm)

            def stage_body(
                q_, out_acc, lse_acc, recv, *tt, _kv_pad=sp.tables.kv_pad
            ):
                qh = _hm(q_, plan.shard_q_pad)
                out_h, lse_h, _ = _call_kernel(
                    qh, recv[:, 0], recv[:, 1], tt, _kv_pad,
                    piece_params, None,
                )
                out_i, lse_i = _headmajor_to_seq(
                    out_h, lse_h, plan.shard_q_len
                )
                return correct_attn_out_lse(out_acc, lse_acc, out_i, lse_i)

            calc_fn = smap(4 + 9, stage_body, n_out=2)
            recv = cast_fn(k, v, *comm_args)
            comm_ms = t_ms(cast_fn, k, v, *comm_args)
            time_hops(sp.comm, str(i))
            calc_ms = t_ms(calc_fn, q, acc_out, acc_lse, recv, *tabs)
            acc_out, acc_lse = calc_fn(q, acc_out, acc_lse, recv, *tabs)
            stages.append(
                StageTiming(
                    stage=str(i),
                    comm_ms=comm_ms,
                    calc_ms=calc_ms,
                    predicted_comm_ms=p_comm_ms[i] if p_comm_ms else None,
                    predicted_calc_ms=p_calc_ms[i] if p_calc_ms else None,
                )
            )
            serial_ms += comm_ms + calc_ms
            hideable_ms += comm_ms

    # the full pipelined path — the same dist_attn_local body the real
    # runtime shard_maps, with the pieces' no-sink params, so the
    # serial-vs-pipelined delta isolates scheduling, not mask content
    device_tables = put(plan.device_tables())
    n_tab = len(device_tables)

    def full_body(q_, k_, v_, *tabs):
        out, _, _ = dist_attn_local(
            q_, k_, v_, tabs, plan, calc_params,
            axis_name=axis_name, sink=None,
        )
        return out

    full_fn = smap(3 + n_tab, full_body)
    measured_total_ms = t_ms(full_fn, q, k, v, *device_tables)

    hidden_ms = max(serial_ms - measured_total_ms, 0.0)
    efficiency = (
        min(hidden_ms / hideable_ms, 1.0) if hideable_ms > 0 else 0.0
    )
    tl = MeasuredTimeline(
        overlap_degree=plan.overlap_degree,
        cp_size=plan.cp_size,
        stages=tuple(stages),
        measured_total_ms=measured_total_ms,
        serial_total_ms=serial_ms,
        hideable_comm_ms=hideable_ms,
        overlap_efficiency=efficiency,
        predicted_total_ms=predicted_total_ms,
        prediction_error_ratio=(
            measured_total_ms / predicted_total_ms
            if predicted_total_ms
            else None
        ),
        hops=tuple(hop_timings),
    )
    if record:
        from .collectors import record_measured_timeline

        record_measured_timeline(tl)
    return tl


def profile_key_timeline(
    key=None,
    *,
    reps: int | None = None,
    inner: int | None = None,
    warmup: int = 1,
    seed: int = 0,
    use_mesh_barrier: bool = False,
    record: bool = True,
) -> MeasuredTimeline:
    """Profile the runtime planned for a :class:`DistAttnRuntimeKey`
    (default: the most recently planned key) with synthesized operands of
    the keyed shape/dtype. The measured-timeline twin of
    ``get_runtime_mgr(key).calc_attn`` — one call audits what the plan's
    overlap schedule actually delivers on the current backend."""
    from ..api import interface as api_interface
    from ..parallel.dist_attn import make_attn_params

    if key is None:
        key = api_interface.get_most_recent_key()
    mgr = api_interface.get_runtime_mgr(key)
    plan = mgr.plan
    if not hasattr(plan, "stages"):
        raise NotImplementedError(
            "profile_key_timeline supports group-cast runtimes only "
            "(qo-comm plans have no stage split to re-execute)"
        )
    _, _, head_block = api_interface._blocking_from(
        key.block_config, key.num_heads_q, key.num_heads_kv
    )
    params = make_attn_params(
        plan,
        key.head_dim,
        softcap=key.softcap,
        has_sink=False,
        out_dtype=key.out_dtype,
        interpret=key.interpret,
        head_block=head_block,
    )
    return profile_plan_timeline(
        plan,
        mgr.mesh,
        params,
        axis_name=key.cp_axis,
        num_heads=(key.num_heads_q, key.num_heads_kv),
        head_dim=key.head_dim,
        dtype=key.out_dtype,
        # cross-attn keys dispatch K/V separately; size their shard right
        shard_k_len=(
            mgr.kv_dispatch_meta.shard_seqlen
            if mgr.kv_dispatch_meta is not None
            else None
        ),
        reps=reps,
        inner=inner,
        warmup=warmup,
        seed=seed,
        use_mesh_barrier=use_mesh_barrier,
        record=record,
    )
