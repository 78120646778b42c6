"""GroupCast / GroupReduce: zero-redundancy group collectives on a mesh axis.

TPU-native re-design of the reference's two custom collectives
(comm/primitive/grpcoll/_group_collective.py:81,255 and the NVSHMEM kernels
of csrc/comm/grpcoll): identical *semantics* — each input split multicast to
a set of destination ranks (cast), partials reduced back to owner ranks with
sum/avg/lse (reduce) — realized as one of two interchangeable SPMD
implementations selected per collective (``MAGI_ATTENTION_GROUP_COLL_IMPL``):

``a2a`` (legacy): one static ``lax.all_to_all`` per call inside
``shard_map``, every (src, dst) pair padded to the GLOBAL max pair size S —

- send routing  : gather rows into a [cp, S] send buffer (SPMD requires a
  uniform shape, the moral equivalent of the reference's
  ``split_alignment`` bucketing),
- all_to_all    : rides ICI; XLA overlaps it with compute where possible,
- recv layout   : receivers select valid rows in (src_rank, send_pos) order,
- reduce        : scatter back through the transposed routing + segment
  reductions (sum / avg / LSE-weighted out+lse merge).

``hops``: a hop-scheduled exchange — for hop k in 1..cp-1, rank r trades
with rank (r±k) mod cp via ``lax.ppermute``, each hop's buffer padded only
to that hop's OWN max pair size ``max_r sizes[r, (r+k) mod cp]``; hops whose
max is zero are traced away entirely (a fully-local plan emits no
collective at all), and hop 0 (self rows) is a plain gather/scatter. Total
wire volume drops from the a2a's ``(cp-1)·S`` rows per rank to
``Σ_k max_r sizes[r, (r+k) mod cp]`` — strictly ≤, and far less on the
skewed per-pair sizes heterogeneous masks produce. The recv layout is
bit-identical to the a2a's (src-rank-major, send-pos order), so consumers
(dist_attn tables, solver CommMeta, LSE merges) cannot tell them apart.

``auto`` (default) resolves per collective at plan-build time by predicted
wire volume (see :func:`_resolve_impl`); the choice and its reason are
recorded as a telemetry gauge.

All routing is captured host-side in padded numpy index arrays (per unique
mask, cached with the runtime key). No WorkWithPostProcessFn-style handle is
needed: XLA's async scheduling replaces the reference's stream/event
plumbing.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry

NEG_INF = float("-inf")

# auto-mode volume bar: hop scheduling is picked when its scheduled rows
# fall strictly below this fraction of the a2a's `cp * max_send` buffer —
# the saving must beat more than the a2a's own (locally-copied) self chunk
# to justify cp-1 dependent ppermutes in place of one fused all_to_all
# (which XLA pipelines internally). Near-uniform pair sizes (dense causal
# over an even shard) stay on a2a; the skewed maps of varlen / SWA /
# block-sparse masks clear the bar by a wide margin.
AUTO_HOPS_MAX_VOLUME_FRACTION = 0.75


def _round_up_to(a: int, b: int) -> int:
    return -(-a // b) * b


def _pair_sizes(send_map) -> np.ndarray:
    """[cp, cp] int64: rows each (src, dst) pair moves."""
    cp = len(send_map)
    sizes = np.zeros((cp, cp), dtype=np.int64)
    for s in range(cp):
        assert len(send_map[s]) == cp
        for d in range(cp):
            sizes[s, d] = len(send_map[s][d])
    return sizes


def _hop_padded_sizes(
    sizes: np.ndarray, pad_to: int
) -> list[tuple[int, int]]:
    """Active hops of a send-size matrix: [(shift, padded Sk)] for every
    hop k in 0..cp-1 whose max pair size ``max_r sizes[r, (r+k) % cp]``
    is nonzero (hop 0 = self rows, a local copy)."""
    cp = sizes.shape[0]
    out = []
    for k in range(cp):
        m = int(max(sizes[r, (r + k) % cp] for r in range(cp)))
        if m:
            out.append((k, _round_up_to(m, pad_to)))
    return out


def _scheduled_rows(hop_specs, cp: int, max_send: int) -> tuple[int, int]:
    """(hops_scheduled, a2a_scheduled) rows per rank: the per-hop padded
    sums over wire-crossing hops (hop 0 is a local copy) vs the full
    ``cp * max_send`` buffer the globally-padded a2a allocates and
    ships."""
    hops_sched = sum(sz for k, sz in hop_specs if k % cp != 0)
    return hops_sched, cp * max_send


def _resolve_impl(
    impl: str, hop_specs, cp: int, max_send: int
) -> tuple[str, str]:
    """Resolve 'auto' to a concrete impl by predicted scheduled volume;
    returns (impl, reason). Strictly-below-threshold keeps near-uniform
    maps (where hop scheduling saves only the a2a's self chunk) on the
    single fused a2a."""
    from .. import env

    if impl not in env.GROUP_COLL_IMPLS:
        raise ValueError(
            f"MAGI_ATTENTION_GROUP_COLL_IMPL={impl!r} is not one of "
            f"{env.GROUP_COLL_IMPLS}"
        )
    if impl != "auto":
        return impl, "env_pinned"
    hops_sched, a2a_sched = _scheduled_rows(hop_specs, cp, max_send)
    if hops_sched == 0:
        # nothing crosses the wire: hops trace NO collective at all
        return "hops", "auto_zero_volume"
    if hops_sched < AUTO_HOPS_MAX_VOLUME_FRACTION * a2a_sched:
        return "hops", "auto_volume"
    return "a2a", "auto_near_uniform"


def predicted_volume_ratio(
    send_map, pad_to: int | None = None, impl: str | None = None
) -> tuple[float, str]:
    """(scheduled_rows / true_rows, resolved impl) that
    :meth:`GroupCollectiveMeta.build` would produce for this send map —
    sizes math only, no routing arrays. The overlap solver prices stage
    comm with this ratio so the timeline model sees the volume the
    selected impl will actually move, not the true-row lower bound and
    not the a2a's global-pad upper bound."""
    from .. import env

    if pad_to is None:
        pad_to = env.comm_pad_to()
    if impl is None:
        impl = env.group_coll_impl()
    sizes = _pair_sizes(send_map)
    cp = sizes.shape[0]
    S = _round_up_to(max(int(sizes.max()), 1), pad_to)
    hop_specs = _hop_padded_sizes(sizes, pad_to)
    resolved, _ = _resolve_impl(impl, hop_specs, cp, S)
    true_rows = int(sizes.sum())
    if resolved == "hops":
        scheduled = cp * sum(sz for k, sz in hop_specs if k % cp != 0)
    else:
        scheduled = cp * cp * S
    if true_rows == 0:
        return (1.0 if scheduled == 0 else float(scheduled)), resolved
    return scheduled / true_rows, resolved


@dataclasses.dataclass(frozen=True, eq=False)
class HopPlan:
    """One hop of the hop-scheduled collective: rank r exchanges with
    rank (r + shift) mod cp, buffer padded to this hop's own max pair
    size. ``shift == 0`` is the self hop (local gather/scatter, no
    collective)."""

    shift: int
    size: int  # Sk: padded rows this hop moves per rank
    send_idx: np.ndarray  # [cp, Sk] int32: [src, pos] -> src-local row
    recv_pos: np.ndarray  # [cp, Sk] int32: [dst, pos] -> recv-buffer row
    # (pads -> max_recv trash slot)
    seg_ids: np.ndarray  # [cp, Sk] int32: [owner, pos] -> owner row
    # (pads -> num_local_rows sentinel, contributes zero to the reduce)


@dataclasses.dataclass(frozen=True, eq=False)
class GroupCollectiveMeta:
    """Host-side routing plan for one group_cast (and its reverse reduce).

    Built from ``send_map[src][dst] = local row indices`` (numpy) via
    :meth:`build`. The stacked arrays have a leading cp axis so that, placed
    in device memory sharded on the cp mesh axis, each rank reads exactly its
    own routing row inside shard_map.
    """

    cp_size: int
    max_send: int  # S: rows any rank sends to any one peer (padded)
    max_recv: int  # R: output rows any rank receives (padded)
    send_total: tuple[int, ...]  # valid send rows per rank (diagnostics)
    recv_total: tuple[int, ...]  # valid recv rows per rank

    send_idx: np.ndarray  # [cp, cp, S] int32: [src, dst, pos] -> src-local row
    recv_sel: np.ndarray  # [cp, R] int32: [dst, out_pos] -> flat (src*S+pos)
    recv_valid: np.ndarray  # [cp, R] bool: out_pos < recv_total[dst]
    seg_ids: np.ndarray  # [cp, cp, S] int32: [owner, src, pos] -> owner row
    # (pad positions -> num_segments sentinel, dropped by the reduce)

    # hop-scheduled realization (ISSUE 5): built when the resolved impl is
    # 'hops'; same recv layout, per-hop exact-size buffers
    pad_to: int = 8
    impl: str = "a2a"
    impl_reason: str = "legacy"
    hops: tuple[HopPlan, ...] = ()
    local_rows_total: int = 0  # self-pair (src == dst) rows, never on wire

    @staticmethod
    def build(
        send_map: Sequence[Sequence[np.ndarray]],
        num_local_rows: Sequence[int],
        pad_to: int | None = None,
        impl: str | None = None,
    ) -> "GroupCollectiveMeta":
        """``send_map[src][dst]``: int array of src-local rows sent src->dst.

        ``num_local_rows[rank]``: rank's local row count (segment count for
        the reverse reduce). Output layout at each dst: concatenation over
        src ranks (rank order) of received rows (send order) — the a2av
        convention the solver's CommMeta is built around, preserved
        bit-identically by both impls.

        ``pad_to`` defaults to ``MAGI_ATTENTION_COMM_PAD_TO`` and ``impl``
        to ``MAGI_ATTENTION_GROUP_COLL_IMPL`` ('auto' resolves here, by
        predicted wire volume).
        """
        from .. import env

        if pad_to is None:
            pad_to = env.comm_pad_to()
        if impl is None:
            impl = env.group_coll_impl()
        cp = len(send_map)
        sizes = _pair_sizes(send_map)
        S = max(int(sizes.max()), 1)
        S = -(-S // pad_to) * pad_to
        recv_tot = sizes.sum(axis=0)  # rows arriving at each dst
        R = max(int(recv_tot.max()), 1)
        R = -(-R // pad_to) * pad_to

        send_idx = np.zeros((cp, cp, S), dtype=np.int32)
        # pad positions point at the trash slot cp*S (one past the real flat
        # recv buffer) so reverse scatters cannot clobber real rows
        recv_sel = np.full((cp, R), cp * S, dtype=np.int32)
        recv_valid = np.zeros((cp, R), dtype=bool)
        seg_ids = np.full((cp, cp, S), 0, dtype=np.int32)
        for s in range(cp):
            for d in range(cp):
                idx = np.asarray(send_map[s][d], dtype=np.int32).reshape(-1)
                assert (idx < num_local_rows[s]).all() if idx.size else True
                send_idx[s, d, : idx.size] = idx
                # reverse direction: rows owner s sent to d come back from d;
                # at owner s, recv row (d, pos) reduces into local row idx[pos]
                seg_ids[s, d, : idx.size] = idx
                seg_ids[s, d, idx.size :] = num_local_rows[s]  # drop sentinel
        for d in range(cp):
            pos = 0
            for s in range(cp):
                n = int(sizes[s, d])
                recv_sel[d, pos : pos + n] = s * S + np.arange(n)
                recv_valid[d, pos : pos + n] = True
                pos += n

        hop_specs = _hop_padded_sizes(sizes, pad_to)
        impl_resolved, reason = _resolve_impl(impl, hop_specs, cp, S)
        hops: tuple[HopPlan, ...] = ()
        if impl_resolved == "hops":
            try:
                from ..resilience import chaos

                chaos.maybe_fail("hops_build_error")
                # dst-side segment offsets of the (src-rank-major) recv
                # layout
                offsets = np.zeros((cp, cp), dtype=np.int64)
                offsets[1:] = np.cumsum(sizes, axis=0)[:-1]  # [src, dst]
                plans = []
                for k, Sk in hop_specs:
                    h_send = np.zeros((cp, Sk), dtype=np.int32)
                    h_recv = np.full((cp, Sk), R, dtype=np.int32)
                    h_seg = np.zeros((cp, Sk), dtype=np.int32)
                    for r in range(cp):
                        d = (r + k) % cp
                        idx = np.asarray(
                            send_map[r][d], dtype=np.int32
                        ).reshape(-1)
                        h_send[r, : idx.size] = idx
                        h_seg[r, : idx.size] = idx
                        h_seg[r, idx.size :] = num_local_rows[r]
                    for d in range(cp):
                        s = (d - k) % cp
                        n = int(sizes[s, d])
                        h_recv[d, :n] = offsets[s, d] + np.arange(n)
                    plans.append(
                        HopPlan(
                            shift=k,
                            size=Sk,
                            send_idx=h_send,
                            recv_pos=h_recv,
                            seg_ids=h_seg,
                        )
                    )
                hops = tuple(plans)
            except Exception as exc:  # noqa: BLE001 — degradation path
                # graceful degradation (ISSUE 8): a failed hop-schedule
                # construction falls back to the always-available
                # globally-padded a2a realization (correct, just more
                # wire volume) — recorded, never silent
                telemetry.record_degraded_path("hops_build_error")
                from ..telemetry.logger import get_logger

                get_logger("resilience").warning(
                    "hop-schedule build failed (%s: %s) — degrading "
                    "this collective to the a2a impl",
                    type(exc).__name__,
                    exc,
                )
                impl_resolved, reason = "a2a", "degraded_hops_build_error"
                hops = ()
        meta = GroupCollectiveMeta(
            cp_size=cp,
            max_send=S,
            max_recv=R,
            send_total=tuple(int(x) for x in sizes.sum(axis=1)),
            recv_total=tuple(int(x) for x in recv_tot),
            send_idx=send_idx,
            recv_sel=recv_sel,
            recv_valid=recv_valid,
            seg_ids=seg_ids,
            pad_to=pad_to,
            impl=impl_resolved,
            impl_reason=reason,
            hops=hops,
            local_rows_total=int(np.trace(sizes)),
        )
        telemetry.record_group_collective_build(meta)
        return meta

    # device-array views (leading cp axis -> shard over the cp mesh axis)
    def device_args(self):
        return (
            jnp.asarray(self.send_idx),
            jnp.asarray(self.recv_sel),
            jnp.asarray(self.recv_valid),
            jnp.asarray(self.seg_ids),
        )

    # ---- volume accounting (rows; the interface layer resolves bytes) ----

    @property
    def padded_rows_per_rank(self) -> int:
        """Legacy a2a payload rows per rank (`cp * max_send`): what the
        globally-padded all_to_all ships regardless of impl choice."""
        return self.cp_size * self.max_send

    @property
    def comm_bytes_per_rank(self) -> int:
        """Padded all-to-all payload rows (volume accounting, per element).

        Back-compat alias of :attr:`padded_rows_per_rank`; prefer
        :attr:`scheduled_rows_per_rank` for what the selected impl will
        actually move."""
        return self.padded_rows_per_rank

    @property
    def scheduled_rows_per_rank(self) -> int:
        """Payload rows per rank the SELECTED impl schedules: the full
        ``cp * max_send`` buffer for a2a, the sum of per-hop padded sizes
        over wire-crossing hops (shift != 0) for hop scheduling."""
        if self.impl == "hops":
            return sum(
                h.size for h in self.hops if h.shift % self.cp_size != 0
            )
        return self.padded_rows_per_rank

    @property
    def true_rows_total(self) -> int:
        """Real routed rows across the group (no padding)."""
        return sum(self.send_total)

    @property
    def scheduled_rows_total(self) -> int:
        return self.cp_size * self.scheduled_rows_per_rank

    @property
    def padding_overhead_ratio(self) -> float:
        """Group-wide scheduled rows / true rows ON THE PAIRS THE IMPL
        SCHEDULES (>= 1.0 when anything is scheduled; 0.0 otherwise):
        pure padding waste of the selected impl. The a2a buffer carries
        every pair including self rows; hop scheduling moves self rows
        by local copy, so its base excludes them — cross-impl volume is
        compared via :attr:`scheduled_rows_per_rank`, not this ratio."""
        base = self.true_rows_total
        if self.impl == "hops":
            base -= self.local_rows_total
        return (self.scheduled_rows_total / base) if base else 0.0

    # ---- per-impl device array layouts ----------------------------------
    # The plan's flattened operand stream ships exactly these, in this
    # order; consumers (dist_attn_local, qo_comm_attn_local) count
    # via num_cast_arrays / num_reduce_arrays.

    def cast_device_arrays(self) -> tuple[np.ndarray, ...]:
        """Arrays the cast (and its AD transpose) needs: a2a ->
        (send_idx, recv_sel, recv_valid); hops -> (send_idx, recv_pos)
        per active hop."""
        if self.impl == "hops":
            out: list[np.ndarray] = []
            for h in self.hops:
                out += [h.send_idx, h.recv_pos]
            return tuple(out)
        return (self.send_idx, self.recv_sel, self.recv_valid)

    def reduce_device_arrays(self) -> tuple[np.ndarray, ...]:
        """Superset layout for casts plus explicit reduces: a2a ->
        (send_idx, recv_sel, recv_valid, seg_ids); hops ->
        (send_idx, recv_pos, seg_ids) per active hop."""
        if self.impl == "hops":
            out: list[np.ndarray] = []
            for h in self.hops:
                out += [h.send_idx, h.recv_pos, h.seg_ids]
            return tuple(out)
        return (self.send_idx, self.recv_sel, self.recv_valid, self.seg_ids)

    @property
    def num_cast_arrays(self) -> int:
        return 2 * len(self.hops) if self.impl == "hops" else 3

    @property
    def num_reduce_arrays(self) -> int:
        return 3 * len(self.hops) if self.impl == "hops" else 4


def group_cast(
    x: jax.Array,  # [T_local, ...] rank-local rows (inside shard_map)
    send_idx: jax.Array,  # [1, cp, S] this rank's routing row
    recv_sel: jax.Array,  # [1, R]
    recv_valid: jax.Array,  # [1, R]
    *,
    axis_name: str,
):
    """Multicast local rows to their destination set; returns [R, ...] rows
    in (src_rank, send_pos) order (padded rows zeroed)."""
    from ..utils.instrument import named_scope

    with named_scope("magi_group_cast"):
        si = send_idx[0]  # [cp, S]
        send_buf = jnp.take(x, si.reshape(-1), axis=0).reshape(
            si.shape + x.shape[1:]
        )  # [cp, S, ...]
        recv = jax.lax.all_to_all(
            send_buf, axis_name, split_axis=0, concat_axis=0, tiled=False
        )  # [cp, S, ...]
        flat = recv.reshape((-1,) + x.shape[1:])
        # pad entries of recv_sel point one past the end; clip + mask out
        out = jnp.take(
            flat, jnp.minimum(recv_sel[0], flat.shape[0] - 1), axis=0
        )
        mask_shape = (out.shape[0],) + (1,) * (out.ndim - 1)
        return jnp.where(recv_valid[0].reshape(mask_shape), out, 0)


def _reverse_a2a(y, recv_sel, recv_valid, cp, S, axis_name):
    """Scatter partial rows back through the transposed cast routing.

    Returns [cp, S, ...]: rows that each peer sent back to me, in my original
    send order (= my cast send_idx positions).
    """
    from ..utils.instrument import named_scope

    with named_scope("magi_group_reduce_a2a"):
        flat = jnp.zeros((cp * S + 1,) + y.shape[1:], dtype=y.dtype)
        mask_shape = (y.shape[0],) + (1,) * (y.ndim - 1)
        y_masked = jnp.where(recv_valid[0].reshape(mask_shape), y, 0)
        flat = flat.at[recv_sel[0]].set(y_masked)  # pads -> trash slot
        send_back = flat[:-1].reshape((cp, S) + y.shape[1:])
        return jax.lax.all_to_all(
            send_back, axis_name, split_axis=0, concat_axis=0, tiled=False
        )


def group_reduce_sum(
    y: jax.Array,  # [R, ...] partial rows (layout of group_cast output)
    acc: jax.Array,  # [T_local, ...] buffer to accumulate into
    send_idx_unused,  # kept for signature symmetry
    recv_sel: jax.Array,
    recv_valid: jax.Array,
    seg_ids: jax.Array,  # [1, cp, S]
    *,
    axis_name: str,
    average: bool = False,
    counts: jax.Array | None = None,  # [T_local] contributions per row (avg)
):
    """Reduce partials back onto owner rows: acc += segment_sum(partials)."""
    from ..utils.instrument import named_scope

    with named_scope("magi_group_reduce_sum"):
        cp, S = seg_ids.shape[1], seg_ids.shape[2]
        recv = _reverse_a2a(y, recv_sel, recv_valid, cp, S, axis_name)
        flat = recv.reshape((cp * S,) + y.shape[1:])
        T = acc.shape[0]
        seg = seg_ids[0].reshape(-1)
        contrib = jax.ops.segment_sum(flat, seg, num_segments=T + 1)[:T]
        if average:
            assert counts is not None
            denom = jnp.maximum(counts, 1).reshape(
                (T,) + (1,) * (acc.ndim - 1)
            )
            return acc + contrib.astype(acc.dtype) / denom.astype(acc.dtype)
        return acc + contrib.astype(acc.dtype)


def group_reduce_lse(
    out_partial: jax.Array,  # [R, h, d] partial attention outputs
    lse_partial: jax.Array,  # [R, h] partial lse (NEG_INF where invalid)
    out_acc: jax.Array,  # [T, h, d] local partial out
    lse_acc: jax.Array,  # [T, h] local partial lse
    recv_sel: jax.Array,
    recv_valid: jax.Array,
    seg_ids: jax.Array,
    *,
    axis_name: str,
):
    """LSE-weighted merge of remote partial (out, lse) onto owner rows.

    The distributed-attention correction (reference functional/utils.py
    correct_attn_out/lse + range_reduce lse op): for contributions i with
    (out_i, lse_i):  lse = log Σ exp(lse_i),  out = Σ exp(lse_i - lse) out_i.
    Rows nobody contributed to keep (out_acc, lse_acc).
    """
    from ..utils.instrument import named_scope

    cp, S = seg_ids.shape[1], seg_ids.shape[2]
    # mark invalid rows with -inf lse so they vanish from the merge
    lse_masked = jnp.where(recv_valid[0], lse_partial.T, NEG_INF).T  # [R, h]
    recv_out = _reverse_a2a(out_partial, recv_sel, recv_valid, cp, S, axis_name)
    # lse travels alongside; -inf encodes "no contribution"
    flat_lse = jnp.full(
        (cp * S + 1,) + lse_partial.shape[1:], NEG_INF, lse_partial.dtype
    )
    flat_lse = flat_lse.at[recv_sel[0]].set(lse_masked)
    with named_scope("magi_group_reduce_lse_a2a"):
        recv_lse = jax.lax.all_to_all(
            flat_lse[:-1].reshape((cp, S) + lse_partial.shape[1:]),
            axis_name,
            split_axis=0,
            concat_axis=0,
            tiled=False,
        )
    T = out_acc.shape[0]
    seg = seg_ids[0].reshape(-1)
    flat_out = recv_out.reshape((cp * S,) + out_partial.shape[1:])
    flat_lse = recv_lse.reshape((cp * S,) + lse_partial.shape[1:])

    # segment-logsumexp including the local accumulator as one contribution
    m_remote = jax.ops.segment_max(flat_lse, seg, num_segments=T + 1)[:T]
    m = jnp.maximum(m_remote, lse_acc)  # [T, h]
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    w_remote = jnp.exp(flat_lse - m_safe[seg.clip(0, T - 1)])
    # zero out sentinel rows (seg == T) explicitly
    w_remote = jnp.where((seg < T)[:, None], w_remote, 0.0)
    w_remote = jnp.where(jnp.isneginf(flat_lse), 0.0, w_remote)
    l_remote = jax.ops.segment_sum(w_remote, seg, num_segments=T + 1)[:T]
    l_local = jnp.where(
        jnp.isneginf(lse_acc), 0.0, jnp.exp(lse_acc - m_safe)
    )
    l_tot = l_remote + l_local  # [T, h]
    lse_new = jnp.where(l_tot > 0, m_safe + jnp.log(jnp.maximum(l_tot, 1e-38)), NEG_INF)

    out_remote = jax.ops.segment_sum(
        w_remote[..., None] * flat_out.astype(jnp.float32),
        seg,
        num_segments=T + 1,
    )[:T]
    out_new = out_remote + l_local[..., None] * out_acc.astype(jnp.float32)
    denom = jnp.where(l_tot > 0, l_tot, 1.0)[..., None]
    return (out_new / denom).astype(out_acc.dtype), lse_new


# ---------------------------------------------------------------------------
# hop-scheduled implementation (ISSUE 5)
# ---------------------------------------------------------------------------


def _hop_perm(world: int, shift: int):
    return [(r, (r + shift) % world) for r in range(world)]


def _hop_groups(hops, arrays):
    """Split the flat per-rank array tuple into per-hop groups. Accepts
    both the cast layout (stride 2: send_idx, recv_pos) and the reduce
    layout (stride 3: + seg_ids)."""
    n = len(hops)
    assert n and len(arrays) % n == 0, (len(arrays), n)
    stride = len(arrays) // n
    assert stride in (2, 3), stride
    return [arrays[i * stride : (i + 1) * stride] for i in range(n)]


def hop_cast(
    x: jax.Array,  # [T_local, ...] rank-local rows (inside shard_map)
    hops: Sequence[HopPlan],
    arrays,  # flat per-rank routing slices (leading dim 1), stride 2 or 3
    max_recv: int,
    *,
    axis_name,
    world: int,
):
    """Hop-scheduled multicast: bit-identical recv layout to
    :func:`group_cast`, wire volume = sum of per-hop padded maxima. Each
    hop is one ``lax.ppermute`` (hop 0 / shift 0 is a local copy, no
    collective); an empty hop list traces nothing at all."""
    from ..utils.instrument import named_scope

    from ..resilience import chaos

    straggle = chaos.enabled()
    with named_scope("magi_group_cast"):
        out = jnp.zeros((max_recv + 1,) + x.shape[1:], x.dtype)
        if hops:
            for hop, grp in zip(hops, _hop_groups(hops, arrays)):
                send_idx, recv_pos = grp[0][0], grp[1][0]  # [Sk]
                buf = jnp.take(x, send_idx, axis=0)
                if hop.shift % world != 0:
                    buf = jax.lax.ppermute(
                        buf, axis_name, _hop_perm(world, hop.shift)
                    )
                if straggle:
                    # injectable straggler: a serialization loop on the
                    # chosen hop (bit-transparent to the payload)
                    buf = chaos.straggler_delay(buf, hop.shift)
                # pads point at the trash slot max_recv; real rows land at
                # their (src-rank-major, send-pos) position. Indices are
                # unique except the pads' shared trash slot, whose primal
                # is sliced off below and whose cotangent is therefore
                # zero — declaring uniqueness keeps the scatter linearly
                # TRANSPOSABLE (group_reduce_hier runs the hier reduce as
                # jax.linear_transpose of this cast; without it the hops
                # intra level dies in scatter's transpose rule)
                out = out.at[recv_pos].set(buf, unique_indices=True)
        return out[:max_recv]


def _hop_reverse(
    y: jax.Array,  # [R, ...] partial rows in cast-output layout
    hops,
    groups,
    max_recv: int,
    *,
    axis_name,
    world: int,
    neg_inf_fill: bool = False,
):
    """Reverse every hop: gather each hop's rows out of the partial
    buffer, mask pads (0, or -inf for lse payloads), ppermute back to the
    owner. Yields (rows [Sk, ...], seg [Sk]) per hop — rows arrive at the
    owner in its original send order, so ``seg`` (= the hop's send_idx
    with a pad sentinel) maps them onto owner rows."""
    from ..utils.instrument import named_scope

    out = []
    for hop, grp in zip(hops, groups):
        recv_pos, seg = grp[1][0], grp[2][0]
        valid = recv_pos < max_recv
        rows = jnp.take(y, jnp.minimum(recv_pos, max_recv - 1), axis=0)
        mask_shape = (rows.shape[0],) + (1,) * (rows.ndim - 1)
        fill = NEG_INF if neg_inf_fill else 0
        rows = jnp.where(valid.reshape(mask_shape), rows, fill)
        if hop.shift % world != 0:
            with named_scope("magi_hop_reverse"):
                rows = jax.lax.ppermute(
                    rows, axis_name, _hop_perm(world, -hop.shift)
                )
        out.append((rows, seg))
    return out


def hop_reduce_sum(
    y: jax.Array,
    acc: jax.Array,
    hops,
    arrays,  # reduce layout (stride 3)
    max_recv: int,
    *,
    axis_name,
    world: int,
    average: bool = False,
    counts: jax.Array | None = None,
):
    """Hop-scheduled :func:`group_reduce_sum`: acc += segment sums of the
    reversed hops (same per-contribution math, wire volume = hop sizes)."""
    from ..utils.instrument import named_scope

    with named_scope("magi_group_reduce_sum"):
        T = acc.shape[0]
        contrib = jnp.zeros((T,) + y.shape[1:], y.dtype)
        if hops:
            groups = _hop_groups(hops, arrays)
            for rows, seg in _hop_reverse(
                y, hops, groups, max_recv, axis_name=axis_name, world=world
            ):
                contrib = contrib + jax.ops.segment_sum(
                    rows, seg, num_segments=T + 1
                )[:T]
        if average:
            assert counts is not None
            denom = jnp.maximum(counts, 1).reshape(
                (T,) + (1,) * (acc.ndim - 1)
            )
            return acc + contrib.astype(acc.dtype) / denom.astype(acc.dtype)
        return acc + contrib.astype(acc.dtype)


def hop_reduce_lse(
    out_partial: jax.Array,  # [R, h, d]
    lse_partial: jax.Array,  # [R, h]
    out_acc: jax.Array,  # [T, h, d]
    lse_acc: jax.Array,  # [T, h]
    hops,
    arrays,  # reduce layout (stride 3)
    max_recv: int,
    *,
    axis_name,
    world: int,
):
    """Hop-scheduled :func:`group_reduce_lse`: the same two-pass segment
    logsumexp (max, then weighted sums) over the reversed hops' rows, so
    the merge math matches the a2a path contribution-for-contribution."""
    T = out_acc.shape[0]
    if not hops:
        return out_acc, lse_acc
    groups = _hop_groups(hops, arrays)
    rec_out = _hop_reverse(
        out_partial, hops, groups, max_recv, axis_name=axis_name, world=world
    )
    rec_lse = _hop_reverse(
        lse_partial,
        hops,
        groups,
        max_recv,
        axis_name=axis_name,
        world=world,
        neg_inf_fill=True,
    )
    # pass 1: per-owner-row max over every remote contribution + local
    m_remote = jnp.full(lse_acc.shape, NEG_INF, lse_partial.dtype)
    for (lse_k, seg) in rec_lse:
        m_remote = jnp.maximum(
            m_remote,
            jax.ops.segment_max(lse_k, seg, num_segments=T + 1)[:T],
        )
    m = jnp.maximum(m_remote, lse_acc)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    # pass 2: weights and weighted sums, segment-accumulated per hop
    l_remote = jnp.zeros(lse_acc.shape, jnp.float32)
    out_remote = jnp.zeros(
        (T,) + out_partial.shape[1:], jnp.float32
    )
    for (out_k, seg), (lse_k, _) in zip(rec_out, rec_lse):
        w = jnp.exp(lse_k - m_safe[seg.clip(0, T - 1)])
        w = jnp.where((seg < T)[:, None], w, 0.0)
        w = jnp.where(jnp.isneginf(lse_k), 0.0, w)
        l_remote = l_remote + jax.ops.segment_sum(
            w, seg, num_segments=T + 1
        )[:T]
        out_remote = out_remote + jax.ops.segment_sum(
            w[..., None] * out_k.astype(jnp.float32),
            seg,
            num_segments=T + 1,
        )[:T]
    l_local = jnp.where(jnp.isneginf(lse_acc), 0.0, jnp.exp(lse_acc - m_safe))
    l_tot = l_remote + l_local
    lse_new = jnp.where(
        l_tot > 0, m_safe + jnp.log(jnp.maximum(l_tot, 1e-38)), NEG_INF
    )
    out_new = out_remote + l_local[..., None] * out_acc.astype(jnp.float32)
    denom = jnp.where(l_tot > 0, l_tot, 1.0)[..., None]
    return (out_new / denom).astype(out_acc.dtype), lse_new


# ---------------------------------------------------------------------------
# impl dispatchers: one call site per collective kind, routed by meta.impl
# ---------------------------------------------------------------------------


def group_cast_m(
    x: jax.Array,
    meta: "GroupCollectiveMeta",
    arrays,  # per-rank slices of meta.cast_device_arrays() (or reduce_)
    *,
    axis_name,
):
    """Multicast through the meta's selected impl. ``arrays`` may be the
    cast or the reduce layout (the hop stride / a2a prefix adapts)."""
    if meta.impl == "hops":
        out = hop_cast(
            x,
            meta.hops,
            arrays,
            meta.max_recv,
            axis_name=axis_name,
            world=meta.cp_size,
        )
    else:
        send_idx, recv_sel, recv_valid = arrays[:3]
        out = group_cast(
            x, send_idx, recv_sel, recv_valid, axis_name=axis_name
        )
    from ..resilience import chaos

    if chaos.enabled():
        # injectable wire corruption: faults land on the recv buffer,
        # the exact surface a corrupted comm payload would poison
        out = chaos.corrupt_cast_payload(out, axis_name=axis_name)
    return out


def group_reduce_sum_m(
    y: jax.Array,
    acc: jax.Array,
    meta: "GroupCollectiveMeta",
    arrays,  # per-rank slices of meta.reduce_device_arrays()
    *,
    axis_name,
    average: bool = False,
    counts: jax.Array | None = None,
):
    telemetry.record_comm_op(meta, "reduce_sum")
    from ..resilience import chaos

    if chaos.enabled():
        y = chaos.corrupt_reduce_payload(y, axis_name=axis_name)
    if meta.impl == "hops":
        return hop_reduce_sum(
            y,
            acc,
            meta.hops,
            arrays,
            meta.max_recv,
            axis_name=axis_name,
            world=meta.cp_size,
            average=average,
            counts=counts,
        )
    send_idx, recv_sel, recv_valid, seg_ids = arrays[:4]
    return group_reduce_sum(
        y,
        acc,
        send_idx,
        recv_sel,
        recv_valid,
        seg_ids,
        axis_name=axis_name,
        average=average,
        counts=counts,
    )


def group_reduce_lse_m(
    out_partial: jax.Array,
    lse_partial: jax.Array,
    out_acc: jax.Array,
    lse_acc: jax.Array,
    meta: "GroupCollectiveMeta",
    arrays,  # per-rank slices of meta.reduce_device_arrays()
    *,
    axis_name,
):
    telemetry.record_comm_op(meta, "reduce_lse")
    from ..resilience import chaos, guards

    if chaos.enabled():
        out_partial = chaos.corrupt_reduce_payload(
            out_partial, axis_name=axis_name
        )
        lse_partial = chaos.corrupt_reduce_payload(
            lse_partial, axis_name=axis_name
        )
    # repair-mode containment: a poisoned partial row merges as a no-op
    # (lse -> -inf drops it from the segment logsumexp exactly); check
    # detection is owned by the callers that thread an error code
    out_partial, lse_partial = guards.quarantine_if_repair(
        out_partial, lse_partial, "reduce_lse"
    )
    if meta.impl == "hops":
        return hop_reduce_lse(
            out_partial,
            lse_partial,
            out_acc,
            lse_acc,
            meta.hops,
            arrays,
            meta.max_recv,
            axis_name=axis_name,
            world=meta.cp_size,
        )
    _, recv_sel, recv_valid, seg_ids = arrays[:4]
    return group_reduce_lse(
        out_partial,
        lse_partial,
        out_acc,
        lse_acc,
        recv_sel,
        recv_valid,
        seg_ids,
        axis_name=axis_name,
    )


@dataclasses.dataclass(frozen=True)
class GrpCollConfig:
    """API-parity shim of the reference's NVSHMEM group-collective tuning
    config (comm/primitive/grpcoll/_config.py:44: SM counts and
    NVLink/RDMA chunk+buffer sizing for its hand-written device kernels).
    On TPU the group collectives are XLA ``all_to_all``s whose buffers
    the compiler sizes and schedules, so every field is accepted for
    drop-in imports and none has any effect."""

    num_sms: int = 24
    nvl_chunk_size: int = 8
    nvl_buffer_size: int = 256
    rdma_chunk_size: int = 16
    rdma_buffer_size: int = 128
    num_nvl_bytes: int = int(2e9)
    num_rdma_bytes: int = 0
