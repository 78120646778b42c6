"""dispatch / undispatch: move tensors between global and CP-sharded layouts.

Role of reference ``functional/dispatch.py``: the forward dispatch selects
each rank's chunks (a pure permutation — communication-free given the
replicated input convention), undispatch is the inverse permutation (an
all-gather in SPMD). We express both as global gathers under jit and let
GSPMD insert the collectives — the XLA-idiomatic form of the reference's
autograd Function pair (dispatch bwd = all-gather-v, undispatch bwd =
reduce-scatter fall out of gather transposition automatically).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..meta.dispatch_meta import DispatchMeta


def dispatch(
    x: jax.Array, meta: DispatchMeta, axis: int = 0, pad_value=0
) -> jax.Array:
    """Permute the global tensor into dispatch order (rank-major chunks).

    Shard the result on the cp mesh axis along ``axis`` to realize the
    rank-local layout; position ids follow meta.position_ids(rank).
    Uneven shard: pad slots (sentinel indices) gather ``pad_value``.
    """
    perm = jnp.asarray(meta.perm_idx)
    if meta.is_uneven:
        return jnp.take(
            x, perm, axis=axis, mode="fill", fill_value=pad_value
        )
    return jnp.take(x, perm, axis=axis)


def undispatch(y: jax.Array, meta: DispatchMeta, axis: int = 0) -> jax.Array:
    """Inverse of :func:`dispatch` (back to natural global order)."""
    unperm = jnp.asarray(meta.unperm_idx)
    return jnp.take(y, unperm, axis=axis)


def position_ids(meta: DispatchMeta) -> jax.Array:
    """Global position of every dispatched slot, [cp*shard] int32 (sharded
    the same way as dispatched activations; used for RoPE etc.). Pad slots
    of an uneven shard read position 0 (their values are never consumed)."""
    perm = meta.perm_idx
    if meta.is_uneven:
        perm = np.where(perm < meta.total_seqlen, perm, 0).astype(np.int32)
    return jnp.asarray(perm)


def padded_dispatch_indices(
    meta: DispatchMeta, canon_to_real: np.ndarray, real_total: int
) -> np.ndarray:
    """Composite gather for the bucketed-plan adapter (ISSUE 20,
    docs/plan_reuse.md): ``dispatched[slot] = x[idx[slot]]`` maps a
    request's TRUE rows straight into the canonical (bucketed) plan's
    dispatch layout. Bucket-pad rows and uneven-shard pad slots both
    carry the sentinel ``real_total`` — gather with
    ``mode="fill"``, exactly the existing trash-slot convention.

    ``canon_to_real`` maps canonical global positions to real positions
    (-1 on pad rows); canonical chunk-pad tail rows (beyond its length)
    are pad too.
    """
    if canon_to_real.shape[0] > meta.total_seqlen:
        raise ValueError(
            f"canon_to_real has {canon_to_real.shape[0]} rows but the "
            f"canonical dispatch meta covers total_seqlen="
            f"{meta.total_seqlen}"
        )
    perm = meta.perm_idx.astype(np.int64)  # sentinel total_seqlen on pads
    c2r = np.full(meta.total_seqlen + 1, -1, np.int64)
    c2r[: canon_to_real.shape[0]] = canon_to_real
    src = c2r[np.minimum(perm, meta.total_seqlen)]
    return np.where(src >= 0, src, real_total).astype(np.int32)


def padded_undispatch_indices(
    meta: DispatchMeta, real_to_canon: np.ndarray
) -> np.ndarray:
    """Inverse of :func:`padded_dispatch_indices`:
    ``x[t] = dispatched[idx[t]]`` for every REAL row ``t`` — canonical
    pad rows are simply never read back, so no fill is needed."""
    bad = (real_to_canon < 0) | (real_to_canon >= meta.total_seqlen)
    if bad.any():
        t = int(np.flatnonzero(bad)[0])
        raise ValueError(
            f"real_to_canon[{t}]={int(real_to_canon[t])} is outside the "
            f"canonical sequence [0, {meta.total_seqlen}) — row maps and "
            "dispatch meta disagree"
        )
    unperm = meta.unperm_idx.astype(np.int64)
    return unperm[real_to_canon.astype(np.int64)].astype(np.int32)


def padded_position_ids(
    meta: DispatchMeta, canon_to_real: np.ndarray
) -> np.ndarray:
    """REAL global position of each canonical dispatched slot (pad slots
    read 0, same convention as :func:`position_ids` — their values are
    never consumed)."""
    perm = meta.perm_idx.astype(np.int64)
    c2r = np.full(meta.total_seqlen + 1, -1, np.int64)
    c2r[: canon_to_real.shape[0]] = canon_to_real
    src = c2r[np.minimum(perm, meta.total_seqlen)]
    return np.where(src >= 0, src, 0).astype(np.int32)


def _roll_src_slots(meta: DispatchMeta, shift: int) -> np.ndarray:
    """Dispatch-space source slot feeding every output slot of a global
    roll by ``shift``; pad slots source themselves (keep their value)."""
    perm = meta.perm_idx.astype(np.int64)
    unperm = meta.unperm_idx.astype(np.int64)
    total = meta.total_seqlen
    slots = np.arange(perm.shape[0], dtype=np.int64)
    valid = perm < total
    src_global = (np.where(valid, perm, 0) - shift) % total
    return np.where(valid, unperm[src_global], slots)


def roll(
    x: jax.Array,
    meta: DispatchMeta,
    shift: int,
    axis: int = 0,
    *,
    mesh=None,
    cp_axis=None,
) -> jax.Array:
    """Distributed roll along the *global* sequence of a dispatched tensor
    (reference functional/roll.py roll_p2p — MTP label shifting): in global
    order, y[i] = x[(i - shift) mod total], computed in dispatch space.
    Uneven shard: pad slots keep their own (pad) value.

    Without ``mesh``, this is one static global gather — correct anywhere,
    but GSPMD lowers it to a full-sequence all-gather (O(N) memory per
    device; exps/run_roll_proof.py records the HLO evidence). Pass
    ``mesh`` + ``cp_axis`` (the mesh axis/axes ``x`` is sharded on along
    ``axis``) for the O(N/P) path: rows that stay on their rank are a
    local gather; only rank-crossing rows (~ |shift| per chunk boundary)
    ride one padded all-to-all — the XLA analogue of the reference's
    ``batch_isend_irecv`` P2P (roll.py:448).
    """
    src_slot = _roll_src_slots(meta, shift)
    if mesh is not None and cp_axis is not None:
        out = _roll_p2p(x, meta, src_slot, axis % x.ndim, mesh, cp_axis)
        if out is not None:
            return out
    gather = src_slot.astype(np.int32)
    return jnp.take(x, jnp.asarray(gather), axis=axis)


def _roll_p2p(x, meta, src_slot, axis, mesh, cp_axis):
    """shard_map roll: local gather + padded a2a of rank-crossing rows.

    Returns None when the exchange degenerates (some rank pair moves a
    near-full shard, so the padded a2a would cost more than the gather's
    all-gather) — the caller falls back.
    """
    from ..common.axes import cp_axis_names, cp_axis_size

    names = cp_axis_names(cp_axis)
    cp = cp_axis_size(mesh, cp_axis)
    if cp != meta.cp_size:
        raise ValueError(
            f"mesh axis {cp_axis!r} has size {cp} but the dispatch meta "
            f"was planned for cp_size={meta.cp_size} "
            f"(total_seqlen={meta.total_seqlen}, "
            f"chunk_size={meta.chunk_size}) — roll must run over the "
            "mesh the plan was built for"
        )
    shard = meta.shard_seqlen
    n = cp * shard
    slots = np.arange(n, dtype=np.int64)
    src_rank = src_slot // shard
    dst_rank = slots // shard
    local = src_rank == dst_rank

    # local part: per-rank gather indices (0 where remote; masked later)
    local_src = np.where(local, src_slot % shard, 0).astype(np.int32)

    rem = np.flatnonzero(~local)
    if rem.size == 0:
        # pure permutation within ranks (e.g. shift=0): no comm at all
        return _shard_roll_apply(
            x, axis, mesh, names,
            local_src.reshape(cp, shard), None, None, None, shard,
        )
    s_r = src_rank[rem]
    d_r = dst_rank[rem]
    # canonical order shared by sender and receiver: group rows by the
    # (src, dst) pair, ordered inside a group by destination slot
    order = np.lexsort((slots[rem], s_r, d_r))
    rem, s_r, d_r = rem[order], s_r[order], d_r[order]
    pair = s_r * cp + d_r
    counts = np.bincount(pair, minlength=cp * cp)
    S = int(counts.max())
    if S * cp >= n:  # padded a2a volume would match/exceed the all-gather
        return None
    # per-(src, dst) sequence numbers, shared sender/receiver convention:
    # position of the row within its pair group (groups are contiguous
    # under a stable sort by pair; rows already ordered by dst slot)
    pair_order = np.argsort(pair, kind="stable")
    sorted_pair = pair[pair_order]
    starts = np.r_[0, np.flatnonzero(np.diff(sorted_pair)) + 1]
    group_of = np.repeat(
        np.arange(starts.size), np.diff(np.r_[starts, sorted_pair.size])
    )
    pos = np.empty(rem.size, dtype=np.int64)
    pos[pair_order] = np.arange(sorted_pair.size) - starts[group_of]

    send_idx = np.zeros((cp, cp, S), dtype=np.int32)
    send_idx[s_r, d_r, pos] = (src_slot[rem] % shard).astype(np.int32)
    # receive buffer at rank d after a2a: flat index = src*S + pos
    recv_sel = np.full((cp, shard), cp * S, dtype=np.int32)  # trash slot
    recv_sel[d_r, rem % shard] = (s_r * S + pos).astype(np.int32)
    recv_valid = np.zeros((cp, shard), dtype=bool)
    recv_valid[d_r, rem % shard] = True

    return _shard_roll_apply(
        x, axis, mesh, names,
        local_src.reshape(cp, shard), send_idx, recv_sel, recv_valid, shard,
    )


def _shard_roll_apply(
    x, axis, mesh, names, local_src, send_idx, recv_sel, recv_valid, shard
):
    from jax.sharding import PartitionSpec as P

    from ..utils.compat import shard_map
    from ..utils.instrument import named_scope

    axis_name = names if len(names) > 1 else names[0]
    # partial-manual shard_map (axis_names=cp only) requires full-rank
    # specs with explicit None for auto dims
    x_spec = P(
        *([None] * axis), axis_name, *([None] * (x.ndim - axis - 1))
    )

    def tab_spec(t):
        return P(axis_name, *([None] * (t.ndim - 1)))

    def _local(x_l, ls, *tabs):
        xm = jnp.moveaxis(x_l, axis, 0)  # [shard, ...]
        loc = jnp.take(xm, ls[0], axis=0)
        if send_idx is not None:
            si, rs, rv = tabs
            si = si[0]  # [cp, S]
            send_buf = jnp.take(xm, si.reshape(-1), axis=0).reshape(
                si.shape + xm.shape[1:]
            )
            with named_scope("magi_roll_a2a"):
                recv = jax.lax.all_to_all(
                    send_buf, axis_name, split_axis=0, concat_axis=0,
                    tiled=False,
                )
            flat = recv.reshape((-1,) + xm.shape[1:])
            remote = jnp.take(
                flat, jnp.minimum(rs[0], flat.shape[0] - 1), axis=0
            )
            mask = rv[0].reshape((shard,) + (1,) * (xm.ndim - 1))
            loc = jnp.where(mask, remote, loc)
        return jnp.moveaxis(loc, 0, axis)

    tabs = (jnp.asarray(local_src),)
    if send_idx is not None:
        tabs += (
            jnp.asarray(send_idx),
            jnp.asarray(recv_sel),
            jnp.asarray(recv_valid),
        )
    specs = tuple(tab_spec(t) for t in tabs)
    fn = shard_map(
        _local,
        mesh=mesh,
        in_specs=(x_spec,) + specs,
        out_specs=x_spec,
        # only the cp axis/axes are manual: shardings of other dims over
        # the remaining mesh axes (e.g. a tp-sharded hidden dim) pass
        # through GSPMD untouched instead of being forced replicated.
        # check_vma must stay True — disabling it rewrites out_specs to
        # full specs, which partial-manual mode rejects
        axis_names=set(names),
    )
    return fn(x, *tabs)


# ---------------------------------------------------------------------------
# the forward shift along a document, local form
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _RowExchange:
    """Host tables of one gather with an exchange: output ``o``, slot
    ``n`` takes row ``src`` of input ``inputs[o]`` (any rank's) or zero.
    ``sel`` [cp, outputs, shard]: -1 zero; below ``shard`` the rank's own
    row; from ``shard`` on, ``shard +`` the row's place in the receive
    buffer. ``send_idx`` / ``send_input`` [cp, cp * width]: the rows a
    rank sends, ``width`` a destination (each row once, whatever the
    outputs that read it). ``offsets[o]``: where every row output ``o``
    reads is the rank's own at one constant offset (the slot ``d``
    before), that offset: the gather is then a slice."""

    inputs: tuple[int, ...]
    sel: np.ndarray
    send_idx: np.ndarray
    send_input: np.ndarray
    width: int
    sent: int  # rows that cross ranks, all ranks'
    offsets: tuple[int | None, ...]

    @property
    def tables(self):
        return (self.sel, self.send_idx, self.send_input)


def _row_exchange(cp: int, shard: int, outputs) -> _RowExchange:
    """``outputs``: (input index, src [cp * shard] dispatch slot or -1)."""
    n = cp * shard
    slots = np.arange(n, dtype=np.int64)
    dst_rank = slots // shard
    sel = np.full((len(outputs), n), -1, np.int64)
    remote, offsets = [], []
    for o, (i, src) in enumerate(outputs):
        valid = src >= 0
        local = valid & (src // shard == dst_rank)
        sel[o, local] = src[local] % shard
        far = np.flatnonzero(valid & ~local)
        remote.append(np.stack(
            [dst_rank[far], src[far] // shard, np.full(far.size, i),
             src[far] % shard, np.full(far.size, o), far], axis=1,
        ))
        back = np.unique((slots - src)[valid])
        offsets.append(
            int(back[0]) if back.size == 1 and not far.size else None
        )
    remote = np.concatenate(remote)
    width = sent = 0
    send_idx = np.zeros((cp, cp, 0), np.int64)
    send_input = send_idx
    if remote.size:
        # a row is sent once: (destination, source, input, row), in the
        # order sender and receiver share
        rows, inverse = np.unique(remote[:, :4], axis=0, return_inverse=True)
        pair = rows[:, 0] * cp + rows[:, 1]
        first = np.searchsorted(pair, pair, side="left")
        place = np.arange(rows.shape[0]) - first  # inside its pair's group
        width, sent = int(place.max()) + 1, rows.shape[0]
        send_idx = np.zeros((cp, cp, width), np.int64)
        send_input = np.zeros((cp, cp, width), np.int64)
        send_idx[rows[:, 1], rows[:, 0], place] = rows[:, 3]
        send_input[rows[:, 1], rows[:, 0], place] = rows[:, 2]
        # after the all-to-all rank d holds source s's rows at s * width
        got = (rows[:, 1] * width + place)[inverse.reshape(-1)]
        sel[remote[:, 4], remote[:, 5]] = shard + got
    return _RowExchange(
        inputs=tuple(i for i, _ in outputs),
        sel=sel.reshape(len(outputs), cp, shard).transpose(1, 0, 2)
        .astype(np.int32),
        send_idx=send_idx.reshape(cp, cp * width).astype(np.int32),
        send_input=send_input.reshape(cp, cp * width).astype(np.int32),
        width=width,
        sent=sent,
        offsets=tuple(offsets),
    )


def _exchange_rows(xs, ex: _RowExchange, tables, axis_name):
    """One output an entry of ``ex.inputs`` from this rank's ``xs`` (each
    [shard, ...]) and every other rank's, in one all-to-all."""
    from ..utils.instrument import named_scope

    sel, send_idx, send_input = (t[0] for t in tables)
    shard = xs[0].shape[0]
    received = None
    if ex.width:
        send = jnp.take(xs[0], send_idx, axis=0)
        for i, x in enumerate(xs[1:], 1):
            mine = (send_input == i).reshape((-1,) + (1,) * (x.ndim - 1))
            send = jnp.where(mine, jnp.take(x, send_idx, axis=0), send)
        cp = send.shape[0] // ex.width
        with named_scope("magi_shift_a2a"):
            received = jax.lax.all_to_all(
                send.reshape((cp, ex.width) + send.shape[1:]), axis_name,
                split_axis=0, concat_axis=0, tiled=False,
            ).reshape(send.shape)
    outs = []
    for o, i in enumerate(ex.inputs):
        x, s = xs[i], sel[o]
        at = s.reshape((shard,) + (1,) * (x.ndim - 1))
        if ex.offsets[o] is not None:  # the rank's own rows, one offset
            rows = jnp.roll(x, ex.offsets[o], axis=0)
        else:
            rows = jnp.take(x, jnp.clip(s, 0, shard - 1), axis=0)
            if received is not None:
                far = jnp.take(
                    received, jnp.clip(s - shard, 0, received.shape[0] - 1),
                    axis=0,
                )
                rows = jnp.where(at >= shard, far, rows)
        outs.append(jnp.where(at >= 0, rows, jnp.zeros((), x.dtype)))
    return outs


@dataclasses.dataclass(frozen=True, eq=False)
class ShiftPlan:
    """The host side of :func:`shift_local` for one dispatch and one set
    of documents: the forward exchange (a tap an output) and its
    transpose's (a tap's cotangent an input)."""

    taps: tuple[int, ...]
    documents: int
    fwd: _RowExchange
    bwd: _RowExchange

    @property
    def remote_rows(self) -> int:
        """The rows one forward application brings from another rank, all
        ranks', a row once whatever the taps that read it."""
        return self.fwd.sent

    def device_tables(self):
        """Arrays with a leading ``cp`` dimension, to be sharded on the
        cp axis as a plan's tables are."""
        return tuple(jnp.asarray(t) for t in self.fwd.tables + self.bwd.tables)


def make_shift_plan(
    meta: DispatchMeta, cu_seqlens, taps=(1, 2)
) -> ShiftPlan:
    """Plan ``y_j[p] = x[p - j]`` for every ``j`` of ``taps`` (positive:
    a token reads its predecessors) along the GLOBAL sequence of a
    dispatched tensor, zero where ``p - j`` falls before the first token
    of ``p``'s document (``cu_seqlens``: the documents' cumulative
    lengths from 0 to ``meta.total_seqlen``). A rank's chunks need not be
    neighbours, so a chunk's first rows read the rank that holds the
    chunk before; every tap's rank-crossing rows ride one exchange."""
    from .. import telemetry

    taps = tuple(int(j) for j in taps)
    if not taps or min(taps) < 1:
        raise ValueError(f"taps {taps}: a shift reads predecessors, j >= 1")
    cu = np.asarray(cu_seqlens, np.int64)
    total = meta.total_seqlen
    if cu[0] != 0 or cu[-1] != total or (np.diff(cu) < 1).any():
        raise ValueError(
            f"cu_seqlens {cu.tolist()} do not cut [0, {total}) into documents"
        )
    perm = meta.perm_idx.astype(np.int64)
    unperm = meta.unperm_idx.astype(np.int64)
    real = perm < total
    p = np.where(real, perm, 0)
    doc = np.searchsorted(cu, p, side="right") - 1
    start, end = cu[doc], cu[doc + 1]

    def slot_of(q, ok):
        return np.where(real & ok, unperm[np.where(ok, q, 0)], -1)

    fwd = _row_exchange(meta.cp_size, meta.shard_seqlen, [
        (0, slot_of(p - j, p - j >= start)) for j in taps
    ])
    bwd = _row_exchange(meta.cp_size, meta.shard_seqlen, [
        (a, slot_of(p + j, p + j < end)) for a, j in enumerate(taps)
    ])
    plan = ShiftPlan(taps=taps, documents=cu.size - 1, fwd=fwd, bwd=bwd)
    telemetry.record_shift(
        rows=plan.remote_rows, taps=taps, documents=plan.documents
    )
    return plan


def shift_valid(tables):
    """Beside :func:`shift_local`, from the same ``tables``: bool [taps,
    shard], whether this rank's slot has a predecessor ``j`` back inside
    its document."""
    return tables[0][0] >= 0


def shift_local(x, tables, plan: ShiftPlan, axis_name):
    """Inside a ``shard_map`` over the cp axis: this rank's rows ``x``
    [shard, ...] of a dispatched tensor -> one array a tap of
    ``plan.taps``, ``y_j[p] = x[p - j]`` in global order, zero at a
    document's first ``j`` tokens (:func:`make_shift_plan`). ``tables``:
    this rank's slices of ``plan.device_tables()``. Differentiable: the
    backward is the shift by ``-j`` with the same zeroing, one exchange
    again."""
    n_fwd = len(plan.fwd.tables)

    @jax.custom_vjp
    def shift(x, tables):
        return tuple(_exchange_rows([x], plan.fwd, tables[:n_fwd], axis_name))

    def shift_fwd(x, tables):
        return shift(x, tables), tables

    def shift_bwd(tables, dys):
        parts = _exchange_rows(list(dys), plan.bwd, tables[n_fwd:], axis_name)
        return sum(parts[1:], parts[0]), None

    shift.defvjp(shift_fwd, shift_bwd)
    return shift(x, tuple(tables))
