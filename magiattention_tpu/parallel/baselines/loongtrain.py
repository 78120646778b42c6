"""LoongTrain-style double-ring context-parallel attention baseline.

Role of reference ``exps/dist_attn/baselines/loongtrain.py`` (2D-Attention):
the sequence ring is factored into (outer x inner) rings — inner rotations
ride the fast links (ICI/intra-node) while the KV block crosses the slow
axis only once per inner cycle. Same per-(rank, step) entry-table scheme as
the plain ring; only the rotation schedule differs:

    step s = so * r_in + si visits src rank (o - so, i - si) (mod each axis);
    every step rotates the inner axis, every r_in-th step also the outer.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...ops.block_meta import Run, build_block_meta_general
from ...ops.correction import correct_attn_out_lse
from ...ops.flex_attn import FlexAttnParams
from ..dist_attn import (
    StageTables,
    _call_kernel,
    _headmajor_to_seq,
    _hm,
    _round_up,
    ensure_kernel_steps,
)


@dataclasses.dataclass(frozen=True, eq=False)
class DoubleRingPlan:
    ring_outer: int
    ring_inner: int
    shard_len: int
    shard_q_pad: int
    shard_k_pad: int
    block_q: int
    block_k: int
    steps: tuple[StageTables, ...]  # one per (so, si) step

    @property
    def cp_size(self) -> int:
        return self.ring_outer * self.ring_inner

    def device_tables(self):
        arrs = []
        for st in self.steps:
            arrs.extend(st.arrays())
        return tuple(jnp.asarray(a) for a in arrs)


def build_double_ring_plan(
    slices: np.ndarray,  # [S, 5] global (qs, qe, ks, ke, type)
    total_seqlen: int,
    ring_outer: int,
    ring_inner: int,
    *,
    block_q: int = 128,
    block_k: int = 128,
) -> DoubleRingPlan:
    """Contiguous sharding in (outer, inner) rank order."""
    cp = ring_outer * ring_inner
    assert total_seqlen % cp == 0
    shard = total_seqlen // cp
    shard_q_pad = _round_up(shard, block_q)
    shard_k_pad = _round_up(shard, block_k)
    steps = []
    for so in range(ring_outer):
        for si in range(ring_inner):
            metas = []
            for r in range(cp):
                o, i = divmod(r, ring_inner)
                # the inner axis is NOT reset between outer cycles: at step
                # (so, si) it has rotated so*(ring_inner-1)+si times, i.e.
                # src_inner = i - si + so (mod ring_inner) — folding the
                # accumulated offset into the table avoids a reset ppermute
                # of the whole KV stack per outer hop
                src = ((o - so) % ring_outer) * ring_inner + (
                    (i - si + so) % ring_inner
                )
                metas.append(
                    build_block_meta_general(
                        slices,
                        [Run(0, r * shard, shard)],
                        [Run(0, src * shard, shard)],
                        shard_q_pad,
                        shard_k_pad,
                        block_q=block_q,
                        block_k=block_k,
                    )
                )
            steps.append(StageTables.from_rank_metas(metas, shard_k_pad))
    return DoubleRingPlan(
        ring_outer=ring_outer,
        ring_inner=ring_inner,
        shard_len=shard,
        shard_q_pad=shard_q_pad,
        shard_k_pad=shard_k_pad,
        block_q=block_q,
        block_k=block_k,
        steps=tuple(steps),
    )


def double_ring_attn_local(
    q: jax.Array,  # [shard, hq, d]
    k: jax.Array,
    v: jax.Array,
    tables,  # 9 arrays per step
    plan: DoubleRingPlan,
    params: FlexAttnParams,
    *,
    axis_outer: str = "ring_out",
    axis_inner: str = "ring_in",
):
    """Inside shard_map over (ring_out, ring_in)."""
    assert not params.has_sink, (
        "attention sink is not supported by the double-ring baseline"
    )
    params = ensure_kernel_steps(params, plan.steps)
    fp32 = dataclasses.replace(params, out_dtype="float32")
    qh = _hm(q, plan.shard_q_pad)
    kv = jnp.stack([k, v], axis=0)
    out = lse = None
    perm_in = [
        (i, (i + 1) % plan.ring_inner) for i in range(plan.ring_inner)
    ]
    perm_out = [
        (o, (o + 1) % plan.ring_outer) for o in range(plan.ring_outer)
    ]
    from ...utils.instrument import named_scope

    step = 0
    for so in range(plan.ring_outer):
        if so > 0:
            # advance the outer ring once per inner cycle; the inner axis is
            # back at its start (it wrapped after ring_inner rotations)
            with named_scope("magi_loongtrain_outer_ppermute"):
                kv = jax.lax.ppermute(kv, axis_outer, perm_out)
        for si in range(plan.ring_inner):
            if si > 0:
                with named_scope("magi_loongtrain_inner_ppermute"):
                    kv = jax.lax.ppermute(kv, axis_inner, perm_in)
            tab = tables[step * 9 : (step + 1) * 9]
            out_h, lse_h, _ = _call_kernel(
                qh, kv[0], kv[1], tab, plan.shard_k_pad, fp32, None
            )
            out_i, lse_i = _headmajor_to_seq(out_h, lse_h, plan.shard_len)
            if out is None:
                out, lse = out_i, lse_i
            else:
                out, lse = correct_attn_out_lse(out, lse, out_i, lse_i)
            step += 1
    return out.astype(params.out_jnp_dtype), lse


def make_double_ring_attn_fn(
    plan: DoubleRingPlan,
    mesh: jax.sharding.Mesh,
    params: FlexAttnParams,
    *,
    axis_outer: str = "ring_out",
    axis_inner: str = "ring_in",
):
    from ...utils.compat import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    assert mesh.shape[axis_outer] == plan.ring_outer
    assert mesh.shape[axis_inner] == plan.ring_inner
    spec = P((axis_outer, axis_inner))
    tables = tuple(
        jax.device_put(t, NamedSharding(mesh, spec))
        for t in plan.device_tables()
    )
    n_tab = len(tables)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec,) * 3 + (spec,) * n_tab,
        out_specs=(spec, spec),
        check_vma=False,
    )
    def _local(q, k, v, *tabs):
        return double_ring_attn_local(
            q,
            k,
            v,
            tabs,
            plan,
            params,
            axis_outer=axis_outer,
            axis_inner=axis_inner,
        )

    def fn(q, k, v):
        return _local(q, k, v, *tables)

    return fn
