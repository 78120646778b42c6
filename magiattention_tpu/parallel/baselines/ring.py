"""Ring context-parallel attention baseline.

Role of reference ``exps/dist_attn/baselines/ring_attn.py``: the classic
ring-P2P CP scheme all CP methods are benchmarked against. TPU-native form:
KV rotates around the cp mesh axis with ``lax.ppermute`` (one ICI hop per
step); each step computes partial attention of the local Q shard against the
visiting KV shard with the flex kernel (host-precomputed per-(rank, step)
entry tables in global coordinates), merged by LSE correction.

Contiguous sharding is assumed (Sequential dispatch); with a causal-family
mask, steps where the visiting shard is entirely masked still rotate but
skip compute (empty tables -> table-driven zero work, matching the
"skip-causal-half" ring optimization).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...ops.block_meta import build_block_meta_general, Run
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
class RingAttnPlan:
    cp_size: int
    shard_len: int
    shard_q_pad: int
    shard_k_pad: int
    block_q: int
    block_k: int
    steps: tuple[StageTables, ...]  # one per ring step (0 = own shard)

    def device_tables(self):
        arrs = []
        for st in self.steps:
            arrs.extend(st.arrays())
        return tuple(jnp.asarray(a) for a in arrs)


def build_ring_attn_plan(
    slices: np.ndarray,  # [S, 5] global (qs, qe, ks, ke, type)
    total_seqlen: int,
    cp_size: int,
    *,
    block_q: int = 128,
    block_k: int = 128,
) -> RingAttnPlan:
    """Plan ring attention for a contiguously-sharded mask."""
    assert total_seqlen % cp_size == 0
    shard = total_seqlen // cp_size
    shard_q_pad = _round_up(shard, block_q)
    shard_k_pad = _round_up(shard, block_k)
    steps = []
    for s in range(cp_size):
        metas = []
        for r in range(cp_size):
            src = (r - s) % cp_size  # whose KV shard visits rank r at step s
            q_runs = [Run(0, r * shard, shard)]
            k_runs = [Run(0, src * shard, shard)]
            metas.append(
                build_block_meta_general(
                    slices,
                    q_runs,
                    k_runs,
                    shard_q_pad,
                    shard_k_pad,
                    block_q=block_q,
                    block_k=block_k,
                )
            )
        steps.append(StageTables.from_rank_metas(metas, shard_k_pad))
    return RingAttnPlan(
        cp_size=cp_size,
        shard_len=shard,
        shard_q_pad=shard_q_pad,
        shard_k_pad=shard_k_pad,
        block_q=block_q,
        block_k=block_k,
        steps=tuple(steps),
    )


def ring_attn_local(
    q: jax.Array,  # [shard, hq, d]
    k: jax.Array,  # [shard, hk, d]
    v: jax.Array,
    tables,  # flattened step tables (9 arrays per step)
    plan: RingAttnPlan,
    params: FlexAttnParams,
    *,
    axis_name: str = "cp",
):
    """Inside shard_map: rotate KV around the ring, merging partials."""
    assert not params.has_sink, (
        "attention sink is not supported by the ring baseline"
    )
    params = ensure_kernel_steps(params, plan.steps)
    cp = plan.cp_size
    fp32_params = dataclasses.replace(params, out_dtype="float32")
    qh = _hm(q, plan.shard_q_pad)
    kv = jnp.stack([k, v], axis=0)  # [2, shard, hk, d]
    out = lse = None
    perm = [(i, (i + 1) % cp) for i in range(cp)]
    from ...utils.instrument import named_scope

    for s in range(cp):
        if s > 0:
            with named_scope("magi_ring_kv_ppermute"):
                kv = jax.lax.ppermute(kv, axis_name, perm)
        tab = tables[s * 9 : (s + 1) * 9]
        out_h, lse_h, _ = _call_kernel(
            qh, kv[0], kv[1], tab, plan.shard_k_pad, fp32_params, None
        )
        out_i, lse_i = _headmajor_to_seq(out_h, lse_h, plan.shard_len)
        if out is None:
            out, lse = out_i, lse_i
        else:
            out, lse = correct_attn_out_lse(out, lse, out_i, lse_i)
    return out.astype(params.out_jnp_dtype), lse


def make_ring_attn_fn(
    plan: RingAttnPlan,
    mesh: jax.sharding.Mesh,
    params: FlexAttnParams,
    *,
    axis_name: str = "cp",
):
    """Jittable fn over contiguously sharded [total, h, d] arrays."""
    from ...utils.compat import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    tables = tuple(
        jax.device_put(t, NamedSharding(mesh, P(axis_name)))
        for t in plan.device_tables()
    )
    n_tab = len(tables)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis_name),) * 3 + (P(axis_name),) * n_tab,
        out_specs=(P(axis_name), P(axis_name)),
        check_vma=False,
    )
    def _local(q, k, v, *tabs):
        return ring_attn_local(q, k, v, tabs, plan, params, axis_name=axis_name)

    def fn(q, k, v):
        return _local(q, k, v, *tables)

    return fn
