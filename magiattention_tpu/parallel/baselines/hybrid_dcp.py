"""Megatron-style Hybrid CP (zigzag all-gather) baseline.

Role of reference ``exps/dist_attn/baselines/hybrid_dcp.py``: the
Megatron-LM context-parallel scheme — the sequence is cut into ``2*cp``
chunks and rank r owns the zigzag pair (r, 2*cp-1-r), which equalizes
causal mask area across ranks; K/V are all-gathered (one collective, no
ring), and each rank attends its two chunks against the full gathered KV.

TPU-native form: ``lax.all_gather(tiled)`` produces the gathered KV in
rank-major zigzag order; per-rank entry tables describe both the local Q
pair and the gathered-buffer layout as runs (local window + local->global
offset), so the ORIGINAL global mask is evaluated directly — any flex
mask works, not just dense causal.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...ops.block_meta import Run, build_block_meta_general
from ...ops.flex_attn import FlexAttnParams
from ..dist_attn import (
    StageTables,
    _call_kernel,
    _headmajor_to_seq,
    _hm,
    _round_up,
    ensure_kernel_steps,
)


def zigzag_chunks(cp_size: int) -> list[tuple[int, int]]:
    """Chunk-id pair owned by each rank (causal-area balancing)."""
    return [(r, 2 * cp_size - 1 - r) for r in range(cp_size)]


def zigzag_perm(total: int, cp_size: int) -> np.ndarray:
    """Gather indices: zigzag_dispatched[i] = x[perm[i]]."""
    ch = total // (2 * cp_size)
    parts = []
    for a, b in zigzag_chunks(cp_size):
        parts.append(np.arange(a * ch, (a + 1) * ch))
        parts.append(np.arange(b * ch, (b + 1) * ch))
    return np.concatenate(parts).astype(np.int32)


def zigzag_dispatch(x: jax.Array, total: int, cp_size: int, axis: int = 0):
    return jnp.take(x, jnp.asarray(zigzag_perm(total, cp_size)), axis=axis)


def zigzag_undispatch(y: jax.Array, total: int, cp_size: int, axis: int = 0):
    perm = zigzag_perm(total, cp_size)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(total, dtype=np.int32)
    return jnp.take(y, jnp.asarray(inv), axis=axis)


@dataclasses.dataclass(frozen=True, eq=False)
class HybridDcpPlan:
    cp_size: int
    shard_len: int  # 2 * chunk rows per rank
    shard_q_pad: int
    kv_pad: int  # gathered-buffer padded length
    block_q: int
    block_k: int
    tables: StageTables

    def device_tables(self):
        return tuple(jnp.asarray(a) for a in self.tables.arrays())


def build_hybrid_dcp_plan(
    slices: np.ndarray,  # [S, 5] global (qs, qe, ks, ke, type)
    total_seqlen: int,
    cp_size: int,
    *,
    block_q: int = 128,
    block_k: int = 128,
) -> HybridDcpPlan:
    assert total_seqlen % (2 * cp_size) == 0, (
        f"total {total_seqlen} must divide into 2*cp={2 * cp_size} chunks"
    )
    ch = total_seqlen // (2 * cp_size)
    shard = 2 * ch
    shard_q_pad = _round_up(shard, block_q)
    kv_pad = _round_up(total_seqlen, block_k)

    # gathered KV layout: rank-major zigzag pairs
    k_runs = []
    pos = 0
    for a, b in zigzag_chunks(cp_size):
        k_runs.append(Run(local_start=pos, global_start=a * ch, length=ch))
        k_runs.append(
            Run(local_start=pos + ch, global_start=b * ch, length=ch)
        )
        pos += shard
    metas = []
    for r in range(cp_size):
        a, b = zigzag_chunks(cp_size)[r]
        q_runs = [
            Run(local_start=0, global_start=a * ch, length=ch),
            Run(local_start=ch, global_start=b * ch, length=ch),
        ]
        metas.append(
            build_block_meta_general(
                slices,
                q_runs,
                k_runs,
                shard_q_pad,
                kv_pad,
                block_q=block_q,
                block_k=block_k,
            )
        )
    return HybridDcpPlan(
        cp_size=cp_size,
        shard_len=shard,
        shard_q_pad=shard_q_pad,
        kv_pad=kv_pad,
        block_q=block_q,
        block_k=block_k,
        tables=StageTables.from_rank_metas(metas, kv_pad),
    )


def hybrid_dcp_attn_local(
    q: jax.Array,  # [shard, hq, d] zigzag-dispatched rank shard
    k: jax.Array,
    v: jax.Array,
    tables,
    plan: HybridDcpPlan,
    params: FlexAttnParams,
    *,
    axis_name: str = "cp",
):
    """Inside shard_map: all-gather KV, one kernel call over the buffer."""
    assert not params.has_sink, (
        "attention sink is not supported by the hybrid-dcp baseline"
    )
    params = ensure_kernel_steps(params, (plan.tables,))
    kg = jax.lax.all_gather(k, axis_name, tiled=True)  # [total, hk, d]
    vg = jax.lax.all_gather(v, axis_name, tiled=True)
    qh = _hm(q, plan.shard_q_pad)
    out_h, lse_h, _ = _call_kernel(
        qh, kg, vg, tables, plan.kv_pad, params, None
    )
    return _headmajor_to_seq(out_h, lse_h, plan.shard_len)


def make_hybrid_dcp_attn_fn(
    plan: HybridDcpPlan,
    mesh: jax.sharding.Mesh,
    params: FlexAttnParams,
    *,
    axis_name: str = "cp",
):
    """Jittable fn over zigzag-dispatched [total, h, d] arrays sharded
    P(axis_name)."""
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
        return hybrid_dcp_attn_local(
            q, k, v, tabs, plan, params, axis_name=axis_name
        )

    def fn(q, k, v):
        return _local(q, k, v, *tables)

    return fn
