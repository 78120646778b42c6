"""Ulysses (head-sharded all-to-all) sequence-parallel attention baseline.

Role of reference ``exps/dist_attn/baselines/ulysess.py``: all_to_all swaps
the sharding from sequence to heads, each rank computes FULL-sequence
attention for its head subset (any flex mask — one shared global entry
table), then all_to_all swaps back. Requires num_heads % cp == 0.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ...ops.block_meta import FlexAttnBlockMeta, build_block_meta
from ...ops.flex_attn import FlexAttnParams, flex_attn_headmajor, fwd_tables, bwd_tables
from ..dist_attn import _headmajor_to_seq, _hm


def seq_to_heads_a2a(x, axis_name: str):
    """[t_loc, h, d] -> [t_glob, h/axis, d]; tiled all_to_all keeps rank
    blocks in order (global-token-major) and transposes cleanly under AD."""
    from ...utils.instrument import named_scope

    with named_scope("magi_ulysses_seq_to_heads_a2a"):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=0, tiled=True
        )


def heads_to_seq_a2a(x, axis_name: str):
    """Inverse of :func:`seq_to_heads_a2a`."""
    from ...utils.instrument import named_scope

    with named_scope("magi_ulysses_heads_to_seq_a2a"):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=0, concat_axis=1, tiled=True
        )


@dataclasses.dataclass(frozen=True, eq=False)
class UlyssesPlan:
    cp_size: int
    total_seqlen: int
    meta: FlexAttnBlockMeta  # global-mask tables, shared by all ranks


def build_ulysses_plan(
    q_ranges,
    k_ranges,
    attn_type_map,
    total_seqlen: int,
    cp_size: int,
    *,
    block_q: int = 128,
    block_k: int = 128,
) -> UlyssesPlan:
    meta = build_block_meta(
        q_ranges,
        k_ranges,
        attn_type_map,
        total_seqlen,
        total_seqlen,
        block_q=block_q,
        block_k=block_k,
    )
    return UlyssesPlan(cp_size=cp_size, total_seqlen=total_seqlen, meta=meta)


def ulysses_attn_local(
    q: jax.Array,  # [shard, hq, d] sequence-sharded
    k: jax.Array,  # [shard, hk, d]
    v: jax.Array,
    plan: UlyssesPlan,
    params: FlexAttnParams,
    *,
    axis_name: str = "cp",
):
    """Inside shard_map: a2a seq->heads, full-seq flex attention, a2a back."""
    assert not params.has_sink, (
        "attention sink is not supported by the ulysses baseline"
    )
    cp = plan.cp_size
    t_loc = q.shape[0]
    t_glob = plan.total_seqlen
    assert t_loc * cp == t_glob
    hq, hk = q.shape[1], k.shape[1]
    assert hq % cp == 0 and hk % cp == 0, (
        f"Ulysses needs heads divisible by cp: hq={hq} hk={hk} cp={cp}"
    )

    def seq_to_heads(x):
        return seq_to_heads_a2a(x, axis_name)

    def heads_to_seq(x):
        return heads_to_seq_a2a(x, axis_name)

    qg = seq_to_heads(q)  # [total, hq/cp, d]
    kg = seq_to_heads(k)
    vg = seq_to_heads(v)

    meta = plan.meta
    tqp = meta.num_q_blocks * meta.block_q
    tkp = meta.num_k_blocks * meta.block_k
    qh = _hm(qg, tqp)
    kh = _hm(kg, tkp)
    vh = _hm(vg, tkp)
    fp32_params = dataclasses.replace(
        params,
        out_dtype="float32",
        # tables become tracers under the surrounding jit; the row-major
        # kernels need the static grid extents from the host-side meta.
        # max(), not or: a caller-supplied steps value sized for a SMALLER
        # plan must never truncate this meta's table (entries past the
        # static extent are silently skipped under tracing)
        fwd_steps=max(params.fwd_steps, meta.fwd_steps),
        bwd_steps=max(params.bwd_steps, meta.bwd_steps),
    )
    out_h, lse_h, _ = flex_attn_headmajor(
        qh, kh, vh, fwd_tables(meta), bwd_tables(meta), fp32_params
    )
    out_g, lse_g = _headmajor_to_seq(out_h, lse_h, plan.total_seqlen)
    out = heads_to_seq(out_g).astype(params.out_jnp_dtype)
    # lse [total, hq/cp] -> [t_loc, hq]
    lse = heads_to_seq(lse_g[..., None])[..., 0]
    return out, lse


def make_ulysses_attn_fn(
    plan: UlyssesPlan,
    mesh: jax.sharding.Mesh,
    params: FlexAttnParams,
    *,
    axis_name: str = "cp",
):
    from ...utils.compat import shard_map
    from jax.sharding import PartitionSpec as P

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis_name),) * 3,
        out_specs=(P(axis_name), P(axis_name)),
        check_vma=False,
    )
    def _local(q, k, v):
        return ulysses_attn_local(
            q, k, v, plan, params, axis_name=axis_name
        )

    return _local
