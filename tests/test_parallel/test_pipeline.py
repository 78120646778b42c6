"""End-to-end pipeline test: dispatch -> dist attn fwd/bwd -> undispatch vs
the jnp oracle, over mask scenarios x cp sizes on a virtual CPU mesh.

Model: reference tests/test_pipeline.py (the flagship test).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from magiattention_tpu.common import AttnMaskType, AttnRanges
from magiattention_tpu.meta import (
    DispatchConfig,
    MinHeapDispatchAlg,
    SequentialDispatchAlg,
    make_dispatch_meta_from_qk_ranges,
)
from magiattention_tpu.parallel import (
    build_dist_attn_plan,
    dispatch,
    make_attn_params,
    make_dist_attn_fn,
    undispatch,
)
from magiattention_tpu.testing import assert_close, ref_attn_from_ranges

F = AttnMaskType.FULL
C = AttnMaskType.CAUSAL
I = AttnMaskType.INVCAUSAL
B = AttnMaskType.BICAUSAL

# named mask scenarios (reference test_pipeline.py:403-857 scaled down):
# (name, total, q_ranges, k_ranges, types)
SCENARIOS = [
    ("full_attn_1k", 1024, [(0, 1024)], [(0, 1024)], [F]),
    ("causal_1k", 1024, [(0, 1024)], [(0, 1024)], [C]),
    (
        "varlen_full",
        768,
        [(0, 256), (256, 640), (640, 768)],
        [(0, 256), (256, 640), (640, 768)],
        [F, F, F],
    ),
    (
        "varlen_block_causal",
        1024,
        [(0, 384), (384, 768), (768, 1024)],
        [(0, 384), (0, 768), (0, 1024)],
        [C, C, C],
    ),
    (
        # q_ranges overlap but (q, k) coverage stays disjoint: the causal
        # slice covers k <= q, the inv-causal slice covers k >= q + 128
        "q_overlap_multi_mask",
        512,
        [(0, 512), (128, 384)],
        [(0, 512), (256, 512)],
        [C, I],
    ),
    (
        "mixed_types_with_holes",
        768,
        [(0, 256), (384, 640), (640, 768)],
        [(0, 384), (128, 640), (384, 768)],
        [C, I, B],
    ),
    (
        # reference share_question_1k_with_q_overlap: two answers share a
        # question prefix; each answer attends (question FULL + itself
        # CAUSAL) and never the other answer
        "share_question_q_overlap",
        768,
        [(0, 256), (256, 512), (256, 512), (512, 768), (512, 768)],
        [(0, 256), (0, 256), (256, 512), (0, 256), (512, 768)],
        [C, F, C, F, C],
    ),
    (
        # reference full_mask_assembled_from_small_pieces_with_8k: a dense
        # full mask tiled from 16 small FULL slices — plan must merge the
        # pieces into the same coverage as one big slice
        "full_assembled_from_pieces",
        512,
        [
            (q0, q0 + 128)
            for q0 in range(0, 512, 128)
            for _k0 in range(0, 512, 128)
        ],
        [
            (k0, k0 + 128)
            for _q0 in range(0, 512, 128)
            for k0 in range(0, 512, 128)
        ],
        [F] * 16,
    ),
]


def _mesh(cp):
    return Mesh(np.array(jax.devices()[:cp]), ("cp",))


def test_stage_tables_carry_real_major_block_counts():
    """StageTables.kernel_steps used to hand max_row_count a misleading
    num_major=1 (harmless for the max only because dummies guarantee
    every major >= 1 entry); from_rank_metas now records the real grid
    geometry and kernel_steps must agree with the per-rank metas."""
    total, cp, chunk, bq, bk = 1024, 4, 64, 64, 128
    q_ranges = AttnRanges.from_ranges([(0, total)])
    k_ranges = AttnRanges.from_ranges([(0, total)])
    mq, _, bucket = make_dispatch_meta_from_qk_ranges(
        q_ranges, k_ranges, [AttnMaskType.CAUSAL], total, total,
        chunk_size=chunk, cp_size=cp,
    )
    plan = build_dist_attn_plan(mq, bucket, block_q=bq, block_k=bk)
    t = plan.merged_tables
    assert t.num_q_blocks == plan.shard_q_pad // bq
    assert t.num_k_blocks == t.kv_pad // bk
    fs, bs = t.kernel_steps()
    assert fs >= 1 and bs >= 1
    # the extents must cover every per-rank row: re-derive from the
    # stacked major arrays with the honest minlength
    from magiattention_tpu.ops.block_meta import max_row_count

    assert fs == max(
        max_row_count(row, t.num_q_blocks) for row in t.fwd_qblk
    )
    assert bs == max(
        max_row_count(row, t.num_k_blocks) for row in t.bwd_kblk
    )


@pytest.mark.parametrize("cp", [1, 2, 4])
@pytest.mark.parametrize(
    "name,total,qr,kr,ts",
    # full_attn is the heaviest scenario post-resurrection (18s at cp=1
    # on this box); causal + varlen keep every cp live in tier-1
    # (ISSUE 7 budget re-tier, docs/testing.md)
    [
        pytest.param(*s, marks=pytest.mark.slow)
        if s[0] == "full_attn_1k" else s
        for s in SCENARIOS
    ],
    ids=[s[0] for s in SCENARIOS],
)
def test_pipeline_fwd_bwd(name, total, qr, kr, ts, cp):
    hq, hk, d = 4, 2, 64
    chunk = total // (4 * cp)  # >= 4 chunks per rank
    mesh = _mesh(cp)

    q_ranges = AttnRanges.from_ranges(qr)
    k_ranges = AttnRanges.from_ranges(kr)
    mq, _, bucket = make_dispatch_meta_from_qk_ranges(
        q_ranges, k_ranges, ts, total, total, chunk_size=chunk, cp_size=cp,
        dispatch_config=DispatchConfig(alg=MinHeapDispatchAlg()),
    )
    plan = build_dist_attn_plan(mq, bucket, block_q=64, block_k=64)
    params = make_attn_params(plan, d, out_dtype="float32")
    attn_fn = make_dist_attn_fn(plan, mesh, params)

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((total, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((total, hk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((total, hk, d)), jnp.float32)
    do = jnp.asarray(rng.standard_normal((total, hq, d)), jnp.float32)

    shard = NamedSharding(mesh, P("cp"))

    def full_fwd(q, k, v):
        qd = jax.lax.with_sharding_constraint(dispatch(q, mq), shard)
        kd = jax.lax.with_sharding_constraint(dispatch(k, mq), shard)
        vd = jax.lax.with_sharding_constraint(dispatch(v, mq), shard)
        out_d, lse_d = attn_fn(qd, kd, vd)
        return undispatch(out_d, mq), undispatch(lse_d, mq)

    out, lse = jax.jit(full_fwd)(q, k, v)
    ref_out, ref_lse, _ = ref_attn_from_ranges(q, k, v, qr, kr, ts)
    assert_close(out, ref_out, atol=2e-5, rtol=2e-5, msg=f"{name} cp{cp} out")
    finite = ~np.isneginf(np.asarray(ref_lse))
    np.testing.assert_array_equal(
        np.isneginf(np.asarray(lse)), ~finite, err_msg=f"{name} cp{cp} lse inf"
    )
    assert_close(
        np.asarray(lse)[finite],
        np.asarray(ref_lse)[finite],
        atol=2e-5,
        rtol=2e-5,
        msg=f"{name} cp{cp} lse",
    )

    # backward through the whole pipeline
    loss = lambda q, k, v: (full_fwd(q, k, v)[0] * do).sum()
    loss_ref = lambda q, k, v: (
        ref_attn_from_ranges(q, k, v, qr, kr, ts)[0] * do
    ).sum()
    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(g, gr, ["dq", "dk", "dv"]):
        assert_close(a, b, atol=5e-5, rtol=5e-5, msg=f"{name} cp{cp} {nm}")


# degree=4 re-tiered slow for the 870s tier-1 budget (ISSUE 16):
# degrees 1+2 keep the multi-stage lse-merge path live on all three
# scenarios, and the auto-degree e2e test exercises high degrees
@pytest.mark.parametrize(
    "degree",
    [1, 2, pytest.param(4, marks=pytest.mark.slow)],
)
@pytest.mark.parametrize(
    "name,total,qr,kr,ts",
    [s for s in SCENARIOS if s[0] in ("causal_1k", "varlen_block_causal", "mixed_types_with_holes")],
    ids=lambda s: s if isinstance(s, str) else "",
)
def test_pipeline_multi_stage_overlap(name, total, qr, kr, ts, degree):
    """Multi-stage overlap path (host stage + lse-merged remote stages)."""
    from magiattention_tpu.meta.solver.overlap_solver import OverlapConfig

    cp = 4
    hq, hk, d = 2, 2, 64
    chunk = total // (4 * cp)
    mesh = _mesh(cp)
    q_ranges = AttnRanges.from_ranges(qr)
    k_ranges = AttnRanges.from_ranges(kr)
    mq, _, bucket = make_dispatch_meta_from_qk_ranges(
        q_ranges, k_ranges, ts, total, total, chunk_size=chunk, cp_size=cp,
    )
    plan = build_dist_attn_plan(
        mq, bucket, block_q=64, block_k=64,
        overlap_config=OverlapConfig(degree=degree, min_stage_rows=64),
    )
    assert plan.overlap_degree == degree
    params = make_attn_params(plan, d, out_dtype="float32")
    attn_fn = make_dist_attn_fn(plan, mesh, params)

    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((total, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((total, hk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((total, hk, d)), jnp.float32)
    do = jnp.asarray(rng.standard_normal((total, hq, d)), jnp.float32)

    def full_fwd(q, k, v):
        out_d, lse_d = attn_fn(dispatch(q, mq), dispatch(k, mq), dispatch(v, mq))
        return undispatch(out_d, mq), undispatch(lse_d, mq)

    out, lse = jax.jit(full_fwd)(q, k, v)
    ref_out, ref_lse, _ = ref_attn_from_ranges(q, k, v, qr, kr, ts)
    assert_close(out, ref_out, atol=3e-5, rtol=3e-5, msg=f"{name} d{degree} out")
    finite = ~np.isneginf(np.asarray(ref_lse))
    assert_close(
        np.asarray(lse)[finite], np.asarray(ref_lse)[finite],
        atol=3e-5, rtol=3e-5, msg=f"{name} d{degree} lse",
    )

    g = jax.jit(
        jax.grad(lambda q, k, v: (full_fwd(q, k, v)[0] * do).sum(), argnums=(0, 1, 2))
    )(q, k, v)
    gr = jax.grad(
        lambda q, k, v: (ref_attn_from_ranges(q, k, v, qr, kr, ts)[0] * do).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b, nm in zip(g, gr, ["dq", "dk", "dv"]):
        assert_close(a, b, atol=1e-4, rtol=1e-4, msg=f"{name} d{degree} {nm}")


def test_zero_redundancy_comm_volume():
    """Causal mask: remote KV rows must be only what is attended, not all-KV."""
    total, cp, chunk = 1024, 4, 64
    q_ranges = AttnRanges.from_ranges([(0, total)])
    mq, _, bucket = make_dispatch_meta_from_qk_ranges(
        q_ranges, q_ranges, [C], total, total, chunk_size=chunk, cp_size=cp,
        dispatch_config=DispatchConfig(alg=SequentialDispatchAlg()),
    )
    plan = build_dist_attn_plan(mq, bucket, block_q=64, block_k=64)
    # sequential split of a causal mask: rank r needs ranks < r fully
    # → recv_total[0] == 0, monotonically increasing
    assert plan.comm.recv_total[0] == 0
    assert list(plan.comm.recv_total) == sorted(plan.comm.recv_total)
    shard = total // cp
    assert plan.comm.recv_total[-1] == (cp - 1) * shard


# full-attn variant re-tiered slow for the 870s tier-1 budget
# (ISSUE 16): the varlen-causal case keeps uneven sharding live
@pytest.mark.parametrize(
    "name,total,qr,kr,ts",
    [
        pytest.param(
            "uneven_full_attn", 640, [(0, 640)], [(0, 640)], [F],
            marks=pytest.mark.slow,
        ),
        (
            "uneven_varlen_causal",
            640,
            [(0, 256), (256, 448), (448, 640)],
            [(0, 256), (256, 448), (448, 640)],
            [C, C, C],
        ),
    ],
    ids=["uneven_full_attn", "uneven_varlen_causal"],
)
def test_uneven_shard_pipeline(name, total, qr, kr, ts):
    """Uneven shard (reference _make_dispatch_meta.py:368-377, api:639-676):
    10 chunks over cp=4 -> ranks own 3/3/2/2 chunks, no cp-multiple padding;
    full api round trip + grads vs the oracle."""
    from magiattention_tpu.api import (
        calc_attn as api_calc_attn,
        dispatch as api_dispatch,
        get_runtime_mgr,
        magi_attn_flex_key,
        roll as api_roll,
        undispatch as api_undispatch,
    )
    from magiattention_tpu.meta import DispatchConfig as DC

    cp, chunk = 4, 64
    hq, hk, d = 2, 2, 32
    mesh = _mesh(cp)
    key = magi_attn_flex_key(
        qr, kr, ts, total, total, mesh,
        num_heads=(hq, hk), head_dim=d, chunk_size=chunk,
        out_dtype="float32",
        dispatch_config=DC(uneven_shard=True, alg=MinHeapDispatchAlg()),
    )
    meta = get_runtime_mgr(key).dispatch_meta
    assert key.pad_size == 0  # 640 is a chunk multiple: no padding at all
    assert meta.is_uneven
    assert sorted(len(p) for p in meta.partitions) == [2, 2, 3, 3]
    assert meta.shard_seqlen == 3 * chunk

    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal((total, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((total, hk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((total, hk, d)), jnp.float32)
    do = jnp.asarray(rng.standard_normal((total, hq, d)), jnp.float32)

    def full_fwd(q, k, v):
        qd = api_dispatch(q, key)
        kd = api_dispatch(k, key)
        vd = api_dispatch(v, key)
        out_d, fm = api_calc_attn(qd, kd, vd, key)
        return api_undispatch(out_d, key), api_undispatch(fm.lse, key)

    out, lse = jax.jit(full_fwd)(q, k, v)
    ref_out, ref_lse, _ = ref_attn_from_ranges(q, k, v, qr, kr, ts)
    assert_close(out, ref_out, atol=2e-5, rtol=2e-5, msg=f"{name} out")
    finite = ~np.isneginf(np.asarray(ref_lse))
    assert_close(
        np.asarray(lse)[finite], np.asarray(ref_lse)[finite],
        atol=2e-5, rtol=2e-5, msg=f"{name} lse",
    )

    loss = lambda q, k, v: (full_fwd(q, k, v)[0] * do).sum()
    loss_ref = lambda q, k, v: (
        ref_attn_from_ranges(q, k, v, qr, kr, ts)[0] * do
    ).sum()
    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(g, gr, ["dq", "dk", "dv"]):
        assert_close(a, b, atol=5e-5, rtol=5e-5, msg=f"{name} {nm}")

    # dispatch/undispatch round trip + roll through pad slots
    x = jnp.arange(total, dtype=jnp.int32)
    xd = api_dispatch(x, key)
    assert xd.shape[0] == cp * meta.shard_seqlen  # physical > total
    np.testing.assert_array_equal(np.asarray(api_undispatch(xd, key)), x)
    got = np.asarray(api_undispatch(api_roll(xd, key, 3), key))
    np.testing.assert_array_equal(got, np.roll(np.arange(total), 3))

    # same mask through the staged multi-stage-overlap path
    from magiattention_tpu.config import DistAttnConfig
    from magiattention_tpu.meta.solver.overlap_solver import OverlapConfig

    key2 = magi_attn_flex_key(
        qr, kr, ts, total, total, mesh,
        num_heads=(hq, hk), head_dim=d, chunk_size=chunk,
        out_dtype="float32",
        dist_attn_config=DistAttnConfig(
            dispatch_config=DC(uneven_shard=True, alg=MinHeapDispatchAlg()),
            overlap_config=OverlapConfig(degree=2, min_stage_rows=64),
        ),
    )
    out2 = jax.jit(
        lambda q, k, v: api_undispatch(
            api_calc_attn(
                api_dispatch(q, key2),
                api_dispatch(k, key2),
                api_dispatch(v, key2),
                key2,
            )[0],
            key2,
        )
    )(q, k, v)
    assert_close(out2, ref_out, atol=2e-5, rtol=2e-5, msg=f"{name} staged")


@pytest.mark.parametrize("degree", [0, 2])
def test_hier_cp_pipeline_2d_mesh(degree):
    """Hierarchical CP through the public API on a (dcn=2, ici=4) mesh
    (reference 2-D cp_group path, api:617-637 + _group_collective_hier.py):
    numerically identical to the oracle, with the inter hop moving no more
    rows than a flat cast would."""
    from magiattention_tpu.api import (
        calc_attn,
        dispatch,
        get_runtime_mgr,
        magi_attn_flex_key,
        undispatch,
    )
    from magiattention_tpu.config import DistAttnConfig
    from magiattention_tpu.meta.solver.overlap_solver import OverlapConfig

    ni, nj = 2, 4
    mesh = Mesh(
        np.array(jax.devices()[: ni * nj]).reshape(ni, nj), ("dcn", "ici")
    )
    total, hq, hk, d = 1024, 2, 2, 32
    qr, kr, ts = [(0, total)], [(0, total)], [C]
    key = magi_attn_flex_key(
        qr, kr, ts, total, total, mesh,
        num_heads=(hq, hk), head_dim=d, cp_axis=("dcn", "ici"),
        chunk_size=32, out_dtype="float32",
        dist_attn_config=DistAttnConfig(
            overlap_config=OverlapConfig(degree=degree, min_stage_rows=64)
        ),
    )
    mgr = get_runtime_mgr(key)
    assert mgr.plan.hier == (ni, nj)
    assert key.cp_size == ni * nj

    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((total, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((total, hk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((total, hk, d)), jnp.float32)
    do = jnp.asarray(rng.standard_normal((total, hq, d)), jnp.float32)

    def full_fwd(q, k, v):
        qd, kd, vd = dispatch(q, key), dispatch(k, key), dispatch(v, key)
        return undispatch(calc_attn(qd, kd, vd, key)[0], key)

    out = jax.jit(full_fwd)(q, k, v)
    ref_out, _, _ = ref_attn_from_ranges(q, k, v, qr, kr, ts)
    assert_close(out, ref_out, atol=2e-5, rtol=2e-5, msg=f"hier d{degree}")

    # grads flow through both hops (the hier reduce is the cast transpose)
    g = jax.jit(
        jax.grad(lambda k: (full_fwd(q, k, v) * do).sum())
    )(k)
    gr = jax.grad(
        lambda k: (ref_attn_from_ranges(q, k, v, qr, kr, ts)[0] * do).sum()
    )(k)
    assert_close(g, gr, atol=5e-5, rtol=5e-5, msg=f"hier dk d{degree}")

    # dedup accounting: inter-hop rows <= what a flat cast would move
    # between nodes (strictly fewer when several ranks of a node share rows)
    plan = mgr.plan
    comms = [plan.merged_comm] if degree == 0 else [s.comm for s in plan.stages]
    for cm in comms:
        assert sum(cm.inter_rows_total) <= sum(cm.recv_total)
    if degree == 0:
        assert sum(plan.merged_comm.inter_rows_total) < sum(
            plan.merged_comm.recv_total
        )


def test_union_comm_empty_stages():
    """Advisor regression: a degree>=1 plan on a fully-local mask
    (block-diagonal varlen aligned to the rank shards) filters out every
    stage; ``plan.comm`` must report zero volume instead of crashing."""
    from magiattention_tpu.meta.solver.overlap_solver import OverlapConfig

    cp, total, chunk = 4, 512, 128
    docs = [(i * chunk, (i + 1) * chunk) for i in range(cp)]
    r = AttnRanges.from_ranges(docs)
    mq, _, bucket = make_dispatch_meta_from_qk_ranges(
        r, r, [F] * cp, total, total, chunk_size=chunk, cp_size=cp,
        dispatch_config=DispatchConfig(alg=SequentialDispatchAlg()),
    )
    plan = build_dist_attn_plan(
        mq, bucket, block_q=64, block_k=64,
        overlap_config=OverlapConfig(degree=2, min_stage_rows=64),
    )
    c = plan.comm  # advisor repro: raised TypeError before the fix
    assert tuple(c.recv_total) == (0,) * cp
    assert tuple(c.send_total) == (0,) * cp
    assert c.max_recv == 0 and c.max_send == 0
    assert isinstance(plan.describe(), str)


def test_load_balanced_plan_beats_sequential():
    total, cp, chunk = 2048, 4, 128
    q_ranges = AttnRanges.from_ranges([(0, total)])
    kwargs = dict(chunk_size=chunk, cp_size=cp)
    mq_b, _, bucket_b = make_dispatch_meta_from_qk_ranges(
        q_ranges, q_ranges, [C], total, total,
        dispatch_config=DispatchConfig(alg=MinHeapDispatchAlg()), **kwargs,
    )
    mq_s, _, bucket_s = make_dispatch_meta_from_qk_ranges(
        q_ranges, q_ranges, [C], total, total,
        dispatch_config=DispatchConfig(alg=SequentialDispatchAlg()), **kwargs,
    )
    plan_b = build_dist_attn_plan(mq_b, bucket_b, block_q=64, block_k=64)
    plan_s = build_dist_attn_plan(mq_s, bucket_s, block_q=64, block_k=64)
    assert plan_b.max_rank_area < plan_s.max_rank_area


@pytest.mark.slow  # 12s cp=8 stress variant (ISSUE 7 re-tier)
def test_large_varlen_block_causal_cp8():
    """Scaled version of the reference's varlen_block_causal_144k flagship
    scenario: 4k tokens, 5 docs, cp=8, chunk 64."""
    total, cp = 4096, 8
    hq, hk, d = 2, 2, 64
    mesh = _mesh(cp)
    cu = [0, 640, 1536, 2048, 3328, 4096]
    q_ranges = AttnRanges.from_cu_seqlens(cu, total)
    k_ranges = AttnRanges.from_ranges([(0, e) for e in cu[1:]])
    ts = [C] * 5
    mq, _, bucket = make_dispatch_meta_from_qk_ranges(
        q_ranges, k_ranges, ts, total, total, chunk_size=64, cp_size=cp,
    )
    plan = build_dist_attn_plan(mq, bucket, block_q=64, block_k=64)
    # load balance must beat the naive contiguous split on block-causal
    assert plan.max_rank_area / (plan.total_area / cp) < 1.2
    params = make_attn_params(plan, d, out_dtype="float32")
    attn_fn = make_dist_attn_fn(plan, mesh, params)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((total, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((total, hk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((total, hk, d)), jnp.float32)
    out = jax.jit(
        lambda q, k, v: undispatch(
            attn_fn(dispatch(q, mq), dispatch(k, mq), dispatch(v, mq))[0], mq
        )
    )(q, k, v)
    ref_out, _, _ = ref_attn_from_ranges(q, k, v, q_ranges, k_ranges, ts)
    assert_close(out, ref_out, atol=3e-5, rtol=3e-5, msg="large cp8")


def test_bf16_distributed_reasonable():
    """bf16 end-to-end CP attention stays within bf16-scale error."""
    total, cp = 1024, 4
    hq, hk, d = 2, 2, 64
    mesh = _mesh(cp)
    q_ranges = AttnRanges.from_ranges([(0, total)])
    mq, _, bucket = make_dispatch_meta_from_qk_ranges(
        q_ranges, q_ranges, [C], total, total, chunk_size=64, cp_size=cp,
    )
    plan = build_dist_attn_plan(mq, bucket, block_q=64, block_k=64)
    params = make_attn_params(plan, d, out_dtype="bfloat16")
    attn_fn = make_dist_attn_fn(plan, mesh, params)
    rng = np.random.default_rng(1)
    qf = jnp.asarray(rng.standard_normal((total, hq, d)), jnp.float32)
    kf = jnp.asarray(rng.standard_normal((total, hk, d)), jnp.float32)
    vf = jnp.asarray(rng.standard_normal((total, hk, d)), jnp.float32)
    q, k, v = (x.astype(jnp.bfloat16) for x in (qf, kf, vf))
    out = undispatch(
        attn_fn(dispatch(q, mq), dispatch(k, mq), dispatch(v, mq))[0], mq
    )
    ref_out, _, _ = ref_attn_from_ranges(qf, kf, vf, q_ranges, q_ranges, [C])
    assert_close(
        out.astype(jnp.float32), ref_out, atol=3e-2, rtol=3e-2, msg="bf16 cp4"
    )
