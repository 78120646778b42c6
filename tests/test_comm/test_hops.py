"""Hop-scheduled vs a2a group-collective parity (ISSUE 5).

Property-style suite over random send maps — skewed, empty pairs,
single-rank, all-local — across cp in {1, 2, 4, 8}: the hops impl must
produce BIT-IDENTICAL cast outputs (same recv layout, same values),
matching reduce results (sum / avg / lse) and matching gradients through
``group_reduce_lse_m``, while tracing strictly less comm volume — and NO
collective at all for zero-volume maps or cp=1.

Uses ``utils.compat.shard_map`` like the runtime (lint rule MAGI001).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from magiattention_tpu.comm.group_collective import (
    AUTO_HOPS_MAX_VOLUME_FRACTION,
    GroupCollectiveMeta,
    group_cast_m,
    group_reduce_lse_m,
    group_reduce_sum_m,
    predicted_volume_ratio,
)
from magiattention_tpu.utils.compat import shard_map

NEG_INF = float("-inf")
CPS = [1, 2, 4, 8]
KINDS = ["skewed", "random", "all_local", "empty"]


def _mesh(cp):
    return Mesh(np.array(jax.devices()[:cp]), ("cp",))


def _shard(mesh, a):
    a = np.asarray(a)
    return jax.device_put(
        jnp.asarray(a),
        NamedSharding(mesh, P("cp", *([None] * (a.ndim - 1)))),
    )


def _send_map(cp, t_local, seed, kind):
    """Send maps spanning the shapes the issue names: heavily skewed pair
    sizes, empty pairs, fully-local (diagonal-only), and fully empty."""
    rng = np.random.default_rng(seed)
    sm = [[np.empty(0, np.int64) for _ in range(cp)] for _ in range(cp)]
    if kind == "empty":
        return sm
    for s in range(cp):
        for d in range(cp):
            if kind == "all_local" and d != s:
                continue
            if kind == "skewed":
                if d == (s + 1) % cp:
                    n = int(rng.integers(t_local // 2, t_local + 1))
                elif rng.random() < 0.5:
                    n = 0
                else:
                    n = int(rng.integers(0, 3))
            else:  # random multicast, self-sends included
                n = int(rng.integers(0, t_local + 1))
            rows = np.sort(
                rng.choice(t_local, size=min(n, t_local), replace=False)
            )
            sm[s][d] = rows.astype(np.int64)
    return sm


def _build_pair(send_map, cp, t_local, pad_to=8):
    a2a = GroupCollectiveMeta.build(
        send_map, [t_local] * cp, pad_to=pad_to, impl="a2a"
    )
    hops = GroupCollectiveMeta.build(
        send_map, [t_local] * cp, pad_to=pad_to, impl="hops"
    )
    # identical recv geometry is what lets every consumer ignore the impl
    assert hops.max_recv == a2a.max_recv
    assert hops.recv_total == a2a.recv_total
    assert hops.send_total == a2a.send_total
    return a2a, hops


def _run_cast(meta, x_all, cp):
    mesh = _mesh(cp)
    arrays = [_shard(mesh, a) for a in meta.reduce_device_arrays()]
    n = len(arrays)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P("cp"),) * (1 + n),
        out_specs=P("cp"),
        check_vma=False,
    )
    def cast(x, *arrs):
        return group_cast_m(x[0], meta, arrs, axis_name="cp")[None]

    return cast, (_shard(mesh, np.stack(x_all)), *arrays)


@pytest.mark.parametrize("cp", CPS)
@pytest.mark.parametrize("kind", KINDS)
def test_cast_bit_identical(cp, kind):
    t_local, d_feat = 12, 8
    send_map = _send_map(cp, t_local, seed=cp * 31 + 1, kind=kind)
    a2a, hops = _build_pair(send_map, cp, t_local)
    rng = np.random.default_rng(0)
    x_all = [
        rng.standard_normal((t_local, d_feat)).astype(np.float32)
        for _ in range(cp)
    ]
    outs = {}
    for meta in (a2a, hops):
        fn, args = _run_cast(meta, x_all, cp)
        outs[meta.impl] = np.asarray(jax.jit(fn)(*args))
    # bit-identical: transport must not touch values or layout
    np.testing.assert_array_equal(outs["a2a"], outs["hops"])
    assert hops.scheduled_rows_per_rank <= a2a.scheduled_rows_per_rank


@pytest.mark.parametrize("cp", [1, 4, 8])
@pytest.mark.parametrize("kind", ["skewed", "random", "all_local"])
@pytest.mark.parametrize("average", [False, True])
def test_reduce_sum_parity(cp, kind, average):
    t_local, d_feat = 10, 4
    send_map = _send_map(cp, t_local, seed=cp * 7 + 2, kind=kind)
    a2a, hops = _build_pair(send_map, cp, t_local)
    rng = np.random.default_rng(3)
    y_all = np.stack(
        [
            rng.standard_normal((a2a.max_recv, d_feat)).astype(np.float32)
            for _ in range(cp)
        ]
    )
    acc_all = np.stack(
        [
            rng.standard_normal((t_local, d_feat)).astype(np.float32)
            for _ in range(cp)
        ]
    )
    counts_all = np.stack(
        [rng.integers(1, 4, size=t_local) for _ in range(cp)]
    ).astype(np.float32)
    res = {}
    for meta in (a2a, hops):
        mesh = _mesh(cp)
        arrays = [_shard(mesh, a) for a in meta.reduce_device_arrays()]
        n = len(arrays)

        @functools.partial(
            shard_map,
            mesh=mesh,
            in_specs=(P("cp"),) * (3 + n),
            out_specs=P("cp"),
            check_vma=False,
        )
        def red(y, acc, cnt, *arrs, _meta=meta):
            return group_reduce_sum_m(
                y[0],
                acc[0],
                _meta,
                arrs,
                axis_name="cp",
                average=average,
                counts=cnt[0],
            )[None]

        res[meta.impl] = np.asarray(
            jax.jit(red)(
                _shard(mesh, y_all),
                _shard(mesh, acc_all),
                _shard(mesh, counts_all),
                *arrays,
            )
        )
    np.testing.assert_allclose(
        res["a2a"], res["hops"], rtol=1e-6, atol=1e-6
    )


def _lse_operands(cp, t_local, h, d_feat, max_recv, seed):
    rng = np.random.default_rng(seed)
    out_p = np.stack(
        [
            rng.standard_normal((max_recv, h, d_feat)).astype(np.float32)
            for _ in range(cp)
        ]
    )
    lse_p = np.stack(
        [
            rng.standard_normal((max_recv, h)).astype(np.float32)
            for _ in range(cp)
        ]
    )
    out_a = np.stack(
        [
            rng.standard_normal((t_local, h, d_feat)).astype(np.float32)
            for _ in range(cp)
        ]
    )
    lse_a = np.stack(
        [
            rng.standard_normal((t_local, h)).astype(np.float32)
            for _ in range(cp)
        ]
    )
    # rows with no local contribution at all
    lse_a[:, 0] = NEG_INF
    out_a[:, 0] = 0.0
    return out_p, lse_p, out_a, lse_a


def _lse_fn(meta, cp, with_grad=False):
    mesh = _mesh(cp)
    arrays = [_shard(mesh, a) for a in meta.reduce_device_arrays()]
    n = len(arrays)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P("cp"),) * (4 + n),
        out_specs=(P("cp"), P("cp")),
        check_vma=False,
    )
    def red(op, lp, oa, la, *arrs):
        o, l = group_reduce_lse_m(
            op[0], lp[0], oa[0], la[0], meta, arrs, axis_name="cp"
        )
        return o[None], l[None]

    if not with_grad:
        return lambda *ops: jax.jit(red)(
            *[_shard(mesh, a) for a in ops], *arrays
        )

    def loss(op, lp, oa, la):
        o, l = red(op, lp, oa, la, *arrays)
        return (
            (o.astype(jnp.float32) ** 2).sum()
            + jnp.where(jnp.isfinite(l), l, 0.0).sum()
        )

    def run(*ops):
        ops = [_shard(mesh, a) for a in ops]
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(*ops)

    return run


@pytest.mark.parametrize("cp", [2, 4, 8])
@pytest.mark.parametrize("kind", ["skewed", "random"])
def test_reduce_lse_parity(cp, kind):
    t_local, h, d_feat = 8, 2, 4
    send_map = _send_map(cp, t_local, seed=cp * 13 + 5, kind=kind)
    a2a, hops = _build_pair(send_map, cp, t_local)
    ops = _lse_operands(cp, t_local, h, d_feat, a2a.max_recv, seed=7)
    o_a, l_a = _lse_fn(a2a, cp)(*ops)
    o_h, l_h = _lse_fn(hops, cp)(*ops)
    np.testing.assert_allclose(
        np.asarray(o_a), np.asarray(o_h), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(l_a), np.asarray(l_h), rtol=1e-5, atol=1e-6
    )


@pytest.mark.slow  # 29s; grad parity through the lse reduce is also
# covered (smaller) by test_reduce_lse_parity + the pipeline grad suites
def test_reduce_lse_grad_parity():
    """Gradients through the lse merge must agree between impls — every
    input (partials, lse partials, local accumulators) gets the same
    cotangent either way."""
    cp, t_local, h, d_feat = 4, 8, 2, 4
    send_map = _send_map(cp, t_local, seed=17, kind="skewed")
    a2a, hops = _build_pair(send_map, cp, t_local)
    ops = _lse_operands(cp, t_local, h, d_feat, a2a.max_recv, seed=11)
    v_a, g_a = _lse_fn(a2a, cp, with_grad=True)(*ops)
    v_h, g_h = _lse_fn(hops, cp, with_grad=True)(*ops)
    np.testing.assert_allclose(
        float(v_a), float(v_h), rtol=1e-5, atol=1e-6
    )
    for ga, gh in zip(g_a, g_h):
        np.testing.assert_allclose(
            np.asarray(ga), np.asarray(gh), rtol=1e-4, atol=1e-5
        )
        assert np.isfinite(np.asarray(ga)).all()


@pytest.mark.parametrize(
    "cp,kind", [(1, "random"), (1, "all_local"), (4, "empty"), (4, "all_local")]
)
def test_no_collective_traced_when_nothing_crosses(cp, kind):
    """cp=1, empty maps, and fully-local maps must trace NO ppermute and
    NO all_to_all under the hops impl — the collective vanishes from the
    program entirely (jaxpr inspection)."""
    t_local, d_feat = 6, 4
    send_map = _send_map(cp, t_local, seed=23, kind=kind)
    meta = GroupCollectiveMeta.build(
        send_map, [t_local] * cp, pad_to=8, impl="hops"
    )
    rng = np.random.default_rng(0)
    x_all = [
        rng.standard_normal((t_local, d_feat)).astype(np.float32)
        for _ in range(cp)
    ]
    fn, args = _run_cast(meta, x_all, cp)
    s = str(jax.make_jaxpr(fn)(*args))
    assert "ppermute" not in s and "all_to_all" not in s, s


def test_ppermute_count_matches_active_hops():
    """One ppermute per wire-crossing hop, none for hop 0 — the traced
    program's collective count equals the schedule's."""
    cp, t_local = 4, 10
    send_map = _send_map(cp, t_local, seed=29, kind="skewed")
    meta = GroupCollectiveMeta.build(
        send_map, [t_local] * cp, pad_to=8, impl="hops"
    )
    wire_hops = sum(1 for h in meta.hops if h.shift % cp != 0)
    rng = np.random.default_rng(1)
    x_all = [
        rng.standard_normal((t_local, 4)).astype(np.float32)
        for _ in range(cp)
    ]
    fn, args = _run_cast(meta, x_all, cp)
    s = str(jax.make_jaxpr(fn)(*args))
    assert s.count("ppermute") == wire_hops, (s.count("ppermute"), wire_hops)
    assert "all_to_all" not in s


# ---------------------------------------------------------------------------
# volume accounting + auto selection (host-side, no mesh)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cp", [2, 4, 8])
def test_scheduled_volume_never_exceeds_padded(cp):
    for seed in range(3):
        send_map = _send_map(cp, 16, seed=seed, kind="random")
        meta = GroupCollectiveMeta.build(
            send_map, [16] * cp, pad_to=8, impl="hops"
        )
        assert meta.scheduled_rows_per_rank <= meta.padded_rows_per_rank
        true_rows = sum(len(send_map[s][d]) for s in range(cp) for d in range(cp))
        assert meta.true_rows_total == true_rows
        assert meta.local_rows_total == sum(
            len(send_map[s][s]) for s in range(cp)
        )
        # the ratio is pure padding waste on the scheduled pairs: >= 1
        # whenever anything is scheduled, regardless of how much of the
        # map is self-rows moved by local copy
        if meta.scheduled_rows_total:
            assert meta.padding_overhead_ratio >= 1.0


def test_auto_picks_hops_on_skewed_a2a_on_uniform():
    cp, t_local = 4, 16
    skewed = _send_map(cp, t_local, seed=3, kind="skewed")
    meta = GroupCollectiveMeta.build(skewed, [t_local] * cp, impl="auto")
    ratio, resolved = predicted_volume_ratio(skewed, pad_to=8, impl="auto")
    assert meta.impl == resolved
    # perfectly uniform nonlocal map: every pair ships the same rows, hop
    # scheduling saves nothing -> a2a keeps the single fused collective
    uniform = [
        [
            np.arange(8, dtype=np.int64)
            if d != s
            else np.empty(0, np.int64)
            for d in range(cp)
        ]
        for s in range(cp)
    ]
    meta_u = GroupCollectiveMeta.build(uniform, [t_local] * cp, impl="auto")
    assert meta_u.impl == "a2a"
    assert meta_u.impl_reason == "auto_near_uniform"
    # empty map: hops with no hops at all
    empty = [[np.empty(0, np.int64)] * cp for _ in range(cp)]
    meta_e = GroupCollectiveMeta.build(empty, [t_local] * cp, impl="auto")
    assert meta_e.impl == "hops" and meta_e.hops == ()
    assert meta_e.impl_reason == "auto_zero_volume"
    assert 0.0 < AUTO_HOPS_MAX_VOLUME_FRACTION < 1.0


def test_pad_to_rounds_hop_sizes(monkeypatch):
    cp, t_local = 4, 20
    send_map = _send_map(cp, t_local, seed=5, kind="skewed")
    meta = GroupCollectiveMeta.build(
        send_map, [t_local] * cp, pad_to=16, impl="hops"
    )
    assert all(h.size % 16 == 0 for h in meta.hops)
    assert meta.max_send % 16 == 0 and meta.max_recv % 16 == 0
    # env-resolved default: a non-power-of-two rung is rejected at read
    monkeypatch.setenv("MAGI_ATTENTION_COMM_PAD_TO", "12")
    with pytest.raises(ValueError, match="power of two"):
        GroupCollectiveMeta.build(send_map, [t_local] * cp, impl="hops")
    monkeypatch.setenv("MAGI_ATTENTION_COMM_PAD_TO", "4")
    meta4 = GroupCollectiveMeta.build(send_map, [t_local] * cp, impl="hops")
    assert meta4.pad_to == 4 and all(h.size % 4 == 0 for h in meta4.hops)


def test_invalid_impl_rejected():
    cp = 2
    sm = _send_map(cp, 4, seed=0, kind="random")
    with pytest.raises(ValueError, match="GROUP_COLL_IMPL"):
        GroupCollectiveMeta.build(sm, [4] * cp, impl="ring")


def test_qo_comm_parity_between_impls(monkeypatch):
    """The qo-comm runtime (Q+KV cast, O lse-reduced back) must produce
    identical attention outputs under either impl — its comm arrays ride
    the metas' impl-dependent layouts (this image's production
    ``make_qo_comm_attn_fn`` needs new-jax shard_map, so the local fn is
    driven through the compat shim directly)."""
    monkeypatch.setenv("MAGI_ATTENTION_KERNEL_BACKEND", "jnp")
    from magiattention_tpu.parallel.dist_attn import make_attn_params
    from magiattention_tpu.parallel.qo_comm import (
        build_qo_comm_plan,
        qo_comm_attn_local,
    )

    total, cp, h, d = 512, 4, 2, 32
    slices = np.array(
        [
            [0, 256, 0, 256, 1],  # causal doc
            [256, 512, 256, 512, 1],
            [256, 512, 0, 128, 0],  # cross slice -> real comm
        ],
        dtype=np.int64,
    )
    rng = np.random.default_rng(0)
    q = rng.standard_normal((total, h, d)).astype(np.float32)
    k = rng.standard_normal((total, h, d)).astype(np.float32)
    v = rng.standard_normal((total, h, d)).astype(np.float32)

    outs = {}
    for impl in ("a2a", "hops"):
        monkeypatch.setenv("MAGI_ATTENTION_GROUP_COLL_IMPL", impl)
        plan = build_qo_comm_plan(
            slices, total, cp, block_q=64, block_k=64
        )
        params = make_attn_params(
            plan, d, out_dtype="float32", interpret=True
        )
        mesh = _mesh(cp)
        tables = plan.device_tables()
        n_tab = len(tables)

        @functools.partial(
            shard_map,
            mesh=mesh,
            in_specs=(P("cp"),) * (3 + n_tab),
            out_specs=(P("cp"), P("cp")),
            check_vma=False,
        )
        def local(q_, k_, v_, *tabs, _plan=plan, _params=params):
            return qo_comm_attn_local(
                q_, k_, v_, tabs, _plan, _params, axis_name="cp"
            )

        sharded = [
            jax.device_put(t, NamedSharding(mesh, P("cp"))) for t in tables
        ]
        o, l = jax.jit(local)(
            *(jnp.asarray(a) for a in (q, k, v)), *sharded
        )
        outs[impl] = (np.asarray(o), np.asarray(l))
        if impl == "hops":
            assert plan.comm_q.impl == "hops" or plan.comm_kv.impl == "hops"
    np.testing.assert_allclose(
        outs["a2a"][0], outs["hops"][0], rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        outs["a2a"][1], outs["hops"][1], rtol=1e-5, atol=1e-6
    )


def test_hier_intra_hops_cast_bit_identical():
    """Hierarchical 2-level cast: the meta-routed hops intra level must
    reproduce the legacy 6-array a2a path bit-for-bit on a (2, 2) mesh."""
    from magiattention_tpu.comm.hier import (
        HierGroupCollectiveMeta,
        group_cast_hier,
    )

    n_inter = n_intra = 2
    n = n_inter * n_intra
    t_local, d_feat = 10, 4
    send_map = _send_map(n, t_local, seed=37, kind="skewed")
    meta_a, src_a = HierGroupCollectiveMeta.build(
        send_map, [t_local] * n, n_inter, n_intra, pad_to=8, impl="a2a"
    )
    meta_h, src_h = HierGroupCollectiveMeta.build(
        send_map, [t_local] * n, n_inter, n_intra, pad_to=8, impl="hops"
    )
    assert meta_h.impl == "hops" and meta_h.intra_hops
    assert meta_h.max_recv == meta_a.max_recv
    assert meta_h.scheduled_rows_per_rank <= meta_a.padded_rows_per_rank
    for a, b in zip(src_a, src_h):  # planner layout untouched
        assert len(a) == len(b)
        for (sa, ra), (sb, rb) in zip(a, b):
            assert sa == sb
            np.testing.assert_array_equal(ra, rb)

    mesh = Mesh(
        np.array(jax.devices()[:n]).reshape(n_inter, n_intra),
        ("dcn", "ici"),
    )

    def shard2(a):
        a = np.asarray(a)
        return jax.device_put(
            jnp.asarray(a),
            NamedSharding(
                mesh, P(("dcn", "ici"), *([None] * (a.ndim - 1)))
            ),
        )

    rng = np.random.default_rng(2)
    x = shard2(
        np.stack(
            [
                rng.standard_normal((t_local, d_feat)).astype(np.float32)
                for _ in range(n)
            ]
        )
    )

    def run(meta, tables_np):
        arrays = [shard2(a) for a in tables_np]

        @functools.partial(
            shard_map,
            mesh=mesh,
            in_specs=(P(("dcn", "ici")),) * (1 + len(arrays)),
            out_specs=P(("dcn", "ici")),
            check_vma=False,
        )
        def cast(x, *arrs):
            return group_cast_hier(
                x[0], arrs, axis_inter="dcn", axis_intra="ici", meta=meta
            )[None]

        return np.asarray(jax.jit(cast)(x, *arrays))

    legacy = run(
        meta_a,
        (
            meta_a.inter_send_idx,
            meta_a.inter_recv_sel,
            meta_a.inter_recv_valid,
            meta_a.intra_send_idx,
            meta_a.intra_recv_sel,
            meta_a.intra_recv_valid,
        ),
    )
    hops = run(meta_h, meta_h.cast_device_arrays())
    np.testing.assert_array_equal(legacy, hops)
