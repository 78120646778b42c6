"""Property tests for the plan sanitizer (analysis/plan_sanity.py):
clean artifacts validate, every mutation class fails, and the
MAGI_ATTENTION_VALIDATE plumbing + telemetry counters work end-to-end."""

import dataclasses

import numpy as np
import pytest

from magiattention_tpu.analysis.plan_sanity import (
    PlanValidationError,
    validate_comm_meta,
    validate_plan,
    validate_slices,
)
from magiattention_tpu.comm.group_collective import GroupCollectiveMeta


def _send_map(cp, T=32, seed=3):
    rng = np.random.default_rng(seed)
    return [
        [
            rng.choice(T, size=int(rng.integers(1, 10)), replace=False)
            if s != d else np.empty(0, np.int64)
            for d in range(cp)
        ]
        for s in range(cp)
    ]


@pytest.fixture(params=["a2a", "hops"])
def meta(request):
    return GroupCollectiveMeta.build(
        _send_map(4), [32] * 4, impl=request.param
    )


# ---------------------------------------------------------------------------
# slices
# ---------------------------------------------------------------------------


def test_clean_slices_pass():
    validate_slices(
        [(0, 64, 0, 64, 1), (64, 128, 0, 128, 0), (0, 32, 0, 32, 3)],
        128, 128,
    )


def test_attn_slice_objects_accepted():
    from magiattention_tpu.common.enum import AttnMaskType
    from magiattention_tpu.common.range import AttnRange
    from magiattention_tpu.meta.containers import AttnSlice

    s = AttnSlice(AttnRange(0, 64), AttnRange(0, 64), AttnMaskType.CAUSAL)
    validate_slices([s], 64, 64)
    # a stepped type's word (ISSUE 42): 9 = CAUSAL at step 4
    validate_slices([(0, 64, 0, 64, int(AttnMaskType.CAUSAL.with_step(4)))], 64, 64)


@pytest.mark.parametrize(
    "bad",
    [
        (0, 128, 0, 64, 1),  # q OOB
        (-8, 64, 0, 64, 0),  # negative start
        (0, 64, 0, 96, 0),  # k OOB
        (8, 8, 0, 64, 0),  # empty q
        (0, 64, 16, 16, 0),  # empty k
        (0, 64, 0, 64, 99),  # unknown type: a step past 2**15
        (0, 64, 0, 64, -1),  # unknown type
        (0, 64, 0, 16, 3),  # bicausal with empty rows
    ],
)
def test_malformed_slices_fail(bad):
    with pytest.raises(PlanValidationError):
        validate_slices([bad], 64, 64)


# ---------------------------------------------------------------------------
# comm metas
# ---------------------------------------------------------------------------


def test_clean_meta_passes(meta):
    validate_comm_meta(meta, num_local_rows=32)


def test_recv_non_permutation_fails(meta):
    rs = np.array(meta.recv_sel, copy=True)
    d = next(i for i in range(4) if meta.recv_total[i] >= 2)
    rs[d, 1] = rs[d, 0]  # two output slots read one source row
    with pytest.raises(PlanValidationError, match="permutation"):
        validate_comm_meta(dataclasses.replace(meta, recv_sel=rs))


def test_recv_pad_not_trash_fails(meta):
    rs = np.array(meta.recv_sel, copy=True)
    d = next(
        (i for i in range(4) if meta.recv_total[i] < meta.max_recv), None
    )
    if d is None:
        pytest.skip("no padded recv slot in this fixture")
    rs[d, meta.max_recv - 1] = 0  # pad slot aimed at a real row
    with pytest.raises(PlanValidationError, match="trash"):
        validate_comm_meta(dataclasses.replace(meta, recv_sel=rs))


def test_scheduled_below_true_fails(meta):
    # claim hop scheduling but drop every hop: scheduled rows 0 < true
    broken = dataclasses.replace(meta, impl="hops", hops=())
    with pytest.raises(PlanValidationError, match="scheduled"):
        validate_comm_meta(broken)


def test_send_recv_total_mismatch_fails(meta):
    st = list(meta.send_total)
    st[0] += 8
    with pytest.raises(PlanValidationError, match="send_total"):
        validate_comm_meta(dataclasses.replace(meta, send_total=tuple(st)))


def test_send_idx_oob_fails(meta):
    with pytest.raises(PlanValidationError, match="num_local_rows"):
        validate_comm_meta(meta, num_local_rows=4)  # real rows are < 32


def test_hop_unpadded_size_fails():
    meta = GroupCollectiveMeta.build(_send_map(4), [32] * 4, impl="hops")
    if not meta.hops:
        pytest.skip("fixture resolved to zero hops")
    h0 = meta.hops[0]
    bad_hop = dataclasses.replace(
        h0,
        size=h0.size + 1,
        send_idx=np.pad(h0.send_idx, ((0, 0), (0, 1))),
        recv_pos=np.pad(h0.recv_pos, ((0, 0), (0, 1))),
        seg_ids=np.pad(h0.seg_ids, ((0, 0), (0, 1))),
    )
    with pytest.raises(PlanValidationError, match="pad"):
        validate_comm_meta(
            dataclasses.replace(meta, hops=(bad_hop,) + meta.hops[1:])
        )


def test_duplicate_hop_shift_fails():
    meta = GroupCollectiveMeta.build(_send_map(4), [32] * 4, impl="hops")
    if len(meta.hops) < 1:
        pytest.skip("fixture resolved to zero hops")
    with pytest.raises(PlanValidationError, match="duplicate"):
        validate_comm_meta(
            dataclasses.replace(meta, hops=meta.hops + (meta.hops[0],))
        )


# ---------------------------------------------------------------------------
# whole plans
# ---------------------------------------------------------------------------


def _plan(degree=0, cp=4, total=1024):
    from magiattention_tpu.common.enum import AttnMaskType
    from magiattention_tpu.common.ranges import AttnRanges
    from magiattention_tpu.meta.dispatch_meta import (
        make_dispatch_meta_from_qk_ranges,
    )
    from magiattention_tpu.meta.solver.overlap_solver import OverlapConfig
    from magiattention_tpu.parallel.dist_attn import build_dist_attn_plan

    qr = AttnRanges.from_ranges([(0, total)])
    mq, _, bucket = make_dispatch_meta_from_qk_ranges(
        qr, qr, [AttnMaskType.CAUSAL], total, total,
        chunk_size=total // 16, cp_size=cp,
    )
    oc = OverlapConfig(degree=degree, min_stage_rows=64) if degree else None
    return build_dist_attn_plan(mq, bucket, overlap_config=oc), bucket


@pytest.mark.parametrize("degree", [0, 2])
def test_clean_plan_passes(degree):
    plan, bucket = _plan(degree=degree)
    validate_plan(plan, total_area=bucket.area)


def test_plan_wrong_total_area_fails():
    plan, bucket = _plan()
    with pytest.raises(PlanValidationError, match="total_area"):
        validate_plan(plan, total_area=bucket.area + 1)


def test_plan_lost_area_fails():
    plan, _ = _plan()
    broken = dataclasses.replace(
        plan, max_rank_area=plan.total_area // (2 * plan.cp_size)
    )
    with pytest.raises(PlanValidationError, match="unassigned"):
        validate_plan(broken)


def test_staged_plan_double_count_fails():
    plan, _ = _plan(degree=2)
    assert plan.stages, "fixture must produce stages"
    big = dataclasses.replace(plan.stages[0], max_rank_area=plan.total_area)
    broken = dataclasses.replace(plan, stages=(big,) + plan.stages[1:])
    with pytest.raises(PlanValidationError, match="double-count"):
        validate_plan(broken)


def test_staged_plan_bad_stage_comm_fails():
    plan, _ = _plan(degree=2)
    sp = plan.stages[0]
    st = list(sp.comm.send_total)
    st[0] += 8
    bad = dataclasses.replace(
        sp, comm=dataclasses.replace(sp.comm, send_total=tuple(st))
    )
    broken = dataclasses.replace(plan, stages=(bad,) + plan.stages[1:])
    with pytest.raises(PlanValidationError):
        validate_plan(broken)


# ---------------------------------------------------------------------------
# env plumbing + telemetry counters
# ---------------------------------------------------------------------------


def test_validate_mode_values(monkeypatch):
    from magiattention_tpu import env

    assert env.validate_mode() == "off"
    for mode in ("plan", "trace", "off"):
        monkeypatch.setenv("MAGI_ATTENTION_VALIDATE", mode)
        assert env.validate_mode() == mode
    monkeypatch.setenv("MAGI_ATTENTION_VALIDATE", "bogus")
    with pytest.raises(ValueError, match="MAGI_ATTENTION_VALIDATE"):
        env.validate_mode()


def test_build_hook_runs_under_plan_mode(monkeypatch):
    monkeypatch.setenv("MAGI_ATTENTION_VALIDATE", "plan")
    plan, _ = _plan()  # clean build must pass through the hook
    assert plan is not None


def test_build_hook_trace_mode(monkeypatch):
    monkeypatch.setenv("MAGI_ATTENTION_VALIDATE", "trace")
    plan, _ = _plan(degree=2)
    assert plan is not None


@pytest.mark.parametrize("mode", ["plan", "trace"])
def test_build_hook_hierarchical_plan(monkeypatch, mode):
    """Hier plans carry a HierGroupCollectiveMeta — the sanitizer must
    take its reduced validation path, not crash on missing flat attrs
    (regression: AttributeError under MAGI_ATTENTION_VALIDATE=plan)."""
    from magiattention_tpu.common.enum import AttnMaskType
    from magiattention_tpu.common.ranges import AttnRanges
    from magiattention_tpu.meta.dispatch_meta import (
        make_dispatch_meta_from_qk_ranges,
    )
    from magiattention_tpu.parallel.dist_attn import build_dist_attn_plan

    monkeypatch.setenv("MAGI_ATTENTION_VALIDATE", mode)
    total = 1024
    qr = AttnRanges.from_ranges([(0, total)])
    mq, _, bucket = make_dispatch_meta_from_qk_ranges(
        qr, qr, [AttnMaskType.CAUSAL], total, total,
        chunk_size=total // 16, cp_size=4,
    )
    plan = build_dist_attn_plan(mq, bucket, cp_mesh_shape=(2, 2))
    assert plan.hier == (2, 2)
    validate_plan(plan, total_area=bucket.area)


def test_validate_counters(monkeypatch):
    from magiattention_tpu import telemetry

    telemetry.set_enabled(True)
    telemetry.reset()
    try:
        plan, bucket = _plan()
        validate_plan(plan, total_area=bucket.area)
        with pytest.raises(PlanValidationError):
            validate_slices([(0, 128, 0, 64, 1)], 64, 64)
        snap = telemetry.snapshot()
        counters = snap.get("counters", {})
        assert counters.get("magi_validate_plan_checks", 0) >= 2
        assert counters.get("magi_validate_failures", 0) >= 1
    finally:
        telemetry.set_enabled(None)
        telemetry.reset()
