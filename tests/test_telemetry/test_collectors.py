"""Collectors + events: a real host-side plan build populates the
documented metric catalog; disabled mode is a strict no-op; span events
ring-buffer and export as Chrome trace JSON."""

import json

import pytest

from magiattention_tpu import telemetry
from magiattention_tpu.common.enum import AttnMaskType
from magiattention_tpu.common.ranges import AttnRanges
from magiattention_tpu.meta.dispatch_meta import (
    make_dispatch_meta_from_qk_ranges,
)
from magiattention_tpu.meta.solver.overlap_solver import OverlapConfig
from magiattention_tpu.parallel.dist_attn import build_dist_attn_plan
from magiattention_tpu.telemetry import collectors as C
from magiattention_tpu.telemetry.events import EventBuffer


@pytest.fixture(autouse=True)
def clean_telemetry():
    """Isolate each test: reset the global registry/ring and restore
    env-flag gating afterwards (other suites must not inherit state)."""
    telemetry.set_enabled(None)
    telemetry.reset()
    yield
    telemetry.set_enabled(None)
    telemetry.reset()


def _build_plan(total=2048, cp=4, chunk=256, degree=0):
    qr = AttnRanges.from_ranges([(0, total)])
    kr = AttnRanges.from_ranges([(0, total)])
    mq, _, bucket = make_dispatch_meta_from_qk_ranges(
        qr, kr, [AttnMaskType.CAUSAL], total, total,
        chunk_size=chunk, cp_size=cp,
    )
    return build_dist_attn_plan(
        mq, bucket, overlap_config=OverlapConfig(degree=degree)
    )


def _has_series(snap, name):
    return any(
        k == name or k.startswith(name + "{")
        for sec in snap.values()
        for k in sec
    )


def test_plan_build_populates_required_catalog():
    telemetry.set_enabled(True)
    plan = _build_plan()
    telemetry.record_runtime_costs(
        plan, num_heads_q=8, num_heads_kv=8, head_dim=128,
        bytes_per_elt=2, generation="v5e",
    )
    snap = telemetry.snapshot()
    missing = [
        m for m in telemetry.REQUIRED_PLAN_METRICS
        if not _has_series(snap, m)
    ]
    assert not missing, f"catalog drift, missing: {missing}"


def test_per_rank_series_match_plan():
    telemetry.set_enabled(True)
    plan = _build_plan(cp=4)
    snap = telemetry.snapshot()
    g = snap["gauges"]
    for r in range(4):
        assert (
            g[f"{C.M_COMM_RECV_ROWS}{{rank={r}}}"]
            == plan.comm.recv_total[r]
        )
        assert (
            g[f"{C.M_COMM_SEND_ROWS}{{rank={r}}}"]
            == plan.comm.send_total[r]
        )
    assert g[C.M_PLAN_OVERLAP_DEGREE] == plan.overlap_degree
    assert g[C.M_PLAN_TOTAL_AREA] == plan.total_area
    assert g[C.M_PLAN_AREA_IMBALANCE] == pytest.approx(
        plan.max_rank_area / (plan.total_area / plan.cp_size)
    )


def test_comm_bytes_resolution():
    telemetry.set_enabled(True)
    plan = _build_plan(cp=4)
    telemetry.record_runtime_costs(
        plan, num_heads_q=8, num_heads_kv=2, head_dim=64,
        bytes_per_elt=2, generation="v5e",
    )
    g = telemetry.snapshot()["gauges"]
    row_bytes = 2 * 2 * 64 * 2  # K+V * hkv * d * bytes
    for r in range(4):
        assert (
            g[f"{C.M_COMM_BYTES_RANK}{{rank={r}}}"]
            == plan.comm.recv_total[r] * row_bytes
        )
    assert g[C.M_MODELED_FLOPS] == 4.0 * plan.total_area * 8 * 64


def test_padding_overhead_ratio_recorded():
    """Satellite of ISSUE 2 (VERDICT: never measured), per-kind +
    impl-aware since ISSUE 5: the group-cast build records the
    scheduled-vs-true volume of the SELECTED impl under kind=cast, plus
    the true / legacy-padded / scheduled row gauges and the impl choice.
    For a causal mask over a contiguous dispatch the send map is uneven,
    so the ratio must be a real overhead (> 1)."""
    telemetry.set_enabled(True)
    plan = _build_plan(cp=4)
    g = telemetry.snapshot()["gauges"]
    comm = plan.comm
    key = f"{C.M_COMM_PADDING_OVERHEAD}{{kind=cast}}"
    assert g[key] == pytest.approx(comm.padding_overhead_ratio)
    assert g[key] > 1.0
    assert g[C.M_COMM_TRUE_ROWS] == comm.true_rows_total
    assert g[C.M_COMM_SCHEDULED_ROWS] == comm.scheduled_rows_per_rank
    assert g[C.M_COMM_PADDED_ROWS] == comm.padded_rows_per_rank
    assert comm.scheduled_rows_per_rank <= comm.padded_rows_per_rank
    choice = [k for k in g if k.startswith(C.M_COMM_IMPL_CHOICE + "{")]
    assert len(choice) == 1 and f"impl={comm.impl}" in choice[0]


def test_padding_overhead_zero_when_cast_moves_nothing():
    """A fully-local mask (block-diagonal varlen matching the chunking)
    casts no rows: the ratio reads 0.0, not inf."""
    telemetry.set_enabled(True)
    from magiattention_tpu.comm.group_collective import GroupCollectiveMeta
    import numpy as np

    empty = [[np.empty(0, np.int64)] * 2 for _ in range(2)]
    GroupCollectiveMeta.build(empty, [8, 8])
    g = telemetry.snapshot()["gauges"]
    assert g[f"{C.M_COMM_PADDING_OVERHEAD}{{kind=cast}}"] == 0.0


def test_unknown_generation_does_not_raise():
    telemetry.set_enabled(True)
    plan = _build_plan()
    telemetry.record_runtime_costs(
        plan, num_heads_q=8, num_heads_kv=8, head_dim=128,
        bytes_per_elt=2, generation="not-a-tpu",
    )
    g = telemetry.snapshot()["gauges"]
    # bytes + flops still recorded; only the cost factors are skipped
    assert C.M_MODELED_FLOPS in g
    assert C.M_MODELED_CALC_S not in g


def test_staged_plan_records_stage_count():
    telemetry.set_enabled(True)
    _build_plan(degree=2)
    g = telemetry.snapshot()["gauges"]
    assert g[C.M_PLAN_OVERLAP_DEGREE] == 2
    assert g[C.M_PLAN_NUM_STAGES] >= 1
    assert g[C.M_PLAN_KERNEL_STEPS_FWD] >= 1
    assert g[C.M_PLAN_KERNEL_STEPS_BWD] >= 1


def test_auto_degree_records_choice_and_makespan():
    telemetry.set_enabled(True)
    _build_plan(degree=None)
    g = telemetry.snapshot()["gauges"]
    assert g[C.M_OVERLAP_AUTO_DEGREE] >= 1
    assert g[C.M_OVERLAP_MAKESPAN] > 0


def test_shrinking_cp_size_drops_stale_rank_series():
    """A cp=4 plan after a cp=8 one must not leave rank=4..7 series in
    the snapshot — 'what did the last plan do' means the LAST plan."""
    telemetry.set_enabled(True)
    plan8 = _build_plan(total=4096, cp=8, chunk=256)
    telemetry.record_runtime_costs(
        plan8, num_heads_q=8, num_heads_kv=8, head_dim=128,
        bytes_per_elt=2, generation="v5e",
    )
    assert f"{C.M_COMM_RECV_ROWS}{{rank=7}}" in telemetry.snapshot()["gauges"]
    plan4 = _build_plan(total=4096, cp=4, chunk=256)
    telemetry.record_runtime_costs(
        plan4, num_heads_q=8, num_heads_kv=8, head_dim=128,
        bytes_per_elt=2, generation="v5e",
    )
    g = telemetry.snapshot()["gauges"]
    for name in (
        C.M_COMM_RECV_ROWS,
        C.M_COMM_SEND_ROWS,
        C.M_COMM_BYTES_RANK,
        C.M_DISPATCH_CHUNKS_RANK,
    ):
        ranks = {k for k in g if k.startswith(name + "{")}
        assert ranks == {f"{name}{{rank={r}}}" for r in range(4)}, ranks


def test_disabled_mode_is_a_strict_noop():
    telemetry.set_enabled(False)
    _build_plan(degree=None)
    assert telemetry.snapshot() == {
        "counters": {},
        "gauges": {},
        "histograms": {},
    }
    assert len(telemetry.get_event_buffer()) == 0


def test_uneven_dispatch_reports_token_imbalance():
    telemetry.set_enabled(True)
    from magiattention_tpu.meta.solver.dispatch_solver import DispatchConfig

    total, chunk, cp = 2560, 256, 4  # 10 chunks over 4 ranks -> uneven
    qr = AttnRanges.from_ranges([(0, total)])
    kr = AttnRanges.from_ranges([(0, total)])
    make_dispatch_meta_from_qk_ranges(
        qr, kr, [AttnMaskType.CAUSAL], total, total,
        chunk_size=chunk, cp_size=cp,
        dispatch_config=DispatchConfig(uneven_shard=True),
    )
    g = telemetry.snapshot()["gauges"]
    assert g[C.M_DISPATCH_UNEVEN] == 1
    assert g[C.M_DISPATCH_TOKEN_IMBALANCE] > 1.0


# ---------------------------------------------------------------------------
# span events
# ---------------------------------------------------------------------------


def test_span_records_event_with_attrs():
    telemetry.set_enabled(True)
    with telemetry.span("unit-span", cp=4):
        pass
    evs = telemetry.get_event_buffer().events()
    ev = [e for e in evs if e["name"] == "unit-span"][0]
    assert ev["ph"] == "X"
    assert ev["dur"] >= 0
    assert ev["args"] == {"cp": 4, "id": ev["args"]["id"]}  # + its own id


def test_plan_build_emits_span():
    telemetry.set_enabled(True)
    _build_plan()
    names = [e["name"] for e in telemetry.get_event_buffer().events()]
    assert "build_dist_attn_plan" in names


def test_ring_buffer_keeps_most_recent():
    buf = EventBuffer(maxlen=3)
    for i in range(5):
        buf.record(f"e{i}", 0.0, 0.0)
    assert [e["name"] for e in buf.events()] == ["e2", "e3", "e4"]


def test_dump_events_chrome_trace_schema(tmp_path):
    telemetry.set_enabled(True)
    with telemetry.span("exported"):
        pass
    path = telemetry.dump_events(str(tmp_path / "trace.json"))
    with open(path) as f:
        trace = json.load(f)
    assert "traceEvents" in trace
    ev = trace["traceEvents"][-1]
    assert set(ev) >= {"name", "ph", "ts", "dur", "pid", "tid"}


def test_dump_metrics_round_trip(tmp_path):
    telemetry.set_enabled(True)
    _build_plan()
    path = telemetry.dump_metrics(str(tmp_path / "metrics.json"))
    with open(path) as f:
        assert json.load(f) == telemetry.snapshot()


def test_get_telemetry_snapshot_api_surface():
    from magiattention_tpu.api import get_telemetry_snapshot

    telemetry.set_enabled(True)
    _build_plan()
    snap = get_telemetry_snapshot()
    assert snap == telemetry.snapshot()
    assert snap["counters"][C.M_PLAN_BUILDS] == 1.0


def test_summary_renders_headline_block():
    telemetry.set_enabled(True)
    plan = _build_plan()
    telemetry.record_runtime_costs(
        plan, num_heads_q=8, num_heads_kv=8, head_dim=128,
        bytes_per_elt=2, generation="v5e",
    )
    text = telemetry.telemetry_summary()
    assert "telemetry summary" in text
    assert "overlap degree" in text
    assert "comm bytes/rank" in text
    # renders off a detached snapshot too
    assert telemetry.telemetry_summary(telemetry.snapshot()) == text
