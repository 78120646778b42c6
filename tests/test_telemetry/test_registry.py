"""MetricsRegistry semantics: counters / gauges / histograms, labeled
series, snapshot round-trip, JSON export, reset."""

import json

import pytest

from magiattention_tpu.telemetry.registry import (
    DEFAULT_BUCKET_BOUNDS,
    MetricsRegistry,
    get_registry,
    series_key,
)


@pytest.fixture
def reg():
    return MetricsRegistry()


def test_series_key_canonical_label_order():
    assert series_key("m") == "m"
    assert series_key("m", {"b": 1, "a": 2}) == "m{a=2,b=1}"
    assert series_key("m", {"a": 2, "b": 1}) == series_key(
        "m", {"b": 1, "a": 2}
    )


def test_counter_accumulates(reg):
    reg.counter_inc("c")
    reg.counter_inc("c", 2.5)
    assert reg.counter_value("c") == 3.5
    # unlabeled and labeled series are distinct
    reg.counter_inc("c", 1, rank=0)
    assert reg.counter_value("c") == 3.5
    assert reg.counter_value("c", rank=0) == 1.0
    # missing series reads 0
    assert reg.counter_value("nope") == 0.0


def test_counter_rejects_negative(reg):
    with pytest.raises(ValueError):
        reg.counter_inc("c", -1)


def test_gauge_last_write_wins(reg):
    reg.gauge_set("g", 1.0)
    reg.gauge_set("g", 7.0)
    assert reg.gauge_value("g") == 7.0
    reg.gauge_set("g", 3.0, rank=1)
    assert reg.gauge_value("g", rank=1) == 3.0
    assert reg.gauge_value("missing", default=-1) == -1


def test_histogram_stats_and_buckets(reg):
    for v in (0.5e-5, 5e-4, 5e-4, 2.0):
        reg.histogram_observe("h", v)
    h = reg.snapshot()["histograms"]["h"]
    assert h["count"] == 4
    assert h["min"] == 0.5e-5 and h["max"] == 2.0
    assert h["sum"] == pytest.approx(0.5e-5 + 2 * 5e-4 + 2.0)
    assert h["mean"] == pytest.approx(h["sum"] / 4)
    assert h["bounds"] == list(DEFAULT_BUCKET_BOUNDS)
    assert sum(h["bucket_counts"]) == 4
    # 0.5e-5 <= 1e-5 -> bucket 0; 5e-4 <= 1e-3 -> bucket 2; 2.0 <= 10 -> 6
    assert h["bucket_counts"][0] == 1
    assert h["bucket_counts"][2] == 2
    assert h["bucket_counts"][6] == 1


def test_histogram_overflow_bucket_and_custom_bounds(reg):
    reg.histogram_observe("h", 1e6)
    assert reg.snapshot()["histograms"]["h"]["bucket_counts"][-1] == 1
    reg.histogram_observe("h2", 3.0, bounds=(1.0, 5.0))
    h2 = reg.snapshot()["histograms"]["h2"]
    assert h2["bounds"] == [1.0, 5.0]
    assert h2["bucket_counts"] == [0, 1, 0]


def test_empty_histogram_never_reports_inf(reg):
    reg.histogram_observe("h", 1.0)
    h = reg.snapshot()["histograms"]["h"]
    assert h["min"] == 1.0
    # fresh registry snapshot has no histograms at all
    assert MetricsRegistry().snapshot()["histograms"] == {}


def test_snapshot_round_trips_through_json(reg):
    reg.counter_inc("c", 2, alg="min_heap")
    reg.gauge_set("g", 1.5, rank=3)
    reg.histogram_observe("h", 0.01)
    snap = reg.snapshot()
    assert json.loads(json.dumps(snap)) == snap


def test_snapshot_is_detached_copy(reg):
    reg.counter_inc("c")
    snap = reg.snapshot()
    reg.counter_inc("c")
    assert snap["counters"]["c"] == 1.0
    assert reg.snapshot()["counters"]["c"] == 2.0


def test_dump_writes_json_file(reg, tmp_path):
    reg.gauge_set("g", 4.0)
    path = reg.dump(str(tmp_path / "metrics.json"))
    with open(path) as f:
        assert json.load(f) == reg.snapshot()


def test_reset_clears_everything(reg):
    reg.counter_inc("c")
    reg.gauge_set("g", 1)
    reg.histogram_observe("h", 1)
    reg.reset()
    assert reg.snapshot() == {
        "counters": {},
        "gauges": {},
        "histograms": {},
    }


def test_global_registry_is_a_singleton():
    assert get_registry() is get_registry()


# ---------------------------------------------------------------------------
# approximate percentiles (ISSUE 3 satellite): p50/p95/p99 derived from
# bucket counts — bucket-resolution estimates, clamped to [min, max]
# ---------------------------------------------------------------------------


def test_histogram_reports_percentile_estimates(reg):
    # 100 samples spread across two buckets of (1, 10, 100): 90 low, 10 high
    for _ in range(90):
        reg.histogram_observe("lat", 0.5, bounds=(1.0, 10.0, 100.0))
    for _ in range(10):
        reg.histogram_observe("lat", 50.0, bounds=(1.0, 10.0, 100.0))
    h = reg.snapshot()["histograms"]["lat"]
    # p50 sits inside the first bucket [min, 1.0]; p95/p99 inside the
    # (10, 100] bucket, clamped by the observed max
    assert 0.5 <= h["p50"] <= 1.0
    assert 10.0 <= h["p95"] <= 50.0
    assert 10.0 <= h["p99"] <= 50.0
    assert h["p50"] <= h["p95"] <= h["p99"]


def test_single_value_histogram_percentiles_collapse_to_value(reg):
    reg.histogram_observe("one", 0.025)
    h = reg.snapshot()["histograms"]["one"]
    # min == max clamps every interpolated estimate to the exact value
    assert h["p50"] == h["p95"] == h["p99"] == 0.025


def test_empty_histogram_percentiles_are_none():
    from magiattention_tpu.telemetry.registry import _Histogram

    h = _Histogram().as_dict()
    assert h["p50"] is None and h["p95"] is None and h["p99"] is None


def test_percentiles_clamped_to_observed_range(reg):
    # everything lands in the +inf overflow bucket: estimates must clamp
    # to the observed [vmin, vmax], not the infinite bucket edge
    for v in (150.0, 200.0, 250.0):
        reg.histogram_observe("big", v)
    h = reg.snapshot()["histograms"]["big"]
    for q in ("p50", "p95", "p99"):
        assert 150.0 <= h[q] <= 250.0


def test_estimate_percentiles_is_shared_helper():
    from magiattention_tpu.telemetry.registry import estimate_percentiles

    p50, p95, p99 = estimate_percentiles(
        (1.0, 10.0), [5, 5, 0], 10, 0.1, 8.0
    )
    assert 0.1 <= p50 <= 1.0
    assert 1.0 <= p95 <= 8.0 and 1.0 <= p99 <= 8.0
    assert estimate_percentiles((1.0,), [0, 0], 0, 0.0, 0.0) == [
        None, None, None,
    ]


def test_estimate_percentiles_survives_single_event_histograms():
    """A one-sample histogram must report that sample for every
    percentile, not interpolate into a bucket edge or divide by zero."""
    from magiattention_tpu.telemetry.registry import estimate_percentiles

    bounds = (1e-5, 1e-4, 1e-3, 1e-2)
    p50, p95, p99 = estimate_percentiles(bounds, [0, 0, 1, 0, 0], 1, 3e-4, 3e-4)
    assert p50 == p95 == p99 == pytest.approx(3e-4)
