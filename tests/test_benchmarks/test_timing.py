"""Rates: all the work of a phase over all of its time; completed steps
to the last end."""

import pytest

from benchmarks import timing


def test_completed_rate_ignores_the_windows_edge():
    ends = [10.0 + 20.0, 10.0 + 40.0]  # two steps of 20 s from t=10
    rate, done = timing.completed_rate(ends, 16384, 10.0, 10.0 + 45.0)
    assert done == 2 and rate == pytest.approx(2 * 16384 / 40.0)
    # a third step cut by the edge, whenever it would have ended,
    # changes nothing; nor does a longer window that it still misses
    for third in (10.0 + 45.1, 10.0 + 60.0):
        assert timing.completed_rate(ends + [third], 16384, 10.0, 55.0) == (
            rate, 2,
        )
    assert timing.completed_rate(ends, 16384, 10.0, 10.0 + 59.0) == (rate, 2)
    # once it completes inside, it counts, to its own end
    rate3, done3 = timing.completed_rate(ends + [70.0], 16384, 10.0, 70.0)
    assert done3 == 3 and rate3 == pytest.approx(3 * 16384 / 60.0)


def test_completed_rate_with_no_completed_step():
    assert timing.completed_rate([], 16384, 0.0, 45.0) == (0.0, 0)
    assert timing.completed_rate([50.0], 16384, 0.0, 45.0) == (0.0, 0)


def test_summary_uses_statistics_quantiles():
    s = timing.summary([1.0, 2.0, 3.0, 4.0, 100.0])
    assert s["n"] == 5 and s["median_s"] == 3.0
    assert (s["q1_s"], s["q3_s"]) == (1.5, 52.0)  # the exclusive method
    assert timing.summary([2.0])["q1_s"] == 2.0


def test_timed_units_time_each_unit_and_the_whole_phase():
    calls = []

    def fn():
        calls.append(1)
        return None

    phase = timing.timed_units(fn, 0.0, min_units=4)
    assert len(phase.per_call_s) == len(calls) == phase.calls == 4
    assert all(t >= 0 for t in phase.per_call_s)
    assert phase.elapsed_s >= sum(phase.per_call_s)
    # a unit of three calls gives one per-call entry
    phase = timing.timed_units(fn, 0.0, inner=3, min_units=2)
    assert len(phase.per_call_s) == 2 and phase.calls == 6
    assert len(calls) == 4 + 6


def test_a_phase_rate_is_all_work_over_all_time(monkeypatch):
    # start 0; unit 1 runs 1.0 -> 4.0 (3 calls), unit 2 runs 4.0 -> 7.6
    clock = iter([0.0, 1.0, 4.0, 4.0, 4.0, 7.6, 7.6])
    monkeypatch.setattr(timing.time, "perf_counter", lambda: next(clock))
    phase = timing.timed_units(lambda: None, 5.0, inner=3, min_units=1)
    assert phase.per_call_s == pytest.approx([1.0, 1.2])
    assert phase.calls == 6 and phase.elapsed_s == pytest.approx(7.6)
    # the second before the first unit is in the rate: a median is blind to it
    assert phase.rate(10.0) == pytest.approx(60.0 / 7.6)


def test_a_stall_in_one_unit_moves_the_rate_and_not_the_median():
    steady = timing.Phase([1.0] * 5, 3, 15.0)
    stalled = timing.Phase([1.0, 1.0, 4.0, 1.0, 1.0], 3, 24.0)
    assert timing.summary(stalled.per_call_s)["median_s"] == 1.0
    assert stalled.rate(1.0) == pytest.approx(steady.rate(1.0) * 15.0 / 24.0)


def test_calls_per_unit():
    assert timing.calls_per_unit(0.145, 11.4) == 21  # a unit of 3 s
    assert timing.calls_per_unit(1.022, 26.6) == 3
    assert timing.calls_per_unit(3.1, 26.6) == 1
    assert timing.calls_per_unit(0.05, 0.6) == 4  # a third of a short phase


def test_settle_stops_when_two_agree(monkeypatch):
    clock = iter([0.0, 2.0, 2.0, 2.3, 2.3, 2.6, 9, 9, 9, 9])
    monkeypatch.setattr(timing.time, "perf_counter", lambda: next(clock))
    assert timing.settle(lambda: None) == pytest.approx([2.0, 0.3, 0.3])
