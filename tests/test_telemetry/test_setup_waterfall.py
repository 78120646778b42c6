"""``exps/setup_waterfall.py``'s reading of a ring (ISSUE 51): the
waterfall that sums to set-up (over the same union the metric
``setup_unspanned_s`` takes), a trace by part, where a trace's unscoped
time lies, and the device's time by the innermost scope, each on a ring
made by hand."""

import importlib.util
import os

import pytest

from magiattention_tpu.telemetry import events

_PATH = os.path.join(
    os.path.dirname(__file__), "..", "..", "exps", "setup_waterfall.py"
)
_spec = importlib.util.spec_from_file_location("setup_waterfall", _PATH)
tool = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(tool)


@pytest.fixture
def ring():
    """Set-up is [100, 120) s: a boot that began before it, an import, a
    key build that jits a small program, a step's trace by part, a
    compile that was a cache load, and the check's trace after the
    window."""
    buf = events.EventBuffer(maxlen=64)

    def rec(name, start, dur, parent=None, **attrs):
        return buf.record(name, start, dur, attrs, parent=parent)["args"]["id"]

    rec("process_boot", 99.5, 8.5, source="proc_stat")
    rec("package_import", 108.0, 0.5, jax_import_s=0.0)
    key = rec("plan_flex_attn", 110.0, 1.0)
    rec("jax.trace", 110.2, 0.3, parent=key, fun_name="tables")
    trace = rec("jax.trace", 112.0, 3.0, fun_name="step")
    proj = rec("trace_part", 112.5, 1.0, parent=trace, scope="magi_proj")
    rec("trace_part", 112.75, 0.5, parent=proj, scope="magi_mla_q")
    rec("trace_part", 114.0, 0.5, parent=trace, scope="magi_proj")
    rec("jax.lower", 115.0, 1.0, fun_name="jit(step)")
    comp = rec("jax.backend_compile", 116.0, 2.0, fun_name="jit(step)")
    rec("jax.cache_load", 116.25, 1.5, parent=comp, fun_name="jit(step)")
    rec("jax.trace", 130.0, 2.0, fun_name="check")
    return buf.events()


def test_the_waterfall_sums_to_set_up(ring):
    rows, gaps, unspanned = tool.waterfall(ring, 100.0, 120.0)
    assert {k: v[0] for k, v in rows.items()} == pytest.approx({
        "process_boot": 8.0,  # clipped to where set-up began
        "package_import": 0.5,
        "plan_flex_attn": 1.0,  # its small program counted once, inside
        "jax.trace step": 3.0,
        "jax.lower jit(step)": 1.0,
        "jax.backend_compile jit(step)": 0.5,
        "jax.cache_load jit(step)": 1.5,
    })
    assert unspanned == pytest.approx(4.5)
    assert sum(v[0] for v in rows.values()) + unspanned == pytest.approx(20.0)
    assert gaps[0] == (
        pytest.approx(2.0), "jax.backend_compile jit(step)", "the window opens"
    )
    assert gaps[1][1:] == ("package_import", "plan_flex_attn")


def test_the_remainder_is_the_metrics(ring, monkeypatch):
    """One union: what ``show`` calls unspanned is what
    ``setup_unspanned_s`` reads on the same ring and window."""
    from benchmarks import harness
    from benchmarks.sources import program_span_uncovered

    class Ring:
        dropped = 0
        events = staticmethod(lambda: ring)

    monkeypatch.setattr(events, "_buffer", Ring)
    monkeypatch.setattr(harness, "_T0", 100.0)
    obs = harness.Observations(
        end_to_end={"setup_s": 20.0}, attempted=1, failed=0, correct=True
    )
    _rows, _gaps, unspanned = tool.waterfall(ring, 100.0, 120.0)
    assert program_span_uncovered.read({}, obs) == pytest.approx(unspanned)


def test_a_trace_splits_by_part_and_the_checks_is_left_out(ring):
    by = tool.trace_by_scope(ring, 120.0)
    assert set(by) == {"jax.trace tables", "jax.trace step", "jax.lower jit(step)"}
    step = by["jax.trace step"]
    assert step["magi_proj"] == [pytest.approx(1.0), 2]  # 0.5 + 0.5 of self
    assert step["magi_mla_q"] == [pytest.approx(0.5), 1]
    assert step["(unscoped)"] == [pytest.approx(1.5), 1]
    assert sum(v[0] for v in step.values()) == pytest.approx(3.0)


def test_unscoped_time_is_placed_before_between_and_after_the_parts(ring):
    by = tool.unscoped_by_place(ring, 120.0)
    assert set(by) == {"jax.trace step"}  # the others hold no part
    # the step's direct parts are [112.5, 113.5) and [114, 114.5) of
    # [112, 115): the nested part is its parent's business
    assert by["jax.trace step"] == pytest.approx([0.5, 0.5, 0.5])
    assert sum(by["jax.trace step"]) == pytest.approx(
        tool.trace_by_scope(ring, 120.0)["jax.trace step"]["(unscoped)"][0]
    )


def test_device_time_goes_to_the_innermost_known_scope():
    trace = {
        "spans": [["phase:window", 0, 100]],
        "ops": [
            [0, "fusion.1", 0, 40, "jit(step)/jvp(magi_proj)/magi_mla_q/dot_general"],
            [0, "fusion.2", 40, 30, "jit(step)/transpose(jvp(magi_proj))/mul"],
            [0, "magi_flex_fwd_kernel.1", 70, 20,
             "jit(step)/magi_merged_kernel/magi_flex_fwd_kernel/pallas_call"],
            [0, "copy.3", 90, 20, ""],  # half of it past the window's end
        ],
    }
    scopes = {"magi_proj", "magi_mla_q", "magi_merged_kernel"}
    assert tool.device_by_scope(trace, scopes) == pytest.approx({
        "magi_mla_q": 40.0, "magi_proj": 30.0, "magi_merged_kernel": 20.0,
        "(none)": 10.0,
    })
    assert tool.device_by_scope({"spans": [], "ops": []}, scopes) == {}
