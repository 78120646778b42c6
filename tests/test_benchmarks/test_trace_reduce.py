"""The reduction from a trace to numbers: exact on a hand-made trace,
and on a small trace recorded on the v5e (``data/recorded_trace.json``:
the plain form of three forward and two forward+backward iterations of
the packed 64k cell, cut from this PR's traced chip run)."""

import json
import os

import pytest

from benchmarks import trace_reduce as tr
from benchmarks.trace_reduce import Op, Span, Trace

HERE = os.path.dirname(os.path.abspath(__file__))


def _pattern(metric: str) -> str:
    """The kernel pattern of a metric's own file: what the runs use."""
    path = os.path.join(HERE, "..", "..", "benchmarks", "metrics", metric + ".json")
    with open(path) as f:
        return json.load(f)["source"]["pattern"]


FWD, BWD = _pattern("flex_fwd_roofline"), _pattern("flex_bwd_roofline")


def _hand_made() -> Trace:
    # device 0: kernel 0-40, copy 30-50 (overlaps), comm 60-80, kernel 70-90
    # device 1: kernel 0-20, comm 20-60 (exposed 20-60), kernel 100-110
    ops = [
        Op(0, "magi_merged_kernel.3", 0, 40, "jit(f)/magi_merged_kernel/pallas_call"),
        Op(0, "copy.1", 30, 20),
        Op(0, "collective-permute.2", 60, 20),
        Op(0, "magi_merged_kernel.4", 70, 20,
           "jit(f)/transpose(jvp())/magi_merged_kernel/pallas_call"),
        Op(1, "magi_merged_kernel.3", 0, 20, "jit(f)/magi_merged_kernel/pallas_call"),
        Op(1, "collective-permute.2", 20, 40),
        Op(1, "magi_merged_kernel.4", 100, 10,
           "jit(f)/transpose(jvp())/magi_merged_kernel/pallas_call"),
    ]
    spans = [
        Span("phase:window", 0, 120),
        Span("plan", 50, 8),
        Span("step", 90, 30),
        Span("compile", 95, 10),  # nested in step: the innermost wins
    ]
    return Trace(ops, spans)


def test_busy_and_idle_are_a_union_averaged_over_devices():
    t = _hand_made()
    # device 0 busy 0-50, 60-90 = 80 ns; device 1 busy 0-60, 100-110 = 70 ns
    assert tr.busy_seconds(t, 0, 120) == pytest.approx(75e-9)
    assert tr.idle_share_pct(t, 0, 120) == pytest.approx(100 * (1 - 75 / 120))
    # clipping to a sub-window
    assert tr.busy_seconds(t, 35, 65) == pytest.approx((20 + 25) / 2 * 1e-9)


def test_kernel_seconds_by_name_or_scope():
    t = _hand_made()
    assert tr.kernel_seconds(t, r"magi_\w*kernel", 0, 120) == pytest.approx(45e-9)
    assert tr.kernel_seconds(t, BWD, 0, 120) == pytest.approx(15e-9)
    assert tr.kernel_seconds(t, FWD, 0, 120) == pytest.approx(30e-9)
    assert tr.kernel_seconds(t, r"^copy", 0, 120) == pytest.approx(20e-9)
    assert tr.kernel_seconds(t, "no_such_kernel", 0, 120) == 0.0


def test_exposed_comm_is_comm_with_no_compute_under_it():
    total, exposed = tr.exposed_comm_seconds(_hand_made(), 0, 120)
    # device 0: 20 total, 10 hidden under the kernel; device 1: 40, all exposed
    assert total == pytest.approx(30e-9)
    assert exposed == pytest.approx(25e-9)


def test_idle_gaps_go_to_the_innermost_host_span():
    gaps = dict(tr.idle_gaps(_hand_made(), 0, 120))
    # device 0 idle: 50-60 (plan covers 50-58) and 90-120 (step, compile 95-105)
    assert gaps == {
        "plan": pytest.approx(8e-9), "other": pytest.approx(2e-9),
        "compile": pytest.approx(10e-9), "step": pytest.approx(20e-9),
    }


def test_top_ops_and_json_round_trip():
    t = _hand_made()
    top = tr.top_ops(t, 0, 120, n=2)
    assert top[0] == ["magi_merged_kernel.3", pytest.approx(30e-9)]
    again = Trace.from_json(json.loads(json.dumps(t.to_json())))
    assert again.ops == t.ops and again.spans == t.spans
    assert again.phase("window") == (0, 120) and again.phase("fwd") is None


def test_hlo_scopes_reads_instruction_names():
    text = (
        '  %magi_merged_kernel.5 = (f32[8]{0}) custom-call(%a), '
        'custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(f)/transpose(jvp())/magi_merged_kernel/pallas_call" '
        'stack_frame_id=6}\n'
        '  ROOT %copy.1 = f32[8]{0} copy(%b), metadata={op_name="jit(f)/x"}\n'
    )
    assert tr.hlo_scopes(text) == {
        "magi_merged_kernel.5":
            "jit(f)/transpose(jvp())/magi_merged_kernel/pallas_call",
        "copy.1": "jit(f)/x",
    }


@pytest.fixture(scope="module")
def recorded() -> Trace:
    with open(os.path.join(HERE, "data", "recorded_trace.json")) as f:
        return Trace.from_json(json.load(f))


def test_recorded_trace_reduces_as_the_chip_run_did(recorded):
    """The numbers the chip run printed for these iterations, again."""
    with open(os.path.join(HERE, "data", "recorded_trace.expect.json")) as f:
        want = json.load(f)
    fwd, bwd = recorded.phase("fwd"), recorded.phase("fwdbwd")
    assert fwd and bwd
    pat_f, pat_b = FWD, BWD
    got = {
        "fwd_kernel_s": tr.kernel_seconds(recorded, pat_f, *fwd),
        "bwd_kernel_s": tr.kernel_seconds(recorded, pat_b, *bwd),
        "fwd_kernel_in_bwd_phase_s": tr.kernel_seconds(recorded, pat_f, *bwd),
        "busy_fwd_s": tr.busy_seconds(recorded, *fwd),
        "idle_bwd_pct": tr.idle_share_pct(recorded, *bwd),
    }
    assert got == pytest.approx(want, rel=1e-9)
    # forward programs run no backward kernel
    assert tr.kernel_seconds(recorded, pat_b, *fwd) == 0.0
    # one forward kernel a forward iteration, in both programs alike
    assert got["fwd_kernel_s"] / 3 == pytest.approx(
        got["fwd_kernel_in_bwd_phase_s"] / 2, rel=1e-3
    )
    # the kernels are nearly all of the busy time, and busy fits its phase
    assert 0.9 < got["fwd_kernel_s"] / got["busy_fwd_s"] <= 1.0
    assert got["busy_fwd_s"] <= (fwd[1] - fwd[0]) / 1e9
    names = {o.name for o in recorded.ops}
    assert any("magi_merged_kernel" in n for n in names)
    assert {s.name for s in recorded.spans} >= {"phase:fwd", "phase:fwdbwd", "step"}
