"""The sources ISSUE 24 adds: a kernel's milliseconds and its share of
busy time from a hand-made trace, the program's spans before the window
from a hand-made span buffer, and all of them through the command at
toy size."""

import io
import json
import os
import shutil
from contextlib import redirect_stdout

import pytest

from benchmarks import harness
from benchmarks.sources import program_span, trace_kernel_ms, trace_kernel_share
from benchmarks.trace_reduce import Op, Span, Trace
from magiattention_tpu import telemetry

REPO = harness.CHECKOUT
TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "toy")
NEW = (
    "flex_fwd_kernel_ms", "flex_dq_kernel_ms", "flex_dkv_kernel_ms",
    "train_flex_kernel_share", "key_build_ms", "program_trace_s",
    "program_lower_s", "program_compile_s", "program_cache_load_s",
)


def _metric(name: str) -> dict:
    with open(os.path.join(REPO, "benchmarks", "metrics", name + ".json")) as f:
        return json.load(f)


def _obs(**kw) -> harness.Observations:
    return harness.Observations(
        end_to_end={}, attempted=1, failed=0, correct=True, **kw
    )


def _kernel_trace() -> Trace:
    """Two devices, a forward phase of 2 iterations and a forward +
    backward phase of 1, names and scopes as the v5e compiler gives
    them (tests/test_aot_compile_tpu.py)."""
    fwd = "jit(fwd)/magi_merged_kernel/magi_flex_fwd_kernel/pallas_call"
    jvp = "jit(fwdbwd)/jvp()/magi_merged_kernel/magi_flex_fwd_kernel/pallas_call"
    bwd = "jit(fwdbwd)/transpose(jvp())/magi_merged_kernel/magi_flex_{}_kernel/pallas_call"
    ops = []
    for dev in (0, 1):
        ops += [
            Op(dev, "magi_flex_fwd_kernel.1", 0, 30_000_000, fwd),
            Op(dev, "copy.3", 30_000_000, 2_000_000),
            Op(dev, "magi_flex_fwd_kernel.1", 40_000_000, 30_000_000, fwd),
            Op(dev, "magi_flex_fwd_kernel.1", 100_000_000, 31_000_000, jvp),
            Op(dev, "magi_flex_dq_kernel.1", 140_000_000, 50_000_000,
               bwd.format("dq")),
            Op(dev, "magi_flex_dkv_kernel.1", 200_000_000, 90_000_000,
               bwd.format("dkv")),
            Op(dev, "fusion.7", 290_000_000, 9_000_000),
        ]
    spans = [
        Span("phase:window", 0, 300_000_000),
        Span("phase:fwd", 0, 100_000_000),
        Span("phase:fwdbwd", 100_000_000, 200_000_000),
    ]
    return Trace(ops, spans)


@pytest.mark.parametrize(
    "metric,want_ms",
    [("flex_fwd_kernel_ms", 30.0), ("flex_dq_kernel_ms", 50.0),
     ("flex_dkv_kernel_ms", 90.0)],
)
def test_kernel_ms_is_device_time_per_iteration(metric, want_ms):
    obs = _obs(iters={"fwd": 2, "fwdbwd": 1}, trace=_kernel_trace(), chips=2)
    spec = _metric(metric)["source"]
    assert trace_kernel_ms.read(spec, obs) == pytest.approx(want_ms)
    # the phase was not traced, or ran nothing: left out of the line
    assert trace_kernel_ms.read({**spec, "phase": "steady"}, obs) is None
    assert trace_kernel_ms.read(spec, _obs(trace=_kernel_trace())) is None


def test_dq_plus_dkv_is_the_time_behind_the_backward_roofline():
    from benchmarks import trace_reduce

    trace = _kernel_trace()
    phase = trace.phase("fwdbwd")
    both = trace_reduce.kernel_seconds(
        trace, _metric("flex_bwd_roofline")["source"]["pattern"], *phase
    )
    obs = _obs(iters={"fwdbwd": 1}, trace=trace)
    parts = sum(
        trace_kernel_ms.read(_metric(m)["source"], obs)
        for m in ("flex_dq_kernel_ms", "flex_dkv_kernel_ms")
    )
    assert parts == pytest.approx(1e3 * both)


def test_kernel_names_of_the_parent_read_nothing():
    """The program before ISSUE 24 names every kernel by its caller's
    scope: the new readers find nothing and do not raise."""
    old = [
        Op(0, "magi_merged_kernel.5", 0, 10,
           "jit(f)/transpose(jvp())/magi_merged_kernel/pallas_call"),
    ]
    trace = Trace(old, [Span("phase:window", 0, 20), Span("phase:fwd", 0, 20),
                        Span("phase:fwdbwd", 0, 20)])
    obs = _obs(iters={"fwd": 1, "fwdbwd": 1}, trace=trace)
    for m in NEW[:3]:
        assert trace_kernel_ms.read(_metric(m)["source"], obs) is None
    spec = _metric("train_flex_kernel_share")["source"]
    assert trace_kernel_share.read(spec, obs) is None


def test_kernel_share_is_kernel_time_over_busy_time():
    obs = _obs(trace=_kernel_trace(), chips=2)
    spec = _metric("train_flex_kernel_share")["source"]
    # kernels 30 + 30 + 31 + 50 + 90 = 231 ms of 242 ms busy
    assert trace_kernel_share.read(spec, obs) == pytest.approx(100 * 231 / 242)
    assert trace_kernel_share.read({**spec, "phase": "steady"}, obs) is None


# -- program_span -------------------------------------------------------------


@pytest.fixture
def span_buffer(monkeypatch):
    """A span buffer made by hand, on the harness's clock: set-up is the
    first 10 s after ``harness._T0``."""
    telemetry.set_enabled(True)
    telemetry.reset()
    buf = telemetry.get_event_buffer()
    t0 = harness._T0

    def record(name, start, dur, parent=None, **attrs):
        return buf.record(name, t0 + start, dur, attrs, parent=parent)["args"]["id"]

    yield record
    telemetry.set_enabled(None)
    telemetry.reset()


def test_program_span_sums_what_ended_before_the_window(span_buffer):
    kb = span_buffer("key_build", 1.0, 0.5, cache="miss")
    span_buffer("build_dist_attn_plan", 1.1, 0.01, parent=kb)
    reuse = span_buffer("key_build", 1.2, 0.2, parent=kb)  # plan reuse
    span_buffer("tile_choice", 1.25, 0.1, parent=reuse)
    span_buffer("key_build", 3.0, 0.25, cache="hit")
    span_buffer("key_build", 9.9, 0.2)  # ends after the window opened
    span_buffer("key_build", 30.0, 1.0)  # the check's
    obs = _obs()
    obs.end_to_end["setup_s"] = 10.0
    spec = _metric("key_build_ms")["source"]
    assert spec["spans"] == ["key_build", "plan_flex_attn"]
    # the nested key is part of its parent: 0.5 + 0.25 s
    assert program_span.read(spec, obs) == pytest.approx(750.0)
    own = {"kind": "program_span", "spans": ["key_build"], "self": True}
    # self time: 0.5 - (0.01 + 0.2) and 0.2 - 0.1 and 0.25
    assert program_span.read(own, obs) == pytest.approx(0.29 + 0.1 + 0.25)
    # a span that never ran reads 0 in a program whose spans form a tree
    assert program_span.read(_metric("program_cache_load_s")["source"], obs) == 0.0


def test_program_compile_is_what_the_cache_did_not_load(span_buffer):
    hit = span_buffer("jax.backend_compile", 2.0, 0.30, fun_name="jit(a)")
    span_buffer("jax.cache_load", 2.05, 0.25, parent=hit, fun_name="jit(a)")
    span_buffer("jax.backend_compile", 3.0, 4.0, fun_name="jit(b)")
    span_buffer("jax.trace", 1.0, 0.5, fun_name="a")
    span_buffer("jax.lower", 1.5, 0.25, fun_name="jit(a)")
    obs = _obs()
    obs.end_to_end["setup_s"] = 10.0
    read = lambda m: program_span.read(_metric(m)["source"], obs)  # noqa: E731
    assert read("program_compile_s") == pytest.approx(4.05)
    assert read("program_cache_load_s") == pytest.approx(0.25)
    assert read("program_trace_s") == pytest.approx(0.5)
    assert read("program_lower_s") == pytest.approx(0.25)


def test_program_without_a_span_tree_reads_nothing(span_buffer, monkeypatch):
    span_buffer("key_build", 1.0, 0.5)
    monkeypatch.delattr(telemetry, "span_self_seconds")
    obs = _obs()
    obs.end_to_end["setup_s"] = 10.0
    for m in NEW[4:]:
        assert program_span.read(_metric(m)["source"], obs) is None


# -- through the command ---------------------------------------------------------


@pytest.fixture
def toy_with_the_new_metrics(tmp_path):
    """The toy benchmark plus this PR's metric files and entries."""
    root = tmp_path / "toy"
    shutil.copytree(TOY, root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {
        "attn": ["toy.varlen", "toy.chunkcausal", "toy.cp4"],
        "train": ["toy.onemask"],
    }
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NEW:
        shutil.copy(
            os.path.join(REPO, "benchmarks", "metrics", name + ".json"),
            root / "benchmarks" / "metrics",
        )
        entry = dict(entries[name])
        on = []
        if any("attn" in w for w in entry["workloads"]):
            on += cells["attn"]
        if any("train" in w for w in entry["workloads"]):
            on += cells["train"]
        entry["workloads"] = on
        bench["per_layer"].append(entry)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


@pytest.mark.parametrize("workload", ["toy.varlen", "toy.onemask"])
def test_rehearsal_reports_the_span_metrics(
    workload, toy_with_the_new_metrics
):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    out = io.StringIO()
    try:
        with jax.enable_x64(False), redirect_stdout(out):
            rc = harness.main(
                ["--workload", workload, "--seed", str(2**31 + 7),
                 "--seconds", "1.5", "--trace", "1",
                 "--root", toy_with_the_new_metrics],
                allow_cpu=True,
            )
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
        telemetry.set_enabled(None)
        telemetry.reset()
    assert rc == 0
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    got = res["metrics"]
    for name in NEW[4:]:
        assert got[name]["unit"] == _metric(name)["unit"]
        assert got[name]["value"] >= 0.0
    assert got["key_build_ms"]["value"] > 0.0
    assert got["program_trace_s"]["value"] > 0.0
    assert got["program_lower_s"]["value"] > 0.0
    # set-up holds them all (the harness's clock and the program's agree)
    setup_s = next(
        float(line.split("set-up took ")[1].split(" s")[0])
        for line in out.getvalue().splitlines() if "set-up took" in line
    )
    assert got["key_build_ms"]["value"] / 1e3 < setup_s
    assert sum(
        got[n]["value"] for n in NEW[5:]
    ) < setup_s
    # no device trace on the CPU: the kernel readers find nothing
    assert not set(NEW[:4]) & set(got)
