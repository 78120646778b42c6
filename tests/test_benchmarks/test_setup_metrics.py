"""The set-up metrics ISSUE 51 adds: five metric files over the program's
span ring (``process_boot``, ``package_import``, ``calc_attn.trace``, the
self time of ``jax.trace``) and the remainder no span covers
(``sources/program_span_uncovered``), from a hand-made ring and through
the command at toy size."""

import io
import json
import os
import shutil
from contextlib import redirect_stdout

import pytest

from benchmarks import harness
from benchmarks.sources import program_span, program_span_uncovered
from magiattention_tpu import telemetry
from magiattention_tpu.telemetry import events

REPO = harness.CHECKOUT
TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "toy")
NEW = {
    "setup_boot_s": ["process_boot"],
    "setup_package_import_s": ["package_import"],
    "program_trace_attn_s": ["calc_attn.trace"],
    "program_trace_unscoped_s": ["jax.trace"],
    "setup_unspanned_s": None,
}


def _metric(name: str) -> dict:
    with open(os.path.join(REPO, "benchmarks", "metrics", name + ".json")) as f:
        return json.load(f)


def _obs(setup_s: float) -> harness.Observations:
    return harness.Observations(
        end_to_end={"setup_s": setup_s}, attempted=1, failed=0, correct=True
    )


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_metric_file_loads_in_every_cell(name):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    cells = [w["name"] for w in bench["workloads"]]
    assert entry == {
        "name": name, "unit": "s", "better": "lower",
        "source": "program_span", "layer": "runtime", "moves": "setup_s",
        "workloads": cells,
    }
    spec = _metric(name)
    if NEW[name] is None:
        assert spec["source"] == {"kind": "program_span_uncovered"}
    else:
        assert spec["source"]["kind"] == "program_span"
        assert spec["source"]["spans"] == NEW[name]
        # a span's name only: scope names belong in no metric file
        assert not [s for s in spec["source"]["spans"] if s.startswith("magi_")]
    assert spec["source"].get("self", False) == (
        name == "program_trace_unscoped_s"
    )
    for cell in cells:
        loaded = harness.load_cell(REPO, cell)
        assert name in [m["name"] for m in loaded.per_layer]


# -- a ring made by hand ----------------------------------------------------------


@pytest.fixture
def span_buffer():
    """A span ring made by hand, on the harness's clock (seconds after
    ``harness._T0``); set-up is its first 10 s."""
    telemetry.set_enabled(True)
    telemetry.reset()
    buf = telemetry.get_event_buffer()

    def record(name, start, dur, parent=None, **attrs):
        ev = buf.record(name, harness._T0 + start, dur, attrs, parent=parent)
        return ev["args"]["id"]

    yield record
    telemetry.set_enabled(None)
    telemetry.reset()


def test_uncovered_is_set_up_less_the_union_of_every_span(span_buffer):
    read = lambda: program_span_uncovered.read(  # noqa: E731
        _metric("setup_unspanned_s")["source"], _obs(10.0)
    )
    assert read() == pytest.approx(10.0)  # an empty ring covers nothing
    # straddles the harness's start: 3 s before it, 2 s after
    span_buffer("process_boot", -3.0, 5.0)
    assert read() == pytest.approx(8.0)
    span_buffer("package_import", 2.0, 0.5)  # abuts
    assert read() == pytest.approx(7.5)
    # nested: a trace, a part inside it, a call inside the part
    trace = span_buffer("jax.trace", 3.0, 2.0, fun_name="step")
    part = span_buffer("trace_part", 3.5, 1.0, parent=trace, scope="magi_proj")
    span_buffer("calc_attn.trace", 3.6, 0.5, parent=part)
    assert read() == pytest.approx(5.5)
    # overlapping, of any name, parent or none: [4.5, 6.0) adds 1 s
    span_buffer("somebody_elses_span", 4.5, 1.5)
    assert read() == pytest.approx(4.5)
    # straddles the window's opening: [9.5, 10) counts, the rest does not
    span_buffer("jax.backend_compile", 9.5, 4.0, fun_name="jit(check)")
    assert read() == pytest.approx(4.0)
    span_buffer("key_build", 30.0, 1.0)  # the check's, after the window
    span_buffer("long_ago", -20.0, 5.0)  # and before the harness began
    assert read() == pytest.approx(4.0)


def test_the_four_span_metrics_read_their_spans(span_buffer):
    span_buffer("process_boot", -0.25, 2.25, source="proc_stat")
    span_buffer("package_import", 2.0, 0.5, jax_import_s=0.0)
    trace = span_buffer("jax.trace", 3.0, 2.0, fun_name="step")
    part = span_buffer("trace_part", 3.25, 1.0, parent=trace, scope="magi_attn_full")
    attn = span_buffer("calc_attn.trace", 3.5, 0.5, parent=part)
    span_buffer("trace_part", 3.5, 0.25, parent=attn, scope="magi_layout")
    span_buffer("trace_part", 4.5, 0.25, parent=trace, scope="magi_ffn")
    span_buffer("jax.trace", 20.0, 1.0, fun_name="check")  # after the window
    obs = _obs(10.0)
    read = lambda m: program_span.read(_metric(m)["source"], obs)  # noqa: E731
    assert read("setup_boot_s") == pytest.approx(2.25)  # whole, not clipped
    assert read("setup_package_import_s") == pytest.approx(0.5)
    assert read("program_trace_attn_s") == pytest.approx(0.5)
    # the trace's own: 2 s less its two direct children's 1 + 0.25
    assert read("program_trace_unscoped_s") == pytest.approx(0.75)
    assert read("program_trace_attn_s") + read("program_trace_unscoped_s") <= (
        read("program_trace_s")
    )


def test_a_program_without_the_new_spans_reads_zero_and_does_not_raise(
    span_buffer,
):
    """The parent commit's ring: phases and key builds, no boot spans."""
    span_buffer("key_build", 1.0, 0.5)
    span_buffer("jax.trace", 2.0, 1.0, fun_name="step")
    obs = _obs(10.0)
    for name, spans in NEW.items():
        source = program_span_uncovered if spans is None else program_span
        value = source.read(_metric(name)["source"], obs)
        assert value is not None and value >= 0.0
    assert program_span.read(_metric("setup_boot_s")["source"], obs) == 0.0
    assert program_span_uncovered.read({}, obs) == pytest.approx(8.5)


def test_a_ring_that_dropped_spans_says_so(span_buffer, capsys, monkeypatch):
    small = events.EventBuffer(maxlen=2)
    monkeypatch.setattr(events, "_buffer", small)
    for i in range(3):
        small.record("jax.trace", harness._T0 + i, 0.5)
    assert program_span_uncovered.read({}, _obs(10.0)) == pytest.approx(9.0)
    assert "WARNING: the span ring dropped 1 spans" in capsys.readouterr().out


# -- through the command ------------------------------------------------------------


@pytest.fixture
def toy_with_the_setup_metrics(tmp_path):
    """The toy benchmark plus this PR's metric files and entries (and
    ``program_trace_s``, which two of them are parts of)."""
    root = tmp_path / "toy"
    shutil.copytree(TOY, root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in [*NEW, "program_trace_s"]:
        shutil.copy(
            os.path.join(REPO, "benchmarks", "metrics", name + ".json"),
            root / "benchmarks" / "metrics",
        )
        bench["per_layer"].append(
            {**entries[name], "workloads": ["toy.varlen", "toy.onemask"]}
        )
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


@pytest.mark.parametrize("workload", ["toy.varlen", "toy.onemask"])
def test_rehearsal_reports_the_setup_metrics(
    workload, toy_with_the_setup_metrics, monkeypatch
):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    # as in a process of the benchmark's own: telemetry not on before
    telemetry.set_enabled(None)
    telemetry.reset()
    monkeypatch.setattr(events, "_boot_posted", False)
    was = jax.config.jax_enable_compilation_cache
    out = io.StringIO()
    try:
        with jax.enable_x64(False), redirect_stdout(out):
            rc = harness.main(
                ["--workload", workload, "--seed", str(2**31 + 51),
                 "--seconds", "1.5", "--trace", "1",
                 "--root", toy_with_the_setup_metrics],
                allow_cpu=True,
            )
        names = [ev["name"] for ev in telemetry.get_event_buffer().events()]
        dropped = telemetry.get_event_buffer().dropped
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
        telemetry.set_enabled(None)
        telemetry.reset()
    assert rc == 0
    log = out.getvalue()
    got = json.loads(log.strip().splitlines()[-1])["metrics"]
    for name in NEW:
        assert got[name]["unit"] == "s"
        assert got[name]["value"] >= 0.0
    assert names[:2] == ["process_boot", "package_import"]
    assert "trace_part" in names
    assert dropped == 0 and "the span ring dropped" not in log
    assert got["setup_boot_s"]["value"] > 0.0
    assert got["setup_package_import_s"]["value"] > 0.0
    assert got["program_trace_unscoped_s"]["value"] > 0.0
    if workload == "toy.varlen":  # the keyed call; a model goes round it
        assert got["program_trace_attn_s"]["value"] > 0.0
    assert (
        got["program_trace_attn_s"]["value"]
        + got["program_trace_unscoped_s"]["value"]
        <= got["program_trace_s"]["value"]
    )
    setup_s = next(
        float(line.split("set-up took ")[1].split(" s")[0])
        for line in log.splitlines() if "set-up took" in line
    )
    # the harness's own calls are under no span: some of set-up is left,
    # and not all of it (the program's spans are inside set-up)
    assert 0.0 < got["setup_unspanned_s"]["value"] < setup_s


def test_the_waterfall_tool_keeps_the_ring_of_a_run(
    tmp_path, monkeypatch, capsys, toy_with_the_setup_metrics
):
    """``exps/setup_waterfall.py run`` is the traced command that also
    writes the ring down (``telemetry.dump_events``) and what it noted
    beside it; ``show`` lays it out to the run's ``setup_s``."""
    import importlib.util

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    spec = importlib.util.spec_from_file_location(
        "setup_waterfall", os.path.join(REPO, "exps", "setup_waterfall.py")
    )
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    # in a process of the tool's own the harness is imported right after
    # the tool's first clock read; here it was long before
    monkeypatch.setattr(harness, "_T0", tool._T_START)
    telemetry.set_enabled(None)
    telemetry.reset()
    monkeypatch.setattr(events, "_boot_posted", False)
    was = jax.config.jax_enable_compilation_cache
    try:
        with jax.enable_x64(False):
            rc = tool.run(
                ["--workload", "toy.onemask", "--seed", "51", "--seconds", "1.5",
                 "--out", str(tmp_path), "--root", toy_with_the_setup_metrics],
                allow_cpu=True,
            )
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
        telemetry.set_enabled(None)
        telemetry.reset()
    assert rc == 0
    log = capsys.readouterr().out.strip().splitlines()
    line = json.loads(log[-1])
    assert line["correct"] is True
    path = tmp_path / "toy.onemask.51.json"
    kept, ring = tool.load(str(path))
    assert kept["dropped"] == 0 and kept["metrics"] == line["metrics"]
    assert [ln for ln in log if f"set-up took {kept['setup_s']:.3f} s" in ln]
    names = {ev["name"] for ev in ring}
    assert {"process_boot", "package_import", "trace_part", "jax.trace"} <= names
    opened_s = kept["began_s"] + kept["setup_s"]
    rows, _gaps, unspanned = tool.waterfall(ring, kept["began_s"], opened_s)
    assert sum(v[0] for v in rows.values()) + unspanned == pytest.approx(
        kept["setup_s"], abs=1e-6
    )
    # the tool's remainder is the line's, from one union over one window
    assert unspanned == pytest.approx(
        line["metrics"]["setup_unspanned_s"]["value"], abs=1e-3  # the log's ms
    )
    step = tool.trace_by_scope(ring, opened_s)["jax.trace step"]
    assert step["(unscoped)"][0] > 0 and step["magi_proj"][0] > 0
    assert sum(tool.unscoped_by_place(ring, opened_s)["jax.trace step"]) == (
        pytest.approx(step["(unscoped)"][0], abs=1e-6)
    )
    tool.show(str(path))
    assert "unspanned; sum" in capsys.readouterr().out
