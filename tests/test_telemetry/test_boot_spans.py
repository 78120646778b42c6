"""The process's own two spans (ISSUE 51): ``process_boot`` (process
start to the package's first statement) and ``package_import``, from two
clock reads the package makes at import, posted once a process the first
time telemetry is on."""

import json
import os
import subprocess
import sys
import time

import pytest

import magiattention_tpu
from magiattention_tpu import telemetry
from magiattention_tpu.telemetry import events

BOOT = ("process_boot", "package_import")


@pytest.fixture(autouse=True)
def first_time_on(monkeypatch):
    """A process in which telemetry has not been on yet."""
    telemetry.set_enabled(None)
    telemetry.reset()
    monkeypatch.setattr(events, "_boot_posted", False)
    yield
    telemetry.set_enabled(None)
    telemetry.reset()


def _ring():
    return telemetry.get_event_buffer().events()


def _named(name):
    (ev,) = [ev for ev in _ring() if ev["name"] == name]
    return ev


def test_posted_once_the_first_time_telemetry_comes_on():
    telemetry.set_enabled(False)
    assert _ring() == [] and not events._boot_posted  # off: nothing, yet
    telemetry.set_enabled(True)
    assert [ev["name"] for ev in _ring()] == list(BOOT)
    telemetry.set_enabled(True)
    telemetry.set_enabled(False)
    telemetry.set_enabled(True)
    assert [ev["name"] for ev in _ring()] == list(BOOT)  # once a process
    for ev in _ring():
        assert "parent" not in ev["args"]


def test_after_a_reset_only_if_asked():
    telemetry.set_enabled(True)
    telemetry.reset()
    telemetry.set_enabled(True)
    telemetry.post_boot_spans()
    with telemetry.span("user"):
        pass
    assert [ev["name"] for ev in _ring()] == ["user"]
    telemetry.post_boot_spans(again=True)
    assert [ev["name"] for ev in _ring()] == ["user", *BOOT]
    telemetry.set_enabled(False)
    telemetry.post_boot_spans(again=True)  # off: asked or not, nothing
    assert len(_ring()) == 3


def test_the_env_flag_alone_posts_them_at_the_first_span(monkeypatch):
    monkeypatch.setenv("MAGI_ATTENTION_TELEMETRY", "1")
    with telemetry.span("outer"):
        telemetry.record_event("posted", time.perf_counter(), 0.0)
    names = [ev["name"] for ev in _ring()]
    assert names == [*BOOT, "posted", "outer"]
    # roots, though a span was opening when they were posted
    for name in BOOT:
        assert "parent" not in _named(name)["args"]
    assert _named("posted")["args"]["parent"] == _named("outer")["args"]["id"]


def test_they_end_before_the_first_user_span_and_abut():
    telemetry.set_enabled(True)
    with telemetry.span("user"):
        pass
    boot, imp, user = (_named(n) for n in (*BOOT, "user"))
    marks = magiattention_tpu._BOOT
    assert boot["ts"] + boot["dur"] == pytest.approx(imp["ts"], abs=1.0)
    assert imp["ts"] == pytest.approx(marks["began"] * 1e6)
    assert imp["ts"] + imp["dur"] == pytest.approx(marks["ended"] * 1e6)
    assert imp["dur"] > 0
    assert imp["ts"] + imp["dur"] <= user["ts"]
    assert 0.0 <= imp["args"]["jax_import_s"] <= imp["dur"] / 1e6
    args = boot["args"]
    assert isinstance(args["jax_imported_before"], bool)
    assert isinstance(args["backend_ready_before"], bool)
    # a backend cannot be up before jax is imported
    assert args["jax_imported_before"] or not args["backend_ready_before"]
    # jax's import is timed only where the package was first to import it
    assert (imp["args"]["jax_import_s"] == 0.0) == args["jax_imported_before"]


def test_process_boot_starts_where_the_process_did():
    """No later than the package's first clock read, and no earlier than
    the process's age (``/proc/self/stat``) allows."""
    if events.process_age_seconds() is None:
        pytest.skip("no /proc/self/stat here")
    telemetry.set_enabled(True)
    boot = _named("process_boot")
    assert boot["args"]["source"] == "proc_stat"
    assert boot["ts"] <= magiattention_tpu._BOOT["began"] * 1e6
    age = events.process_age_seconds()  # read later: the larger
    tick = 1.0 / os.sysconf("SC_CLK_TCK")
    assert boot["ts"] / 1e6 >= time.perf_counter() - age - 2 * tick
    # and the interpreter was up before it imported anything: dur > 0
    assert boot["dur"] > 0


def test_process_age_reads_field_22_past_a_command_with_spaces(
    tmp_path, monkeypatch
):
    ticks = os.sysconf("SC_CLK_TCK")
    now = time.clock_gettime(time.CLOCK_BOOTTIME)
    fields = ["0"] * 52
    fields[0], fields[1] = "4242", "(python3 -m a) b)"
    fields[21] = str(int((now - 12.5) * ticks))  # field 22: started 12.5 s ago
    stat = tmp_path / "stat"
    stat.write_text(" ".join(fields) + "\n")
    monkeypatch.setattr(events, "_PROC_STAT", str(stat))
    assert events.process_age_seconds() == pytest.approx(12.5, abs=0.1)
    stat.write_text("4242 (python3) S 1 2 3\n")  # cut short
    assert events.process_age_seconds() is None


def test_where_proc_is_absent_the_boot_span_has_zero_length(
    tmp_path, monkeypatch
):
    monkeypatch.setattr(events, "_PROC_STAT", str(tmp_path / "no-such-file"))
    assert events.process_age_seconds() is None
    telemetry.set_enabled(True)
    boot, imp = _named("process_boot"), _named("package_import")
    assert boot["dur"] == 0.0 and boot["args"]["source"] == "unknown"
    assert boot["ts"] == imp["ts"]
    assert imp["dur"] > 0


def test_the_summary_has_one_start_up_line():
    telemetry.set_enabled(True)
    text = telemetry.telemetry_summary()
    (line,) = [ln for ln in text.splitlines() if "start-up:" in ln]
    assert "process boot" in line and "package import" in line
    # a snapshot handed in may be merged or another process's: the line
    # is this process's boot, so only the live summary carries it
    given = telemetry.telemetry_summary(telemetry.snapshot())
    assert "start-up:" not in given and given + "\n" + line == text
    telemetry.reset()
    assert "start-up:" not in telemetry.telemetry_summary()


def test_the_dump_holds_both(tmp_path):
    telemetry.set_enabled(True)
    with open(telemetry.dump_events(str(tmp_path / "t.json"))) as f:
        spans = [e for e in json.load(f)["traceEvents"] if e["ph"] == "X"]
    assert [e["name"] for e in spans] == list(BOOT)


_CHILD = """
import json, sys
{before}
import magiattention_tpu
from magiattention_tpu.utils.compat import backends_are_initialized
marks = dict(magiattention_tpu._BOOT)
marks["backend_after"] = backends_are_initialized()
print(json.dumps(marks))
"""


@pytest.mark.parametrize(
    "before, jax_before, backend_before",
    [
        ("", False, False),
        ("import jax", True, False),
        ("import jax; jax.devices()", True, True),
    ],
    ids=["package_first", "jax_first", "backend_first"],
)
def test_the_import_marks_in_a_fresh_process(before, jax_before, backend_before):
    """What the package notes at import, and that importing it brings no
    backend up (so ``backend_ready_before`` is true of its first
    statement as of its last)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MAGI_ATTENTION_TELEMETRY", None)
    out = subprocess.run(
        [sys.executable, "-c", _CHILD.format(before=before)],
        env=env, capture_output=True, text=True, check=True,
        cwd=os.path.dirname(os.path.dirname(magiattention_tpu.__file__)),
    )
    marks = json.loads(out.stdout.strip().splitlines()[-1])
    assert marks["jax_before"] is jax_before
    assert marks["backend_before"] is backend_before
    assert marks["backend_after"] is backend_before
    assert marks["began"] < marks["ended"]
    if jax_before:
        assert marks["jax_import_s"] == 0.0
    else:
        assert 0.0 < marks["jax_import_s"] < marks["ended"] - marks["began"]
