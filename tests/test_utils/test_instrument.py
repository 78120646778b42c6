"""utils/instrument.py: the profile-mode default of switch_profile and
named_scope (host spans are telemetry.span: tests/test_telemetry/
test_span_tree.py)."""

import pytest

from magiattention_tpu import env, telemetry
from magiattention_tpu.utils import instrument


@pytest.fixture(autouse=True)
def clean_telemetry():
    telemetry.set_enabled(None)
    telemetry.reset()
    yield
    telemetry.set_enabled(None)
    telemetry.reset()


def test_switch_profile_noop_without_flag(monkeypatch):
    monkeypatch.delenv("MAGI_ATTENTION_PROFILE_MODE", raising=False)
    calls = []
    monkeypatch.setattr(
        "jax.profiler.start_trace", lambda d: calls.append(("start", d))
    )
    monkeypatch.setattr(
        "jax.profiler.stop_trace", lambda: calls.append(("stop",))
    )
    with instrument.switch_profile():
        pass
    assert calls == []


def test_switch_profile_honors_profile_mode_default(monkeypatch, tmp_path):
    """MAGI_ATTENTION_PROFILE_MODE=1 turns the bare switch_profile() into
    a default-on trace into env.trace_dir() (previously a dead flag)."""
    monkeypatch.setenv("MAGI_ATTENTION_PROFILE_MODE", "1")
    monkeypatch.setenv("MAGI_ATTENTION_TRACE_DIR", str(tmp_path / "tr"))
    calls = []
    monkeypatch.setattr(
        "jax.profiler.start_trace", lambda d: calls.append(("start", d))
    )
    monkeypatch.setattr(
        "jax.profiler.stop_trace", lambda: calls.append(("stop",))
    )
    with instrument.switch_profile():
        pass
    assert calls == [("start", str(tmp_path / "tr")), ("stop",)]
    assert env.trace_dir() == str(tmp_path / "tr")


def test_switch_profile_explicit_dir_wins(monkeypatch):
    monkeypatch.setenv("MAGI_ATTENTION_PROFILE_MODE", "1")
    calls = []
    monkeypatch.setattr(
        "jax.profiler.start_trace", lambda d: calls.append(("start", d))
    )
    monkeypatch.setattr(
        "jax.profiler.stop_trace", lambda: calls.append(("stop",))
    )
    with instrument.switch_profile("/explicit/dir"):
        pass
    assert calls == [("start", "/explicit/dir"), ("stop",)]


def test_switch_profile_stops_on_exception(monkeypatch):
    calls = []
    monkeypatch.setattr(
        "jax.profiler.start_trace", lambda d: calls.append("start")
    )
    monkeypatch.setattr(
        "jax.profiler.stop_trace", lambda: calls.append("stop")
    )
    with pytest.raises(RuntimeError):
        with instrument.switch_profile("/d"):
            raise RuntimeError("boom")
    assert calls == ["start", "stop"]


# ---------------------------------------------------------------------------
# trace-session re-entrancy + exception safety (ISSUE 3 satellite)
# ---------------------------------------------------------------------------


def test_switch_profile_nested_session_is_noop(monkeypatch):
    """A switch_profile inside an active session must not raise out of
    jax.profiler (one session per process): the inner one warns and
    no-ops, the outer stops exactly once."""
    calls = []
    monkeypatch.setattr(
        "jax.profiler.start_trace", lambda d: calls.append(("start", d))
    )
    monkeypatch.setattr(
        "jax.profiler.stop_trace", lambda: calls.append(("stop",))
    )
    with instrument.switch_profile("/outer"):
        assert instrument.trace_session_active()
        with instrument.switch_profile("/inner"):
            pass
        # the inner exit must NOT have stopped the outer session
        assert instrument.trace_session_active()
    assert not instrument.trace_session_active()
    assert calls == [("start", "/outer"), ("stop",)]


def test_switch_profile_start_failure_degrades(monkeypatch):
    """start_trace raising (e.g. a session started directly through
    jax.profiler that our guard can't see) degrades to a warning no-op;
    stop_trace is never called for a session we didn't start."""

    def boom(d):
        raise RuntimeError("profiler already active")

    calls = []
    monkeypatch.setattr("jax.profiler.start_trace", boom)
    monkeypatch.setattr(
        "jax.profiler.stop_trace", lambda: calls.append("stop")
    )
    with instrument.switch_profile("/d"):
        pass  # body still runs
    assert calls == []
    assert not instrument.trace_session_active()


def test_switch_profile_stop_failure_never_masks_body_exception(
    monkeypatch,
):
    monkeypatch.setattr("jax.profiler.start_trace", lambda d: None)

    def bad_stop():
        raise RuntimeError("flush failed")

    monkeypatch.setattr("jax.profiler.stop_trace", bad_stop)
    with pytest.raises(ValueError, match="body error"):
        with instrument.switch_profile("/d"):
            raise ValueError("body error")
    # the guard is released even when stop_trace raised
    assert not instrument.trace_session_active()


def test_switch_profile_reusable_after_exception(monkeypatch):
    calls = []
    monkeypatch.setattr(
        "jax.profiler.start_trace", lambda d: calls.append(("start", d))
    )
    monkeypatch.setattr(
        "jax.profiler.stop_trace", lambda: calls.append(("stop",))
    )
    with pytest.raises(RuntimeError):
        with instrument.switch_profile("/a"):
            raise RuntimeError
    with instrument.switch_profile("/b"):
        pass
    assert calls == [("start", "/a"), ("stop",), ("start", "/b"), ("stop",)]


def test_named_scope_is_usable_anywhere():
    """named_scope must work both under tracing and in plain host code
    (jax.named_scope is a no-op outside traced regions)."""
    import jax.numpy as jnp

    with instrument.named_scope("magi_test_scope"):
        assert float(jnp.asarray(1.0) + 1.0) == 2.0
