"""Program observability: the compile tracker (ISSUE 16 tentpole).

The observability stack explains requests (traces) and bytes (memory
ledger) — this module explains *programs*. It answers three questions no
other layer can:

- **How many distinct XLA executables does this process build, and how
  expensive are they?** A process-wide :class:`CompileTracker` ingests
  ``jax.monitoring`` compile-duration events (via the
  ``utils/compat.register_compile_listeners`` shim — never a hard
  dependency on the monitoring API) and keys them by *program label*:
  whatever :func:`program` context is live on the compiling thread
  (``prefill[start=S,t=N]``, ``decode[b=B]`` from the serving engine,
  ``anon`` outside any label).
- **Is a label recompiling pathologically?** N compiles of the SAME
  label inside a sliding window (``MAGI_ATTENTION_RECOMPILE_STORM_
  THRESHOLD``, default 0 = off) fires a deferred ``recompile_storm``
  flight-recorder trigger tagged with the triggering scheduler tick and
  the live trace id — the serving post-mortem for shape thrash.
- **Where does a scheduler tick's wall-clock go?** :meth:`CompileTracker.
  mark`/:meth:`~CompileTracker.since` give the scheduler per-tick
  (compile count, compile seconds) deltas, and the always-on solver
  accumulator (:func:`add_solver_seconds`, fed by the plan-LRU /
  ``build_dist_attn_plan`` timing in ``api/interface.py`` and
  ``parallel/dist_attn.py``) gives host-solver seconds — the tick
  decomposition ``serving/scheduler.py`` reconciles against wall-clock.

The same listeners turn jax's own account of a program's life into
spans (ISSUE 24): ``jax.trace``, ``jax.lower``, ``jax.backend_compile``
and ``jax.cache_load``, each with the function's name, children of
whatever span is live (``docs/observability.md``, "Spans").

Gating discipline (the telemetry-check contract): the tracker's OWN
accumulators are plain module/instance state *outside* the metrics
registry and always on — per-tick attribution must work in production
with telemetry off, like the flight recorder. Only the registry series
(``magi_compile_total{program=}``, ``magi_compile_seconds``,
``magi_jit_cache_entries``) go through the usual
:func:`telemetry.enabled` gate, via ``collectors.record_compile``.

Everything here is host-side; nothing may be called from traced code.
"""

from __future__ import annotations

import contextlib
import contextvars
import threading
import time
from collections import deque
from dataclasses import dataclass, field

# event-name suffixes that mean "one XLA backend compile finished"
# (jax spells it backend_compile_duration on current releases and
# backend_compile_time_sec on some older ones; match either)
_COMPILE_EVENT_SUFFIXES = (
    "backend_compile_duration",
    "backend_compile_time_sec",
)

# the label compiles fall under when no program() context is live
ANON_PROGRAM = "anon"

# sliding window of the recompile-storm detector (seconds): wide enough
# that a thrashing serving loop (ticks are ms-scale) cannot stay under
# it, narrow enough that N legitimate cold compiles spread over a long
# bring-up don't alias into a storm
STORM_WINDOW_S = 30.0


# ---------------------------------------------------------------------------
# program labels
# ---------------------------------------------------------------------------

_current_program: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "magi_current_program", default=None
)


@contextlib.contextmanager
def program(label: str):
    """Attribute every XLA compile on this thread/context to ``label``
    while the body runs (contextvar, so async/thread-local like
    ``request_context``). The serving engine wraps its prefill/decode
    launches in this; nesting keeps the innermost label."""
    tok = _current_program.set(str(label))
    try:
        yield
    finally:
        _current_program.reset(tok)


def current_program() -> str | None:
    """The live program label, or None outside any :func:`program`."""
    return _current_program.get()


def prefill_program_label(start: int, tokens: int) -> str:
    """Canonical label of one prefill-chunk program: chunked-prefill
    geometry is per-(history offset, chunk rows) — each distinct pair is
    its own traced program (the cross path attends ``start`` gathered
    rows)."""
    return f"prefill[start={int(start)},t={int(tokens)}]"


def decode_program_label(batch: int) -> str:
    """Canonical label of one batched decode-step program: within one
    engine the decode geometry is keyed by batch size (split count and
    cache geometry resolve deterministically from it)."""
    return f"decode[b={int(batch)}]"


def tick_program_label(rows: int, entries: int, splits: int) -> str:
    """Canonical label of one unified serving-tick program (ISSUE 17):
    keyed by the PADDED geometry buckets (row capacity, entry capacity,
    split count) — never by the request mix — so a multi-tenant trace
    cycles a bounded label set and the per-label compile count the
    tick-check gate queries stays flat after warmup."""
    return f"tick[r={int(rows)},e={int(entries)},s={int(splits)}]"


# ---------------------------------------------------------------------------
# the tracker
# ---------------------------------------------------------------------------


@dataclass
class ProgramCompileStats:
    """Per-label compile record (plain data; snapshot via
    :meth:`CompileTracker.stats`)."""

    count: int = 0
    total_s: float = 0.0
    # timestamps (perf_counter) of recent compiles — the storm window
    recent_t: deque = field(default_factory=lambda: deque(maxlen=256))


class CompileTracker:
    """Process-wide XLA-compile registry, fed by ``jax.monitoring``.

    Always on: ingestion is one dict update per *compile* (compiles are
    rare and seconds-scale — the bookkeeping is noise), so unlike the
    metrics registry there is no enable gate on the accumulators. The
    registry series it mirrors are gated as usual inside
    ``collectors.record_compile``.

    ``jax.monitoring`` has no listener deregistration, so the listeners
    install once per process (:func:`get_compile_tracker`) and
    :func:`reset_compile_tracker` clears the records while keeping them
    installed.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._stats: dict[str, ProgramCompileStats] = {}
        self._total_count = 0
        self._total_seconds = 0.0
        # always-on host-solver accumulator (plan builds + LRU lookups)
        self._solver_seconds = 0.0
        # measured plan-build cost model for the ms-saved credit
        self._plan_build_count = 0
        self._plan_build_total_s = 0.0
        # the scheduler stamps its tick number here so a storm dump can
        # name the tick that thrashed
        self._tick: int | None = None
        self.ingestion: str = "none"  # compat shim verdict, for tests/CI

    # -- ingestion --------------------------------------------------------

    def note_compile(
        self, seconds: float, label: str | None = None
    ) -> None:
        """One finished XLA backend compile (the monitoring listener's
        entry point; tests call it directly to plant scenarios)."""
        lab = label if label is not None else (
            current_program() or ANON_PROGRAM
        )
        now = time.perf_counter()
        with self._lock:
            st = self._stats.get(lab)
            if st is None:
                st = self._stats[lab] = ProgramCompileStats()
            st.count += 1
            st.total_s += float(seconds)
            st.recent_t.append(now)
            self._total_count += 1
            self._total_seconds += float(seconds)
            total_programs = self._total_count
            tick = self._tick
            in_window = sum(
                1 for t in st.recent_t if now - t <= STORM_WINDOW_S
            )
        from .collectors import record_compile

        record_compile(lab, float(seconds), total_programs)
        self._maybe_storm(lab, in_window, tick)

    def _maybe_storm(
        self, label: str, compiles_in_window: int, tick: int | None
    ) -> None:
        """Fire the deferred recompile-storm trigger exactly when the
        window count REACHES the threshold (not on every compile past
        it — the flight recorder's first-signal-wins arm would ignore
        repeats anyway, but the exact-match keeps the trigger record's
        count meaningful)."""
        from .. import env

        threshold = env.recompile_storm_threshold()
        if threshold <= 0 or compiles_in_window != threshold:
            return
        from .trace import current_trace, get_flight_recorder

        cur = current_trace()
        get_flight_recorder().trigger(
            "recompile_storm",
            immediate=False,  # flush at tick end: the dump holds the tick
            program=label,
            compiles_in_window=compiles_in_window,
            threshold=threshold,
            window_s=STORM_WINDOW_S,
            tick=tick,
            trace_id=cur[0] if cur is not None else None,
        )

    # -- per-tick attribution ---------------------------------------------

    def note_tick(self, step: int) -> None:
        """The scheduler's current tick number (storm-dump tagging)."""
        with self._lock:
            self._tick = int(step)

    def mark(self) -> tuple[int, float]:
        """Opaque point-in-time mark for :meth:`since`."""
        with self._lock:
            return (self._total_count, self._total_seconds)

    def since(self, mark: tuple[int, float]) -> tuple[int, float]:
        """(compiles, compile seconds) since ``mark``."""
        with self._lock:
            return (
                self._total_count - mark[0],
                self._total_seconds - mark[1],
            )

    def add_solver_seconds(self, seconds: float) -> None:
        with self._lock:
            self._solver_seconds += float(seconds)

    def solver_mark(self) -> float:
        with self._lock:
            return self._solver_seconds

    def solver_since(self, mark: float) -> float:
        with self._lock:
            return self._solver_seconds - mark

    def note_plan_build(self, seconds: float) -> None:
        """One measured cold plan build — the sample the cache-hit
        ms-saved credit prices against."""
        with self._lock:
            self._plan_build_count += 1
            self._plan_build_total_s += float(seconds)

    def plan_build_mean_s(self) -> float | None:
        """Mean measured cold-build seconds (None before any build)."""
        with self._lock:
            if not self._plan_build_count:
                return None
            return self._plan_build_total_s / self._plan_build_count

    # -- introspection ----------------------------------------------------

    def stats(self) -> dict[str, dict]:
        """Plain-dict per-label view: ``{label: {count, total_s}}``."""
        with self._lock:
            return {
                lab: {"count": st.count, "total_s": st.total_s}
                for lab, st in self._stats.items()
            }

    def total(self) -> tuple[int, float]:
        """(compile count, compile seconds) process-wide."""
        with self._lock:
            return (self._total_count, self._total_seconds)

    def reset(self) -> None:
        """Clear records (listeners stay installed — jax.monitoring has
        no deregistration)."""
        with self._lock:
            self._stats.clear()
            self._total_count = 0
            self._total_seconds = 0.0
            self._solver_seconds = 0.0
            self._plan_build_count = 0
            self._plan_build_total_s = 0.0
            self._tick = None


# ---------------------------------------------------------------------------
# process singleton + module-level conveniences
# ---------------------------------------------------------------------------

_tracker: CompileTracker | None = None
_tracker_lock = threading.Lock()


# the phases of a program's life as jax.monitoring names them -> the span
# each becomes (ISSUE 24); jax hands every one the function's name
_PHASE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.backend_compile",
}
_PHASE_NAMES = frozenset(_PHASE_SPANS.values())
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}


def _on_duration(event: str, duration: float, **_kw) -> None:
    """The jax.monitoring duration listener: backend compiles feed the
    tracker; a retrieval from the persistent cache (reported from inside
    the backend-compile phase, as a duration that has just ended)
    becomes a ``jax.cache_load`` span. Defensive about signature growth
    — newer jax may pass extra keyword context."""
    try:
        if any(event.endswith(s) for s in _COMPILE_EVENT_SUFFIXES):
            get_compile_tracker().note_compile(float(duration))
        elif event == _CACHE_LOAD_EVENT:
            from .events import current_span, record_event

            live = current_span()
            fun_name = live.attrs.get("fun_name") if live is not None else None
            record_event(
                "jax.cache_load",
                time.perf_counter() - float(duration),
                float(duration),
                {"fun_name": fun_name} if fun_name is not None else None,
            )
    except Exception:  # pragma: no cover — observability must not raise
        pass


def _on_event(event: str, **_kw) -> None:
    """The jax.monitoring event listener: the persistent cache's hits
    and misses, counted (registry, gated as the rest)."""
    try:
        result = _CACHE_EVENTS.get(event)
        if result is not None:
            from .collectors import record_compile_cache

            record_compile_cache(result)
    except Exception:  # pragma: no cover — observability must not raise
        pass


def _live_phase():
    """The innermost live span that is a jax phase, if any."""
    from .events import current_span

    live = current_span()
    while live is not None and live.name not in _PHASE_NAMES:
        live = live.parent
    return live


# phases that began inside the live one and are part of it (they nest
# properly, so a count is enough to pair each end with its start)
_folded: contextvars.ContextVar[int] = contextvars.ContextVar(
    "magi_folded_phases", default=0
)


def _on_phase_start(event: str, _start: float, **kw) -> None:
    """A trace, a lowering or a backend compile begins: a live span, so
    that what runs inside it (``calc_attn.trace``, the cache load) names
    it as its parent. A phase that begins inside a live one is part of
    it and gets no span of its own: tracing one program traces every
    jitted function it calls, and lowering a kernel traces more —
    thousands of them in a model step."""
    try:
        name = _PHASE_SPANS.get(event)
        if name is None:
            return
        from . import enabled

        if _live_phase() is not None:
            _folded.set(_folded.get() + 1)
        elif enabled():
            from .events import begin_span

            begin_span(name, {"fun_name": str(kw.get("fun_name", ""))})
    except Exception:  # pragma: no cover — observability must not raise
        pass


def _on_phase_end(event: str, start: float, end: float, **kw) -> None:
    """The phase ends: close its live span. Where it has none (telemetry
    came on midway, or this jax reports no start), record it whole, on
    the span buffer's clock, ending now."""
    try:
        name = _PHASE_SPANS.get(event)
        if name is None:
            return
        from . import enabled
        from .events import end_span, record_event

        live = _live_phase()
        if live is not None and _folded.get():
            _folded.set(_folded.get() - 1)
        elif live is not None and live.name == name:
            end_span(live)
        elif enabled():
            dur = float(end) - float(start)
            record_event(
                name, time.perf_counter() - dur, dur,
                {"fun_name": str(kw.get("fun_name", ""))},
            )
    except Exception:  # pragma: no cover — observability must not raise
        pass


_KEY_METADATA = "jax_compilation_cache_include_metadata_in_key"
# jax's own value of that option while telemetry holds it on, else None
_key_metadata_was: bool | None = None


def key_cache_on_metadata(on: bool) -> None:
    """While telemetry is on, the persistent compilation cache keys a
    program on its debug info too (named scopes, source lines); when it
    goes off, jax's own setting is back. jax strips the debug info from
    the key by default, and a ``named_scope`` lives only there: a run
    that reads scopes from a device trace or from ``exe.as_text()`` could
    otherwise load an executable that older code compiled, with that
    code's scopes, and read nothing, with no error. The cost: such a run
    shares no cache entry with a run that has telemetry off, nor with
    code whose lines moved (docs/observability.md, "Device scopes")."""
    global _key_metadata_was
    import jax

    if on and _key_metadata_was is None:
        _key_metadata_was = bool(getattr(jax.config, _KEY_METADATA))
        jax.config.update(_KEY_METADATA, True)
    elif not on and _key_metadata_was is not None:
        jax.config.update(_KEY_METADATA, _key_metadata_was)
        _key_metadata_was = None


# the compat shim's verdict once the listeners are in: jax.monitoring has
# no deregistration, so they go in once a process, whatever becomes of
# the tracker (a second set would count every compile twice: ROADMAP D7)
_ingestion: str | None = None


def get_compile_tracker() -> CompileTracker:
    """The process-wide tracker; the first call of a process installs
    the monitoring listeners (via the compat shim — "monitoring", or
    "none" when the hook is missing; the tracker still works for
    directly-planted events either way) and, where telemetry is on by
    the env flag alone, keys the persistent cache on metadata
    (:func:`key_cache_on_metadata`; ``telemetry.set_enabled`` does it
    for the programmatic switch)."""
    global _tracker, _ingestion
    if _tracker is None:
        with _tracker_lock:
            if _tracker is None:
                tracker = CompileTracker()
                from . import enabled
                from ..utils.compat import register_compile_listeners

                if _ingestion is None:
                    _ingestion = register_compile_listeners(
                        _on_event, _on_duration, _on_phase_start, _on_phase_end
                    )
                tracker.ingestion = _ingestion
                key_cache_on_metadata(enabled())
                _tracker = tracker
    return _tracker


def reset_compile_tracker() -> None:
    """Clear the tracker's records (no-op if never created). Explicit —
    deliberately NOT part of ``telemetry.reset()``: compile history is
    process-lifetime state (executables stay cached across registry
    resets), and per-tick attribution uses marks, not absolutes."""
    if _tracker is not None:
        _tracker.reset()


def add_solver_seconds(seconds: float) -> None:
    """Always-on host-solver accumulator (plan builds + LRU lookups);
    the scheduler diffs it per tick. Outside the metrics registry by
    design — the disabled-mode no-op contract covers the registry."""
    get_compile_tracker().add_solver_seconds(seconds)
