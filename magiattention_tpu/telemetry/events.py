"""Host-side span-event ring buffer + structured trace export.

Role of the reference's nvtx event stream, TPU-shaped: ``jax.named_scope``
annotates *traced* computations for the XLA profiler, but host-side
planning work (dispatch solve, comm routing, table emission) never enters
a trace — this buffer is where those spans land. ``dump_events`` writes
the Chrome trace-event JSON format (the ``chrome://tracing`` /
Perfetto / TensorBoard "trace viewer" schema), so host planning spans can
be laid next to an XLA device trace.

The buffer is a fixed-size ring (``collections.deque(maxlen=...)``): a
long-running trainer with telemetry left on keeps the most recent N spans
and never grows without bound. Recording is gated by
:func:`magiattention_tpu.telemetry.enabled` at every *call site* (the
``span``/``record_event`` helpers here check it too), so the disabled
path allocates nothing.

Spans form a tree (ISSUE 24): each carries its own ``id``, the ``parent``
that caused it (the innermost span live on the thread when it began: a
``contextvars`` chain) and, where it belongs to a runtime key, the short
``key`` id every span of that key's life shares — all three in the Chrome
event's ``args``. :func:`span_self_seconds` turns the tree into each
span's own time. While jax is loaded a live span is also written as
``jax.profiler.TraceAnnotation("magi:" + name)``, which the profiler
keeps only while one of its sessions records: the same span then sits
in the ``.xplane.pb`` on the profiler's clock, beside ``XLA Ops``.

Two spans are the process's own (ISSUE 51): ``process_boot`` (process
start to the package's first statement) and ``package_import``, posted
once a process the first time telemetry is on (:func:`post_boot_spans`).
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import json
import os
import sys
import threading
import time
from collections import deque

# registry counter ticked when the ring evicts a span to admit a new one
# (collectors re-exports it as M_TRACE_DROPPED; the trace-check CI
# asserts it): silent truncation would make a reconstructed request
# trace look complete when it is not
DROPPED_COUNTER = "magi_trace_events_dropped_total"

# what a span is called in the profiler's trace (beside the device rows)
ANNOTATION_PREFIX = "magi:"

_span_ids = itertools.count(1)  # next() is atomic under the GIL


def key_id(key) -> str:
    """The short id the spans of one runtime key share: eight hex digits
    of the key's hash, stable within a process (str hashes are salted per
    process, so never compare ids across processes)."""
    return f"{hash(key) & 0xFFFFFFFF:08x}"


class EventBuffer:
    """Ring buffer of span events (host wall-clock, microsecond stamps).

    Spans land on the recording thread's track by default; ``track=``
    puts a span on a named synthetic track instead (a small stable tid +
    a ``thread_name`` metadata event at dump time) — how the scheduler's
    tick decomposition gets a Perfetto track of its own instead of
    burying every span on the host thread."""

    def __init__(self, maxlen: int = 4096):
        self._lock = threading.Lock()
        self._events: deque[dict] = deque(maxlen=maxlen)
        # track name -> synthetic tid (small ints, far below real thread
        # idents, assigned in first-use order — deterministic per run)
        self._tracks: dict[str, int] = {}
        # spans silently evicted by the ring (oldest-first): surfaced as
        # a counter + one-time warning so a truncated trace is
        # detectable, and read by export_request_traces to mark
        # reconstructed span trees partial instead of complete
        self._dropped = 0
        self._drop_warned = False

    @property
    def dropped(self) -> int:
        """Spans evicted from the ring since construction/clear."""
        with self._lock:
            return self._dropped

    def _track_tid(self, track: str) -> int:
        tid = self._tracks.get(track)
        if tid is None:
            tid = self._tracks[track] = len(self._tracks) + 1
        return tid

    def track_names(self) -> dict[int, str]:
        """Synthetic-track names by tid (for dump-time metadata)."""
        with self._lock:
            return {tid: name for name, tid in self._tracks.items()}

    def record(
        self,
        name: str,
        start_s: float,
        duration_s: float,
        attrs: dict | None = None,
        *,
        track: str | None = None,
        span_id: int | None = None,
        parent: int | None = None,
    ) -> dict:
        """Append one completed span and return its event. ``span_id``
        defaults to a fresh id; ``parent`` is the id of the span that
        caused it (None at a root)."""
        args = dict(attrs) if attrs else {}
        args["id"] = next(_span_ids) if span_id is None else span_id
        if parent is not None:
            args["parent"] = parent
        with self._lock:
            tid = (
                self._track_tid(track)
                if track is not None
                else threading.get_ident()
            )
            ev = {
                "name": name,
                "ph": "X",  # Chrome trace "complete" event
                "ts": start_s * 1e6,  # trace format wants microseconds
                "dur": duration_s * 1e6,
                "pid": os.getpid(),
                "tid": tid,
                "args": args,
            }
            full = (
                self._events.maxlen is not None
                and len(self._events) >= self._events.maxlen
            )
            if full:
                self._dropped += 1
            warn_first_drop = full and not self._drop_warned
            if warn_first_drop:
                self._drop_warned = True
            self._events.append(ev)
        if full:
            from .registry import get_registry

            get_registry().counter_inc(DROPPED_COUNTER)
        if warn_first_drop:
            from .logger import get_logger

            get_logger("telemetry").warning(
                "span-event ring full (maxlen=%d): oldest spans are being "
                "dropped — request traces reconstructed from this buffer "
                "will be marked partial. Raise "
                "MAGI_ATTENTION_TELEMETRY_RING_SIZE to keep more.",
                self._events.maxlen,
            )
        return ev

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._dropped = 0
            self._drop_warned = False

    def self_seconds(self, name: str) -> float:
        """Summed self time of the buffered spans called ``name``: each
        one's duration minus the part its children cover."""
        events = self.events()
        own = span_self_seconds(events)
        return sum(
            own[ev["args"]["id"]] for ev in events if ev["name"] == name
        )

    def dump(self, path: str) -> str:
        """Write the buffered spans as Chrome trace-event JSON; returns
        ``path``. Loadable in Perfetto / chrome://tracing / TensorBoard's
        trace viewer. Metadata events (phase ``M``) name each pid/tid
        track, so the viewer shows "magiattention host (pid N)" instead of
        a raw number."""
        events = self.events()
        payload = {
            "traceEvents": trace_metadata_events(
                events, thread_names=self.track_names()
            )
            + events,
            "displayTimeUnit": "ms",
        }
        with open(path, "w") as f:
            json.dump(payload, f, indent=1)
            f.write("\n")
        return path


def trace_metadata_events(
    events: list[dict],
    process_name: str | None = None,
    thread_names: dict[int, str] | None = None,
) -> list[dict]:
    """Chrome-trace metadata (phase ``M``) naming every pid/tid seen in
    ``events``: one ``process_name`` per distinct pid, one ``thread_name``
    per distinct (pid, tid). Perfetto then labels the tracks instead of
    showing raw ids. ``thread_names`` maps tids of synthetic tracks
    (per-hop comm spans) to their names; unlisted tids keep the generic
    host-thread label. The cross-rank merge (``telemetry/aggregate.py``)
    reuses this with a per-rank ``process_name`` and the rank-local
    thread names it harvested."""
    pids: dict[int, set] = {}
    for ev in events:
        if ev.get("ph") == "M":
            continue
        pid = ev.get("pid", 0)
        pids.setdefault(pid, set()).add(ev.get("tid", 0))
    meta: list[dict] = []
    for pid in sorted(pids):
        meta.append(
            {
                "name": "process_name",
                "ph": "M",
                "pid": pid,
                "tid": 0,
                "args": {
                    "name": process_name or f"magiattention host (pid {pid})"
                },
            }
        )
        for tid in sorted(pids[pid]):
            name = (thread_names or {}).get(tid) or f"host thread {tid}"
            meta.append(
                {
                    "name": "thread_name",
                    "ph": "M",
                    "pid": pid,
                    "tid": tid,
                    "args": {"name": name},
                }
            )
    return meta


def _default_buffer() -> EventBuffer:
    from .. import env

    return EventBuffer(maxlen=env.telemetry_ring_size())


_buffer: EventBuffer | None = None
_buffer_lock = threading.Lock()


def get_event_buffer() -> EventBuffer:
    """The process-global span ring buffer (lazily sized from
    ``MAGI_ATTENTION_TELEMETRY_RING_SIZE``)."""
    global _buffer
    if _buffer is None:
        with _buffer_lock:
            if _buffer is None:
                _buffer = _default_buffer()
    return _buffer


def span_self_seconds(events: list[dict]) -> dict[int, float]:
    """span id -> self seconds: a span's duration minus the union of its
    direct children's intervals (clipped to its own). ``events`` is what
    :meth:`EventBuffer.events` returns; a child whose parent fell out of
    the ring counts for nobody."""
    children: dict[int, list[tuple[float, float]]] = {}
    for ev in events:
        parent = ev.get("args", {}).get("parent")
        if parent is not None:
            children.setdefault(parent, []).append(
                (ev["ts"], ev["ts"] + ev["dur"])
            )
    out: dict[int, float] = {}
    for ev in events:
        sid = ev.get("args", {}).get("id")
        if sid is None:
            continue
        t0, t1 = ev["ts"], ev["ts"] + ev["dur"]
        covered, edge = 0.0, t0
        for a, b in sorted(children.get(sid, ())):
            a, b = max(a, edge), min(b, t1)
            if b > a:
                covered += b - a
                edge = b
        out[sid] = (ev["dur"] - covered) / 1e6
    return out


# ---------------------------------------------------------------------------
# live spans: the tree's parent links, and the profiler's copy
# ---------------------------------------------------------------------------


def _with_key_id(attrs: dict) -> dict:
    """``key=`` may be the runtime key itself (so that a call site pays
    no hash while telemetry is off): it is recorded as its id."""
    key = attrs.get("key")
    if key is not None and not isinstance(key, str):
        attrs["key"] = key_id(key)
    return attrs


class LiveSpan:
    """A span that has begun and not ended: what its children name as
    their parent, and where attributes learned on the way are set
    (``live.set(cache="hit")``)."""

    __slots__ = ("name", "id", "parent", "attrs", "t0", "_below", "_annotation")

    def __init__(self, name: str, parent: "LiveSpan | None", attrs: dict):
        self.name = name
        self.id = next(_span_ids)
        self.parent = parent
        self.attrs = _with_key_id(attrs)
        # a key is inherited downwards at the start ...
        if parent is not None and "key" not in attrs and "key" in parent.attrs:
            attrs["key"] = parent.attrs["key"]
        # ... and upwards-late at the end: the events recorded under this
        # span, so a key learned only when it ends still reaches them
        self._below: list[dict] = []
        self._annotation = None
        self.t0 = 0.0

    def set(self, **attrs) -> None:
        self.attrs.update(_with_key_id(attrs))


_live: contextvars.ContextVar[LiveSpan | None] = contextvars.ContextVar(
    "magi_live_span", default=None
)


def current_span() -> LiveSpan | None:
    """The innermost span live on this thread/context, if any."""
    return _live.get()


def annotate_span(**attrs) -> None:
    """Set attributes on the innermost live span (nothing where none is
    live, as with telemetry off)."""
    live = _live.get()
    if live is not None:
        live.set(**attrs)


def begin_span(name: str, attrs: dict | None = None) -> LiveSpan:
    """Open a span by hand (the compile tracker's listeners learn of a
    jax phase's start and end in two separate calls); pair with
    :func:`end_span`. Call sites check :func:`telemetry.enabled`."""
    if not _boot_posted:
        post_boot_spans()  # telemetry came on by the env flag alone
    live = LiveSpan(name, _live.get(), dict(attrs) if attrs else {})
    _live.set(live)
    jax = sys.modules.get("jax")
    if jax is not None:
        # kept by the profiler only while one of its sessions records
        live._annotation = jax.profiler.TraceAnnotation(
            ANNOTATION_PREFIX + name
        )
        live._annotation.__enter__()
    live.t0 = time.perf_counter()
    return live


def end_span(live: LiveSpan) -> dict:
    """Close ``live`` (and whatever was left open above it) and record
    it; returns the event."""
    t1 = time.perf_counter()
    if live._annotation is not None:
        live._annotation.__exit__(None, None, None)
    _live.set(live.parent)
    ev = get_event_buffer().record(
        live.name, live.t0, t1 - live.t0, live.attrs,
        span_id=live.id,
        parent=live.parent.id if live.parent is not None else None,
    )
    key = live.attrs.get("key")
    if key is not None:
        for below in live._below:
            below["args"].setdefault("key", key)
    if live.parent is not None:
        live.parent._below.extend(live._below)
        live.parent._below.append(ev)
    return ev


def record_event(
    name: str,
    start_s: float,
    duration_s: float,
    attrs: dict | None = None,
    *,
    track: str | None = None,
) -> None:
    """Append one completed span (no-op while telemetry is disabled);
    its parent is the innermost span live now. ``track`` routes it onto
    a named synthetic Chrome-trace track."""
    from . import enabled

    if not enabled():
        return
    if not _boot_posted:
        post_boot_spans()
    parent = _live.get()
    if parent is not None and "key" in parent.attrs:
        attrs = {"key": parent.attrs["key"], **(attrs or {})}
    ev = get_event_buffer().record(
        name, start_s, duration_s, attrs, track=track,
        parent=parent.id if parent is not None else None,
    )
    if parent is not None:
        parent._below.append(ev)


# ---------------------------------------------------------------------------
# the process's own two spans: boot and package import (ISSUE 51)
# ---------------------------------------------------------------------------

_PROC_STAT = "/proc/self/stat"
_boot_posted = False


def process_age_seconds() -> float | None:
    """Seconds since this process started: field 22 of ``/proc/self/stat``
    (its start, in clock ticks since the machine's boot) against
    ``CLOCK_BOOTTIME``; None where that cannot be read (no ``/proc``)."""
    try:
        with open(_PROC_STAT) as f:
            # the command (field 2) may hold spaces: count from its ")"
            after_comm = f.read().rsplit(")", 1)[1].split()
        started = int(after_comm[19]) / os.sysconf("SC_CLK_TCK")
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - started
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return age if age >= 0.0 else None


def post_boot_spans(again: bool = False) -> None:
    """Post ``process_boot`` and ``package_import`` as ended spans, from
    the clock reads that ``magiattention_tpu/__init__.py`` made as its
    first and last statement. Once a process, the first time telemetry
    is on (``set_enabled(True)``, or the first span under the env flag);
    ``again=True`` posts them anew, for a reader whose ``telemetry.reset()``
    cleared the ring. Nothing while telemetry is off.

    ``process_boot`` starts at the process's start carried onto this
    ring's clock (:func:`process_age_seconds`); where that is unknown it
    has zero length and ``source="unknown"``. Its attributes say whether
    jax's import and the backend's start (on a TPU host, seconds of
    runtime start-up) lie inside it."""
    global _boot_posted
    if _boot_posted and not again:
        return
    from . import enabled
    from .. import _BOOT as marks

    if not enabled() or "ended" not in marks:  # off, or still importing
        return
    _boot_posted = True
    began, ended = marks["began"], marks["ended"]
    age = process_age_seconds()
    start = began if age is None else min(time.perf_counter() - age, began)
    buffer = get_event_buffer()
    buffer.record(
        "process_boot", start, began - start,
        {
            "source": "unknown" if age is None else "proc_stat",
            "jax_imported_before": marks["jax_before"],
            "backend_ready_before": marks["backend_before"],
        },
    )
    buffer.record(
        "package_import", began, ended - began,
        {"jax_import_s": marks["jax_import_s"]},
    )


@contextlib.contextmanager
def span(name: str, **attrs):
    """Time a host-side region into the ring buffer; yields the
    :class:`LiveSpan` (None while disabled). Disabled mode yields
    immediately with no clock reads, no allocation and no jax."""
    from . import enabled

    if not enabled():
        yield None
        return
    live = begin_span(name, attrs)
    try:
        yield live
    finally:
        end_span(live)
