"""From a profiler trace to numbers: the one reduction every PR shares.

``load_xplane`` reads the ``.xplane.pb`` the JAX profiler writes (with
nothing but ``jax.profiler.ProfileData``) into a small plain form — the
device's operations and the benchmark's own host spans on one clock —
and the functions below reduce that form to busy time, kernel time,
exposed collective time and attributed idle gaps. The plain form
round-trips through JSON, so the test keeps a small recorded trace.

A device operation is an event of the ``XLA Ops`` line of a
``/device:TPU:<n>`` plane. A host span is an event whose name starts
with ``bench:`` (written by ``jax.profiler.TraceAnnotation`` from the
benchmark's files).
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench:"
PHASE_PREFIX = "phase:"  # spans that bound a phase, not host work
# names under which the profiler has carried an op's jax scope
_SCOPE_STATS = ("tf_op", "long_name", "name_scope", "hlo_op_name")
COLLECTIVE = re.compile(
    r"all-to-all|all-gather|all-reduce|collective-permute|reduce-scatter"
    r"|alltoall|allgather|allreduce|collectivepermute|reducescatter"
    r"|ppermute|psum|send|recv",
    re.IGNORECASE,
)


@dataclasses.dataclass(frozen=True)
class Op:
    device: int
    name: str
    start_ns: int
    dur_ns: int
    scope: str = ""

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass(frozen=True)
class Span:
    name: str  # without the "bench:" prefix
    start_ns: int
    dur_ns: int

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    ops: list[Op]
    spans: list[Span]
    lines_seen: dict = dataclasses.field(default_factory=dict)

    def to_json(self) -> dict:
        return {
            "ops": [
                [o.device, o.name, o.start_ns, o.dur_ns, o.scope]
                for o in self.ops
            ],
            "spans": [[s.name, s.start_ns, s.dur_ns] for s in self.spans],
        }

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(
            [Op(int(a), b, int(c), int(e), f) for a, b, c, e, f in d["ops"]],
            [Span(a, int(b), int(c)) for a, b, c in d["spans"]],
        )

    def phase(self, name: str) -> tuple[int, int] | None:
        """[start, end) of the phase span of that name, if traced."""
        for s in self.spans:
            if s.name == PHASE_PREFIX + name:
                return s.start_ns, s.end_ns
        return None

    def within(self, t0: int, t1: int) -> list[Op]:
        """Operations clipped to [t0, t1)."""
        out = []
        for o in self.ops:
            a, b = max(o.start_ns, t0), min(o.end_ns, t1)
            if b > a:
                out.append(dataclasses.replace(o, start_ns=a, dur_ns=b - a))
        return out


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(
        glob.glob(
            os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
        )
    )
    return found[-1] if found else None


def load_xplane(path: str, scopes: dict[str, str] | None = None) -> Trace:
    """Read a profiler trace. ``scopes`` maps an HLO instruction name to
    its jax scope (from the compiled programs' text), used where the
    trace's own events carry none."""
    from jax.profiler import ProfileData

    scopes = scopes or {}
    ops: list[Op] = []
    spans: list[Span] = []
    lines_seen: dict[str, list[str]] = {}
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        lines = list(plane.lines)
        lines_seen[plane.name] = [ln.name for ln in lines]
        for line in lines:
            if m and line.name == OPS_LINE:
                dev = int(m.group(1))
                for ev in line.events:
                    scope = ""
                    for key, value in ev.stats:
                        if key in _SCOPE_STATS and isinstance(value, str):
                            scope = value
                            break
                    # the TPU profiler names an op by its whole HLO line
                    name = ev.name.split(" = ", 1)[0].lstrip("%")
                    ops.append(
                        Op(
                            dev, name, int(ev.start_ns),
                            int(ev.duration_ns),
                            scope or scopes.get(name, ""),
                        )
                    )
            elif not m:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(
                            Span(
                                ev.name[len(SPAN_PREFIX):],
                                int(ev.start_ns), int(ev.duration_ns),
                            )
                        )
    ops.sort(key=lambda o: (o.device, o.start_ns))
    spans.sort(key=lambda s: s.start_ns)
    return Trace(ops, spans, lines_seen)


def hlo_scopes(hlo_text: str) -> dict[str, str]:
    """instruction name -> jax scope (``op_name``) of a compiled
    program's text."""
    out = {}
    for m in re.finditer(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?metadata=\{[^}]*?"
        r"op_name=\"([^\"]*)\"",
        hlo_text, re.MULTILINE,
    ):
        out[m.group(1)] = m.group(2)
    return out


# ---------------------------------------------------------------------------
# reductions (all times in seconds)
# ---------------------------------------------------------------------------


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _devices(ops: list[Op]) -> list[int]:
    return sorted({o.device for o in ops})


def busy_seconds(trace: Trace, t0: int, t1: int) -> float:
    """Seconds in which an operation ran on the device inside [t0, t1),
    the union of the intervals, averaged over the devices seen."""
    ops = trace.within(t0, t1)
    devs = _devices(ops)
    if not devs:
        return 0.0
    total = 0
    for d in devs:
        total += sum(
            b - a
            for a, b in _union(
                [(o.start_ns, o.end_ns) for o in ops if o.device == d]
            )
        )
    return total / len(devs) / 1e9


def idle_share_pct(trace: Trace, t0: int, t1: int) -> float:
    return 100.0 * (1.0 - busy_seconds(trace, t0, t1) / ((t1 - t0) / 1e9))


def kernel_seconds(trace: Trace, pattern: str, t0: int, t1: int) -> float:
    """Summed device time of the operations whose ``"<name> <scope>"``
    matches ``pattern``, averaged over the devices that ran one."""
    rx = re.compile(pattern)
    hit = [
        o for o in trace.within(t0, t1) if rx.search(f"{o.name} {o.scope}")
    ]
    devs = _devices(hit)
    if not devs:
        return 0.0
    return sum(o.dur_ns for o in hit) / len(devs) / 1e9


def exposed_comm_seconds(trace: Trace, t0: int, t1: int) -> tuple[float, float]:
    """(total, exposed) collective seconds, averaged over devices: the
    time collective operations ran, and the part of it during which no
    other operation ran on that device."""
    ops = trace.within(t0, t1)
    devs = _devices(ops)
    if not devs:
        return 0.0, 0.0
    total = exposed = 0
    for d in devs:
        mine = [o for o in ops if o.device == d]
        is_comm = [
            bool(COLLECTIVE.search(o.name) or COLLECTIVE.search(o.scope))
            for o in mine
        ]
        comm = _union(
            [(o.start_ns, o.end_ns) for o, c in zip(mine, is_comm) if c]
        )
        compute = _union(
            [(o.start_ns, o.end_ns) for o, c in zip(mine, is_comm) if not c]
        )
        total += sum(b - a for a, b in comm)
        exposed += sum(b - a for a, b in comm) - _overlap(comm, compute)
    return total / len(devs) / 1e9, exposed / len(devs) / 1e9


def _overlap(xs: list[tuple[int, int]], ys: list[tuple[int, int]]) -> int:
    """Total overlap of two sorted lists of disjoint intervals."""
    i = j = tot = 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            tot += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return tot


def top_ops(trace: Trace, t0: int, t1: int, n: int = 10) -> list[list]:
    """[name, seconds] of the operations that took most device time
    (summed over events, averaged over devices)."""
    ops = trace.within(t0, t1)
    devs = _devices(ops)
    if not devs:
        return []
    acc: dict[str, int] = {}
    for o in ops:
        acc[o.name] = acc.get(o.name, 0) + o.dur_ns
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / len(devs) / 1e9] for name, ns in ranked]


def idle_gaps(trace: Trace, t0: int, t1: int, n: int = 10) -> list[list]:
    """[what the host was doing, seconds] for the device's idle time in
    [t0, t1): each gap of the first device's busy union is shared out
    to the host spans that overlap it (the innermost, where spans nest),
    and the rest is ``other``."""
    ops = trace.within(t0, t1)
    devs = _devices(ops)
    mine = [o for o in ops if o.device == devs[0]] if devs else []
    busy = _union([(o.start_ns, o.end_ns) for o in mine])
    gaps, cur = [], t0
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if cur < t1:
        gaps.append((cur, t1))
    # innermost span first: a span that starts later and ends sooner
    spans = sorted(
        (
            s for s in trace.spans
            if s.end_ns > t0 and s.start_ns < t1
            and not s.name.startswith(PHASE_PREFIX)
        ),
        key=lambda s: s.dur_ns,
    )
    acc: dict[str, int] = {}
    for ga, gb in gaps:
        left = [(ga, gb)]
        for s in spans:
            nxt = []
            for a, b in left:
                oa, ob = max(a, s.start_ns), min(b, s.end_ns)
                if ob > oa:
                    acc[s.name] = acc.get(s.name, 0) + (ob - oa)
                    if a < oa:
                        nxt.append((a, oa))
                    if ob < b:
                        nxt.append((ob, b))
                else:
                    nxt.append((a, b))
            left = nxt
        rest = sum(b - a for a, b in left)
        if rest:
            acc["other"] = acc.get("other", 0) + rest
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]
