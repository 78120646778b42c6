"""Seconds the program's own spans took during set-up: the spans named
in ``spans`` that ended before the window opened, read from the
program's in-memory span buffer (``telemetry.get_event_buffer()``), so
that the check's key builds and compiles after the window are left out.

``{"kind": "program_span", "spans": [<name>, ...], "self": false,
"scale": 1000.0}``

A span with an ancestor among the named ones is part of that ancestor
and is not counted again (a key built for another key's plan reuse).
With ``"self": true`` each span counts its self time instead: its
duration minus what its children cover.

The span buffer and ``setup_s`` are both on ``time.perf_counter``, so
the window opened at the harness's start plus ``setup_s`` (late by the
harness's own import, some milliseconds in which the program records
nothing). A program whose spans form no tree yet has nothing to read; in
one that has, a span that never ran reads 0.
"""

from .. import harness


def read(spec: dict, obs):
    from magiattention_tpu import telemetry

    self_seconds = getattr(telemetry, "span_self_seconds", None)
    if self_seconds is None:
        return None
    buffer = telemetry.get_event_buffer()
    if buffer.dropped:
        harness.log(
            f"WARNING: the span ring dropped {buffer.dropped} spans; "
            f"{spec['spans']} may read low"
        )
    events = buffer.events()
    opened_us = 1e6 * (harness._T0 + obs.end_to_end["setup_s"])
    names = set(spec["spans"])
    by_id = {ev["args"]["id"]: ev for ev in events}
    own = self_seconds(events) if spec.get("self") else None

    def inside_a_named_one(ev) -> bool:
        parent = by_id.get(ev["args"].get("parent"))
        while parent is not None:
            if parent["name"] in names:
                return True
            parent = by_id.get(parent["args"].get("parent"))
        return False

    total = 0.0
    for ev in events:
        if ev["name"] not in names or ev["ts"] + ev["dur"] > opened_us:
            continue
        if own is not None:
            total += own[ev["args"]["id"]]
        elif not inside_a_named_one(ev):
            total += ev["dur"] / 1e6
    return total * spec.get("scale", 1.0)
