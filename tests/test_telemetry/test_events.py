"""Chrome-trace export metadata (ISSUE 3 satellite): dump_events names
every pid/tid track with phase-M metadata events so Perfetto shows
human-readable labels."""

import json
import os
import threading

from magiattention_tpu import telemetry
from magiattention_tpu.telemetry.events import (
    EventBuffer,
    trace_metadata_events,
)


def test_dump_events_emits_track_metadata(tmp_path):
    buf = EventBuffer(maxlen=16)
    buf.record("plan_build", 0.0, 0.5, {"cp": 4})
    path = buf.dump(str(tmp_path / "trace.json"))
    with open(path) as f:
        trace = json.load(f)
    evs = trace["traceEvents"]
    meta = [e for e in evs if e.get("ph") == "M"]
    spans = [e for e in evs if e.get("ph") == "X"]
    assert len(spans) == 1
    pid, tid = os.getpid(), threading.get_ident()
    proc = [e for e in meta if e["name"] == "process_name"]
    thr = [e for e in meta if e["name"] == "thread_name"]
    assert [e["pid"] for e in proc] == [pid]
    assert str(pid) in proc[0]["args"]["name"]
    assert [(e["pid"], e["tid"]) for e in thr] == [(pid, tid)]


def _two_named_tracks(tmp_path):
    buf = EventBuffer(maxlen=16)
    buf.record("cast", 0.0, 0.1, {}, track="hop 1 (cp)")
    buf.record("cast", 0.1, 0.1, {}, track="hop 2 (cp)")
    buf.record("cast", 0.2, 0.1, {}, track="hop 1 (cp)")
    with open(buf.dump(str(tmp_path / "tracks.json"))) as f:
        return json.load(f)


def _thread_names(trace):
    return [
        (e["pid"], e["args"]["name"])
        for e in trace["traceEvents"]
        if e.get("ph") == "M" and e["name"] == "thread_name"
    ]


def test_named_tracks_get_a_tid_and_a_name_each(tmp_path):
    trace = _two_named_tracks(tmp_path)
    names = sorted(n for _, n in _thread_names(trace))
    assert names == ["hop 1 (cp)", "hop 2 (cp)"]
    tids = [e["tid"] for e in trace["traceEvents"] if e.get("ph") == "X"]
    assert tids[0] == tids[2] != tids[1]


def test_merge_keeps_one_named_track_per_rank(tmp_path):
    trace = _two_named_tracks(tmp_path)
    merged = telemetry.merge_chrome_traces(
        [trace, json.loads(json.dumps(trace))], labels=["r0", "r1"]
    )
    named = _thread_names(merged)
    assert len(named) == len(set(named)) == 4
    assert {pid for pid, _ in named} == {0, 1}


def test_trace_metadata_events_ignores_existing_metadata():
    events = [
        {"name": "x", "ph": "X", "pid": 1, "tid": 2},
        {"name": "process_name", "ph": "M", "pid": 9, "tid": 0,
         "args": {"name": "stale"}},
    ]
    meta = trace_metadata_events(events)
    assert {e["pid"] for e in meta} == {1}


def test_trace_metadata_custom_process_name():
    events = [{"name": "x", "ph": "X", "pid": 1, "tid": 2}]
    meta = trace_metadata_events(events, process_name="rank 3")
    proc = [e for e in meta if e["name"] == "process_name"]
    assert proc[0]["args"]["name"] == "rank 3"


def test_empty_buffer_dump_has_no_metadata(tmp_path):
    buf = EventBuffer(maxlen=4)
    path = buf.dump(str(tmp_path / "empty.json"))
    with open(path) as f:
        trace = json.load(f)
    assert trace["traceEvents"] == []


def test_global_dump_events_roundtrip(tmp_path):
    telemetry.set_enabled(True)
    try:
        telemetry.reset()
        with telemetry.span("spanned"):
            pass
        path = telemetry.dump_events(str(tmp_path / "t.json"))
        with open(path) as f:
            trace = json.load(f)
        names = [e["name"] for e in trace["traceEvents"]]
        assert "spanned" in names and "process_name" in names
    finally:
        telemetry.set_enabled(None)
        telemetry.reset()
