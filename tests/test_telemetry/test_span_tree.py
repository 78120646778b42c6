"""The span tree (ISSUE 24): one primitive whose spans carry an id, the
parent that caused them and their key's id; self time; the copy a live
profiler session keeps; the jax.monitoring phases as spans."""

import glob
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from magiattention_tpu import telemetry
from magiattention_tpu.telemetry import events


@pytest.fixture(autouse=True)
def clean_telemetry():
    telemetry.set_enabled(True)
    telemetry.reset()
    yield
    telemetry.set_enabled(None)
    telemetry.reset()


def _by_name():
    out = {}
    for ev in telemetry.get_event_buffer().events():
        out.setdefault(ev["name"], []).append(ev)
    return out


def test_span_records_id_parent_and_key():
    with telemetry.span("outer", key="k0") as outer:
        with telemetry.span("inner", cp=4):
            telemetry.record_event("posted", time.perf_counter(), 0.0)
        assert events.current_span() is outer
    assert events.current_span() is None
    ev = {n: e[0] for n, e in _by_name().items()}
    ids = {n: e["args"]["id"] for n, e in ev.items()}
    assert len(set(ids.values())) == 3
    assert "parent" not in ev["outer"]["args"]
    assert ev["inner"]["args"]["parent"] == ids["outer"]
    assert ev["posted"]["args"]["parent"] == ids["inner"]
    assert ev["inner"]["args"]["cp"] == 4
    # a key is inherited by every span under the one that carries it
    assert {e["args"]["key"] for e in ev.values()} == {"k0"}


def test_key_learned_at_the_end_reaches_the_spans_below():
    class Key:  # hashable, as a DistAttnRuntimeKey is
        pass

    key = Key()
    with telemetry.span("key_build") as live:
        with telemetry.span("tile_choice"):
            with telemetry.span("deeper"):
                pass
        live.set(key=key)  # the object itself: recorded as its id
        with telemetry.span("attn_fn_build"):
            pass
    with telemetry.span("elsewhere"):
        pass
    ev = {n: e[0] for n, e in _by_name().items()}
    kid = telemetry.key_id(key)
    assert len(kid) == 8 and kid == telemetry.key_id(key)
    for name in ("key_build", "tile_choice", "deeper", "attn_fn_build"):
        assert ev[name]["args"]["key"] == kid
    assert "key" not in ev["elsewhere"]["args"]


def test_annotate_span_sets_the_innermost_live_span():
    telemetry.annotate_span(cache="nobody")  # no live span: nothing
    with telemetry.span("key_build"):
        with telemetry.span("child"):
            pass
        telemetry.annotate_span(cache="miss")
    ev = {n: e[0] for n, e in _by_name().items()}
    assert ev["key_build"]["args"]["cache"] == "miss"
    assert "cache" not in ev["child"]["args"]


def test_self_seconds_is_duration_minus_the_children():
    buf = events.EventBuffer(maxlen=16)
    root = buf.record("root", 10.0, 10.0)["args"]["id"]
    buf.record("a", 11.0, 2.0, parent=root)
    # b overlaps a by one second and runs past the root's end: the union
    # of the children clipped to the root is [11, 13) + [13, 20)
    b = buf.record("b", 12.0, 9.0, parent=root)["args"]["id"]
    buf.record("grandchild", 12.5, 1.0, parent=b)
    buf.record("orphan", 0.0, 1.0, parent=10**9)  # parent fell out
    assert buf.self_seconds("root") == pytest.approx(1.0)
    assert buf.self_seconds("a") == pytest.approx(2.0)
    assert buf.self_seconds("b") == pytest.approx(8.0)
    assert buf.self_seconds("orphan") == pytest.approx(1.0)
    assert buf.self_seconds("no such span") == 0.0
    own = telemetry.span_self_seconds(buf.events())
    assert own[root] == pytest.approx(1.0)


def test_chrome_export_carries_the_tree(tmp_path):
    import json

    with telemetry.span("outer", key="k1"):
        with telemetry.span("inner"):
            pass
    with open(telemetry.dump_events(str(tmp_path / "t.json"))) as f:
        spans = [e for e in json.load(f)["traceEvents"] if e["ph"] == "X"]
    args = {e["name"]: e["args"] for e in spans}
    assert args["inner"]["parent"] == args["outer"]["id"]
    assert args["inner"]["key"] == args["outer"]["key"] == "k1"


def test_off_path_reads_no_clock_and_touches_no_jax(monkeypatch):
    telemetry.set_enabled(False)

    def boom(*_a, **_k):
        raise AssertionError("the disabled span read the clock")

    class NoJax:
        def __getattr__(self, name):
            raise AssertionError("the disabled span touched jax")

    monkeypatch.setattr(events.time, "perf_counter", boom)
    monkeypatch.setitem(sys.modules, "jax", NoJax())
    with telemetry.span("quiet", key=object()) as live:
        assert live is None
    telemetry.annotate_span(cache="hit")
    telemetry.record_event("quiet", 0.0, 0.0)
    assert len(telemetry.get_event_buffer()) == 0


def test_span_survives_an_exception():
    with pytest.raises(RuntimeError):
        with telemetry.span("boom"):
            raise RuntimeError("x")
    assert [e["name"] for e in telemetry.get_event_buffer().events()] == ["boom"]
    assert events.current_span() is None


def test_span_as_decorator_checks_the_gate_at_each_call():
    @telemetry.span("decorated")
    def f(x):
        return x + 1

    telemetry.set_enabled(False)
    assert f(1) == 2
    assert len(telemetry.get_event_buffer()) == 0
    telemetry.set_enabled(True)
    assert f(2) == 3
    assert [e["name"] for e in telemetry.get_event_buffer().events()] == ["decorated"]


# -- the profiler's copy -----------------------------------------------------


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    out = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(events.ANNOTATION_PREFIX):
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns)
                    )
    return out


def test_live_profiler_session_holds_the_spans_nested(tmp_path):
    """While a jax.profiler session records, a span is also a
    ``magi:<name>`` row of the .xplane.pb, on the profiler's clock."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with telemetry.span("key_build"):
            time.sleep(0.002)
            with telemetry.span("build_dist_attn_plan"):
                time.sleep(0.002)
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    rows = _host_events(str(tmp_path))
    ((k0, k1),) = rows["magi:key_build"]
    ((p0, p1),) = rows["magi:build_dist_attn_plan"]
    assert k0 <= p0 and p1 <= k1
    assert p1 - p0 >= 1_000_000  # the 2 ms sleep, in ns


# -- jax's own phases ---------------------------------------------------------


def test_jax_phases_land_as_spans_with_fun_name():
    tracker = telemetry.get_compile_tracker()
    assert tracker.ingestion == "monitoring"
    # by order, not by what else this worker ran (ROADMAP D7): no span of
    # another test's is live for the phases to be folded into, ...
    assert events.current_span() is None

    def magi_span_probe(x):
        # nested jitted jnp calls, a constant and a length no other test
        # jits: in no cache of this process. The persistent cache may hold
        # it (the harness and the suite keep every program, however quick):
        # a load is a backend-compile phase all the same
        return jnp.sin(x) * 3.0451 + jnp.cos(x)

    x = jnp.arange(45.0)  # its own small program, before the probe's
    # ... and the compile is counted on a label of the probe's own
    with telemetry.program("magi_span_probe"):
        with telemetry.span("around") as around:
            jax.block_until_ready(jax.jit(magi_span_probe)(x))
    mine = {
        n: [e for e in evs if "magi_span_probe" in e["args"].get("fun_name", "")]
        for n, evs in _by_name().items()
    }
    for name in ("jax.trace", "jax.lower", "jax.backend_compile"):
        (ev,) = mine[name]  # one each: nested phases are folded in
        assert ev["args"]["parent"] == around.id
        assert ev["dur"] > 0
    # the tracker's meaning is unchanged: backend compiles only, each
    # counted once (the listeners go in once a process)
    stats = tracker.stats()["magi_span_probe"]
    assert stats["count"] == 1
    # held by order, not by a ratio of two clocks (under six busy workers
    # they drift apart): the tracker's seconds are the compile's, and the
    # compile happened inside the span
    (enclosing,) = [e for e in _by_name()["around"] if e["args"]["id"] == around.id]
    assert 0 < stats["total_s"] <= enclosing["dur"] / 1e6
    assert events.current_span() is None


def test_the_listeners_go_in_once_a_process(monkeypatch):
    """A tracker made anew (a test that empties ``compile._tracker``)
    shares the listeners the first one installed: ``jax.monitoring`` has
    no deregistration, and a second set counted every compile twice."""
    from magiattention_tpu.telemetry import compile as tc
    from magiattention_tpu.utils import compat

    first = telemetry.get_compile_tracker()
    calls = []
    monkeypatch.setattr(
        compat, "register_compile_listeners",
        lambda *a, **k: calls.append(a) or "monitoring",
    )
    monkeypatch.setattr(tc, "_tracker", None)
    second = tc.get_compile_tracker()
    assert second is not first and calls == []
    assert second.ingestion == first.ingestion == "monitoring"

    def magi_once_probe(x):
        return jnp.cos(x) * 2.0451

    x = jnp.arange(46.0)  # its own small program, outside the label
    with telemetry.program("magi_once_probe"):
        jax.block_until_ready(jax.jit(magi_once_probe)(x))
    assert second.stats()["magi_once_probe"]["count"] == 1


def test_phase_that_began_with_telemetry_off_is_recorded_whole():
    from magiattention_tpu.telemetry import compile as tc

    event = "/jax/core/compile/jaxpr_trace_duration"
    telemetry.set_enabled(False)
    tc._on_phase_start(event, time.time(), fun_name="late")
    telemetry.set_enabled(True)
    now = time.time()
    tc._on_phase_end(event, now - 0.25, now, fun_name="late")
    (ev,) = _by_name()["jax.trace"]
    assert ev["args"]["fun_name"] == "late"
    assert ev["dur"] == pytest.approx(0.25e6, rel=1e-3)


def test_cache_events_become_a_span_and_a_counter():
    from magiattention_tpu.telemetry import compile as tc

    telemetry.get_compile_tracker()
    event = "/jax/core/compile/backend_compile_duration"
    tc._on_phase_start(event, time.time(), fun_name="jit(f)")
    tc._on_event("/jax/compilation_cache/cache_hits")
    tc._on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.001)
    now = time.time()
    tc._on_phase_end(event, now, now, fun_name="jit(f)")
    tc._on_event("/jax/compilation_cache/cache_misses")
    tc._on_event("/jax/compilation_cache/cache_misses")
    ev = {n: e[0] for n, e in _by_name().items()}
    load, compile_ = ev["jax.cache_load"], ev["jax.backend_compile"]
    assert load["args"]["parent"] == compile_["args"]["id"]
    assert load["args"]["fun_name"] == "jit(f)"
    assert load["dur"] == pytest.approx(1e3)
    counters = telemetry.snapshot()["counters"]
    assert counters["magi_compile_cache_total{result=hit}"] == 1
    assert counters["magi_compile_cache_total{result=miss}"] == 2


# -- a key's life ---------------------------------------------------------------


@pytest.fixture
def toy_key():
    from jax.sharding import Mesh

    from magiattention_tpu import api

    api.clear_cache()
    mesh = Mesh(np.array(jax.devices()[:1]), ("cp",))
    build = lambda: api.magi_attn_varlen_key(  # noqa: E731
        [0, 100, 256], 256, mesh, num_heads=(2, 1), head_dim=64,
        out_dtype="float32", interpret=True,
    )
    return api, build


def test_key_build_yields_the_span_tree(toy_key):
    api, build = toy_key
    key = build()
    names = _by_name()
    (kb,) = names["key_build"]  # varlen returns through flex: one span
    kid = telemetry.key_id(key)
    assert kb["args"]["key"] == kid and kb["args"]["cache"] == "miss"
    assert "parent" not in kb["args"]
    for child in (
        "tile_choice", "dispatch_solve", "build_dist_attn_plan",
        "attn_fn_build",
    ):
        (ev,) = names[child]
        assert ev["args"]["parent"] == kb["args"]["id"], child
        assert ev["args"]["key"] == kid, child
    buf = telemetry.get_event_buffer()
    assert 0.0 <= buf.self_seconds("key_build") <= kb["dur"] / 1e6
    # the histogram behind attn_plan_build_ms still covers the plan alone
    hist = telemetry.snapshot()["histograms"]["magi_plan_build_seconds"]
    assert hist["count"] == 1
    assert hist["sum"] <= kb["dur"] / 1e6

    telemetry.get_event_buffer().clear()
    assert build() == key
    (hit,) = _by_name()["key_build"]
    assert hit["args"]["cache"] == "hit" and hit["args"]["key"] == kid


def test_runtime_calls_carry_the_key(toy_key):
    api, build = toy_key
    key = build()
    kid = telemetry.key_id(key)
    telemetry.get_event_buffer().clear()
    x = jnp.ones((256, 2, 64), jnp.float32)
    kv = jnp.ones((256, 1, 64), jnp.float32)
    xd = api.dispatch(x, key)
    kd = api.dispatch(kv, key)

    def fwd(q, k, v):
        out, _meta = api.calc_attn(q, k, v, key)
        return out

    lowered = jax.jit(fwd).lower(xd, kd, kd)
    api.undispatch(xd, key)
    names = _by_name()
    assert len(names["dispatch"]) == 2 and len(names["undispatch"]) == 1
    for ev in names["dispatch"] + names["undispatch"]:
        assert ev["args"]["key"] == kid
    (ct,) = names["calc_attn.trace"]
    assert ct["args"]["key"] == kid
    (outer,) = [
        e for e in names["jax.trace"] if e["args"]["fun_name"] == "fwd"
    ]
    assert ct["args"]["parent"] == outer["args"]["id"]
    assert outer["dur"] >= ct["dur"]
    del lowered


def test_plan_flex_attn_span_has_the_same_children():
    from jax.sharding import Mesh

    from magiattention_tpu.common.ranges import AttnRanges
    from magiattention_tpu.models._common import plan_flex_attn

    class Cfg:
        n_heads, n_kv_heads, head_dim, dtype = 2, 1, 64, jnp.float32

    mesh = Mesh(np.array(jax.devices()[:1]), ("cp",))
    ranges = AttnRanges.from_ranges([(0, 100), (100, 256)])
    plan_flex_attn(
        Cfg, mesh, 256, ranges, ranges, [1, 1], chunk_size=128,
        cp_axis="cp", interpret=True,
    )
    names = _by_name()
    (root,) = names["plan_flex_attn"]
    for child in (
        "dispatch_solve", "tile_choice", "build_dist_attn_plan",
        "attn_fn_build",
    ):
        (ev,) = names[child]
        assert ev["args"]["parent"] == root["args"]["id"], child
