"""A trace's Python time by part (ISSUE 51): while telemetry is on and
jax is tracing the caller, ``utils.instrument.named_scope`` is also a
host span ``trace_part`` with ``scope=<name>``; the ``jax.named_scope`` it
emits is the same either way."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from magiattention_tpu import telemetry
from magiattention_tpu.utils.instrument import named_scope


@pytest.fixture(autouse=True)
def clean_telemetry():
    telemetry.set_enabled(True)
    telemetry.reset()
    telemetry.get_compile_tracker()  # the listeners that make jax.trace
    yield
    telemetry.set_enabled(None)
    telemetry.reset()


def _toy_step():
    """A fresh function each call (jax caches a trace by the function)."""

    def magi_part_probe(x, w):
        with named_scope("magi_proj"):
            h = x @ w
            with named_scope("magi_mla_q"):
                h = jnp.tanh(h)
        with named_scope("magi_ffn"):
            h = h * 2.0 + x
        with named_scope("magi_proj"):
            return (h @ w.T).sum()

    return magi_part_probe


ARGS = (jnp.ones((8, 16), jnp.float32), jnp.ones((16, 16), jnp.float32))


def _spans(name):
    return [
        ev for ev in telemetry.get_event_buffer().events() if ev["name"] == name
    ]


def test_under_a_trace_each_scope_is_a_span_with_the_right_parent():
    jax.jit(jax.grad(_toy_step())).lower(*ARGS)
    (trace,) = [
        ev for ev in _spans("jax.trace")
        if "magi_part_probe" in ev["args"]["fun_name"]
    ]
    parts = _spans("trace_part")
    assert [p["args"]["scope"] for p in parts] == [
        # a span is recorded as it ends: the inner one first
        "magi_mla_q", "magi_proj", "magi_ffn", "magi_proj",
    ]
    inner, outer, ffn, last = parts
    assert inner["args"]["parent"] == outer["args"]["id"]
    for part in (outer, ffn, last):
        assert part["args"]["parent"] == trace["args"]["id"]
    for part in parts:
        assert part["dur"] > 0
        assert trace["ts"] <= part["ts"]
        assert part["ts"] + part["dur"] <= trace["ts"] + trace["dur"]
    assert telemetry.events.current_span() is None


def test_an_eager_call_records_nothing():
    with named_scope("magi_proj"):
        x = np.ones(3) * 2.0
    with telemetry.span("around"):  # a live span that is no jax phase
        with named_scope("magi_ffn"):
            x = x + 1.0
    assert _spans("trace_part") == []


def test_with_telemetry_off_a_trace_records_nothing():
    telemetry.set_enabled(False)
    jax.jit(_toy_step()).lower(*ARGS)
    assert len(telemetry.get_event_buffer()) == 0


def test_the_lowered_text_is_the_same_with_the_span_on_and_off():
    """Scopes, source lines and all: the span changes nothing jax sees."""
    texts = {}
    for on in (True, False, True):
        telemetry.set_enabled(on)
        lowered = jax.jit(jax.grad(_toy_step(), argnums=(0, 1))).lower(*ARGS)
        texts.setdefault(on, []).append(lowered.as_text(debug_info=True))
    assert "jvp(magi_proj)/magi_mla_q" in texts[True][0]
    assert texts[True][0] == texts[False][0] == texts[True][1]
    assert len(_spans("trace_part")) == 8  # and the span was on, twice


def _subtree(events, root_id):
    below = collections.defaultdict(list)
    for ev in events:
        below[ev["args"].get("parent")].append(ev)
    out, todo = [], [root_id]
    while todo:
        for ev in below[todo.pop()]:
            out.append(ev)
            todo.append(ev["args"]["id"])
    return out


def test_a_toy_decoders_trace_adds_up_by_part():
    """``trace_part`` self-seconds + ``calc_attn.trace`` + the self time
    of ``jax.trace`` are the ``jax.trace`` span, to a millisecond, and the
    ring drops nothing on a step."""
    from tests.test_models import pattern_harness as toy
    from tests.test_models.test_scope_catalogue import toy_model

    with jax.enable_x64(False):
        model, params = toy_model("afmoe")
        opt = optax.adamw(1e-3)
        batch = jnp.zeros((1, toy.TOTAL), jnp.int32)
        telemetry.reset()
        model.make_train_step(opt).lower(
            params, opt.init(params), batch, batch, batch
        )
    buffer = telemetry.get_event_buffer()
    assert buffer.dropped == 0
    events = buffer.events()
    own = telemetry.span_self_seconds(events)
    (trace,) = [
        ev for ev in events
        if ev["name"] == "jax.trace" and ev["args"]["fun_name"] == "step"
    ]
    inside = _subtree(events, trace["args"]["id"])
    assert {ev["name"] for ev in inside} <= {"trace_part", "calc_attn.trace"}
    by_scope = collections.Counter()
    for ev in inside:
        by_scope[ev["args"].get("scope", ev["name"])] += own[ev["args"]["id"]]
    for scope in (
        "magi_embed", "magi_proj", "magi_attn_full", "magi_attn_sliding",
        "magi_merged_kernel", "magi_moe_experts", "magi_head", "magi_optimizer",
    ):
        assert by_scope[scope] > 0, scope
    total = sum(by_scope.values()) + own[trace["args"]["id"]]
    assert total == pytest.approx(trace["dur"] / 1e6, abs=1e-3)
    # a step's spans are hundreds, the ring holds thousands
    assert len(inside) < 1000


def test_a_keyed_calls_trace_nests_where_it_lies():
    """``calc_attn.trace`` under a scope of the caller's is that part's
    child, and the runtime's own scopes are its children."""
    from jax.sharding import Mesh

    from magiattention_tpu import api

    api.clear_cache()
    mesh = Mesh(np.array(jax.devices()[:1]), ("cp",))
    key = api.magi_attn_varlen_key(
        [0, 100, 256], 256, mesh, num_heads=(2, 1), head_dim=64,
        out_dtype="float32", interpret=True,
    )
    q = api.dispatch(jnp.ones((256, 2, 64), jnp.float32), key)
    kv = api.dispatch(jnp.ones((256, 1, 64), jnp.float32), key)
    telemetry.reset()

    def magi_nest_probe(q, k, v):
        with named_scope("magi_attn_full"):
            out, _meta = api.calc_attn(q, k, v, key)
        return out

    jax.jit(magi_nest_probe).lower(q, kv, kv)
    events = telemetry.get_event_buffer().events()
    by_id = {ev["args"]["id"]: ev for ev in events}
    (attn,) = _spans("calc_attn.trace")
    caller = by_id[attn["args"]["parent"]]
    assert caller["name"] == "trace_part"
    assert caller["args"]["scope"] == "magi_attn_full"
    assert by_id[caller["args"]["parent"]]["name"] == "jax.trace"
    below = _subtree(events, attn["args"]["id"])
    assert {ev["args"]["scope"] for ev in below} >= {"magi_layout"}
    own = telemetry.span_self_seconds(events)
    assert sum(own[ev["args"]["id"]] for ev in below) <= attn["dur"] / 1e6
