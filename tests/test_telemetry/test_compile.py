"""Compile tracker (ISSUE 16): program-label attribution, per-tick
marks, the always-on solver accumulator, the gated registry mirror, and
the recompile-storm trigger."""

import pytest

from magiattention_tpu import telemetry
from magiattention_tpu.telemetry import compile as comp
from magiattention_tpu.telemetry import trace


@pytest.fixture(autouse=True)
def clean_telemetry():
    telemetry.set_enabled(None)
    telemetry.reset()
    telemetry.reset_compile_tracker()
    yield
    telemetry.set_enabled(None)
    telemetry.reset()
    telemetry.reset_compile_tracker()


class TestProgramLabels:
    def test_no_label_outside_context(self):
        assert comp.current_program() is None

    def test_context_sets_and_restores(self):
        with comp.program("decode[b=4]"):
            assert comp.current_program() == "decode[b=4]"
        assert comp.current_program() is None

    def test_nesting_keeps_innermost(self):
        with comp.program("outer"):
            with comp.program("inner"):
                assert comp.current_program() == "inner"
            assert comp.current_program() == "outer"

    def test_canonical_labels(self):
        assert comp.prefill_program_label(16, 8) == "prefill[start=16,t=8]"
        assert comp.decode_program_label(3) == "decode[b=3]"


class TestTrackerAccounting:
    def test_note_compile_attributes_to_live_label(self):
        tr = comp.get_compile_tracker()
        with comp.program("decode[b=2]"):
            tr.note_compile(0.25)
        tr.note_compile(0.5)  # outside any label -> anon
        stats = tr.stats()
        assert stats["decode[b=2]"] == {"count": 1, "total_s": 0.25}
        assert stats[comp.ANON_PROGRAM]["count"] == 1
        assert tr.total() == (2, 0.75)

    def test_explicit_label_overrides_context(self):
        tr = comp.get_compile_tracker()
        with comp.program("ctx"):
            tr.note_compile(0.1, label="explicit")
        assert "explicit" in tr.stats()
        assert "ctx" not in tr.stats()

    def test_mark_since_gives_tick_deltas(self):
        tr = comp.get_compile_tracker()
        tr.note_compile(1.0)
        mark = tr.mark()
        tr.note_compile(0.5)
        tr.note_compile(0.25)
        count, seconds = tr.since(mark)
        assert count == 2
        assert seconds == pytest.approx(0.75)

    def test_solver_accumulator_always_on(self):
        telemetry.set_enabled(False)
        tr = comp.get_compile_tracker()
        mark = tr.solver_mark()
        comp.add_solver_seconds(0.002)
        comp.add_solver_seconds(0.001)
        assert tr.solver_since(mark) == pytest.approx(0.003)
        # nothing leaked into the gated registry
        snap = telemetry.snapshot()
        assert not any(snap.values())

    def test_plan_build_mean(self):
        tr = comp.get_compile_tracker()
        assert tr.plan_build_mean_s() is None
        tr.note_plan_build(0.010)
        tr.note_plan_build(0.020)
        assert tr.plan_build_mean_s() == pytest.approx(0.015)

    def test_reset_clears_records(self):
        tr = comp.get_compile_tracker()
        tr.note_compile(1.0)
        tr.note_plan_build(0.01)
        comp.add_solver_seconds(0.5)
        telemetry.reset_compile_tracker()
        assert tr.total() == (0, 0.0)
        assert tr.stats() == {}
        assert tr.plan_build_mean_s() is None

    def test_listener_ingestion_mode_recorded(self):
        tr = comp.get_compile_tracker()
        assert tr.ingestion in ("monitoring", "none")

    def test_duration_listener_filters_event_names(self):
        tr = comp.get_compile_tracker()
        before = tr.total()[0]
        comp._on_duration("/jax/core/unrelated_event", 1.0)
        assert tr.total()[0] == before
        comp._on_duration(
            "/jax/core/compile/backend_compile_duration", 0.1
        )
        assert tr.total()[0] == before + 1


class TestRegistryMirror:
    def test_enabled_mirrors_to_registry(self):
        telemetry.set_enabled(True)
        tr = comp.get_compile_tracker()
        with comp.program("prefill[start=0,t=8]"):
            tr.note_compile(0.5)
        snap = telemetry.snapshot()
        key = "magi_compile_total{program=prefill[start=0,t=8]}"
        assert snap["counters"][key] == 1.0
        assert snap["histograms"]["magi_compile_seconds"]["count"] == 1
        assert snap["gauges"]["magi_jit_cache_entries"] >= 1

    def test_disabled_records_nothing_in_registry(self):
        telemetry.set_enabled(False)
        tr = comp.get_compile_tracker()
        tr.note_compile(0.5)
        snap = telemetry.snapshot()
        assert not any(snap.values())
        # but the always-on tracker still counted it
        assert tr.total() == (1, 0.5)

    def test_record_plan_solver_hit_credits_build_mean(self):
        telemetry.set_enabled(True)
        tr = comp.get_compile_tracker()
        telemetry.record_plan_solver(0.010, cache_hit=False)
        telemetry.record_plan_solver(0.0001, cache_hit=True)
        snap = telemetry.snapshot()
        assert snap["counters"][
            "magi_plan_solver_ms_saved_total"
        ] == pytest.approx(10.0)
        hists = snap["histograms"]
        assert hists["magi_plan_solver_seconds{outcome=miss}"]["count"] == 1
        assert hists["magi_plan_solver_seconds{outcome=hit}"]["count"] == 1
        # the always-on accumulator saw both resolutions
        assert tr.solver_mark() == pytest.approx(0.0101)

    def test_hit_before_any_build_credits_nothing(self):
        telemetry.set_enabled(True)
        telemetry.record_plan_solver(0.0001, cache_hit=True)
        snap = telemetry.snapshot()
        assert "magi_plan_solver_ms_saved_total" not in snap["counters"]


class TestTickCensus:
    def test_record_tick_programs_distinct_launches(self):
        telemetry.set_enabled(True)
        telemetry.record_tick_programs(
            step=3, start_s=1.0, wall_s=0.01,
            programs=["decode[b=2]", "prefill[start=0,t=8]",
                      "prefill[start=0,t=8]"],
            compiles=1, solver_s=0.001, compile_s=0.002,
            device_s=0.005, residual_s=0.002,
        )
        snap = telemetry.snapshot()
        hist = snap["histograms"]["magi_sched_launches_per_tick"]
        assert hist["count"] == 1
        assert hist["max"] == 2.0  # DISTINCT programs, not raw launches
        evs = [
            e for e in telemetry.get_event_buffer().events()
            if e["name"] == "sched_tick"
        ]
        assert len(evs) == 1
        args = evs[0]["args"]
        assert args["launches"] == 2
        assert args["programs"] == {
            "decode[b=2]": 1, "prefill[start=0,t=8]": 2,
        }
        assert args["residual_ms"] == pytest.approx(2.0)

    def test_negative_residual_surfaced_not_clamped(self):
        telemetry.set_enabled(True)
        telemetry.record_tick_programs(
            step=1, start_s=0.0, wall_s=0.001, programs=[],
            compiles=5, solver_s=0.0, compile_s=0.5, device_s=0.0,
            residual_s=-0.499,
        )
        evs = [
            e for e in telemetry.get_event_buffer().events()
            if e["name"] == "sched_tick"
        ]
        assert evs[0]["args"]["residual_ms"] == pytest.approx(-499.0)


class TestRecompileStorm:
    def test_storm_fires_deferred_trigger_at_threshold(
        self, monkeypatch, tmp_path
    ):
        monkeypatch.setenv(
            "MAGI_ATTENTION_RECOMPILE_STORM_THRESHOLD", "3"
        )
        monkeypatch.setenv("MAGI_ATTENTION_TRACE_DIR", str(tmp_path))
        trace.reset_flight_recorder()
        fr = trace.get_flight_recorder()
        tr = comp.get_compile_tracker()
        tr.note_tick(42)
        fr.record_tick({"step": 42})
        with comp.program("thrash"):
            for _ in range(4):
                tr.note_compile(0.01)
        path = fr.flush()
        trace.reset_flight_recorder()
        assert path is not None
        import json

        with open(path) as fh:
            dump = json.load(fh)
        assert dump["trigger"]["trigger"] == "recompile_storm"
        ctx = dump["trigger"]["context"]
        assert ctx["program"] == "thrash"
        assert ctx["tick"] == 42
        assert ctx["threshold"] == 3
        assert ctx["compiles_in_window"] == 3

    def test_no_storm_when_disabled(self, monkeypatch, tmp_path):
        monkeypatch.delenv(
            "MAGI_ATTENTION_RECOMPILE_STORM_THRESHOLD", raising=False
        )
        monkeypatch.setenv("MAGI_ATTENTION_TRACE_DIR", str(tmp_path))
        trace.reset_flight_recorder()
        fr = trace.get_flight_recorder()
        tr = comp.get_compile_tracker()
        fr.record_tick({"step": 1})
        with comp.program("thrash"):
            for _ in range(10):
                tr.note_compile(0.01)
        assert fr.flush() is None
        trace.reset_flight_recorder()

    def test_different_labels_do_not_alias_into_a_storm(
        self, monkeypatch, tmp_path
    ):
        monkeypatch.setenv(
            "MAGI_ATTENTION_RECOMPILE_STORM_THRESHOLD", "3"
        )
        monkeypatch.setenv("MAGI_ATTENTION_TRACE_DIR", str(tmp_path))
        trace.reset_flight_recorder()
        fr = trace.get_flight_recorder()
        tr = comp.get_compile_tracker()
        fr.record_tick({"step": 1})
        for i in range(6):  # 6 compiles, never 3 of ONE label
            with comp.program(f"label{i % 3}"):
                tr.note_compile(0.01)
        # 2 per label < threshold: nothing armed
        assert fr.flush() is None
        trace.reset_flight_recorder()

    def test_invalid_threshold_rejected(self, monkeypatch):
        from magiattention_tpu import env

        monkeypatch.setenv(
            "MAGI_ATTENTION_RECOMPILE_STORM_THRESHOLD", "-1"
        )
        with pytest.raises(ValueError, match="RECOMPILE_STORM"):
            env.recompile_storm_threshold()


class TestCacheKeyHoldsMetadataWhileTelemetryIsOn:
    """A ``named_scope`` lives in a program's debug info, which jax
    strips from the persistent cache's key by default: a traced run could
    load an executable older code compiled and read that code's scopes.
    While telemetry is on the key holds the metadata (ISSUE 34)."""

    OPTION = "jax_compilation_cache_include_metadata_in_key"

    @staticmethod
    def _key(scope: str) -> str:
        """The persistent cache's key of one function lowered under
        ``scope`` (the same source lines whatever the scope is)."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax._src import cache_key, compiler

        from magiattention_tpu.utils.instrument import named_scope

        def f(x):
            with named_scope(scope):
                return x * 2 + 1

        devices = np.array(jax.devices()[:1])
        return cache_key.get(
            jax.jit(f).lower(jnp.ones(4)).compiler_ir(),
            devices,
            compiler.get_compile_options(num_replicas=1, num_partitions=1),
            devices[0].client,
        )

    def test_one_key_off_two_keys_on_and_none_restores(self):
        import jax

        def keys():  # one call site: the key then holds its line too
            return [self._key(s) for s in ("magi_a", "magi_b", "magi_a")]

        was = getattr(jax.config, self.OPTION)
        assert len(set(keys())) == 1
        telemetry.set_enabled(True)
        assert getattr(jax.config, self.OPTION) is True
        a, b, a_again = keys()
        assert a == a_again != b
        telemetry.set_enabled(None)
        assert getattr(jax.config, self.OPTION) == was
        assert len(set(keys())) == 1

    @pytest.mark.parametrize("jaxs_own", [False, True])
    def test_off_gives_back_what_jax_had(self, jaxs_own):
        import jax

        was = getattr(jax.config, self.OPTION)
        jax.config.update(self.OPTION, jaxs_own)
        try:
            telemetry.set_enabled(True)
            telemetry.set_enabled(True)  # twice on: jax's value is kept
            assert getattr(jax.config, self.OPTION) is True
            telemetry.set_enabled(False)
            assert getattr(jax.config, self.OPTION) is jaxs_own
        finally:
            telemetry.set_enabled(None)
            jax.config.update(self.OPTION, was)

    def test_the_env_flag_alone_turns_it_on_where_the_listeners_install(
        self, monkeypatch
    ):
        import jax

        monkeypatch.setenv("MAGI_ATTENTION_TELEMETRY", "1")
        monkeypatch.setattr(comp, "_tracker", None)
        try:
            comp.get_compile_tracker()
            assert getattr(jax.config, self.OPTION) is True
        finally:
            monkeypatch.delenv("MAGI_ATTENTION_TELEMETRY")
            telemetry.set_enabled(None)
        assert getattr(jax.config, self.OPTION) is False
