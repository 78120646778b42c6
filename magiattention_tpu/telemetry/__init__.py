"""Runtime telemetry: metrics registry, span events, structured export.

The observability spine of MagiAttention-TPU (ISSUE 1). The runtime
computes everything the paper's value proposition rests on — per-rank
comm volume, chunk balance, overlap degree, kernel step counts — during
planning; this package records those facts instead of discarding them.

Layout:

- :mod:`.registry`   — process-global counters/gauges/histograms +
  ``snapshot()``/``dump``
- :mod:`.events`     — host-side span ring buffer + Chrome-trace export
- :mod:`.collectors` — the ``record_*`` hooks each planning layer calls
  (and the metric-name catalog)
- :mod:`.logger`     — ``MAGI_ATTENTION_LOG_LEVEL`` -> logging config

Gating: everything is OFF by default. ``MAGI_ATTENTION_TELEMETRY=1`` (or
``set_enabled(True)`` programmatically, e.g. from tests and benches) turns
recording on; while off, every hook is a single predicate call — no dict
churn, no clock reads, and nothing whatsoever inside jitted regions
(recording is host-side plan/bench-time only by construction).

Typical use::

    from magiattention_tpu import telemetry
    telemetry.set_enabled(True)
    ... build plans / run benches ...
    snap = telemetry.snapshot()
    telemetry.dump_metrics("metrics.json")
    telemetry.dump_events("trace.json")   # chrome://tracing format
"""

from __future__ import annotations

from .aggregate import (  # noqa: F401
    aggregate_across_mesh,
    merge_chrome_traces,
    merge_snapshots,
)
from .collectors import (  # noqa: F401
    REQUIRED_ANALYSIS_METRICS,
    REQUIRED_COMPILE_METRICS,
    REQUIRED_DISTSERVE_METRICS,
    REQUIRED_MEMORY_METRICS,
    REQUIRED_NUMERICS_METRICS,
    REQUIRED_PLAN_CACHE_METRICS,
    REQUIRED_PLAN_METRICS,
    REQUIRED_PREFIX_METRICS,
    REQUIRED_RESILIENCE_METRICS,
    REQUIRED_SCHED_METRICS,
    REQUIRED_SERVING_METRICS,
    REQUIRED_TRACE_METRICS,
    REQUIRED_VALIDATE_METRICS,
    record_admission,
    record_admission_watermark,
    record_analysis_run,
    record_autotune_cache,
    record_autotune_decision,
    record_autotune_measure_failure,
    record_autotune_measurement,
    record_cache_access,
    record_comm_op,
    record_compile,
    record_compile_cache,
    record_decode_step,
    record_degraded_path,
    record_dispatch_meta,
    record_flex_bwd_form,
    record_flex_forward_kept,
    record_flex_dead_step_share,
    record_flex_stepped_tile_share,
    record_mask_step,
    record_flex_kernel_build,
    record_model_attn_plan,
    record_moe_rows_permuted,
    record_moe_route_ahead,
    record_handed_on,
    record_mhc,
    record_mhc_coef,
    record_mla_kv_cast_width,
    record_ssd_model,
    record_ssd_scan,
    record_ssm_scan,
    record_model_loop,
    record_shift,
    record_moe_load,
    record_dispatch_solution,
    record_dynamic_solution,
    record_group_collective_build,
    record_guard_check,
    record_guard_repair,
    record_guard_violation,
    record_hbm_sample,
    record_kvcache_state,
    record_memory_comparison,
    record_memory_ledger,
    record_memory_measurement,
    record_memory_pool,
    record_numerics_census,
    record_overlap_choice,
    record_page_stream,
    record_plan,
    record_plan_bucket,
    record_plan_cache_eviction,
    record_plan_incremental,
    record_plan_solver,
    record_prefill,
    record_prefix_cow,
    record_prefix_eviction,
    record_prefix_lookup,
    record_prefix_registered,
    record_request_queue_time,
    record_request_token_latency,
    record_request_ttft,
    record_runtime_costs,
    record_sched_step,
    record_shadow_check,
    record_stream_queue_depth,
    record_tick_programs,
    record_tier_fault,
    record_tier_state,
    record_tuning_cache_io_error,
    record_validate,
    telemetry_summary,
)
from .compile import (  # noqa: F401
    CompileTracker,
    add_solver_seconds,
    current_program,
    decode_program_label,
    get_compile_tracker,
    key_cache_on_metadata,
    prefill_program_label,
    program,
    reset_compile_tracker,
    tick_program_label,
)
from .events import (  # noqa: F401
    EventBuffer,
    annotate_span,
    get_event_buffer,
    key_id,
    post_boot_spans,
    record_event,
    span,
    span_self_seconds,
    trace_metadata_events,
)
from .exposition import (  # noqa: F401
    MetricsServer,
    ensure_metrics_server,
    parse_prometheus_text,
    render_prometheus,
    snapshot_delta,
    start_metrics_server,
    stop_metrics_server,
)
from .trace import (  # noqa: F401
    FlightRecorder,
    RequestTrace,
    dump_request_traces,
    dump_request_traces_jsonl,
    export_request_traces,
    get_flight_recorder,
    record_request_span,
    request_context,
    request_traces_to_chrome,
    reset_flight_recorder,
    reset_request_traces,
)
from .memory import (  # noqa: F401
    LedgerEntry,
    MemoryComparison,
    MemoryLedger,
    MemPressureWatcher,
    PoolFragmentationMap,
    engine_memory_snapshot,
    fragmentation_map,
    ledger_vs_measured,
    measure_program_memory,
    plan_memory_ledger,
    sample_memory_stats,
    serving_memory_ledger,
    tiered_memory_ledger,
)
from .numerics import (  # noqa: F401
    DEFAULT_BUDGETS,
    DivergenceReport,
    ErrorBudget,
    ErrorBudgetExceeded,
    NumericsCensus,
    assert_within_budget,
    budget_for_dtype,
    divergence_report,
    get_numerics_census,
    nudge_ulps,
    reset_numerics_census,
    ulp_distance,
)
from .logger import configure_logging, get_logger  # noqa: F401
from .registry import (  # noqa: F401
    MetricsRegistry,
    get_registry,
    series_key,
)

# tri-state programmatic override: None -> defer to the env flag
_enabled_override: bool | None = None


def enabled() -> bool:
    """Is telemetry recording on? Programmatic override first, then the
    ``MAGI_ATTENTION_TELEMETRY`` env flag. This is THE gate every hook
    checks; keep it a couple of dict lookups."""
    if _enabled_override is not None:
        return _enabled_override
    from .. import env

    return env.is_telemetry_enabled()


def set_enabled(value: bool | None) -> None:
    """Force telemetry on/off (``True``/``False``) or restore env-flag
    control (``None``). Benches and tests use this; long-running jobs
    usually just set the env var. While it is on, the persistent
    compilation cache's key holds the program's metadata, so that a
    traced run sees the scopes of the code it runs
    (:func:`compile.key_cache_on_metadata`). The first time it comes on
    in a process, the ring gets the process's own two spans,
    ``process_boot`` and ``package_import``
    (:func:`events.post_boot_spans`)."""
    global _enabled_override
    _enabled_override = value
    key_cache_on_metadata(enabled())
    post_boot_spans()


def snapshot() -> dict:
    """Plain-dict snapshot of the global registry (always available, even
    when disabled — it is then simply empty)."""
    return get_registry().snapshot()


def reset() -> None:
    """Clear the global registry, the span ring buffer, and the
    per-request trace sequence counters. The process's two start-up
    spans go with the ring: ``post_boot_spans(again=True)`` posts them
    anew."""
    get_registry().reset()
    get_event_buffer().clear()
    reset_request_traces()


def dump_metrics(path: str) -> str:
    """Write the registry snapshot as JSON; returns ``path``."""
    return get_registry().dump(path)


def dump_events(path: str) -> str:
    """Write buffered spans as Chrome trace-event JSON; returns ``path``."""
    return get_event_buffer().dump(path)


__all__ = [
    "CompileTracker",
    "EventBuffer",
    "FlightRecorder",
    "LedgerEntry",
    "MemPressureWatcher",
    "MemoryComparison",
    "MemoryLedger",
    "DEFAULT_BUDGETS",
    "DivergenceReport",
    "ErrorBudget",
    "ErrorBudgetExceeded",
    "MetricsRegistry",
    "MetricsServer",
    "NumericsCensus",
    "PoolFragmentationMap",
    "REQUIRED_ANALYSIS_METRICS",
    "REQUIRED_COMPILE_METRICS",
    "REQUIRED_MEMORY_METRICS",
    "REQUIRED_NUMERICS_METRICS",
    "REQUIRED_PLAN_METRICS",
    "REQUIRED_RESILIENCE_METRICS",
    "REQUIRED_SERVING_METRICS",
    "REQUIRED_TRACE_METRICS",
    "REQUIRED_VALIDATE_METRICS",
    "RequestTrace",
    "add_solver_seconds",
    "aggregate_across_mesh",
    "annotate_span",
    "assert_within_budget",
    "budget_for_dtype",
    "divergence_report",
    "configure_logging",
    "current_program",
    "decode_program_label",
    "dump_events",
    "dump_metrics",
    "dump_request_traces",
    "dump_request_traces_jsonl",
    "enabled",
    "engine_memory_snapshot",
    "ensure_metrics_server",
    "export_request_traces",
    "fragmentation_map",
    "get_compile_tracker",
    "get_event_buffer",
    "get_flight_recorder",
    "get_logger",
    "get_numerics_census",
    "get_registry",
    "nudge_ulps",
    "ledger_vs_measured",
    "measure_program_memory",
    "merge_chrome_traces",
    "key_id",
    "merge_snapshots",
    "parse_prometheus_text",
    "plan_memory_ledger",
    "post_boot_spans",
    "prefill_program_label",
    "program",
    "record_admission",
    "record_admission_watermark",
    "record_autotune_cache",
    "record_autotune_decision",
    "record_autotune_measure_failure",
    "record_autotune_measurement",
    "record_cache_access",
    "record_comm_op",
    "record_compile",
    "record_compile_cache",
    "record_decode_step",
    "record_degraded_path",
    "record_dispatch_meta",
    "record_flex_bwd_form",
    "record_flex_forward_kept",
    "record_flex_dead_step_share",
    "record_flex_stepped_tile_share",
    "record_mask_step",
    "record_flex_kernel_build",
    "record_model_attn_plan",
    "record_moe_rows_permuted",
    "record_moe_route_ahead",
    "record_handed_on",
    "record_mhc",
    "record_mhc_coef",
    "record_mla_kv_cast_width",
    "record_ssd_model",
    "record_ssd_scan",
    "record_ssm_scan",
    "record_model_loop",
    "record_shift",
    "record_moe_load",
    "record_dispatch_solution",
    "record_dynamic_solution",
    "record_event",
    "record_group_collective_build",
    "record_guard_check",
    "record_guard_repair",
    "record_guard_violation",
    "record_hbm_sample",
    "record_memory_comparison",
    "record_memory_ledger",
    "record_memory_measurement",
    "record_memory_pool",
    "record_numerics_census",
    "record_overlap_choice",
    "record_kvcache_state",
    "record_plan",
    "record_plan_bucket",
    "record_plan_cache_eviction",
    "record_plan_incremental",
    "record_plan_solver",
    "record_prefill",
    "record_request_span",
    "record_runtime_costs",
    "record_tick_programs",
    "render_prometheus",
    "request_context",
    "request_traces_to_chrome",
    "record_shadow_check",
    "reset_compile_tracker",
    "reset_flight_recorder",
    "reset_numerics_census",
    "reset_request_traces",
    "record_tuning_cache_io_error",
    "ulp_distance",
    "record_validate",
    "reset",
    "sample_memory_stats",
    "series_key",
    "serving_memory_ledger",
    "set_enabled",
    "snapshot",
    "snapshot_delta",
    "span",
    "span_self_seconds",
    "tiered_memory_ledger",
    "start_metrics_server",
    "stop_metrics_server",
    "telemetry_summary",
    "tick_program_label",
    "trace_metadata_events",
]
