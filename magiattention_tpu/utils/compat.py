"""The one home for jax API spellings that have moved between versions.

The package targets the installed jax (``pyproject.toml`` pins the
floor). EVERY module in this package — production runtime, profiler,
tests — goes through this shim: ``jax.shard_map`` /
``pltpu.CompilerParams`` must not be spelled anywhere else in the tree
(enforced by rule MAGI001 of ``magiattention_tpu/analysis/lint.py``),
so the next rename is a one-file change.
"""

from __future__ import annotations


def shard_map(
    f,
    *,
    mesh,
    in_specs,
    out_specs,
    check_vma: bool = True,
    axis_names=None,
):
    """``jax.shard_map``. ``axis_names`` selects partial-manual mode:
    only the named mesh axes become manual; the rest stay under GSPMD."""
    import jax

    kwargs = {}
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(
        f,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=check_vma,
        **kwargs,
    )


def backends_are_initialized() -> bool:
    """Has this process brought a jax backend up yet (on a TPU host:
    has it started the TPU runtime)? ``jax._src.xla_bridge`` holds the
    only answer; False where this jax has none."""
    try:
        from jax._src import xla_bridge

        return bool(xla_bridge.backends_are_initialized())
    except (ImportError, AttributeError):
        return False


def tpu_compiler_params(**kwargs):
    """``pltpu.CompilerParams`` — the flex-attention kernels and the
    serving decode kernel both launch through this."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(**kwargs)


def jaxpr_types() -> tuple[type, type]:
    """``(Jaxpr, ClosedJaxpr)`` from ``jax.extend.core`` (they left
    ``jax.core``) — what the trace auditors walk."""
    from jax.extend import core

    return core.Jaxpr, core.ClosedJaxpr


def register_compile_listeners(
    on_event, on_duration, on_phase_start=None, on_phase_end=None
) -> str:
    """Feed jax's own account of its compile pipeline to the compile
    tracker (``telemetry/compile.py``) through ``jax.monitoring``:
    ``on_event(name)`` per event (the persistent cache's hits and
    misses), ``on_duration(name, seconds)`` per duration event (backend
    compiles arrive as ``.../backend_compile_duration``, cache
    retrievals as ``.../cache_retrieval_time_sec``), and — where this
    jax has them — ``on_phase_start(name, start, fun_name=)`` as a
    trace, a lowering or a backend compile begins (jax records its start
    as a scalar) and ``on_phase_end(name, start, end, fun_name=)`` as it
    ends (a time span; both on ``time.time()``). Never a hard dependency
    and never raises. Returns the ingestion mode actually wired:
    ``"monitoring"``, or ``"none"`` when the hook is missing — the
    tracker still accepts directly-planted events (tests, manual
    instrumentation).
    """
    try:
        from jax import monitoring

        if on_event is not None:
            monitoring.register_event_listener(on_event)
        monitoring.register_event_duration_secs_listener(on_duration)
    except (ImportError, AttributeError):
        return "none"
    for hook, callback in (
        ("register_scalar_listener", on_phase_start),
        ("register_event_time_span_listener", on_phase_end),
    ):
        register = getattr(monitoring, hook, None)
        if callback is not None and register is not None:
            register(callback)
    return "monitoring"
