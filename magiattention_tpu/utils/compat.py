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


def register_compile_listeners(on_event, on_duration) -> str:
    """Feed XLA-compile observations to the compile tracker
    (``telemetry/compile.py``) through ``jax.monitoring``
    (``on_event(name)`` per event, ``on_duration(name, seconds)`` per
    duration event; backend compiles arrive as
    ``.../backend_compile_duration``). Never a hard dependency and never
    raises. Returns the ingestion mode actually wired: ``"monitoring"``,
    or ``"none"`` when the hook is missing — the tracker still accepts
    directly-planted events (tests, manual instrumentation).
    """
    try:
        from jax import monitoring

        if on_event is not None:
            monitoring.register_event_listener(on_event)
        monitoring.register_event_duration_secs_listener(on_duration)
        return "monitoring"
    except (ImportError, AttributeError):
        return "none"
