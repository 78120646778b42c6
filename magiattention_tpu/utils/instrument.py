"""Tracing / profiling instrumentation.

Role of reference ``utils/nvtx.py`` (add_nvtx_event, switch_profile): on
TPU the equivalents are ``jax.named_scope`` (annotates traced
computations so they show up in the XLA profiler timeline) plus
``jax.profiler`` trace sessions. Host-side spans are ``telemetry.span``
(``telemetry/events.py``), the package's one span primitive: while a
session of :func:`switch_profile` records, each is also written into the
profiler's trace as ``magi:<name>``, beside the device rows.
"""

from __future__ import annotations

import contextlib
import logging
import threading

logger = logging.getLogger("magiattention_tpu.utils.instrument")


def named_scope(name: str):
    """``jax.named_scope`` context for traced regions (overlap-stage
    kernels, group casts/reduces, a model's parts): the scope name
    survives into XLA metadata, so ``jax.profiler`` / Perfetto device
    traces show ``magi_stage0_cast``-style labels instead of anonymous
    fusions, and a metric file reads a part's share of the device's time.

    The package's one door to a device scope. Nothing at run time. At
    trace time, with telemetry off, one predicate; with telemetry on and
    a jax phase span live (jax is tracing the caller, or lowering a
    kernel whose body Mosaic traces: ``telemetry/compile._live_phase``),
    the region is also a host span ``trace_part`` with ``scope=<name>``,
    child of the innermost live span: the Python time a trace spends in
    each part (``docs/observability.md``, "Spans"). The ``jax.named_scope``
    emitted is the same either way, and an eager call records nothing."""
    import jax

    scope = jax.named_scope(name)
    from ..telemetry import enabled

    if enabled():
        from ..telemetry.compile import _live_phase

        if _live_phase() is not None:
            return _trace_part(scope, name)
    return scope


@contextlib.contextmanager
def _trace_part(scope, name: str):
    from ..telemetry.events import begin_span, end_span

    live = begin_span("trace_part", {"scope": name})
    try:
        with scope:
            yield
    finally:
        end_span(live)


# jax.profiler supports one trace session per process; this guard makes our
# wrapper re-entrant (nested/overlapping sessions degrade to a warning
# no-op instead of raising out of jax.profiler) and exception-safe (the
# session always stops exactly once, even when the body raises).
_trace_session_lock = threading.Lock()
_trace_session_dir: str | None = None


def trace_session_active() -> bool:
    """Is a :func:`switch_profile` session currently recording?"""
    return _trace_session_dir is not None


@contextlib.contextmanager
def switch_profile(trace_dir: str | None = None):
    """Profiler session (reference switch_profile / cudaProfilerStart-Stop):
    writes an XLA trace viewable in TensorBoard / xprof.

    ``trace_dir=None`` honors ``MAGI_ATTENTION_PROFILE_MODE`` as a
    default-on switch: profile mode on -> trace into ``env.trace_dir()``
    (``MAGI_ATTENTION_TRACE_DIR``); off -> no-op, as before.

    Re-entrant and exception-safe: a ``switch_profile`` inside an active
    session (ours, or one started directly via ``jax.profiler``) warns and
    no-ops instead of letting ``start_trace`` raise; the outer session
    keeps recording and is stopped exactly once. A body exception
    propagates unchanged — the trace is still stopped, and a failing
    ``stop_trace`` never masks it.
    """
    global _trace_session_dir
    from .. import env

    if trace_dir is None and env.is_profile_mode():
        trace_dir = env.trace_dir()
    if trace_dir is None:
        yield
        return
    import jax

    started = False
    with _trace_session_lock:
        if _trace_session_dir is not None:
            logger.warning(
                "switch_profile(%r): a trace session into %r is already "
                "active; jax.profiler supports one session per process — "
                "this nested session is a no-op (the outer one keeps "
                "recording)",
                trace_dir,
                _trace_session_dir,
            )
        else:
            try:
                jax.profiler.start_trace(trace_dir)
                started = True
                _trace_session_dir = trace_dir
            except Exception as e:
                # e.g. a session started directly via jax.profiler that
                # this module cannot see — surface it, keep running
                logger.warning(
                    "switch_profile(%r): jax.profiler.start_trace failed "
                    "(%r); continuing without a trace session",
                    trace_dir,
                    e,
                )
    try:
        yield
    finally:
        if started:
            with _trace_session_lock:
                _trace_session_dir = None
                try:
                    jax.profiler.stop_trace()
                except Exception as e:
                    # never mask the body's exception with a stop failure
                    logger.warning(
                        "switch_profile(%r): jax.profiler.stop_trace "
                        "failed (%r); trace output may be incomplete",
                        trace_dir,
                        e,
                    )
