"""Seconds of set-up that lie under no span of the program's: ``setup_s``
minus the union of every span in the program's ring
(``telemetry.get_event_buffer()``), of any name, clipped to the set-up:
from the harness's start (``harness._T0``) to ``setup_s`` later, where
the window opened.

``{"kind": "program_span_uncovered"}``

Nested and overlapping spans count once. What the remainder holds is
what the program cannot see from inside: the harness's own calls of
compiled programs before the window (weights, the first step, settling,
the batches), and jax's import and the device claim where they fall after
``_T0`` and before the package's first statement (the ``process_boot``
span covers them once the program posts it). Both clocks are
``time.perf_counter``, as in ``program_span``.
"""

from .. import harness


def cover(events, began_us: float, opened_us: float):
    """The stretches of ``[began_us, opened_us]`` that some span covers,
    in order, as ``(start_us, end_us, span)``: each stretch goes to the
    span that reached it first, so nested and overlapping spans count
    once. ``exps/setup_waterfall.py`` lays its rows out from the same
    stretches, so its remainder is this metric's."""
    edge = began_us
    for ev in sorted(events, key=lambda ev: ev["ts"]):
        a, b = max(ev["ts"], edge), min(ev["ts"] + ev["dur"], opened_us)
        if b > a:
            yield a, b, ev
            edge = b


def read(spec: dict, obs):
    from magiattention_tpu import telemetry

    buffer = telemetry.get_event_buffer()
    if buffer.dropped:
        harness.log(
            f"WARNING: the span ring dropped {buffer.dropped} spans; "
            "the uncovered part of set-up may read high"
        )
    setup_s = obs.end_to_end["setup_s"]
    began_us = 1e6 * harness._T0
    covered = sum(
        b - a
        for a, b, _ev in cover(
            buffer.events(), began_us, began_us + 1e6 * setup_s
        )
    )
    return setup_s - covered / 1e6
