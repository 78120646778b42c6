"""Timing: a rate is all the work of a phase over all of its time.

Copied in spirit from ``magiattention_tpu/benchmarking/bench.py``
``do_bench`` (regions end in ``jax.block_until_ready`` on the whole
result, checked on the chip in PR 21). A phase is a run of timed units
(a few back-to-back calls each, see ``timed_units``). An end-to-end
rate is the work of every completed unit over the time from the phase's
start to the end of its last unit (``Phase.rate``): a stall or a compile
anywhere inside moves it. The median unit is kept beside it as a
per-layer value, where a steadier statistic belongs.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import statistics
import time
from typing import Callable


def settle(fn: Callable[[], object], *, rel: float = 0.05,
           max_iters: int = 8) -> list[float]:
    """Run whole iterations until two successive ones agree to ``rel``
    (the first call after a compile or a cache load runs long: 2.18 s
    against 0.31 s steady in PR 21). Returns the times seen."""
    import jax

    times: list[float] = []
    for _ in range(max_iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
        if len(times) >= 2 and abs(times[-1] - times[-2]) <= rel * times[-2]:
            break
    return times


@dataclasses.dataclass
class Phase:
    """A run of timed units: each unit's time per call, the calls of a
    unit, and the seconds from the phase's start to its last unit's end
    (the gaps between units are inside)."""

    per_call_s: list[float]
    calls_per_unit: int
    elapsed_s: float

    @property
    def calls(self) -> int:
        return len(self.per_call_s) * self.calls_per_unit

    def rate(self, work_per_call: float) -> float:
        """All the work done over all the time it took."""
        return self.calls * work_per_call / self.elapsed_s


def timed_units(fn: Callable[[], object], seconds: float, *,
                inner: int = 1, min_units: int | None = None,
                span=contextlib.nullcontext) -> Phase:
    """Run ``fn`` in timed units for ``seconds`` seconds.

    A timed unit is ``inner`` calls enqueued back to back, ending in
    ``block_until_ready`` on the last call's whole result (the device
    runs them in order, so every earlier one is complete too). A unit
    starts only while the phase's clock has not run out (and at least
    ``min_units`` run). ``span`` wraps each unit in a host span.

    Why units and not single calls: between two synchronous calls the
    device waits for the host (wake-up, dispatch). On the v5e that gap
    was 1.3 ms in one process and 4 ms in the next of the same code,
    with the device's busy time equal to 0.01% (PR 23) — 1.8% of a
    145 ms forward call. A model enqueues its layers back to back and
    never pays it, so a unit does the same and spans seconds, which
    makes the one gap left at its end small change.
    """
    import jax

    min_units = MIN_UNITS if min_units is None else min_units
    times: list[float] = []
    start = time.perf_counter()
    end = start + seconds
    while len(times) < min_units or time.perf_counter() < end:
        with span():
            t0 = time.perf_counter()
            result = None
            for _ in range(inner):
                result = fn()  # the previous result is dropped here
            jax.block_until_ready(result)
            last = time.perf_counter()
            times.append((last - t0) / inner)
    return Phase(times, inner, last - start)


# A timed unit: the contract wants a host-clock time to span 250 ms or
# more; 3 s makes the one host gap at a unit's end (1.3-4 ms, PR 23)
# 0.1% of it. A phase holds at least three units, so where it is shorter
# than 9 s (a rehearsal) a unit is a third of it.
TIMED_UNIT_S = 3.0
MIN_UNITS = 3


def calls_per_unit(call_s: float, phase_s: float) -> int:
    """Calls of ``call_s`` seconds that make one timed unit of a phase
    of ``phase_s`` seconds."""
    unit_s = min(TIMED_UNIT_S, phase_s / MIN_UNITS)
    return max(1, math.ceil(unit_s / call_s))


def summary(times: list[float]) -> dict:
    """Count, median and quartiles (seconds) of a list of times."""
    if len(times) >= 2:
        q1, _q2, q3 = statistics.quantiles(times, n=4)
    else:
        q1 = q3 = times[0]
    return {
        "n": len(times),
        "median_s": statistics.median(times),
        "q1_s": q1,
        "q3_s": q3,
        "min_s": min(times),
        "max_s": max(times),
    }


def completed_rate(step_ends: list[float], work_per_step: float,
                   window_start: float, window_end: float) -> tuple[float, int]:
    """(work per second, steps counted) of a closed loop: the work of
    the steps that *completed* inside the window over the time from the
    window's start to the end of the last completed step. A step cut by
    the window's edge costs nothing and adds nothing. No completed step
    gives (0.0, 0)."""
    done = [t for t in step_ends if t <= window_end]
    if not done:
        return 0.0, 0
    return len(done) * work_per_step / (max(done) - window_start), len(done)
