"""do_bench: timing harness for TPU.

Role of reference ``benchmarking/bench.py`` (CUDA-event do_bench + NVML
memory recorder): wall-clock timing in which every
measured region ends in ``jax.block_until_ready`` on the whole result
(jax returns before the device finishes, so a timing without it measures
the enqueue), plus jax device memory stats.
"""

from __future__ import annotations

import dataclasses
import statistics
import time
from typing import Any, Callable

import jax


class MemoryRecorder:
    """Sample device memory while a region runs (role of the reference's
    NVML ``MemRecorder``, bench.py:45-77). A background thread polls
    ``memory_stats()`` of the given devices at ``interval_s``; on exit
    ``peak_bytes`` holds the max bytes_in_use seen per device within the
    region (polled — see the note in ``__exit__``).

    Backends without memory_stats (CPU) record nothing and stay usable —
    ``peak_bytes`` is then an empty dict.

    The actual sampling is ONE implementation shared with the memory
    observability layer (ISSUE 14):
    :func:`~..telemetry.memory.sample_memory_stats` — this class only
    adds the polling thread + peak folding.

    Usage::

        with MemoryRecorder() as rec:
            run_step()
        print(rec.peak_bytes)     # {device: bytes}
    """

    def __init__(self, devices=None, interval_s: float = 0.01):
        self.devices = list(devices) if devices else jax.local_devices()
        self.interval_s = interval_s
        self.peak_bytes: dict[Any, int] = {}
        self.samples: list[dict[Any, int]] = []
        self._stop = None
        self._thread = None

    def _poll_once(self) -> dict[Any, int]:
        from ..telemetry.memory import sample_memory_stats

        return sample_memory_stats(self.devices)

    def __enter__(self):
        import threading

        self._stop = threading.Event()

        def loop():
            while not self._stop.is_set():
                self.record()  # one fold implementation (gauges incl.)
                self._stop.wait(self.interval_s)

        self._thread = threading.Thread(target=loop, daemon=True)
        self._thread.start()
        return self

    def record(self) -> None:
        """Take one sample now (for callers that poll at known-quiet
        points instead of running the background thread). With
        telemetry on, the sample also lands on the
        ``magi_mem_hbm_bytes_in_use``/``_peak`` gauges (ISSUE 14)."""
        sample = self._poll_once()
        if sample:
            self.samples.append(sample)
            for d, b in sample.items():
                if b > self.peak_bytes.get(d, 0):
                    self.peak_bytes[d] = b
            from ..telemetry import record_hbm_sample

            record_hbm_sample(sample)

    def __exit__(self, exc_type, exc_val, exc_tb):
        self._stop.set()
        self._thread.join(timeout=5.0)
        self.record()  # one final sample at region end
        # NOTE: peaks are POLLED values; an allocation spike shorter than
        # interval_s between two ticks can be missed. The allocator's own
        # peak_bytes_in_use is deliberately NOT folded in — it is a
        # process-lifetime high-water mark that would contaminate this
        # region with earlier history.
        return False


@dataclasses.dataclass(frozen=True)
class BenchResult:
    mean_ms: float
    median_ms: float
    min_ms: float
    max_ms: float
    reps: int
    peak_bytes: int | None = None  # max over devices
    peak_bytes_per_device: tuple[int, ...] = ()

    def tflops(self, flops: float) -> float:
        return flops / (self.median_ms * 1e-3) / 1e12


def chained_ms(step, carry, iters: int = 8, batches: int = 3) -> float:
    """Median per-application wall-clock ms of ``step`` chained ``iters``
    times inside ONE jitted ``lax.fori_loop`` dispatch.

    ``step`` maps a pytree carry to a same-structure, same-dtype carry
    (e.g. ``(q, k, v) -> (out, k, v)`` for a forward,
    ``(q, k, v) -> (dq, dk, dv)`` for a gradient — returning EVERY grad
    through the carry keeps every backward kernel live against DCE).
    Serial data dependence through the carry defeats CSE, and the single
    dispatch amortizes the fixed host cost of a dispatch down to
    ~cost/iters per application. Keep loop-invariant operands (k/v)
    inside the carry rather than closed over: closure constants embed in
    the HLO as literals.
    """
    import jax

    f = jax.jit(
        lambda c: jax.lax.fori_loop(0, iters, lambda i, cc: step(cc), c)
    )
    jax.block_until_ready(f(carry))  # compile + settle
    times = []
    for _ in range(batches):
        t0 = time.perf_counter()
        jax.block_until_ready(f(carry))
        times.append((time.perf_counter() - t0) / iters * 1e3)
    times.sort()
    return times[len(times) // 2]


def do_bench(
    fn: Callable,
    *args,
    warmup: int = 3,
    rep: int = 10,
    inner: int = 5,
    record_memory: bool = False,
    **kwargs,
) -> BenchResult:
    """Time fn(*args) with warmup; each rep runs ``inner`` calls between
    syncs so fixed sync latency amortizes (reference do_bench :79).

    ``record_memory``: samples memory BETWEEN reps (after each sync, via
    :class:`MemoryRecorder.record` — no concurrent polling thread, so the
    memory_stats RPCs never perturb the timed regions; use a standalone
    MemoryRecorder context for continuous in-flight sampling)."""
    r = fn(*args, **kwargs)  # at least one call before timing (compile)
    for _ in range(max(warmup - 1, 0)):
        r = fn(*args, **kwargs)
    jax.block_until_ready(r)
    rec = MemoryRecorder() if record_memory else None
    times = []
    for _ in range(rep):
        t0 = time.perf_counter()
        for _ in range(inner):
            r = fn(*args, **kwargs)
        jax.block_until_ready(r)
        times.append((time.perf_counter() - t0) / inner * 1e3)
        if rec is not None:
            rec.record()  # outside the timed window
    peaks = tuple(sorted(rec.peak_bytes.values())) if rec else ()
    return BenchResult(
        mean_ms=statistics.fmean(times),
        median_ms=statistics.median(times),
        min_ms=min(times),
        max_ms=max(times),
        reps=rep,
        peak_bytes=max(peaks) if peaks else None,
        peak_bytes_per_device=peaks,
    )


def enable_compile_cache() -> str:
    """Turn on the persistent XLA compilation cache and return its
    directory. The cache is placed from outside: where
    ``JAX_COMPILATION_CACHE_DIR`` is set jax already holds that
    directory and none is set here; otherwise the cache lives at
    ``<checkout>/.jax_cache``, derived from this package's location — a
    path that never moves with the caller's working directory, because a
    directory that moves never hits. Call before the first jit."""
    import os

    import jax

    cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir:
        checkout = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        cache_dir = os.path.join(checkout, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
