"""do_bench / perf_report: timing harness for TPU.

Role of reference ``benchmarking/bench.py`` (CUDA-event do_bench + NVML
memory recorder + Mark/perf_report): wall-clock timing in which every
measured region ends in ``jax.block_until_ready`` on the whole result
(jax returns before the device finishes, so a timing without it measures
the enqueue), plus jax device memory stats.
"""

from __future__ import annotations

import dataclasses
import functools
import statistics
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp


def mesh_barrier(mesh) -> None:
    """Rendezvous every device of a mesh and block the host on the result
    (role of the reference's ``maybe_dist_sync``: cuda.synchronize +
    dist.barrier before each sweep, bench.py:328). One psum over all mesh
    axes forces every device to reach this point."""
    fn, zero = _barrier_cache(mesh)
    jax.block_until_ready(fn(zero))


@functools.lru_cache(maxsize=8)
def _barrier_cache(mesh):
    """Jitted barrier + placed scalar per mesh — a fresh closure each call
    would retrace/compile every rep."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..utils.compat import shard_map
    from ..utils.instrument import named_scope

    names = tuple(mesh.axis_names)

    def _psum_all(v):
        with named_scope("magi_bench_barrier"):
            return jax.lax.psum(v, names)

    def _b(x):
        return shard_map(
            _psum_all,
            mesh=mesh,
            in_specs=P(),
            out_specs=P(),
            check_vma=False,
        )(x)

    zero = jax.device_put(jnp.zeros(()), NamedSharding(mesh, P()))
    return jax.jit(_b), zero


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
    mesh=None,
    **kwargs,
) -> BenchResult:
    """Time fn(*args) with warmup; each rep runs ``inner`` calls between
    syncs so fixed sync latency amortizes (reference do_bench :79).

    ``mesh``: rendezvous every device of the mesh before each timed rep
    (:func:`mesh_barrier` — the reference's maybe_dist_sync role), so
    multi-device sweeps never time one device's leftover queue.
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
        if mesh is not None:
            mesh_barrier(mesh)
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


@dataclasses.dataclass(frozen=True)
class Benchmark:
    """Declarative benchmark grid (reference ``Benchmark``/``Mark``,
    benchmarking/bench.py:232-767): sweep ``x_vals`` along ``x_name``, one
    measured line per value of ``line_arg`` in ``line_vals``; the decorated
    function receives (x_name=..., line_arg=..., **args) per cell and
    returns a float (ms) or a dict of extra columns."""

    x_name: str
    x_vals: Sequence[Any]
    line_arg: str
    line_vals: Sequence[Any]
    line_names: Sequence[str] | None = None
    plot_name: str = "benchmark"
    args: dict[str, Any] = dataclasses.field(default_factory=dict)
    ylabel: str = "ms"


class Mark:
    """Runner bound to one Benchmark grid; produced by :func:`perf_grid`."""

    def __init__(self, fn: Callable, bench: Benchmark):
        self._fn = fn
        self.bench = bench

    def run(
        self,
        *,
        print_data: bool = True,
        save_path: str | None = None,
        show_plots: bool = False,
    ) -> list[dict[str, Any]]:
        b = self.bench
        names = list(b.line_names or [str(v) for v in b.line_vals])
        rows: list[dict[str, Any]] = []
        for x in b.x_vals:
            row: dict[str, Any] = {b.x_name: x}
            for lv, nm in zip(b.line_vals, names):
                res = self._fn(**{b.x_name: x, b.line_arg: lv}, **b.args)
                if isinstance(res, dict):
                    for key, val in res.items():
                        row[f"{nm}_{key}"] = val
                else:
                    row[nm] = res
            rows.append(row)
        if print_data:
            print(perf_report(rows))
        if save_path and rows:
            import csv
            import os

            os.makedirs(save_path, exist_ok=True)
            csv_path = os.path.join(save_path, f"{b.plot_name}.csv")
            fields: list[str] = []  # union across rows (cells may differ)
            for r in rows:
                fields.extend(k for k in r if k not in fields)
            with open(csv_path, "w", newline="") as f:
                w = csv.DictWriter(f, fieldnames=fields, restval="")
                w.writeheader()
                w.writerows(rows)
            self._plot(rows, names, save_path, show_plots)
        return rows

    def _plot(self, rows, names, save_path, show):
        try:
            import os

            import matplotlib

            matplotlib.use("Agg")
            import matplotlib.pyplot as plt
        except Exception:  # matplotlib optional
            return
        b = self.bench
        xs = [r[b.x_name] for r in rows]
        fig, ax = plt.subplots(figsize=(7, 4.5))
        for nm in names:
            if nm in rows[0]:
                ax.plot(xs, [r[nm] for r in rows], marker="o", label=nm)
        ax.set_xlabel(b.x_name)
        ax.set_ylabel(b.ylabel)
        ax.set_title(b.plot_name)
        ax.legend()
        ax.grid(True, alpha=0.3)
        fig.tight_layout()
        fig.savefig(os.path.join(save_path, f"{b.plot_name}.png"), dpi=120)
        if show:  # pragma: no cover
            plt.show()
        plt.close(fig)


def perf_grid(bench: Benchmark):
    """Decorator: ``@perf_grid(Benchmark(...))`` -> a :class:`Mark` whose
    ``.run(save_path=...)`` sweeps the grid, prints the table, and writes
    CSV + PNG (reference perf_report decorator)."""

    def wrap(fn: Callable) -> Mark:
        return Mark(fn, bench)

    return wrap


def perf_report(
    rows: Sequence[dict[str, Any]],
    *,
    sort_key: str | None = None,
) -> str:
    """Plain-text table of benchmark rows (reference Mark/perf_report)."""
    if not rows:
        return "(no results)"
    cols = list(rows[0].keys())
    if sort_key:
        rows = sorted(rows, key=lambda r: r[sort_key])
    widths = {
        c: max(len(str(c)), *(len(f"{r.get(c, '')}") for r in rows))
        for c in cols
    }
    lines = [
        "  ".join(str(c).ljust(widths[c]) for c in cols),
        "  ".join("-" * widths[c] for c in cols),
    ]
    for r in rows:
        lines.append(
            "  ".join(f"{r.get(c, '')}".ljust(widths[c]) for c in cols)
        )
    return "\n".join(lines)


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


def image_grid(
    paths: Sequence[str],
    out_path: str,
    cols: int | None = None,
) -> str | None:
    """Tile saved benchmark plot PNGs into one grid image (role of
    reference ``benchmarking/image_grid.py``: its make_grid collage of
    sweep plots). ``cols=None`` picks the near-square factorization.
    Returns ``out_path``, or None when matplotlib/PIL are unavailable or
    no inputs exist (report tooling must never take a bench run down)."""
    import math
    import os

    paths = [p for p in paths if os.path.exists(p)]
    if not paths:
        return None
    try:
        import matplotlib

        matplotlib.use("Agg")
        import matplotlib.image as mpimg
        import matplotlib.pyplot as plt
    except Exception:
        return None
    n = len(paths)
    if cols is None:
        cols = max(1, int(math.ceil(math.sqrt(n))))
    nrows = -(-n // cols)
    try:
        fig, axes = plt.subplots(
            nrows, cols, figsize=(5.5 * cols, 4.0 * nrows), squeeze=False
        )
        for i, ax in enumerate(axes.flat):
            ax.axis("off")
            if i < n:
                ax.imshow(mpimg.imread(paths[i]))
                ax.set_title(os.path.basename(paths[i]), fontsize=8)
        fig.tight_layout()
        fig.savefig(out_path, dpi=120)
        plt.close(fig)
    except Exception:
        # truncated PNG, unwritable out_path, ... — report tooling must
        # never take a bench run down
        try:
            plt.close("all")
        except Exception:
            pass
        return None
    return out_path
