"""The general harness: find a cell's files by name, run its traffic
kind, reduce what was observed to the metrics ``BENCHMARK.json`` lists.

Driven by data. A cell is an entry of ``workloads``; its configuration
is the ``file`` of its ``configs`` entry; its traffic is
``<paths[0]>/traffic/<traffic>.json``, whose ``kind`` names a module of
``benchmarks/kinds``; a per-layer metric is
``<paths[0]>/metrics/<name>.json``, whose ``source.kind`` names a module
of ``benchmarks/sources``. A later PR adds files and an entry, and edits
nothing.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import json
import os
import shutil
import sys
import time

CODE_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(CODE_DIR)


_T0 = time.perf_counter()


def log(msg: str) -> None:
    """An earlier line of the run (never the last: that is the result),
    stamped with the seconds since the harness was imported."""
    print(f"[{time.perf_counter() - _T0:7.2f}] {msg}", flush=True)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list[str]  # the metrics this cell reports, --trace 0
    per_layer: list[dict]  # the metric files of this cell, --trace 1
    units: dict[str, str]


@dataclasses.dataclass
class Observations:
    """What a traffic kind hands back: end-to-end values it computed,
    named values it measured, and what the sources read from."""

    end_to_end: dict[str, float]
    attempted: int
    failed: int
    correct: bool
    values: dict[str, float] = dataclasses.field(default_factory=dict)
    flops: dict[str, float] = dataclasses.field(default_factory=dict)
    iters: dict[str, int] = dataclasses.field(default_factory=dict)
    hlo_scopes: dict[str, str] = dataclasses.field(default_factory=dict)
    # filled by the harness
    registry: dict = dataclasses.field(default_factory=dict)
    trace: object = None
    peaks: dict | None = None
    chips: int = 1


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _in_cell(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(root: str, workload: str) -> Cell:
    """Everything ``BENCHMARK.json`` under ``root`` says of one cell."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    try:
        entry = next(w for w in bench["workloads"] if w["name"] == workload)
    except StopIteration:
        names = [w["name"] for w in bench["workloads"]]
        raise SystemExit(f"no workload {workload!r}; known: {names}") from None
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    home = os.path.join(root, bench["paths"][0])
    per_layer = []
    for m in bench["per_layer"]:
        if _in_cell(m, workload):
            spec = _load_json(os.path.join(home, "metrics", m["name"] + ".json"))
            for key in ("unit", "layer", "moves"):
                if spec[key] != m[key]:
                    raise SystemExit(
                        f"metric {m['name']}: {key} {spec[key]!r} in its "
                        f"file, {m[key]!r} in BENCHMARK.json"
                    )
            per_layer.append({**spec, "name": m["name"]})
    return Cell(
        name=workload,
        chips=int(entry["chips"]),
        config_name=entry["config"],
        config=_load_json(os.path.join(root, cfg_entry["file"])),
        traffic_name=entry["traffic"],
        traffic=_load_json(
            os.path.join(home, "traffic", entry["traffic"] + ".json")
        ),
        end_to_end=[
            m["name"] for m in bench["end_to_end"] if _in_cell(m, workload)
        ],
        per_layer=per_layer,
        units={
            m["name"]: m["unit"]
            for m in bench["end_to_end"] + bench["per_layer"]
        },
    )


# ---------------------------------------------------------------------------
# the device, the compile cache, the trace
# ---------------------------------------------------------------------------


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a path that never moves
    (the path is part of the cache's key): where
    ``JAX_COMPILATION_CACHE_DIR`` is set, jax already holds it and none
    is set here — the rule of the program's own
    ``benchmarking.enable_compile_cache``, which then agrees; otherwise
    ``<checkout>/.jax_cache``. Every program is kept, however fast it
    compiled, so that a later run's set-up compiles nothing."""
    import jax

    cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir:
        cache_dir = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def set_compile_cache(on: bool) -> None:
    """Turn the persistent cache's use on or off from here on (jax reads
    the switch once and remembers: ``reset_cache`` makes it look again)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", on)
    compilation_cache.reset_cache()


def claim_devices(chips: int, allow_cpu: bool):
    """The first ``chips`` devices; fails where jax came up on anything
    but a TPU, or on fewer chips than the cell asks for."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not (allow_cpu and platform == "cpu"):
        raise SystemExit(
            f"this benchmark measures a TPU; jax came up on {platform!r}"
        )
    if len(devices) < chips:
        raise SystemExit(
            f"the cell needs {chips} chips; jax offers {len(devices)}"
        )
    return devices[:chips]


def device_report(devices) -> dict:
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices
    ]
    seen = [p for p in peaks if p is not None]
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(seen) if seen else None,
    }


def key_from_seed(seed: int):
    """A jax PRNG key from any whole number (``--seed`` may pass 2**31)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


class Tracer:
    """The profiler around a window, and the host spans inside it. With
    tracing off, a span does nothing."""

    def __init__(self, on: bool, out_dir: str):
        self.on = on
        self.dir = out_dir

    def start(self) -> None:
        if not self.on:
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # no per-call python events
        options.host_tracer_level = 1  # the bench: annotations
        jax.profiler.start_trace(self.dir, profiler_options=options)

    def stop(self) -> None:
        if self.on:
            import jax

            jax.profiler.stop_trace()

    def span(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("bench:" + name)

    def phase(self, name: str):
        return self.span("phase:" + name)

    def load(self, scopes: dict[str, str]):
        from . import trace_reduce

        path = trace_reduce.find_xplane(self.dir)
        if path is None:
            raise SystemExit(f"the profiler wrote no trace under {self.dir}")
        return trace_reduce.load_xplane(path, scopes)


@dataclasses.dataclass
class Context:
    """What the harness gives a traffic kind."""

    seed: int
    seconds: float
    trace: bool
    devices: list
    tracer: Tracer
    t_start: float  # perf_counter at process start: set-up counts from here
    setup_s: float | None = None

    def window_opens(self) -> None:
        """The kind calls this as its set-up ends."""
        self.setup_s = time.perf_counter() - self.t_start
        log(f"set-up took {self.setup_s:.3f} s")


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def run_cell(
    root: str, workload: str, seed: int, seconds: float, trace: bool,
    *, t_start: float, allow_cpu: bool = False,
) -> dict:
    """Run one cell once and return the result line's object."""
    cell = load_cell(root, workload)
    import jax

    cache_dir = enable_compile_cache()
    devices = claim_devices(cell.chips, allow_cpu)
    log(
        f"cell {cell.name}: config {cell.config_name}, traffic "
        f"{cell.traffic_name} ({cell.traffic['kind']}), {cell.chips} x "
        f"{devices[0].device_kind} ({devices[0].platform}), jax "
        f"{jax.__version__}, compile cache {cache_dir}"
    )
    from magiattention_tpu import telemetry

    # the program's registry is plan-time host code; read only when traced
    telemetry.set_enabled(trace)
    out_dir = os.path.join(CHECKOUT, ".bench_out", cell.name, "trace")
    ctx = Context(
        seed=seed, seconds=float(seconds), trace=trace, devices=devices,
        tracer=Tracer(trace, out_dir), t_start=t_start,
    )
    kind = importlib.import_module(f"{__package__}.kinds.{cell.traffic['kind']}")
    obs: Observations = kind.run(cell, ctx)
    obs.chips = cell.chips
    if ctx.setup_s is None:
        raise SystemExit(f"kind {cell.traffic['kind']} never opened its window")
    obs.end_to_end["setup_s"] = ctx.setup_s

    result = {
        "correct": bool(obs.correct),
        "attempted": int(obs.attempted),
        "failed": int(obs.failed),
    }
    device = device_report(devices)
    if not trace:
        missing = [m for m in cell.end_to_end if m not in obs.end_to_end]
        if missing:
            raise SystemExit(f"the run gave no value for {missing}")
        names, values = cell.end_to_end, obs.end_to_end
    else:
        from . import flops, trace_reduce

        if devices[0].platform == "tpu":
            obs.peaks = flops.load_peaks(devices[0].device_kind)
        obs.registry = telemetry.snapshot()
        obs.trace = ctx.tracer.load(obs.hlo_scopes)
        log(f"trace lines: {json.dumps(obs.trace.lines_seen)}")
        with open(os.path.join(os.path.dirname(out_dir), "trace.json"), "w") as f:
            json.dump(obs.trace.to_json(), f)  # the plain form, to look at
        window = obs.trace.phase("window")
        if window is None:
            raise SystemExit("the trace holds no bench:phase:window span")
        device["window_s"] = (window[1] - window[0]) / 1e9
        device["busy_s"] = trace_reduce.busy_seconds(obs.trace, *window)
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(obs.trace, *window),
            "idle_gaps": trace_reduce.idle_gaps(obs.trace, *window),
        }
        values = {}
        for spec in cell.per_layer:
            source = importlib.import_module(
                f"{__package__}.sources.{spec['source']['kind']}"
            )
            value = source.read(spec["source"], obs)
            if value is not None:  # nothing to read: left out of the line
                values[spec["name"]] = value
        names = list(values)
    result["metrics"] = {
        n: {"value": float(values[n]), "unit": cell.units[n]} for n in names
    }
    result["device"] = device
    return result


def main(argv=None, *, allow_cpu: bool = False, t_start: float | None = None) -> int:
    import argparse

    t_start = _T0 if t_start is None else t_start
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument(
        "--root", default=CHECKOUT,
        help="the directory that holds BENCHMARK.json (tests point it at "
        "a toy benchmark made of new files only)",
    )
    args = p.parse_args(argv)
    if not os.path.isdir(os.path.join(CHECKOUT, "magiattention_tpu")):
        raise SystemExit(
            f"{CHECKOUT} holds the benchmark but not the program it measures"
        )
    result = run_cell(
        args.root, args.workload, args.seed, args.seconds, bool(args.trace),
        t_start=t_start, allow_cpu=allow_cpu,
    )
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0
