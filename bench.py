"""Round benchmark: flex-flash-attention on the TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}, and
exits non-zero when jax did not come up on a TPU, when the on-chip
parity check fails, or when any measurement of the headline fails:
there is no cached value to fall back to.

Metric: forward TFLOPs/s of the Pallas flex-flash-attention kernel on
long-context dense causal (64k tokens — the top of the reference's kernel
sweep, cp_benchmark.md:78-86 — head_dim 128, bf16, 8:8 heads).
vs_baseline: ratio against jax's own official TPU flash-attention kernel
(jax.experimental.pallas.ops.tpu.flash_attention) on the SAME chip and
shape — the TPU analogue of the reference's "FFA is comparable to FA3"
headline.

One process: the measurement and the host-side telemetry block run here,
one after the other (``--telemetry`` runs the block alone, on any
backend).
"""

import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_TELEMETRY_OUT = os.path.join(_HERE, "BENCH_TELEMETRY.json")
_HISTORY = os.path.join(_HERE, "BENCH_HISTORY.jsonl")
_KEYS = ("metric", "value", "unit", "vs_baseline")
# the headline workload spec: 64k dense causal, 8:8 heads, head_dim 128,
# bf16. ONE definition shared by the measurement (_measure) and the
# autotune-rung history record (_bench_autotune_rung) so the recorded
# rung can never diverge from the shape the kernel actually ran.
_HEADLINE_T, _HEADLINE_HQ, _HEADLINE_HK, _HEADLINE_D = 65536, 8, 8, 128
_HEADLINE_DTYPE = "bfloat16"
# the heterogeneous-mask headline (BASELINE config 2's kernel half): ONE
# spec shared by the extras measurement, the mask-density context, and
# the roofline probe, so the recorded density/efficiency can never
# describe a different workload than the metric they annotate
_VARLEN_T = 16384
_VARLEN_METRIC = "flex_attn_fwd_tflops_16k_varlen_block_causal_bf16"


def _varlen_slices():
    """(q_ranges, k_ranges, attn_type_map) of the 16k varlen headline."""
    from magiattention_tpu.testing.workloads import (
        ranges_of,
        varlen_block_causal,
    )

    return ranges_of(varlen_block_causal(_VARLEN_T))

sys.path.insert(0, _HERE)


def _timeit(fn, *args, n=20, batches=3):
    """Median of several timing batches."""
    import jax

    jax.block_until_ready(fn(*args))
    results = []
    for _b in range(batches):
        t0 = time.time()
        for _i in range(n):
            r = fn(*args)
        jax.block_until_ready(r)
        results.append((time.time() - t0) / n)
    results.sort()
    return results[len(results) // 2]


def _run_real() -> None:
    """Measure on the chip, append the run to the history, print.

    Refuses any backend but a TPU (the metric is an on-chip measurement)
    and a run whose on-chip parity check fails: both raise."""
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise RuntimeError(
            f"bench refuses the {device.platform} backend ({device}); the "
            "metric is an on-chip measurement"
        )
    from magiattention_tpu.benchmarking import enable_compile_cache

    enable_compile_cache()
    if not _parity_check():
        raise RuntimeError("on-chip parity check failed; nothing measured")
    payload, dt_fwd_64k = _measure()
    try:  # extras never take the headline down
        extras = _measure_extras(dt_fwd_64k)
    except Exception as e:
        print(f"extra metrics failed: {e!r}", file=sys.stderr)
        extras = {}
    meta = dict(payload)
    meta["recorded_unix"] = int(time.time())
    meta["device"] = str(device)
    # peak-HBM context (ISSUE 14): the device allocator's own
    # peak_bytes_in_use high-water mark (a TRUE peak covering the
    # measured kernels' transient scratch) where the runtime
    # exposes one, else an instantaneous post-run bytes_in_use sample
    try:
        from magiattention_tpu.telemetry.memory import sample_memory_stats

        hbm = sample_memory_stats(key="peak_bytes_in_use")
        if not hbm:
            hbm = sample_memory_stats()
        if hbm:
            meta["peak_hbm_bytes"] = max(hbm.values())
    except Exception as e:
        print(f"peak-HBM sample failed: {e!r}", file=sys.stderr)
    _append_history(meta, extras)
    print(json.dumps({k: payload[k] for k in _KEYS}))


def _bench_autotune_rung() -> "str | None":
    """The block-config rung the headline workload resolves to (host-side
    re-query of the deterministic tuner decision the measured kernel ran
    with): ``"BQxBKxHB"``. The perf gate flags rung changes between
    history entries — a TF/s delta with a rung change is a tuning story,
    without one a kernel/runtime story."""
    try:
        from magiattention_tpu.ops.flex_attn import auto_block_config

        t = _HEADLINE_T
        bq, bk, hb = auto_block_config(
            [(0, t)], [(0, t)], _HEADLINE_HQ, _HEADLINE_HK,
            attn_type_map=[1], head_dim=_HEADLINE_D,
            dtype=_HEADLINE_DTYPE,
        )
        return f"{bq}x{bk}x{hb}"
    except Exception as e:
        print(f"autotune rung query failed: {e!r}", file=sys.stderr)
        return None


def _bench_varlen_rung() -> "str | None":
    """The 16k-varlen headline's resolved rung INCLUDING the grid
    layout, ``"BQxBKxHB:grid"`` (ISSUE 15): the sparse-grid kernel is
    what the varlen TF/s extra measures now, and a silent fallback to
    the row-major grid (or a rung change) must be attributable when the
    number moves — same host-side re-query discipline as
    :func:`_bench_autotune_rung`."""
    try:
        from magiattention_tpu.ops.flex_attn import auto_kernel_config

        qr, kr, ts = _varlen_slices()
        bq, bk, hb, grid = auto_kernel_config(
            qr, kr, _HEADLINE_HQ, _HEADLINE_HK,
            attn_type_map=ts, head_dim=_HEADLINE_D,
            dtype=_HEADLINE_DTYPE,
        )
        return f"{bq}x{bk}x{hb}:{grid}"
    except Exception as e:
        print(f"varlen rung query failed: {e!r}", file=sys.stderr)
        return None


def _bench_mask_profile(metrics: dict) -> "tuple[dict, dict]":
    """Per-metric (mask_density, roofline_efficiency) context maps for
    the benched workloads (ISSUE 10): density = true entries / dense S²
    (exact host-side counting, ``tuning/cost_model.exact_mask_area``),
    efficiency = measured TF/s / the generation's peak. Recorded next to
    ``autotune_rung`` so the perf gate can attribute a TF/s delta to a
    rung vs a density (workload) change. Never fatal — empty maps on any
    error."""
    densities: dict = {}
    efficiencies: dict = {}
    try:
        from magiattention_tpu.telemetry.roofline import resolve_peak_tflops
        from magiattention_tpu.tuning.cost_model import exact_mask_area

        def causal_density(t):
            return (t + 1) / (2 * t)

        varlen_density = None
        for name, value in metrics.items():
            if not (
                name.startswith("flex_attn_")
                and "tflops" in name
                and isinstance(value, (int, float))
            ):
                continue
            if "64k_causal" in name:
                densities[name] = round(causal_density(65536), 6)
            elif "128k_causal" in name:
                densities[name] = round(causal_density(131072), 6)
            elif "16k_varlen_block_causal" in name:
                if varlen_density is None:
                    qr, kr, ts = _varlen_slices()
                    varlen_density = exact_mask_area(qr, kr, ts) / float(
                        _VARLEN_T * _VARLEN_T
                    )
                densities[name] = round(varlen_density, 6)
            else:
                continue
            efficiencies[name] = round(
                float(value) / resolve_peak_tflops(), 4
            )
    except Exception as e:
        print(f"mask-profile context failed: {e!r}", file=sys.stderr)
    return densities, efficiencies


def _append_history(meta: dict, extras: dict) -> None:
    """Append the run to BENCH_HISTORY.jsonl — the machine-readable
    perf trajectory exps/run_perf_gate.py gates on. Never fatal."""
    try:
        from magiattention_tpu.telemetry import baseline

        metrics = {meta["metric"]: meta["value"]}
        metrics.update(extras or {})
        densities, efficiencies = _bench_mask_profile(metrics)
        baseline.append_history(
            _HISTORY,
            baseline.make_history_entry(
                source="bench.py",
                metrics=metrics,
                recorded_unix=meta.get("recorded_unix"),
                device=meta.get("device"),
                vs_baseline=meta.get("vs_baseline"),
                autotune_rung=_bench_autotune_rung(),
                varlen_rung=_bench_varlen_rung(),
                mask_density=densities,
                roofline_efficiency=efficiencies,
                peak_hbm_bytes=meta.get("peak_hbm_bytes"),
                compile_s=meta.get("compile_s"),
            ),
        )
        print(f"bench history appended -> {_HISTORY}", file=sys.stderr)
    except Exception as e:
        print(f"bench history append failed: {e!r}", file=sys.stderr)


def _telemetry_block() -> None:
    """Per-run observability block (ISSUE 1): build the representative
    distributed plan HOST-SIDE with telemetry on, print the summary to
    stderr, and archive the full snapshot next to the BENCH_*.json
    artifacts (same schema style: one committed JSON file).

    Planning is pure numpy — no devices — so this records real
    comm-bytes / imbalance / overlap numbers for the bench shape on any
    backend. Never fatal: everything goes to stderr.
    """
    try:
        from magiattention_tpu import env, telemetry
        from magiattention_tpu.common.enum import AttnMaskType
        from magiattention_tpu.common.ranges import AttnRanges
        from magiattention_tpu.meta.dispatch_meta import (
            make_dispatch_meta_from_qk_ranges,
        )
        from magiattention_tpu.meta.solver.overlap_solver import OverlapConfig
        from magiattention_tpu.parallel.dist_attn import build_dist_attn_plan
        from magiattention_tpu.utils.cost import (
            get_calc_cost_factor,
            get_comm_cost_factor,
        )

        telemetry.set_enabled(True)
        telemetry.reset()
        # the dist_bench reference shape: 64k causal over cp=4, auto degree
        total, cp, hq, hkv, d = 65536, 4, 8, 8, 128
        chunk = total // (env.min_chunks_per_rank() * cp)
        qr = AttnRanges.from_ranges([(0, total)])
        kr = AttnRanges.from_ranges([(0, total)])
        mq, _, bucket = make_dispatch_meta_from_qk_ranges(
            qr, kr, [AttnMaskType.CAUSAL], total, total,
            chunk_size=chunk, cp_size=cp,
        )
        gen = env.tpu_generation()
        oc = OverlapConfig(
            degree=None,
            calc_cost_factor=get_calc_cost_factor(hq, d, gen),
            comm_cost_factor=get_comm_cost_factor(hkv, d, gen),
        )
        plan = build_dist_attn_plan(mq, bucket, overlap_config=oc)
        telemetry.record_runtime_costs(
            plan, num_heads_q=hq, num_heads_kv=hkv, head_dim=d,
            bytes_per_elt=2, generation=gen,
        )
        _roofline_block()  # before the snapshot: gauges ride the archive
        snap = telemetry.snapshot()
        payload = {
            "provenance": (
                "host-side plan telemetry for the bench shape (64k causal "
                "bf16, cp=4, auto overlap degree); see docs/observability.md"
            ),
            "recorded_unix": int(time.time()),
            "snapshot": snap,
        }
        tmp = _TELEMETRY_OUT + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, _TELEMETRY_OUT)
        print(telemetry.telemetry_summary(snap), file=sys.stderr)
        print(f"telemetry snapshot -> {_TELEMETRY_OUT}", file=sys.stderr)
        _decode_summary_line()
        _comm_summary_line()
    except Exception as e:  # observability must never take the bench down
        print(f"telemetry block failed: {e!r}", file=sys.stderr)
    finally:
        try:
            from magiattention_tpu import telemetry

            telemetry.set_enabled(None)
        except Exception:
            pass


def _roofline_block() -> None:
    """Roofline section of the bench summary (ISSUE 10): mask-aware
    achieved-vs-peak on the heterogeneous 16k varlen headline — exact
    host-side FLOPs/occupancy counting at the rung the autotuner picks,
    with the measured TF/s pulled from the newest history entry. Prints
    the ``roofline probe:`` line and records the ``magi_roofline_*``
    gauges into the archived snapshot. Never fatal."""
    try:
        from magiattention_tpu import telemetry
        from magiattention_tpu.telemetry import baseline

        measured, _ = baseline.newest_metric_value(
            baseline.load_history(_HISTORY), _VARLEN_METRIC
        )
        qr, kr, ts = _varlen_slices()
        rep = telemetry.profile_roofline(
            qr,
            kr,
            ts,
            num_heads_q=_HEADLINE_HQ,
            num_heads_kv=_HEADLINE_HK,
            head_dim=_HEADLINE_D,
            dtype=_HEADLINE_DTYPE,
            workload="16k_varlen_block_causal",
            measured_tflops=measured,
        )
        f = rep.gap_fractions()
        head = (
            f"achieved {rep.efficiency:.1%} of {rep.peak_tflops:g} TF/s "
            f"peak ({rep.measured_tflops:.2f} TF/s, newest history "
            "entry)"
            if measured is not None
            else "no measured TF/s in history; modeled gap"
        )
        print(
            f"roofline probe: 16k varlen: {head}, "
            f"dead-step {f['dead_steps']:.1%}, "
            f"dominant waste {rep.dominant_waste}, "
            f"density {rep.mask_density:.4f}",
            file=sys.stderr,
        )
    except Exception as e:
        print(f"roofline probe failed: {e!r}", file=sys.stderr)


def _decode_summary_line() -> None:
    """Decode section of the bench summary (ISSUE 4): one steady-state
    split-KV decode step on the serving subsystem — tokens/s and
    effective KV bandwidth for the probe config, on whatever backend
    the process runs (the line names it: a number off the TPU is not a
    device metric). Never fatal."""
    try:
        import jax

        from exps.run_decode_bench import bench_one, quick_probe_config

        on_tpu = jax.default_backend() == "tpu"
        if not on_tpu:
            os.environ.setdefault("MAGI_ATTENTION_KERNEL_BACKEND", "jnp")
        batch, kv_len, ps, splits = quick_probe_config(on_tpu)
        r = bench_one(batch, kv_len, ps, splits, reps=5)
        print(
            f"decode probe: batch {r['batch']} x kv {r['kv_len']} "
            f"(page {r['page_size']}, splits {r['num_splits']}): "
            f"{r['step_ms']:.2f} ms/step, {r['tokens_per_s']:.0f} tok/s, "
            f"{r['kv_gbps']:.2f} GB/s KV "
            f"[{jax.default_backend()} backend]",
            file=sys.stderr,
        )
    except Exception as e:
        print(f"decode probe failed: {e!r}", file=sys.stderr)


def _comm_summary_line() -> None:
    """Comm section of the bench summary (ISSUE 5): true vs scheduled
    group-cast rows and the auto-chosen collective impl for the headline
    varlen-heterogeneous plan (16k varlen-block-causal, cp=4). Host-side
    planning only. Never fatal."""
    try:
        from exps.run_comm_check import comm_probe

        p = comm_probe()
        print(
            f"comm probe: 16k varlen cp={p['cp']}: impl {p['impl']} "
            f"({p['impl_reason']}), true {p['true_rows_total']} rows, "
            f"scheduled {p['scheduled_rows_per_rank']}/rank vs legacy "
            f"padded {p['padded_rows_per_rank']}/rank "
            f"(-{p['volume_reduction']:.1%})",
            file=sys.stderr,
        )
    except Exception as e:
        print(f"comm probe failed: {e!r}", file=sys.stderr)


def _parity_check() -> bool:
    """One small flex-mask case vs the fp32 jnp oracle, ON THIS BACKEND.

    Every correctness test runs on the CPU sim / interpret mode; this is
    the one numerics assertion that executes the compiled Pallas kernel on
    the same chip the throughput number comes from. Mask: a varlen mix
    (causal doc + full doc + one cross slice) so all run-field paths fire.
    """
    import jax.numpy as jnp
    import numpy as np

    from magiattention_tpu.ops import flex_flash_attn_func
    from magiattention_tpu.testing.precision import calc_rel_err
    from magiattention_tpu.testing.ref_attn import ref_attn_from_ranges

    t, h, d = 2048, 4, 128
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((t, h, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((t, h, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((t, h, d)), jnp.bfloat16)
    qr = [(0, 1024), (1024, 2048), (256, 768)]
    kr = [(0, 1024), (1024, 2048), (1024, 1536)]
    ts = [1, 0, 0]  # causal doc, full doc, cross slice
    out = flex_flash_attn_func(q, k, v, qr, kr, ts)[0]
    ref = ref_attn_from_ranges(
        q.astype(jnp.float32), k.astype(jnp.float32),
        v.astype(jnp.float32), qr, kr, ts,
    )[0]
    rel = calc_rel_err(np.asarray(out, np.float32), np.asarray(ref))
    ok = bool(np.isfinite(rel) and rel < 2e-2)
    print(f"on-chip parity: rel_err={rel:.2e} ok={ok}", file=sys.stderr)
    return ok


def _stock_flash_tf(q, k, v, area, hq, d, n, block_sizes=None):
    """Time jax's official flash_attention on [t,h,d] inputs, causal,
    returning TFLOPs/s under the shared mask-area FLOPs convention.
    Single definition so the headline ratio and the tuned-baseline
    control can never drift apart in layout or FLOPs accounting."""
    import jax

    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention,
    )

    qb = q.transpose(1, 0, 2)[None]  # [1, h, t, d]
    kb = k.transpose(1, 0, 2)[None]
    vb = v.transpose(1, 0, 2)[None]
    f = jax.jit(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_sizes=block_sizes
        )
    )
    dt = _timeit(f, qb, kb, vb, n=n)
    return 4 * area * hq * d / dt / 1e12


def _measure() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from magiattention_tpu.ops import flex_flash_attn_func

    tq = _HEADLINE_T
    hq, hk = _HEADLINE_HQ, _HEADLINE_HK
    d = _HEADLINE_D
    dt = jnp.dtype(_HEADLINE_DTYPE)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((tq, hq, d)), dt)
    k = jnp.asarray(rng.standard_normal((tq, hk, d)), dt)
    v = jnp.asarray(rng.standard_normal((tq, hk, d)), dt)
    qr, kr, ts = [(0, tq)], [(0, tq)], [1]  # dense causal

    area = tq * (tq + 1) // 2
    flops = 4 * area * hq * d

    # block sizes: auto (auto_block_config picks the 64k-entry-safe config)
    fwd = jax.jit(
        lambda q, k, v: flex_flash_attn_func(q, k, v, qr, kr, ts)[0]
    )
    # cold-compile seconds vs warm step time (ISSUE 16 satellite): the
    # first call pays trace + lowering + XLA compile (minus whatever the
    # persistent compile cache absorbed); subtracting the warm step
    # isolates the compile share so compile-time regressions become
    # perf-gate-visible alongside TF/s
    t_cold = time.perf_counter()
    jax.block_until_ready(fwd(q, k, v))
    cold_s = time.perf_counter() - t_cold
    dt = _timeit(fwd, q, k, v, n=5)
    compile_s = max(cold_s - dt, 0.0)
    tflops = flops / dt / 1e12
    print(
        f"flex fwd: {dt*1e3:.2f} ms  {tflops:.2f} TFLOPs/s  "
        f"(cold compile {compile_s:.2f} s)",
        file=sys.stderr,
    )

    # baseline: jax official TPU flash attention, causal, same shape
    ref_tflops = _stock_flash_tf(q, k, v, area, hq, d, n=5)
    print(
        f"jax flash: {ref_tflops:.2f} TFLOPs/s (default blocks)",
        file=sys.stderr,
    )
    vs = tflops / ref_tflops

    return {
        "metric": "flex_attn_fwd_tflops_64k_causal_bf16",
        "value": round(tflops, 3),
        "unit": "TFLOPs/s",
        "vs_baseline": round(vs, 3),
        "compile_s": round(compile_s, 3),
    }, dt


def _measure_extras(dt_fwd_64k: float) -> dict:
    """Secondary on-chip metrics: 64k causal pure-bwd, 16k
    varlen-block-causal fwd (BASELINE config 2's kernel half), 128k
    causal fwd (config 3's kernel half). Recorded next to the headline
    in the history; the one-line stdout contract is unchanged."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from magiattention_tpu.ops import flex_flash_attn_func

    hq = hk = 8
    d = 128
    rng = np.random.default_rng(0)
    extras: dict = {}

    def qkv(t):
        return (
            jnp.asarray(rng.standard_normal((t, hq, d)), jnp.bfloat16),
            jnp.asarray(rng.standard_normal((t, hk, d)), jnp.bfloat16),
            jnp.asarray(rng.standard_normal((t, hk, d)), jnp.bfloat16),
        )

    def fwd_tf(t, qr, kr, ts, area, n=5):
        q, k, v = qkv(t)
        f = jax.jit(lambda q, k, v: flex_flash_attn_func(q, k, v, qr, kr, ts)[0])
        dt = _timeit(f, q, k, v, n=n)
        return 4 * area * hq * d / dt / 1e12

    # 1. 64k causal pure-bwd: (fwd+bwd) - fwd at 2.5x fwd FLOPs
    #    (the exps/run_kernel_bench.py convention, cp_benchmark.md:45);
    #    the fwd time is the headline's own measurement, not re-timed
    t = 65536
    qr, kr, ts = [(0, t)], [(0, t)], [1]
    area = t * (t + 1) // 2
    q, k, v = qkv(t)
    dt_fwd = dt_fwd_64k
    g = jax.jit(
        jax.grad(
            lambda q, k, v: flex_flash_attn_func(q, k, v, qr, kr, ts)[0]
            .astype(jnp.float32)
            .sum(),
            argnums=(0, 1, 2),
        )
    )
    dt_fb = _timeit(lambda q, k, v: g(q, k, v)[0], q, k, v, n=3)
    bwd_ms = max(dt_fb - dt_fwd, 1e-9)
    extras["flex_attn_bwd_tflops_64k_causal_bf16"] = round(
        2.5 * 4 * area * hq * d / bwd_ms / 1e12, 3
    )
    print(
        f"extras: 64k bwd {bwd_ms*1e3:.1f} ms  "
        f"{extras['flex_attn_bwd_tflops_64k_causal_bf16']:.1f} TF/s",
        file=sys.stderr,
    )

    # 2. 16k varlen block-causal fwd (the shared _VARLEN_* headline spec)
    t = _VARLEN_T
    qr, kr, ts = _varlen_slices()
    # exact area via the mask oracle (host-side, cheap at 16k)
    from magiattention_tpu.testing.ref_attn import make_attn_mask_from_ranges

    mask = make_attn_mask_from_ranges(qr, kr, ts, t, t)
    area = int(np.asarray(mask).sum())
    tf_varlen = fwd_tf(t, qr, kr, ts, area, n=10)
    extras[_VARLEN_METRIC] = round(tf_varlen, 3)
    print(f"extras: 16k varlen fwd {tf_varlen:.1f} TF/s", file=sys.stderr)

    # 3. 128k causal fwd (BASELINE config 3's single-chip kernel half)
    t = 131072
    qr, kr, ts = [(0, t)], [(0, t)], [1]
    area = t * (t + 1) // 2
    tf_128k = fwd_tf(t, qr, kr, ts, area, n=3)
    extras["flex_attn_fwd_tflops_128k_causal_bf16"] = round(tf_128k, 3)
    print(f"extras: 128k causal fwd {tf_128k:.1f} TF/s", file=sys.stderr)

    # 4. TUNED stock-kernel control (VERDICT r4 weakness 3): the headline
    #    vs_baseline times jax's flash_attention at its DEFAULT block
    #    sizes, which under-uses the chip at 64k. Sweep a few tuned
    #    BlockSizes and record the best, so the committed ratio has an
    #    honest tuned-baseline control next to it. Reuses section 1's
    #    still-live 64k q/k/v (no second 64k allocation), and any failure
    #    here must not discard sections 1-3 (whole section guarded).
    try:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            BlockSizes,
        )

        t = 65536
        area = t * (t + 1) // 2
        best = 0.0
        best_cfg = None
        for bq, bk in ((256, 512), (512, 1024), (1024, 1024)):
            try:
                bs = BlockSizes(
                    block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
                    block_q_major_dkv=bq, block_k_major_dkv=bk,
                    block_q_dkv=bq, block_k_dkv=bk,
                    block_q_dq=bq, block_k_dq=bk, block_k_major_dq=bk,
                )
                tf = _stock_flash_tf(q, k, v, area, hq, d, n=3,
                                     block_sizes=bs)
                print(
                    f"extras: stock flash tuned ({bq},{bk}): {tf:.1f} TF/s",
                    file=sys.stderr,
                )
                if tf > best:
                    best, best_cfg = tf, (bq, bk)
            except Exception as e:
                print(
                    f"extras: stock flash ({bq},{bk}) failed: {e!r}",
                    file=sys.stderr,
                )
        if best > 0:
            extras["jax_flash_fwd_tflops_64k_causal_bf16_best_tuned"] = round(
                best, 3
            )
            extras["jax_flash_best_tuned_blocks"] = list(best_cfg)
    except Exception as e:  # never lose sections 1-3 to the control
        print(f"extras: tuned-baseline control failed: {e!r}", file=sys.stderr)

    # 5. comm-volume metric for the heterogeneous varlen plan (ISSUE 5):
    #    legacy-padded / scheduled group-cast rows (higher = better), so
    #    the perf gate catches scheduled-volume regressions like TF/s.
    #    Host-side planning only; guarded like the control.
    try:
        from exps.run_comm_check import HEADLINE_METRIC, comm_probe

        p = comm_probe()
        extras[HEADLINE_METRIC] = p["volume_reduction_metric"]
        print(
            f"extras: comm volume reduction {p['volume_reduction_metric']}x "
            f"(impl {p['impl']})",
            file=sys.stderr,
        )
    except Exception as e:
        print(f"extras: comm volume metric failed: {e!r}", file=sys.stderr)

    # 6. unified serving tick (ISSUE 17): launches-per-tick and per-tick
    #    engine latency of the canonical scheduler trace under
    #    MAGI_ATTENTION_UNIFIED_TICK=on — the serving-side trajectory
    #    the tick gate bounds, recorded next to the kernel TF/s so the
    #    perf gate can watch it drift. Guarded like sections 4-5.
    try:
        from exps.run_tick_check import tick_probe

        p = tick_probe()
        extras.update(p)
        print(
            "extras: unified tick "
            f"{p['sched_launches_per_tick_unified_max']} launch/tick, "
            f"p50 {p['sched_tick_latency_ms_p50']} ms",
            file=sys.stderr,
        )
    except Exception as e:  # never lose sections 1-5 to the probe
        print(f"extras: unified tick probe failed: {e!r}", file=sys.stderr)

    # 7. plan-reuse scorecard (ISSUE 20): the fleet-replayed plan-cache
    #    hit rate + solver-ms-saved the plan-reuse gate bounds, recorded
    #    into history so run_perf_gate.py watches the same numbers drift.
    #    Host-side planning only; guarded like sections 4-6.
    try:
        from exps.run_plan_reuse_check import fleet_probe

        p = fleet_probe()
        extras["flex_attn_plan_cache_hit_rate"] = p[
            "flex_attn_plan_cache_hit_rate"
        ]
        extras["flex_attn_plan_solver_ms_saved"] = p[
            "flex_attn_plan_solver_ms_saved"
        ]
        print(
            "extras: plan reuse hit rate "
            f"{p['flex_attn_plan_cache_hit_rate']} "
            f"({p['flex_attn_plan_solver_ms_saved']} ms saved)",
            file=sys.stderr,
        )
    except Exception as e:  # never lose sections 1-6 to the probe
        print(f"extras: plan-reuse probe failed: {e!r}", file=sys.stderr)
    return extras


if __name__ == "__main__":
    if "--telemetry" not in sys.argv[1:]:
        _run_real()
    _telemetry_block()
