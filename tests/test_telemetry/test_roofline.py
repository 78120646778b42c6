"""Mask-aware roofline accounting (telemetry/roofline.py): exact area
single-sourced with the cost model, the A <= C <= B area nesting, the
gap decomposition pointing at planted culprits, the peak-table override,
and the magi_roofline_* gauge catalog."""

import numpy as np
import pytest

from magiattention_tpu import telemetry
from magiattention_tpu.telemetry.roofline import (
    CPU_PEAK_TFLOPS,
    analyze_workload,
    profile_roofline,
    resolve_peak_tflops,
)
from magiattention_tpu.testing.ref_attn import make_attn_mask_from_ranges
from magiattention_tpu.testing.workloads import varlen_block_causal
from magiattention_tpu.tuning.cost_model import exact_mask_area
from magiattention_tpu.utils.cost import TPU_PEAK_SPECS


@pytest.fixture(autouse=True)
def _telemetry():
    telemetry.set_enabled(True)
    telemetry.reset()
    yield
    telemetry.set_enabled(None)
    telemetry.reset()


def _random_slices(seed, total=192):
    rng = np.random.default_rng(seed)
    qr, kr, ts = [], [], []
    for _ in range(int(rng.integers(1, 6))):
        a, b = sorted(rng.integers(0, total, 2).tolist())
        c, d = sorted(rng.integers(0, total, 2).tolist())
        if a < b and c < d:
            qr.append((a, b))
            kr.append((c, d))
            ts.append(int(rng.choice([0, 1, 2])))
    return qr, kr, ts


def _disjoint_slices(seed, total=192):
    """Random varlen-style slices with DISJOINT q ranges — the kernel's
    no-(q,k)-overlap contract, under which per-slice area == the dense
    union mask's popcount."""
    rng = np.random.default_rng(seed)
    cuts = np.sort(
        rng.choice(np.arange(1, total), int(rng.integers(2, 6)),
                   replace=False)
    )
    bounds = [0, *[int(c) for c in cuts], total]
    qr, kr, ts = [], [], []
    for a, b in zip(bounds, bounds[1:]):
        c, d = sorted(rng.integers(0, total, 2).tolist())
        if c == d:
            continue
        qr.append((a, b))
        kr.append((c, d))
        ts.append(int(rng.choice([0, 1, 2])))
    return qr, kr, ts


@pytest.mark.parametrize("seed", [0, 2, 5, 9])
def test_exact_mask_area_matches_oracle(seed):
    total = 192
    qr, kr, ts = _disjoint_slices(seed, total)
    if not qr:
        pytest.skip("degenerate draw")
    mask = np.asarray(make_attn_mask_from_ranges(qr, kr, ts, total, total))
    assert exact_mask_area(qr, kr, ts) == int(mask.sum())


@pytest.mark.parametrize("seed", [1, 4, 7])
@pytest.mark.parametrize("bq,bk", [(32, 32), (64, 16)])
def test_area_nesting_invariant(seed, bq, bk):
    """A (mask) <= C (covered intervals) <= B (scheduled tiles)."""
    qr, kr, ts = _random_slices(seed)
    if not qr:
        pytest.skip("degenerate draw")
    rep = analyze_workload(
        qr, kr, ts, num_heads_q=4, num_heads_kv=4, head_dim=64,
        block_q=bq, block_k=bk, generation="v5e", backend="tpu",
    )
    assert rep.mask_area <= rep.covered_area <= rep.tile_area
    assert rep.overcompute_ratio >= 1.0
    assert rep.mask_flops == 4.0 * rep.mask_area * 4 * 64


def test_gap_fractions_partition_the_gap():
    sl = varlen_block_causal(2048, n_docs=6)
    rep = analyze_workload(
        [(a, b) for a, b, *_ in sl],
        [(s[2], s[3]) for s in sl],
        [s[4] for s in sl],
        num_heads_q=8, num_heads_kv=8, head_dim=128,
        block_q=128, block_k=128, head_block=8,
        generation="v5e", backend="tpu", measured_tflops=8.0,
    )
    f = rep.gap_fractions()
    assert set(f) == {
        "dead_steps", "partial_tile", "masked_overcompute",
        "step_overhead", "unattributed",
    }
    assert all(0.0 <= v <= 1.0 for v in f.values())
    assert sum(f.values()) <= 1.0 + 1e-9
    assert rep.dominant_waste in (
        "dead_steps", "partial_tile", "masked_overcompute",
        "step_overhead",
    )


def test_dominant_waste_never_names_a_zero_share_term():
    # aligned dense FULL attention at a perfectly even blocking: no dead
    # slots, no tile waste — only the live-step fee and the unpriced
    # residual remain, and the verdict must say so
    rep = analyze_workload(
        [(0, 4096)], [(0, 4096)], [0],
        num_heads_q=8, num_heads_kv=8, head_dim=128,
        block_q=128, block_k=128, head_block=8,
        generation="v5e", backend="tpu",
    )
    assert rep.dead_slots == 0
    assert rep.mask_area == rep.covered_area == rep.tile_area
    assert rep.dominant_waste == "step_overhead"
    f = rep.gap_fractions()
    assert f[rep.dominant_waste] > 0


def test_dead_block_plant_attributed_to_dead_steps():
    total, blk = 2048, 128
    n = total // blk
    qr = [(0, blk)] + [(i * blk, (i + 1) * blk) for i in range(1, n)]
    kr = [(0, total)] + [(i * blk, (i + 1) * blk) for i in range(1, n)]
    ts = [0] * n
    rep = analyze_workload(
        qr, kr, ts, num_heads_q=8, num_heads_kv=8, head_dim=128,
        block_q=blk, block_k=blk, head_block=8,
        generation="v5e", backend="tpu",
    )
    assert rep.dead_slots > 0
    assert rep.dominant_waste == "dead_steps"
    # tile-aligned full slices: the FLOPs-side wastes are exactly zero
    assert rep.covered_area == rep.mask_area == rep.tile_area


def test_masked_overcompute_dominates_wide_causal_blocks():
    # a dense causal mask at a tall q-block: half of every covered
    # interval is the masked causal wedge -> masked-entry overcompute
    rep = analyze_workload(
        [(0, 1024)], [(0, 1024)], [1],
        num_heads_q=8, num_heads_kv=8, head_dim=128,
        block_q=512, block_k=128, head_block=8,
        generation="v5e", backend="tpu",
    )
    assert rep.masked_overcompute_seconds > rep.partial_tile_seconds
    assert rep.masked_overcompute_seconds > rep.dead_step_seconds


def test_efficiency_is_measured_over_peak_and_ms_round_trip():
    rep = analyze_workload(
        [(0, 512)], [(0, 512)], [1],
        num_heads_q=4, num_heads_kv=4, head_dim=64,
        block_q=64, block_k=64, generation="v5p", backend="tpu",
        measured_tflops=45.9,
    )
    assert rep.peak_tflops == TPU_PEAK_SPECS["v5p"].bf16_tflops
    assert rep.efficiency == pytest.approx(45.9 / rep.peak_tflops)
    # measured_ms derived through the mask-FLOPs convention
    assert rep.measured_ms == pytest.approx(
        rep.mask_flops / (45.9e12) * 1e3
    )
    # and the reverse direction agrees
    rep2 = analyze_workload(
        [(0, 512)], [(0, 512)], [1],
        num_heads_q=4, num_heads_kv=4, head_dim=64,
        block_q=64, block_k=64, generation="v5p", backend="tpu",
        measured_ms=rep.measured_ms,
    )
    assert rep2.measured_tflops == pytest.approx(45.9)


def test_peak_table_and_override(monkeypatch):
    monkeypatch.delenv("MAGI_ATTENTION_PEAK_TFLOPS", raising=False)
    assert resolve_peak_tflops("v6e", "tpu") == (
        TPU_PEAK_SPECS["v6e"].bf16_tflops
    )
    # the jnp/CPU backends get the placeholder, not a chip number
    assert resolve_peak_tflops("v5e", "jnp") == CPU_PEAK_TFLOPS
    # a generation the table does not know is an error, not v5e's peak
    with pytest.raises(ValueError, match="unknown TPU generation"):
        resolve_peak_tflops("v9z", "tpu")
    monkeypatch.setenv("MAGI_ATTENTION_PEAK_TFLOPS", "123.5")
    assert resolve_peak_tflops("v5e", "tpu") == 123.5
    assert resolve_peak_tflops("v5e", "jnp") == 123.5
    monkeypatch.setenv("MAGI_ATTENTION_PEAK_TFLOPS", "-1")
    with pytest.raises(ValueError):
        resolve_peak_tflops()


def test_record_roofline_populates_catalog_and_summary():
    rep = analyze_workload(
        [(0, 512)], [(0, 512)], [1],
        num_heads_q=4, num_heads_kv=4, head_dim=64,
        block_q=64, block_k=64, generation="v5e", backend="tpu",
        workload="unit", measured_tflops=10.0,
    )
    telemetry.record_roofline(rep)
    snap = telemetry.snapshot()

    def has(name):
        return any(
            k == name or k.startswith(name + "{")
            for sec in snap.values()
            for k in sec
        )

    missing = [
        m for m in telemetry.REQUIRED_ROOFLINE_METRICS if not has(m)
    ]
    assert not missing, missing
    assert snap["gauges"][
        "magi_roofline_achieved_tflops{workload=unit}"
    ] == 10.0
    summary = telemetry.telemetry_summary(snap)
    assert "roofline probe" in summary and "dead-step fraction" in summary


def test_record_disabled_is_noop():
    telemetry.set_enabled(False)
    rep = analyze_workload(
        [(0, 128)], [(0, 128)], [1],
        num_heads_q=2, num_heads_kv=2, head_dim=32,
        block_q=32, block_k=32, generation="v5e", backend="tpu",
    )
    telemetry.record_roofline(rep)
    assert not any(telemetry.snapshot().values())


def test_profile_roofline_resolves_rung_and_measures(monkeypatch):
    """The measure=True path: auto rung + a real timed jnp-backend run
    feeding the mask-FLOPs convention."""
    monkeypatch.setenv("MAGI_ATTENTION_KERNEL_BACKEND", "jnp")
    rep = profile_roofline(
        [(0, 256)], [(0, 256)], [1],
        num_heads_q=2, num_heads_kv=2, head_dim=32,
        dtype="float32", workload="measured_unit",
        measure=True, reps=1,
    )
    assert rep.block_q > 0 and rep.block_k > 0  # auto rung resolved
    assert rep.measured_ms is not None and rep.measured_ms > 0
    assert rep.measured_tflops is not None and rep.measured_tflops > 0
    assert "measured" in rep.report()
    snap = telemetry.snapshot()
    assert any(
        k.startswith("magi_roofline_efficiency{")
        for k in snap["gauges"]
    )


def test_report_names_the_parts():
    rep = analyze_workload(
        [(0, 512)], [(0, 512)], [1],
        num_heads_q=4, num_heads_kv=4, head_dim=64,
        block_q=128, block_k=128, generation="v5e", backend="tpu",
        workload="report_unit", measured_tflops=5.0,
    )
    text = rep.report()
    for needle in (
        "mask-aware roofline: report_unit",
        "mask density",
        "gap attribution",
        "dominant waste term",
        "dead steps",
        "partial-tile",
        "masked-entry overcompute",
    ):
        assert needle in text, (needle, text)


def test_gap_fractions_jointly_rescaled_when_model_overprices():
    """Modeled terms larger than the actual gap must keep their relative
    shares and sum to <= 1 — never 100% each."""
    rep = analyze_workload(
        [(0, 1024)], [(0, 1024)], [1],
        num_heads_q=8, num_heads_kv=8, head_dim=128,
        block_q=512, block_k=128, head_block=8,
        generation="v5e", backend="tpu",
        # measured barely above ideal: the gap is tiny, the modeled
        # masked-overcompute term alone is far bigger
        measured_tflops=TPU_PEAK_SPECS["v5e"].bf16_tflops * 0.99,
    )
    f = rep.gap_fractions()
    assert sum(f.values()) <= 1.0 + 1e-9
    assert all(v <= 1.0 for v in f.values())
    # relative ordering of the modeled terms survives the rescale
    assert f["masked_overcompute"] >= f["partial_tile"] >= 0.0
    assert f["unattributed"] == pytest.approx(0.0, abs=1e-9)


def test_static_analysis_still_gets_a_summary_line():
    rep = analyze_workload(
        [(0, 256)], [(0, 256)], [1],
        num_heads_q=2, num_heads_kv=2, head_dim=32,
        block_q=64, block_k=64, generation="v5e", backend="tpu",
        workload="static_unit",
    )
    telemetry.record_roofline(rep)
    summary = telemetry.telemetry_summary()
    assert "roofline probe{workload=static_unit}: modeled vs" in summary


def test_measure_true_runs_the_priced_rung(monkeypatch):
    """An explicitly requested blocking must be the one the kernel is
    timed at — priced rung == executed rung."""
    monkeypatch.setenv("MAGI_ATTENTION_KERNEL_BACKEND", "jnp")
    seen = {}
    import magiattention_tpu.ops.flex_attn as fa

    real = fa.flex_flash_attn_func

    def spy(*args, **kwargs):
        seen["block_q"] = kwargs.get("block_q")
        seen["block_k"] = kwargs.get("block_k")
        return real(*args, **kwargs)

    import magiattention_tpu.ops as ops_pkg

    # _measure_ms resolves the kernel through the ops package at call
    # time, so patching the package attribute intercepts the real call
    monkeypatch.setattr(ops_pkg, "flex_flash_attn_func", spy)
    rep = profile_roofline(
        [(0, 256)], [(0, 256)], [1],
        num_heads_q=2, num_heads_kv=2, head_dim=32,
        dtype="float32", block_q=64, block_k=128, head_block=1,
        workload="pinned_rung", measure=True, reps=1, record=False,
    )
    assert (seen["block_q"], seen["block_k"]) == (64, 128)
    assert (rep.block_q, rep.block_k) == (64, 128)


def test_rerecord_without_measurement_clears_stale_efficiency():
    kw = dict(
        num_heads_q=2, num_heads_kv=2, head_dim=32,
        block_q=64, block_k=64, generation="v5e", backend="tpu",
        workload="reprofiled",
    )
    telemetry.record_roofline(
        analyze_workload([(0, 256)], [(0, 256)], [1],
                         measured_tflops=10.0, **kw)
    )
    g = telemetry.snapshot()["gauges"]
    assert "magi_roofline_efficiency{workload=reprofiled}" in g
    # a later STATIC re-analysis of the same workload must drop the
    # measured pair instead of pairing it with fresh fractions
    telemetry.record_roofline(
        analyze_workload([(0, 256)], [(0, 256)], [1], **kw)
    )
    g = telemetry.snapshot()["gauges"]
    assert "magi_roofline_efficiency{workload=reprofiled}" not in g
    assert "magi_roofline_achieved_tflops{workload=reprofiled}" not in g
    assert "magi_roofline_peak_tflops{workload=reprofiled}" in g


def test_sparse_grid_report_has_zero_dead_slots():
    """ISSUE 15: a sparse-grid analysis prices zero dead slots (the
    compact grid's extent IS the entry count) and its dead-step gap
    share is exactly 0."""
    from magiattention_tpu.telemetry.roofline import analyze_workload

    qr = [(0, 1000), (1000, 4096)]
    kr = [(0, 1000), (1000, 4096)]
    ts = [1, 1]
    row = analyze_workload(
        qr, kr, ts, num_heads_q=8, num_heads_kv=8, head_dim=128,
        block_q=128, block_k=512, head_block=8,
    )
    sp = analyze_workload(
        qr, kr, ts, num_heads_q=8, num_heads_kv=8, head_dim=128,
        block_q=128, block_k=512, head_block=8, grid="sparse",
    )
    assert row.dead_slots > 0  # the skewed rows burn dead slots
    assert sp.dead_slots == 0
    assert sp.grid == "sparse"
    assert sp.gap_fractions()["dead_steps"] == 0.0
    assert sp.live_slots == row.live_slots  # same entries, no clamping
    # the sparse grid prices the dynamic-map fee on live steps
    assert sp.live_step_seconds > row.live_step_seconds
