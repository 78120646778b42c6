"""Mask-aware roofline accounting: where the lost TF/s actually go.

The headline dense paths run at 101-113 TF/s while 16k varlen
block-causal sits at 8.4 TF/s, and the naive roofline (measured / peak)
cannot say *why*: the TF/s convention divides by TRUE mask FLOPs, but
the entry-table kernel schedules full (block_q x block_k) MXU tiles and
a static ``steps`` grid extent, so a sparse heterogeneous mask pays for
work the convention never credits. This module decomposes that gap with
the SAME counting the autotuner's cost model ranks rungs with
(``tuning/cost_model.py`` — single source of truth, see
docs/autotune.md), at three nested area granularities per workload:

- ``A`` — exact mask area (valid entries; the TF/s convention's FLOPs);
- ``C`` — per-q-block covered-interval area: each q-block row's exact
  attended k-interval, before tile quantization. ``C - A`` is
  **masked-entry overcompute**: in-interval entries the mask zeroes
  (e.g. the causal wedge inside a tile row);
- ``B`` — scheduled tile area: every emitted entry pays a full
  ``block_q x block_k`` tile (incl. dead-row dummies). ``B - C`` is
  **partial-tile waste**: pure block-quantization padding (rows past the
  slice end, k columns past the interval).

plus the grid-step dimension: live slots pay the calibrated per-step fee
and clamped **dead steps** (rows shorter than the static ``steps``
extent) a reduced one (``STEP_OVERHEAD_S`` / ``DEAD_STEP_OVERHEAD_S`` —
the cost model's calibrated constants, reused verbatim).

Measured TF/s (bench ``do_bench`` discipline, or any number on the mask-
FLOPs convention) divides by a per-backend/per-generation peak table
(``MAGI_ATTENTION_PEAK_TFLOPS`` overrides) into the achieved fraction,
and the remaining gap is attributed term by term as modeled time over
measured time — with the honest ``unattributed`` residual for what the
model cannot price (dispatch floors, HBM stalls, layout churn).

Everything is host-side numpy on the slice lists — no devices needed —
so the analysis runs identically on CPU CI and next to an on-chip bench.
:func:`record_roofline` writes the ``magi_roofline_*`` gauges
(docs/observability.md catalog; ``make roofline-check`` guards drift).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from ..tuning.cost_model import (
    DEAD_STEP_OVERHEAD_S,
    SPARSE_STEP_OVERHEAD_S,
    STEP_OVERHEAD_S,
    _normalize_slices,
    estimate_entries,
    exact_mask_area,
    slice_block_k_spans,
)
from ..utils.cost import peak_spec

# per-backend nominal peak rates where no TPU generation spec applies:
# the jnp/CPU reference backend has no MXU — the placeholder keeps CPU
# CI runs finite and obviously not-a-chip (efficiencies >> 100% or
# << 1% both read as "wrong denominator, calibrate or override")
CPU_PEAK_TFLOPS = 0.10


def resolve_peak_tflops(
    generation: str | None = None, backend: str | None = None
) -> float:
    """The roofline denominator: ``MAGI_ATTENTION_PEAK_TFLOPS`` if set,
    else the CPU placeholder for the jnp backend, else the
    generation's datasheet bf16 peak (``utils/cost.py``
    TPU_PEAK_SPECS) — a generation the table does not know is an error,
    not a default."""
    from .. import env

    override = env.peak_tflops_override()
    if override is not None:
        return override
    backend = backend if backend is not None else env.kernel_backend()
    if backend in ("jnp", "jnp_online", "cpu"):
        return CPU_PEAK_TFLOPS
    gen = generation if generation is not None else env.tpu_generation()
    return peak_spec(gen).bf16_tflops


@dataclasses.dataclass(frozen=True)
class RooflineReport:
    """One workload's mask-aware roofline decomposition."""

    workload: str
    generation: str
    peak_tflops: float
    block_q: int
    block_k: int
    head_block: int
    num_heads_q: int
    head_dim: int
    # area accounting (A <= C <= B, entries in mask-entry units)
    mask_area: int  # A: exact valid entries
    covered_area: int  # C: per-q-block covered intervals
    tile_area: int  # B: entries * block_q * block_k
    mask_density: float  # A / (Sq * Sk) dense entries
    # grid accounting
    entries: int
    steps: int
    num_q_blocks: int
    grid_rows: int  # heads / head_block
    live_slots: int
    dead_slots: int
    bytes_moved: float  # modeled HBM traffic (q/o + per-entry kv re-reads)
    # kernel grid layout the accounting describes: the sparse entry walk
    # launches exactly ``live_slots`` slots (dead_slots == 0 by
    # construction — ROADMAP item 1's gate condition)
    grid: str = "row_major"
    # measurement (mask-FLOPs TF/s convention); None = static analysis
    measured_tflops: float | None = None
    measured_ms: float | None = None

    # -- FLOPs (mask-FLOPs convention: 4 * area * hq * d) -----------------

    @property
    def mask_flops(self) -> float:
        return 4.0 * self.mask_area * self.num_heads_q * self.head_dim

    @property
    def scheduled_flops(self) -> float:
        return 4.0 * self.tile_area * self.num_heads_q * self.head_dim

    @property
    def overcompute_ratio(self) -> float:
        """Scheduled tile FLOPs / true mask FLOPs (>= 1.0)."""
        return self.scheduled_flops / max(self.mask_flops, 1.0)

    @property
    def arithmetic_intensity(self) -> float:
        """Scheduled FLOPs per modeled HBM byte — which roof applies."""
        return self.scheduled_flops / max(self.bytes_moved, 1.0)

    # -- modeled time components (seconds) --------------------------------

    def _area_seconds(self, area: float) -> float:
        return (
            4.0 * area * self.num_heads_q * self.head_dim
            / (self.peak_tflops * 1e12)
        )

    @property
    def ideal_seconds(self) -> float:
        """Mask FLOPs at peak: the roofline floor measured time is held
        against (efficiency == ideal / measured by construction)."""
        return self._area_seconds(self.mask_area)

    @property
    def masked_overcompute_seconds(self) -> float:
        return self._area_seconds(self.covered_area - self.mask_area)

    @property
    def partial_tile_seconds(self) -> float:
        return self._area_seconds(self.tile_area - self.covered_area)

    @property
    def dead_step_seconds(self) -> float:
        return self.dead_slots * DEAD_STEP_OVERHEAD_S

    @property
    def live_step_seconds(self) -> float:
        fee = STEP_OVERHEAD_S + (
            SPARSE_STEP_OVERHEAD_S if self.grid == "sparse" else 0.0
        )
        return self.live_slots * fee

    @property
    def modeled_seconds(self) -> float:
        return (
            self.ideal_seconds
            + self.masked_overcompute_seconds
            + self.partial_tile_seconds
            + self.dead_step_seconds
            + self.live_step_seconds
        )

    # -- the decomposition ------------------------------------------------

    @property
    def efficiency(self) -> float | None:
        """Achieved fraction of peak on the TRUE mask FLOPs — the
        mask-aware roofline headline (== measured_tflops / peak)."""
        if self.measured_tflops is None:
            return None
        return self.measured_tflops / self.peak_tflops

    def gap_fractions(self) -> dict[str, float]:
        """Attribute the non-useful time: each waste term's modeled
        seconds over the total gap (measured - ideal when a measurement
        exists, modeled - ideal otherwise), plus the ``unattributed``
        residual (clamped at 0 when the model over-prices). Keys:
        ``dead_steps``, ``partial_tile``, ``masked_overcompute``,
        ``step_overhead``, ``unattributed``."""
        total = (
            self.measured_ms * 1e-3
            if self.measured_ms is not None
            else self.modeled_seconds
        )
        gap = max(total - self.ideal_seconds, 1e-30)
        parts = {
            "dead_steps": self.dead_step_seconds,
            "partial_tile": self.partial_tile_seconds,
            "masked_overcompute": self.masked_overcompute_seconds,
            "step_overhead": self.live_step_seconds,
        }
        # joint rescale when the model over-prices the gap (a measured
        # run faster than the modeled terms, or a wrong peak): the terms
        # keep their RELATIVE shares and sum to <= 1, never 100% each
        modeled = sum(parts.values())
        scale = min(gap / modeled, 1.0) if modeled > 0 else 0.0
        out = {k: v * scale / gap for k, v in parts.items()}
        out["unattributed"] = max(1.0 - sum(out.values()), 0.0)
        return out

    @property
    def dominant_waste(self) -> str:
        """The modeled waste term with the largest share of the gap —
        ``unattributed`` only when every modeled term is ~zero (the model
        priced nothing; naming a 0%-share term would be a lie)."""
        f = self.gap_fractions()
        terms = (
            "dead_steps", "partial_tile", "masked_overcompute",
            "step_overhead",
        )
        best = max(terms, key=lambda k: f[k])
        return best if f[best] > 1e-9 else "unattributed"

    def report(self) -> str:
        """Human-readable roofline verdict, ``MeasuredTimeline.report``
        style: accounting lines, then the gap attribution."""
        lines = [
            f"mask-aware roofline: {self.workload} on {self.generation} "
            f"(peak {self.peak_tflops:g} TF/s)",
            f"  rung {self.block_q}x{self.block_k}x{self.head_block} "
            f"[{self.grid}]: "
            f"{self.entries} entries over {self.num_q_blocks} q-blocks x "
            f"{self.steps} steps x {self.grid_rows} head rows "
            f"(dead slots {self.dead_slots}/"
            f"{self.dead_slots + self.live_slots})",
            f"  mask density {self.mask_density:.4f}  "
            f"true {self.mask_flops:.4g} FLOPs vs scheduled "
            f"{self.scheduled_flops:.4g} "
            f"({self.overcompute_ratio:.2f}x overcompute)",
            f"  modeled HBM bytes {self.bytes_moved:.4g} "
            f"(intensity {self.arithmetic_intensity:.1f} FLOP/B)",
        ]
        f = self.gap_fractions()
        if self.measured_tflops is not None:
            lines.append(
                f"  measured {self.measured_tflops:.2f} TF/s = "
                f"{self.efficiency:.1%} of peak "
                f"(ideal {self.ideal_seconds * 1e3:.3f} ms vs measured "
                f"{('%.3f' % self.measured_ms) if self.measured_ms is not None else '-'} ms)"
            )
        else:
            lines.append(
                f"  no measurement: attributing the MODELED gap "
                f"({self.modeled_seconds * 1e3:.3f} ms total, ideal "
                f"{self.ideal_seconds * 1e3:.3f} ms)"
            )
        lines.append(
            "  gap attribution: "
            f"masked-entry overcompute {f['masked_overcompute']:.1%}, "
            f"partial-tile {f['partial_tile']:.1%}, "
            f"dead steps {f['dead_steps']:.1%}, "
            f"step overhead {f['step_overhead']:.1%}, "
            f"unattributed {f['unattributed']:.1%}"
        )
        lines.append(f"  dominant waste term: {self.dominant_waste}")
        return "\n".join(lines)


def _covered_area(q, k, t, block_q: int) -> int:
    """C: sum over (slice, q-block) of rows-in-block x exact attended
    k-interval — the covered rectangles before k/row tile quantization."""
    total = 0
    for (q0, q1), (k0, k1), mt in zip(q.tolist(), k.tolist(), t.tolist()):
        if q1 <= q0 or k1 <= k0:
            continue
        _, lo, hi, k_lo, k_hi = slice_block_k_spans(
            q0, q1, k0, k1, mt, block_q
        )
        total += int(
            ((hi - lo) * np.maximum(k_hi - k_lo, 0)).sum()
        )
    return total


def analyze_workload(
    q_ranges,
    k_ranges,
    attn_type_map,
    *,
    num_heads_q: int,
    num_heads_kv: int,
    head_dim: int,
    block_q: int,
    block_k: int,
    head_block: int = 1,
    grid: str = "row_major",
    bytes_per_elt: int = 2,
    generation: str | None = None,
    backend: str | None = None,
    workload: str = "workload",
    measured_tflops: float | None = None,
    measured_ms: float | None = None,
    total_seqlen_q: int | None = None,
    total_seqlen_k: int | None = None,
) -> RooflineReport:
    """Static mask-aware roofline accounting of one workload at one rung.

    ``grid`` names the kernel grid layout being priced: the sparse entry
    walk has zero dead slots by construction (its grid extent IS the
    entry count), so the dead-step term vanishes and live slots carry
    the sparse dynamic-map fee — the same pricing the autotuner ranks
    with (single-sourced constants).

    Exactly one of ``measured_tflops`` / ``measured_ms`` (or neither, for
    a pure static analysis) — the other is derived through the mask-FLOPs
    convention. ``total_seqlen_*`` widen the dense denominator of the
    density beyond the slices' own extent (dispatched/padded layouts).
    """
    q, k, t = _normalize_slices(q_ranges, k_ranges, attn_type_map)
    from .. import env

    gen = generation if generation is not None else env.tpu_generation()
    peak = resolve_peak_tflops(generation=gen, backend=backend)
    entries, steps, nq = estimate_entries(q, k, t, block_q, block_k)
    area = exact_mask_area(q, k, t)
    covered = _covered_area(q, k, t, block_q)
    tile_area = entries * block_q * block_k
    sq = (
        int(total_seqlen_q)
        if total_seqlen_q is not None
        else (int(q[:, 1].max()) if q.size else 0)
    )
    sk = (
        int(total_seqlen_k)
        if total_seqlen_k is not None
        else (int(k[:, 1].max()) if k.size else 0)
    )
    grid_rows = max(num_heads_q // max(head_block, 1), 1)
    live = grid_rows * entries
    dead = 0 if grid == "sparse" else max(grid_rows * nq * steps - live, 0)
    # modeled HBM traffic: Q read + O write once per row-head, K+V
    # re-read once per emitted tile column (the entry table's DMA shape)
    qo_bytes = 2.0 * sq * num_heads_q * head_dim * bytes_per_elt
    kv_bytes = 2.0 * entries * block_k * num_heads_kv * head_dim * bytes_per_elt
    mask_flops = 4.0 * area * num_heads_q * head_dim
    if measured_tflops is None and measured_ms is not None and measured_ms > 0:
        measured_tflops = mask_flops / (measured_ms * 1e-3) / 1e12
    elif measured_ms is None and measured_tflops:
        measured_ms = mask_flops / (measured_tflops * 1e12) * 1e3
    return RooflineReport(
        workload=workload,
        generation=gen,
        peak_tflops=peak,
        block_q=block_q,
        block_k=block_k,
        head_block=head_block,
        num_heads_q=num_heads_q,
        head_dim=head_dim,
        mask_area=area,
        covered_area=covered,
        tile_area=tile_area,
        mask_density=(area / (sq * sk)) if sq and sk else 0.0,
        entries=entries,
        steps=steps,
        num_q_blocks=nq,
        grid_rows=grid_rows,
        live_slots=live,
        dead_slots=dead,
        bytes_moved=qo_bytes + kv_bytes,
        grid=grid,
        measured_tflops=measured_tflops,
        measured_ms=measured_ms,
    )


def profile_roofline(
    q_ranges,
    k_ranges,
    attn_type_map=None,
    *,
    num_heads_q: int,
    num_heads_kv: int | None = None,
    head_dim: int,
    block_q: int | None = None,
    block_k: int | None = None,
    head_block: int | None = None,
    grid: str | None = None,
    dtype: str = "bfloat16",
    generation: str | None = None,
    workload: str = "workload",
    measured_tflops: float | None = None,
    measured_ms: float | None = None,
    measure: bool = False,
    reps: int = 5,
    warmup: int = 1,
    seed: int = 0,
    record: bool = True,
) -> RooflineReport:
    """Roofline-profile one workload: resolve the blocking the kernel
    would run (``auto_block_config`` — the autotuner's own decision, so
    the analysis prices what actually executed), optionally time the
    single-device kernel with the ``do_bench`` discipline
    (``measure=True``; otherwise pass ``measured_tflops``/``measured_ms``
    or get a static analysis), and record the ``magi_roofline_*`` gauges.

    The distributed twin is driving :func:`analyze_workload` with a
    measured time from ``profile_plan_timeline`` (see
    ``exps/run_roofline_check.py``); the keyed-runtime entry point is
    ``api.profile_roofline``.
    """
    hkv = num_heads_kv if num_heads_kv is not None else num_heads_q
    if block_q is None or block_k is None or head_block is None:
        from ..ops.flex_attn import auto_kernel_config

        bq, bk, hb, ag = auto_kernel_config(
            [(int(a), int(b)) for a, b in np.asarray(q_ranges).reshape(-1, 2)],
            [(int(a), int(b)) for a, b in np.asarray(k_ranges).reshape(-1, 2)],
            num_heads_q,
            hkv,
            attn_type_map=attn_type_map,
            head_dim=head_dim,
            dtype=dtype,
        )
        block_q = block_q if block_q is not None else bq
        block_k = block_k if block_k is not None else bk
        head_block = head_block if head_block is not None else hb
        grid = grid if grid is not None else ag
    if grid is None:
        # fully pinned blocking: price/run what a pinned
        # flex_flash_attn_func call at this blocking actually executes
        # (env override, else row-major) — NOT the autotuner's winning
        # grid for a DIFFERENT rung
        from .. import env

        override = env.grid_override()
        grid = override if override is not None else "row_major"
    if measure:
        measured_ms = _measure_ms(
            q_ranges, k_ranges, attn_type_map,
            num_heads_q, hkv, head_dim, dtype,
            # pin the kernel to the rung being priced — an explicitly
            # requested blocking must be the one that runs
            block_q=block_q, block_k=block_k, head_block=head_block,
            grid=grid, reps=reps, warmup=warmup, seed=seed,
        )
        measured_tflops = None  # re-derived from the mask-FLOPs convention
    rep = analyze_workload(
        q_ranges,
        k_ranges,
        attn_type_map,
        num_heads_q=num_heads_q,
        num_heads_kv=hkv,
        head_dim=head_dim,
        block_q=block_q,
        block_k=block_k,
        head_block=head_block,
        grid=grid,
        bytes_per_elt=int(np.dtype(dtype).itemsize),
        generation=generation,
        workload=workload,
        measured_tflops=measured_tflops,
        measured_ms=measured_ms,
    )
    if record:
        from .collectors import record_roofline

        record_roofline(rep)
    return rep


def _measure_ms(
    q_ranges, k_ranges, attn_type_map, hq, hkv, head_dim, dtype,
    *, block_q, block_k, head_block, grid, reps, warmup, seed,
) -> float:
    """Time the single-device flex kernel on synthesized operands with
    the ``do_bench`` sync discipline, at the EXACT blocking
    the analysis prices; returns median ms."""
    import jax
    import jax.numpy as jnp

    from ..benchmarking.bench import do_bench
    from ..ops import flex_flash_attn_func

    qr = [(int(a), int(b)) for a, b in np.asarray(q_ranges).reshape(-1, 2)]
    kr = [(int(a), int(b)) for a, b in np.asarray(k_ranges).reshape(-1, 2)]
    ts = (
        [int(x) for x in np.asarray(attn_type_map).reshape(-1)]
        if attn_type_map is not None
        else [0] * len(qr)
    )
    tq = max(b for _, b in qr)
    tk = max(b for _, b in kr)
    rng = np.random.default_rng(seed)
    dt = jnp.dtype(dtype)
    q = jnp.asarray(rng.standard_normal((tq, hq, head_dim)), dt)
    k = jnp.asarray(rng.standard_normal((tk, hkv, head_dim)), dt)
    v = jnp.asarray(rng.standard_normal((tk, hkv, head_dim)), dt)
    fwd = jax.jit(
        lambda q, k, v: flex_flash_attn_func(
            q, k, v, qr, kr, ts,
            block_q=block_q, block_k=block_k, head_block=head_block,
            grid=grid,
        )[0]
    )
    return do_bench(fwd, q, k, v, warmup=warmup, rep=reps).median_ms
