"""Autotuner front door: mode dispatch, cache orchestration, telemetry.

``MAGI_ATTENTION_AUTOTUNE`` modes:

- ``off``     — the legacy static preference table
  (``ops.flex_attn._static_block_config``), unchanged.
- ``model``   — (default) analytic cost-model ranking
  (:mod:`.cost_model`), cached by workload fingerprint.
- ``measure`` — model ranking first, then the top candidates are timed
  on device via the caller-supplied ``measure_fn`` and the measured
  winner is persisted (process + disk cache). Callers that cannot
  microbenchmark (traced inputs, distributed planning) degrade to
  ``model`` for that call — the decision records why.

Every decision is recorded through the telemetry registry (chosen rung,
source, predicted/measured cost, cache layer) so a plan snapshot shows
which rung each workload chose and why (``docs/observability.md``).
"""

from __future__ import annotations

import dataclasses
import math

from .cache import TuningRecord, get_tuning_cache
from .cost_model import any_feasible_rung, rank_candidates, smem_entries
from .fingerprint import make_fingerprint

AUTOTUNE_MODES = ("off", "model", "measure")
# candidates microbenchmarked in measure mode (the model's top picks)
MEASURE_TOP_K = 3


@dataclasses.dataclass(frozen=True)
class TuningDecision:
    """The resolved block configuration plus its provenance."""

    block_q: int
    block_k: int
    head_block: int
    source: str  # "static" | "model" | "measured" | "measure_failed"
    cache_layer: str  # "memory" | "disk" | "none"
    fingerprint_hash: str  # "" for static decisions
    predicted_ms: float
    measured_ms: float | None
    reason: str  # one-line human-readable why
    # kernel grid layout ("row_major" | "sparse"): heterogeneous masks
    # resolve to the compact sparse entry walk (ROADMAP item 1)
    grid: str = "row_major"
    # what the SMEM feasibility test read (flex decisions off the static
    # table): the chosen rung's entry count, which count it is (``exact``:
    # the global tables' own; ``bound``: per-rank tables, every slice's
    # bounding box times the rank's share) and how many candidates the test
    # dropped — so a record says when the test, not the price, chose
    smem_entries: int = 0
    smem_count: str = ""
    rejected_smem: int = 0
    # what the operand-bytes term read (flex decisions off the static
    # table; ISSUE 35): the chosen rung's forward MXU time and the time its
    # K and V stream takes at the priced share of the HBM's peak, which of
    # the two binds (``mxu`` | ``hbm``) and how many rungs that were cheaper
    # by tiles and steps alone the term passed over as HBM-bound
    mxu_seconds: float = 0.0
    hbm_seconds: float = 0.0
    bound: str = ""
    rejected_bytes: int = 0
    # which measured preference order the ranking broke its tie by
    # (``cost_model._preference_order``): ``long_seq``, the 64k dense
    # slice's big-tile lead, or ``measured``, the table's own order (every
    # mask under 16,384 rows or under ``SPARSE_DENSITY_THRESHOLD``); or
    # ``priced_pair``: the chosen rung led its smaller ``block_q`` in the
    # tie pool by its own price (``cost_model.PAIR_PRICE_MARGIN``)
    tie_order: str = ""

    @property
    def config(self) -> tuple[int, int, int]:
        return (self.block_q, self.block_k, self.head_block)

    @property
    def kernel_config(self) -> tuple[int, int, int, str]:
        return (self.block_q, self.block_k, self.head_block, self.grid)


def _bytes_verdict(rec: TuningRecord) -> dict:
    """The operand-bytes fields of a decision and its ranking's tie
    order, from the ranking's candidates as a record keeps them
    (``CandidateScore.as_dict``): the same answer on a miss and on a
    hit."""
    rung = ("block_q", "block_k", "head_block", "grid")
    chosen = next(
        (
            c
            for c in rec.candidates
            if all(c.get(f) == getattr(rec, f) for f in rung)
        ),
        None,
    )
    if chosen is None or "bound" not in chosen:
        return {}
    return dict(
        mxu_seconds=chosen["mxu_seconds"],
        hbm_seconds=chosen["hbm_seconds"],
        bound=chosen["bound"],
        # cheaper by tiles and steps alone, the price before the bytes
        rejected_bytes=sum(
            c.get("feasible", True)
            and c.get("bound") == "hbm"
            and c["compute_seconds"] < chosen["compute_seconds"]
            for c in rec.candidates
        ),
        tie_order=chosen.get("tie_order", ""),
    )


def _static_decision(q_ranges, k_ranges, hq: int, hk: int) -> TuningDecision:
    from ..ops.flex_attn import _static_block_config

    bq, bk, hb = _static_block_config(q_ranges, k_ranges, hq, hk)
    return TuningDecision(
        block_q=bq,
        block_k=bk,
        head_block=hb,
        source="static",
        cache_layer="none",
        fingerprint_hash="",
        predicted_ms=0.0,
        measured_ms=None,
        reason="MAGI_ATTENTION_AUTOTUNE=off: legacy seqlen-keyed table",
    )


def select_block_config(
    q_ranges,
    k_ranges,
    attn_type_map,
    hq: int,
    hk: int,
    *,
    head_dim: int = 128,
    dtype: str = "bfloat16",
    mode: str | None = None,
    max_block_q: int | None = None,
    max_block_k: int | None = None,
    cp_size: int = 1,
    measure_fn=None,
    include_sparse: bool = True,
    v_head_dim: int | None = None,
) -> TuningDecision | None:
    """Resolve (block_q, block_k, head_block, grid) for one workload.

    ``measure_fn(block_q, block_k, head_block, grid) -> seconds`` times
    one candidate on device (only consulted in ``measure`` mode;
    exceptions disqualify the candidate rather than failing the plan).
    ``include_sparse=False`` restricts the ranking to the row-major grid
    (the distributed plan builder's contract). ``cp_size`` says whose
    tables the SMEM test counts (``cost_model.smem_entries``): 1 the global
    ones, exactly; more a rank's, by the bounding-box estimate.

    Returns ``None`` when the caller's ``max_block_q``/``max_block_k``
    constraints leave no candidate rung — the caller falls back to its
    own default blocking (distributed plans with tiny per-rank shards).
    """
    from .. import env, telemetry

    if mode is None:
        mode = env.autotune_mode()
    if mode not in AUTOTUNE_MODES:
        raise ValueError(
            f"MAGI_ATTENTION_AUTOTUNE={mode!r} is not one of "
            f"{AUTOTUNE_MODES}"
        )
    if mode == "off":
        decision = _static_decision(q_ranges, k_ranges, hq, hk)
        _record(decision)
        return decision

    fp = make_fingerprint(
        q_ranges,
        k_ranges,
        attn_type_map,
        hq,
        hk,
        head_dim=head_dim,
        dtype=dtype,
        max_block_q=max_block_q,
        max_block_k=max_block_k,
        include_sparse=include_sparse,
        v_head_dim=v_head_dim,
    )
    cache = get_tuning_cache()
    rec, layer = cache.get(fp)
    aliased = False
    smem = None  # what the SMEM test reads for the cached rung, here
    if rec is not None:
        smem = smem_entries(
            q_ranges,
            k_ranges,
            attn_type_map,
            rec.block_q,
            rec.block_k,
            cp_size,
        )
    if (
        smem is not None
        and not smem.feasible
        and any_feasible_rung(
            q_ranges,
            k_ranges,
            attn_type_map,
            max_block_q=max_block_q,
            max_block_k=max_block_k,
            cp_size=cp_size,
        )
    ):
        # bucket-edge aliasing: the fingerprint's ~9% log2 buckets can
        # serve a winner whose entry table does not fit THIS workload's
        # exact SMEM budget — re-rank instead of failing at kernel launch.
        # (Unless NO rung fits — then the cached escalation winner is as
        # good as re-ranking, and serving it keeps the hit path cheap.)
        rec = None
        aliased = True
    if (
        rec is not None
        and mode == "measure"
        and measure_fn is not None
        and rec.source == "model"
        and any(c.get("feasible") for c in rec.candidates)
    ):
        # a model-sourced winner (e.g. cached under jit tracing, where no
        # microbenchmark is possible) must not permanently pre-empt the
        # measurement this call CAN run: fall through and upgrade the
        # entry. "measure_failed" records stay — every candidate crashed
        # once already; re-compiling and re-crashing them on every call
        # would turn one bad workload into a per-step compile storm. The
        # feasibility check keeps infeasible-everywhere workloads (nothing
        # will ever be measurable) on the cheap hit path instead of
        # re-ranking and rewriting the disk entry per call
        rec = None
    if rec is not None:
        telemetry.record_autotune_cache(hit=True, layer=layer)
        decision = TuningDecision(
            block_q=rec.block_q,
            block_k=rec.block_k,
            head_block=rec.head_block,
            source=rec.source,
            cache_layer=layer,
            fingerprint_hash=fp.stable_hash(),
            predicted_ms=rec.predicted_ms,
            measured_ms=rec.measured_ms,
            reason=f"tuning-cache {layer} hit ({rec.source} winner)",
            grid=rec.grid,
            smem_entries=smem.entries,
            smem_count=smem.count,
            rejected_smem=sum(
                not c.get("feasible", True) for c in rec.candidates
            ),
            **_bytes_verdict(rec),
        )
        _record(decision)
        return decision
    telemetry.record_autotune_cache(hit=False, layer="miss")

    scores = rank_candidates(
        q_ranges,
        k_ranges,
        attn_type_map,
        hq,
        hk,
        head_dim=head_dim,
        dtype=dtype,
        max_block_q=max_block_q,
        max_block_k=max_block_k,
        cp_size=cp_size,
        include_sparse=include_sparse,
        v_head_dim=v_head_dim,
    )
    if not scores:
        return None  # constraints excluded every rung
    best = scores[0]
    source = "model"
    measured_ms = None
    reason = (
        f"cost model: {best.block_q}x{best.block_k}x{best.head_block} "
        f"({best.grid}) ~{best.cost_seconds * 1e3:.2f} ms "
        f"(mxu {best.mxu_seconds * 1e3:.2f} + grid "
        f"{best.step_seconds * 1e3:.2f} + bytes "
        f"{best.hbm_excess_seconds * 1e3:.2f}; {best.entries} entries, "
        f"steps {best.steps})"
    )
    if mode == "measure" and measure_fn is not None:
        _check_measure_fn_arity(measure_fn)
        timed: list[tuple[float, object]] = []
        attempted = 0
        for cand in [s for s in scores if s.feasible][:MEASURE_TOP_K]:
            attempted += 1
            try:
                t = float(
                    measure_fn(
                        cand.block_q, cand.block_k, cand.head_block, cand.grid
                    )
                )
            except Exception as e:  # noqa: BLE001 — a crashing candidate
                # is disqualified, not fatal (e.g. over-budget SMEM)
                telemetry.record_autotune_measure_failure(
                    f"{cand.block_q}x{cand.block_k}x{cand.head_block}"
                    f":{cand.grid}",
                    str(e),
                )
                continue
            timed.append((t, cand))
            telemetry.record_autotune_measurement()
        if timed:
            t_best, best = min(timed, key=lambda x: x[0])
            source = "measured"
            measured_ms = t_best * 1e3
            reason = (
                f"measured winner {best.block_q}x{best.block_k}x"
                f"{best.head_block} ({best.grid}): {measured_ms:.2f} ms "
                f"over {len(timed)} candidates (fwd-only timing)"
            )
        elif attempted:
            source = "measure_failed"
            reason += (
                f" (all {attempted} microbenchmark candidates failed; "
                "model winner)"
            )
        else:
            # nothing was feasible to time — that is a model decision,
            # not a measurement failure
            reason += " (no feasible candidate to measure)"
    elif mode == "measure":
        reason += " (measure requested, no microbenchmark available here)"

    rec = TuningRecord(
        block_q=best.block_q,
        block_k=best.block_k,
        head_block=best.head_block,
        source=source,
        predicted_ms=best.cost_seconds * 1e3,
        measured_ms=measured_ms,
        candidates=tuple(s.as_dict() for s in scores),
        grid=best.grid,
    )
    if not aliased:
        cache.put(fp, rec)
    # aliased: the fingerprint slot keeps the resident workload's winner
    # (possibly an expensive on-chip measurement) — caching this exact
    # workload's re-rank would clobber it and set up an A/B re-tune
    # ping-pong; the rare collision victim re-ranks per call instead
    decision = TuningDecision(
        block_q=best.block_q,
        block_k=best.block_k,
        head_block=best.head_block,
        source=source,
        cache_layer="none",
        fingerprint_hash=fp.stable_hash(),
        predicted_ms=rec.predicted_ms,
        measured_ms=measured_ms,
        reason=reason,
        grid=best.grid,
        smem_entries=best.smem_entries,
        smem_count=best.smem_count,
        rejected_smem=sum(not s.feasible for s in scores),
        **_bytes_verdict(rec),
    )
    _record(decision)
    return decision


def _check_measure_fn_arity(measure_fn) -> None:
    """Fail loudly on a pre-sparse 3-arg ``measure_fn``: the contract
    grew a 4th ``grid`` argument (ISSUE 15), and without this check the
    per-candidate TypeError would be swallowed by the crashed-candidate
    handler — measure mode silently degrading to the model with the
    caller believing on-device timings ranked the rungs."""
    import inspect

    try:
        sig = inspect.signature(measure_fn)
    except (TypeError, ValueError):  # builtins/C callables: trust them
        return
    positional = [
        p
        for p in sig.parameters.values()
        if p.kind
        in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD, p.VAR_POSITIONAL)
    ]
    if any(p.kind == p.VAR_POSITIONAL for p in positional):
        return
    if len(positional) < 4:
        raise TypeError(
            "measure_fn must accept (block_q, block_k, head_block, grid) "
            f"— got a {len(positional)}-argument callable; the grid axis "
            "was added to the microbenchmark contract by the sparse-grid "
            "autotuner (ISSUE 15)"
        )


def _record(decision: TuningDecision) -> None:
    from .. import telemetry

    telemetry.record_autotune_decision(decision)


# chips with a megacore pair run the decode grid's "parallel" dimensions
# on two tensorcores; single-core chips gain nothing from extra splits
_MEGACORE_GENERATIONS = {"v4": 2, "v5p": 2}
# a merge level is a fused elementwise map over [batch, hq, d] — cheap,
# but not free; priced per level of the log-depth tree
_DECODE_MERGE_LEVEL_US = 3.0


def select_decode_splits(
    batch: int,
    max_pages_per_seq: int,
    page_size: int,
    hq: int,
    hk: int,
    *,
    head_dim: int = 128,
    dtype: str = "bfloat16",
    prefix_groups: int = 0,
) -> TuningDecision:
    """Resolve the split-KV decode split count (the ``decode``
    fingerprint kind; ISSUE 4).

    Decode is KV-bandwidth-bound (q_len = 1: every cached K/V byte is
    read once per step while the MXU sees a rank-1 product), so the
    model prices candidates as::

        time(s) = kv_bytes / (hbm_bw * min(batch * s, cores) / cores)
                + log2(s) * merge_level_cost

    i.e. splits only help until the grid's parallel dimensions cover the
    chip's tensorcore count (megacore pairs on v4/v5p; v5e/v6e run the
    sequential grid on one core and want s = 1 unless the batch is
    degenerate), and every extra split level costs one LSE-merge map.
    Candidates are the divisors of ``max_pages_per_seq`` (a split is a
    whole number of pages), capped at 16. The winner is cached in the
    shared tuning cache under the decode fingerprint with the record
    convention ``block_q = 1, block_k = pages per split, head_block =
    NUM SPLITS``. Consumers read the split count from ``head_block``,
    NOT from ``mpp // block_k``: the fingerprint buckets
    ``max_pages_per_seq`` (~9% log2 buckets), so a cache hit can serve a
    record computed at a nearby mpp whose ``block_k`` neither divides
    nor even fits the current geometry — the ratio-free split count
    survives the aliasing, and the caller clamps it to a divisor.

    ``prefix_groups`` (ISSUE 9): the cascade prefix-group count of the
    workload (0 = flat decode). It is a fingerprint axis only — the
    shared-prefix phase runs the same kernel at the group's batch, but
    its access pattern (one hot page set for the whole batch) must not
    share a tuned winner with flat decode at the same geometry.
    """
    from .. import env, telemetry
    from ..utils.cost import TPU_PEAK_SPECS
    from .fingerprint import make_decode_fingerprint

    mpp = max(int(max_pages_per_seq), 1)
    fp = make_decode_fingerprint(
        batch,
        mpp,
        page_size,
        hq,
        hk,
        head_dim=head_dim,
        dtype=dtype,
        prefix_groups=prefix_groups,
    )
    cache = get_tuning_cache()
    rec, layer = cache.get(fp)
    if rec is not None:
        telemetry.record_autotune_cache(hit=True, layer=layer)
        decision = TuningDecision(
            block_q=rec.block_q,
            block_k=rec.block_k,
            head_block=rec.head_block,
            source=rec.source,
            cache_layer=layer,
            fingerprint_hash=fp.stable_hash(),
            predicted_ms=rec.predicted_ms,
            measured_ms=rec.measured_ms,
            reason=f"decode tuning-cache {layer} hit ({rec.source} winner)",
        )
        _record(decision)
        return decision
    telemetry.record_autotune_cache(hit=False, layer="miss")

    gen = env.tpu_generation()
    cores = _MEGACORE_GENERATIONS.get(gen, 1)
    spec = TPU_PEAK_SPECS.get(gen)
    hbm_gbps = spec.hbm_gbps if spec else 819.0
    bytes_per_elt = 2 if "16" in str(dtype) else 4
    kv_bytes = (
        2 * batch * mpp * page_size * hk * head_dim * bytes_per_elt
    )
    candidates = sorted(
        s for s in range(1, min(mpp, 16) + 1) if mpp % s == 0
    )
    scored = []
    for s in candidates:
        speedup = min(max(batch, 1) * s, cores) / cores
        read_s = kv_bytes / (hbm_gbps * 1e9 * max(speedup, 1e-9))
        merge_s = math.log2(s) * _DECODE_MERGE_LEVEL_US * 1e-6 if s > 1 else 0.0
        scored.append((read_s + merge_s, s))
    scored.sort()
    best_cost, best_s = scored[0]
    pages_per_split = mpp // best_s
    rec = TuningRecord(
        block_q=1,
        block_k=pages_per_split,
        head_block=best_s,  # the split count (see docstring convention)
        source="model",
        predicted_ms=best_cost * 1e3,
        measured_ms=None,
        candidates=tuple(
            {
                "num_splits": s,
                "pages_per_split": mpp // s,
                "cost_seconds": c,
                "feasible": True,
            }
            for c, s in scored
        ),
    )
    cache.put(fp, rec)
    decision = TuningDecision(
        block_q=1,
        block_k=pages_per_split,
        head_block=best_s,
        source="model",
        cache_layer="none",
        fingerprint_hash=fp.stable_hash(),
        predicted_ms=rec.predicted_ms,
        measured_ms=None,
        reason=(
            f"decode model: {best_s} split(s) x {pages_per_split} pages "
            f"(~{best_cost * 1e3:.3f} ms, {cores} core(s), batch {batch})"
        ),
    )
    _record(decision)
    return decision


def select_tick_splits(
    row_capacity: int,
    entry_capacity: int,
    page_size: int,
    hq: int,
    hk: int,
    *,
    head_dim: int = 128,
    dtype: str = "bfloat16",
    prefill_rows: int = 0,
) -> TuningDecision:
    """Resolve the split count of one unified serving tick (the ``tick``
    fingerprint kind; ISSUE 17).

    The unified tick is the split-KV decode kernel driven over the
    tick's padded per-row page table, so the same bandwidth argument
    applies with the row capacity standing in for the decode batch:
    splits help only until ``rows * s`` covers the chip's tensorcore
    count, and every level costs one LSE-merge map. A tick's row count
    is a whole scheduler budget (tens to hundreds of rows), so the model
    almost always lands on ``s = 1`` — the fingerprinted cache entry is
    what matters: ``measure``-mode winners and real-chip recalibration
    slot in without touching the serving path, exactly like flex/decode.
    Candidates divide ``entry_capacity`` (a power of two, so every
    ``s <= 16`` power of two qualifies); the record keeps the decode
    convention ``head_block = NUM SPLITS`` with the caller clamping to a
    divisor of its live geometry.

    ``prefill_rows`` is a fingerprint axis only (decode-dominated and
    prefill-dominated ticks read different live-KV fractions through the
    same padded shape and must not share a winner)."""
    from .. import env, telemetry
    from ..utils.cost import TPU_PEAK_SPECS
    from .fingerprint import make_tick_fingerprint

    rows = max(int(row_capacity), 1)
    width = max(int(entry_capacity), 1)
    fp = make_tick_fingerprint(
        rows,
        width,
        page_size,
        hq,
        hk,
        head_dim=head_dim,
        dtype=dtype,
        prefill_rows=prefill_rows,
    )
    cache = get_tuning_cache()
    rec, layer = cache.get(fp)
    if rec is not None:
        telemetry.record_autotune_cache(hit=True, layer=layer)
        decision = TuningDecision(
            block_q=rec.block_q,
            block_k=rec.block_k,
            head_block=rec.head_block,
            source=rec.source,
            cache_layer=layer,
            fingerprint_hash=fp.stable_hash(),
            predicted_ms=rec.predicted_ms,
            measured_ms=rec.measured_ms,
            reason=f"tick tuning-cache {layer} hit ({rec.source} winner)",
        )
        _record(decision)
        return decision
    telemetry.record_autotune_cache(hit=False, layer="miss")

    gen = env.tpu_generation()
    cores = _MEGACORE_GENERATIONS.get(gen, 1)
    spec = TPU_PEAK_SPECS.get(gen)
    hbm_gbps = spec.hbm_gbps if spec else 819.0
    bytes_per_elt = 2 if "16" in str(dtype) else 4
    kv_bytes = (
        2 * rows * width * page_size * hk * head_dim * bytes_per_elt
    )
    candidates = sorted(
        s for s in range(1, min(width, 16) + 1) if width % s == 0
    )
    scored = []
    for s in candidates:
        speedup = min(rows * s, cores) / cores
        read_s = kv_bytes / (hbm_gbps * 1e9 * max(speedup, 1e-9))
        merge_s = (
            math.log2(s) * _DECODE_MERGE_LEVEL_US * 1e-6 if s > 1 else 0.0
        )
        scored.append((read_s + merge_s, s))
    scored.sort()
    best_cost, best_s = scored[0]
    rec = TuningRecord(
        block_q=1,
        block_k=width // best_s,
        head_block=best_s,  # the split count (decode record convention)
        source="model",
        predicted_ms=best_cost * 1e3,
        measured_ms=None,
        candidates=tuple(
            {
                "num_splits": s,
                "pages_per_split": width // s,
                "cost_seconds": c,
                "feasible": True,
            }
            for c, s in scored
        ),
    )
    cache.put(fp, rec)
    decision = TuningDecision(
        block_q=1,
        block_k=width // best_s,
        head_block=best_s,
        source="model",
        cache_layer="none",
        fingerprint_hash=fp.stable_hash(),
        predicted_ms=rec.predicted_ms,
        measured_ms=None,
        reason=(
            f"tick model: {best_s} split(s) x {width // best_s} pages "
            f"(~{best_cost * 1e3:.3f} ms, {cores} core(s), "
            f"{rows} tick rows)"
        ),
    )
    _record(decision)
    return decision


def resolve_block_config(
    q_ranges,
    k_ranges,
    types,
    total_q_padded: int,
    total_k_padded: int,
    cp_size: int,
    hq: int,
    hkv: int,
    head_dim: int,
    out_dtype: str,
    v_head_dim: int | None = None,
) -> tuple[int, int, int] | None:
    """Plan-aware block config for a distributed plan (keyed runtime or
    model-harness builder), or None for the legacy env-flag blocking.

    The autotuner steps aside when the user pinned a blocking via
    MAGI_ATTENTION_BLOCK_Q/_BLOCK_K, when MAGI_ATTENTION_AUTOTUNE=off, or
    when the per-rank shard is smaller than every candidate rung (tiny
    test meshes) — those cases keep the pre-ISSUE-2 behavior bit-for-bit.

    Candidates are constrained to the per-rank shard geometry (a tile
    wider than the rank's buffer is pure padding). At cp = 1 the plan's
    tables are the global tables and the SMEM test reads their exact entry
    count; at cp > 1 they are per-rank, and it reads the bounding-box
    estimate scaled to a rank (global entries / cp, doubled for run
    fragmentation). ``measure`` mode degrades to the cost model here —
    there is no way to microbenchmark a full distributed plan during key
    creation; the decision's telemetry records that. Sparse-grid rungs
    are excluded (``include_sparse=False``): the distributed kernels run
    the row-major grid (per-rank stacked tables with a static steps
    extent), so pricing a grid they cannot launch would mis-rank.
    """
    from .. import env

    if env.autotune_mode() == "off":
        return None
    if env.block_q_override() is not None or env.block_k_override() is not None:
        return None  # user-pinned blocking wins

    shard_q = max(total_q_padded // max(cp_size, 1), 1)
    shard_k = max(total_k_padded // max(cp_size, 1), 1)
    decision = select_block_config(
        q_ranges,
        k_ranges,
        types,
        hq,
        hkv,
        head_dim=head_dim,
        dtype=str(out_dtype),
        max_block_q=shard_q,
        max_block_k=shard_k,
        cp_size=cp_size,
        include_sparse=False,
        v_head_dim=v_head_dim,
    )
    if decision is None:
        return None
    hb_env = env.head_block_override()
    from ..ops.flex_attn import _auto_head_block

    hb = (
        decision.head_block
        if hb_env is None
        else _auto_head_block(hb_env, hq, max(hq // max(hkv, 1), 1))
    )
    return (decision.block_q, decision.block_k, hb)
