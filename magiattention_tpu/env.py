"""Environment-variable flags (reference ``magi_attention/env/``).

Same MAGI_ATTENTION_* names where the concept survives on TPU; CUDA-specific
flags (sm margins, NVSHMEM buffers, JIT build dirs) are intentionally absent
— XLA's async scheduler and AOT compilation replace them. Flags that
influence planning are folded into DistAttnRuntimeKey hashing (reference
dist_attn_runtime_mgr.py:61-119) via :func:`flags_fingerprint`.
"""

from __future__ import annotations

import os


def _env_bool(name: str, default: bool = False) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.strip().lower() in ("1", "true", "yes", "on")


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    return int(v) if v is not None else default


def _env_str(name: str, default: str) -> str:
    return os.environ.get(name, default)


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    return float(v) if v is not None else default


def log_level() -> str:
    """Logging level for the ``magiattention_tpu`` logger tree; consumed
    by :func:`magiattention_tpu.telemetry.logger.configure_logging` at
    package import."""
    return _env_str("MAGI_ATTENTION_LOG_LEVEL", "WARNING")


def log_level_explicit() -> bool:
    """Whether ``MAGI_ATTENTION_LOG_LEVEL`` was set at all: the logging
    config only claims the logger tree when the user asked (embedders
    who run their own ``logging.basicConfig`` keep control otherwise)."""
    return "MAGI_ATTENTION_LOG_LEVEL" in os.environ


VALIDATE_MODES = ("off", "plan", "trace")


def validate_mode() -> str:
    """Plan-sanitizer mode (``analysis/plan_sanity.py``), validated here:

    - ``off`` (default): no checks — zero overhead.
    - ``plan``: every ``build_dist_attn_plan`` output is run through the
      structural sanitizer (ranges in-bounds, recv-layout permutation,
      scheduled >= true >= local rows, area accounting) before it is
      returned; host-side only, adds low single-digit ms per build.
    - ``trace``: ``plan`` checks plus an abstract-eval collective census
      of the plan's group casts against its CommMeta (no execution, but
      traces a small program per comm meta — noticeably slower; meant
      for CI and debugging, not serving).

    Pure validation — never changes what is built, so NOT part of
    :func:`flags_fingerprint`."""
    v = _env_str("MAGI_ATTENTION_VALIDATE", "off").strip().lower()
    if v not in VALIDATE_MODES:
        raise ValueError(
            f"MAGI_ATTENTION_VALIDATE={v!r} must be one of {VALIDATE_MODES}"
        )
    return v


NUMERICS_MODES = ("off", "census")


def numerics_mode() -> str:
    """Numerics-observability mode (``telemetry/numerics.py``, ISSUE
    18), validated here:

    - ``off`` (default): no census — the traced programs carry ZERO
      extra ops and outputs stay bit-identical (proved by the
      numerics-check trace audit).
    - ``census``: the guard sites in ``parallel/dist_attn.py`` and
      ``serving/decode_attn.py`` additionally emit cheap traced value
      summaries (max logit, lse min/max, out max-abs, softmax-mass
      deviation), consumed at the jit boundary into the
      ``magi_numerics_*`` gauges/histograms and embedded in every
      flight dump as a ``numerics`` section. Pure reductions over
      already-materialized partials — no collectives are added.

    Changes the traced program (extra summary outputs), so part of
    :func:`flags_fingerprint`."""
    v = _env_str("MAGI_ATTENTION_NUMERICS", "off").strip().lower()
    if v not in NUMERICS_MODES:
        raise ValueError(
            f"MAGI_ATTENTION_NUMERICS={v!r} must be one of {NUMERICS_MODES}"
        )
    return v


def shadow_sample_rate() -> int:
    """Shadow-sampled drift-sentinel rate (``serving/engine.py``, ISSUE
    18): every Nth decode batch is re-computed through the f32 jnp
    reference path and scored against the production output with the
    error-budget oracle (``telemetry/numerics.py``); a budget breach
    records ``magi_numerics_shadow_divergence`` and arms a deferred
    ``numeric_drift`` flight dump tagged with the live trace id. ``0``
    (the default) disables the sentinel. Serving-host behavior only (the
    shadow runs OUTSIDE the production program and never changes a plan
    or a distributed runtime key), so NOT part of
    :func:`flags_fingerprint`."""
    v = _env_int("MAGI_ATTENTION_SHADOW_SAMPLE_RATE", 0)
    if v < 0:
        raise ValueError(
            f"MAGI_ATTENTION_SHADOW_SAMPLE_RATE={v} must be >= 0 "
            "(re-check every Nth decode batch; 0 disables)"
        )
    return v


GUARD_MODES = ("off", "check", "repair")


def guard_mode() -> str:
    """Numerical-guard mode (``resilience/guards.py``), validated here:

    - ``off`` (default): no sentinels — the traced programs contain ZERO
      guard ops (proved by the trace audit's guard census).
    - ``check``: non-finite partials at the guarded merge boundaries
      accumulate an in-graph error code; at the jit boundary a typed
      ``NumericalGuardError`` is raised naming the failing stage/site.
      Data is bit-identical to ``off``.
    - ``repair``: bad rows are additionally quarantined in-graph
      (lse -> -inf, out -> 0) so one poisoned partial merges as a no-op
      through the hardened correction path.

    Changes the traced program, so part of :func:`flags_fingerprint`."""
    v = _env_str("MAGI_ATTENTION_GUARD", "off").strip().lower()
    if v not in GUARD_MODES:
        raise ValueError(
            f"MAGI_ATTENTION_GUARD={v!r} must be one of {GUARD_MODES}"
        )
    return v


# last spec that passed grammar validation: chaos hooks sit on per-
# admission / per-allocate host paths and call the accessor repeatedly,
# so an unchanged spec must not re-parse every time
_chaos_spec_validated: str | None = None


def chaos_spec() -> str:
    """Raw fault-injection spec (``resilience/chaos.py``); '' = chaos
    off (the default — every hook is then a single predicate). A
    non-empty spec is grammar-validated here (one clause per injector,
    ``kind:key=value,...`` joined by ';' — see docs/resilience.md),
    once per distinct value.

    Injectors edit the traced program / host control flow, so the spec
    is part of :func:`flags_fingerprint` — a chaos run can never share a
    runtime key with a clean one."""
    global _chaos_spec_validated
    v = _env_str("MAGI_ATTENTION_CHAOS", "").strip()
    if v and v != _chaos_spec_validated:
        from .resilience.chaos import parse_chaos_spec

        parse_chaos_spec(v)  # raises ValueError on bad grammar
        _chaos_spec_validated = v
    return v


def mask_skip_disabled() -> bool:
    """Debug: force the diagnostic needs-mask flag to 1 on every entry
    in ``ops/block_meta.py``. Since the round-5 rewrite the kernels mask
    every tile unconditionally via the row-interval form, so this
    affects plan diagnostics (interior-tile statistics) only — never the
    execution path. Any non-empty value sets it — mirrors the
    historical raw ``MAGI_DISABLE_MASK_SKIP`` read this accessor
    replaced."""
    return bool(os.environ.get("MAGI_DISABLE_MASK_SKIP"))


def is_telemetry_enabled() -> bool:
    """Turn on the runtime telemetry layer (``telemetry/``): plan/comm/
    solver introspection metrics + host-side span events. Off by default;
    the disabled path is a no-op predicate per hook. Pure observability —
    never influences planning, so NOT part of :func:`flags_fingerprint`."""
    return _env_bool("MAGI_ATTENTION_TELEMETRY")


def telemetry_ring_size() -> int:
    """Capacity of the host-side span-event ring buffer (most recent N
    spans are kept; see telemetry/events.py)."""
    return _env_int("MAGI_ATTENTION_TELEMETRY_RING_SIZE", 4096)


def trace_dir() -> str:
    """Default XLA profiler trace directory used by
    ``utils/instrument.py::switch_profile`` when profile mode is on and no
    explicit ``trace_dir`` is passed."""
    return _env_str("MAGI_ATTENTION_TRACE_DIR", "./magi_attention_trace")


def metrics_port() -> int:
    """TCP port of the live Prometheus exposition endpoint
    (``telemetry/exposition.py``): ``0`` (the default) keeps the HTTP
    thread off entirely; a positive port starts one stdlib
    ``http.server`` thread per process serving ``GET /metrics`` in
    Prometheus text format (plus ``/metrics.json`` and ``/healthz``) the
    first time a :class:`ServingEngine` is built (or on an explicit
    ``telemetry.start_metrics_server()``). Pure observability — never
    influences planning, so NOT part of :func:`flags_fingerprint`."""
    v = _env_int("MAGI_ATTENTION_METRICS_PORT", 0)
    if v < 0 or v > 65535:
        raise ValueError(
            f"MAGI_ATTENTION_METRICS_PORT={v} must be 0 (off) or a valid "
            "TCP port"
        )
    return v


def flight_recorder_depth() -> int:
    """Tick capacity of the serving flight recorder
    (``telemetry/trace.py``): the last N scheduler ticks (StepReport +
    queue depth + budget utilization) and admission decisions kept in a
    bounded host ring, auto-dumped to ``MAGI_ATTENTION_TRACE_DIR`` when
    a resilience signal fires (NumericalGuardError, degradation path,
    admission-rejection storm, engine fault). ``0`` disables recording
    entirely. Always-on by default — the per-tick cost is one small dict
    append, negligible next to a scheduler tick's device work. Pure
    observability, NOT part of :func:`flags_fingerprint`."""
    v = _env_int("MAGI_ATTENTION_FLIGHT_RECORDER_DEPTH", 64)
    if v < 0:
        raise ValueError(
            f"MAGI_ATTENTION_FLIGHT_RECORDER_DEPTH={v} must be >= 0 "
            "(0 disables the recorder)"
        )
    return v


def mem_pressure_threshold() -> float:
    """Free-page fraction under which the scheduler's memory-pressure
    watcher (``telemetry/memory.MemPressureWatcher``) counts a tick as
    pressured; N consecutive pressured ticks (watcher default 8) arm a
    ``mem_pressure`` flight-recorder dump with the memory ledger +
    fragmentation snapshot embedded (ISSUE 14 OOM forensics). ``0.0``
    (the default) disables the watcher. Must be in [0, 1]. Pure
    observability, NOT part of :func:`flags_fingerprint`."""
    v = _env_float("MAGI_ATTENTION_MEM_PRESSURE_THRESHOLD", 0.0)
    if not 0.0 <= v <= 1.0:
        raise ValueError(
            f"MAGI_ATTENTION_MEM_PRESSURE_THRESHOLD={v} must be in "
            "[0, 1] (a free-page fraction; 0 disables)"
        )
    return v


def recompile_storm_threshold() -> int:
    """Compiles of the SAME program label inside the compile tracker's
    sliding window (30 s) that fire a deferred ``recompile_storm``
    flight-recorder dump (``telemetry/compile.py``, ISSUE 16), tagged
    with the triggering scheduler tick and live trace id — the serving
    post-mortem for shape thrash. ``0`` (the default) disables the
    detector; the tracker's compile accounting stays on either way.
    Must be >= 0. Pure observability, NOT part of
    :func:`flags_fingerprint`."""
    v = _env_int("MAGI_ATTENTION_RECOMPILE_STORM_THRESHOLD", 0)
    if v < 0:
        raise ValueError(
            f"MAGI_ATTENTION_RECOMPILE_STORM_THRESHOLD={v} must be >= 0 "
            "(compiles of one label per window; 0 disables)"
        )
    return v


def is_sanity_check_enabled() -> bool:
    """Deep invariant checks in the planners (reference env/general.py:75)."""
    return _env_bool("MAGI_ATTENTION_SANITY_CHECK")


def min_chunks_per_rank() -> int:
    """Auto chunk-size resolution divisor (reference env/general.py, =8)."""
    return _env_int("MAGI_ATTENTION_MIN_CHUNKS_PER_RANK", 8)


def runtime_dict_size() -> int:
    """LRU capacity of the runtime-key cache (reference env/general.py)."""
    return _env_int("MAGI_ATTENTION_RUNTIME_DICT_SIZE", 100)


PLAN_REUSE_MODES = ("off", "bucket")


def plan_reuse_mode() -> str:
    """Fingerprint-bucketed plan reuse (ISSUE 20, ``docs/plan_reuse.md``):

    - ``off`` (default): every novel mask pays the full host solve —
      today's behavior, bit-identical.
    - ``bucket``: on an exact-key LRU miss, ``magi_attn_flex_key`` /
      ``magi_attn_varlen_key`` canonicalize the mask to pow2-ish length
      buckets and consult a fingerprint-keyed second-level cache; a hit
      serves a padded-dispatch adapter over the bucketed plan instead of
      re-solving.

    Part of :func:`flags_fingerprint`: for the SAME runtime key the
    served plan differs between modes (exact plan vs bucketed adapter),
    so a mid-process flip must re-key rather than alias stale entries.
    """
    mode = _env_str("MAGI_ATTENTION_PLAN_REUSE", "off").lower()
    if mode not in PLAN_REUSE_MODES:
        raise ValueError(
            f"MAGI_ATTENTION_PLAN_REUSE={mode!r} is not one of "
            f"{PLAN_REUSE_MODES}"
        )
    return mode


def plan_cache_size() -> int:
    """Capacity of the fingerprint->canonical-plan second-level cache
    (``meta/plan_fingerprint.PlanReuseCache``); defaults to the runtime
    LRU capacity. Deliberately NOT part of :func:`flags_fingerprint`:
    capacity only changes WHEN an entry is evicted (and re-solved),
    never WHAT any plan contains — every plan is a pure function of its
    key, so two processes with different capacities still serve
    identical plans for identical keys."""
    size = _env_int("MAGI_ATTENTION_PLAN_CACHE_SIZE", runtime_dict_size())
    if size < 1:
        raise ValueError(
            f"MAGI_ATTENTION_PLAN_CACHE_SIZE={size} must be >= 1 (the "
            "second-level plan cache cannot hold zero fingerprints)"
        )
    return size


def kernel_backend() -> str:
    """'pallas' (TPU production), 'jnp' (any-platform dense reference
    path), or 'jnp_online' (block-wise online-softmax reference path)."""
    return _env_str("MAGI_ATTENTION_KERNEL_BACKEND", "pallas").lower()


def block_q() -> int:
    return _env_int("MAGI_ATTENTION_BLOCK_Q", 128)


def block_k() -> int:
    return _env_int("MAGI_ATTENTION_BLOCK_K", 128)


def block_q_override() -> int | None:
    """Explicitly-set kernel tile height, or None when the flag is unset.

    The keyed runtime treats an explicit MAGI_ATTENTION_BLOCK_Q/_BLOCK_K
    as a user-pinned blocking (the autotuner steps aside); :func:`block_q`
    keeps returning the 128 default for legacy call sites."""
    v = os.environ.get("MAGI_ATTENTION_BLOCK_Q")
    return int(v) if v else None


def block_k_override() -> int | None:
    v = os.environ.get("MAGI_ATTENTION_BLOCK_K")
    return int(v) if v else None


def autotune_mode() -> str:
    """Kernel block-config autotuner mode (``tuning/``): 'off' = the
    legacy static seqlen-keyed table, 'model' (default) = plan-aware
    analytic cost-model ranking, 'measure' = additionally time the top
    model candidates on device and persist winners in the tuning cache.
    Validated at use (autotuner + check_flag_comb)."""
    return _env_str("MAGI_ATTENTION_AUTOTUNE", "model").strip().lower()


def grid_override() -> str | None:
    """Pinned flex-kernel grid layout, or None (auto). 'row_major' keeps
    the static (heads, q-blocks, steps) grid, 'sparse' forces the
    compact occupied-entry walk (``ops/flex_attn.py`` GRID_KINDS) — the
    A/B lever for benching the two grids at a fixed blocking, honoured
    by ``auto_kernel_config`` and by the keyed runtime's
    ``make_attn_params``."""
    v = _env_str("MAGI_ATTENTION_GRID", "auto").strip().lower()
    if v in ("", "auto"):
        return None
    if v not in ("row_major", "sparse"):
        raise ValueError(
            f"MAGI_ATTENTION_GRID={v!r} must be 'auto', 'row_major', or "
            "'sparse'"
        )
    return v


def autotune_cache_dir() -> str:
    """Disk directory backing the tuning cache ('' = process-level cache
    only). Winners are stored per workload fingerprint; see
    docs/autotune.md for the file layout."""
    return _env_str("MAGI_ATTENTION_AUTOTUNE_CACHE_DIR", "")


def page_size() -> int:
    """KV-cache page size in tokens (``serving/kv_cache.py``): the unit
    of paged allocation and the decode kernel's K-side granularity. Must
    be a multiple of 8 (TPU sublane tiling of the page's token axis);
    128 keeps a page one full lane tile at head_dim 128."""
    return _env_int("MAGI_ATTENTION_PAGE_SIZE", 128)


def prefill_chunk() -> int | None:
    """Chunked-prefill chunk size in tokens (``serving/engine.py``,
    ``serving/scheduler.py``): prompts longer than this are prefilled in
    chunk-sized steps, each attending to the already-written cache via
    the cross path, so a long prompt never stalls the decode batch — the
    scheduler interleaves one chunk per step. Unset/0/'off' (default) =
    single-shot prefill. Serving-host behavior only (it never changes a
    plan or a distributed runtime key), so NOT part of
    :func:`flags_fingerprint`."""
    v = _env_str("MAGI_ATTENTION_PREFILL_CHUNK", "0").strip().lower()
    if v in ("", "0", "off", "none"):
        return None
    iv = int(v)
    if iv < 1:
        raise ValueError(
            f"MAGI_ATTENTION_PREFILL_CHUNK={v!r} must be a positive token "
            "count (or 0/off to disable chunking)"
        )
    return iv


CASCADE_MODES = ("auto", "on", "off")


def cascade_mode() -> str:
    """Cascade (two-level shared-prefix) decode attention mode
    (``serving/prefix.py``), validated here:

    - ``auto`` (default): cascade whenever >= 2 decode-batch members
      share a resident full-page prefix; flat split-KV otherwise.
    - ``on``: cascade for every prefix-carrying sequence, singleton
      groups included (the parity-test mode).
    - ``off``: always the flat split-KV path (prefix pages are still
      shared for memory — only the decode compute shape changes).

    Bit-parity between the paths (within dtype tolerance) is asserted by
    ``make sched-check``, so the mode is a performance choice, not a
    semantic one — and therefore NOT part of :func:`flags_fingerprint`."""
    v = _env_str("MAGI_ATTENTION_CASCADE", "auto").strip().lower()
    if v not in CASCADE_MODES:
        raise ValueError(
            f"MAGI_ATTENTION_CASCADE={v!r} must be one of {CASCADE_MODES}"
        )
    return v


UNIFIED_TICK_MODES = ("auto", "on", "off")


def unified_tick_mode() -> str:
    """Unified serving-tick attention mode (``serving/unified_tick.py``,
    ISSUE 17), validated here:

    - ``off`` (default): today's per-request path — one flex launch per
      prefilling request plus a batched decode call per tick,
      byte-for-byte unchanged.
    - ``auto``: fuse the tick into ONE sparse-grid launch whenever the
      per-request path would launch >= 2 distinct programs (any mixed
      prefill+decode tick, or >= 2 concurrent prefill chunks).
    - ``on``: every tick with attention work runs the unified kernel,
      single-program ticks included (the parity-test mode).

    Unlike ``MAGI_ATTENTION_CASCADE`` (a pure performance choice), this
    IS part of :func:`flags_fingerprint`: the unified path resolves its
    own ``tick``-kind tuning records and compiles a different program
    population, so runs sharing a tuning/plan cache directory across
    modes must not alias."""
    v = _env_str("MAGI_ATTENTION_UNIFIED_TICK", "off").strip().lower()
    if v not in UNIFIED_TICK_MODES:
        raise ValueError(
            f"MAGI_ATTENTION_UNIFIED_TICK={v!r} must be one of "
            f"{UNIFIED_TICK_MODES}"
        )
    return v


SERVING_TIERS = ("prefill", "decode")


def serving_mesh() -> dict | None:
    """Disaggregated-serving mesh spec (``serving/distributed.py``),
    validated here: ``MAGI_ATTENTION_SERVING_MESH`` names how many chips
    each serving tier owns, e.g. ``"prefill=1,decode=4"`` (four
    single-chip decode replicas) or ``"prefill=2,decode=2x2"`` (decode =
    2 data-parallel replicas x TP degree 2 — ``DxT`` chips). Unset/''
    (the default) returns ``None`` = single-chip serving, the
    :class:`~magiattention_tpu.serving.engine.ServingEngine` path.

    Returns ``{"prefill": P, "decode_dp": D, "decode_tp": T}``. Chip
    availability (P + D*T <= len(jax.devices())) is checked where the
    tiers are built, not here — env parsing stays jax-free. Serving-host
    topology only (never changes a plan or a distributed runtime key),
    so NOT part of :func:`flags_fingerprint`."""
    v = _env_str("MAGI_ATTENTION_SERVING_MESH", "").strip().lower()
    if not v:
        return None
    out = {"prefill": 1, "decode_dp": 1, "decode_tp": 1}
    seen = set()
    for item in v.split(","):
        tier, eq, count = item.partition("=")
        tier = tier.strip()
        if not eq or tier not in SERVING_TIERS:
            raise ValueError(
                f"MAGI_ATTENTION_SERVING_MESH: bad clause {item!r} "
                f"(want tier=count with tier in {SERVING_TIERS})"
            )
        if tier in seen:
            raise ValueError(
                f"MAGI_ATTENTION_SERVING_MESH: duplicate tier {tier!r}"
            )
        seen.add(tier)
        count = count.strip()
        try:
            if tier == "decode" and "x" in count:
                dp, _, tp = count.partition("x")
                out["decode_dp"], out["decode_tp"] = int(dp), int(tp)
            elif tier == "decode":
                out["decode_dp"] = int(count)
            else:
                out[tier] = int(count)
        except ValueError:
            raise ValueError(
                f"MAGI_ATTENTION_SERVING_MESH: {item!r} count must be an "
                "integer (decode also takes DxT for dp x tp)"
            ) from None
    if out["prefill"] < 1 or out["decode_dp"] < 1 or out["decode_tp"] < 1:
        raise ValueError(
            f"MAGI_ATTENTION_SERVING_MESH={v!r}: every tier count must be "
            ">= 1"
        )
    return out


def tier_token_budget(tier: str) -> int:
    """Per-tier token budget of one :class:`~magiattention_tpu.serving.
    distributed.TieredScheduler` tick (``MAGI_ATTENTION_TIER_BUDGET_PREFILL``
    / ``_DECODE``): the tiers run on DIFFERENT chips, so each gets its own
    budget instead of sharing the single-chip ``token_budget``. Decode
    counts one token per decoding sequence per tick; prefill counts chunk
    rows. Explicit constructor arguments win. Serving-host behavior only,
    so NOT part of :func:`flags_fingerprint`."""
    if tier not in SERVING_TIERS:
        raise ValueError(f"tier_token_budget: unknown tier {tier!r}")
    name = {
        "prefill": "MAGI_ATTENTION_TIER_BUDGET_PREFILL",
        "decode": "MAGI_ATTENTION_TIER_BUDGET_DECODE",
    }[tier]
    v = _env_int(name, 256)
    if v < 1:
        raise ValueError(f"{name}={v} must be a positive token count")
    return v


def decode_splits() -> int | None:
    """Split-KV decode split count (``serving/decode_attn.py``): an
    integer pins the number of KV splits per sequence; 'auto' (default)
    resolves through the tuning autotuner's decode fingerprint kind
    (``tuning.autotuner.select_decode_splits``)."""
    v = _env_str("MAGI_ATTENTION_DECODE_SPLITS", "auto").strip().lower()
    return None if v in ("", "auto") else int(v)


def head_block() -> int:
    """Q heads batched per kernel grid step in the distributed runtime
    (clamped to a divisor of hq that is a GQA-group multiple)."""
    return _env_int("MAGI_ATTENTION_HEAD_BLOCK", 8)


def head_block_override() -> int | None:
    """Explicitly-set head_block, or None when the flag is unset (the
    autotuned rung's measured head_block then applies)."""
    v = os.environ.get("MAGI_ATTENTION_HEAD_BLOCK")
    return int(v) if v else None


def tpu_generation() -> str:
    """TPU generation key for the cost model (utils/cost.py specs)."""
    return _env_str("MAGI_ATTENTION_TPU_GENERATION", "v5e")


def group_coll_impl() -> str:
    """Group-collective realization (``comm/group_collective.py``):
    'a2a' = one globally-padded ``lax.all_to_all`` per cast (legacy),
    'hops' = hop-scheduled exact-size ``lax.ppermute`` exchanges (hop k
    pads only to that hop's max pair size; zero-volume hops trace away),
    'auto' (default) = pick per collective by predicted wire volume at
    plan-build time. Validated at use (GroupCollectiveMeta.build +
    check_flag_comb); folded into :func:`flags_fingerprint`."""
    return _env_str("MAGI_ATTENTION_GROUP_COLL_IMPL", "auto").strip().lower()


GROUP_COLL_IMPLS = ("a2a", "hops", "auto")


def comm_pad_to() -> int:
    """Row-count bucketing rung for group-collective buffers
    (``MAGI_ATTENTION_COMM_PAD_TO``): every padded send/recv extent is
    rounded up to a multiple of this. Must be a power of two (sublane
    alignment); with hop-wise padding the rung actually matters at small
    pair sizes, hence configurable. Part of the key fingerprint."""
    v = _env_int("MAGI_ATTENTION_COMM_PAD_TO", 8)
    if v < 1 or (v & (v - 1)) != 0:
        raise ValueError(
            f"MAGI_ATTENTION_COMM_PAD_TO={v} must be a power of two >= 1"
        )
    return v


def overlap_degree_default() -> int | None:
    """Default multi-stage-overlap degree when no DistAttnConfig is given:
    an integer, or 'auto' for the degree=None cost-model search."""
    v = _env_str("MAGI_ATTENTION_OVERLAP_DEGREE", "0").strip().lower()
    return None if v == "auto" else int(v)


def min_stage_rows() -> int:
    return _env_int("MAGI_ATTENTION_MIN_STAGE_ROWS", 512)


def dynamic_max_degree() -> int:
    """Auto-degree search cap (reference OverlapConfig.dynamic_max_degree)."""
    return _env_int("MAGI_ATTENTION_DYNAMIC_MAX_DEGREE", 8)


def is_forward_high_precision_reduce() -> bool:
    """Keep the staged out/lse merge accumulator in fp32 (reference
    MAGI_ATTENTION_FORWARD_HIGH_PRECISION_REDUCE; default on)."""
    return _env_bool("MAGI_ATTENTION_FORWARD_HIGH_PRECISION_REDUCE", True)


def is_backward_high_precision_reduce() -> bool:
    """Carry the KV cast payload in fp32 so the transposed dKV reduce
    accumulates in fp32 (2x comm volume; reference
    MAGI_ATTENTION_BACKWARD_HIGH_PRECISION_REDUCE; default off)."""
    return _env_bool("MAGI_ATTENTION_BACKWARD_HIGH_PRECISION_REDUCE", False)


def is_qo_comm_enable() -> bool:
    """Route magi_attn_flex_key through the qo-comm runtime (dynamic
    plane partition moving Q/O as well as KV — reference
    MAGI_ATTENTION_QO_COMM, selecting DynamicAttnSolver at
    _make_attn_meta.py:40). Incompatible with hierarchical comm and
    uneven shard (check_flag_comb); sink is supported via the post-merge
    fold (parallel/qo_comm.py)."""
    return _env_bool("MAGI_ATTENTION_QO_COMM", False)


def is_hierarchical_comm_enable() -> bool:
    """Assert-only companion of the structural selection (reference
    MAGI_ATTENTION_HIERARCHICAL_COMM): hierarchical comm is chosen by
    passing a 2-D (inter, intra) cp_axis to magi_attn_flex_key; setting
    this flag with a 1-D cp_axis is rejected by check_flag_comb so a
    reference-style deployment script fails loudly instead of silently
    running flat comm."""
    return _env_bool("MAGI_ATTENTION_HIERARCHICAL_COMM", False)


def is_auto_range_merge_enable() -> bool:
    """Sort/merge overlapping k-ranges during kernel planning (reference
    MAGI_ATTENTION_AUTO_RANGE_MERGE)."""
    return _env_bool("MAGI_ATTENTION_AUTO_RANGE_MERGE", False)


def is_cpp_backend_enabled() -> bool:
    """Use the native C++ planning accelerators (parity-tested against the
    python fallback, so not part of the key fingerprint). Default-on:
    only an explicit 0/false/off/no disables it."""
    v = os.environ.get("MAGI_ATTENTION_CPP_BACKEND")
    if v is None:
        return True
    return v.strip().lower() not in ("0", "false", "off", "no")


def is_profile_mode() -> bool:
    """Default-on switch for the profiler helpers (reference
    MAGI_ATTENTION_PROFILE_MODE): ``switch_profile()`` with no explicit
    ``trace_dir`` starts an XLA trace into :func:`trace_dir`; off, it is
    a no-op. With telemetry on, the host's ``telemetry.span``s land in
    that trace as ``magi:<name>`` rows."""
    return _env_bool("MAGI_ATTENTION_PROFILE_MODE", False)


def recommended_compiler_options() -> dict:
    """XLA compile options the multi-stage overlap design depends on.

    The runtime's central bet (parallel/dist_attn.py docstring) is that
    XLA hides the per-stage KV group_cast under the Pallas kernel — the
    role the reference plays with sm_margin SM reservation and
    KernelBarrier stream ordering (reference functional/dist_attn.py:
    1073-1103, :3053-3116). On current TPU toolchains the all-to-all that
    group_cast lowers to stays *synchronous* unless
    ``xla_tpu_enable_async_all_to_all`` is set — measured in
    exps/run_overlap_proof.py: without it zero kernels are scheduled in
    the collective's in-flight window, with it the host-stage kernel is.

    Pass to jit: ``jax.jit(fn, compiler_options=...)`` (or
    ``fn.lower(...).compile(compiler_options=...)``).
    """
    return {
        "xla_tpu_enable_latency_hiding_scheduler": "true",
        "xla_tpu_enable_async_all_to_all": "true",
    }


def flags_fingerprint() -> tuple:
    """The behavior-influencing flags, folded into runtime-key hashing."""
    return (
        kernel_backend(),
        block_q(),
        block_k(),
        head_block(),
        tpu_generation(),
        overlap_degree_default(),
        min_stage_rows(),
        dynamic_max_degree(),
        is_forward_high_precision_reduce(),
        is_backward_high_precision_reduce(),
        is_auto_range_merge_enable(),
        is_qo_comm_enable(),
        is_hierarchical_comm_enable(),
        autotune_mode(),
        grid_override(),
        group_coll_impl(),
        comm_pad_to(),
        guard_mode(),
        chaos_spec(),
        unified_tick_mode(),
        numerics_mode(),
        plan_reuse_mode(),
    )
