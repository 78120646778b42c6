"""Plan-time metric collectors: the runtime's introspection surface.

Each ``record_*`` function is called from ONE spot in the planning stack
(dispatch meta builder, group-collective routing, overlap auto-tuner, plan
builder, keyed interface) and translates what that layer just computed —
and previously discarded — into registry series. Every function no-ops
immediately while telemetry is disabled, so the planning hot path pays a
single predicate call.

The metric catalog (names, labels, units) is defined here as constants and
documented in ``docs/observability.md``; ``make telemetry-check`` asserts
the two stay in sync by building a real plan and checking the snapshot for
:data:`REQUIRED_PLAN_METRICS`.
"""

from __future__ import annotations

import time

from .events import DROPPED_COUNTER as M_TRACE_DROPPED
from .registry import get_registry


def _marker_event(name: str, attrs: dict) -> None:
    """Zero-duration marker on the same perf_counter clock ``span()``
    stamps real spans with — a ts=0 marker would stretch the Chrome
    trace's time axis back to system boot and collapse every real span
    to an invisible sliver."""
    from .events import record_event

    record_event(name, time.perf_counter(), 0.0, attrs)

# ---------------------------------------------------------------------------
# metric catalog (see docs/observability.md for the prose version)
# ---------------------------------------------------------------------------

# counters
M_PLAN_BUILDS = "magi_plan_builds_total"  # build_dist_attn_plan completions
M_DISPATCH_BUILDS = "magi_dispatch_meta_builds_total"
M_GRPCOLL_BUILDS = "magi_group_collective_builds_total"
M_CACHE_HITS = "magi_runtime_cache_hits_total"
M_CACHE_MISSES = "magi_runtime_cache_misses_total"
# plan-LRU visibility (ISSUE 9 satellite, seeds ROADMAP item 5): the
# canonical names for the keyed interface's plan-cache behavior — every
# hit is a full host-side solve NOT paid. Same events as the legacy
# magi_runtime_cache_* counters above (kept for dashboards); the pair
# is REQUIRED_PLAN_CACHE_METRICS so renames/drops fail the drift guard
M_PLAN_CACHE_HITS = "magi_plan_cache_hits"
M_PLAN_CACHE_MISSES = "magi_plan_cache_misses"
# plan-sanitizer counters (analysis/plan_sanity.py): only ticked while
# MAGI_ATTENTION_VALIDATE != off AND telemetry is enabled. checks counts
# every sanitizer invocation (pass or fail); failures counts raised
# PlanValidationErrors — alarm on failures > 0
M_VALIDATE_CHECKS = "magi_validate_plan_checks"
M_VALIDATE_FAILURES = "magi_validate_failures"

# gauges — dispatch layer
M_DISPATCH_NUM_CHUNKS = "magi_dispatch_num_chunks"
M_DISPATCH_CHUNKS_RANK = "magi_dispatch_chunks_per_rank"  # {rank=}
M_DISPATCH_TOKEN_IMBALANCE = "magi_dispatch_token_imbalance_ratio"
M_DISPATCH_UNEVEN = "magi_dispatch_uneven"  # 0/1
M_SOLVER_MINIMAX = "magi_dispatch_solver_minimax_workload"
M_SOLVER_BALANCE = "magi_dispatch_solver_balance_ratio"  # max/mean bucket
M_DYN_SOLVER_BALANCE = "magi_dynamic_solver_balance_ratio"  # qo-comm plane

# gauges — comm layer (rows are payload rows; bytes are resolved by the
# interface layer, which knows heads/head_dim/dtype)
M_COMM_SEND_ROWS = "magi_comm_send_rows"  # {rank=}
M_COMM_RECV_ROWS = "magi_comm_recv_rows"  # {rank=}
M_COMM_PADDED_ROWS = "magi_comm_padded_payload_rows"
M_COMM_BYTES_RANK = "magi_comm_bytes_per_rank"  # {rank=}, bytes
# rows the SELECTED impl schedules on the wire per rank (a2a: the full
# cp*max_send globally-padded buffer; hops: sum of per-hop padded maxima)
# vs the true routed rows across the group — the pair ISSUE 5 splits the
# old padded-only accounting into
M_COMM_SCHEDULED_ROWS = "magi_comm_scheduled_payload_rows"
M_COMM_TRUE_ROWS = "magi_comm_true_rows_total"
# scheduled payload rows / true rows across the group, per collective
# kind ({kind=cast|reduce_sum|reduce_lse}; >= 1.0 when anything moves,
# 0.0 when the collective moves nothing). The SPMD uniform-shape cost the
# reference pays via split_alignment — never measured before ISSUE 2,
# per-kind + impl-aware since ISSUE 5 (was one blended padded/true gauge)
M_COMM_PADDING_OVERHEAD = "magi_comm_padding_overhead_ratio"
# which group-collective impl the last build selected and why: value 1,
# labels impl=a2a|hops, reason=env_pinned|auto_volume|auto_zero_volume|
# auto_near_uniform (mirrors the autotuner's choice gauge)
M_COMM_IMPL_CHOICE = "magi_comm_impl_choice"

# gauges — plan layer
M_PLAN_OVERLAP_DEGREE = "magi_plan_overlap_degree"
M_PLAN_NUM_STAGES = "magi_plan_num_stages"
M_PLAN_TOTAL_AREA = "magi_plan_total_area"
M_PLAN_MAX_RANK_AREA = "magi_plan_max_rank_area"
M_PLAN_AREA_IMBALANCE = "magi_plan_area_imbalance_ratio"
M_PLAN_KERNEL_STEPS_FWD = "magi_plan_kernel_steps_fwd"
M_PLAN_KERNEL_STEPS_BWD = "magi_plan_kernel_steps_bwd"
M_OVERLAP_AUTO_DEGREE = "magi_overlap_auto_degree"
M_OVERLAP_MAKESPAN = "magi_overlap_modeled_makespan_s"

# gauges — cost model (interface layer; utils/cost.py factors)
M_MODELED_FLOPS = "magi_plan_modeled_flops"
M_MODELED_CALC_S = "magi_plan_modeled_calc_seconds"
M_MODELED_COMM_S = "magi_plan_modeled_comm_seconds"

# counters + gauges — kernel autotuner (tuning/; see docs/autotune.md)
M_AUTOTUNE_CACHE_HITS = "magi_autotune_cache_hits_total"  # {layer=}
M_AUTOTUNE_CACHE_MISSES = "magi_autotune_cache_misses_total"
M_AUTOTUNE_MEASUREMENTS = "magi_autotune_measurements_total"
M_AUTOTUNE_MEASURE_FAILURES = "magi_autotune_measure_failures_total"
M_AUTOTUNE_BLOCK_Q = "magi_autotune_block_q"
M_AUTOTUNE_BLOCK_K = "magi_autotune_block_k"
M_AUTOTUNE_HEAD_BLOCK = "magi_autotune_head_block"
M_AUTOTUNE_PREDICTED_MS = "magi_autotune_predicted_ms"
M_AUTOTUNE_MEASURED_MS = "magi_autotune_measured_ms"
# cost-model rung decisions, by what binds the chosen rung: {bound=mxu|hbm}
M_AUTOTUNE_DECISIONS = "magi_autotune_decisions_total"
# which rung the last decision chose and why: value 1, labels rung=/source=
M_AUTOTUNE_CHOICE = "magi_autotune_choice"
# flex pallas_calls built (trace time), by role, by the q heads one grid
# step takes and by the grid walked: {kernel=fwd|bwd, heads_per_step=,
# grid=row_major|sparse}. Beside the head_block gauge above it says
# whether each kernel honoured the choice
M_FLEX_KERNEL_BUILDS = "magi_flex_kernel_build_total"
# plans by the form of their backward (make_attn_params): {form=fused}, the
# one k-major kernel that adds dq into HBM. Before PR 43 every plan ran
# the split form (dq q-major, then dkv); the label is kept so that a later
# form can be read against this one
M_FLEX_BWD_FORM = "magi_flex_bwd_form_total"
# gauge — the newest plan's grid decision (make_attn_params): percent of
# the steps the chosen grid launches over forward, dq and dkv that do no
# work (dead row-major steps; padded and dummy entries on both grids)
M_FLEX_DEAD_STEP_SHARE = "magi_flex_dead_step_share"
# gauge — the newest plan's tables (make_attn_params): percent of the
# live steps over forward, dq and dkv whose tile a stepped bound crosses
# (entries of a slice with a step above 1 that are not whole: the tiles
# that pay for the staircase); 0 for a mask with no stepped slice
M_FLEX_STEPPED_TILE_SHARE = "magi_flex_stepped_tile_share"
# gauge — the largest step of any slice of a model's attention mask, by
# attention kind (models/_common._plan_on_dispatch): {kind=sliding|full};
# 1: every bound moves a key a row
M_MASK_STEP = "magi_mask_step"
# attention plans a model builder made, by attention kind
# (models/pattern.py: one dispatch, a plan per kind): {kind=sliding|full}
M_MODEL_ATTN_PLANS = "magi_model_attn_plans_total"
# attention calls whose forward rule was traced in the kept form
# (ops/flex_attn._flex_attn_core_fwd under FlexAttnParams.kept), by the
# layer's attention kind: {kind=sliding|full}. A gradient traced once
# counts a trunk's once-applied attention layers; the looped trunk's
# layers, kept by nobody, count 0
M_FLEX_FORWARD_KEPT = "magi_flex_forward_kept_total"
# gauges — an expert layer's load on the experts THIS rank holds, from a
# step the caller read on the host (MagiPattern.record_expert_load):
# token-expert pairs computed here, and the busiest held expert's pairs
# over the mean of the held experts: {layer=}
M_MOE_PAIRS_HERE = "magi_moe_pairs_here"
M_MOE_LOAD_MAX_OVER_MEAN = "magi_moe_load_max_over_mean"
# counter — expert layers differentiated with their rows moved by the
# sort's permutation (models/pattern.held_expert_ffn where every chunk of
# pair rows runs), counted once at each end of the path where the rule's
# forward is traced: {end=combine|dispatch}
M_MOE_ROWS_PERMUTED = "magi_moe_rows_permuted_total"
# gauge — the expert layers whose route is made on the attention half's
# normed input, before the layer's attention call
# (PatternConfig.router_input "attn"), set where the model is built
# (build_magi_pattern); 0 where every router reads the FFN half's input
M_MOE_ROUTE_AHEAD_LAYERS = "magi_moe_route_ahead_layers"
# gauge — what a key-value cast carries a token under latent attention,
# in elements, set where a latent model is built (build_magi_pattern):
# {form=expanded} every head's k and v as the kernels take them (what the
# cast carries today), {form=latent} the latent and the shared rotary key
M_MLA_KV_CAST_WIDTH = "magi_mla_kv_cast_width"
# gauges — a pattern decoder's passes through its layer stack and the
# layer applications a forward makes (layers x passes), set where the
# model is built (build_magi_pattern); 1 and the layer count unless the
# decoder is a looped one
M_MODEL_LOOP_STEPS = "magi_model_loop_steps"
M_MODEL_LAYER_APPLICATIONS = "magi_model_layer_applications"
# counter — rows a forward shift along the documents brings from another
# rank, all ranks' and all taps' (each row once), added where a shift is
# planned (parallel/dispatch.make_shift_plan): 0 at cp = 1
M_SHIFT_REMOTE_ROWS = "magi_shift_remote_rows_total"
# the selective scan (ops/selective_scan.py) and what a pattern decoder's
# layers hand on (models/pattern.py). counter — scan calls traced:
# {phase=fwd|bwd}; gauges — the documents whose first row zeroes the
# state, set where the model is built; a call's chunks and the bytes of
# the chunk boundaries' states its forward keeps for the backward
M_SSM_SCAN_CALLS = "magi_ssm_scan_calls_total"
M_SSM_DOCUMENTS = "magi_ssm_documents"
M_SSM_CHUNKS = "magi_ssm_chunks"
M_SSM_STATE_BYTES = "magi_ssm_state_bytes"
# the state-space-dual scan (ops/ssd_scan.py). counter — scan calls
# traced: {phase=fwd|bwd}; gauges — a call's heads, its chunks and the
# bytes of the chunk boundaries' states its forward keeps for the
# backward; the chunks with a document's start strictly inside (the reset
# is a mask on their tile), set where the model is built
M_SSD_SCAN_CALLS = "magi_ssd_scan_calls_total"
M_SSD_HEADS = "magi_ssd_heads"
M_SSD_CHUNKS = "magi_ssd_chunks"
M_SSD_RESET_CHUNKS = "magi_ssd_reset_chunks"
M_SSD_STATE_BYTES = "magi_ssd_state_bytes"
# gauge — a pattern decoder's scalars on the embedding, the residual
# adds, the softmax and the logits: {multiplier=embed|residual|softmax|logits}
M_MODEL_MULTIPLIERS = "magi_model_multipliers"
# gauge — layers that read the keys and values one layer handed on;
# counter — rows of that pair a rank's casts carried for a reader after
# the first (0 at cp = 1: every reader casts the pair again, ROADMAP R11);
# gauge — the share of the q and k lanes the flex kernels are handed that
# is padding (0.5 where 64-wide heads ride 128-wide kernel heads)
M_SHARED_KV_READERS = "magi_shared_kv_readers"
M_SHARED_KV_RECAST_ROWS = "magi_shared_kv_recast_rows_total"
M_FLEX_PAD_LANE_SHARE = "magi_flex_pad_lane_share"
# gauges — a pattern decoder's residual streams under hyper-connections
# (models/pattern.py, ``hc_mult``): how many, the Sinkhorn rounds a
# half-layer's mixing matrix takes, and the bytes a training step's stream
# mix moves at the least (a half-layer reads the state twice and writes it
# once forward, three passes more backward, and under remat forward again)
M_MHC_STREAMS = "magi_mhc_streams"
M_MHC_SINKHORN_ITERS = "magi_mhc_sinkhorn_iters"
M_MHC_STREAM_BYTES = "magi_mhc_stream_bytes"
# counter — half-layers whose coefficients were traced, by the passes their
# product makes over the state: {form=bf16_one_pass|float32_three_pass}
# (models/pattern._mhc_coef; a compiled step counts each half-layer once)
M_MHC_COEF_HALVES = "magi_mhc_coef_halves"

# counters + gauges — serving subsystem (serving/; see docs/serving.md).
# decode layer: per continuous-batching step
M_DECODE_STEPS = "magi_decode_steps_total"
M_DECODE_TOKENS = "magi_decode_tokens_total"  # one per sequence per step
M_DECODE_BATCH = "magi_decode_batch_size"
# resolved flat split count of the last decode step; 0 = the step ran
# cascade attention, which resolves splits per phase (see the cascade
# gauge below)
M_DECODE_SPLITS = "magi_decode_num_splits"
M_DECODE_MAX_SEQ_LEN = "magi_decode_max_seq_len"
# shared-prefix groups the last decode step's cascade ran (0 = flat)
M_DECODE_CASCADE_GROUPS = "magi_decode_cascade_groups"
M_PREFILL_TOKENS = "magi_prefill_tokens_total"
# kv-cache layer: page-pool occupancy (PageAllocator accounting)
M_KVCACHE_PAGES_TOTAL = "magi_kvcache_pages_total"
M_KVCACHE_PAGES_USED = "magi_kvcache_pages_in_use"
M_KVCACHE_OCCUPANCY = "magi_kvcache_occupancy_ratio"
M_KVCACHE_ACTIVE_SEQS = "magi_kvcache_active_seqs"
M_KVCACHE_PAGE_SIZE = "magi_kvcache_page_size"
# resident pages referenced by more than one owner (CoW sharing)
M_KVCACHE_SHARED = "magi_kvcache_shared_pages"

# counters + gauges — shared-prefix cache (serving/prefix.py; ISSUE 9).
# hits/misses count admissions that carried token ids; matched tokens is
# the prefill compute the trie saved (one count per token NOT recomputed)
M_PREFIX_HITS = "magi_prefix_cache_hits_total"
M_PREFIX_MISSES = "magi_prefix_cache_misses_total"
M_PREFIX_MATCHED_TOKENS = "magi_prefix_matched_tokens_total"
M_PREFIX_RESIDENT = "magi_prefix_resident_pages"  # gauge: trie-pinned
M_PREFIX_REGISTERED = "magi_prefix_registered_pages_total"  # newly pinned
M_PREFIX_COW = "magi_prefix_cow_splits_total"  # pages privatized on write
M_PREFIX_EVICTED = "magi_prefix_evicted_pages_total"  # LRU pressure drops

# counters + gauges + histograms — chunked-prefill scheduler
# (serving/scheduler.py; ISSUE 9): per-step interleave accounting and the
# per-request SLO surface (queue wait, time-to-first-token, per-token
# decode latency)
M_SCHED_STEPS = "magi_sched_steps_total"
M_SCHED_PREFILL_CHUNKS = "magi_sched_prefill_chunks_total"
M_SCHED_DECODE_STEPS = "magi_sched_decode_steps_total"
M_SCHED_WAITING = "magi_sched_waiting_requests"  # gauge: queued
M_SCHED_ACTIVE = "magi_sched_active_requests"  # gauge: prefilling+decoding
M_SCHED_STEP_TOKENS = "magi_sched_step_tokens"  # gauge: last step's usage
# per-tick saturation surface (ISSUE 11 satellite): the fraction of the
# token budget the last tick actually spent, and the queue depth at tick
# START (before admissions) — scheduler saturation visible from a
# scrape, no trace replay needed
M_SCHED_BUDGET_UTIL = "magi_sched_budget_utilization"
M_SCHED_QUEUE_DEPTH = "magi_sched_queue_depth"
H_REQ_QUEUE_S = "magi_request_queue_seconds"
H_REQ_TTFT_S = "magi_request_ttft_seconds"
H_REQ_TOKLAT_S = "magi_request_token_latency_seconds"

# counters + gauges — disaggregated serving (serving/distributed.py;
# ISSUE 12). The page-transfer queue moves committed prefill pages to a
# decode replica's pool: streams/pages/bytes count the wire traffic of
# the prefill->decode hand-off, queue depth is the streams parked
# waiting for decode-tier capacity (sustained nonzero = the decode tier
# is the bottleneck). Tier gauges ({tier=prefill|decode, replica=})
# give per-chip occupancy; faults count decode-replica failures the
# requeue+replay path absorbed
M_PAGE_STREAMS = "magi_page_streams_total"
M_STREAM_PAGES = "magi_page_stream_pages_total"
M_STREAM_BYTES = "magi_page_stream_bytes_total"
M_STREAM_QUEUE = "magi_page_stream_queue_depth"  # gauge
M_TIER_FAULTS = "magi_tier_faults_total"  # {tier=, replica=}
M_TIER_PAGES_USED = "magi_tier_pages_in_use"  # {tier=, replica=}
M_TIER_ACTIVE = "magi_tier_active_requests"  # {tier=}

# gauges — memory observability (telemetry/memory.py; ISSUE 14; see
# docs/observability.md "Memory ledger & OOM forensics"). The ledger
# side ({ledger=, phase=}) is what the static pricing predicts; the
# measured side ({program=, kind=argument|output|temp|alias}) is XLA's
# compiled-executable memory_analysis; delta/unattributed pair them up
# (delta gates args+outputs — both sides price those exactly —
# unattributed is the honest temp residual, never folded into the gate)
M_MEM_PREDICTED = "magi_mem_predicted_bytes"  # {ledger=, phase=}
M_MEM_MEASURED = "magi_mem_measured_bytes"  # {program=, kind=}
M_MEM_DELTA = "magi_mem_delta_ratio"  # {program=} predicted/measured io
M_MEM_UNATTRIBUTED = "magi_mem_unattributed_bytes"  # {program=}
# pool forensics ({pool=}): unusable-free-run fraction at the current
# reservation granularity, longest free run, per-state page counts
# ({state=free|live|shared|trie}; shared = CoW, counted once), and the
# allocator's lifetime high-water mark
M_MEM_POOL_FRAG = "magi_mem_pool_fragmentation_ratio"  # {pool=}
M_MEM_POOL_FREE_RUN = "magi_mem_pool_free_run_max"  # {pool=}
M_MEM_POOL_PAGES = "magi_mem_pool_pages"  # {pool=, state=}
M_MEM_POOL_PEAK = "magi_mem_pool_peak_pages"  # {pool=}
# device HBM sampler ({device=}) — populated only where the backend
# exposes memory_stats (TPU/GPU; CPU runs record nothing), so NOT part
# of REQUIRED_MEMORY_METRICS
M_MEM_HBM_IN_USE = "magi_mem_hbm_bytes_in_use"  # {device=}
M_MEM_HBM_PEAK = "magi_mem_hbm_peak_bytes"  # process high-water
# admission watermark (ISSUE 13's headroom rule, made observable in
# ISSUE 14): free pages an evictionless admission must leave for decode
# growth, and the pool's current free pages — the pair a dashboard
# needs to see backpressure coming. BOTH are single-sourced from the
# scheduler's per-tick record_admission_watermark, which reads the
# admission-facing allocator — so a TieredEngine's decode replicas can
# never clobber the prefill-pool figure the headroom pairs with
M_SCHED_HEADROOM = "magi_sched_admission_headroom"
M_KVCACHE_FREE = "magi_kvcache_free_pages"

# counters — request-lifecycle tracing (telemetry/trace.py; ISSUE 11).
# traces started (one per Scheduler.submit); ring spans dropped
# (M_TRACE_DROPPED, defined next to the ring in events.py — nonzero
# means reconstructed span trees are partial); flight-recorder
# post-mortem dumps written ({trigger=})
M_REQ_TRACES = "magi_request_traces_total"
M_FLIGHT_DUMPS = "magi_flight_recorder_dumps_total"

# counters + gauges — resilience layer (resilience/; docs/resilience.md).
# guard counters ({site=host|merged|stageN|splitN|correction|reduce_lse}):
# checks ticks once per guard TRACED (trace-time, like record_comm_op);
# violations/repairs tick when an accumulated error code decodes nonzero
# at the jit boundary (check resp. repair mode)
M_GUARD_CHECKS = "magi_guard_checks"
M_GUARD_VIOLATIONS = "magi_guard_violations"
M_GUARD_REPAIRS = "magi_guard_repairs"
# admission control (serving/engine.py): rejections ({reason=}) and
# evictions performed by the bounded evict-lowest-priority-then-retry
# policy before a rejection or a late admission
M_ADMISSION_REJECTED = "magi_admission_rejected"
M_ADMISSION_EVICTIONS = "magi_admission_evictions"
# which degradation path last engaged: value 1, label reason=
# plan_build_error | hops_build_error — degradation is observable,
# never silent
M_DEGRADED_PATH = "magi_degraded_path"
# tuning-cache disk faults ({op=load|store}): previously swallowed
# silently by the load/store except paths
M_TUNING_CACHE_IO = "magi_tuning_cache_io_errors"

# histograms (seconds)
H_PLAN_BUILD_S = "magi_plan_build_seconds"
H_DISPATCH_SOLVE_S = "magi_dispatch_solve_seconds"

# program observability (telemetry/compile.py + the scheduler's launch
# ledger; ISSUE 16). Compile counter is per program label ({program=};
# prefill[start=S,t=N] / decode[b=B] / anon); compile seconds is the
# cumulative/percentile latency histogram; jit-cache entries is the
# executables-built-this-process gauge (a lower bound on live jit-cache
# entries — XLA rarely evicts). Launches-per-tick is a histogram of the
# DISTINCT jitted programs each Scheduler/TieredScheduler tick launched
# (ROADMAP item 2's "launches-per-tick -> 1-2" gate reads its p50/p95).
# Solver seconds times build_dist_attn_plan + plan-LRU lookups
# ({outcome=hit|miss}); ms-saved is credited on each cache hit with the
# mean measured cold-build latency (ROADMAP item 3's figure)
M_COMPILE_TOTAL = "magi_compile_total"  # {program=}
H_COMPILE_S = "magi_compile_seconds"
M_JIT_CACHE_ENTRIES = "magi_jit_cache_entries"
# jax's persistent compilation cache, as jax.monitoring reports it
M_COMPILE_CACHE_TOTAL = "magi_compile_cache_total"  # {result=hit|miss}
M_SCHED_LAUNCHES = "magi_sched_launches_per_tick"
H_PLAN_SOLVER_S = "magi_plan_solver_seconds"  # {outcome=}
M_SOLVER_MS_SAVED = "magi_plan_solver_ms_saved_total"
# fingerprint-bucketed plan reuse (ISSUE 20, docs/plan_reuse.md).
# Evictions: one tick per entry dropped by a capacity-bound cache
# ({cache=runtime} — the exact-key LRU, {cache=fingerprint} — the
# second-level PlanReuseCache). Bucket hits/misses: second-level
# lookups AFTER an exact-key miss (a bucket hit serves a padded-
# dispatch adapter instead of re-solving; both still tick the
# magi_plan_cache_* pair, which stays the hit-rate source of truth).
# Incremental: tail-extend deltas patched in O(delta) vs falling back
# to a full row-map rebuild (either way, no solver)
M_PLAN_CACHE_EVICTIONS = "magi_plan_cache_evictions_total"  # {cache=}
M_PLAN_BUCKET_HITS = "magi_plan_bucket_hits_total"
M_PLAN_BUCKET_MISSES = "magi_plan_bucket_misses_total"
M_PLAN_INCR_PATCHES = "magi_plan_incremental_patches_total"
M_PLAN_INCR_FALLBACKS = "magi_plan_incremental_fallbacks_total"

# the named synthetic Chrome-trace track the per-tick decomposition
# spans land on (events.py ``track=`` mechanism — one tick-decomposition
# track next to the request tracks)
TICK_TRACK = "scheduler ticks"

# the acceptance-criteria floor: one build_dist_attn_plan through the keyed
# interface must populate at least these (the drift guard's contract)
REQUIRED_PLAN_METRICS: tuple[str, ...] = (
    M_PLAN_BUILDS,
    M_DISPATCH_BUILDS,
    M_GRPCOLL_BUILDS,
    M_DISPATCH_TOKEN_IMBALANCE,
    M_PLAN_AREA_IMBALANCE,
    M_PLAN_OVERLAP_DEGREE,
    M_PLAN_KERNEL_STEPS_FWD,
    M_PLAN_KERNEL_STEPS_BWD,
    M_COMM_SEND_ROWS,
    M_COMM_RECV_ROWS,
    M_COMM_BYTES_RANK,
    M_COMM_PADDING_OVERHEAD,
    M_COMM_SCHEDULED_ROWS,
    M_COMM_TRUE_ROWS,
    M_COMM_IMPL_CHOICE,
    M_MODELED_FLOPS,
    M_MODELED_CALC_S,
    M_MODELED_COMM_S,
    H_PLAN_BUILD_S,
)

# populated by one cold + one warm resolution through the keyed
# interface (``magi_attn_flex_key``); asserted by make telemetry-check's
# plan-LRU step (ISSUE 9 satellite — the visibility ROADMAP item 5's
# plan-reuse work will be measured with)
REQUIRED_PLAN_CACHE_METRICS: tuple[str, ...] = (
    M_PLAN_CACHE_HITS,
    M_PLAN_CACHE_MISSES,
)

# populated by one prefill + one ServingEngine decode step; asserted by
# make telemetry-check's serving step and make serving-check, documented
# in docs/observability.md "Serving metrics" + docs/serving.md
REQUIRED_SERVING_METRICS: tuple[str, ...] = (
    M_DECODE_STEPS,
    M_DECODE_TOKENS,
    M_DECODE_BATCH,
    M_DECODE_SPLITS,
    M_DECODE_MAX_SEQ_LEN,
    M_DECODE_CASCADE_GROUPS,
    M_PREFILL_TOKENS,
    M_KVCACHE_PAGES_TOTAL,
    M_KVCACHE_PAGES_USED,
    M_KVCACHE_OCCUPANCY,
    M_KVCACHE_ACTIVE_SEQS,
    M_KVCACHE_PAGE_SIZE,
    M_KVCACHE_SHARED,
)

# populated by one hit + one miss prefix admission, a commit, a CoW
# split and an LRU eviction; asserted by make telemetry-check's
# shared-prefix step and exercised end-to-end by make sched-check,
# documented in docs/observability.md + docs/serving.md
REQUIRED_PREFIX_METRICS: tuple[str, ...] = (
    M_PREFIX_HITS,
    M_PREFIX_MISSES,
    M_PREFIX_MATCHED_TOKENS,
    M_PREFIX_RESIDENT,
    M_PREFIX_REGISTERED,
    M_PREFIX_COW,
    M_PREFIX_EVICTED,
)

# populated by a few Scheduler.step() ticks over a mixed prefill+decode
# trace; asserted by make telemetry-check's scheduler step and
# exercised end-to-end by make sched-check
REQUIRED_SCHED_METRICS: tuple[str, ...] = (
    M_SCHED_STEPS,
    M_SCHED_PREFILL_CHUNKS,
    M_SCHED_DECODE_STEPS,
    M_SCHED_WAITING,
    M_SCHED_ACTIVE,
    M_SCHED_STEP_TOKENS,
    M_SCHED_BUDGET_UTIL,
    M_SCHED_QUEUE_DEPTH,
    H_REQ_QUEUE_S,
    H_REQ_TTFT_S,
    H_REQ_TOKLAT_S,
)

# populated by one TieredEngine/TieredScheduler run that streams at
# least one committed prompt prefill->decode and absorbs one injected
# decode-replica fault; asserted by make distserve-check
# (exps/run_distserve_check.py), documented in docs/serving.md
# "Disaggregated serving" + docs/observability.md
REQUIRED_DISTSERVE_METRICS: tuple[str, ...] = (
    M_PAGE_STREAMS,
    M_STREAM_PAGES,
    M_STREAM_BYTES,
    M_STREAM_QUEUE,
    M_TIER_FAULTS,
    M_TIER_PAGES_USED,
    M_TIER_ACTIVE,
)

# populated by a traced scheduler run that overflows a (deliberately
# tiny) span ring and fires one flight-recorder dump; asserted by
# make trace-check (exps/run_trace_check.py), documented in
# docs/observability.md "Request tracing & exposition"
REQUIRED_TRACE_METRICS: tuple[str, ...] = (
    M_REQ_TRACES,
    M_TRACE_DROPPED,
    M_FLIGHT_DUMPS,
)

# populated by one ledger_vs_measured pass over the jitted decode /
# dist_attn programs plus a live serving trace (pool forensics +
# admission watermark); asserted by make memory-check
# (exps/run_memory_check.py), documented in docs/observability.md
# "Memory ledger & OOM forensics". The HBM sampler gauges are
# deliberately absent: CPU backends expose no memory_stats, and a
# REQUIRED metric must be populatable everywhere the check runs
REQUIRED_MEMORY_METRICS: tuple[str, ...] = (
    M_MEM_PREDICTED,
    M_MEM_MEASURED,
    M_MEM_DELTA,
    M_MEM_UNATTRIBUTED,
    M_MEM_POOL_FRAG,
    M_MEM_POOL_FREE_RUN,
    M_MEM_POOL_PAGES,
    M_MEM_POOL_PEAK,
    M_SCHED_HEADROOM,
    M_KVCACHE_FREE,
)


# populated by one guarded run + one chaos-degraded admission/build +
# one injected tuning-cache fault; asserted by make telemetry-check's
# resilience step and exercised end-to-end by make resilience-check,
# documented in docs/observability.md + docs/resilience.md
REQUIRED_RESILIENCE_METRICS: tuple[str, ...] = (
    M_GUARD_CHECKS,
    M_GUARD_VIOLATIONS,
    M_GUARD_REPAIRS,
    M_ADMISSION_REJECTED,
    M_ADMISSION_EVICTIONS,
    M_DEGRADED_PATH,
    M_TUNING_CACHE_IO,
)


# populated by the plan sanitizer while MAGI_ATTENTION_VALIDATE != off;
# asserted by make telemetry-check's validate step, documented in
# docs/observability.md + docs/static_analysis.md
REQUIRED_VALIDATE_METRICS: tuple[str, ...] = (
    M_VALIDATE_CHECKS,
    M_VALIDATE_FAILURES,
)


# the interleaving checker's exploration counters (ISSUE 13): canonical
# states visited and counterexamples found across model-check runs;
# populated by analysis/lifecycle.explore, asserted by
# make telemetry-check's analysis step, documented in
# docs/static_analysis.md "Pass 5"
M_ANALYSIS_STATES = "magi_analysis_states_explored"
M_ANALYSIS_CEX = "magi_analysis_counterexamples"

REQUIRED_ANALYSIS_METRICS: tuple[str, ...] = (
    M_ANALYSIS_STATES,
    M_ANALYSIS_CEX,
)


# populated by a multi-tenant trace through the real scheduler (compile
# tracker + launch ledger + tick cost attribution) plus one cold+warm
# keyed plan resolution; asserted by make compile-check
# (exps/run_compile_check.py), swept by trace-check's exposition pass,
# documented in docs/observability.md "Program observability"
REQUIRED_COMPILE_METRICS: tuple[str, ...] = (
    M_COMPILE_TOTAL,
    H_COMPILE_S,
    M_JIT_CACHE_ENTRIES,
    M_SCHED_LAUNCHES,
    H_PLAN_SOLVER_S,
    M_SOLVER_MS_SAVED,
)


# numerics observability (telemetry/numerics.py; ISSUE 18). Census
# gauges carry the last consumed in-graph value summary per guard site
# ({layer=parallel|decode, site=, stat=logit_max|lse_min|lse_max|
# out_max_abs}); the two histograms track the distribution of the
# magnitude stats that actually drift (out max-abs per census, and the
# softmax-mass deviation of the final merge — accumulated merge
# rounding). Shadow-sentinel series: checks counts every Nth-batch f32
# re-computation (MAGI_ATTENTION_SHADOW_SAMPLE_RATE), divergence is the
# max-ulp score of each check, breaches counts budget violations (0
# increments still materialize the series, record_analysis_run-style)
M_NUMERICS_CENSUS = "magi_numerics_census"  # {layer=, site=, stat=}
H_NUMERICS_OUT_MAX_ABS = "magi_numerics_out_max_abs"  # {layer=}
H_NUMERICS_MASS_DEV = "magi_numerics_mass_dev"  # {layer=}
M_NUMERICS_SHADOW_CHECKS = "magi_numerics_shadow_checks"
H_NUMERICS_SHADOW_DIVERGENCE = "magi_numerics_shadow_divergence"
M_NUMERICS_SHADOW_BREACHES = "magi_numerics_shadow_breaches"

# out max-abs in powers of two (attention outputs are O(1) convex
# combinations; a finite-corruption plant shows up in the top buckets)
_OUT_MAX_ABS_BOUNDS = (0.25, 0.5, 1.0, 2.0, 4.0, 8.0, 16.0, 64.0)
# mass deviation is ~ulp-scale rounding when healthy, O(1) when a
# partial is corrupt: log-spaced decades
_MASS_DEV_BOUNDS = (1e-7, 1e-6, 1e-5, 1e-4, 1e-3, 1e-2, 1e-1, 1.0)
# shadow divergence is scored in ulps of the production dtype: healthy
# split-merge drift sits in the low buckets, corruption at the top
_SHADOW_ULP_BOUNDS = (
    1.0, 8.0, 64.0, 512.0, 4096.0, 32768.0, 2.0**20, 2.0**30,
)

# populated by one census-mode decode + one shadow-sentinel check;
# asserted by make numerics-check (exps/run_numerics_check.py), swept
# by trace-check's exposition pass, documented in docs/observability.md
# "Numerics"
REQUIRED_NUMERICS_METRICS: tuple[str, ...] = (
    M_NUMERICS_CENSUS,
    H_NUMERICS_OUT_MAX_ABS,
    H_NUMERICS_MASS_DEV,
    M_NUMERICS_SHADOW_CHECKS,
    H_NUMERICS_SHADOW_DIVERGENCE,
    M_NUMERICS_SHADOW_BREACHES,
)

def record_numerics_census(
    layer: str, site: str, stats: dict
) -> None:
    """One consumed in-graph value census for one guard site: gauges
    for every stat, plus the out-max-abs / mass-deviation histograms
    (``site='final'`` carries only ``mass_dev``)."""
    if not _enabled():
        return
    reg = get_registry()
    for stat, val in stats.items():
        v = float(val)
        reg.gauge_set(
            M_NUMERICS_CENSUS, v, layer=layer, site=site, stat=stat
        )
        if stat == "out_max_abs":
            reg.histogram_observe(
                H_NUMERICS_OUT_MAX_ABS, v,
                bounds=_OUT_MAX_ABS_BOUNDS, layer=layer,
            )
        elif stat == "mass_dev":
            reg.histogram_observe(
                H_NUMERICS_MASS_DEV, v,
                bounds=_MASS_DEV_BOUNDS, layer=layer,
            )


def record_shadow_check(
    divergence_ulp: float, *, breached: bool
) -> None:
    """One drift-sentinel shadow re-computation: the max-ulp score of
    production vs f32 reference, and whether it breached the error
    budget (0 increments still materialize the breach series)."""
    if not _enabled():
        return
    reg = get_registry()
    reg.counter_inc(M_NUMERICS_SHADOW_CHECKS)
    reg.histogram_observe(
        H_NUMERICS_SHADOW_DIVERGENCE,
        max(float(divergence_ulp), 0.0),
        bounds=_SHADOW_ULP_BOUNDS,
    )
    reg.counter_inc(M_NUMERICS_SHADOW_BREACHES, 1 if breached else 0)


def record_analysis_run(
    states_explored: int, counterexamples: int
) -> None:
    """One interleaving-checker exploration: canonical states visited
    and counterexamples found (0 increments still materialize the
    series, so the catalog check sees a clean run)."""
    if not _enabled():
        return
    reg = get_registry()
    reg.counter_inc(M_ANALYSIS_STATES, max(int(states_explored), 0))
    reg.counter_inc(M_ANALYSIS_CEX, max(int(counterexamples), 0))


def _enabled() -> bool:
    from . import enabled

    return enabled()


def record_validate(failed: bool) -> None:
    """One plan-sanitizer outcome (``analysis/plan_sanity.py``): every
    call ticks the checks counter, failures additionally tick the
    failure counter."""
    if not _enabled():
        return
    reg = get_registry()
    reg.counter_inc(M_VALIDATE_CHECKS)
    if failed:
        reg.counter_inc(M_VALIDATE_FAILURES)


# ---------------------------------------------------------------------------
# dispatch layer
# ---------------------------------------------------------------------------


def record_dispatch_meta(meta) -> None:
    """One DispatchMeta built (``meta/dispatch_meta.py``): chunk counts and
    the token-level imbalance of the physical shard (1.0 = perfectly even;
    >1 means pad slots on the lighter ranks of an uneven shard)."""
    if not _enabled():
        return
    reg = get_registry()
    reg.counter_inc(M_DISPATCH_BUILDS)
    reg.gauge_set(M_DISPATCH_NUM_CHUNKS, meta.num_chunks)
    reg.gauge_set(M_DISPATCH_UNEVEN, int(meta.is_uneven))
    valid = meta.rank_valid_lens
    mean_valid = sum(valid) / max(len(valid), 1)
    reg.gauge_set(
        M_DISPATCH_TOKEN_IMBALANCE,
        (meta.shard_seqlen / mean_valid) if mean_valid else 1.0,
    )
    reg.clear_metric(M_DISPATCH_CHUNKS_RANK)  # cp may shrink between plans
    for r, p in enumerate(meta.partitions):
        reg.gauge_set(M_DISPATCH_CHUNKS_RANK, len(p), rank=r)


def record_dispatch_solution(
    alg: str, minimax_workload: float, bucket_workloads, solve_seconds: float
) -> None:
    """Dispatch-solver quality (``meta/solver/dispatch_solver.py``): the
    minimax objective, the achieved max/mean balance ratio, and solve
    latency."""
    if not _enabled():
        return
    reg = get_registry()
    reg.gauge_set(M_SOLVER_MINIMAX, float(minimax_workload), alg=alg)
    loads = list(bucket_workloads)
    mean = sum(loads) / max(len(loads), 1)
    reg.gauge_set(
        M_SOLVER_BALANCE,
        (max(loads) / mean) if mean else 1.0,
        alg=alg,
    )
    reg.histogram_observe(H_DISPATCH_SOLVE_S, solve_seconds, alg=alg)


def record_dynamic_solution(solver: str, balance_ratio: float) -> None:
    """qo-comm plane-partition quality (``meta/solver/dynamic_attn_solver``
    via ``parallel/qo_comm.py``)."""
    if not _enabled():
        return
    get_registry().gauge_set(
        M_DYN_SOLVER_BALANCE, float(balance_ratio), solver=solver
    )


# ---------------------------------------------------------------------------
# comm layer
# ---------------------------------------------------------------------------


def record_group_collective_build(comm) -> None:
    """One GroupCollectiveMeta routed (``comm/group_collective.py``): counts
    builds and keeps the latest true / legacy-padded / impl-scheduled row
    figures plus the scheduled-vs-true overhead ratio for the cast — the
    SPMD uniform-shape tax an uneven send map pays (VERDICT: never
    measured before ISSUE 2; exact-size hop scheduling shrinks it in
    ISSUE 5). Per-rank rows are recorded at plan level
    (:func:`record_plan`) where the *primary* comm meta is known —
    build() also runs for per-stage sub-metas."""
    if not _enabled():
        return
    reg = get_registry()
    reg.counter_inc(M_GRPCOLL_BUILDS)
    reg.gauge_set(M_COMM_PADDED_ROWS, comm.padded_rows_per_rank)
    reg.gauge_set(M_COMM_SCHEDULED_ROWS, comm.scheduled_rows_per_rank)
    reg.gauge_set(M_COMM_TRUE_ROWS, comm.true_rows_total)
    reg.gauge_set(
        M_COMM_PADDING_OVERHEAD, comm.padding_overhead_ratio, kind="cast"
    )
    reg.clear_metric(M_COMM_IMPL_CHOICE)  # one live choice at a time
    reg.gauge_set(
        M_COMM_IMPL_CHOICE, 1, impl=comm.impl, reason=comm.impl_reason
    )


def record_comm_op(comm, kind: str) -> None:
    """One group-collective op traced against a meta (``group_reduce_*_m``
    dispatchers): keeps the scheduled-vs-true overhead ratio per
    collective kind. Runs at trace time (host-side, static meta facts
    only) — once per compiled program, like the named scopes."""
    if not _enabled():
        return
    get_registry().gauge_set(
        M_COMM_PADDING_OVERHEAD, comm.padding_overhead_ratio, kind=kind
    )


# ---------------------------------------------------------------------------
# plan layer
# ---------------------------------------------------------------------------


def record_overlap_choice(degree: int, modeled_makespan_s: float) -> None:
    """Auto overlap-degree search result (``_choose_overlap_degree``)."""
    if not _enabled():
        return
    reg = get_registry()
    reg.gauge_set(M_OVERLAP_AUTO_DEGREE, degree)
    reg.gauge_set(M_OVERLAP_MAKESPAN, modeled_makespan_s)


def record_plan(plan, build_seconds: float | None = None) -> None:
    """One DistAttnPlan built (``parallel/dist_attn.py``): overlap degree,
    stage count, per-rank comm rows, mask-area balance, and the static
    kernel-grid step extents the Pallas kernels will run."""
    if not _enabled():
        return
    reg = get_registry()
    reg.counter_inc(M_PLAN_BUILDS)
    reg.gauge_set(M_PLAN_OVERLAP_DEGREE, plan.overlap_degree)
    reg.gauge_set(M_PLAN_NUM_STAGES, len(plan.stages))
    reg.gauge_set(M_PLAN_TOTAL_AREA, plan.total_area)
    reg.gauge_set(M_PLAN_MAX_RANK_AREA, plan.max_rank_area)
    reg.gauge_set(
        M_PLAN_AREA_IMBALANCE,
        plan.max_rank_area / max(plan.total_area / plan.cp_size, 1),
    )
    comm = plan.comm
    reg.clear_metric(M_COMM_SEND_ROWS)  # cp may shrink between plans
    reg.clear_metric(M_COMM_RECV_ROWS)
    for r in range(plan.cp_size):
        reg.gauge_set(M_COMM_SEND_ROWS, comm.send_total[r], rank=r)
        reg.gauge_set(M_COMM_RECV_ROWS, comm.recv_total[r], rank=r)
    fwd = bwd = 0
    for t in (
        plan.merged_tables,
        plan.host_tables,
        *(sp.tables for sp in plan.stages),
    ):
        if t is None:
            continue
        a, b = t.kernel_steps()
        fwd = max(fwd, a)
        bwd = max(bwd, b)
    reg.gauge_set(M_PLAN_KERNEL_STEPS_FWD, fwd)
    reg.gauge_set(M_PLAN_KERNEL_STEPS_BWD, bwd)
    if build_seconds is not None:
        reg.histogram_observe(H_PLAN_BUILD_S, build_seconds)


def record_runtime_costs(
    plan,
    *,
    num_heads_q: int,
    num_heads_kv: int,
    head_dim: int,
    bytes_per_elt: int,
    generation: str,
) -> None:
    """Interface-layer resolution of rows -> bytes and area -> seconds:
    per-rank comm bytes for the K+V payload, plus the ``utils/cost.py``
    modeled FLOPs / calc seconds / comm seconds the overlap solver prices
    plans with (so measured vs modeled can be compared offline)."""
    if not _enabled():
        return
    from ..utils.cost import get_calc_cost_factor, get_comm_cost_factor

    reg = get_registry()
    comm = plan.comm
    row_bytes = 2 * num_heads_kv * head_dim * bytes_per_elt  # K + V
    reg.clear_metric(M_COMM_BYTES_RANK)  # cp may shrink between plans
    for r in range(plan.cp_size):
        reg.gauge_set(
            M_COMM_BYTES_RANK, comm.recv_total[r] * row_bytes, rank=r
        )
    flops = 4.0 * plan.total_area * num_heads_q * head_dim
    reg.gauge_set(M_MODELED_FLOPS, flops)
    try:
        calc_f = get_calc_cost_factor(num_heads_q, head_dim, generation)
        comm_f = get_comm_cost_factor(
            num_heads_kv, head_dim, generation, bytes_per_elt=bytes_per_elt
        )
    except ValueError:
        # unknown generation string must never take planning down
        return
    reg.gauge_set(M_MODELED_CALC_S, plan.max_rank_area * calc_f)
    reg.gauge_set(
        M_MODELED_COMM_S, max(comm.recv_total, default=0) * comm_f
    )


def record_cache_access(hit: bool) -> None:
    """Keyed-runtime plan-LRU behavior (``api/interface.py``): one tick
    per ``magi_attn_*_key`` resolution, under both the canonical
    ``magi_plan_cache_*`` names (ISSUE 9, REQUIRED_PLAN_METRICS) and the
    legacy ``magi_runtime_cache_*`` spelling."""
    if not _enabled():
        return
    reg = get_registry()
    reg.counter_inc(M_CACHE_HITS if hit else M_CACHE_MISSES)
    reg.counter_inc(M_PLAN_CACHE_HITS if hit else M_PLAN_CACHE_MISSES)


def record_plan_cache_eviction(cache: str) -> None:
    """One entry dropped by a capacity-bound plan cache (ISSUE 20):
    ``cache`` is ``runtime`` (the exact-key LRU in ``api/interface``) or
    ``fingerprint`` (the second-level ``PlanReuseCache``)."""
    if not _enabled():
        return
    get_registry().counter_inc(M_PLAN_CACHE_EVICTIONS, cache=cache)


def record_plan_bucket(hit: bool) -> None:
    """One fingerprint-bucketed second-level lookup after an exact-key
    miss (``MAGI_ATTENTION_PLAN_REUSE=bucket`` only)."""
    if not _enabled():
        return
    get_registry().counter_inc(
        M_PLAN_BUCKET_HITS if hit else M_PLAN_BUCKET_MISSES
    )


def record_plan_incremental(patched: bool) -> None:
    """Bucket-hit row-map resolution: ``patched`` means the tail-extend
    O(delta) patch applied; otherwise the full rebuild ran (both avoid
    the solver — this decomposes hit cost, not hit rate)."""
    if not _enabled():
        return
    get_registry().counter_inc(
        M_PLAN_INCR_PATCHES if patched else M_PLAN_INCR_FALLBACKS
    )


# ---------------------------------------------------------------------------
# kernel autotuner (tuning/)
# ---------------------------------------------------------------------------


def record_autotune_cache(hit: bool, layer: str) -> None:
    """Tuning-cache behavior (``tuning/cache.py``): hits are labeled with
    the layer that answered (memory | disk)."""
    if not _enabled():
        return
    reg = get_registry()
    if hit:
        reg.counter_inc(M_AUTOTUNE_CACHE_HITS, layer=layer)
    else:
        reg.counter_inc(M_AUTOTUNE_CACHE_MISSES)


def record_autotune_measurement() -> None:
    """One on-device candidate microbenchmark completed (measure mode)."""
    if not _enabled():
        return
    get_registry().counter_inc(M_AUTOTUNE_MEASUREMENTS)


def record_autotune_measure_failure(candidate: str, error: str) -> None:
    """A measure-mode candidate crashed (disqualified, not fatal)."""
    if not _enabled():
        return
    get_registry().counter_inc(M_AUTOTUNE_MEASURE_FAILURES)
    _marker_event(
        "autotune_measure_failed",
        {"candidate": candidate, "error": error[:200]},
    )


def record_autotune_decision(decision) -> None:
    """One resolved block-config decision (``tuning/autotuner.py``): the
    chosen rung, its provenance (static table / cost model / measured /
    cache layer), and the predicted/measured cost — so every plan records
    which rung it chose and why."""
    if not _enabled():
        return
    from ..ops.flex_attn import BWD_FORM

    reg = get_registry()
    reg.gauge_set(M_AUTOTUNE_BLOCK_Q, decision.block_q)
    reg.gauge_set(M_AUTOTUNE_BLOCK_K, decision.block_k)
    reg.gauge_set(M_AUTOTUNE_HEAD_BLOCK, decision.head_block)
    reg.gauge_set(M_AUTOTUNE_PREDICTED_MS, decision.predicted_ms)
    if decision.measured_ms is not None:
        reg.gauge_set(M_AUTOTUNE_MEASURED_MS, decision.measured_ms)
    reg.clear_metric(M_AUTOTUNE_CHOICE)  # one live choice series at a time
    rung = f"{decision.block_q}x{decision.block_k}x{decision.head_block}"
    reg.gauge_set(M_AUTOTUNE_CHOICE, 1, rung=rung, source=decision.source)
    if decision.bound:  # a flex rung the cost model priced
        reg.counter_inc(
            M_AUTOTUNE_DECISIONS,
            bound=decision.bound,
            tie_order=decision.tie_order,
        )
    _marker_event(
        "autotune_decision",
        {
            "rung": rung,
            "source": decision.source,
            "cache_layer": decision.cache_layer,
            "fingerprint": decision.fingerprint_hash,
            "reason": decision.reason,
            "smem_entries": decision.smem_entries,
            "smem_count": decision.smem_count,
            "rejected_smem": decision.rejected_smem,
            "mxu_seconds": decision.mxu_seconds,
            "hbm_seconds": decision.hbm_seconds,
            "bound": decision.bound,
            "rejected_bytes": decision.rejected_bytes,
            # the preference order the ranking broke its tie by: how
            # often the long-sequence lead was used (ISSUE 54), and how
            # often the priced pair put block_q 256 first (ISSUE 56)
            "tie_order": decision.tie_order,
            # the form of the backward the rung's kernels run: one value
            # since PR 43, kept so that a later form reads against it
            "bwd_form": BWD_FORM,
        },
    )


def record_flex_kernel_build(
    kernel: str, heads_per_step: int, grid: str, **form: str
) -> None:
    """One flex ``pallas_call`` built (``ops/flex_attn._flex_pallas_call``,
    while jax traces the caller — never inside a compiled step). ``form``:
    the forward's ``stats=compact|lanes``, the backward's ``delta=xla`` and
    ``dq=visits|zero_filled``."""
    if not _enabled():
        return
    get_registry().counter_inc(
        M_FLEX_KERNEL_BUILDS,
        kernel=kernel,
        heads_per_step=heads_per_step,
        grid=grid,
        **form,
    )


def record_flex_bwd_form(form: str) -> None:
    """One plan's backward form, where ``make_attn_params`` gives the plan
    its kernels' parameters."""
    if not _enabled():
        return
    get_registry().counter_inc(M_FLEX_BWD_FORM, form=form)


def record_flex_dead_step_share(pct: float) -> None:
    """Share of the steps that do no work, on the grid
    ``parallel/dist_attn.make_attn_params`` chose for a plan's kernels."""
    if not _enabled():
        return
    get_registry().gauge_set(M_FLEX_DEAD_STEP_SHARE, pct)


def record_flex_stepped_tile_share(pct: float) -> None:
    """Share of the live steps whose tile a stepped bound crosses, of the
    plan ``parallel/dist_attn.make_attn_params`` was handed."""
    if not _enabled():
        return
    get_registry().gauge_set(M_FLEX_STEPPED_TILE_SHARE, pct)


def record_mask_step(kind: str, step: int) -> None:
    """The largest step among a model's mask slices of attention
    ``kind`` (host side, at plan time)."""
    if not _enabled():
        return
    get_registry().gauge_set(M_MASK_STEP, float(step), kind=kind)


def record_model_attn_plan(kind: str) -> None:
    """One attention plan built for a model's attention ``kind``
    (``models/_common._plan_on_dispatch``, host side)."""
    if not _enabled():
        return
    get_registry().counter_inc(M_MODEL_ATTN_PLANS, kind=kind)


def record_flex_forward_kept(kind: str) -> None:
    """One attention call of kind ``kind`` differentiated in the kept form
    (``ops/flex_attn._flex_attn_core_fwd``, while jax traces the gradient:
    never inside a compiled step)."""
    if not _enabled():
        return
    get_registry().counter_inc(M_FLEX_FORWARD_KEPT, kind=kind)


def record_moe_rows_permuted(end: str) -> None:
    """One expert layer differentiated with its rows moved by the sort's
    permutation at ``end`` (``combine`` / ``dispatch``;
    ``models/pattern.held_expert_ffn`` where every chunk runs, while jax
    traces the rule: never inside a compiled step)."""
    if not _enabled():
        return
    get_registry().counter_inc(M_MOE_ROWS_PERMUTED, end=end)


def record_moe_route_ahead(layers: int) -> None:
    """The expert layers of a pattern decoder whose route is made before
    the layer's attention call (``models/pattern.build_magi_pattern``,
    host side)."""
    if not _enabled():
        return
    get_registry().gauge_set(M_MOE_ROUTE_AHEAD_LAYERS, float(layers))


def record_moe_load(layer: int, counts) -> None:
    """Pairs per held expert of one expert layer in one step (host
    values, read outside any timed window)."""
    if not _enabled():
        return
    counts = [float(c) for c in counts]
    pairs = sum(counts)
    reg = get_registry()
    reg.gauge_set(M_MOE_PAIRS_HERE, pairs, layer=layer)
    reg.gauge_set(
        M_MOE_LOAD_MAX_OVER_MEAN,
        max(counts) * len(counts) / pairs if pairs else 0.0,
        layer=layer,
    )


def record_model_loop(n_loops: int, n_layers: int) -> None:
    """A pattern decoder's passes and layer applications a forward
    (``models/pattern.build_magi_pattern``, host side)."""
    if not _enabled():
        return
    reg = get_registry()
    reg.gauge_set(M_MODEL_LOOP_STEPS, float(n_loops))
    reg.gauge_set(M_MODEL_LAYER_APPLICATIONS, float(n_loops * n_layers))


def record_shift(*, rows: int, taps, documents: int) -> None:
    """One planned forward shift (``parallel/dispatch.make_shift_plan``,
    host side): the rows one application brings from another rank."""
    if not _enabled():
        return
    get_registry().counter_inc(M_SHIFT_REMOTE_ROWS, rows)
    _marker_event(
        "shift", {"rows": rows, "taps": list(taps), "documents": documents}
    )


def record_ssm_scan(phase: str, *, chunks: int, state_bytes: int) -> None:
    """One selective scan traced (``ops/selective_scan.py``, trace time:
    once a compiled program, like the named scopes)."""
    if not _enabled():
        return
    reg = get_registry()
    reg.counter_inc(M_SSM_SCAN_CALLS, 1, phase=phase)
    reg.gauge_set(M_SSM_CHUNKS, float(chunks))
    reg.gauge_set(M_SSM_STATE_BYTES, float(state_bytes))


def record_ssd_scan(phase: str, *, heads: int, chunks: int,
                    state_bytes: int) -> None:
    """One state-space-dual scan traced (``ops/ssd_scan.py``, trace time:
    once a compiled program, like the named scopes)."""
    if not _enabled():
        return
    reg = get_registry()
    reg.counter_inc(M_SSD_SCAN_CALLS, 1, phase=phase)
    reg.gauge_set(M_SSD_HEADS, float(heads))
    reg.gauge_set(M_SSD_CHUNKS, float(chunks))
    reg.gauge_set(M_SSD_STATE_BYTES, float(state_bytes))


def record_ssd_model(*, documents: int, reset_chunks: int,
                     multipliers: dict[str, float]) -> None:
    """A pattern decoder with state-space-dual layers
    (``models/pattern.build_magi_pattern``, host side): the documents a
    scan resets at, the scan chunks with a start strictly inside, and the
    model's four scalars."""
    if not _enabled():
        return
    reg = get_registry()
    reg.gauge_set(M_SSM_DOCUMENTS, float(documents))
    reg.gauge_set(M_SSD_RESET_CHUNKS, float(reset_chunks))
    for name, value in multipliers.items():
        # the label is ``multiplier``: ``name`` is the registry's own word
        reg.gauge_set(M_MODEL_MULTIPLIERS, float(value), multiplier=name)


def record_handed_on(
    *, documents: int | None, kv_readers: int, recast_rows: int,
    pad_lane_share: float,
) -> None:
    """What a pattern decoder's layers hand on
    (``models/pattern.build_magi_pattern``, host side): the documents a
    scan resets at (None: no state-space layer), the readers of the
    shared keys and values and the rows their casts carry again, the
    padded share of the kernels' q and k lanes."""
    if not _enabled():
        return
    reg = get_registry()
    if documents is not None:
        reg.gauge_set(M_SSM_DOCUMENTS, float(documents))
    reg.gauge_set(M_SHARED_KV_READERS, float(kv_readers))
    reg.counter_inc(M_SHARED_KV_RECAST_ROWS, recast_rows)
    reg.gauge_set(M_FLEX_PAD_LANE_SHARE, float(pad_lane_share))


def record_mhc(*, streams: int, sinkhorn_iters: int, stream_bytes: int,
               pad_lane_share: float) -> None:
    """A pattern decoder's residual streams
    (``models/pattern.build_magi_pattern``, host side), and the padded
    share of the lanes its latent heads' q and k ride into the kernels on."""
    if not _enabled():
        return
    reg = get_registry()
    reg.gauge_set(M_MHC_STREAMS, float(streams))
    reg.gauge_set(M_MHC_SINKHORN_ITERS, float(sinkhorn_iters))
    reg.gauge_set(M_MHC_STREAM_BYTES, float(stream_bytes))
    reg.gauge_set(M_FLEX_PAD_LANE_SHARE, float(pad_lane_share))


def record_mhc_coef(form: str) -> None:
    """One half-layer's coefficients traced (``models/pattern._mhc_coef``,
    trace time: once a compiled program, like the named scopes)."""
    if not _enabled():
        return
    get_registry().counter_inc(M_MHC_COEF_HALVES, form=form)


def record_mla_kv_cast_width(*, expanded: int, latent: int) -> None:
    """The two widths a latent-attention model's key-value cast could
    carry a token (``models/pattern.build_magi_pattern``, host side)."""
    if not _enabled():
        return
    reg = get_registry()
    reg.gauge_set(M_MLA_KV_CAST_WIDTH, float(expanded), form="expanded")
    reg.gauge_set(M_MLA_KV_CAST_WIDTH, float(latent), form="latent")


# ---------------------------------------------------------------------------
# resilience layer (resilience/ + its call sites)
# ---------------------------------------------------------------------------


def record_guard_check(site: str) -> None:
    """One numerical guard traced at ``site`` (``resilience/guards.py``):
    runs at trace time — once per compiled program, like the named
    scopes and :func:`record_comm_op`."""
    if not _enabled():
        return
    get_registry().counter_inc(M_GUARD_CHECKS, site=site)


def record_guard_violation(site: str) -> None:
    """A check-mode guard's error code decoded nonzero at the jit
    boundary — a non-finite partial reached ``site``. Alarm on this."""
    if not _enabled():
        return
    get_registry().counter_inc(M_GUARD_VIOLATIONS, site=site)


def record_guard_repair(site: str) -> None:
    """A repair-mode guard quarantined a poisoned partial at ``site``
    (the merge proceeded with that contribution weighted to zero)."""
    if not _enabled():
        return
    get_registry().counter_inc(M_GUARD_REPAIRS, site=site)


def record_admission(result) -> None:
    """One ``ServingEngine.admit`` outcome (``AdmissionResult``):
    rejections count by reason, evictions by the retry policy count
    regardless of the final verdict."""
    if not _enabled():
        return
    reg = get_registry()
    if result.evicted:
        reg.counter_inc(M_ADMISSION_EVICTIONS, len(result.evicted))
    if not result.admitted:
        reg.counter_inc(M_ADMISSION_REJECTED, reason=result.reason)


def record_degraded_path(reason: str) -> None:
    """A degradation path engaged (plan-build -> dense degree-0 plan,
    hops build -> a2a impl): gauge value 1 labeled with the reason, plus
    a marker event so traces show WHEN it happened. Also arms/writes a
    flight-recorder dump (outside the telemetry gate — the recorder is
    always-on) and, when a request context is live, a ``degraded`` span
    on that request's trace."""
    from .trace import SPAN_DEGRADED, get_flight_recorder, span_for_current

    get_flight_recorder().trigger("degraded_path", reason=reason)
    if not _enabled():
        return
    span_for_current(SPAN_DEGRADED, reason=reason)
    get_registry().gauge_set(M_DEGRADED_PATH, 1, reason=reason)
    _marker_event("degraded_path", {"reason": reason})


def record_tuning_cache_io_error(op: str) -> None:
    """A tuning-cache disk load/store failed (``tuning/cache.py``): the
    failure is still non-fatal (a miss / skipped persist), but no longer
    invisible."""
    if not _enabled():
        return
    get_registry().counter_inc(M_TUNING_CACHE_IO, op=op)


# ---------------------------------------------------------------------------
# serving subsystem (serving/)
# ---------------------------------------------------------------------------


def record_decode_step(
    *,
    batch_size: int,
    num_splits: int,
    max_seq_len: int,
    cascade_groups: int = 0,
) -> None:
    """One continuous-batching decode step (``serving/engine.py``):
    counts steps/tokens and keeps the latest batch geometry — the
    resolved split count is what the flat split-KV kernel ran
    (``num_splits = 0`` means the step ran cascade attention, which
    resolves splits per phase; ``cascade_groups`` is then the
    shared-prefix group count)."""
    if not _enabled():
        return
    reg = get_registry()
    reg.counter_inc(M_DECODE_STEPS)
    reg.counter_inc(M_DECODE_TOKENS, batch_size)
    reg.gauge_set(M_DECODE_BATCH, int(batch_size))
    reg.gauge_set(M_DECODE_SPLITS, int(num_splits))
    reg.gauge_set(M_DECODE_MAX_SEQ_LEN, int(max_seq_len))
    reg.gauge_set(M_DECODE_CASCADE_GROUPS, int(cascade_groups))


def record_prefill(num_tokens: int) -> None:
    """One prefill written into the paged cache (``prefill_into_cache``
    via the engine)."""
    if not _enabled():
        return
    get_registry().counter_inc(M_PREFILL_TOKENS, int(num_tokens))


def record_kvcache_state(occupancy: dict) -> None:
    """Page-pool occupancy after an admission/growth/free event
    (``serving/kv_cache.PageAllocator.occupancy`` payload)."""
    if not _enabled():
        return
    reg = get_registry()
    reg.gauge_set(M_KVCACHE_PAGES_TOTAL, int(occupancy["pages_total"]))
    reg.gauge_set(M_KVCACHE_PAGES_USED, int(occupancy["pages_in_use"]))
    reg.gauge_set(M_KVCACHE_OCCUPANCY, float(occupancy["occupancy_ratio"]))
    reg.gauge_set(M_KVCACHE_ACTIVE_SEQS, int(occupancy["active_seqs"]))
    reg.gauge_set(M_KVCACHE_PAGE_SIZE, int(occupancy["page_size"]))
    reg.gauge_set(M_KVCACHE_SHARED, int(occupancy.get("shared_pages", 0)))
    # magi_kvcache_free_pages is deliberately NOT set here: every
    # engine's _record_pool runs this collector, and on a TieredEngine
    # the decode replicas would overwrite the admission-facing prefill
    # pool's figure — the one the headroom gauge pairs with. The
    # scheduler's per-tick record_admission_watermark is the single
    # source (it reads the admission-facing allocator).


# ---------------------------------------------------------------------------
# memory observability (telemetry/memory.py; ISSUE 14)
# ---------------------------------------------------------------------------


def record_memory_ledger(ledger) -> None:
    """One static memory-ledger pricing (``telemetry/memory.py``
    :class:`MemoryLedger`): per-phase predicted bytes plus the total,
    labeled with the ledger name so plan/serving/tier ledgers keep
    separate series. Overwrite semantics per (ledger, phase): a
    re-priced configuration with FEWER phases should use a fresh name
    (how the checks do) rather than rely on stale-phase clearing."""
    if not _enabled():
        return
    reg = get_registry()
    for phase, b in ledger.by_phase().items():
        reg.gauge_set(M_MEM_PREDICTED, int(b), ledger=ledger.name,
                      phase=phase)
    reg.gauge_set(M_MEM_PREDICTED, int(ledger.total()),
                  ledger=ledger.name, phase="total")


def record_memory_measurement(program: str, measured: dict) -> None:
    """One XLA compiled-executable memory analysis
    (``measure_program_memory`` payload): argument/output/temp/alias
    bytes of a jitted program."""
    if not _enabled():
        return
    reg = get_registry()
    for kind in ("argument", "output", "temp", "alias"):
        v = measured.get(f"{kind}_bytes")
        if v is not None:
            reg.gauge_set(M_MEM_MEASURED, int(v), program=program,
                          kind=kind)


def record_memory_comparison(cmp) -> None:
    """One predicted-vs-measured verdict
    (``telemetry/memory.MemoryComparison``): the gated io delta ratio
    and the honest unattributed temp residual."""
    if not _enabled():
        return
    reg = get_registry()
    reg.gauge_set(M_MEM_DELTA, float(cmp.delta_ratio), program=cmp.program)
    reg.gauge_set(
        M_MEM_UNATTRIBUTED, int(cmp.unattributed_bytes),
        program=cmp.program,
    )
    _marker_event(
        "memory_probe",
        {
            "program": cmp.program,
            "predicted_io_bytes": cmp.predicted_io_bytes,
            "measured_io_bytes": cmp.measured_io_bytes,
            "delta_ratio": cmp.delta_ratio,
            "unattributed_bytes": cmp.unattributed_bytes,
        },
    )


def record_memory_pool(fmap) -> None:
    """One pool-forensics snapshot (``telemetry/memory.
    PoolFragmentationMap``): fragmentation ratio, longest free run,
    per-state page counts, lifetime peak."""
    if not _enabled():
        return
    reg = get_registry()
    p = fmap.pool
    reg.gauge_set(M_MEM_POOL_FRAG, float(fmap.fragmentation_ratio), pool=p)
    reg.gauge_set(M_MEM_POOL_FREE_RUN, int(fmap.free_run_max), pool=p)
    reg.gauge_set(M_MEM_POOL_PEAK, int(fmap.peak_pages), pool=p)
    for state, count in fmap.state_counts().items():
        reg.gauge_set(M_MEM_POOL_PAGES, int(count), pool=p, state=state)


def record_hbm_sample(samples: dict) -> None:
    """One device memory_stats sample (``telemetry/memory.
    sample_memory_stats``): bytes_in_use per device plus the running
    process-wide peak. Empty samples (CPU) record nothing."""
    if not _enabled() or not samples:
        return
    reg = get_registry()
    peak = 0
    for dev, b in samples.items():
        reg.gauge_set(M_MEM_HBM_IN_USE, int(b), device=str(dev))
        peak = max(peak, int(b))
    prev = reg.gauge_value(M_MEM_HBM_PEAK, default=0)
    reg.gauge_set(M_MEM_HBM_PEAK, max(int(prev or 0), peak))


def record_admission_watermark(headroom: int, free_pages: int) -> None:
    """The scheduler's per-tick admission watermark (ISSUE 13's rule,
    observable since ISSUE 14): pages an evictionless admission must
    leave free for decode growth, next to the pool's actual free
    pages — ``free - headroom`` trending to 0 is backpressure arriving."""
    if not _enabled():
        return
    reg = get_registry()
    reg.gauge_set(M_SCHED_HEADROOM, int(headroom))
    reg.gauge_set(M_KVCACHE_FREE, int(free_pages))


# ---------------------------------------------------------------------------
# shared-prefix cache + scheduler (serving/prefix.py, serving/scheduler.py)
# ---------------------------------------------------------------------------


def record_prefix_lookup(*, hit: bool, matched_tokens: int = 0) -> None:
    """One token-carrying admission consulted the prefix trie
    (``ServingEngine.admit``); on a hit, ``matched_tokens`` prompt
    tokens were installed by reference instead of prefilled."""
    if not _enabled():
        return
    reg = get_registry()
    reg.counter_inc(M_PREFIX_HITS if hit else M_PREFIX_MISSES)
    if matched_tokens:
        reg.counter_inc(M_PREFIX_MATCHED_TOKENS, int(matched_tokens))


def record_prefix_registered(newly_pinned: int, resident_pages: int) -> None:
    """One prompt registered as shareable (``ServingEngine.commit_prefix``):
    counts the pages newly pinned by the trie and refreshes the resident
    gauge (registered - evicted = resident, reconcilable offline)."""
    if not _enabled():
        return
    reg = get_registry()
    if newly_pinned:
        reg.counter_inc(M_PREFIX_REGISTERED, int(newly_pinned))
    reg.gauge_set(M_PREFIX_RESIDENT, int(resident_pages))


def record_prefix_cow() -> None:
    """One copy-on-write page split: a sequence needed to write into a
    still-shared tail page and got its private copy. When a request
    context is live (the scheduler wraps engine calls), the split also
    lands as a ``cow`` span on that request's trace."""
    if not _enabled():
        return
    from .trace import SPAN_COW, span_for_current

    span_for_current(SPAN_COW)
    get_registry().counter_inc(M_PREFIX_COW)


def record_prefix_eviction(pages_freed: int, resident_pages: int) -> None:
    """Pool pressure dropped LRU unreferenced prefix pages
    (``PrefixCache.evict`` via admission)."""
    if not _enabled():
        return
    reg = get_registry()
    reg.counter_inc(M_PREFIX_EVICTED, int(pages_freed))
    reg.gauge_set(M_PREFIX_RESIDENT, int(resident_pages))


def record_sched_step(
    *,
    waiting: int,
    active: int,
    tokens_used: int,
    prefill_chunks: int,
    decode_ran: bool,
    budget_utilization: float | None = None,
    queue_depth: int | None = None,
) -> None:
    """One ``Scheduler.step`` tick: queue depths and what the token
    budget actually bought (chunks started, decode step or not), plus
    the tick's budget utilization and start-of-tick queue depth (ISSUE
    11 satellite — saturation without trace replay)."""
    if not _enabled():
        return
    reg = get_registry()
    reg.counter_inc(M_SCHED_STEPS)
    if prefill_chunks:
        reg.counter_inc(M_SCHED_PREFILL_CHUNKS, int(prefill_chunks))
    if decode_ran:
        reg.counter_inc(M_SCHED_DECODE_STEPS)
    reg.gauge_set(M_SCHED_WAITING, int(waiting))
    reg.gauge_set(M_SCHED_ACTIVE, int(active))
    reg.gauge_set(M_SCHED_STEP_TOKENS, int(tokens_used))
    if budget_utilization is not None:
        reg.gauge_set(M_SCHED_BUDGET_UTIL, float(budget_utilization))
    if queue_depth is not None:
        reg.gauge_set(M_SCHED_QUEUE_DEPTH, int(queue_depth))


def record_compile(
    program: str, seconds: float, total_programs: int
) -> None:
    """One finished XLA backend compile, attributed to its program
    label (``telemetry/compile.py`` ingestion — the tracker's own
    accumulators are always-on; only this registry mirror is gated).
    ``total_programs`` is the process-cumulative executable count, the
    jit-cache-entries gauge (XLA rarely evicts, so cumulative builds
    lower-bound the live cache)."""
    if not _enabled():
        return
    reg = get_registry()
    reg.counter_inc(M_COMPILE_TOTAL, program=program)
    reg.histogram_observe(H_COMPILE_S, float(seconds))
    reg.gauge_set(M_JIT_CACHE_ENTRIES, int(total_programs))


def record_compile_cache(result: str) -> None:
    """One program looked up in jax's persistent compilation cache:
    ``result`` is ``hit`` (loaded) or ``miss`` (compiled, then written)."""
    if not _enabled():
        return
    get_registry().counter_inc(M_COMPILE_CACHE_TOTAL, result=result)


def record_plan_solver(seconds: float, *, cache_hit: bool) -> None:
    """One host-solver resolution: a plan-LRU lookup that hit
    (``api/interface.py``) or a cold ``build_dist_attn_plan``
    (``parallel/dist_attn.py``, the miss path's dominant cost).

    ALWAYS feeds the compile tracker's solver accumulator (plain module
    state outside the registry — the scheduler's per-tick cost
    attribution must work with telemetry off; the disabled-mode no-op
    contract covers the registry only). With telemetry on, the seconds
    land on ``magi_plan_solver_seconds{outcome=}`` and each hit credits
    ``magi_plan_solver_ms_saved_total`` with the mean measured
    cold-build latency — the figure ROADMAP item 3's plan-reuse gate
    reads."""
    from . import compile as _compile

    _compile.add_solver_seconds(float(seconds))
    if not cache_hit:
        _compile.get_compile_tracker().note_plan_build(float(seconds))
    if not _enabled():
        return
    reg = get_registry()
    reg.histogram_observe(
        H_PLAN_SOLVER_S,
        float(seconds),
        outcome="hit" if cache_hit else "miss",
    )
    if cache_hit:
        mean_s = _compile.get_compile_tracker().plan_build_mean_s()
        if mean_s:
            reg.counter_inc(M_SOLVER_MS_SAVED, mean_s * 1e3)


def record_tick_programs(
    *,
    step: int,
    start_s: float,
    wall_s: float,
    programs: list,
    compiles: int,
    solver_s: float,
    compile_s: float,
    device_s: float,
    residual_s: float,
) -> None:
    """One scheduler tick's launch ledger + cost decomposition (ISSUE
    16): the distinct-program launch count lands on the
    ``magi_sched_launches_per_tick`` histogram, and the full
    decomposition — geometry census (label -> launches), compile count,
    solver/compile/device ms and the HONEST unattributed residual
    (negative when attribution over-counts; surfaced, never folded into
    a gate) — rides a span on the dedicated tick-decomposition
    Chrome-trace track."""
    if not _enabled():
        return
    from .events import record_event

    census: dict[str, int] = {}
    for p in programs:
        census[p] = census.get(p, 0) + 1
    reg = get_registry()
    reg.histogram_observe(
        M_SCHED_LAUNCHES,
        float(len(census)),
        bounds=(0.0, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0),
    )
    record_event(
        "sched_tick",
        start_s,
        wall_s,
        {
            "step": int(step),
            "launches": len(census),
            "programs": census,
            "compiles": int(compiles),
            "solver_ms": round(solver_s * 1e3, 3),
            "compile_ms": round(compile_s * 1e3, 3),
            "device_ms": round(device_s * 1e3, 3),
            "residual_ms": round(residual_s * 1e3, 3),
            "wall_ms": round(wall_s * 1e3, 3),
        },
        track=TICK_TRACK,
    )


def record_request_traced() -> None:
    """One request entered the traced lifecycle (``trace.span_submit``)."""
    if not _enabled():
        return
    get_registry().counter_inc(M_REQ_TRACES)


def record_flight_dump(trigger: str) -> None:
    """One flight-recorder post-mortem dump was written ({trigger=})."""
    if not _enabled():
        return
    get_registry().counter_inc(M_FLIGHT_DUMPS, trigger=trigger)
    _marker_event("flight_recorder_dump", {"trigger": trigger})


def _slo_observe(name: str, seconds: float, tier: str | None) -> None:
    """``tier=`` threading for the SLO histograms (ISSUE 12): every
    sample lands on the unlabeled historical series — the fleet-wide
    aggregate existing dashboards and the trace-check reconciliation
    scrape, which must not go blank when a deployment switches to
    tiered serving — and a tiered sample ADDITIONALLY lands on a
    ``tier=``-labeled series so each tier's p99 is scrapeable on its
    own."""
    reg = get_registry()
    reg.histogram_observe(name, seconds)
    if tier is not None:
        reg.histogram_observe(name, seconds, tier=tier)


def record_request_queue_time(seconds: float, *, tier: str | None = None) -> None:
    """Submission -> admission wait of one request (SLO surface)."""
    if not _enabled():
        return
    _slo_observe(H_REQ_QUEUE_S, float(seconds), tier)


def record_request_ttft(seconds: float, *, tier: str | None = None) -> None:
    """Submission -> first decoded token of one request (SLO surface)."""
    if not _enabled():
        return
    _slo_observe(H_REQ_TTFT_S, float(seconds), tier)


def record_request_token_latency(
    seconds: float, *, tier: str | None = None
) -> None:
    """Inter-token decode latency of one generated token (SLO surface)."""
    if not _enabled():
        return
    _slo_observe(H_REQ_TOKLAT_S, float(seconds), tier)


# ---------------------------------------------------------------------------
# disaggregated serving (serving/distributed.py; ISSUE 12)
# ---------------------------------------------------------------------------


def record_page_stream(
    *, pages: int, nbytes: int, queue_depth: int
) -> None:
    """One committed prompt's pages streamed prefill -> decode tier
    (``PageTransferQueue.pump``): the wire traffic of the
    disaggregation hand-off, plus the post-pump queue depth."""
    if not _enabled():
        return
    reg = get_registry()
    reg.counter_inc(M_PAGE_STREAMS)
    reg.counter_inc(M_STREAM_PAGES, int(pages))
    reg.counter_inc(M_STREAM_BYTES, int(nbytes))
    reg.gauge_set(M_STREAM_QUEUE, int(queue_depth))


def record_stream_queue_depth(depth: int) -> None:
    """Streams parked waiting for decode-tier capacity (a stream that
    could not place this tick). Sustained nonzero = decode tier is the
    fleet bottleneck — admission backpressure follows."""
    if not _enabled():
        return
    get_registry().gauge_set(M_STREAM_QUEUE, int(depth))


def record_tier_fault(tier: str, replica: int) -> None:
    """One tier chip/replica failed (chaos-injected or organic) and was
    absorbed by the requeue+replay path."""
    if not _enabled():
        return
    get_registry().counter_inc(M_TIER_FAULTS, tier=tier, replica=replica)


def record_tier_state(
    tier: str, *, pages_in_use: int, active: int, replica: int | None = None
) -> None:
    """One tier member's pool occupancy + live-request count (after an
    admission / stream / free)."""
    if not _enabled():
        return
    reg = get_registry()
    labels = {"tier": tier}
    if replica is not None:
        labels["replica"] = replica
    reg.gauge_set(M_TIER_PAGES_USED, int(pages_in_use), **labels)
    reg.gauge_set(M_TIER_ACTIVE, int(active), tier=tier)


# ---------------------------------------------------------------------------
# summaries
# ---------------------------------------------------------------------------


def telemetry_summary(snapshot: dict | None = None) -> str:
    """Human-readable block of the headline plan/comm metrics. Works on
    any snapshot dict (defaults to the live registry's, and then ends
    with how this process started, from its span ring)."""
    live = snapshot is None
    if live:
        snapshot = get_registry().snapshot()
    g = snapshot.get("gauges", {})
    c = snapshot.get("counters", {})

    def fmt(v):
        if v is None:
            return "-"
        if isinstance(v, float):
            return f"{v:.4g}"
        return str(v)

    def series(prefix):
        vals = {
            k: v for k, v in g.items() if k.startswith(prefix + "{")
        }
        # (len, str) orders rank=2 before rank=10 without parsing labels
        return [v for _, v in sorted(vals.items(), key=lambda kv: (len(kv[0]), kv[0]))]

    lines = [
        "telemetry summary:",
        f"  plans built: {fmt(c.get(M_PLAN_BUILDS, 0))}  "
        f"dispatch metas: {fmt(c.get(M_DISPATCH_BUILDS, 0))}  "
        f"cache hits/misses: {fmt(c.get(M_CACHE_HITS, 0))}/"
        f"{fmt(c.get(M_CACHE_MISSES, 0))}",
        f"  overlap degree: {fmt(g.get(M_PLAN_OVERLAP_DEGREE))}  "
        f"stages: {fmt(g.get(M_PLAN_NUM_STAGES))}  "
        f"kernel steps fwd/bwd: {fmt(g.get(M_PLAN_KERNEL_STEPS_FWD))}/"
        f"{fmt(g.get(M_PLAN_KERNEL_STEPS_BWD))}",
        f"  area imbalance: {fmt(g.get(M_PLAN_AREA_IMBALANCE))}  "
        f"token imbalance: {fmt(g.get(M_DISPATCH_TOKEN_IMBALANCE))}",
        f"  comm recv rows/rank: {[int(v) for v in series(M_COMM_RECV_ROWS)]}",
        f"  comm bytes/rank: {[int(v) for v in series(M_COMM_BYTES_RANK)]}",
    ]
    impl_choice = [k for k in g if k.startswith(M_COMM_IMPL_CHOICE + "{")]
    if impl_choice or g.get(M_COMM_SCHEDULED_ROWS) is not None:
        lines.append(
            f"  comm impl: {impl_choice[0][len(M_COMM_IMPL_CHOICE):] if impl_choice else '-'}  "
            f"scheduled rows/rank {fmt(g.get(M_COMM_SCHEDULED_ROWS))} "
            f"(legacy padded {fmt(g.get(M_COMM_PADDED_ROWS))})  "
            f"true rows total {fmt(g.get(M_COMM_TRUE_ROWS))}"
        )
    lines += [
        f"  modeled flops: {fmt(g.get(M_MODELED_FLOPS))}  "
        f"calc s: {fmt(g.get(M_MODELED_CALC_S))}  "
        f"comm s: {fmt(g.get(M_MODELED_COMM_S))}",
    ]
    choice = [
        k for k in g if k.startswith(M_AUTOTUNE_CHOICE + "{")
    ]
    if choice:
        hits = sum(
            v for k, v in c.items()
            if k.startswith(M_AUTOTUNE_CACHE_HITS)
        )
        lines.append(
            f"  autotune: {choice[0][len(M_AUTOTUNE_CHOICE):]} "
            f"predicted {fmt(g.get(M_AUTOTUNE_PREDICTED_MS))} ms  "
            f"cache hits/misses: {fmt(hits)}/"
            f"{fmt(c.get(M_AUTOTUNE_CACHE_MISSES, 0))}"
        )
    if c.get(M_DECODE_STEPS):
        lines.append(
            f"  decode: steps {fmt(c.get(M_DECODE_STEPS))}  "
            f"tokens {fmt(c.get(M_DECODE_TOKENS))}  "
            f"batch {fmt(g.get(M_DECODE_BATCH))}  "
            f"splits {fmt(g.get(M_DECODE_SPLITS))}  "
            f"max len {fmt(g.get(M_DECODE_MAX_SEQ_LEN))}"
        )
    if g.get(M_KVCACHE_PAGES_TOTAL) is not None:
        lines.append(
            f"  kv cache: {fmt(g.get(M_KVCACHE_PAGES_USED))}/"
            f"{fmt(g.get(M_KVCACHE_PAGES_TOTAL))} pages "
            f"({fmt(g.get(M_KVCACHE_OCCUPANCY))} occupancy)  "
            f"active seqs {fmt(g.get(M_KVCACHE_ACTIVE_SEQS))}  "
            f"page size {fmt(g.get(M_KVCACHE_PAGE_SIZE))}  "
            f"prefill tokens {fmt(c.get(M_PREFILL_TOKENS, 0))}"
        )
    # program observability (ISSUE 16): compiles by label + the plan
    # solver's saved-ms credit, when any compile was attributed
    compile_keys = [
        k for k in c if k.startswith(M_COMPILE_TOTAL + "{")
    ]
    if compile_keys:
        total_compiles = sum(c[k] for k in compile_keys)
        lines.append(
            f"  programs: {len(compile_keys)} labels, "
            f"{fmt(total_compiles)} compiles  "
            f"jit cache entries {fmt(g.get(M_JIT_CACHE_ENTRIES))}  "
            f"solver ms saved "
            f"{fmt(c.get(M_SOLVER_MS_SAVED, 0))}"
        )
    # one line per compared program: predicted-vs-measured io bytes +
    # the honest unattributed temp residual (ISSUE 14)
    from .registry import series_key

    for key in sorted(k for k in g if k.startswith(M_MEM_DELTA + "{")):
        labels = key[len(M_MEM_DELTA):]
        prog = labels[len("{program="):-1]
        pred = g.get(series_key(
            M_MEM_PREDICTED, {"ledger": prog, "phase": "total"}
        ))
        lines.append(
            f"  memory probe{labels}: predicted {fmt(pred)} B, "
            f"io delta {fmt(g.get(key))}, unattributed "
            f"{fmt(g.get(M_MEM_UNATTRIBUTED + labels))} B temp"
        )
    # how this process started (ISSUE 51): the ring's two start-up spans,
    # the only line here that no registry series stands behind, so a
    # snapshot handed in (merged, or another process's) does not get it
    from .events import get_event_buffer

    boot = {
        ev["name"]: ev for ev in get_event_buffer().events()
        if live and ev["name"] in ("process_boot", "package_import")
    }
    if len(boot) == 2:
        args = boot["process_boot"]["args"]
        lines.append(
            f"  start-up: process boot "
            f"{fmt(boot['process_boot']['dur'] / 1e6)} s "
            f"(clock {args['source']}; jax imported before the package: "
            f"{args['jax_imported_before']}, backend up: "
            f"{args['backend_ready_before']})  package import "
            f"{fmt(boot['package_import']['dur'] / 1e6)} s (jax "
            f"{fmt(boot['package_import']['args']['jax_import_s'])} s)"
        )
    return "\n".join(lines)
