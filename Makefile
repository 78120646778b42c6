# Developer entry points (role of reference makefile:36-46).
#
# Everything runs on the 8-device virtual CPU mesh (tests/conftest.py
# forces the platform); no TPU needed for any target here.

PY ?= python

.PHONY: install test test-fast test-slow lint typecheck telemetry-check autotune-check serving-check sched-check comm-check analyze spmd-audit lifecycle-check resilience-check trace-check distserve-check memory-check compile-check tick-check numerics-check plan-reuse-check check

install:
	$(PY) -m pip install -e . --no-build-isolation

# fast subset: host-side planning/solver/common layers (seconds-minutes)
test-fast:
	$(PY) -m pytest tests/test_common tests/test_meta tests/test_api/test_window_masks.py -q

# default tier: slow-marked heavyweights auto-skip via conftest (and
# MAGI_RUN_SLOW=1 re-enables them); measured tier times in docs/testing.md
test:
	$(PY) -m pytest tests -q

# full tier: default + the slow-marked heavyweights (redundant-coverage
# oracle-exactness params, full-size 10k-15k-token scenarios)
test-slow:
	$(PY) -m pytest tests -q --run-slow

lint:
	@if $(PY) -m ruff --version >/dev/null 2>&1; then \
		$(PY) -m ruff check magiattention_tpu tests exps examples; \
	else \
		echo "ruff not installed; syntax-checking via compileall"; \
		$(PY) -m compileall -q magiattention_tpu tests exps examples chip_smoke.py __graft_entry__.py; \
	fi

typecheck:
	@if $(PY) -m mypy --version >/dev/null 2>&1; then \
		$(PY) -m mypy; \
	else \
		echo "mypy not installed; skipping (pip install -e .[dev])"; \
	fi

# telemetry drift guard: build a tiny CPU-backend plan with telemetry on
# and assert the snapshot carries every metric docs/observability.md
# documents (exps/run_telemetry_check.py exits non-zero on drift)
telemetry-check:
	JAX_PLATFORMS=cpu $(PY) exps/run_telemetry_check.py

# autotuner drift guard: assert the cost model's rung choice on three
# canonical workloads (64k causal / 16k varlen-block-causal / 16k SWA)
# against exps/data/autotune_expectations.json (run with --update after
# an intentional recalibration)
autotune-check:
	JAX_PLATFORMS=cpu $(PY) exps/run_autotune_check.py

# serving drift guard (CPU, jnp backend): decode-vs-prefill parity on
# causal masks over varied page sizes/split counts, cp=2 loopback merge
# parity, paged-cache invariants (exps/run_serving_check.py exits
# non-zero on any violation)
serving-check:
	JAX_PLATFORMS=cpu $(PY) exps/run_serving_check.py

# shared-prefix serving drift guard (ISSUE 9, CPU): multi-tenant trace
# (one system prompt x many users) asserting cascade decode parity vs
# dense oracles on BOTH backends (jnp + pallas-interpret), shared prefix
# pages resident exactly once (+1 CoW boundary page per diverging user
# on unaligned prefixes), chunked-prefill round-trip parity, and that no
# scheduler step with an active decode batch skips decode while a long
# prefill drains under the token budget
# (exps/run_scheduler_check.py exits non-zero on any violation)
sched-check:
	JAX_PLATFORMS=cpu $(PY) exps/run_scheduler_check.py

# group-collective drift guard (CPU, virtual mesh): hops-vs-a2a parity
# on a canonical skewed varlen plan (bit-identical cast recv buffer, no
# all_to_all traced), >= 30% scheduled-volume reduction on the 16k
# headline varlen plan, and auto-mode impl-choice sanity
# (exps/run_comm_check.py exits non-zero on any violation)
comm-check:
	JAX_PLATFORMS=cpu $(PY) exps/run_comm_check.py

# static-analysis gate (ISSUEs 7 + 13, jax-CPU only, ~50s): AST
# compat/idiom lint (MAGI001-005 + allowlist), jaxpr trace audit
# (collective census vs CommMeta across plans x cp x dtypes, upcast
# census, retrace guard, tp-decode/cascade zero-collective + dtype
# contract, hier per-level census), plan-sanitizer self-check, the SPMD
# collective-consistency audit (pass 4) and the serving lifecycle model
# check (pass 5), plus --self-test proof that each pass can fail on a
# seeded violation — incl. both replanted historical lifecycle bugs
# (docs/static_analysis.md)
analyze:
	JAX_PLATFORMS=cpu $(PY) exps/run_static_analysis.py --self-test

# pass 4 standalone (ISSUE 13): per-rank collective signatures of every
# production collective path (flat + hier group cast/reduce, dist_attn
# calc+grad, cp/tp decode, degradation/chaos variants) must be
# identical across ranks, hop pairing well-formed; --self-test plants a
# rank-gated extra ppermute and a one-sided perm
spmd-audit:
	JAX_PLATFORMS=cpu $(PY) exps/run_static_analysis.py --only spmd --self-test

# pass 5 standalone (ISSUE 13): exhaustive bounded serving-state
# interleavings over the REAL host objects (allocator/trie/engine/
# scheduler/tiered) on a stubbed device layer — >= 10k canonical states
# with zero invariant violations; --self-test replants the PR 9
# double-free and PR 12 dangling-victim bugs and requires <= 8-event
# minimal counterexamples
lifecycle-check:
	JAX_PLATFORMS=cpu $(PY) exps/run_static_analysis.py --only lifecycle --self-test

# resilience gate (ISSUE 8, CPU, ~4 min): every chaos injector is
# caught by its matching guard or degradation path (zero silent
# corruptions) — stage/split guard detection + repair with grad parity,
# wire-corruption containment, straggler tracing, backpressure +
# evict-then-retry, plan/hops build fallbacks, prefill-fault page
# release, tuning-io counters — and a no-chaos GUARD=check run is
# bit-identical to off with the trace count unchanged
# (docs/resilience.md)
resilience-check:
	JAX_PLATFORMS=cpu $(PY) exps/run_resilience_check.py

# request-tracing & exposition gate (ISSUE 11, CPU): a multi-tenant
# scheduler trace must reconstruct to complete, monotonically ordered
# per-request span trees whose derived stats reconcile EXACTLY with the
# SLO histograms, export as a valid one-track-per-request Chrome trace
# + JSONL, mark ring-truncated traces partial (dropped-span counter),
# dump the flight recorder (incl. the faulting tick) on an injected
# MAGI_ATTENTION_CHAOS prefill fault, and render a Prometheus exposition
# that parses and covers every REQUIRED_* metric catalog
# (exps/run_trace_check.py exits non-zero on any violation)
trace-check:
	JAX_PLATFORMS=cpu $(PY) exps/run_trace_check.py

# disaggregated-serving gate (ISSUE 12, the ROADMAP item-2 gate; CPU,
# 8 emulated chips): KV-head-sharded TP decode bitwise-matches the
# single-chip reference, prefill->decode page streams round-trip
# exactly (digest + gathered-KV equality), aggregate decode tokens/s
# scales with decode chip count at flat p99 token latency (logical tick
# clock; trace written to exps/data/distserve_scaling.json), and a
# chaos-injected decode-chip fault ends in trace-verified
# requeue+replay with a flight-recorder post-mortem — never a hang
# (exps/run_distserve_check.py exits non-zero on any violation)
distserve-check:
	JAX_PLATFORMS=cpu $(PY) exps/run_distserve_check.py

# memory observability gate (ISSUE 14, CPU): ledger-vs-measured bytes
# within tolerance on the jitted decode + dist_attn programs (XLA
# memory_analysis; per-stage cast buffers single-sourced with
# CommMeta.scheduled_rows_per_rank), REQUIRED_MEMORY_METRICS populated
# by a live serving trace + the telemetry_summary memory probe line,
# fragmentation map bit-equal to a brute-force free-list scan, a chaos
# pool_exhaust run ending in a flight dump carrying the memory ledger +
# fragmentation snapshot and the triggering admission's trace id, and
# --self-test proof that a planted ledger mispricing is caught
memory-check:
	JAX_PLATFORMS=cpu $(PY) exps/run_memory_check.py --self-test

# program-observability gate (ISSUE 16; CPU): launch ledger + compile
# registry reconciled on a multi-tenant trace, warm-pass solver-ms
# credit with flat per-shape compiles, full REQUIRED_COMPILE_METRICS
# exposition; --self-test plants a recompile storm that must produce a
# tick-tagged flight dump
compile-check:
	JAX_PLATFORMS=cpu $(PY) exps/run_compile_check.py --self-test

# unified serving-tick gate (ISSUE 17): one launch per tick under
# MAGI_ATTENTION_UNIFIED_TICK=on, exact token-schedule parity vs the
# per-request path, per-bucket compile count flat after warmup, and a
# planted demux off-by-one the parity oracle must catch
tick-check:
	JAX_PLATFORMS=cpu $(PY) exps/run_tick_check.py --self-test

# numerics observability gate (ISSUE 18; CPU): REQUIRED_NUMERICS_METRICS
# populated by a live census+shadow trace (decode + parallel layers, zero
# breaches when clean), a planted guard-invisible finite:8.0 split
# corruption caught by the shadow sentinel with a trace-id-tagged
# numeric_drift flight dump, census-off transparency (bit-identical
# out/lse, trace count 1/1, identical collective census), and
# --self-test proof that a 2-ulp-over-budget divergence fails the
# error-budget gate by exactly the planted margin
numerics-check:
	JAX_PLATFORMS=cpu $(PY) exps/run_numerics_check.py --self-test

# plan-reuse gate (ISSUE 20; CPU): fingerprint-bucketed plan reuse —
# bucketed-adapter parity (fwd+grad, jnp AND pallas-interpret backends,
# both the fingerprint-miss and bucket-hit flavors), exact-hit identity
# (the exact LRU stays byte-for-byte in front of the fingerprint cache),
# and --self-test proof that one stolen REAL dispatch row trips the
# parity oracle
plan-reuse-check:
	JAX_PLATFORMS=cpu $(PY) exps/run_plan_reuse_check.py --self-test
	JAX_PLATFORMS=cpu $(PY) exps/run_plan_reuse_check.py

# the default check flow: syntax, static analysis, telemetry catalog +
# aggregate semantics, autotuner rung expectations, serving parity,
# shared-prefix/scheduler gate, group-collective parity/volume,
# resilience gate, request tracing/exposition gate,
# disaggregated-serving gate, memory observability gate, unified-tick
# gate, numerics observability gate, plan-reuse gate — all CPU-safe
check: lint analyze telemetry-check autotune-check serving-check sched-check comm-check resilience-check trace-check distserve-check memory-check compile-check tick-check numerics-check plan-reuse-check
