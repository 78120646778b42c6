"""Chunked-prefill continuous-batching scheduler (ISSUE 9 tentpole).

The missing control layer over :class:`ServingEngine`: without it, a
long prompt occupies the engine for one giant prefill while every
decoding user stalls — exactly the head-of-line blocking FlashInfer's
serving composition (arxiv 2501.01005) schedules away. The
:class:`Scheduler` runs a step loop under a **token budget**:

1. **Admit** queued requests (priority-desc, FIFO within a priority)
   through the engine's typed admission — shared prefixes install by
   reference, backpressure parks the queue head instead of raising.
2. **Decode first**: if any sequence is decoding, ONE batched decode
   step runs before any prefill work. This is the anti-starvation
   invariant ``make sched-check`` asserts: while a long prefill drains
   chunk by chunk, every step still produces a token for every decoding
   sequence.
3. **Prefill chunks** with the remaining budget: the highest-priority
   prefilling request advances by up to ``MAGI_ATTENTION_PREFILL_CHUNK``
   tokens per step (the engine's cross path attends each chunk to the
   already-written cache), so prompt progress and decode progress
   interleave at token granularity.

Requests carry their attention inputs directly (this repo is the
attention runtime, not a model): per-token prompt q/k/v, and one q/k/v
row per decode step — a "model" is simulated by the caller. Completion
is ``max_new_tokens`` decode steps.

Per-request SLO telemetry lands on the existing metrics registry
(``magi_request_queue_seconds`` / ``magi_request_ttft_seconds`` /
``magi_request_token_latency_seconds`` histograms + the ``magi_sched_*``
step counters/gauges) — the observability ROADMAP item 2 asks for.

Request-lifecycle tracing (ISSUE 11): every request gets a trace id at
submission and the scheduler emits typed lifecycle spans (submit /
admitted / prefill_chunk / decode_step / evicted / requeued / finished
...) through ``telemetry/trace.py`` into the span ring — the SLO
histogram samples are emitted by the same helpers, so the per-request
trace and the aggregate histograms are computed from one number.
``telemetry.export_request_traces()`` reconstructs the span trees;
every tick also lands in the always-on flight recorder, which
auto-dumps on resilience signals (a tick that aborts on an engine
fault is recorded before the dump flushes, so the post-mortem contains
the faulting tick).

Host-side only: the scheduler never traces; the jitted work is the
engine's pure ops underneath.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Sequence

import jax
import jax.numpy as jnp

from .. import telemetry
from ..telemetry import trace as reqtrace
from .engine import ServingEngine

QUEUED = "queued"
PREFILLING = "prefilling"
DECODING = "decoding"
FINISHED = "finished"
REJECTED = "rejected"


@dataclasses.dataclass
class Request:
    """One serving request, attention-level.

    - ``prompt_q/k/v``: ``[P, h, d]`` per-token prompt projections.
    - ``tokens``: optional host token ids (length P) — enables shared-
      prefix matching/registration at admission.
    - ``decode_q/k/v``: ``[G, h, d]`` the projections of each generated
      step (the caller's stand-in for the model's next-token compute);
      ``max_new_tokens`` defaults to G.
    - ``priority``: admission priority (higher wins; the engine may
      evict strictly-lower-priority residents under pressure).
    - ``trace_id``: request-lifecycle trace id (ISSUE 11); None (the
      default) lets :meth:`Scheduler.submit` assign a process-unique
      one. Every lifecycle span the serving stack emits for this
      request is tagged with it.
    """

    rid: int
    prompt_q: jax.Array
    prompt_k: jax.Array
    prompt_v: jax.Array
    decode_q: jax.Array
    decode_k: jax.Array
    decode_v: jax.Array
    tokens: Sequence[int] | None = None
    max_new_tokens: int | None = None
    priority: int = 0
    trace_id: str | None = None

    @property
    def prompt_len(self) -> int:
        return self.prompt_q.shape[0]

    @property
    def num_new_tokens(self) -> int:
        if self.max_new_tokens is not None:
            return int(self.max_new_tokens)
        return int(self.decode_q.shape[0])


@dataclasses.dataclass
class RequestState:
    """Scheduler-side lifecycle record of one request."""

    request: Request
    status: str = QUEUED
    slot: int | None = None
    submitted_at: float = 0.0
    # the SLO clock origin: == submitted_at normally, reset to the
    # requeue instant after a priority eviction — a restarted
    # generation's queue wait and TTFT are measured from requeue (the
    # ISSUE 9 clock-reset hardening, made explicit and trace-asserted
    # in ISSUE 11). submitted_at itself is NOT reset: it keeps the
    # original FIFO seniority in the admission order.
    slo_start: float = 0.0
    admitted_at: float | None = None
    first_token_at: float | None = None
    last_token_at: float | None = None
    prefill_pos: int = 0  # prompt tokens committed (incl. shared prefix)
    prefix_len: int = 0  # tokens installed by reference at admission
    tokens_done: int = 0
    prefill_chunk_idx: int = 0  # chunks run so far (trace span index)
    evictions: int = 0  # priority evictions suffered
    trace_id: str = ""
    prefill_out_tail: jax.Array | None = None  # last prompt row's out
    decode_outs: list = dataclasses.field(default_factory=list)

    @property
    def rid(self) -> int:
        return self.request.rid


@dataclasses.dataclass(frozen=True)
class StepReport:
    """What one :meth:`Scheduler.step` tick actually did (the
    sched-check starvation assertions read these)."""

    step: int
    admitted: tuple[int, ...]
    rejected: tuple[int, ...]
    decode_ran: bool
    decode_batch: int
    prefill_chunks: tuple[tuple[int, int], ...]  # (rid, chunk tokens)
    tokens_used: int
    finished: tuple[int, ...]
    # ISSUE 11 satellite: saturation at tick granularity — the queue
    # depth when the tick started (before admissions) and the fraction
    # of the token budget it spent; also exported as the
    # magi_sched_queue_depth / magi_sched_budget_utilization gauges
    queue_depth: int = 0
    budget_utilization: float = 0.0

    @property
    def idle(self) -> bool:
        return (
            not self.decode_ran
            and not self.prefill_chunks
            and not self.admitted
        )


class Scheduler:
    """Token-budget continuous-batching loop over one engine.

    ``token_budget``: attention tokens one step may process (decode
    counts 1 per sequence, a prefill chunk its row count). ``chunk``
    overrides ``MAGI_ATTENTION_PREFILL_CHUNK`` (None = env; env unset =
    whole remaining prompt, bounded by the budget).
    """

    # tier labels threaded into spans and the SLO histograms: None on
    # this single-chip scheduler (the historical unlabeled series, so
    # trace-check's exact reconciliation is untouched); the
    # TieredScheduler (serving/distributed.py, ISSUE 12) overrides both
    # so every sample lands on a per-tier series too
    _prefill_tier: str | None = None
    _decode_tier: str | None = None

    def __init__(
        self,
        engine: ServingEngine,
        *,
        token_budget: int = 256,
        chunk: int | None = None,
        max_decode_batch: int | None = None,
        clock=time.perf_counter,
        plan_probe=None,
    ):
        from .. import env

        self.engine = engine
        # plan-reuse probe (ISSUE 20): threads each tick's REAL request
        # shapes through the keyed-runtime planner so the plan-cache hit
        # rate is measured against genuine traffic. Host solver work
        # only — it must never append to the launch ledger
        # (_tick_programs), whose census invariants assume device
        # programs exclusively.
        self.plan_probe = plan_probe
        self.token_budget = int(token_budget)
        self.chunk = int(chunk) if chunk is not None else env.prefill_chunk()
        self.max_decode_batch = max_decode_batch
        self._clock = clock
        self._queue: list[RequestState] = []
        self._active: dict[int, RequestState] = {}  # rid -> state
        self._finished: dict[int, RequestState] = {}
        self._step = 0
        self._flight = reqtrace.get_flight_recorder()
        # OOM forensics (ISSUE 14): sustained low free-page fraction
        # arms a mem_pressure flight dump (threshold from
        # MAGI_ATTENTION_MEM_PRESSURE_THRESHOLD, 0 = off by default)
        from ..telemetry.memory import MemPressureWatcher

        self._mem_watcher = MemPressureWatcher()
        # launch ledger (ISSUE 16): program labels launched this tick +
        # engine-call wall seconds, reset at the top of every step()
        self._tick_programs: list[str] = []
        self._tick_engine_s = 0.0

    # -- submission ------------------------------------------------------

    def submit(self, request: Request) -> RequestState:
        now = self._clock()
        st = RequestState(
            request=request,
            submitted_at=now,
            slo_start=now,
            trace_id=(
                request.trace_id
                if request.trace_id is not None
                else reqtrace.new_trace_id(request.rid)
            ),
        )
        self._queue.append(st)
        reqtrace.span_submit(
            st.trace_id,
            st.rid,
            prompt_len=request.prompt_len,
            max_new_tokens=request.num_new_tokens,
            priority=request.priority,
        )
        return st

    @property
    def waiting(self) -> int:
        return len(self._queue)

    @property
    def num_active(self) -> int:
        return len(self._active)

    @property
    def done(self) -> bool:
        return not self._queue and not self._active

    def result(self, rid: int) -> RequestState:
        return self._finished[rid]

    # -- the step loop ---------------------------------------------------

    def _admission_order(self) -> list[RequestState]:
        # stable sort: priority desc, then submission order (FIFO)
        return sorted(
            self._queue, key=lambda s: (-s.request.priority, s.submitted_at)
        )

    def _admit_queued(self) -> tuple[list[int], list[int]]:
        admitted, rejected = [], []
        for st in self._admission_order():
            req = st.request
            # a request whose prompt PLUS decode budget can never fit
            # one sequence's page reservation — OR the whole pool —
            # is permanently unservable: reject it here like the
            # engine's own too_long verdict. The engine only sees the
            # prompt; left unchecked, the decode-time reservation
            # growth raises out of the tick loop (or, with the
            # preemption below, self-preempts and replays forever) and
            # one oversized request takes the whole scheduler down
            # (ISSUE 13 interleaving checker).
            alc = self.engine.allocator
            cap = (
                min(alc.max_pages_per_seq, alc.num_pages)
                * alc.page_size
            )
            if req.prompt_len + req.num_new_tokens > cap:
                st.status = REJECTED
                self._queue.remove(st)
                self._finished[st.rid] = st
                rejected.append(st.rid)
                # the engine never saw this admission — mirror its
                # rejection telemetry so magi_admission_rejected and
                # the flight recorder's storm detector keep counting
                from .engine import AdmissionResult

                telemetry.record_admission(
                    AdmissionResult(False, None, "too_long")
                )
                self._flight.note_admission(False, "too_long")
                reqtrace.span_rejected(
                    st.trace_id, st.rid, reason="too_long"
                )
                continue
            # pool-headroom watermark (ISSUE 13): an admission with NO
            # eviction power (no live request of strictly lower
            # priority) must leave one free page of decode-growth
            # headroom per currently decoding sequence. Without it, a
            # request the decode-pressure preemption below just
            # requeued re-admits straight into the pages its own
            # preemption freed, the survivor's growth fails again, and
            # the loop ping-pongs forever without producing a token.
            # Requests that CAN evict keep the engine's bounded
            # evict-then-retry semantics untouched (priority admission
            # may still displace decoders — that converges by rank).
            if not any(
                s.request.priority < req.priority
                for s in self._active.values()
            ):
                headroom = self._admission_headroom()
                alloc = self.engine.allocator
                free = alloc.num_pages - alloc.pages_in_use
                if headroom and (
                    free - alloc.pages_needed(req.prompt_len) < headroom
                ):
                    reqtrace.span_backpressure(
                        st.trace_id, st.rid, reason="decode_headroom"
                    )
                    break  # transient: decoders finish, pages free
            with reqtrace.request_context(st.trace_id, st.rid):
                res = self.engine.admit(
                    req.prompt_len,
                    priority=req.priority,
                    tokens=req.tokens,
                )
            # an admission ATTEMPT may have evicted lower-priority
            # residents even when it ultimately failed (the engine's
            # bounded evict-then-retry can give up after evicting) —
            # requeue victims unconditionally, or they dangle in
            # _active with slots the engine already released
            for victim_slot in res.evicted:
                self._handle_eviction(victim_slot)
            if not res.admitted:
                if res.reason == "too_long":
                    # permanent: no eviction makes it fit — surface it.
                    # The cap pre-check above strictly dominates this
                    # for ServingEngine today; it stays as the backstop
                    # should an engine's capacity notion ever diverge
                    # from the scheduler's (a permanent reason treated
                    # as transient backpressure would livelock)
                    st.status = REJECTED
                    self._queue.remove(st)
                    self._finished[st.rid] = st
                    rejected.append(st.rid)
                    reqtrace.span_rejected(
                        st.trace_id, st.rid, reason=res.reason
                    )
                    continue
                reqtrace.span_backpressure(
                    st.trace_id, st.rid, reason=res.reason
                )
                break  # transient backpressure: keep FIFO order, retry later
            st.slot = res.slot
            st.prefix_len = res.prefix_len
            st.prefill_pos = res.prefix_len
            st.admitted_at = self._clock()
            # zero-suffix prompts (fully-cached) still take one empty
            # prefill tick, which runs the registration hook
            st.status = PREFILLING
            self._queue.remove(st)
            self._active[st.rid] = st
            admitted.append(st.rid)
            # span + SLO histogram from the same float (cannot drift);
            # queue wait measured from the SLO clock origin, which a
            # requeue resets
            reqtrace.span_admitted(
                st.trace_id,
                st.rid,
                slot=res.slot,
                prefix_len=res.prefix_len,
                shared_pages=res.prefix_len // max(
                    self.engine.allocator.page_size, 1
                ),
                evicted=len(res.evicted),
                queue_s=st.admitted_at - st.slo_start,
                tier=self._prefill_tier,
            )
        return admitted, rejected

    def _admission_headroom(self) -> int:
        """Free pages an admission must leave for decode growth: one
        per decoding sequence sharing THIS allocator's pool. The
        TieredScheduler overrides it to 0 — its decode pools
        live on the replicas, disjoint from the admission-facing
        prefill pool — and skips the decode-state scan entirely."""
        return len(self._decode_states())

    def _handle_eviction(self, slot: int) -> None:
        """A live sequence was priority-evicted by the engine: push its
        request back to the queue for a clean retry (prefix pages it
        shared are still resident, so the retry re-forks cheaply)."""
        for st in list(self._active.values()):
            if st.slot == slot:
                self._requeue(st)
                return

    def _requeue(
        self, st: RequestState, *, tier: str | None = None,
        reason: str | None = None,
    ) -> None:
        """Push one in-flight request back to the queue for a clean
        retry — the shared tail of a priority eviction and (ISSUE 12) a
        decode-tier fault. Prefix pages it shared are still resident,
        so the retry re-forks/re-streams cheaply."""
        reqtrace.span_evicted(
            st.trace_id, st.rid, slot=st.slot, tier=tier, reason=reason
        )
        self._active.pop(st.rid, None)
        st.slot = None
        st.status = QUEUED
        st.prefill_pos = 0
        st.prefix_len = 0
        st.tokens_done = 0
        st.prefill_chunk_idx = 0
        st.evictions += 1
        st.decode_outs.clear()
        # the restarted generation gets a fresh SLO record: its
        # TTFT must be measured again and a stale last_token_at
        # would push one eviction+requeue+re-prefill-sized
        # outlier into the inter-token latency histogram. The
        # SLO clock restarts at the requeue instant — TTFT and
        # queue wait of the retry measure the retry, not the
        # whole first life (trace-asserted end to end by
        # tests/test_serving/test_scheduler.py and trace-check)
        st.first_token_at = None
        st.last_token_at = None
        st.slo_start = self._clock()
        self._queue.append(st)
        reqtrace.span_requeued(st.trace_id, st.rid)

    def _decode_states(self) -> list[RequestState]:
        return [
            st for st in self._active.values() if st.status == DECODING
        ]

    def _run_decode(self, states: list[RequestState]) -> int:
        if self.max_decode_batch is not None:
            states = states[: self.max_decode_batch]
        return self._decode_group(states)

    def _decode_group(
        self, states: list[RequestState], *, replica: int | None = None
    ) -> int:
        """One batched decode step over ``states`` + the per-request
        span/SLO bookkeeping. The single-chip scheduler calls it once
        per tick with every decoding state; the TieredScheduler calls
        it once per decode replica (``replica`` labels the spans) so a
        replica fault is isolated to its own group."""
        from .kv_cache import PageAllocatorError

        if self.plan_probe is not None:
            self.plan_probe.note_decode(states)
        qs = jnp.stack([st.request.decode_q[st.tokens_done] for st in states])
        ks = jnp.stack([st.request.decode_k[st.tokens_done] for st in states])
        vs = jnp.stack([st.request.decode_v[st.tokens_done] for st in states])
        slots = [st.slot for st in states]
        t0 = time.perf_counter()
        try:
            # the batch lead's identity tags engine-internal emissions
            # for this step (ISSUE 18: the shadow sentinel's deferred
            # numeric_drift dump carries a LIVE trace id this way, like
            # admission backpressure dumps carry the admitting request)
            with reqtrace.request_context(states[0].trace_id, states[0].rid):
                out, _lse = self.engine.decode_step(qs, ks, vs, slots)
        except PageAllocatorError:
            # transient pool pressure mid-growth (reservation extension
            # or a CoW split found the pool empty). Resource pressure
            # is an operating condition, not a crash (the PR 8
            # contract): preempt the lowest-priority, youngest group
            # member — its pages go back to the pool, its request
            # replays through admission — and retry the batch next
            # tick. Found by the ISSUE 13 interleaving checker: the
            # uncaught error killed the whole serving loop.
            victim = min(
                states,
                key=lambda s: (s.request.priority, -s.submitted_at),
            )
            # unlike eviction/fault requeues, the engine still holds
            # this slot — release it so the pages actually free
            self.engine.free(victim.slot)
            self._requeue(victim, reason="decode_pressure")
            return 0
        dur = time.perf_counter() - t0
        # what the engine's step actually resolved (split count /
        # cascade grouping): per-request decode spans carry it
        info = getattr(self.engine, "last_decode_info", None) or {}
        group_of = info.get("cascade_group_of", {})
        # launch ledger (ISSUE 16): one batched decode program launched
        program = info.get("program") or telemetry.decode_program_label(
            len(states)
        )
        self._tick_programs.append(program)
        self._tick_engine_s += dur
        now = self._clock()
        for j, st in enumerate(states):
            st.decode_outs.append(out[j])
            st.tokens_done += 1
            ttft_s = token_latency_s = None
            if st.first_token_at is None:
                st.first_token_at = now
                # from the SLO clock origin: the submit instant, or the
                # requeue instant after a priority eviction
                ttft_s = now - st.slo_start
            else:
                token_latency_s = now - (st.last_token_at or now)
            st.last_token_at = now
            # span + histograms from the same floats (cannot drift)
            reqtrace.span_decode_step(
                st.trace_id,
                st.rid,
                token_idx=st.tokens_done - 1,
                batch=len(states),
                num_splits=int(info.get("num_splits", 0)),
                cascade_group=group_of.get(st.slot),
                start_s=t0,
                duration_s=dur,
                ttft_s=ttft_s,
                token_latency_s=token_latency_s,
                tier=self._decode_tier,
                replica=replica,
                program=program,
            )
            if st.tokens_done >= st.request.num_new_tokens:
                self._finish(st)
        return len(states)

    def _finish(self, st: RequestState) -> None:
        st.status = FINISHED
        self.engine.free(st.slot)
        del self._active[st.rid]
        self._finished[st.rid] = st
        now = self._clock()
        reqtrace.span_finished(
            st.trace_id,
            st.rid,
            tokens=st.tokens_done,
            prefill_chunks=st.prefill_chunk_idx,
            prefix_len=st.prefix_len,
            evictions=st.evictions,
            e2e_s=now - st.submitted_at,
            slo_window_s=now - st.slo_start,
        )

    def _prefill_states(self) -> list[RequestState]:
        sts = [
            st for st in self._active.values() if st.status == PREFILLING
        ]
        return sorted(
            sts, key=lambda s: (-s.request.priority, s.submitted_at)
        )

    def _run_prefill_chunk(self, st: RequestState, budget: int) -> int:
        req = st.request
        remaining = req.prompt_len - st.prefill_pos
        cap = self.chunk if self.chunk else remaining
        n = max(min(cap, remaining, budget), 0)
        if remaining > 0 and n == 0:
            return 0  # budget exhausted
        lo, hi = st.prefill_pos, st.prefill_pos + n
        if self.plan_probe is not None and n:
            self.plan_probe.note_prefill(st.rid, lo, hi)
        t0 = time.perf_counter()
        with reqtrace.request_context(st.trace_id, st.rid):
            out, _lse = self.engine.prefill(
                req.prompt_q[lo:hi],
                req.prompt_k[lo:hi],
                req.prompt_v[lo:hi],
                st.slot,
            )
        dur = time.perf_counter() - t0
        # launch ledger (ISSUE 16): a zero-token chunk (fully-cached
        # prompt) launches nothing — the engine returns without any
        # device program
        program = telemetry.prefill_program_label(lo, n) if n else None
        if n:
            self._tick_programs.append(program)
            self._tick_engine_s += dur
        reqtrace.span_prefill_chunk(
            st.trace_id,
            st.rid,
            tokens=n,
            chunk_idx=st.prefill_chunk_idx,
            start=lo,
            start_s=t0,
            duration_s=dur,
            tier=self._prefill_tier,
            program=program,
        )
        st.prefill_chunk_idx += 1
        st.prefill_pos = hi
        if n and hi == req.prompt_len:
            st.prefill_out_tail = out[-1]
        if st.prefill_pos >= req.prompt_len:
            st.status = DECODING
            if req.num_new_tokens == 0:
                self._finish(st)
        return n

    def step(self) -> StepReport:
        """One scheduler tick: admissions, at most ONE decode step, then
        prefill chunks with whatever budget remains. Every tick lands in
        the flight recorder; a tick aborted by an engine fault is
        recorded (with the error) before the armed post-mortem dump
        flushes, so the dump contains the faulting tick."""
        self._step += 1
        tick_start = time.perf_counter()  # flight-recorder arm window
        # tick cost attribution (ISSUE 16): mark the compile tracker's
        # always-on accumulators so the tick can diff them at the end —
        # works with telemetry off, like the flight recorder itself
        tracker = telemetry.get_compile_tracker()
        tracker.note_tick(self._step)
        compile_mark = tracker.mark()
        solver_mark = tracker.solver_mark()
        self._tick_programs = []
        self._tick_engine_s = 0.0
        queue_depth = self.waiting  # at tick START, before admissions
        try:
            report = self._step_body(queue_depth)
        except Exception as e:  # noqa: BLE001 — recorded, then re-raised
            self._flight.record_tick(
                {
                    "step": self._step,
                    "aborted": repr(e),
                    "queue_depth": queue_depth,
                    "active": self.num_active,
                    "budget": self.token_budget,
                },
                start_t=tick_start,
            )
            self._flight.flush()
            raise
        # decompose the tick's wall-clock: host solver (plan builds +
        # LRU lookups), compile (tracker delta), device (engine-call
        # wall minus the compiles that happened inside it), and an
        # HONEST unattributed residual — may be negative when
        # attribution over-counts (a compile outside an engine call);
        # surfaced as-is, never folded into a gate
        wall_s = time.perf_counter() - tick_start
        compile_n, compile_s = tracker.since(compile_mark)
        solver_s = tracker.solver_since(solver_mark)
        device_s = max(self._tick_engine_s - compile_s, 0.0)
        residual_s = wall_s - solver_s - compile_s - device_s
        programs = list(self._tick_programs)
        launches = len(set(programs))
        telemetry.record_sched_step(
            waiting=self.waiting,
            active=self.num_active,
            tokens_used=report.tokens_used,
            prefill_chunks=len(
                [c for c in report.prefill_chunks if c[1] > 0]
            ),
            decode_ran=report.decode_ran,
            budget_utilization=report.budget_utilization,
            queue_depth=report.queue_depth,
        )
        telemetry.record_tick_programs(
            step=self._step,
            start_s=tick_start,
            wall_s=wall_s,
            programs=programs,
            compiles=compile_n,
            solver_s=solver_s,
            compile_s=compile_s,
            device_s=device_s,
            residual_s=residual_s,
        )
        # ISSUE 14: the admission watermark, observable — headroom the
        # evictionless-admission rule demands vs the pages actually
        # free — plus the sustained-pressure watcher: N consecutive
        # ticks under the free-fraction threshold arm a mem_pressure
        # flight dump (deferred; the flush below writes it with the
        # ledger + fragmentation snapshots embedded)
        alloc = self.engine.allocator
        free = alloc.num_pages - alloc.pages_in_use
        telemetry.record_admission_watermark(
            self._admission_headroom(), free
        )
        if self._mem_watcher.observe(free / max(alloc.num_pages, 1)):
            self._flight.trigger(
                "mem_pressure",
                immediate=False,
                free_pages=free,
                pages_total=alloc.num_pages,
                threshold=self._mem_watcher.threshold,
                consecutive_ticks=self._mem_watcher.ticks,
            )
        self._flight.record_tick(
            {
                "step": report.step,
                "admitted": list(report.admitted),
                "rejected": list(report.rejected),
                "decode_ran": report.decode_ran,
                "decode_batch": report.decode_batch,
                "prefill_chunks": [list(c) for c in report.prefill_chunks],
                "tokens_used": report.tokens_used,
                "budget": self.token_budget,
                "budget_utilization": report.budget_utilization,
                "queue_depth": report.queue_depth,
                "waiting": self.waiting,
                "active": self.num_active,
                "finished": list(report.finished),
                "launches": launches,
                "programs": programs,
                "compiles": compile_n,
                "cost_ms": {
                    "wall": round(wall_s * 1e3, 3),
                    "solver": round(solver_s * 1e3, 3),
                    "compile": round(compile_s * 1e3, 3),
                    "device": round(device_s * 1e3, 3),
                    "residual": round(residual_s * 1e3, 3),
                },
            },
            start_t=tick_start,
        )
        self._flight.flush()
        if self.plan_probe is not None:
            self.plan_probe.on_step_end(report)
        return report

    def _step_body(self, queue_depth: int) -> StepReport:
        budget = self.token_budget
        admitted, rejected = self._admit_queued()
        finished_before = set(self._finished)

        # ONE pass over the active set per tick (ISSUE 17 satellite):
        # the decode and prefill censuses are computed here and threaded
        # to whichever launch path runs below, which must not re-scan.
        # Hoisting the prefill list above the decode step is
        # behavior-identical: decode only finishes or requeues DECODING
        # states, never grows or shrinks the PREFILLING set.
        decoding = self._decode_states()
        prefilling = self._prefill_states()

        unified = self._unified_tick_enabled(decoding, prefilling)
        if unified:
            report = self._unified_step_body(
                budget, admitted, rejected, finished_before, queue_depth,
                decoding, prefilling,
            )
        else:
            decode_ran = False
            decode_batch = 0
            if decoding:
                decode_batch = self._run_decode(decoding)
                decode_ran = True
                budget -= decode_batch

            chunks, budget = self._run_prefill_loop(
                budget, states=prefilling
            )

            tokens_used = self.token_budget - budget
            report = StepReport(
                step=self._step,
                admitted=tuple(admitted),
                rejected=tuple(rejected),
                decode_ran=decode_ran,
                decode_batch=decode_batch,
                prefill_chunks=tuple(chunks),
                tokens_used=tokens_used,
                finished=tuple(set(self._finished) - finished_before),
                queue_depth=queue_depth,
                budget_utilization=tokens_used / max(self.token_budget, 1),
            )
        # launch census (ISSUE 17 satellite): the hoisted lists predict
        # the tick's program count EXACTLY — one unified program when
        # any attention ran, else one per decode group + one per
        # token-carrying prefill chunk. Drift here means a launch loop
        # re-scanned the active set behind the census's back.
        if unified:
            expected = (
                1
                if (
                    report.decode_batch > 0
                    or any(n for _rid, n in report.prefill_chunks)
                )
                else 0
            )
        else:
            expected = (1 if report.decode_batch > 0 else 0) + sum(
                1 for _rid, n in report.prefill_chunks if n > 0
            )
        assert len(self._tick_programs) == expected, (
            f"scheduler launch census drift: {len(self._tick_programs)} "
            f"programs recorded ({self._tick_programs}) but the hoisted "
            f"tick census predicted {expected} (unified={unified}, "
            f"decode_batch={report.decode_batch}, "
            f"chunks={report.prefill_chunks})"
        )
        return report

    def _unified_tick_enabled(
        self,
        decoding: list[RequestState],
        prefilling: list[RequestState],
    ) -> bool:
        """Does THIS tick's work run as one fused launch (ISSUE 17)?
        ``MAGI_ATTENTION_UNIFIED_TICK``: ``off`` never (the default —
        the per-request path stays byte-for-byte), ``on`` whenever any
        attention work exists (the parity-test mode), ``auto`` exactly
        when the per-request path would launch >= 2 distinct programs
        (a decode group alongside >= 1 prefill chunk, or >= 2 prefill
        chunks) — a fused singleton would only re-bucket a launch that
        is already minimal. A TP-substituted decode realization opts
        out: the tick kernel IS the attention."""
        from .. import env

        mode = env.unified_tick_mode()
        if mode == "off":
            return False
        if not hasattr(self.engine, "unified_tick"):
            return False
        if getattr(self.engine, "_decode_attn_fn", None) is not None:
            return False
        n_pf = sum(
            1
            for st in prefilling
            if st.request.prompt_len - st.prefill_pos > 0
        )
        if mode == "on":
            return bool(decoding) or n_pf > 0
        return (bool(decoding) and n_pf > 0) or n_pf >= 2

    def _unified_step_body(
        self,
        budget: int,
        admitted: list,
        rejected: list,
        finished_before: set,
        queue_depth: int,
        decoding: list[RequestState],
        prefilling: list[RequestState],
    ) -> StepReport:
        """One fused tick (ISSUE 17): the decode group and every planned
        prefill chunk go down as ONE ``engine.unified_tick`` call — one
        program label in the launch ledger — then the per-request
        span/SLO/finish bookkeeping of ``_decode_group`` and
        ``_run_prefill_chunk`` replays over the demuxed outputs.

        Chunk planning is the same policy as ``_run_prefill_loop``:
        priority order, at most one chunk per request, stop when the
        budget cannot fit the next chunk's first token; zero-token
        chunks (fully-cached prompts) ride along for their completion
        hooks. Pool pressure mid-growth preempts the lowest-priority,
        youngest decode member and retries the WHOLE tick next step
        (the legacy path instead still ran prefill the same tick — the
        one scheduling difference, visible only under pressure)."""
        from .kv_cache import PageAllocatorError

        decode_states = decoding
        if self.max_decode_batch is not None:
            decode_states = decode_states[: self.max_decode_batch]
        decode_ran = bool(decode_states)
        b = budget - len(decode_states)
        plan: list[tuple[RequestState, int, int]] = []  # (st, lo, n)
        for st in prefilling:
            if b <= 0:
                break
            remaining = st.request.prompt_len - st.prefill_pos
            cap = self.chunk if self.chunk else remaining
            n = max(min(cap, remaining, b), 0)
            if remaining > 0 and n == 0:
                break  # budget can't fit the next chunk's first token
            plan.append((st, st.prefill_pos, n))
            b -= n

        if self.plan_probe is not None:
            if decode_states:
                self.plan_probe.note_decode(decode_states)
            for st, lo, n in plan:
                if n:
                    self.plan_probe.note_prefill(st.rid, lo, lo + n)
        decode_items = [
            (
                st.slot,
                st.request.decode_q[st.tokens_done],
                st.request.decode_k[st.tokens_done],
                st.request.decode_v[st.tokens_done],
            )
            for st in decode_states
        ]
        prefill_items = [
            (
                st.slot,
                st.request.prompt_q[lo : lo + n],
                st.request.prompt_k[lo : lo + n],
                st.request.prompt_v[lo : lo + n],
            )
            for st, lo, n in plan
        ]
        t0 = time.perf_counter()
        try:
            decode_res, prefill_res = self.engine.unified_tick(
                decode_items, prefill_items
            )
        except PageAllocatorError:
            # transient pool pressure mid-growth: same preemption policy
            # as _decode_group — lowest-priority, youngest member out,
            # pages back to the pool, retry next tick. Nothing launched.
            if not decode_states:
                raise
            victim = min(
                decode_states,
                key=lambda s: (s.request.priority, -s.submitted_at),
            )
            self.engine.free(victim.slot)
            self._requeue(victim, reason="decode_pressure")
            return StepReport(
                step=self._step,
                admitted=tuple(admitted),
                rejected=tuple(rejected),
                decode_ran=decode_ran,
                decode_batch=0,
                prefill_chunks=(),
                tokens_used=0,
                finished=tuple(set(self._finished) - finished_before),
                queue_depth=queue_depth,
                budget_utilization=0.0,
            )
        dur = time.perf_counter() - t0
        info = getattr(self.engine, "last_tick_info", None) or {}
        program = info.get("program")
        if program is not None:
            # launch ledger (ISSUE 16): the WHOLE tick was one program
            self._tick_programs.append(program)
            self._tick_engine_s += dur
        group_of = info.get("cascade_group_of", {})
        now = self._clock()
        for j, st in enumerate(decode_states):
            out_row, _lse_row = decode_res[j]
            st.decode_outs.append(out_row)
            st.tokens_done += 1
            ttft_s = token_latency_s = None
            if st.first_token_at is None:
                st.first_token_at = now
                ttft_s = now - st.slo_start
            else:
                token_latency_s = now - (st.last_token_at or now)
            st.last_token_at = now
            reqtrace.span_decode_step(
                st.trace_id,
                st.rid,
                token_idx=st.tokens_done - 1,
                batch=len(decode_states),
                num_splits=int(info.get("num_splits", 0)),
                cascade_group=group_of.get(st.slot),
                start_s=t0,
                duration_s=dur,
                ttft_s=ttft_s,
                token_latency_s=token_latency_s,
                tier=self._decode_tier,
                program=program,
            )
            if st.tokens_done >= st.request.num_new_tokens:
                self._finish(st)
        chunks: list[tuple[int, int]] = []
        for (st, lo, n), (out_rows, _lse_rows) in zip(plan, prefill_res):
            req = st.request
            hi = lo + n
            reqtrace.span_prefill_chunk(
                st.trace_id,
                st.rid,
                tokens=n,
                chunk_idx=st.prefill_chunk_idx,
                start=lo,
                start_s=t0,
                duration_s=dur if n else 0.0,
                tier=self._prefill_tier,
                program=program if n else None,
            )
            st.prefill_chunk_idx += 1
            st.prefill_pos = hi
            if n and hi == req.prompt_len:
                st.prefill_out_tail = out_rows[-1]
            if st.prefill_pos >= req.prompt_len:
                st.status = DECODING
                if req.num_new_tokens == 0:
                    self._finish(st)
            chunks.append((st.rid, n))
        tokens_used = self.token_budget - b
        return StepReport(
            step=self._step,
            admitted=tuple(admitted),
            rejected=tuple(rejected),
            decode_ran=decode_ran,
            decode_batch=len(decode_states),
            prefill_chunks=tuple(chunks),
            tokens_used=tokens_used,
            finished=tuple(set(self._finished) - finished_before),
            queue_depth=queue_depth,
            budget_utilization=tokens_used / max(self.token_budget, 1),
        )

    def _run_prefill_loop(
        self, budget: int, states: list[RequestState] | None = None
    ) -> tuple[list[tuple[int, int]], int]:
        """Advance prefilling requests (priority order, at most one
        chunk each) until the chunk budget is spent; returns the
        started ``(rid, tokens)`` chunks and the budget left. Shared
        with the TieredScheduler, whose prefill tier spends its own
        budget. ``states`` threads the tick's hoisted prefill census
        (ISSUE 17 satellite); None re-scans, for callers that do not
        hoist."""
        chunks: list[tuple[int, int]] = []
        if states is None:
            states = self._prefill_states()
        for st in states:
            if budget <= 0:
                break
            n = self._run_prefill_chunk(st, budget)
            if n == 0 and st.request.prompt_len - st.prefill_pos > 0:
                break  # budget can't fit the next chunk's first token
            budget -= n
            chunks.append((st.rid, n))
        return chunks, budget

    def run(self, max_steps: int = 10_000) -> list[StepReport]:
        """Step until every submitted request finished (or the safety
        cap trips — an idle step with work still pending means a
        deadlock and raises)."""
        reports = []
        while not self.done:
            if len(reports) >= max_steps:
                raise RuntimeError(
                    f"Scheduler.run: {max_steps} steps without draining "
                    f"({self.waiting} queued, {self.num_active} active)"
                )
            rep = self.step()
            reports.append(rep)
            if rep.idle and not self.done and self.num_active == 0:
                raise RuntimeError(
                    "Scheduler.run: queue blocked with no active work "
                    "(pool too small for the queue head?)"
                )
        return reports
