"""Serving front end: continuous batching over one paged cache.

The minimal decode engine (ISSUE 4 tentpole): prefill runs through the
existing flex-attention path and writes its KV into pages, decode steps
run the split-KV kernel over the same pool — so a sequence's lifetime
(admit → prefill → N decode steps → free) round-trips through ONE cache
with no re-layout.

Layers:

- :class:`DecodeBatch` — the ragged batch descriptor the jitted step
  consumes: per-sequence cache slots; true lengths live in the cache's
  ``seq_lens`` so growth never re-traces.
- :func:`magi_attn_decode` — the public decode attention entry
  (``api.magi_attn_decode``).
- :func:`prefill_into_cache` — flex-attention prefill + paged KV write.
- :class:`ServingEngine` — host-side continuous batching: admission via
  :class:`~magiattention_tpu.serving.kv_cache.PageAllocator`, slot
  recycling, telemetry (``magi_decode_*`` / ``magi_kvcache_*``).

Every stage records counters/gauges through the telemetry registry and
annotates device traces with named scopes (``magi_prefill_attn`` /
``magi_decode_attn`` / ``magi_kvcache_append``).
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..telemetry import exposition, numerics, trace
from ..common.enum import AttnMaskType
from ..utils.instrument import named_scope
from .decode_attn import (
    decode_attn_paged,
    decode_reference,
    resolve_num_splits,
)
from .kv_cache import (
    PagedKVCache,
    PageAllocator,
    PageAllocatorError,
    append_kv,
    assign_block_table,
    copy_page,
    gather_kv,
    make_paged_kv_cache,
    reset_slot,
    swap_block_table_page,
    write_prefill_kv,
)
from .prefix import PrefixCache, cascade_decode_attn, plan_cascade_groups
from .unified_tick import demux_tick, resolve_tick_splits, unified_tick_attn


@dataclasses.dataclass(frozen=True)
class AdmissionResult:
    """Typed outcome of :meth:`ServingEngine.admit` (ISSUE 8).

    Admission control never raises on resource pressure: a full pool is
    an operating condition of a loaded serving fleet, not a crash. The
    caller checks ``admitted`` — ``backpressure`` means "retry later /
    shed upstream" and is recorded as ``magi_admission_rejected``.

    - ``admitted``: True with a usable ``slot``; False = backpressure
      (``slot`` is None).
    - ``reason``: ``"ok"`` | ``"pool_exhausted"`` | ``"no_free_slot"``
      | ``"too_long"`` | ``"alloc_error"``.
    - ``evicted``: slots freed by the bounded
      evict-lowest-priority-then-retry policy on the way to this verdict
      (possibly non-empty on BOTH verdicts).
    - ``prefix_len`` (ISSUE 9): tokens of the prompt already resident as
      a shared prefix (0 without prefix sharing / on a miss). The
      caller prefills ONLY rows ``prefix_len:`` — the cache's
      ``seq_lens`` already stands at ``prefix_len`` for this slot.
    """

    admitted: bool
    slot: int | None
    reason: str = "ok"
    evicted: tuple[int, ...] = ()
    prefix_len: int = 0

    def __bool__(self) -> bool:
        return self.admitted


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class DecodeBatch:
    """One continuous-batching decode step's ragged batch descriptor.

    ``slots`` [b] int32: each sequence's cache slot. The per-sequence KV
    lengths are NOT duplicated here — they are read from the shared
    cache's ``seq_lens`` at the slots, which is what lets one traced
    program serve every mix of sequence lengths.
    """

    slots: jax.Array  # [b] int32

    @property
    def batch_size(self) -> int:
        return self.slots.shape[0]

    def tree_flatten(self):
        return ((self.slots,), None)

    @classmethod
    def tree_unflatten(cls, _aux, children):
        return cls(*children)

    @staticmethod
    def of(slots) -> "DecodeBatch":
        return DecodeBatch(jnp.asarray(np.asarray(slots), jnp.int32))


def magi_attn_decode(
    q: jax.Array,  # [b, hq, head_dim] the step's query token per sequence
    cache: PagedKVCache,
    batch: DecodeBatch,
    *,
    num_splits: int | None = None,
    scale: float | None = None,
    softcap: float = 0.0,
    out_dtype=None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Public decode attention over a paged cache (split-KV + LSE merge).

    Attends each query to its sequence's ``seq_lens[slot]`` cached
    tokens. For standard causal decode, :func:`append_kv` the step's own
    K/V first, then call this. Returns ``(out [b, hq, d], lse [b, hq])``.
    """
    return decode_attn_paged(
        q,
        cache,
        batch.slots,
        num_splits=num_splits,
        scale=scale,
        softcap=softcap,
        out_dtype=out_dtype,
        interpret=interpret,
    )


def prefill_into_cache(
    q: jax.Array,  # [t, hq, head_dim] the prompt's queries
    k: jax.Array,  # [t, hk, head_dim]
    v: jax.Array,
    cache: PagedKVCache,
    slot,
    *,
    length=None,  # traced valid prompt length (None = all t rows)
    scale: float | None = None,
    softcap: float = 0.0,
    out_dtype=None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, PagedKVCache]:
    """Causal prefill through the existing flex-attention path, with the
    prompt's KV written into the slot's pages — prefill and decode share
    one storage layout, so the decode step that follows reads exactly
    what prefill computed against.

    Returns ``(out [t, hq, d], lse [t, hq], updated cache)``. With a
    traced ``length`` the attention still runs over the padded ``t`` rows
    (the mask is static); rows at or past ``length`` are garbage the
    caller discards — only the CACHE write is masked to ``length``.
    """
    from ..ops import flex_flash_attn_func

    t = q.shape[0]
    with named_scope("magi_prefill_attn"):
        out, lse = flex_flash_attn_func(
            q,
            k,
            v,
            [(0, t)],
            [(0, t)],
            [int(AttnMaskType.CAUSAL)],
            scale=scale,
            softcap=softcap,
            out_dtype=out_dtype,
            interpret=interpret,
        )
    with named_scope("magi_kvcache_prefill_write"):
        cache = write_prefill_kv(cache, slot, k, v, length=length)
    return out, lse, cache


def continue_prefill_into_cache(
    q: jax.Array,  # [t, hq, head_dim] this CHUNK's queries
    k: jax.Array,  # [t, hk, head_dim]
    v: jax.Array,
    cache: PagedKVCache,
    slot,
    *,
    start: int,  # host-side: tokens already written for this slot
    scale: float | None = None,
    softcap: float = 0.0,
    out_dtype=None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, PagedKVCache]:
    """One chunked-prefill step: the cross path (ISSUE 9).

    Writes the chunk's KV at the slot's current position, then runs
    causal flex attention of the chunk's queries against the WHOLE
    written history gathered from the cache — bottom-right-aligned
    CAUSAL over q ``(0, t)`` x k ``(0, start + t)`` allows key ``j`` for
    chunk row ``i`` iff ``j <= start + i``, i.e. exactly what token
    ``start + i`` of a single-shot prefill would see. This one function
    serves both long-prompt chunking and shared-prefix continuation (a
    forked sequence's suffix attending to the shared prefix KV it never
    computed).

    ``start`` is HOST state (the engine's committed length; must equal
    ``seq_lens[slot]``): the gather width and mask ranges are static per
    (start, t). Compile-reuse shape: each chunk of ONE prompt is its own
    geometry (the history grows), which is inherent to the static-mask
    flex kernel — the bottom-right-aligned CAUSAL bound needs the exact
    ``start + t`` endpoint, so the width cannot be bucketed without
    shifting the diagonal. Reuse happens ACROSS requests and steps: the
    scheduler feeds fixed-size chunks at aligned starts, so a
    steady-state multi-tenant cadence replays the same (start, t)
    programs instead of compiling per request.
    """
    t = q.shape[0]
    start = int(start)
    with named_scope("magi_kvcache_prefill_write"):
        cache = write_prefill_kv(cache, slot, k, v)
    kc, vc = gather_kv(cache, slot, max_len=start + t)
    from ..ops import flex_flash_attn_func

    with named_scope("magi_prefill_attn"):
        out, lse = flex_flash_attn_func(
            q,
            kc,
            vc,
            [(0, t)],
            [(0, start + t)],
            [int(AttnMaskType.CAUSAL)],
            scale=scale,
            softcap=softcap,
            out_dtype=out_dtype,
            interpret=interpret,
        )
    return out, lse, cache


class ServingEngine:
    """Minimal continuous-batching host loop over one paged cache.

    Host-side object: owns the allocator and the (functional) device
    cache, exposes admit/step/free. The engine methods themselves are
    host loops (slot bookkeeping, reservation growth, telemetry) and are
    NOT jittable; the jit boundary is the pure ops they drive — in
    production, wrap ``append_kv`` + :func:`magi_attn_decode` in one
    ``jax.jit`` with a donated cache and keep the engine's bookkeeping
    outside it.
    """

    def __init__(
        self,
        *,
        num_pages: int,
        num_kv_heads: int,
        head_dim: int,
        page_size: int | None = None,
        max_seqs: int = 64,
        max_pages_per_seq: int | None = None,
        dtype=jnp.bfloat16,
        max_admission_evictions: int = 4,
        prefix_sharing: bool = True,
        decode_attn_fn=None,
        register_flight_memory: bool = True,
    ):
        from .. import env

        if page_size is None:
            page_size = env.page_size()
        if max_pages_per_seq is None:
            max_pages_per_seq = max(num_pages // max(max_seqs, 1), 1)
        self.cache = make_paged_kv_cache(
            num_pages,
            page_size,
            num_kv_heads,
            head_dim,
            max_seqs=max_seqs,
            max_pages_per_seq=max_pages_per_seq,
            dtype=dtype,
        )
        self.allocator = PageAllocator(
            num_pages, page_size, max_seqs, max_pages_per_seq
        )
        # shared-prefix trie (ISSUE 9). Inert until an admission carries
        # host token ids — tokenless admissions behave exactly as before
        self.prefix: PrefixCache | None = (
            PrefixCache(page_size) if prefix_sharing else None
        )
        self._lengths: dict[int, int] = {}
        self._priorities: dict[int, int] = {}
        self._tokens: dict[int, tuple[int, ...]] = {}
        # slot -> (shared FULL prefix pages, their token count): the
        # cascade grouping key (set on fork, or at commit_prefix)
        self._slot_prefix: dict[int, tuple[tuple[int, ...], int]] = {}
        self.max_admission_evictions = int(max_admission_evictions)
        # ISSUE 12: a pluggable attention realization for decode_step —
        # ``(q, cache, slots, **kw) -> (out, lse)``. A decode-tier
        # replica substitutes the KV-head-sharded TP decode
        # (serving/distributed.tp_decode_attn) here while keeping every
        # host concern (reservation growth, CoW, append, telemetry)
        # from THIS engine. None = the standard flat/cascade paths.
        self._decode_attn_fn = decode_attn_fn
        # what the last decode_step resolved (split count, cascade
        # grouping): the scheduler reads this to tag per-request
        # decode_step trace spans (ISSUE 11) — plain host state, not
        # gated on telemetry
        self.last_decode_info: dict = {}
        # ditto for the last prefill call (program label + chunk
        # geometry): the launch ledger (ISSUE 16) reads it
        self.last_prefill_info: dict = {}
        # and for the last unified tick (ISSUE 17): the scheduler reads
        # the resolved tick geometry/label to tag per-request spans and
        # assert its launch census
        self.last_tick_info: dict = {}
        self._flight = trace.get_flight_recorder()
        # OOM forensics (ISSUE 14): every flight dump embeds this
        # engine's memory ledger + pool fragmentation map (weakly held —
        # a retired engine unregisters itself by dying); pool_exhausted
        # backpressure arms a deferred dump once per pressure episode.
        # A TieredEngine registers ONE aggregated per-tier source
        # instead and opts its member engines out here
        if register_flight_memory:
            self._flight.register_memory_source("engine", self)
        # numerics forensics (ISSUE 18): (re-)attach the process-global
        # value census to the CURRENT recorder so dumps carry a
        # `numerics` section even after a reset_flight_recorder(), and
        # count decode batches for the shadow-sampled drift sentinel
        # (every Nth batch re-computed through the f32 reference and
        # scored against production output — MAGI_ATTENTION_SHADOW_
        # SAMPLE_RATE, 0 = off)
        numerics.ensure_flight_registration()
        self._shadow_counter = 0
        self._pool_exhausted_armed = False
        # live exposition (ISSUE 11): one scrape thread per process when
        # MAGI_ATTENTION_METRICS_PORT is set; no-op (None) by default
        exposition.ensure_metrics_server()
        self._record_pool()

    # -- admission / retirement (host) --

    def admit(
        self,
        num_tokens: int,
        *,
        priority: int = 0,
        tokens: "Sequence[int] | None" = None,
    ) -> AdmissionResult:
        """Reserve a slot + pages for a sequence of ``num_tokens`` prompt
        tokens (plus later decode growth via :meth:`reserve_growth`).

        ``tokens`` (ISSUE 9): the prompt's host-side token ids. With
        prefix sharing enabled, the longest already-resident prefix is
        installed by REFERENCE (``PageAllocator.fork`` — a refcount
        bump, no copy, ``seq_lens`` pre-set to the match); only the
        remaining tokens need pages and prefill. The match length comes
        back as ``AdmissionResult.prefix_len``. Tokenless admissions
        behave exactly as before.

        Returns a typed :class:`AdmissionResult` — NEVER raises on
        resource pressure (ISSUE 8). Under pressure the policy is: drop
        least-recently-used UNSHARED prefix-cache pages first (cached
        KV is a disposable optimization, live sequences are not), then
        the bounded evict-lowest-priority-then-retry pass over live
        sequences whose ``priority`` is strictly below the incoming
        one; if that still doesn't fit, the verdict is backpressure
        (``magi_admission_rejected{reason=}``).
        """
        need = max(self.allocator.pages_needed(num_tokens), 1)
        if need > self.allocator.max_pages_per_seq:
            # no amount of evicting makes an over-long sequence fit
            res = AdmissionResult(False, None, "too_long")
            self._note_admission(res)
            return res
        tokens = tuple(int(t) for t in tokens) if tokens is not None else None
        evicted: list[int] = []
        while True:
            # re-match every round: a prefix eviction below may have
            # released pages an earlier match pointed at
            match = (
                self.prefix.match(tokens)
                if self.prefix is not None and tokens is not None
                else None
            )
            if match is not None and match.hit:
                if self.allocator.can_fork(match.pages, num_tokens):
                    from ..resilience import chaos

                    try:
                        slot, pages = self.allocator.fork(
                            match.pages, num_tokens
                        )
                    except (chaos.ChaosInjectedError, PageAllocatorError):
                        # raced/injected allocator failure after the
                        # can_fork probe — degrade to backpressure,
                        # like the allocate path (admission never
                        # raises on resource pressure). Deliberately
                        # NOT bare RuntimeError: unrelated errors must
                        # surface, not masquerade as pressure
                        res = AdmissionResult(
                            False, None, "alloc_error", tuple(evicted)
                        )
                        self._note_admission(res)
                        self._record_pool()
                        return res
                    try:
                        self.cache = assign_block_table(
                            self.cache, slot, pages, keep_len=match.length
                        )
                    except Exception:
                        self.allocator.free(slot)
                        self._record_pool()
                        raise
                    return self._finish_admit(
                        slot, priority, tokens, evicted,
                        prefix_len=match.length,
                        shared_full=(
                            match.pages[: match.full_pages],
                            match.full_pages * self.allocator.page_size,
                        ),
                    )
            elif self.allocator.can_admit(num_tokens):
                from ..resilience import chaos

                try:
                    slot, pages = self.allocator.allocate(num_tokens)
                except (chaos.ChaosInjectedError, PageAllocatorError):
                    # raced/injected allocator failure after the
                    # can_admit probe — degrade to backpressure
                    # (narrowed like the fork path: unrelated
                    # RuntimeErrors must surface, not masquerade)
                    res = AdmissionResult(
                        False, None, "alloc_error", tuple(evicted)
                    )
                    self._note_admission(res)
                    self._record_pool()
                    return res
                try:
                    self.cache = assign_block_table(self.cache, slot, pages)
                except Exception:
                    # device-side install failed: roll the allocator
                    # back so the reservation is not leaked
                    self.allocator.free(slot)
                    self._record_pool()
                    raise
                return self._finish_admit(slot, priority, tokens, evicted)
            # pressure: cached-but-unreferenced prefix pages go first —
            # but ONLY when pages are actually the bottleneck. A slot
            # shortage (or a raced alloc failure) cannot be fixed by
            # dropping cached KV, and flushing the trie then would
            # destroy every future shared-prefix hit for nothing.
            shared = len(match.pages) if match is not None else 0
            free = self.allocator.num_pages - self.allocator.pages_in_use
            deficit = need - shared - free
            if (
                deficit > 0
                and self.prefix is not None
                and self.allocator.active_seqs < self.allocator.max_seqs
            ):
                freed = self.prefix.evict(self.allocator, deficit)
                if freed > 0:
                    telemetry.record_prefix_eviction(
                        freed, self.prefix.resident_pages
                    )
                    continue
            if len(evicted) >= self.max_admission_evictions:
                break  # bounded: give up rather than churn the pool
            victim = self._eviction_candidate(int(priority))
            if victim is None:
                break
            self.free(victim)
            evicted.append(victim)
        reason = (
            "no_free_slot"
            if self.allocator.active_seqs >= self.allocator.max_seqs
            else "pool_exhausted"
        )
        res = AdmissionResult(False, None, reason, tuple(evicted))
        self._note_admission(res)
        self._record_pool()
        return res

    def _note_admission(self, res: AdmissionResult) -> None:
        """Shared admission telemetry: registry counters (gated on the
        telemetry flag) + the always-on flight recorder's rejection-storm
        detector (ISSUE 11 — a run of consecutive rejections arms a
        post-mortem dump). ISSUE 14 adds OOM forensics: the FIRST
        ``pool_exhausted`` verdict of a pressure episode arms a
        deferred flight dump tagged with the triggering admission's
        trace id (the scheduler's tick-end flush writes it, ledger +
        fragmentation snapshot embedded); the arm re-enables once an
        admission succeeds again."""
        telemetry.record_admission(res)
        self._flight.note_admission(res.admitted, res.reason)
        if res.admitted:
            self._pool_exhausted_armed = False
        elif res.reason == "pool_exhausted" and not self._pool_exhausted_armed:
            self._pool_exhausted_armed = True
            cur = trace.current_trace()
            self._flight.trigger(
                "pool_exhausted",
                immediate=False,
                trace_id=cur[0] if cur is not None else None,
                pages_in_use=self.allocator.pages_in_use,
                pages_total=self.allocator.num_pages,
                active_seqs=self.allocator.active_seqs,
            )

    def _finish_admit(
        self,
        slot: int,
        priority: int,
        tokens: tuple[int, ...] | None,
        evicted: list[int],
        *,
        prefix_len: int = 0,
        shared_full: tuple[tuple[int, ...], int] | None = None,
    ) -> AdmissionResult:
        """Shared tail of both admission paths: bookkeeping + telemetry."""
        self._priorities[slot] = int(priority)
        if tokens is not None:
            self._tokens[slot] = tokens
            if self.prefix is not None:
                # only admissions that actually consulted the trie count
                # toward the hit/miss series — a disabled prefix cache
                # must not report a phantom 0% hit rate
                telemetry.record_prefix_lookup(
                    hit=prefix_len > 0, matched_tokens=prefix_len
                )
        if prefix_len:
            self._lengths[slot] = prefix_len
        if shared_full is not None and shared_full[0]:
            self._slot_prefix[slot] = (tuple(shared_full[0]), shared_full[1])
        res = AdmissionResult(
            True, slot, "ok", tuple(evicted), prefix_len=prefix_len
        )
        self._note_admission(res)
        self._record_pool()
        return res

    def _eviction_candidate(self, incoming_priority: int) -> int | None:
        """Lowest-priority live slot strictly below the incoming
        priority (ties -> lowest slot id, deterministic); None when
        nothing is evictable."""
        candidates = [
            (p, s)
            for s, p in self._priorities.items()
            if p < incoming_priority
        ]
        if not candidates:
            return None
        return min(candidates)[1]

    def reserve_growth(self, slot: int, total_tokens: int) -> None:
        """Extend a slot's page reservation to ``total_tokens`` (prompt +
        planned decode budget) before stepping past its current pages."""
        pages = self.allocator.extend(slot, total_tokens)
        self.cache = assign_block_table(self.cache, slot, pages, keep_len=True)
        self._record_pool()

    def free(self, slot: int) -> None:
        """Retire a sequence: one page reference dropped per page (a
        prefix page still held by the trie or by sibling forks stays
        resident — the refcount decrement ISSUE 9 specifies), slot
        reusable. A double free raises the allocator's typed
        ``InvalidFreeError``.

        Exception-safe ordering: the device-side slot reset is computed
        BEFORE the allocator mutates — if it throws, the allocator still
        owns the pages and nothing is half-freed; once the allocator has
        released them, the reset commits unconditionally."""
        fresh = reset_slot(self.cache, slot)
        self.allocator.free(slot)
        self.cache = fresh
        self._lengths.pop(slot, None)
        self._priorities.pop(slot, None)
        self._tokens.pop(slot, None)
        self._slot_prefix.pop(slot, None)
        self._record_pool()

    # -- device steps --

    def _ensure_reserved(self, slot: int, total_tokens: int) -> None:
        """Grow the slot's page reservation to cover ``total_tokens``
        before any write could land past its installed pages — a write
        beyond the reservation would otherwise scatter onto pages owned
        by OTHER sequences (unreserved block-table entries are 0, the
        first-admitted sequence's page)."""
        if (
            self.allocator.pages_needed(total_tokens)
            > self.allocator.reserved_pages(slot)
        ):
            self.reserve_growth(slot, total_tokens)

    def _ensure_writable(self, slot: int, start: int) -> None:
        """Copy-on-write split (ISSUE 9) before a write at position
        ``start``: when the write lands MID-page (``start % page_size
        != 0``) and that page is shared (refcount > 1 — a forked partial
        tail, or the registrant's own tail after the trie pinned it),
        give the slot a private copy first. Writes that start on a page
        boundary land on a fresh page from the slot's own reservation
        and never need a split; full shared prefix pages are therefore
        never copied.

        Atomicity: ``cow_page`` validates (and can refuse on pool
        exhaustion) before any bookkeeping moves; the device-side copy
        and table swap are infallible index ops on the committed ids."""
        ps = self.allocator.page_size
        if start <= 0 or start % ps == 0 or start >= self.cache.max_seq_len:
            return
        idx = start // ps
        pages = self.allocator.slot_pages(slot)
        if idx >= len(pages):
            return  # page not reserved yet: growth installs a fresh one
        if self.allocator.page_ref(pages[idx]) <= 1:
            return  # private already
        old, new = self.allocator.cow_page(slot, idx)
        with named_scope("magi_kvcache_cow"):
            self.cache = swap_block_table_page(
                copy_page(self.cache, old, new), slot, idx, new
            )
        telemetry.record_prefix_cow()

    def prefill(self, q, k, v, slot: int, **kw):
        """Prefill prompt rows into ``slot``; returns the prefill out/lse.

        ISSUE 9 generalizes this to a *continuation-capable, chunked*
        prefill:

        - a slot with committed tokens (a shared-prefix fork, or a prior
          chunk) takes the cross path: each chunk's KV is written, then
          its queries attend the WHOLE gathered history causally
          (:func:`continue_prefill_into_cache`) — output rows are
          bit-comparable to the same rows of a single-shot prefill;
        - prompts longer than ``MAGI_ATTENTION_PREFILL_CHUNK`` are split
          into chunk-sized steps internally (unset = single shot), so a
          long prompt never occupies the engine for one giant kernel —
          the :class:`~magiattention_tpu.serving.scheduler.Scheduler`
          instead feeds one chunk per scheduler step to interleave with
          decode;
        - if the admission carried token ids and this call completes the
          prompt, the pages are auto-registered as shareable
          (:meth:`commit_prefix`).

        A ``length=`` padded prompt is only supported on the one-shot
        path (chunk continuation needs the true rows).

        Exception-safe (ISSUE 8 satellite): a failure mid prefill —
        attention crash, cache-write crash, injected ``prefill_error``
        chaos — releases the half-admitted slot entirely (pages back to
        the pool, bookkeeping cleared) before re-raising, so the next
        admission reuses those pages instead of leaking them."""
        from .. import env
        from ..resilience import chaos

        length = kw.pop("length", None)
        t = q.shape[0]
        wrote = t if length is None else int(length)
        start = self._lengths.get(slot, 0)
        if t == 0 and length is None:
            # fully-cached prompt (the shared prefix covered every
            # token): nothing to write or attend — just the hooks
            toks = self._tokens.get(slot)
            if toks is not None and start >= len(toks):
                self.commit_prefix(slot)
            return (
                jnp.zeros((0, q.shape[1], q.shape[2]), q.dtype),
                jnp.zeros((0, q.shape[1]), jnp.float32),
            )
        chunk = env.prefill_chunk()
        # reservation growth and the CoW split stay OUTSIDE the fault
        # cleanup: a refused extension/split (transient pool exhaustion)
        # mutates nothing — both are check-before-pop — and must leave
        # the slot's committed KV intact, exactly like the identical
        # error from decode_step's growth path (resource pressure is an
        # operating condition, not a reason to destroy the sequence)
        self._ensure_reserved(slot, start + wrote)
        self._ensure_writable(slot, start)
        label = telemetry.prefill_program_label(start, wrote)
        self.last_prefill_info = {
            "program": label,
            "start": start,
            "tokens": wrote,
        }
        try:
            chaos.maybe_fail("prefill_error")
            with telemetry.program(label):
                if start == 0 and (chunk is None or t <= chunk):
                    out, lse, new_cache = prefill_into_cache(
                        q, k, v, self.cache, slot, length=length, **kw
                    )
                    self.cache = new_cache
                else:
                    assert length is None, (
                        "chunked/continuation prefill requires unpadded "
                        "prompts (length=None); pre-slice the valid rows"
                    )
                    out, lse = self._chunked_prefill(
                        q, k, v, slot, start, chunk, **kw
                    )
        except Exception:
            self._release_after_fault(slot)
            raise
        self._lengths[slot] = start + wrote
        telemetry.record_prefill(wrote)
        toks = self._tokens.get(slot)
        if toks is not None and self._lengths[slot] >= len(toks):
            self.commit_prefix(slot)
        return out, lse

    def _chunked_prefill(self, q, k, v, slot, start, chunk, **kw):
        """Drive ``continue_prefill_into_cache`` chunk by chunk; each
        chunk's cache write commits before the next chunk attends (the
        cross path reads it back). A fault mid-loop reaches
        :meth:`prefill`'s cleanup, which tears the slot down whole."""
        t = q.shape[0]
        step = int(chunk) if chunk else t
        outs, lses = [], []
        pos = 0
        while pos < t:
            n = min(step, t - pos)
            o, l, new_cache = continue_prefill_into_cache(
                q[pos : pos + n],
                k[pos : pos + n],
                v[pos : pos + n],
                self.cache,
                slot,
                start=start + pos,
                **kw,
            )
            self.cache = new_cache
            outs.append(o)
            lses.append(l)
            pos += n
        if len(outs) == 1:
            return outs[0], lses[0]
        return jnp.concatenate(outs, axis=0), jnp.concatenate(lses, axis=0)

    def commit_prefix(self, slot: int) -> int:
        """Register the slot's prefilled pages as a shareable prefix
        (host trie + one allocator reference per newly recorded page).
        Auto-invoked by :meth:`prefill` when the admission's token ids
        are fully written; call manually after driving the pure ops
        yourself. Returns the number of pages newly pinned."""
        if self.prefix is None:
            return 0
        toks = self._tokens.get(slot)
        n = min(self._lengths.get(slot, 0), len(toks) if toks else 0)
        if not toks or n == 0:
            return 0
        pages = self.allocator.slot_pages(slot)
        newly = self.prefix.register(toks[:n], pages, self.allocator)
        full = n // self.allocator.page_size
        if full and slot not in self._slot_prefix:
            # fresh registrant: its own leading full pages ARE the trie's
            # resident copy — the cascade group key. (A forked slot keeps
            # the key of the prefix it shares with its siblings.)
            self._slot_prefix[slot] = (
                tuple(pages[:full]),
                full * self.allocator.page_size,
            )
        telemetry.record_prefix_registered(
            newly, self.prefix.resident_pages
        )
        self._record_pool()
        return newly

    def _release_after_fault(self, slot: int) -> None:
        """Tear a faulted slot all the way down (best-effort, never
        raises over the original fault): allocator pages returned, slot
        length zeroed, bookkeeping dropped. Arms a flight-recorder dump
        (deferred, ISSUE 11): when a scheduler drives this engine, its
        tick loop records the aborted tick and flushes — the post-mortem
        contains the tick the fault killed."""
        self._flight.trigger("engine_fault", immediate=False, slot=slot)
        try:
            self.free(slot)
        except Exception:
            from ..telemetry.logger import get_logger

            get_logger("resilience").warning(
                "fault cleanup could not release slot %s", slot
            )
        self._lengths.pop(slot, None)
        self._priorities.pop(slot, None)
        self._tokens.pop(slot, None)
        self._slot_prefix.pop(slot, None)
        self._record_pool()

    def decode_step(self, q, k_new, v_new, slots, *, cascade=None, **kw):
        """One continuous-batching decode step: append each sequence's
        new KV, then attend over the whole history (the new token
        included — standard causal decode). Page reservations grow
        automatically when a sequence crosses into an unreserved page; a
        sequence appending into a still-shared tail page gets its
        copy-on-write split here, before the write.

        ``cascade`` (ISSUE 9): ``None`` follows ``MAGI_ATTENTION_CASCADE``
        (``auto`` = two-level cascade attention whenever >= 2 batch
        members share a resident full-page prefix), ``True``/``'on'``
        forces cascade for every prefix-carrying sequence (singleton
        groups included — the parity-test mode), ``False``/``'off'``
        forces the flat split-KV path. Parity between the two paths is
        ``make sched-check``'s acceptance criterion."""
        from .. import env

        batch = DecodeBatch.of(slots)
        slot_list = np.asarray(slots).tolist()
        for s in slot_list:
            self._ensure_reserved(s, self._lengths.get(s, 0) + 1)
            self._ensure_writable(s, self._lengths.get(s, 0))
        if cascade is None:
            mode = env.cascade_mode()
        elif isinstance(cascade, str):
            mode = cascade
        else:
            mode = "on" if cascade else "off"
        groups = []
        if mode != "off" and self._slot_prefix and self._decode_attn_fn is None:
            groups = plan_cascade_groups(
                self._slot_prefix,
                slot_list,
                min_group=1 if mode == "on" else 2,
            )
        label = telemetry.decode_program_label(batch.batch_size)
        with telemetry.program(label):
            with named_scope("magi_kvcache_append"):
                self.cache = append_kv(
                    self.cache, batch.slots, k_new, v_new
                )
            for s in slot_list:
                self._lengths[s] = self._lengths.get(s, 0) + 1
            if groups:
                # per-phase split resolution happens inside the cascade
                # (prefix tables and suffix tables have their own
                # widths); the num_splits gauge reports 0 = "per phase"
                out, lse = cascade_decode_attn(
                    q,
                    self.cache,
                    np.asarray(slot_list),
                    groups,
                    num_splits=kw.get("num_splits"),
                    scale=kw.get("scale"),
                    softcap=kw.get("softcap", 0.0),
                    out_dtype=kw.get("out_dtype"),
                    interpret=kw.get("interpret"),
                )
                resolved = 0
            elif self._decode_attn_fn is not None:
                # substituted realization (TP decode over the sharded
                # pool): split resolution happens inside the substitute,
                # so the num_splits gauge reads 0 = "externally
                # resolved", like the cascade per-phase convention
                out, lse = self._decode_attn_fn(
                    q, self.cache, batch.slots, **kw
                )
                resolved = 0
            else:
                # resolve the split count ONCE (fingerprint + cache
                # lookup) and hand the concrete int down — decode is the
                # per-token hot loop
                kw["num_splits"] = resolved = resolve_num_splits(
                    kw.get("num_splits"), self.cache, batch.batch_size,
                    q.shape[1],
                )
                out, lse = magi_attn_decode(q, self.cache, batch, **kw)
        # per-step resolution facts for the request tracer (ISSUE 11):
        # the scheduler tags each member's decode_step span with them
        self.last_decode_info = {
            "batch": batch.batch_size,
            "program": label,
            "num_splits": resolved,
            "cascade_groups": len(groups),
            "cascade_group_of": {
                int(slot_list[pos]): gi
                for gi, g in enumerate(groups)
                for pos in g.members
            },
        }
        telemetry.record_decode_step(
            batch_size=batch.batch_size,
            num_splits=resolved,
            max_seq_len=max(
                (self._lengths.get(s, 0) for s in slot_list), default=0
            ),
            cascade_groups=len(groups),
        )
        self._maybe_shadow_check(q, slot_list, out, lse, kw)
        return out, lse

    def _maybe_shadow_check(self, q, slot_list, out, lse, kw) -> None:
        """Shadow-sampled drift sentinel (ISSUE 18): every Nth decode
        batch (``MAGI_ATTENTION_SHADOW_SAMPLE_RATE``; 0 = off) is
        re-computed through :func:`decode_reference` — the f32
        single-split jnp oracle that lives OUTSIDE every resilience
        hook — and scored against the production output with the
        error-budget oracle. Every check lands in the
        ``magi_numerics_shadow_*`` series and the census ring; a budget
        breach arms a DEFERRED ``numeric_drift`` flight dump tagged
        with the live trace id (the scheduler's tick-end flush writes
        it, so the dump carries the faulting tick too). Host-side only:
        the shadow never changes a plan, a key, or the production
        output."""
        from .. import env

        rate = env.shadow_sample_rate()
        if rate <= 0:
            return
        self._shadow_counter += 1
        if self._shadow_counter % rate:
            return
        if isinstance(out, jax.core.Tracer):
            # decode_step traced into a larger program: the sentinel
            # needs concrete outputs, so this sample is skipped (the
            # scheduler's host loop — the production caller — is eager)
            return
        slots = np.asarray(slot_list)
        bt = self.cache.block_tables[slots]
        seq_lens = self.cache.seq_lens[slots]
        ref_out, ref_lse = decode_reference(
            q,
            self.cache,
            bt,
            seq_lens,
            scale=kw.get("scale"),
            softcap=kw.get("softcap", 0.0),
        )
        report = numerics.divergence_report(
            ref_out, out, ref_lse=ref_lse, test_lse=lse
        )
        try:
            budget = numerics.budget_for_dtype(report.dtype)
        except ValueError:
            # exotic out dtype without a calibrated row: score against
            # the f32 budget rather than silently skipping the check
            budget = numerics.budget_for_dtype("float32")
        violations = budget.violations(report)
        breached = bool(violations)
        telemetry.record_shadow_check(
            report.out_max_ulp, breached=breached
        )
        ctx = trace.current_trace()
        record = {
            "batch": len(slot_list),
            "trace_id": ctx[0] if ctx else None,
            "rid": ctx[1] if ctx else None,
            "breached": breached,
            "violations": list(violations),
            "report": report.to_json(),
        }
        numerics.get_numerics_census().note_shadow(
            record, breached=breached
        )
        if breached:
            self._flight.trigger(
                "numeric_drift",
                immediate=False,
                trace_id=ctx[0] if ctx else None,
                rid=ctx[1] if ctx else None,
                violations=list(violations),
                max_ulp=report.out_max_ulp,
                dominant=report.dominant,
            )

    def unified_tick(
        self,
        decode_items,
        prefill_items,
        *,
        cascade=None,
        num_splits: int | None = None,
        scale: float | None = None,
        softcap: float = 0.0,
        interpret: bool | None = None,
    ):
        """One-kernel serving tick (ISSUE 17 tentpole): every decode step
        AND every prefill chunk of this tick runs as rows of a single
        :func:`~.unified_tick.unified_tick_attn` launch over the shared
        paged pool, then demuxes back into per-request outputs.

        - ``decode_items``: ``[(slot, q [hq, d], k [hk, d], v [hk, d])]``
          — one new token per decoding sequence, appended then attended
          over the whole history (same contract as :meth:`decode_step`).
        - ``prefill_items``: ``[(slot, q [t, hq, d], k, v)]`` — one chunk
          per prefilling sequence at the slot's committed position; a
          ``t = 0`` item runs only the completion hooks (fully-cached
          prompt), exactly like :meth:`prefill`'s early return.

        Returns ``(decode_results, prefill_results)`` aligned with the
        inputs: decode entries ``(out [hq, d], lse [hq])``, prefill
        entries ``(out [t, hq, d], lse [t, hq])`` — numerically the
        split-KV realization of the per-request paths (same masked
        softmax; the reduction ORDER differs with the table width, so
        parity is float-tight, not bitwise).

        Cascade (``MAGI_ATTENTION_CASCADE`` semantics, decode members
        only): a shared-prefix group's members each contribute a suffix
        row plus a prefix row over the SAME shared pages inside the one
        launch; the pair is merged through ``ops/correction`` before
        demux, so the group still batches its prefix partial once per
        member without a second program.

        Faults mirror the per-request paths: a device-phase failure
        releases every prefill item's slot (:meth:`_release_after_fault`)
        and re-raises; decode slots are kept, like :meth:`decode_step`.
        A ``PageAllocatorError`` from reservation growth propagates
        untouched (check-before-pop: nothing is half-committed)."""
        from .. import env
        from ..resilience import chaos
        from ..ops.block_sparse import TickEnumeration

        if self._decode_attn_fn is not None:
            raise ValueError(
                "unified_tick does not compose with a substituted decode "
                "realization (_decode_attn_fn): the tick kernel IS the "
                "attention — TP decode tiers keep the per-request path"
            )
        ps = self.allocator.page_size
        decode_slots = [int(it[0]) for it in decode_items]
        prefill_slots = [int(it[0]) for it in prefill_items]
        overlap = set(decode_slots) & set(prefill_slots)
        if overlap:
            raise ValueError(
                f"unified_tick: slots {sorted(overlap)} appear as both "
                "decode and prefill items — a sequence is in exactly one "
                "phase per tick"
            )
        # host phase first (reservation growth + CoW splits), before any
        # device work — identical ordering to decode_step / prefill, and
        # BEFORE the enumeration reads the slot page lists (a CoW swap
        # changes a page id)
        for slot in decode_slots:
            self._ensure_reserved(slot, self._lengths.get(slot, 0) + 1)
            self._ensure_writable(slot, self._lengths.get(slot, 0))
        prefill_meta = []  # (slot, start, t) aligned with prefill_items
        for slot, q, _k, _v in prefill_items:
            t = int(q.shape[0])
            start = self._lengths.get(slot, 0)
            prefill_meta.append((slot, start, t))
            if t:
                self._ensure_reserved(slot, start + t)
                self._ensure_writable(slot, start)
        if cascade is None:
            mode = env.cascade_mode()
        elif isinstance(cascade, str):
            mode = cascade
        else:
            mode = "on" if cascade else "off"
        groups = []
        if mode != "off" and self._slot_prefix and decode_slots:
            groups = plan_cascade_groups(
                self._slot_prefix,
                decode_slots,
                min_group=1 if mode == "on" else 2,
            )
        group_of_pos = {
            pos: g for g in groups for pos in g.members
        }
        # -- compose the tick enumeration (host) --
        tick = TickEnumeration(ps)
        q_parts = []  # row-ordered [n, hq, d] pieces
        for j, (slot, q1, _k, _v) in enumerate(decode_items):
            new_len = self._lengths.get(slot, 0) + 1
            pages = self.allocator.slot_pages(slot)
            need = -(-new_len // ps)
            g = group_of_pos.get(j)
            if g is not None:
                ns = len(g.shared_pages)
                tick.add_decode(
                    ("d", j),
                    tuple(pages[ns:need]),
                    new_len - g.prefix_len,
                    prefix_pages=tuple(g.shared_pages),
                    prefix_len=g.prefix_len,
                )
                # prefix row precedes the main row; both attend with
                # this member's query
                q_parts.append(q1[None])
                q_parts.append(q1[None])
            else:
                tick.add_decode(("d", j), tuple(pages[:need]), new_len)
                q_parts.append(q1[None])
        prefill_rows = 0
        for j, (slot, q, _k, _v) in enumerate(prefill_items):
            _slot, start, t = prefill_meta[j]
            if t == 0:
                continue
            pages = self.allocator.slot_pages(slot)
            need = -(-(start + t) // ps)
            tick.add_prefill(("p", j), tuple(pages[:need]), start, t)
            q_parts.append(q)
            prefill_rows += t
        if tick.num_rows == 0:
            # nothing to launch: run the zero-chunk completion hooks
            # (fully-cached prompts) and return empty results
            prefill_results = []
            for j, (slot, q, _k, _v) in enumerate(prefill_items):
                toks = self._tokens.get(slot)
                if toks is not None and self._lengths.get(slot, 0) >= len(
                    toks
                ):
                    self.commit_prefix(slot)
                prefill_results.append(
                    (
                        jnp.zeros((0, q.shape[1], q.shape[2]), q.dtype),
                        jnp.zeros((0, q.shape[1]), jnp.float32),
                    )
                )
            self.last_tick_info = {
                "program": None,
                "rows": 0,
                "entries": 0,
                "num_splits": 0,
                "decode_batch": 0,
                "prefill_rows": 0,
                "cascade_groups": 0,
                "cascade_group_of": {},
            }
            return [], prefill_results
        rows, entries = tick.finalize()
        hq = int(q_parts[0].shape[1])
        head_dim = int(q_parts[0].shape[2])
        resolved = resolve_tick_splits(
            num_splits, self.cache, rows, entries, hq,
            prefill_rows=prefill_rows,
        )
        label = telemetry.tick_program_label(rows, entries, resolved)
        # -- device phase: ONE program label for the whole tick --
        try:
            if any(t for _s, _lo, t in prefill_meta):
                chaos.maybe_fail("prefill_error")
            with telemetry.program(label):
                if decode_items:
                    batch = DecodeBatch.of(decode_slots)
                    k_new = jnp.stack([it[2] for it in decode_items])
                    v_new = jnp.stack([it[3] for it in decode_items])
                    with named_scope("magi_kvcache_append"):
                        self.cache = append_kv(
                            self.cache, batch.slots, k_new, v_new
                        )
                    for s in decode_slots:
                        self._lengths[s] = self._lengths.get(s, 0) + 1
                for j, (slot, _q, k, v) in enumerate(prefill_items):
                    if prefill_meta[j][2] == 0:
                        continue
                    with named_scope("magi_kvcache_prefill_write"):
                        self.cache = write_prefill_kv(self.cache, slot, k, v)
                q_rows = jnp.concatenate(q_parts, axis=0)
                pad = rows - q_rows.shape[0]
                if pad:
                    q_rows = jnp.concatenate(
                        [
                            q_rows,
                            jnp.zeros((pad, hq, head_dim), q_rows.dtype),
                        ],
                        axis=0,
                    )
                out, lse = unified_tick_attn(
                    q_rows,
                    self.cache,
                    tick,
                    num_splits=resolved,
                    scale=scale,
                    softcap=softcap,
                    interpret=interpret,
                )
                parts = demux_tick(tick, out, lse)
        except Exception:
            for slot, _lo, t in prefill_meta:
                if t:
                    self._release_after_fault(slot)
            raise
        # -- demux + per-request completion hooks (host) --
        decode_results = []
        for j, (slot, q1, _k, _v) in enumerate(decode_items):
            o, l = parts[("d", j)]
            decode_results.append((o[0].astype(q1.dtype), l[0]))
        prefill_results = []
        for j, (slot, q, _k, _v) in enumerate(prefill_items):
            _s, start, t = prefill_meta[j]
            if t:
                o, l = parts[("p", j)]
                prefill_results.append((o.astype(q.dtype), l))
                self._lengths[slot] = start + t
                telemetry.record_prefill(t)
            else:
                prefill_results.append(
                    (
                        jnp.zeros((0, q.shape[1], q.shape[2]), q.dtype),
                        jnp.zeros((0, q.shape[1]), jnp.float32),
                    )
                )
            toks = self._tokens.get(slot)
            if toks is not None and self._lengths.get(slot, 0) >= len(toks):
                self.commit_prefix(slot)
        if decode_items:
            telemetry.record_decode_step(
                batch_size=len(decode_items),
                num_splits=resolved,
                max_seq_len=max(
                    (self._lengths.get(s, 0) for s in decode_slots),
                    default=0,
                ),
                cascade_groups=len(groups),
            )
        self.last_tick_info = {
            "program": label,
            "rows": rows,
            "entries": entries,
            "num_splits": resolved,
            "decode_batch": len(decode_items),
            "prefill_rows": prefill_rows,
            "cascade_groups": len(groups),
            "cascade_group_of": {
                int(decode_slots[pos]): gi
                for gi, g in enumerate(groups)
                for pos in g.members
            },
        }
        return decode_results, prefill_results

    # -- introspection --

    def occupancy(self) -> dict:
        return self.allocator.occupancy()

    def memory_snapshot(self) -> dict:
        """JSON-safe memory forensics of this engine (ISSUE 14): the
        priced serving ledger (pool split live/trie/free, CoW pages
        once) + the page-granular fragmentation map — what the flight
        recorder embeds in every post-mortem dump."""
        from ..telemetry.memory import engine_memory_snapshot

        return engine_memory_snapshot(self)

    def _record_pool(self) -> None:
        telemetry.record_kvcache_state(self.allocator.occupancy())
