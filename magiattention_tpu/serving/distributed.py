"""Multi-chip disaggregated serving (ISSUE 12, ROADMAP item 2).

Every serving layer below this file — paged KV, CoW shared prefixes,
cascade decode, the chunked-prefill scheduler — runs on ONE chip. This
module shards the engine itself, after FlashInfer's composable
distributed-serving decomposition (arxiv 2501.01005) and the Orca-style
generalization of continuous batching to *tier placement*:

- **Sharded page pool.** ``kv_cache.shard_kv_cache`` pins a pool's
  ``k_pages``/``v_pages`` to a mesh, split on the **KV-head axis** (the
  SNIPPETS ``sharded_paged_attention`` layout); block tables, sequence
  lengths and the :class:`~.kv_cache.PageAllocator` stay host-side —
  ONE logical free list over device-sharded storage, so admission
  decisions are global while no chip ever holds more than its head
  slice.

- **TP decode** (:func:`tp_decode_attn`). ``utils/compat.shard_map``
  over the existing split-KV ``decode_attn_paged`` kernel: q sharded on
  the query-head axis, pages on the KV-head axis, tables replicated.
  Softmax is per-head, so each chip's local split-KV partials merge
  with the UNCHANGED LSE tree — zero collectives in the decode step,
  bitwise-identical to the single-chip reference (asserted by
  ``make distserve-check``).

- **Prefill/decode disaggregation** (:class:`TieredEngine`). Dedicated
  mesh slices per tier (``MAGI_ATTENTION_SERVING_MESH``, e.g.
  ``prefill=1,decode=2x2``): chunked prefill runs on the prefill tier
  (with the PR 9 prefix trie, so shared prompts prefill once), and a
  committed prompt's pages stream to a decode replica through the
  :class:`PageTransferQueue` — the comm layer of the hand-off
  (``jax.device_put`` across tiers = ICI/DCN on real hardware),
  round-trip-exact by page digest. The decode tier is ``dp`` replicas
  x ``tp`` chips; placement picks the least-loaded live replica.

- **Tier scheduling** (:class:`TieredScheduler`). Extends the PR 9
  :class:`~.scheduler.Scheduler` with per-tier token budgets (the tiers
  are different chips — decode no longer pays for prefill chunks),
  per-replica decode groups, and per-tier SLO histograms (``tier=``
  label on the existing collectors). Lifecycle spans ``tier_assigned``
  / ``pages_streamed`` / ``tier_migrated`` flow through the PR 11
  trace ring.

- **Fleet resilience.** PR 8 admission backpressure generalizes:
  :meth:`TieredEngine.admit` returns ``decode_saturated`` when the
  decode tier cannot fit the request or the transfer queue is at its
  bound — evicted/requeued requests therefore never land on a
  saturated tier. A chaos-injected ``decode_fault`` (a decode chip
  dying mid-step) fails ONLY that replica: its requests requeue and
  replay through the prefill tier (the prefix trie makes the re-prefill
  a fork, the re-stream cheap), the replica restarts with a fresh pool,
  and the flight recorder dumps the faulting window — never a hang.

Everything here runs on emulated CPU devices
(``--xla_force_host_platform_device_count``) exactly as on a real
mesh; ``tests/test_serving/test_distributed.py`` and
``make distserve-check`` drive it on >= 4 emulated chips.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .. import telemetry
from ..resilience import chaos
from ..telemetry import trace as reqtrace
from ..utils.compat import shard_map
from ..utils.instrument import named_scope
from .decode_attn import decode_attn_paged, resolve_num_splits
from .engine import AdmissionResult, ServingEngine
from .kv_cache import (
    PagedKVCache,
    PageAllocatorError,
    assign_block_table,
    kv_head_sharding,
    shard_kv_cache,
)
from .scheduler import DECODING, Scheduler, StepReport


class DecodeTierFault(RuntimeError):
    """A decode replica died mid-step (chaos ``decode_fault`` or an
    organic replica-local failure). Carries the replica index and every
    logical sequence id that lost its KV — the
    :class:`TieredScheduler` requeues exactly those for replay."""

    def __init__(self, replica: int, sids: Sequence[int], cause: str = ""):
        super().__init__(
            f"decode replica {replica} failed"
            + (f": {cause}" if cause else "")
            + f" ({len(tuple(sids))} sequences requeued for replay)"
        )
        self.replica = int(replica)
        self.sids = tuple(int(s) for s in sids)


# ---------------------------------------------------------------------------
# TP decode: KV-head-sharded paged attention
# ---------------------------------------------------------------------------


def tp_decode_attn(
    q: jax.Array,  # [b, hq, head_dim] one query token per sequence
    cache: PagedKVCache,
    slots,  # [b] int32 cache slots
    *,
    mesh: Mesh,
    axis_name: str = "tp",
    num_splits: int | None = None,
    scale: float | None = None,
    softcap: float = 0.0,
    out_dtype=None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Tensor-parallel split-KV decode over a KV-head-sharded pool.

    The SNIPPETS ``sharded_paged_attention`` layout via
    ``utils/compat.shard_map``: q is split on its head axis
    (``P(None, tp, None)``), the page pools on their KV-head axis
    (``P(None, None, tp, None)``), block tables / lengths replicated.
    Attention is independent per head, so each chip runs the UNCHANGED
    single-chip kernel (:func:`~.decode_attn.decode_attn_paged` — same
    split-KV partials, same LSE merge tree) on its local head slice and
    the outputs concatenate along heads with **zero collectives**. The
    mesh axis must divide ``num_kv_heads`` (q heads follow, GQA group
    intact per shard).

    ``mesh.shape[axis_name] == 1`` degenerates to the plain local call,
    so one entry point serves every replica width.
    """
    tp = int(mesh.shape[axis_name])
    slots = jnp.asarray(slots, jnp.int32)
    if tp == 1:
        return decode_attn_paged(
            q, cache, slots, num_splits=num_splits, scale=scale,
            softcap=softcap, out_dtype=out_dtype, interpret=interpret,
        )
    b, hq, d = q.shape
    hk = cache.num_kv_heads
    if hk % tp or hq % tp:
        raise ValueError(
            f"tp_decode_attn: kv_heads {hk} / q heads {hq} not divisible "
            f"by the {axis_name}={tp} mesh axis — the KV-head-sharded "
            "layout needs equal head slices per chip"
        )
    # resolve the split count ONCE on the host, with the FULL head
    # count: an auto resolution then hits the exact fingerprint the
    # single-chip call would, so the chosen KV partition — and with it
    # the LSE merge order — is identical and the bitwise-parity
    # guarantee holds for auto splits too (the per-chip workload
    # differs only by the head slice, which the bandwidth-bound decode
    # cost model keys on far more weakly than the page geometry)
    num_splits = resolve_num_splits(num_splits, cache, b, hq)

    def _local(q_, kp, vp, bt, sl, slots_):
        c = PagedKVCache(kp, vp, bt, sl)
        return decode_attn_paged(
            q_, c, slots_, num_splits=num_splits, scale=scale,
            softcap=softcap, out_dtype=out_dtype, interpret=interpret,
        )

    f = shard_map(
        _local,
        mesh=mesh,
        in_specs=(
            P(None, axis_name, None),  # q: query heads
            P(None, None, axis_name, None),  # k_pages: kv heads
            P(None, None, axis_name, None),  # v_pages
            P(),  # block tables (host control state, replicated)
            P(),  # seq_lens
            P(),  # slots
        ),
        out_specs=(P(None, axis_name, None), P(None, axis_name)),
        check_vma=False,
    )
    with named_scope("magi_tp_decode_attn"):
        return f(
            q, cache.k_pages, cache.v_pages, cache.block_tables,
            cache.seq_lens, slots,
        )


# ---------------------------------------------------------------------------
# page-transfer queue: the prefill -> decode comm layer
# ---------------------------------------------------------------------------


def pages_digest(k_payload, v_payload) -> str:
    """Content hash of a page payload (host-side; the stream-integrity
    oracle: digest(source pages) must equal digest(re-gathered
    destination pages) after a stream)."""
    h = hashlib.sha256()
    h.update(np.asarray(k_payload).tobytes())
    h.update(np.asarray(v_payload).tobytes())
    return h.hexdigest()


@dataclasses.dataclass
class PendingStream:
    """One committed prompt waiting for decode-tier capacity."""

    sid: int
    length: int  # committed tokens the stream must carry
    attempts: int = 0


@dataclasses.dataclass(frozen=True)
class StreamReport:
    """What one completed stream actually moved (the scheduler turns
    these into ``pages_streamed`` / ``tier_migrated`` spans — the
    engine does not know trace ids)."""

    sid: int
    replica: int
    pages: int
    tokens: int
    nbytes: int
    digest_ok: bool | None  # None = verification off
    duration_s: float


# ---------------------------------------------------------------------------
# the tiered engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DecodeReplica:
    """One decode-tier member: ``tp`` chips running TP decode over its
    own sharded pool (its engine's allocator is that pool's one host
    free list)."""

    index: int
    devices: tuple
    mesh: Mesh
    tp: int
    engine: ServingEngine
    alive: bool = True
    restarts: int = 0


class TieredEngine:
    """Prefill/decode-disaggregated serving over a device mesh.

    Speaks the exact host interface :class:`~.scheduler.Scheduler`
    drives (``admit`` / ``prefill`` / ``decode_step`` / ``free`` /
    ``allocator`` / ``last_decode_info``) but behind a **logical
    sequence id**: a request admits onto the prefill tier, prefills
    (chunked, prefix-shared) there, and — once its prompt is fully
    committed — its pages stream through the :class:`PageTransferQueue`
    to a decode replica, where every subsequent decode step runs. The
    mapping sid -> (tier, slot) is host state, exactly like the page
    allocator's free lists.

    Fleet backpressure: admission is refused (``decode_saturated``)
    while no live replica could place the request or the transfer queue
    is at ``stream_queue_max`` — the upstream reject/degrade point the
    PR 8 machinery expects, and the reason a requeued victim can never
    be force-placed onto a saturated tier.
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
        mesh_spec: dict | None = None,
        devices: Sequence | None = None,
        max_admission_evictions: int = 4,
        verify_streams: bool = False,
        stream_queue_max: int = 16,
    ):
        from .. import env

        if mesh_spec is None:
            mesh_spec = env.serving_mesh() or {
                "prefill": 1, "decode_dp": 1, "decode_tp": 1,
            }
        self.mesh_spec = dict(mesh_spec)
        n_prefill = int(mesh_spec["prefill"])
        dp = int(mesh_spec["decode_dp"])
        tp = int(mesh_spec["decode_tp"])
        devices = list(devices if devices is not None else jax.devices())
        need = n_prefill + dp * tp
        if need > len(devices):
            raise ValueError(
                f"TieredEngine: mesh spec {mesh_spec} needs {need} devices, "
                f"only {len(devices)} available (emulate more via "
                "XLA_FLAGS=--xla_force_host_platform_device_count)"
            )
        if num_kv_heads % tp:
            raise ValueError(
                f"TieredEngine: decode_tp={tp} must divide num_kv_heads "
                f"{num_kv_heads} (KV-head-sharded decode layout)"
            )
        self.verify_streams = bool(verify_streams)
        self.stream_queue_max = int(stream_queue_max)
        self._geom = dict(
            num_pages=num_pages, num_kv_heads=num_kv_heads,
            head_dim=head_dim, page_size=page_size, max_seqs=max_seqs,
            max_pages_per_seq=max_pages_per_seq, dtype=dtype,
            max_admission_evictions=max_admission_evictions,
            # the TieredEngine registers ONE aggregated per-tier memory
            # source below; member engines must not each register too
            register_flight_memory=False,
        )
        # prefill tier: the full slice is reserved for prefill compute
        # (CP/TP prefill over it composes via the existing dist_attn
        # runtime and is out of scope here); the POOL pins to the
        # slice's first chip — prefill writes are single-stream
        self.prefill_devices = tuple(devices[:n_prefill])
        self._prefill = ServingEngine(prefix_sharing=True, **self._geom)
        self._prefill_mesh = Mesh(
            np.asarray(self.prefill_devices[:1]), ("tp",)
        )
        self._prefill.cache = shard_kv_cache(
            self._prefill.cache, self._prefill_mesh
        )
        # decode tier: dp replicas x tp chips, each with its own sharded
        # pool + its own engine (reservation growth, append, telemetry
        # all reused) running TP decode through the decode_attn_fn hook
        self.replicas: list[DecodeReplica] = []
        for r in range(dp):
            devs = tuple(devices[n_prefill + r * tp : n_prefill + (r + 1) * tp])
            self.replicas.append(self._build_replica(r, devs, tp))
        self._pending: list[PendingStream] = []
        self._stream_reports: list[StreamReport] = []
        self._evicted_sids: list[int] = []
        self._seq: dict[int, dict] = {}  # sid -> lifecycle record
        self._next_sid = 0
        self.last_decode_info: dict = {}
        self._flight = reqtrace.get_flight_recorder()
        # OOM forensics (ISSUE 14): one aggregated memory source for the
        # whole fleet — per-tier ledgers + fragmentation maps in every
        # flight dump (replicas rebuilt after a fault are picked up
        # live because the snapshot walks self.replicas at dump time)
        self._flight.register_memory_source("tiered", self)
        self._record_tiers()

    # -- construction ----------------------------------------------------

    def _build_replica(self, index: int, devs: tuple, tp: int) -> DecodeReplica:
        mesh = Mesh(np.asarray(devs), ("tp",))
        fn = None
        if tp > 1:
            fn = functools.partial(tp_decode_attn, mesh=mesh, axis_name="tp")
        eng = ServingEngine(
            prefix_sharing=False, decode_attn_fn=fn, **self._geom
        )
        eng.cache = shard_kv_cache(eng.cache, mesh)
        return DecodeReplica(
            index=index, devices=devs, mesh=mesh, tp=tp, engine=eng
        )

    # -- introspection ---------------------------------------------------

    @property
    def allocator(self):
        """The prefill tier's allocator (the admission-facing one — the
        scheduler reads ``page_size`` etc. from here)."""
        return self._prefill.allocator

    @property
    def prefix(self):
        return self._prefill.prefix

    @property
    def pending_streams(self) -> int:
        return len(self._pending)

    def replica_of(self, sid: int) -> int | None:
        rec = self._seq.get(int(sid))
        return rec["replica"] if rec and rec["stage"] == "decode" else None

    def placed(self, sid: int) -> bool:
        """Is this sequence resident on the decode tier (streamed and
        decodable)? False while its stream is parked for capacity."""
        rec = self._seq.get(int(sid))
        return bool(rec) and rec["stage"] == "decode"

    def occupancy(self) -> dict:
        return {
            "prefill": self._prefill.allocator.occupancy(),
            "decode": [
                r.engine.allocator.occupancy() for r in self.replicas
            ],
            "pending_streams": len(self._pending),
        }

    def memory_snapshot(self) -> dict:
        """Per-tier memory forensics (ISSUE 14): the prefill tier's and
        every decode replica's ledger + fragmentation map, keyed
        ``tier_prefill`` / ``tier_decode_r<N>`` — the tier-split view a
        fleet post-mortem needs (which pool actually ran out)."""
        from ..telemetry.memory import engine_memory_snapshot

        out = {
            "tier_prefill": engine_memory_snapshot(
                self._prefill, pool="tier_prefill"
            ),
            "pending_streams": len(self._pending),
        }
        for rep in self.replicas:
            name = f"tier_decode_r{rep.index}"
            out[name] = engine_memory_snapshot(rep.engine, pool=name)
        return out

    # -- admission (fleet backpressure) ----------------------------------

    def _decode_can_fit(self, num_tokens: int, priority: int = 0) -> bool:
        # the admission gate and the stream placement must agree on
        # what "a replica can take this request" means — ONE predicate
        # (_pick_replica: live + capacity, else eviction-assisted via
        # strictly-lower-priority residents) serves both
        return self._pick_replica(num_tokens, int(priority)) is not None

    def admit(
        self,
        num_tokens: int,
        *,
        priority: int = 0,
        tokens: Sequence[int] | None = None,
    ) -> AdmissionResult:
        """Fleet admission: the request must fit the prefill tier NOW
        and the decode tier must plausibly fit it LATER (capacity on
        some live replica, transfer queue below its bound) — otherwise
        the verdict is ``decode_saturated`` backpressure and the
        request stays queued upstream. On success the returned ``slot``
        is a LOGICAL sequence id valid across the migration."""
        if (
            len(self._pending) >= self.stream_queue_max
            or not self._decode_can_fit(num_tokens, int(priority))
        ):
            res = AdmissionResult(False, None, "decode_saturated")
            telemetry.record_admission(res)
            self._flight.note_admission(False, "decode_saturated")
            return res
        res = self._prefill.admit(
            num_tokens, priority=priority, tokens=tokens
        )
        evicted = self._translate_evicted(res.evicted)
        if not res.admitted:
            return dataclasses.replace(res, evicted=evicted)
        sid = self._next_sid
        self._next_sid += 1
        self._seq[sid] = {
            "stage": "prefill",
            "pslot": res.slot,
            "replica": None,
            "dslot": None,
            "expected": int(num_tokens),
            "priority": int(priority),
        }
        reqtrace.span_for_current(
            reqtrace.SPAN_TIER_ASSIGNED, tier="prefill", slot=sid
        )
        self._record_tiers()
        return dataclasses.replace(res, slot=sid, evicted=evicted)

    def _translate_evicted(self, pslots: tuple) -> tuple:
        """The prefill engine evicts in ITS slot space; the scheduler
        requeues by logical sid. Victims lose their mapping (and any
        parked stream) here — the prefill engine already released their
        pages."""
        if not pslots:
            return ()
        victims = [
            sid
            for sid, rec in self._seq.items()
            if rec["stage"] in ("prefill", "stream_queued")
            and rec["pslot"] in pslots
        ]
        for sid in victims:
            self._pending = [p for p in self._pending if p.sid != sid]
            del self._seq[sid]
        telemetry.record_stream_queue_depth(len(self._pending))
        return tuple(victims)

    # -- prefill tier ----------------------------------------------------

    def prefill(self, q, k, v, sid: int, **kw):
        """Prefill rows into the sequence's prefill-tier slot (chunked
        and prefix-shared exactly as the single-chip engine). The call
        that completes the prompt enqueues the page stream and pumps
        the transfer queue immediately — a committed prompt reaches the
        decode tier the same tick when capacity exists."""
        rec = self._require(sid, "prefill")
        out, lse = self._prefill.prefill(q, k, v, rec["pslot"], **kw)
        if self._prefill._lengths.get(rec["pslot"], 0) >= rec["expected"]:
            rec["stage"] = "stream_queued"
            self._pending.append(
                PendingStream(sid=sid, length=rec["expected"])
            )
            self.pump_streams()
        return out, lse

    def _require(self, sid: int, *stages: str) -> dict:
        rec = self._seq.get(int(sid))
        if rec is None or (stages and rec["stage"] not in stages):
            raise KeyError(
                f"TieredEngine: sequence {sid} is "
                + ("unknown" if rec is None else f"in stage {rec['stage']!r}")
                + (f", expected {stages}" if stages else "")
            )
        return rec

    # -- the page-transfer queue (comm layer) ----------------------------

    def pump_streams(self) -> list[StreamReport]:
        """Try to place every parked stream (FIFO): pick the
        least-loaded live replica with capacity, move the pages, retire
        the prefill-side slot. Streams that cannot place stay parked —
        the queue depth gauge (and, at the bound, admission
        backpressure) is the fleet's saturation signal. Returns the
        streams completed by THIS pump (also retrievable via
        :meth:`take_stream_reports`)."""
        done: list[StreamReport] = []
        still: list[PendingStream] = []
        for ps in self._pending:
            rep = self._place_stream(ps)
            if rep is None:
                ps.attempts += 1
                still.append(ps)
            else:
                done.append(rep)
        self._pending = still
        for rep in done:
            telemetry.record_page_stream(
                pages=rep.pages, nbytes=rep.nbytes,
                queue_depth=len(self._pending),
            )
        telemetry.record_stream_queue_depth(len(self._pending))
        if done:
            self._stream_reports.extend(done)
            self._record_tiers()
        return done

    def take_stream_reports(self) -> list[StreamReport]:
        """Drain the completed-stream reports (the scheduler turns them
        into per-request spans)."""
        out, self._stream_reports = self._stream_reports, []
        return out

    def take_evicted_sids(self) -> list[int]:
        """Drain decode-tier priority-eviction victims — sequences a
        higher-priority placement displaced (the scheduler requeues
        them, exactly like prefill-tier evictions)."""
        out, self._evicted_sids = self._evicted_sids, []
        return out

    def _pick_replica(
        self, num_tokens: int, priority: int = 0
    ) -> DecodeReplica | None:
        live = [r for r in self.replicas if r.alive]
        fits = [
            r for r in live if r.engine.allocator.can_admit(num_tokens)
        ]
        if not fits:
            # eviction-assisted placement: a replica holding strictly-
            # lower-priority residents can make room (the replica
            # engine's bounded evict-then-retry does the work)
            fits = [
                r for r in live
                if any(
                    p < priority for p in r.engine._priorities.values()
                )
            ]
        if not fits:
            return None
        return min(
            fits,
            key=lambda r: (
                r.engine.allocator.pages_in_use,
                r.engine.allocator.active_seqs,
                r.index,
            ),
        )

    def _on_replica_evictions(self, replica: int, dslots) -> None:
        """A priority placement evicted lower-priority decode residents
        (the replica engine already released their pages): drop their
        mappings and surface the sids for requeue."""
        victims = [
            sid for sid, rec in self._seq.items()
            if rec["stage"] == "decode"
            and rec["replica"] == replica
            and rec["dslot"] in set(dslots)
        ]
        for sid in victims:
            del self._seq[sid]
        self._evicted_sids.extend(victims)

    def _place_stream(self, ps: PendingStream) -> StreamReport | None:
        rec = self._seq.get(ps.sid)
        if rec is None:  # freed/evicted while parked
            return None
        rep = self._pick_replica(ps.length, rec["priority"])
        if rep is None:
            return None
        t0 = time.perf_counter()
        # reserve the destination FIRST — a refused reservation must not
        # cost a wasted cross-tier transfer (the expensive hop). The
        # request's priority travels with it: the replica engine may
        # evict strictly-lower-priority decode residents to make room
        # (victims surface via take_evicted_sids for requeue).
        try:
            res = rep.engine.admit(ps.length, priority=rec["priority"])
        except PageAllocatorError:
            return None
        if res.evicted:
            self._on_replica_evictions(rep.index, res.evicted)
        if not res.admitted:
            return None
        dslot = res.slot
        src = self._prefill.cache
        src_pages = self._prefill.allocator.slot_pages(rec["pslot"])
        n = max(self._prefill.allocator.pages_needed(ps.length), 1)
        src_pages = src_pages[:n]
        # gather on the prefill chip, transfer to the replica's
        # sharding (device_put IS the wire hop on real hardware),
        # scatter into the replica pool
        idx = jnp.asarray(src_pages, jnp.int32)
        dst_pages = rep.engine.allocator.slot_pages(dslot)
        didx = jnp.asarray(dst_pages[:n], jnp.int32)
        with named_scope("magi_page_stream"):
            # the device_put IS the cross-tier wire hop — it lives
            # inside the stream scope so a device trace names it
            pk = jax.device_put(
                src.k_pages[idx], kv_head_sharding(rep.mesh)
            )
            pv = jax.device_put(
                src.v_pages[idx], kv_head_sharding(rep.mesh)
            )
            cache = rep.engine.cache
            cache = PagedKVCache(
                k_pages=cache.k_pages.at[didx].set(pk),
                v_pages=cache.v_pages.at[didx].set(pv),
                block_tables=cache.block_tables,
                seq_lens=cache.seq_lens,
            )
            cache = assign_block_table(
                cache, dslot, dst_pages, keep_len=ps.length
            )
            # re-pin: the eager scatter may have resharded the pool;
            # storage stays device-sharded by contract
            rep.engine.cache = shard_kv_cache(cache, rep.mesh)
        rep.engine._lengths[dslot] = ps.length
        digest_ok = None
        if self.verify_streams:
            digest_ok = pages_digest(pk, pv) == pages_digest(
                rep.engine.cache.k_pages[didx],
                rep.engine.cache.v_pages[didx],
            )
        nbytes = 2 * pk.size * pk.dtype.itemsize
        # the prefill-side copy retires; pages the prefix trie
        # registered stay resident over there for future forks
        self._prefill.free(rec["pslot"])
        rec.update(
            stage="decode", pslot=None, replica=rep.index, dslot=dslot
        )
        return StreamReport(
            sid=ps.sid, replica=rep.index, pages=n, tokens=ps.length,
            nbytes=int(nbytes), digest_ok=digest_ok,
            duration_s=time.perf_counter() - t0,
        )

    # -- decode tier -----------------------------------------------------

    def decode_step(self, q, k_new, v_new, sids, **kw):
        """One decode step over placed sequences (grouped by replica;
        each group is its own device step). A replica-local failure —
        injected ``decode_fault`` chaos, or an organic allocator
        exhaustion mid-growth — fails ONLY that replica: its sequences
        are torn down for replay and a :class:`DecodeTierFault` names
        them; other replicas' tokens in the same call are lost with it
        (callers that need isolation call per replica, as the
        TieredScheduler does)."""
        sid_list = [int(s) for s in np.asarray(sids).tolist()]
        by_rep: dict[int, list[int]] = {}
        for pos, sid in enumerate(sid_list):
            rec = self._require(sid, "decode")
            by_rep.setdefault(rec["replica"], []).append(pos)
        outs: list = [None] * len(sid_list)
        lses: list = [None] * len(sid_list)
        homogeneous = len(by_rep) == 1
        splits_seen: set[int] = set()
        for r, poss in sorted(by_rep.items()):
            rep = self.replicas[r]
            dslots = [self._seq[sid_list[p]]["dslot"] for p in poss]
            # a homogeneous batch maps positions [0..b) in order by
            # construction — hand the full operands and the replica's
            # already-batched output straight through (no per-row
            # re-slice/re-stack on the per-token hot path)
            if homogeneous:
                qs, ks, vs = q, k_new, v_new
            else:
                pidx = np.asarray(poss)
                qs, ks, vs = q[pidx], k_new[pidx], v_new[pidx]
            try:
                chaos.maybe_fail("decode_fault")
                o, l = rep.engine.decode_step(qs, ks, vs, dslots, **kw)
            except (chaos.ChaosInjectedError, PageAllocatorError) as e:
                affected = self.fail_replica(r, reason=repr(e))
                raise DecodeTierFault(r, affected, repr(e)) from e
            if homogeneous:
                outs, lses = o, l
            else:
                for j, p in enumerate(poss):
                    outs[p] = o[j]
                    lses[p] = l[j]
            splits_seen.add(
                int(rep.engine.last_decode_info.get("num_splits", 0))
            )
        self.last_decode_info = {
            "batch": len(sid_list),
            # per-replica decode programs are batch-keyed on the inner
            # engine; the tiered view reports the merged batch's label
            # (the launch ledger counts per-replica groups separately
            # when the TieredScheduler calls per replica)
            "program": telemetry.decode_program_label(len(sid_list)),
            "num_splits": (
                splits_seen.pop() if len(splits_seen) == 1 else 0
            ),
            "cascade_groups": 0,
            "cascade_group_of": {},
            "replicas": sorted(by_rep),
        }
        if homogeneous:
            return outs, lses
        # rows live on DIFFERENT replicas' devices — gather to host
        # before restitching (on real hardware the per-replica outputs
        # would feed per-replica samplers and never meet; the merged
        # view is a host-side convenience for the scheduler)
        return (
            jnp.asarray(np.stack([np.asarray(o) for o in outs])),
            jnp.asarray(np.stack([np.asarray(l) for l in lses])),
        )

    def fail_replica(self, index: int, *, reason: str = "") -> tuple:
        """Tear a decode replica down (its pool is gone with the chip)
        and restart it with a fresh sharded pool. Every sequence it
        held loses its KV; their sids are returned for requeue+replay.
        Arms a deferred flight-recorder dump, so the post-mortem
        contains the tick the fault killed."""
        rep = self.replicas[index]
        affected = [
            sid for sid, rec in self._seq.items()
            if rec["stage"] == "decode" and rec["replica"] == index
        ]
        for sid in affected:
            del self._seq[sid]
        restarts = rep.restarts + 1
        fresh = self._build_replica(index, rep.devices, rep.tp)
        fresh.restarts = restarts
        self.replicas[index] = fresh
        telemetry.record_tier_fault("decode", index)
        self._flight.trigger(
            "decode_tier_fault", immediate=False, replica=index,
            sequences=len(affected), reason=reason,
        )
        from ..telemetry.logger import get_logger

        get_logger("serving").warning(
            "decode replica %d failed (%s): %d sequences requeued for "
            "replay, replica restarted with a fresh pool",
            index, reason or "unspecified", len(affected),
        )
        self._record_tiers()
        return tuple(affected)

    # -- retirement ------------------------------------------------------

    def free(self, sid: int) -> None:
        """Retire a sequence wherever it lives: decode replica slot,
        prefill slot, or a parked stream (both the queue entry and the
        prefill slot)."""
        rec = self._require(sid)
        if rec["stage"] == "decode":
            self.replicas[rec["replica"]].engine.free(rec["dslot"])
        else:
            self._pending = [p for p in self._pending if p.sid != sid]
            self._prefill.free(rec["pslot"])
        del self._seq[int(sid)]
        telemetry.record_stream_queue_depth(len(self._pending))
        self._record_tiers()

    # -- telemetry -------------------------------------------------------

    def _record_tiers(self) -> None:
        telemetry.record_tier_state(
            "prefill",
            pages_in_use=self._prefill.allocator.pages_in_use,
            active=sum(
                1 for rec in self._seq.values()
                if rec["stage"] in ("prefill", "stream_queued")
            ),
        )
        decode_active = sum(
            1 for rec in self._seq.values() if rec["stage"] == "decode"
        )
        for r in self.replicas:
            telemetry.record_tier_state(
                "decode",
                replica=r.index,
                pages_in_use=r.engine.allocator.pages_in_use,
                active=decode_active,
            )


# ---------------------------------------------------------------------------
# the tiered scheduler
# ---------------------------------------------------------------------------


class TieredScheduler(Scheduler):
    """Per-tier continuous batching over a :class:`TieredEngine`.

    Extends the PR 9 :class:`~.scheduler.Scheduler`:

    - **Per-tier token budgets** (``MAGI_ATTENTION_TIER_BUDGET_PREFILL``
      / ``_DECODE``, constructor args win): the tiers run on different
      chips, so decode steps no longer spend the prefill budget — the
      decode-first anti-starvation invariant holds per tier by
      construction, and ``make distserve-check`` still asserts it.
    - **Per-replica decode groups**: each live replica's batch is its
      own device step, so a :class:`DecodeTierFault` requeues exactly
      that replica's requests (``evicted{tier=decode}`` + ``requeued``
      spans) while every other replica's tokens land normally.
    - **Per-tier SLO histograms**: every queue/TTFT/inter-token sample
      additionally lands on a ``tier=``-labeled series.
    - **Stream spans**: completed page streams become ``pages_streamed``
      + ``tier_migrated`` spans on the owning request's trace.
    """

    _prefill_tier = "prefill"
    _decode_tier = "decode"

    def __init__(
        self,
        engine: TieredEngine,
        *,
        prefill_budget: int | None = None,
        decode_budget: int | None = None,
        chunk: int | None = None,
        max_decode_batch: int | None = None,
        clock=time.perf_counter,
        plan_probe=None,
    ):
        from .. import env

        self.prefill_budget = (
            int(prefill_budget)
            if prefill_budget is not None
            else env.tier_token_budget("prefill")
        )
        self.decode_budget = (
            int(decode_budget)
            if decode_budget is not None
            else env.tier_token_budget("decode")
        )
        super().__init__(
            engine,
            token_budget=self.prefill_budget + self.decode_budget,
            chunk=chunk,
            max_decode_batch=max_decode_batch,
            clock=clock,
            plan_probe=plan_probe,
        )

    # -- decode (per replica) --------------------------------------------

    def _admission_headroom(self) -> int:
        # decode growth happens on the replicas' own pools, not the
        # prefill pool admission draws from
        return 0

    def _decode_states(self):
        # only sequences RESIDENT on the decode tier decode; a request
        # whose stream is still parked for capacity waits (the pump at
        # the next tick places it — or frees capacity does)
        return [
            st for st in self._active.values()
            if st.status == DECODING and self.engine.placed(st.slot)
        ]

    def _run_decode(self, states) -> int:
        if self.max_decode_batch is not None:
            states = states[: self.max_decode_batch]
        by_rep: dict[int, list] = {}
        for st in states:
            by_rep.setdefault(self.engine.replica_of(st.slot), []).append(st)
        produced = 0
        for rep in sorted(by_rep):
            try:
                produced += self._decode_group(by_rep[rep], replica=rep)
            except DecodeTierFault as fault:
                self._requeue_fault(fault)
        return produced

    def _requeue_fault(self, fault: DecodeTierFault) -> None:
        """A decode replica died: requeue every request it held for
        replay through the prefill tier (the prefix trie makes the
        re-prefill a fork; the re-stream re-places on a live replica).
        This is the ISSUE 12 no-hang guarantee — the fault consumes one
        tick of the victims' progress, never the scheduler."""
        by_sid = {st.slot: st for st in list(self._active.values())}
        for sid in fault.sids:
            st = by_sid.get(sid)
            if st is not None:
                self._requeue(st, tier="decode", reason="decode_fault")

    # -- the tiered tick -------------------------------------------------

    def _emit_stream_spans(self) -> None:
        reports = self.engine.take_stream_reports()
        by_sid = {st.slot: st for st in self._active.values()}
        for rep in reports:
            st = by_sid.get(rep.sid)
            if st is None:
                continue
            reqtrace.span_pages_streamed(
                st.trace_id, st.rid, pages=rep.pages, tokens=rep.tokens,
                nbytes=rep.nbytes, replica=rep.replica,
                digest_ok=rep.digest_ok, duration_s=rep.duration_s,
            )
            reqtrace.span_tier_migrated(
                st.trace_id, st.rid, from_tier="prefill",
                to_tier="decode", replica=rep.replica,
            )
        # a priority placement may have displaced lower-priority decode
        # residents: requeue them like any other eviction
        for sid in self.engine.take_evicted_sids():
            st = by_sid.get(sid)
            if st is not None:
                self._requeue(st, tier="decode", reason="priority_eviction")

    def _step_body(self, queue_depth: int) -> StepReport:
        # place parked streams first: decode capacity freed last tick
        # should serve THIS tick
        self.engine.pump_streams()
        self._emit_stream_spans()
        admitted, rejected = self._admit_queued()
        finished_before = set(self._finished)

        decoding = self._decode_states()[: self.decode_budget]
        decode_ran = bool(decoding)
        decode_batch = self._run_decode(decoding) if decoding else 0

        chunks, budget = self._run_prefill_loop(self.prefill_budget)
        # prompts completed this tick stream now (engine.prefill pumps
        # eagerly; this sweeps the spans into the trace ring)
        self._emit_stream_spans()

        tokens_used = (self.prefill_budget - budget) + decode_batch
        return StepReport(
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
