"""Memory observability (ISSUE 14): ledger parity with the allocator /
``gather_kv``, fragmentation map vs brute-force free-list scan, XLA
``memory_analysis`` delta tolerance on CPU, pool forensics in flight
dumps, and the admission-watermark gauges."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from magiattention_tpu import telemetry
from magiattention_tpu.serving import Request, Scheduler, ServingEngine
from magiattention_tpu.serving.kv_cache import PageAllocator, gather_kv
from magiattention_tpu.telemetry import memory as mem
from magiattention_tpu.telemetry import trace

D, HK, HQ, PS = 16, 2, 4, 8


@pytest.fixture(autouse=True)
def _jnp_backend(monkeypatch):
    monkeypatch.setenv("MAGI_ATTENTION_KERNEL_BACKEND", "jnp")


@pytest.fixture()
def live_telemetry():
    telemetry.set_enabled(True)
    telemetry.reset()
    yield
    telemetry.set_enabled(None)
    telemetry.reset()


def _engine(**kw):
    kw.setdefault("num_pages", 32)
    kw.setdefault("max_seqs", 4)
    kw.setdefault("max_pages_per_seq", 8)
    return ServingEngine(
        num_kv_heads=HK, head_dim=D, page_size=PS, dtype=jnp.float32, **kw
    )


def _page_bytes(cache):
    return 2 * cache.page_size * cache.num_kv_heads * cache.head_dim * (
        cache.k_pages.dtype.itemsize
    )


def _prefill(eng, rng, slot, n):
    q = jnp.asarray(rng.standard_normal((n, HQ, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((n, HK, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((n, HK, D)), jnp.float32)
    eng.prefill(q, k, v, slot)


# ---------------------------------------------------------------------------
# ledger <-> allocator / gather_kv parity
# ---------------------------------------------------------------------------


class TestServingLedgerParity:
    def test_pool_split_partitions_every_page(self):
        eng = _engine()
        rng = np.random.default_rng(0)
        res = eng.admit(2 * PS + 3)
        _prefill(eng, rng, res.slot, 2 * PS + 3)
        led = mem.serving_memory_ledger(eng)
        comp = {e.component: e for e in led.entries if e.phase == "pool"}
        pb = _page_bytes(eng.cache)
        pages = {
            k: comp[k].nbytes // pb
            for k in ("pages_live", "pages_trie", "pages_free")
        }
        assert sum(pages.values()) == eng.allocator.num_pages
        assert led.total("pool") == eng.allocator.num_pages * pb
        assert pages["pages_live"] == eng.allocator.pages_in_use
        assert pages["pages_free"] == (
            eng.allocator.num_pages - eng.allocator.pages_in_use
        )

    def test_live_bytes_match_gather_kv_capacity(self):
        """The live pool bytes are exactly the installed-page capacity
        of the live sequences: gather_kv over each slot's reserved
        pages accounts for every live byte once."""
        eng = _engine()
        rng = np.random.default_rng(1)
        lens = (PS + 1, 2 * PS, 3)
        slots = []
        for n in lens:
            res = eng.admit(n)
            _prefill(eng, rng, res.slot, n)
            slots.append(res.slot)
        led = mem.serving_memory_ledger(eng)
        live = next(
            e for e in led.entries if e.component == "pages_live"
        )
        pb = _page_bytes(eng.cache)
        expect_pages = sum(eng.allocator.pages_needed(n) for n in lens)
        assert live.nbytes == expect_pages * pb
        # and the gathered KV of each slot round-trips inside exactly
        # its reserved pages (the storage the ledger priced)
        for slot, n in zip(slots, lens):
            k, v = gather_kv(eng.cache, slot, max_len=n)
            assert k.shape[0] == n
            assert (
                eng.allocator.reserved_pages(slot)
                == eng.allocator.pages_needed(n)
            )

    def test_cow_shared_pages_counted_once(self):
        """Two forks of one resident prefix: the shared pages appear
        ONCE in the pool split (residency, not references), under the
        shared/trie classes — the memory win the refcounts buy."""
        eng = _engine(num_pages=32)
        rng = np.random.default_rng(2)
        toks = list(range(2 * PS))  # two full shareable pages
        r0 = eng.admit(len(toks), tokens=toks)
        _prefill(eng, rng, r0.slot, len(toks))  # registers the prefix
        in_use_before = eng.allocator.pages_in_use
        r1 = eng.admit(len(toks) + 3, tokens=toks + [91, 92, 93])
        assert r1.prefix_len == len(toks)  # forked, no copy
        # the fork added only the suffix page, not a prefix copy
        assert eng.allocator.pages_in_use == in_use_before + 1
        led = mem.serving_memory_ledger(eng)
        states = eng.allocator.page_states()
        assert len(states["shared"]) == 2  # the two prefix pages
        pb = _page_bytes(eng.cache)
        live = next(
            e for e in led.entries if e.component == "pages_live"
        )
        # live bytes = slot-owned residency counted once
        assert live.nbytes == eng.allocator.pages_in_use * pb
        assert live.detail["shared"] == 2

    def test_trie_only_pages_classified_trie(self):
        """Pages kept resident ONLY by the prefix cache (the registrant
        retired) leave the live class and land in trie."""
        eng = _engine()
        rng = np.random.default_rng(3)
        toks = list(range(2 * PS))
        r0 = eng.admit(len(toks), tokens=toks)
        _prefill(eng, rng, r0.slot, len(toks))
        eng.free(r0.slot)
        states = eng.allocator.page_states()
        assert len(states["trie"]) == 2  # full pages the trie pinned
        assert not states["live"] and not states["shared"]
        led = mem.serving_memory_ledger(eng)
        trie_e = next(
            e for e in led.entries if e.component == "pages_trie"
        )
        assert trie_e.nbytes == 2 * _page_bytes(eng.cache)

    def test_page_states_partition_under_churn(self):
        alloc = PageAllocator(24, PS, 6, 8)
        rng = np.random.default_rng(4)
        live = {}
        for _ in range(60):
            if live and rng.random() < 0.4:
                slot = rng.choice(list(live))
                alloc.free(int(slot))
                del live[int(slot)]
            elif alloc.can_admit(PS * int(rng.integers(1, 4))):
                n = PS * int(rng.integers(1, 4))
                slot, pages = alloc.allocate(n)
                live[slot] = pages
            states = alloc.page_states()
            allp = sorted(
                p for cls in states.values() for p in cls
            )
            assert allp == list(range(24))  # exact partition
            assert set(states["free"]) == set(alloc._free_pages)
            owned = set().union(*live.values()) if live else set()
            assert set(states["live"]) | set(states["shared"]) == owned

    def test_peak_pages_high_water(self):
        alloc = PageAllocator(16, PS, 4, 8)
        s0, _ = alloc.allocate(3 * PS)
        s1, _ = alloc.allocate(2 * PS)
        assert alloc.peak_pages_in_use == 5
        alloc.free(s0)
        assert alloc.pages_in_use == 2
        assert alloc.peak_pages_in_use == 5  # the mark survives frees
        alloc.allocate(PS)
        assert alloc.peak_pages_in_use == 5
        assert alloc.occupancy()["peak_pages_in_use"] == 5
        assert alloc.occupancy()["free_pages"] == 16 - 3
        del s1


# ---------------------------------------------------------------------------
# fragmentation map == brute-force free-list scan
# ---------------------------------------------------------------------------


def _brute_force_runs(free_set, num_pages):
    runs, cur = [], 0
    for p in range(num_pages):
        if p in free_set:
            cur += 1
        elif cur:
            runs.append(cur)
            cur = 0
    if cur:
        runs.append(cur)
    return runs


class TestFragmentationMap:
    def test_matches_brute_force_scan(self):
        """The map's free runs / ratio equal an independent scan of the
        free set, across a random admit/free churn."""
        alloc = PageAllocator(40, PS, 8, 8)
        rng = np.random.default_rng(5)
        live = {}
        for step in range(80):
            if live and rng.random() < 0.45:
                slot = int(rng.choice(list(live)))
                alloc.free(slot)
                del live[slot]
            else:
                n = PS * int(rng.integers(1, 4))
                if alloc.can_admit(n):
                    slot, pages = alloc.allocate(n)
                    live[slot] = pages
            g = int(rng.integers(1, 5))
            fmap = mem.fragmentation_map(alloc, granularity=g)
            free = set(alloc.page_states()["free"])
            runs = _brute_force_runs(free, 40)
            assert sorted(fmap.free_runs()) == sorted(runs)
            assert fmap.free_run_max == (max(runs) if runs else 0)
            unusable = sum(r % g for r in runs)
            assert fmap.unusable_free_pages == unusable
            expect = unusable / len(free) if free else 0.0
            assert fmap.fragmentation_ratio == pytest.approx(expect)
            assert fmap.free_pages == len(free)

    def test_default_granularity_is_largest_reservation(self):
        alloc = PageAllocator(16, PS, 4, 8)
        alloc.allocate(3 * PS)
        alloc.allocate(PS)
        fmap = mem.fragmentation_map(alloc)
        assert fmap.granularity == 3
        empty = PageAllocator(16, PS, 4, 8)
        assert mem.fragmentation_map(empty).granularity == 1

    def test_json_round_trip_and_heatmap(self, tmp_path):
        alloc = PageAllocator(20, PS, 4, 8)
        s, _ = alloc.allocate(2 * PS)
        alloc.allocate(PS)
        alloc.free(s)  # punch a hole at the front
        fmap = mem.fragmentation_map(alloc, granularity=2, page_bytes=64)
        path = fmap.dump(str(tmp_path / "frag.json"))
        loaded = mem.PoolFragmentationMap.load(path)
        assert loaded == fmap
        with open(path) as f:
            payload = json.load(f)
        assert payload["fragmentation_ratio"] == pytest.approx(
            fmap.fragmentation_ratio
        )
        art = fmap.ascii_heatmap(width=10)
        assert "pool" in art and "|" in art
        # 20 pages at width 10 = 2 rows + the header
        assert len(art.splitlines()) == 3

    def test_fragmented_vs_compact_pool(self):
        """A checkerboarded pool reports high fragmentation at a
        multi-page granularity; a compacted one reports zero."""
        alloc = PageAllocator(16, PS, 16, 4)
        slots = [alloc.allocate(PS)[0] for _ in range(16)]
        for s in slots[::2]:  # free every other page
            alloc.free(s)
        frag = mem.fragmentation_map(alloc, granularity=2)
        assert frag.free_pages == 8
        assert frag.free_run_max == 1
        assert frag.fragmentation_ratio == 1.0  # no run fits 2 pages
        compact = PageAllocator(16, PS, 16, 4)
        for _ in range(4):
            compact.allocate(PS)
        assert mem.fragmentation_map(
            compact, granularity=2
        ).fragmentation_ratio == 0.0


# ---------------------------------------------------------------------------
# XLA memory_analysis confirmation (CPU)
# ---------------------------------------------------------------------------


class TestMeasuredConfirmation:
    def test_decode_ledger_within_tolerance(self, live_telemetry):
        """The acceptance gate, unit-sized: ledger-predicted io bytes of
        the jitted decode program within 10% of XLA's argument+output
        accounting on CPU."""
        from magiattention_tpu.serving.decode_attn import decode_attn_paged

        eng = _engine()
        rng = np.random.default_rng(6)
        res = eng.admit(2 * PS)
        _prefill(eng, rng, res.slot, 2 * PS)
        led = mem.serving_memory_ledger(
            eng, name="decode", num_q_heads=HQ, decode_batch=1,
            num_splits=2,
        )
        q = jnp.zeros((1, HQ, D), jnp.float32)
        slots = jnp.zeros((1,), jnp.int32)
        f = jax.jit(
            lambda q, c, s: decode_attn_paged(q, c, s, num_splits=2)
        )
        measured = mem.measure_program_memory(f, q, eng.cache, slots)
        assert measured is not None, "CPU memory_analysis unavailable"
        cmp = mem.ledger_vs_measured(led, measured, program="decode")
        assert cmp.within(0.10), cmp.to_json()
        # gauges landed under the documented names
        snap = telemetry.snapshot()
        g = snap["gauges"]
        assert any(k.startswith("magi_mem_delta_ratio{") for k in g)
        assert any(k.startswith("magi_mem_measured_bytes{") for k in g)
        assert any(k.startswith("magi_mem_predicted_bytes{") for k in g)

    def test_mispriced_ledger_caught(self, live_telemetry):
        """A planted mispricing (pool priced at double the itemsize)
        must fall outside the tolerance — the gate can actually fail."""
        from magiattention_tpu.serving.decode_attn import decode_attn_paged

        eng = _engine()
        rng = np.random.default_rng(7)
        res = eng.admit(PS)
        _prefill(eng, rng, res.slot, PS)
        led = mem.serving_memory_ledger(
            eng, name="decode_bad", num_q_heads=HQ, decode_batch=1,
            num_splits=2,
        )
        bad = mem.MemoryLedger(
            name="decode_bad",
            entries=tuple(
                mem.LedgerEntry(e.phase, e.component, e.nbytes * 2, e.detail)
                if e.component == "pages_free" else e
                for e in led.entries
            ),
        )
        q = jnp.zeros((1, HQ, D), jnp.float32)
        slots = jnp.zeros((1,), jnp.int32)
        f = jax.jit(
            lambda q, c, s: decode_attn_paged(q, c, s, num_splits=2)
        )
        measured = mem.measure_program_memory(f, q, eng.cache, slots)
        assert measured is not None
        cmp = mem.ledger_vs_measured(
            bad, measured, program="decode_bad", record=False
        )
        assert not cmp.within(0.10)

    def test_measure_program_memory_never_raises(self):
        # a function XLA cannot lower for this backend returns None
        assert mem.measure_program_memory(
            lambda: (_ for _ in ()).throw(RuntimeError("boom"))
        ) is None

    def test_sample_memory_stats_cpu_safe(self):
        # CPU devices expose no memory_stats: empty dict, no raise —
        # the promoted bench.py sampler contract
        out = mem.sample_memory_stats()
        assert isinstance(out, dict)
        for v in out.values():
            assert isinstance(v, int)


# ---------------------------------------------------------------------------
# plan ledger
# ---------------------------------------------------------------------------


class TestPlanLedger:
    def _plan(self, cp=2, degree=2):
        from magiattention_tpu.common.enum import AttnMaskType
        from magiattention_tpu.common.ranges import AttnRanges
        from magiattention_tpu.meta.dispatch_meta import (
            make_dispatch_meta_from_qk_ranges,
        )
        from magiattention_tpu.meta.solver.overlap_solver import (
            OverlapConfig,
        )
        from magiattention_tpu.parallel.dist_attn import (
            build_dist_attn_plan,
        )

        total = 2048
        qr = AttnRanges.from_ranges([(0, total)])
        kr = AttnRanges.from_ranges([(0, total)])
        mq, _, bucket = make_dispatch_meta_from_qk_ranges(
            qr, kr, [AttnMaskType.CAUSAL], total, total,
            chunk_size=256, cp_size=cp,
        )
        return build_dist_attn_plan(
            mq, bucket, block_q=64, block_k=64,
            overlap_config=OverlapConfig(degree=degree, min_stage_rows=64),
        )

    def test_stage_phases_single_sourced_with_comm_meta(self):
        plan = self._plan()
        led = mem.plan_memory_ledger(
            plan, num_heads_q=2, num_heads_kv=2, head_dim=64,
            bytes_per_elt=4,
        )
        phases = led.phases()
        assert "host_kernel" in phases and "outputs" in phases
        row_bytes = 2 * 2 * 64 * 4
        for i, sp in enumerate(plan.stages):
            cast = next(
                e for e in led.entries if e.phase == f"stage{i}_cast"
            )
            # the SAME figure the solver and timeline predictor price
            assert cast.nbytes == (
                sp.comm.scheduled_rows_per_rank * row_bytes
            )
            kern = [
                e for e in led.entries if e.phase == f"stage{i}_kernel"
            ]
            assert {e.component for e in kern} == {"partials", "lse"}

    def test_degree0_prices_merged_path(self):
        plan = self._plan(degree=0)
        assert plan.overlap_degree == 0
        led = mem.plan_memory_ledger(
            plan, num_heads_q=2, num_heads_kv=2, head_dim=64,
        )
        assert "stage0_cast" in led.phases()
        assert "stage0_kernel" in led.phases()
        assert "host_kernel" not in led.phases()
        cast = next(
            e for e in led.entries if e.phase == "stage0_cast"
        )
        assert cast.nbytes == (
            plan.merged_comm.scheduled_rows_per_rank * 2 * 2 * 64 * 2
        )

    def test_ledger_json_round_trip(self):
        plan = self._plan()
        led = mem.plan_memory_ledger(
            plan, num_heads_q=2, num_heads_kv=2, head_dim=64,
        )
        clone = mem.MemoryLedger.from_json(led.as_json())
        assert clone.by_phase() == led.by_phase()
        assert clone.total() == led.total()
        assert "memory ledger" in led.report()

    def test_plan_method_is_the_pricing_hook(self):
        plan = self._plan()
        via_method = plan.memory_ledger(
            num_heads_q=2, num_heads_kv=2, head_dim=64,
        )
        via_fn = mem.plan_memory_ledger(
            plan, num_heads_q=2, num_heads_kv=2, head_dim=64,
        )
        assert via_method.by_phase() == via_fn.by_phase()


# ---------------------------------------------------------------------------
# mem-pressure watcher + flight-dump forensics
# ---------------------------------------------------------------------------


class TestMemPressure:
    def test_watcher_fires_once_per_episode(self):
        w = mem.MemPressureWatcher(0.2, ticks=3)
        assert [w.observe(f) for f in (0.1, 0.1)] == [False, False]
        assert w.observe(0.15) is True  # third consecutive tick
        assert w.observe(0.1) is False  # fired already
        assert w.observe(0.5) is False  # recovery re-arms
        assert [w.observe(0.0) for _ in range(3)] == [False, False, True]

    def test_threshold_zero_disables(self):
        w = mem.MemPressureWatcher(0.0, ticks=1)
        assert not any(w.observe(0.0) for _ in range(10))

    def test_env_default_off(self, monkeypatch):
        monkeypatch.delenv(
            "MAGI_ATTENTION_MEM_PRESSURE_THRESHOLD", raising=False
        )
        assert mem.MemPressureWatcher().threshold == 0.0


def _req(rng, rid, prompt_len, gen, priority=0):
    return Request(
        rid=rid,
        prompt_q=jnp.asarray(
            rng.standard_normal((prompt_len, HQ, D)), jnp.float32
        ),
        prompt_k=jnp.asarray(
            rng.standard_normal((prompt_len, HK, D)), jnp.float32
        ),
        prompt_v=jnp.asarray(
            rng.standard_normal((prompt_len, HK, D)), jnp.float32
        ),
        decode_q=jnp.asarray(rng.standard_normal((gen, HQ, D)), jnp.float32),
        decode_k=jnp.asarray(rng.standard_normal((gen, HK, D)), jnp.float32),
        decode_v=jnp.asarray(rng.standard_normal((gen, HK, D)), jnp.float32),
        priority=priority,
    )


@pytest.fixture()
def flight_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("MAGI_ATTENTION_TRACE_DIR", str(tmp_path))
    trace.reset_flight_recorder()
    yield tmp_path
    trace.reset_flight_recorder()


class TestOOMForensics:
    def test_pool_exhausted_dump_has_ledger_and_trace_id(
        self, live_telemetry, flight_dir
    ):
        """A pool_exhausted admission inside a scheduler tick ends in a
        flight dump embedding the memory section (ledger +
        fragmentation) and the triggering admission's trace id."""
        rng = np.random.default_rng(8)
        # pool fits ONE 2-page sequence; the second admission at equal
        # priority cannot evict and backpressures on pool_exhausted
        eng = _engine(num_pages=2, max_seqs=4, max_pages_per_seq=2)
        sched = Scheduler(eng, token_budget=64, chunk=None)
        # prompt 2*PS - 2 + gen 2 = the slot's full 2-page capacity:
        # rid 0 holds the whole pool through the tick, so the dump's
        # flush-time snapshot still shows the exhaustion
        sched.submit(_req(rng, 0, 2 * PS - 2, gen=2))
        big = sched.submit(_req(rng, 1, PS, gen=1))
        sched.step()  # rid 0 admitted; rid 1 -> pool_exhausted, armed
        rec = trace.get_flight_recorder()
        assert rec.dump_paths, "pool_exhausted did not produce a dump"
        with open(rec.dump_paths[0]) as f:
            payload = json.load(f)
        assert payload["trigger"]["trigger"] == "pool_exhausted"
        assert payload["trigger"]["context"]["trace_id"] == big.trace_id
        memsec = payload["memory"]
        (src,) = [k for k in memsec if k.startswith("engine#")]
        snap = memsec[src]
        assert snap["ledger"]["by_phase"]["pool"] > 0
        states = snap["fragmentation"]["state_counts"]
        assert states["free"] == 0  # the pool WAS exhausted
        assert sum(states.values()) == 2

    def test_pool_exhausted_rearms_after_success(
        self, live_telemetry, flight_dir
    ):
        eng = _engine(num_pages=2, max_seqs=4, max_pages_per_seq=2)
        r0 = eng.admit(2 * PS)
        assert not eng.admit(PS).admitted  # arms (deferred, no ticks)
        assert eng._pool_exhausted_armed
        eng.free(r0.slot)
        assert eng.admit(PS).admitted
        assert not eng._pool_exhausted_armed  # success re-arms

    def test_mem_pressure_trigger_fires_and_dumps(
        self, live_telemetry, flight_dir, monkeypatch
    ):
        monkeypatch.setenv(
            "MAGI_ATTENTION_MEM_PRESSURE_THRESHOLD", "0.5"
        )
        rng = np.random.default_rng(9)
        eng = _engine(num_pages=4, max_seqs=4, max_pages_per_seq=4)
        sched = Scheduler(eng, token_budget=64, chunk=None)
        sched._mem_watcher = mem.MemPressureWatcher(0.5, ticks=2)
        # the prompt occupies 3/4 of the pool -> free fraction 0.25
        # stays under the 0.5 threshold tick after tick
        sched.submit(_req(rng, 0, 3 * PS, gen=8))
        for _ in range(4):
            sched.step()
        rec = trace.get_flight_recorder()
        assert rec.dump_paths
        with open(rec.dump_paths[0]) as f:
            payload = json.load(f)
        assert payload["trigger"]["trigger"] == "mem_pressure"
        assert payload["trigger"]["context"]["threshold"] == 0.5
        assert "memory" in payload

    def test_engine_memory_snapshot_json_safe(self, live_telemetry):
        eng = _engine()
        rng = np.random.default_rng(10)
        res = eng.admit(PS + 1)
        _prefill(eng, rng, res.slot, PS + 1)
        snap = eng.memory_snapshot()
        json.dumps(snap)  # JSON-safe end to end
        assert snap["fragmentation"]["page_bytes"] == _page_bytes(eng.cache)


# ---------------------------------------------------------------------------
# admission watermark gauges + collectors
# ---------------------------------------------------------------------------


class TestWatermarkGauges:
    def test_scheduler_tick_records_headroom_and_free(
        self, live_telemetry
    ):
        rng = np.random.default_rng(11)
        eng = _engine()
        sched = Scheduler(eng, token_budget=64, chunk=None)
        sched.submit(_req(rng, 0, PS, gen=2))
        sched.run()
        g = telemetry.snapshot()["gauges"]
        assert "magi_sched_admission_headroom" in g
        assert "magi_kvcache_free_pages" in g
        assert g["magi_kvcache_free_pages"] == eng.allocator.num_pages

    def test_kvcache_free_single_sourced_from_watermark(
        self, live_telemetry
    ):
        """Only the scheduler's watermark path writes the free-pages
        gauge — an engine's own pool recording must NOT (a tiered
        deployment's decode replicas would clobber the admission-facing
        prefill figure the headroom gauge pairs with)."""
        eng = _engine()
        eng.admit(2 * PS)
        g = telemetry.snapshot()["gauges"]
        assert "magi_kvcache_free_pages" not in g
        telemetry.record_admission_watermark(
            1, eng.allocator.num_pages - eng.allocator.pages_in_use
        )
        g = telemetry.snapshot()["gauges"]
        assert g["magi_kvcache_free_pages"] == (
            eng.allocator.num_pages - eng.allocator.pages_needed(2 * PS)
        )
        assert g["magi_sched_admission_headroom"] == 1

    def test_pool_forensics_gauges(self, live_telemetry):
        alloc = PageAllocator(16, PS, 4, 8)
        alloc.allocate(2 * PS)
        mem.fragmentation_map(alloc, pool="p0", record=True)
        g = telemetry.snapshot()["gauges"]
        assert "magi_mem_pool_fragmentation_ratio{pool=p0}" in g
        assert "magi_mem_pool_free_run_max{pool=p0}" in g
        assert "magi_mem_pool_peak_pages{pool=p0}" in g
        assert g["magi_mem_pool_pages{pool=p0,state=live}"] == 2
        assert g["magi_mem_pool_pages{pool=p0,state=free}"] == 14

    def test_required_memory_catalog_is_exported(self):
        assert set(telemetry.REQUIRED_MEMORY_METRICS) >= {
            "magi_mem_predicted_bytes",
            "magi_mem_measured_bytes",
            "magi_mem_delta_ratio",
            "magi_mem_unattributed_bytes",
            "magi_sched_admission_headroom",
            "magi_kvcache_free_pages",
        }
