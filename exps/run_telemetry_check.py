"""Telemetry drift guard (``make telemetry-check``).

Builds a tiny CPU-backend distributed plan with telemetry enabled and
asserts the snapshot contains every metric name the documentation
promises (``telemetry.REQUIRED_PLAN_METRICS`` — the same catalog
``docs/observability.md`` documents). If a refactor renames or drops a
metric without updating the catalog/docs, this exits non-zero.

Also sanity-checks the two structured exporters (metrics JSON + Chrome
trace events JSON) and the disabled-mode no-op contract, so the guard
covers the full acceptance surface of ISSUE 1 without needing devices.

ISSUE 3 extensions: cross-rank snapshot merging must keep its
counters-sum/gauge-skew/histogram-bucket semantics with deterministic
ordering, and Chrome trace dumps must carry track-naming metadata
events.

ISSUE 4 extension: one ServingEngine prefill + decode step must populate
every ``REQUIRED_SERVING_METRICS`` name (the ``magi_decode_*`` /
``magi_kvcache_*`` catalog documented in docs/observability.md).
"""

import json
import os
import sys
import tempfile

import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the keyed-interface step plans over a real (tiny) mesh: virtual CPU
# mesh + the any-platform jnp kernel backend, set BEFORE jax initializes
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
).strip()
os.environ.setdefault("MAGI_ATTENTION_KERNEL_BACKEND", "jnp")

from magiattention_tpu import telemetry  # noqa: E402
from magiattention_tpu.common.enum import AttnMaskType  # noqa: E402
from magiattention_tpu.common.ranges import AttnRanges  # noqa: E402
from magiattention_tpu.meta.dispatch_meta import (  # noqa: E402
    make_dispatch_meta_from_qk_ranges,
)
from magiattention_tpu.parallel.dist_attn import (  # noqa: E402
    build_dist_attn_plan,
)


def has_series(snapshot: dict, name: str) -> bool:
    """A metric is present if any section holds the bare name or a
    labeled ``name{...}`` series."""
    for section in snapshot.values():
        for key in section:
            if key == name or key.startswith(name + "{"):
                return True
    return False


def main() -> int:
    # 1. disabled mode records nothing
    telemetry.set_enabled(False)
    telemetry.reset()
    total, cp, chunk = 2048, 4, 256
    qr = AttnRanges.from_ranges([(0, total)])
    kr = AttnRanges.from_ranges([(0, total)])
    mq, _, bucket = make_dispatch_meta_from_qk_ranges(
        qr, kr, [AttnMaskType.CAUSAL], total, total,
        chunk_size=chunk, cp_size=cp,
    )
    build_dist_attn_plan(mq, bucket)
    snap = telemetry.snapshot()
    if any(snap.values()):
        print(f"FAIL: disabled-mode telemetry recorded data: {snap}")
        return 1

    # 2. enabled mode populates the documented catalog
    telemetry.set_enabled(True)
    mq, _, bucket = make_dispatch_meta_from_qk_ranges(
        qr, kr, [AttnMaskType.CAUSAL], total, total,
        chunk_size=chunk, cp_size=cp,
    )
    with telemetry.span("telemetry-check"):
        plan = build_dist_attn_plan(mq, bucket)
    telemetry.record_runtime_costs(
        plan, num_heads_q=8, num_heads_kv=8, head_dim=128,
        bytes_per_elt=2, generation="v5e",
    )
    snap = telemetry.snapshot()
    missing = [
        m for m in telemetry.REQUIRED_PLAN_METRICS
        if not has_series(snap, m)
    ]
    if missing:
        print(
            "FAIL: documented metrics missing from a real plan snapshot "
            f"(catalog drift): {missing}"
        )
        return 1

    # 2b. plan-LRU visibility (ISSUE 9 satellite): one cold + one warm
    # resolution through the KEYED interface must tick the canonical
    # magi_plan_cache_hits/misses counters the docs promise
    import jax
    from jax.sharding import Mesh

    from magiattention_tpu.api import magi_attn_flex_key

    mesh_lru = Mesh(np.array(jax.devices()[:2]), ("cp",))
    for _ in range(2):  # miss, then hit
        magi_attn_flex_key(
            [(0, 1024)], [(0, 1024)], [1], 1024, 1024, mesh_lru,
            num_heads=(2, 2), head_dim=64, chunk_size=256,
        )
    snap = telemetry.snapshot()
    missing = [
        m for m in telemetry.REQUIRED_PLAN_CACHE_METRICS
        if not has_series(snap, m)
    ]
    if missing:
        print(
            "FAIL: plan-LRU counters missing after a cold+warm keyed "
            f"resolution (catalog drift): {missing}"
        )
        return 1
    if snap["counters"].get("magi_plan_cache_hits", 0) < 1:
        print("FAIL: warm keyed resolution did not count a plan-cache hit")
        return 1

    # 3. exporters round-trip through JSON; traces carry track-naming
    # metadata events (phase M) for Perfetto
    with tempfile.TemporaryDirectory() as d:
        mpath = telemetry.dump_metrics(os.path.join(d, "metrics.json"))
        epath = telemetry.dump_events(os.path.join(d, "events.json"))
        with open(mpath) as f:
            if json.load(f) != snap:
                print("FAIL: dump_metrics does not round-trip the snapshot")
                return 1
        with open(epath) as f:
            trace = json.load(f)
        if "traceEvents" not in trace or not trace["traceEvents"]:
            print(f"FAIL: dump_events wrote no trace events: {trace}")
            return 1
        meta_names = {
            e["name"] for e in trace["traceEvents"] if e.get("ph") == "M"
        }
        if not {"process_name", "thread_name"} <= meta_names:
            print(
                "FAIL: dump_events trace lacks process_name/thread_name "
                f"metadata events (got {sorted(meta_names)})"
            )
            return 1

    # 4. cross-rank aggregation semantics + deterministic ordering
    snap_b = json.loads(json.dumps(snap))  # simulated second rank
    agg = telemetry.merge_snapshots([snap, snap_b], ranks=[0, 1])
    plan_builds = agg["counters"].get("magi_plan_builds_total")
    if plan_builds != 2 * snap["counters"]["magi_plan_builds_total"]:
        print(f"FAIL: aggregate counters are not summed: {plan_builds}")
        return 1
    tot = agg["gauges"].get("magi_plan_modeled_calc_seconds")
    if not tot or sorted(tot) != [
        "argmax", "max", "mean", "min", "per_rank",
    ] or sorted(tot["per_rank"]) != ["0", "1"]:
        print(f"FAIL: aggregate gauge skew stats malformed: {tot}")
        return 1
    hists = agg["histograms"].get("magi_plan_build_seconds")
    if not hists or hists["count"] != 2 * snap["histograms"][
        "magi_plan_build_seconds"
    ]["count"]:
        print(f"FAIL: aggregate histograms are not bucket-merged: {hists}")
        return 1
    if json.dumps(agg, sort_keys=False) != json.dumps(
        telemetry.merge_snapshots([snap, snap_b], ranks=[0, 1]),
        sort_keys=False,
    ):
        print("FAIL: aggregate output ordering is not deterministic")
        return 1
    agg_loop = telemetry.aggregate_across_mesh(snap)
    if agg_loop["num_ranks"] != 1 or agg_loop["counters"] != {
        k: float(v) for k, v in snap["counters"].items()
    }:
        print("FAIL: aggregate_across_mesh loopback mismatch")
        return 1

    # 5. serving catalog: one tiny prefill + decode step through the
    # engine must populate every magi_decode_* / magi_kvcache_* metric
    import jax.numpy as jnp

    from magiattention_tpu.serving import ServingEngine

    telemetry.reset()
    rng = np.random.default_rng(0)
    hq, hk, d = 4, 2, 32
    eng = ServingEngine(
        num_pages=16, num_kv_heads=hk, head_dim=d, page_size=16,
        max_seqs=2, max_pages_per_seq=4, dtype=jnp.float32,
    )
    slot = eng.admit(24).slot
    mk = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    eng.prefill(mk(24, hq, d), mk(24, hk, d), mk(24, hk, d), slot)
    eng.decode_step(mk(1, hq, d), mk(1, hk, d), mk(1, hk, d), [slot])
    snap = telemetry.snapshot()
    missing = [
        m for m in telemetry.REQUIRED_SERVING_METRICS
        if not has_series(snap, m)
    ]
    if missing:
        print(
            "FAIL: documented serving metrics missing after a prefill + "
            f"decode step (catalog drift): {missing}"
        )
        return 1
    summary = telemetry.telemetry_summary(snap)
    if "decode:" not in summary or "kv cache:" not in summary:
        print(f"FAIL: summary lacks the serving section:\n{summary}")
        return 1

    # 6. plan-sanitizer counters (ISSUE 7): one clean validate_plan must
    # tick magi_validate_plan_checks; one seeded-bad validation must tick
    # magi_validate_failures — both names are documented catalog entries
    from magiattention_tpu.analysis.plan_sanity import (
        PlanValidationError,
        validate_plan,
        validate_slices,
    )

    telemetry.reset()
    validate_plan(plan, total_area=bucket.area)
    try:
        validate_slices([(0, 128, 0, 64, 1)], 64, 64)  # OOB: must fail
        print("FAIL: seeded-bad slice PASSED the plan sanitizer")
        return 1
    except PlanValidationError:
        pass
    snap = telemetry.snapshot()
    missing = [
        m for m in telemetry.REQUIRED_VALIDATE_METRICS
        if not has_series(snap, m)
    ]
    if missing:
        print(
            "FAIL: documented validate counters missing after a pass + "
            f"fail sanitizer round (catalog drift): {missing}"
        )
        return 1

    # 7. resilience catalog (ISSUE 8): real guarded/degraded paths must
    # populate every magi_guard_* / admission / degraded / tuning-io
    # metric the docs promise — exercised through the actual call sites
    # (decode guards, engine admission, comm build, tuning cache), not
    # by poking the record_* functions
    from magiattention_tpu.resilience import (
        NumericalGuardError,
        reset_chaos,
    )

    telemetry.reset()
    env_backup = {
        k: os.environ.get(k)
        for k in ("MAGI_ATTENTION_GUARD", "MAGI_ATTENTION_CHAOS")
    }
    try:
        # guard checks + violations: chaos-poisoned decode split under
        # check mode must raise with the failing site
        os.environ["MAGI_ATTENTION_GUARD"] = "check"
        os.environ["MAGI_ATTENTION_CHAOS"] = (
            "corrupt_partial:site=split0,field=out,value=nan"
        )
        reset_chaos()
        cache2 = eng.cache
        from magiattention_tpu.serving import decode_attn_paged

        try:
            decode_attn_paged(
                mk(1, hq, d), cache2, jnp.asarray([slot]), num_splits=2
            )
            print("FAIL: chaos-poisoned decode did not trip the guard")
            return 1
        except NumericalGuardError:
            pass
        # repairs: same fault under repair mode merges finitely
        os.environ["MAGI_ATTENTION_GUARD"] = "repair"
        out_r, _ = decode_attn_paged(
            mk(1, hq, d), cache2, jnp.asarray([slot]), num_splits=2
        )
        if not np.isfinite(np.asarray(out_r)).all():
            print("FAIL: repair mode produced non-finite decode output")
            return 1
        # admission backpressure under injected pool exhaustion
        os.environ["MAGI_ATTENTION_CHAOS"] = "pool_exhaust"
        reset_chaos()
        res = eng.admit(8)
        if res.admitted or res.reason != "pool_exhausted":
            print(f"FAIL: chaos pool exhaustion not rejected: {res}")
            return 1
        # eviction counter: fill the slot table at low priority, then
        # admit a higher-priority sequence — the bounded
        # evict-then-retry policy must evict and count it
        os.environ.pop("MAGI_ATTENTION_CHAOS", None)
        reset_chaos()
        if not eng.admit(8, priority=0).admitted:
            print("FAIL: low-priority filler admission failed")
            return 1
        res_e = eng.admit(8, priority=5)
        if not res_e.admitted or not res_e.evicted:
            print(f"FAIL: priority admission did not evict: {res_e}")
            return 1
        # degraded path: hops build failure falls back to a2a
        os.environ["MAGI_ATTENTION_CHAOS"] = "hops_build_error"
        reset_chaos()
        from magiattention_tpu.comm.group_collective import (
            GroupCollectiveMeta,
        )

        smap = [
            [
                np.arange(4, dtype=np.int64) if s != dd else
                np.empty(0, np.int64)
                for dd in range(2)
            ]
            for s in range(2)
        ]
        meta = GroupCollectiveMeta.build(smap, [8, 8], impl="hops")
        if meta.impl != "a2a":
            print(f"FAIL: hops build chaos did not degrade: {meta.impl}")
            return 1
        # tuning-cache disk fault counter
        os.environ["MAGI_ATTENTION_CHAOS"] = "cache_io_error:op=store"
        reset_chaos()
        from magiattention_tpu.tuning import (
            TuningCache,
            TuningRecord,
            make_fingerprint,
        )

        with tempfile.TemporaryDirectory() as d2:
            TuningCache(d2).put(
                make_fingerprint([(0, 512)], [(0, 512)], [1], 4, 4),
                TuningRecord(128, 128, 1, "model", 1.0, None, ()),
            )
    finally:
        for k, v in env_backup.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        reset_chaos()
    snap = telemetry.snapshot()
    missing = [
        m for m in telemetry.REQUIRED_RESILIENCE_METRICS
        if not has_series(snap, m)
    ]
    if missing:
        print(
            "FAIL: documented resilience metrics missing after guarded/"
            f"degraded rounds (catalog drift): {missing}"
        )
        return 1

    # 8. shared-prefix + scheduler catalogs (ISSUE 9): a miss+hit+fork
    # admission with an unaligned prefix (forces a CoW split), pool
    # pressure (forces an LRU prefix eviction), then a few Scheduler
    # ticks over a mixed prefill/decode trace must populate every
    # magi_prefix_* / magi_sched_* / magi_request_* metric documented
    from magiattention_tpu.serving import Request, Scheduler

    telemetry.reset()
    rng = np.random.default_rng(9)
    ps = 8
    eng9 = ServingEngine(
        num_pages=8, num_kv_heads=hk, head_dim=d, page_size=ps,
        max_seqs=4, max_pages_per_seq=8, dtype=jnp.float32,
    )
    prefix9 = [int(t) for t in rng.integers(0, 50, 2 * ps + 3)]

    def _req(rid, toks, gen, prio=0):
        return Request(
            rid=rid,
            prompt_q=mk(len(toks), hq, d),
            prompt_k=mk(len(toks), hk, d),
            prompt_v=mk(len(toks), hk, d),
            decode_q=mk(gen, hq, d),
            decode_k=mk(gen, hk, d),
            decode_v=mk(gen, hk, d),
            tokens=toks,
            priority=prio,
        )

    sched9 = Scheduler(eng9, token_budget=32, chunk=16)
    sched9.submit(_req(0, prefix9, gen=2))  # prefix miss + registration
    for _ in range(3):  # drain request 0's prefill so the trie is warm
        sched9.step()
    sched9.submit(_req(1, prefix9 + [1, 2, 3], gen=2))  # hit + CoW split
    sched9.run()
    # pressure round: a prompt that only fits if the trie's now-unused
    # prefix pages are LRU-evicted (3 trie pages resident, 5 free, 6
    # needed)
    res9 = eng9.admit(6 * ps, tokens=None)
    if not res9.admitted:
        print(f"FAIL: pressure admission did not evict prefix pages: {res9}")
        return 1
    eng9.free(res9.slot)
    snap = telemetry.snapshot()
    missing = [
        m
        for m in (
            telemetry.REQUIRED_PREFIX_METRICS
            + telemetry.REQUIRED_SCHED_METRICS
        )
        if not has_series(snap, m)
    ]
    if missing:
        print(
            "FAIL: documented shared-prefix/scheduler metrics missing "
            f"after a multi-tenant trace (catalog drift): {missing}"
        )
        return 1

    # 9. analysis catalog (ISSUE 13): one smoke interleaving-checker
    # exploration (clean: states > 0, counterexamples == 0) plus one
    # mutated exploration (the replanted PR 9 double-free: the
    # counterexample counter must move) populate the
    # REQUIRED_ANALYSIS_METRICS catalog through the real explore() path
    from magiattention_tpu.analysis import lifecycle as lc

    telemetry.reset()
    with lc.stubbed_device_layer():
        res_clean = lc.explore(lc.EngineModel(), max_depth=3)
        with lc.planted_double_free():
            res_bad = lc.explore(lc.EngineModel(), max_depth=6)
    snap = telemetry.snapshot()
    missing = [
        m for m in telemetry.REQUIRED_ANALYSIS_METRICS
        if not has_series(snap, m)
    ]
    if missing:
        print(
            "FAIL: documented analysis metrics missing after "
            f"interleaving-checker runs (catalog drift): {missing}"
        )
        return 1
    states = snap["counters"].get("magi_analysis_states_explored", 0)
    cex = snap["counters"].get("magi_analysis_counterexamples", 0)
    if states < res_clean.states or not res_bad.counterexamples or cex < 1:
        print(
            "FAIL: analysis counters did not track the explorations "
            f"(states={states}, counterexamples={cex})"
        )
        return 1

    telemetry.set_enabled(None)
    print(
        f"telemetry-check OK: {len(telemetry.REQUIRED_PLAN_METRICS)} plan "
        f"+ {len(telemetry.REQUIRED_PLAN_CACHE_METRICS)} plan-LRU "
        f"+ {len(telemetry.REQUIRED_SERVING_METRICS)} serving "
        f"+ {len(telemetry.REQUIRED_PREFIX_METRICS)} prefix "
        f"+ {len(telemetry.REQUIRED_SCHED_METRICS)} scheduler "
        f"metrics + {len(telemetry.REQUIRED_VALIDATE_METRICS)} validate "
        f"counters + {len(telemetry.REQUIRED_RESILIENCE_METRICS)} "
        "resilience metrics present, cross-rank merge semantics hold, "
        "exporters round-trip with track metadata, disabled mode is a "
        "no-op"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
