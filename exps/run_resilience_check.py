"""Resilience drift guard (``make resilience-check``) — ISSUE 8, CPU.

The acceptance surface of the resilience subsystem, device-free (virtual
8-device CPU mesh, jnp kernel backend): every chaos injector is caught
by its matching guard or degradation path — zero silent corruptions —
and the guards cost nothing when off:

1. **Transparency**: a no-chaos ``GUARD=check`` run is bit-identical to
   ``GUARD=off`` with the jit trace count unchanged, and the ``off``
   trace contains ZERO guard ops (is_finite census).
2. **Detection** (``check``): nan/inf planted in each stage partial
   (out and lse independently), in a decode split partial, and in a
   group-cast payload raises ``NumericalGuardError`` naming the site.
3. **Containment** (``repair``): the same faults merge finitely, with
   output AND grad parity on unaffected rows; a corrupted group-reduce
   partial is quarantined; repair stays differentiable.
4. **Degradation**: injected pool exhaustion -> ``AdmissionResult``
   backpressure without raising (+ the bounded evict-then-retry path);
   injected plan-build failure -> dense degree-0 fallback; injected
   hop-schedule build failure -> a2a fallback; injected prefill fault
   -> the half-admitted slot is fully released and re-admission reuses
   its pages; injected tuning-cache disk faults -> visible counters,
   planning continues. All degraded paths record
   ``magi_degraded_path`` / ``magi_admission_rejected`` /
   ``magi_tuning_cache_io_errors``.
5. **Straggler**: the hop-targeted delay injector traces its
   serialization loop (a ``while`` eqn) into the chosen hop and stays
   bit-transparent — the observability substrate for straggler drills.
   A finite-value ``permute_cast`` corruption is asserted *effective*
   (output differs) — documenting that numerical guards do not cover
   wrong-but-finite payloads (the degradation matrix's honest row).

Exits non-zero on any violation.
"""

import os
import sys
import tempfile

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["MAGI_ATTENTION_KERNEL_BACKEND"] = "jnp"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from magiattention_tpu import telemetry  # noqa: E402
from magiattention_tpu.common.enum import AttnMaskType  # noqa: E402
from magiattention_tpu.common.ranges import AttnRanges  # noqa: E402
from magiattention_tpu.meta.dispatch_meta import (  # noqa: E402
    make_dispatch_meta_from_qk_ranges,
)
from magiattention_tpu.meta.solver.overlap_solver import (  # noqa: E402
    OverlapConfig,
)
from magiattention_tpu.parallel.dist_attn import (  # noqa: E402
    build_dist_attn_plan,
    make_attn_params,
    make_dist_attn_fn,
)
from magiattention_tpu.resilience import (  # noqa: E402
    ChaosInjectedError,
    NumericalGuardError,
    reset_chaos,
)

TOTAL, CP, CHUNK = 1024, 2, 128
HQ, HKV, D = 2, 2, 32


def fail(msg: str) -> int:
    print(f"FAIL: {msg}")
    return 1


def set_env(guard: str | None = None, chaos: str | None = None) -> None:
    for key, val in (
        ("MAGI_ATTENTION_GUARD", guard),
        ("MAGI_ATTENTION_CHAOS", chaos),
    ):
        if val is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = val
    reset_chaos()


def build_fixture(degree: int = 2):
    qr = AttnRanges.from_ranges([(0, TOTAL)])
    kr = AttnRanges.from_ranges([(0, TOTAL)])
    mq, _, bucket = make_dispatch_meta_from_qk_ranges(
        qr, kr, [AttnMaskType.CAUSAL], TOTAL, TOTAL,
        chunk_size=CHUNK, cp_size=CP,
    )
    plan = build_dist_attn_plan(
        mq, bucket, block_q=64, block_k=64,
        overlap_config=OverlapConfig(degree=degree, min_stage_rows=64),
    )
    mesh = Mesh(np.array(jax.devices()[:CP]), ("cp",))
    params = make_attn_params(plan, D, out_dtype="float32")
    return plan, mesh, params


def make_fn(plan, mesh, params):
    return make_dist_attn_fn(plan, mesh, params)


_PLAN_CACHE: dict = {}


def fixture(degree: int = 2):
    if degree not in _PLAN_CACHE:
        with_env = (
            os.environ.get("MAGI_ATTENTION_GUARD"),
            os.environ.get("MAGI_ATTENTION_CHAOS"),
        )
        set_env(None, None)  # plans are guard/chaos-agnostic; build clean
        _PLAN_CACHE[degree] = build_fixture(degree)
        set_env(*with_env)
    return _PLAN_CACHE[degree]


def operands(seed: int = 0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((TOTAL, HQ, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((TOTAL, HKV, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((TOTAL, HKV, D)), jnp.float32)
    return q, k, v


# ---------------------------------------------------------------------------
# 1. transparency: check == off, bit for bit, trace for trace
# ---------------------------------------------------------------------------


def check_transparency() -> int:
    from magiattention_tpu.analysis.trace_audit import count_traces

    plan, mesh, params = fixture()
    q, k, v = operands()
    q2, k2, v2 = operands(1)

    results = {}
    for mode in ("off", "check"):
        set_env(guard=None if mode == "off" else mode)
        fn = make_fn(plan, mesh, params)
        body = count_traces(lambda a, b, c, _fn=fn: _fn(a, b, c))
        jf = jax.jit(body)
        out1, lse1 = map(np.asarray, jf(q, k, v))
        jf(q2, k2, v2)  # value change at fixed shapes: no retrace
        results[mode] = (out1, lse1, body.traces)
    set_env()
    (o_off, l_off, t_off), (o_chk, l_chk, t_chk) = (
        results["off"], results["check"],
    )
    if not (np.array_equal(o_off, o_chk) and np.array_equal(l_off, l_chk)):
        return fail("no-chaos GUARD=check is not bit-identical to off")
    if t_off != 1 or t_chk != 1:
        return fail(
            f"trace count changed: off={t_off} check={t_chk} (want 1/1 "
            "across value-mutated calls)"
        )

    # the off path is provably free: zero guard ops in the traced program
    from magiattention_tpu.analysis.trace_audit import guard_census

    set_env(guard="off")
    fn = make_fn(plan, mesh, params)
    n_off = guard_census(jax.make_jaxpr(lambda a, b, c: fn(a, b, c))(q, k, v))
    set_env(guard="check")
    fn = make_fn(plan, mesh, params)
    n_chk = guard_census(jax.make_jaxpr(lambda a, b, c: fn(a, b, c))(q, k, v))
    set_env()
    if n_off != 0:
        return fail(f"GUARD=off traced {n_off} guard ops (want 0)")
    if n_chk == 0:
        return fail("GUARD=check traced zero guard ops")
    print(
        "resilience-check: guard transparency OK (bit-identical, "
        f"1 trace, census off/check = 0/{n_chk})"
    )
    return 0


# ---------------------------------------------------------------------------
# 2 + 3. detection and containment at every stage site
# ---------------------------------------------------------------------------


def check_stage_guards() -> int:
    plan, mesh, params = fixture()
    q, k, v = operands()
    set_env()
    base_out, base_lse = map(np.asarray, make_fn(plan, mesh, params)(q, k, v))

    sites = ["host"] + [f"stage{i}" for i in range(len(plan.stages))]
    for site in sites:
        for field in ("out", "lse"):
            value = "nan" if field == "out" else "inf"
            spec = (
                f"corrupt_partial:site={site},field={field},"
                f"value={value},rank=0"
            )
            set_env(guard="check", chaos=spec)
            try:
                make_fn(plan, mesh, params)(q, k, v)
                return fail(f"{spec}: no NumericalGuardError raised")
            except NumericalGuardError as exc:
                if site not in exc.sites:
                    return fail(
                        f"{spec}: wrong site encoded ({exc.sites})"
                    )

            # repair: finite everywhere, parity on unaffected rows
            set_env(guard="repair", chaos=spec)
            out_r, lse_r = map(
                np.asarray, make_fn(plan, mesh, params)(q, k, v)
            )
            if not np.isfinite(out_r).all():
                return fail(f"{spec}: repair output not finite")
            # the injector plants at rank 0, local row 0, head 0 ->
            # global dispatched row 0; every other row must be intact
            if not np.allclose(out_r[1:], base_out[1:], atol=1e-6):
                return fail(f"{spec}: repair changed unaffected rows")
            if not np.allclose(lse_r[1:], base_lse[1:], atol=1e-6):
                return fail(f"{spec}: repair changed unaffected lse rows")
    set_env()

    # degree-0 merged path has its own single guard site
    plan0, mesh0, params0 = fixture(degree=0)
    set_env(guard="check", chaos="corrupt_partial:site=merged,value=nan")
    try:
        make_fn(plan0, mesh0, params0)(q, k, v)
        return fail("merged-site corruption not detected")
    except NumericalGuardError as exc:
        if "merged" not in exc.sites:
            return fail(f"merged-site detection named {exc.sites}")
    set_env()
    print(
        f"resilience-check: stage guards OK ({len(sites)} staged sites "
        "x out/lse x check+repair, + merged site)"
    )
    return 0


def check_repair_grads() -> int:
    """GUARD=repair is differentiable through a quarantined stage: vjp
    finiteness everywhere and grad parity on unaffected rows."""
    plan, mesh, params = fixture()
    q, k, v = operands()
    row_mask = np.ones((TOTAL,), np.float32)
    row_mask[0] = 0.0  # the planted row
    mask = jnp.asarray(row_mask)[:, None, None]

    def loss_fn(fn):
        def loss(q_, k_, v_):
            out, _ = fn(q_, k_, v_)
            return (out * mask).sum()

        return loss

    set_env()
    g_base = jax.grad(loss_fn(make_fn(plan, mesh, params)), argnums=(0, 1, 2))(
        q, k, v
    )
    set_env(
        guard="repair",
        chaos="corrupt_partial:site=stage0,field=out,value=nan,rank=0",
    )
    g_rep = jax.grad(loss_fn(make_fn(plan, mesh, params)), argnums=(0, 1, 2))(
        q, k, v
    )
    set_env()
    for name, gb, gr in zip("qkv", g_base, g_rep):
        gb, gr = np.asarray(gb), np.asarray(gr)
        if not np.isfinite(gr).all():
            return fail(f"repair grad d{name} not finite under stage NaN")
        # the quarantine only reweights the planted row's merge; grads of
        # the unaffected-row loss stay within fp tolerance of baseline
        if not np.allclose(gb, gr, atol=1e-4):
            return fail(
                f"repair grad d{name} lost parity on unaffected rows "
                f"(max diff {np.abs(gb - gr).max():.2e})"
            )
    print("resilience-check: repair-mode vjp finite with grad parity OK")
    return 0


# ---------------------------------------------------------------------------
# decode split guards
# ---------------------------------------------------------------------------


def check_decode_guards() -> int:
    from magiattention_tpu.serving import ServingEngine, decode_attn_paged

    rng = np.random.default_rng(3)
    hq, hk, d = 4, 2, 32
    mk = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731

    def fresh_engine():
        eng = ServingEngine(
            num_pages=16, num_kv_heads=hk, head_dim=d, page_size=16,
            max_seqs=2, max_pages_per_seq=4, dtype=jnp.float32,
        )
        slot = eng.admit(40).slot
        eng.prefill(q_p, k_p, v_p, slot)
        return eng, slot

    q_p, k_p, v_p = mk(40, hq, d), mk(40, hk, d), mk(40, hk, d)
    set_env()
    eng, slot = fresh_engine()
    qd = mk(1, hq, d)
    base, _ = decode_attn_paged(qd, eng.cache, jnp.asarray([slot]),
                                num_splits=2)
    base = np.asarray(base)

    set_env(guard="check",
            chaos="corrupt_partial:site=split0,field=out,value=nan")
    try:
        decode_attn_paged(qd, eng.cache, jnp.asarray([slot]), num_splits=2)
        return fail("decode split corruption not detected in check mode")
    except NumericalGuardError as exc:
        if "split0" not in exc.sites:
            return fail(f"decode detection named {exc.sites}")
    # the engine's hot loop surfaces the same typed error
    try:
        eng.decode_step(qd, mk(1, hk, d), mk(1, hk, d), [slot], num_splits=2)
        return fail("engine decode_step swallowed the guard error")
    except NumericalGuardError:
        pass

    set_env(guard="repair",
            chaos="corrupt_partial:site=split0,field=out,value=nan")
    eng2, slot2 = fresh_engine()
    out_r, _ = decode_attn_paged(qd, eng2.cache, jnp.asarray([slot2]),
                                 num_splits=2)
    out_r = np.asarray(out_r)
    set_env()
    if not np.isfinite(out_r).all():
        return fail("decode repair output not finite")
    print("resilience-check: decode split guards OK (check + repair, "
          "engine surfaces the typed error)")
    return 0


# ---------------------------------------------------------------------------
# comm payload corruption + straggler
# ---------------------------------------------------------------------------


def check_comm_chaos() -> int:
    plan, mesh, params = fixture()
    q, k, v = operands()
    set_env()
    base_out, _ = map(np.asarray, make_fn(plan, mesh, params)(q, k, v))

    # nan on the wire -> the downstream stage kernel emits nan -> the
    # stage guard catches it (the cast has no guard of its own; the
    # detection point is the first guarded merge after the fault)
    set_env(guard="check", chaos="corrupt_cast:value=nan,rank=0")
    try:
        make_fn(plan, mesh, params)(q, k, v)
        return fail("cast payload NaN not detected by the stage guards")
    except NumericalGuardError:
        pass

    # repair survives the same wire fault
    set_env(guard="repair", chaos="corrupt_cast:value=nan,rank=0")
    out_r, _ = map(np.asarray, make_fn(plan, mesh, params)(q, k, v))
    if not np.isfinite(out_r).all():
        return fail("repair did not contain a cast payload NaN")

    # a finite permutation corrupts silently past the numerical guards —
    # asserted EFFECTIVE (output differs) and documented as covered only
    # by parity harnesses (docs/resilience.md degradation matrix)
    set_env(guard="check", chaos="permute_cast")  # every rank's recv
    out_p, _ = map(np.asarray, make_fn(plan, mesh, params)(q, k, v))
    if np.allclose(out_p, base_out, atol=1e-6):
        return fail("permute_cast injector was a no-op")
    set_env()
    print("resilience-check: comm chaos OK (wire NaN detected/repaired; "
          "finite permutation provably out of numerical-guard scope)")
    return 0


def check_reduce_quarantine() -> int:
    """A poisoned group-reduce partial is quarantined in repair mode
    (both impls): the merged rows stay finite."""
    from jax.sharding import PartitionSpec as P

    from magiattention_tpu.comm.group_collective import (
        GroupCollectiveMeta,
        group_reduce_lse_m,
    )
    from magiattention_tpu.utils.compat import shard_map

    cp, T = 2, 16
    rng = np.random.default_rng(5)
    send_map = [
        [
            rng.choice(T, size=6, replace=False) if s != d_
            else np.empty(0, np.int64)
            for d_ in range(cp)
        ]
        for s in range(cp)
    ]
    mesh = Mesh(np.array(jax.devices()[:cp]), ("cp",))
    results = {}
    for mode in (None, "repair"):
        chaos = "corrupt_reduce:value=nan,rank=0" if mode else None
        set_env(guard=mode, chaos=chaos)
        meta = GroupCollectiveMeta.build(send_map, [T] * cp, impl="a2a")
        arrays = tuple(jnp.asarray(a) for a in meta.reduce_device_arrays())
        R = meta.max_recv
        y = jnp.asarray(rng.standard_normal((cp, R, 2, 4)), jnp.float32)
        lse = jnp.asarray(rng.standard_normal((cp, R, 2)), jnp.float32)
        acc = jnp.asarray(rng.standard_normal((cp, T, 2, 4)), jnp.float32)
        lacc = jnp.asarray(rng.standard_normal((cp, T, 2)), jnp.float32)

        def _body(y_, l_, ao_, al_, *arrs, _m=meta):
            o, s = group_reduce_lse_m(
                y_[0], l_[0], ao_[0], al_[0], _m, arrs, axis_name="cp"
            )
            return o[None], s[None]

        f = shard_map(
            _body, mesh=mesh,
            in_specs=(P("cp"),) * (4 + len(arrays)),
            out_specs=(P("cp"), P("cp")), check_vma=False,
        )
        out, lse_out = f(y, lse, acc, lacc, *arrays)
        results[mode] = (np.asarray(out), np.asarray(lse_out))
    set_env()
    out_r, lse_r = results["repair"]
    if not (np.isfinite(out_r).all() and np.isfinite(lse_r).all()):
        return fail("repair did not quarantine a poisoned reduce partial")
    print("resilience-check: group-reduce quarantine OK (poisoned "
          "partial merges finitely in repair mode)")
    return 0


def check_straggler() -> int:
    import functools

    from jax.sharding import PartitionSpec as P

    from magiattention_tpu.comm.group_collective import (
        GroupCollectiveMeta,
        group_cast_m,
    )
    from magiattention_tpu.utils.compat import shard_map

    cp, T = 2, 16
    send_map = [
        [
            np.arange(8, dtype=np.int64) if s != d_ else
            np.empty(0, np.int64)
            for d_ in range(cp)
        ]
        for s in range(cp)
    ]
    mesh = Mesh(np.array(jax.devices()[:cp]), ("cp",))
    x = jnp.arange(cp * T * 4, dtype=jnp.float32).reshape(cp, T, 4)

    def program():
        meta = GroupCollectiveMeta.build(send_map, [T] * cp, impl="hops")
        arrays = tuple(jnp.asarray(a) for a in meta.cast_device_arrays())

        def _body(x_, *arrs, _m=meta):
            return group_cast_m(x_[0], _m, arrs, axis_name="cp")[None]

        f = shard_map(
            _body, mesh=mesh, in_specs=(P("cp"),) * (1 + len(arrays)),
            out_specs=P("cp"), check_vma=False,
        )
        jaxpr = jax.make_jaxpr(functools.partial(f))(x, *arrays)
        n_while = sum(
            1
            for eqn in __import__(
                "magiattention_tpu.analysis.trace_audit",
                fromlist=["iter_eqns"],
            ).iter_eqns(jaxpr)
            if eqn.primitive.name == "while"
        )
        return np.asarray(f(x, *arrays)), n_while

    set_env()
    base, n_clean = program()
    set_env(chaos="straggler:hop=1,delay=16")
    slow, n_chaos = program()
    set_env()
    if n_chaos <= n_clean:
        return fail(
            f"straggler did not trace its delay loop (while eqns "
            f"{n_clean} -> {n_chaos})"
        )
    if not np.array_equal(base, slow):
        return fail("straggler delay corrupted the payload")
    print("resilience-check: straggler OK (delay loop traced on the "
          "chosen hop, payload bit-identical)")
    return 0


# ---------------------------------------------------------------------------
# graceful degradation
# ---------------------------------------------------------------------------


def check_degradation() -> int:
    from magiattention_tpu.comm.group_collective import GroupCollectiveMeta
    from magiattention_tpu.serving import ServingEngine

    telemetry.set_enabled(True)
    telemetry.reset()

    # plan-build failure -> dense degree-0 fallback, recorded
    qr = AttnRanges.from_ranges([(0, TOTAL)])
    kr = AttnRanges.from_ranges([(0, TOTAL)])
    mq, _, bucket = make_dispatch_meta_from_qk_ranges(
        qr, kr, [AttnMaskType.CAUSAL], TOTAL, TOTAL,
        chunk_size=CHUNK, cp_size=CP,
    )
    set_env(chaos="plan_error:times=1")
    plan = build_dist_attn_plan(
        mq, bucket, overlap_config=OverlapConfig(degree=2, min_stage_rows=64)
    )
    if plan.overlap_degree != 0 or plan.merged_comm is None:
        return fail("plan-build chaos did not degrade to the degree-0 plan")

    # hop-schedule build failure -> a2a impl, recorded
    set_env(chaos="hops_build_error:times=1")
    smap = [
        [
            np.arange(4, dtype=np.int64) if s != d_ else
            np.empty(0, np.int64)
            for d_ in range(2)
        ]
        for s in range(2)
    ]
    meta = GroupCollectiveMeta.build(smap, [8, 8], impl="hops")
    if meta.impl != "a2a" or meta.impl_reason != "degraded_hops_build_error":
        return fail(f"hops-build chaos did not degrade to a2a: {meta.impl}")
    set_env(chaos=None)
    meta_ok = GroupCollectiveMeta.build(smap, [8, 8], impl="hops")
    if meta_ok.impl != "hops":
        return fail("hops impl did not recover once chaos cleared")

    # pool exhaustion -> backpressure, engine never raises
    eng = ServingEngine(
        num_pages=8, num_kv_heads=2, head_dim=32, page_size=16,
        max_seqs=4, max_pages_per_seq=4, dtype=jnp.float32,
    )
    set_env(chaos="pool_exhaust")
    res = eng.admit(16)
    if res.admitted or res.reason != "pool_exhausted":
        return fail(f"injected exhaustion not a backpressure verdict: {res}")
    set_env()
    if not eng.admit(16).admitted:
        return fail("engine did not recover once exhaustion cleared")

    # allocator exception -> backpressure (alloc_error), not a raise
    set_env(chaos="alloc_fail:times=1")
    res = eng.admit(16)
    if res.admitted or res.reason != "alloc_error":
        return fail(f"injected allocator failure not degraded: {res}")
    set_env()

    # bounded evict-lowest-priority-then-retry: fill the pool with
    # low-priority residents, then admit a high-priority sequence
    eng2 = ServingEngine(
        num_pages=4, num_kv_heads=2, head_dim=32, page_size=16,
        max_seqs=4, max_pages_per_seq=4, dtype=jnp.float32,
    )
    lows = [eng2.admit(16, priority=1).slot for _ in range(4)]
    if any(s is None for s in lows):
        return fail("setup: low-priority admissions failed")
    res = eng2.admit(32, priority=5)
    if not res.admitted or len(res.evicted) != 2:
        return fail(f"evict-then-retry verdict wrong: {res}")
    same_prio = eng2.admit(64, priority=1)
    if same_prio.admitted or same_prio.reason != "pool_exhausted":
        return fail(
            f"equal-priority admission must NOT evict: {same_prio}"
        )

    # injected prefill fault: the half-admitted slot must release its
    # pages and a re-admission must reuse them (satellite regression)
    eng3 = ServingEngine(
        num_pages=4, num_kv_heads=2, head_dim=32, page_size=16,
        max_seqs=2, max_pages_per_seq=4, dtype=jnp.float32,
    )
    res = eng3.admit(48)
    pages_before = set(eng3.allocator._slot_pages[res.slot])
    set_env(chaos="prefill_error:times=1")
    rng = np.random.default_rng(7)
    mk = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    try:
        eng3.prefill(mk(48, 4, 32), mk(48, 2, 32), mk(48, 2, 32), res.slot)
        return fail("injected prefill fault did not surface")
    except ChaosInjectedError:
        pass
    set_env()
    if eng3.occupancy()["pages_in_use"] != 0:
        return fail("prefill fault leaked reserved pages")
    res2 = eng3.admit(48)
    if not res2.admitted:
        return fail("re-admission after a prefill fault failed")
    if set(eng3.allocator._slot_pages[res2.slot]) != pages_before:
        return fail("re-admission did not reuse the released pages")
    eng3.prefill(mk(48, 4, 32), mk(48, 2, 32), mk(48, 2, 32), res2.slot)

    # tuning-cache disk faults: visible, non-fatal
    from magiattention_tpu.tuning import (
        TuningCache,
        TuningRecord,
        make_fingerprint,
    )

    fp = make_fingerprint([(0, 512)], [(0, 512)], [1], 4, 4)
    rec = TuningRecord(128, 128, 1, "model", 1.0, None, ())
    with tempfile.TemporaryDirectory() as cdir:
        TuningCache(cdir).put(fp, rec)  # real file on disk
        set_env(chaos="cache_io_error:op=load,times=1")
        got, layer = TuningCache(cdir).get(fp)
        if got is not None or layer != "miss":
            return fail("injected load fault did not degrade to a miss")
        set_env(chaos="cache_io_error:op=store,times=1")
        TuningCache(cdir).put(fp, rec)  # must not raise
    set_env()

    snap = telemetry.snapshot()
    needed = [
        "magi_degraded_path{reason=plan_build_error}",
        "magi_degraded_path{reason=hops_build_error}",
        "magi_admission_rejected{reason=pool_exhausted}",
        "magi_admission_rejected{reason=alloc_error}",
        "magi_tuning_cache_io_errors{op=load}",
        "magi_tuning_cache_io_errors{op=store}",
    ]
    flat = {**snap.get("counters", {}), **snap.get("gauges", {})}
    missing = [m for m in needed if m not in flat]
    telemetry.set_enabled(None)
    if missing:
        return fail(f"degradation telemetry missing: {missing}")
    print("resilience-check: degradation OK (plan fallback, hops "
          "fallback, backpressure, evict-then-retry, prefill-fault "
          "release+reuse, tuning-io counters)")
    return 0


def main() -> int:
    checks = [
        check_transparency,
        check_stage_guards,
        check_repair_grads,
        check_decode_guards,
        check_comm_chaos,
        check_reduce_quarantine,
        check_straggler,
        check_degradation,
    ]
    for check in checks:
        rc = check()
        if rc:
            set_env()
            return rc
    print(
        "resilience-check OK: every injector caught by its guard or "
        "degradation path; no-chaos guards bit-transparent and "
        "trace-count-neutral"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
