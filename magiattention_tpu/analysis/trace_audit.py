"""Pass 2 — jaxpr trace auditor.

Abstract-evals the real entry points (``magi_attn_flex_key`` calc +
grad, the group cast/reduce collectives for both impls,
``magi_attn_decode``) over a matrix of plans x cp x dtypes and
statically asserts, without executing anything:

- **collective census** — the traced primitive counts match the plan's
  CommMeta exactly: zero collectives for fully-local plans and cp=1,
  one ``all_to_all`` per nonzero a2a cast, ``ppermute`` count ==
  active wire hops for the hops impl (grad = 2x: cast + its AD
  transpose). ``psum`` eqns with empty ``axes`` are shard_map transpose
  artifacts that move nothing on the wire and are ignored.
- **dtype-promotion audit** — on the bf16 path, every eqn that takes a
  bf16 input to an f32 output is counted per primitive and compared to
  the checked-in census (``exps/data/trace_audit_expectations.json``):
  the documented LSE/accumulator upcasts are expected; a NEW silent
  upcast changes the census and fails the audit until either fixed or
  re-recorded with ``--update``. Output dtypes are hard-asserted
  (out == bf16, lse == f32).
- **retrace guard** — plan-VALUE changes at fixed shapes must not
  retrace: the local attention program takes its tables as traced
  operands, so a value-mutated (same-shape) table set must hit the jit
  cache.

Everything runs on the virtual CPU mesh with the jnp kernel backend —
this is a tracing exercise; no kernel ever executes.
"""

from __future__ import annotations

import json
from typing import Callable, Iterable

MATRIX_CPS = (1, 2, 4, 8)
WIRE_PRIMS = (
    "ppermute",
    "all_to_all",
    "all_gather",
    "psum",
    "psum_scatter",
    "reduce_scatter",
)


class AuditFailure(AssertionError):
    """A traced program violated a statically-checkable invariant."""


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------


def _sub_jaxprs(value) -> list:
    from ..utils.compat import jaxpr_types

    jaxpr_t, closed_jaxpr_t = jaxpr_types()
    out = []
    if isinstance(value, jaxpr_t):
        out.append(value)
    elif isinstance(value, closed_jaxpr_t):
        out.append(value.jaxpr)
    elif isinstance(value, (tuple, list)):
        for v in value:
            out.extend(_sub_jaxprs(v))
    return out


def iter_eqns(jaxpr) -> Iterable:
    """All eqns of a (Closed)Jaxpr, recursing into every sub-jaxpr
    (pjit bodies, shard_map bodies, custom_vjp branches, scan/cond)."""
    if hasattr(jaxpr, "jaxpr"):  # ClosedJaxpr
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from iter_eqns(sub)


def collective_census(jaxpr) -> dict[str, int]:
    """Counts of wire-crossing collective primitives in a traced program.

    ``psum``-family eqns with empty ``axes`` are counted as nothing:
    shard_map's transpose machinery inserts them as no-op markers and
    they lower to no communication."""
    counts: dict[str, int] = {}
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name not in WIRE_PRIMS:
            continue
        axes = eqn.params.get("axes", None)
        if axes is not None and len(tuple(axes)) == 0:
            continue
        counts[name] = counts.get(name, 0) + 1
    return counts


def guard_census(jaxpr) -> int:
    """Count of numerical-guard sentinel eqns in a traced program.

    The guards (``resilience/guards.py``) funnel every detection through
    ``jnp.isfinite`` — the ``is_finite`` primitive is their census
    marker by construction (nothing else in the runtime traces it; the
    legitimate -inf handling uses ``eq``-based ``isneginf``). A
    ``MAGI_ATTENTION_GUARD=off`` trace must census ZERO — the off path
    is provably free, not just probably."""
    return sum(
        1 for eqn in iter_eqns(jaxpr) if eqn.primitive.name == "is_finite"
    )


def upcast_census(jaxpr) -> dict[str, int]:
    """Per-primitive counts of bf16 -> f32 boundary eqns: any eqn with a
    bfloat16 array input and a float32 array output. The documented
    LSE/accumulator upcasts all cross this boundary via
    ``convert_element_type`` / accumulating ``dot_general``; a silent
    promotion introduced anywhere shows up as census drift."""
    import numpy as np

    def _dtype(aval):
        return getattr(aval, "dtype", None)

    counts: dict[str, int] = {}
    bf16 = "bfloat16"
    for eqn in iter_eqns(jaxpr):
        # container eqns (shard_map/pjit/custom_vjp/scan/...) mix their
        # body's input and output dtypes at the boundary; the body's own
        # eqns are walked anyway, so counting the wrapper double-counts
        if any(_sub_jaxprs(v) for v in eqn.params.values()):
            continue
        in_bf16 = any(
            _dtype(v.aval) is not None and str(_dtype(v.aval)) == bf16
            for v in eqn.invars
            if hasattr(v, "aval")
        )
        if not in_bf16:
            continue
        out_f32 = any(
            _dtype(v.aval) is not None
            and _dtype(v.aval) == np.dtype("float32")
            for v in eqn.outvars
            if hasattr(v, "aval")
        )
        if out_f32:
            name = eqn.primitive.name
            counts[name] = counts.get(name, 0) + 1
    return counts


# ---------------------------------------------------------------------------
# expectations from comm metas
# ---------------------------------------------------------------------------


def _active_wire_hops(comm) -> int:
    return sum(1 for h in comm.hops if h.shift % comm.cp_size != 0)


def expected_cast_collectives(comm) -> dict[str, int]:
    """Collectives ONE group cast of this meta must trace: the hops impl
    ships one ``ppermute`` per active wire hop (zero-volume plans and
    cp=1 resolve to zero hops -> no collective at all); the a2a impl
    always ships its single globally-padded ``all_to_all``."""
    if comm.cp_size == 1:
        return {}
    if comm.impl == "hops":
        n = _active_wire_hops(comm)
        return {"ppermute": n} if n else {}
    return {"all_to_all": 1}


def expected_reduce_collectives(comm, kind: str) -> dict[str, int]:
    """Collectives one explicit group reduce must trace. The a2a impl
    reverses with one ``all_to_all`` (lse reduces ship the lse payload
    in a second one); the hops impl reverses each active hop (lse: out
    and lse payloads reverse separately)."""
    assert kind in ("sum", "lse"), kind
    if comm.cp_size == 1:
        return {}
    factor = 2 if kind == "lse" else 1
    if comm.impl == "hops":
        n = _active_wire_hops(comm) * factor
        return {"ppermute": n} if n else {}
    return {"all_to_all": factor}


def expected_plan_cast_collectives(plan) -> dict[str, int]:
    """Sum of :func:`expected_cast_collectives` over the plan's comm
    metas — what one forward ``calc_attn`` trace must contain (the grad
    trace contains exactly twice this: each cast plus its transpose)."""
    metas = (
        [plan.merged_comm]
        if plan.overlap_degree == 0
        else [sp.comm for sp in plan.stages]
    )
    total: dict[str, int] = {}
    for m in metas:
        for k, v in expected_cast_collectives(m).items():
            total[k] = total.get(k, 0) + v
    return total


def _scale_counts(counts: dict[str, int], factor: int) -> dict[str, int]:
    return {k: v * factor for k, v in counts.items()}


def audit_plan_collectives(plan, *, axis_name: str = "cp") -> list[str]:
    """Build-time census (``MAGI_ATTENTION_VALIDATE=trace``): trace each
    of the plan's group casts over a scratch mesh and assert the
    collective census matches :func:`expected_cast_collectives`.

    Abstract tracing only (nothing executes), but each meta costs one
    small trace — this is the documented overhead of ``trace`` mode.
    Returns error strings; skips quietly (empty list) when the host has
    fewer devices than cp or the plan uses hierarchical comm (the 2-axis
    cast program needs the real mesh topology)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from ..comm.group_collective import group_cast_m
    from ..utils.compat import shard_map

    cp = plan.cp_size
    if plan.hier is not None or len(jax.devices()) < cp:
        return []
    mesh = Mesh(np.array(jax.devices()[:cp]), (axis_name,))
    metas = (
        [plan.merged_comm]
        if plan.overlap_degree == 0
        else [sp.comm for sp in plan.stages]
    )
    errors: list[str] = []
    for i, meta in enumerate(metas):
        arrays = tuple(
            jnp.asarray(a) for a in meta.cast_device_arrays()
        )
        T = max(int(meta.send_idx.max(initial=0)) + 1, 1)
        x = jnp.zeros((cp, T, 1), jnp.float32)

        @functools.partial(
            shard_map,
            mesh=mesh,
            in_specs=(P(axis_name),) * (1 + len(arrays)),
            out_specs=P(axis_name),
            check_vma=False,
        )
        def cast(x_, *arrs, _m=meta):
            return group_cast_m(x_[0], _m, arrs, axis_name=axis_name)[None]

        got = collective_census(jax.make_jaxpr(cast)(x, *arrays))
        want = expected_cast_collectives(meta)
        if got != want:
            errors.append(
                f"plan comm meta {i} ({meta.impl}): traced census "
                f"{_fmt(got)} != CommMeta expectation {_fmt(want)}"
            )
    return errors


# ---------------------------------------------------------------------------
# retrace guard
# ---------------------------------------------------------------------------


def count_traces(fn: Callable):
    """Wrap ``fn`` so each (re)trace bumps ``wrapper.traces`` — call the
    wrapped version under jit with same-shape different-value operands
    to prove values are not baked into the program."""

    def wrapper(*args, **kwargs):
        wrapper.traces += 1
        return fn(*args, **kwargs)

    wrapper.traces = 0
    return wrapper


# ---------------------------------------------------------------------------
# matrix audit (the CLI entry; imports jax lazily)
# ---------------------------------------------------------------------------


def _mesh(cp: int):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < cp:
        raise AuditFailure(
            f"audit needs {cp} devices (virtual CPU mesh); got {len(devs)} "
            "— run via exps/run_static_analysis.py, which forces "
            "xla_force_host_platform_device_count=8"
        )
    return Mesh(np.array(devs[:cp]), ("cp",))


def _workload(kind: str, total: int, chunk: int):
    """(q_ranges, k_ranges, types): 'causal' = one dense causal slice
    (cross-rank comm), 'local' = chunk-diagonal FULL blocks (after
    dispatch every rank's K needs are its own rows -> zero comm)."""
    if kind == "causal":
        return [(0, total)], [(0, total)], [1]
    n = total // chunk
    blocks = [(i * chunk, (i + 1) * chunk) for i in range(n)]
    return blocks, list(blocks), [0] * n


def _build_key(cp, kind, mesh, dtype_name, total, chunk, degree=None):
    from ..api import magi_attn_flex_key
    from ..config import DistAttnConfig
    from ..meta.solver.overlap_solver import OverlapConfig

    qr, kr, ts = _workload(kind, total, chunk)
    cfg = None
    if degree is not None:
        cfg = DistAttnConfig(
            overlap_config=OverlapConfig(degree=degree, min_stage_rows=64)
        )
    return magi_attn_flex_key(
        qr,
        kr,
        ts,
        total,
        total,
        mesh,
        num_heads=(2, 2),
        head_dim=32,
        chunk_size=chunk,
        out_dtype=dtype_name,
        dist_attn_config=cfg,
    )


def _trace_calc(key, dtype_name, total, grad: bool):
    import jax
    import jax.numpy as jnp

    from ..api import calc_attn, dispatch

    dt = jnp.dtype(dtype_name)
    q = jnp.zeros((total, 2, 32), dt)

    def f(q_, k_, v_):
        out, fm = calc_attn(
            dispatch(q_, key), dispatch(k_, key), dispatch(v_, key), key
        )
        return out, fm.lse

    if not grad:
        return jax.make_jaxpr(f)(q, q, q)

    def loss(q_, k_, v_):
        out, _ = f(q_, k_, v_)
        return out.astype(jnp.float32).sum()

    return jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)


def _fmt(c: dict) -> str:
    return json.dumps(c, sort_keys=True)


def audit_flex_matrix(
    *, total: int = 512, chunk: int = 64
) -> tuple[list[str], dict]:
    """Collective census of calc + grad over plans x cp x impls.

    Hard assertions (ISSUE 7 acceptance): local plans and cp=1 trace
    ZERO collectives (calc and grad both); hops plans trace exactly
    active-hop ppermutes and never an all_to_all; a2a plans exactly
    their per-stage all_to_alls.
    """
    from ..api import get_runtime_mgr

    errors: list[str] = []
    report: dict = {}
    cases = []
    for cp in MATRIX_CPS:
        cases.append((cp, "local", None, None))
        cases.append((cp, "causal", None, None))
    # impl-pinned and staged variants on one representative cp
    cases += [
        (4, "causal", "hops", None),
        (4, "causal", "a2a", None),
        (4, "causal", "hops", 2),
        (8, "causal", "hops", None),
    ]
    for cp, kind, impl, degree in cases:
        label = f"flex cp={cp} {kind}" + (
            f" impl={impl}" if impl else ""
        ) + (f" degree={degree}" if degree is not None else "")
        with _pinned_impl(impl):
            mesh = _mesh(cp)
            key = _build_key(
                cp, kind, mesh, "bfloat16", total, chunk, degree=degree
            )
            plan = get_runtime_mgr(key).plan
            expect_fwd = expected_plan_cast_collectives(plan)
            fwd = collective_census(_trace_calc(key, "bfloat16", total, False))
            bwd = collective_census(_trace_calc(key, "bfloat16", total, True))
        expect_bwd = _scale_counts(expect_fwd, 2)
        report[label] = {"fwd": fwd, "grad": bwd, "expected_fwd": expect_fwd}
        if kind == "local" or cp == 1:
            if fwd or bwd:
                errors.append(
                    f"{label}: local/cp=1 plan must trace ZERO collectives; "
                    f"got fwd={_fmt(fwd)} grad={_fmt(bwd)}"
                )
            continue
        if fwd != expect_fwd:
            errors.append(
                f"{label}: fwd census {_fmt(fwd)} != CommMeta expectation "
                f"{_fmt(expect_fwd)}"
            )
        if bwd != expect_bwd:
            errors.append(
                f"{label}: grad census {_fmt(bwd)} != 2x cast expectation "
                f"{_fmt(expect_bwd)}"
            )
        if impl == "hops" and ("all_to_all" in fwd or "all_to_all" in bwd):
            errors.append(f"{label}: hops impl still traces an all_to_all")
    return errors, report


class _pinned_env:
    """Temporarily pin one env var (None value = leave untouched)."""

    def __init__(self, name: str, value: str | None):
        self.name = name
        self.value = value

    def __enter__(self):
        import os

        # save/restore pin, not a config read
        self.prev = os.environ.get(self.name)  # magi-allow: MAGI002
        if self.value is not None:
            os.environ[self.name] = self.value  # magi-allow: MAGI002
        return self

    def __exit__(self, *exc):
        import os

        if self.value is not None:
            if self.prev is None:
                os.environ.pop(self.name, None)  # magi-allow: MAGI002
            else:
                os.environ[self.name] = self.prev  # magi-allow: MAGI002
        return False


class _pinned_impl(_pinned_env):
    """Temporarily pin MAGI_ATTENTION_GROUP_COLL_IMPL (None = leave)."""

    def __init__(self, impl: str | None):
        super().__init__("MAGI_ATTENTION_GROUP_COLL_IMPL", impl)


def audit_guard_ops(*, total: int = 512, chunk: int = 64) -> tuple[list[str], dict]:
    """Guard census over the real flex entry (ISSUE 8 satellite).

    ``MAGI_ATTENTION_GUARD=off`` must trace ZERO guard ops in calc AND
    grad — the guards' disabled path is provably free. ``check`` must
    trace at least one per guarded merge site (detection is actually in
    the program, not just claimed) while keeping the output avals
    identical to the off trace (bit-transparency has an execution-level
    proof in ``make resilience-check``; here we pin the structural
    half)."""
    errors: list[str] = []
    report: dict = {}
    mesh = _mesh(2)
    with _pinned_env("MAGI_ATTENTION_GUARD", "off"):
        key_off = _build_key(2, "causal", mesh, "bfloat16", total, chunk)
        off_fwd = _trace_calc(key_off, "bfloat16", total, False)
        off_grad = _trace_calc(key_off, "bfloat16", total, True)
        n_off = guard_census(off_fwd) + guard_census(off_grad)
        off_avals = [str(a) for a in off_fwd.out_avals]
    with _pinned_env("MAGI_ATTENTION_GUARD", "check"):
        key_chk = _build_key(2, "causal", mesh, "bfloat16", total, chunk)
        chk_fwd = _trace_calc(key_chk, "bfloat16", total, False)
        n_chk = guard_census(chk_fwd)
        chk_avals = [str(a) for a in chk_fwd.out_avals]
    report["guard_census"] = {"off": n_off, "check_fwd": n_chk}
    if n_off:
        errors.append(
            f"GUARD=off traced {n_off} guard op(s) (is_finite) — the "
            "off path must be provably free"
        )
    if n_chk == 0:
        errors.append(
            "GUARD=check traced zero guard ops — detection is not in "
            "the program"
        )
    if off_avals != chk_avals:
        errors.append(
            f"GUARD=check changed the entry's output avals: off="
            f"{off_avals} check={chk_avals}"
        )
    return errors, report


def audit_group_collectives(*, cp: int = 4) -> tuple[list[str], dict]:
    """Trace group cast / reduce_sum / reduce_lse for both impls on a
    skewed synthetic send map and assert the census matches the meta."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from ..comm.group_collective import (
        GroupCollectiveMeta,
        group_cast_m,
        group_reduce_lse_m,
        group_reduce_sum_m,
    )
    from ..utils.compat import shard_map

    errors: list[str] = []
    report: dict = {}
    rng = np.random.default_rng(0)
    T = 32
    send_map = [
        [
            rng.choice(T, size=int(rng.integers(0, 12)), replace=False)
            if s != d
            else np.empty(0, np.int64)
            for d in range(cp)
        ]
        for s in range(cp)
    ]
    mesh = _mesh(cp)
    for impl in ("a2a", "hops"):
        meta = GroupCollectiveMeta.build(send_map, [T] * cp, impl=impl)
        arrays_np = meta.reduce_device_arrays()
        n = len(arrays_np)
        x = jnp.zeros((cp, T, 4), jnp.float32)  # cast payload rows
        R = meta.max_recv
        y = jnp.zeros((cp, R, 2, 4), jnp.float32)  # partial out [R, h, d]
        lse = jnp.zeros((cp, R, 2), jnp.float32)  # partial lse [R, h]
        acc = jnp.zeros((cp, T, 2, 4), jnp.float32)
        lacc = jnp.zeros((cp, T, 2), jnp.float32)
        sum_y = jnp.zeros((cp, R, 4), jnp.float32)
        sum_acc = jnp.zeros((cp, T, 4), jnp.float32)
        arrays = tuple(jnp.asarray(a) for a in arrays_np)

        def smap(f, n_in, n_out=1):
            return shard_map(
                f,
                mesh=mesh,
                in_specs=(P("cp"),) * n_in,
                out_specs=(P("cp"),) * n_out if n_out > 1 else P("cp"),
                check_vma=False,
            )

        cast = smap(
            lambda x_, *arrs: group_cast_m(
                x_[0], meta, arrs, axis_name="cp"
            )[None],
            1 + n,
        )
        red = smap(
            lambda y_, a_, *arrs: group_reduce_sum_m(
                y_[0], a_[0], meta, arrs, axis_name="cp"
            )[None],
            2 + n,
        )

        def _lse(y_, l_, ao_, al_, *arrs):
            o, s = group_reduce_lse_m(
                y_[0], l_[0], ao_[0], al_[0], meta, arrs, axis_name="cp"
            )
            return o[None], s[None]

        redl = smap(_lse, 4 + n, n_out=2)

        checks = [
            ("cast", jax.make_jaxpr(cast)(x, *arrays),
             expected_cast_collectives(meta)),
            ("reduce_sum", jax.make_jaxpr(red)(sum_y, sum_acc, *arrays),
             expected_reduce_collectives(meta, "sum")),
            ("reduce_lse", jax.make_jaxpr(redl)(y, lse, acc, lacc, *arrays),
             expected_reduce_collectives(meta, "lse")),
        ]
        for kind, jaxpr, expect in checks:
            got = collective_census(jaxpr)
            report[f"group_{kind}_{impl}"] = {
                "census": got, "expected": expect,
            }
            if got != expect:
                errors.append(
                    f"group {kind} [{impl}]: census {_fmt(got)} != "
                    f"expected {_fmt(expect)}"
                )
    return errors, report


def audit_decode() -> tuple[list[str], dict]:
    """``magi_attn_decode`` (single-host split-KV path) must trace no
    collective at all, return (bf16 out, f32 lse), and keep its upcast
    census stable."""
    import jax
    import jax.numpy as jnp

    from ..serving import DecodeBatch, magi_attn_decode
    from ..serving.kv_cache import make_paged_kv_cache

    import dataclasses as _dc

    errors: list[str] = []
    cache = make_paged_kv_cache(
        num_pages=8, page_size=8, num_kv_heads=2, head_dim=32, max_seqs=2
    )
    cache = _dc.replace(cache, seq_lens=jnp.array([13, 5], jnp.int32))
    batch = DecodeBatch.of([0, 1])
    q = jnp.zeros((2, 2, 32), jnp.bfloat16)

    def f(q_, cache_):
        return magi_attn_decode(q_, cache_, batch, num_splits=2)

    jaxpr = jax.make_jaxpr(f)(q, cache)
    census = collective_census(jaxpr)
    if census:
        errors.append(
            f"magi_attn_decode traced collectives {_fmt(census)} — the "
            "single-host decode path must be collective-free"
        )
    out_aval, lse_aval = jaxpr.out_avals[0], jaxpr.out_avals[1]
    if str(out_aval.dtype) != "bfloat16":
        errors.append(f"decode out dtype {out_aval.dtype} != bfloat16")
    if str(lse_aval.dtype) != "float32":
        errors.append(f"decode lse dtype {lse_aval.dtype} != float32")
    return errors, {"decode": {"census": census,
                               "upcasts": upcast_census(jaxpr)}}


def audit_dtypes(
    expectations: dict | None,
    *,
    total: int = 512,
    chunk: int = 64,
) -> tuple[list[str], dict]:
    """bf16-path dtype audit on the canonical cp=4 causal entry.

    Hard checks: out is bf16, lse is f32, and the f32 path stays f32.
    Census check: the per-primitive bf16->f32 upcast counts must equal
    the checked-in expectations (the documented LSE/accumulator set);
    drift = a new silent upcast (or an intentional change needing
    ``run_static_analysis.py --update``).
    """
    errors: list[str] = []
    report: dict = {}
    mesh = _mesh(4)

    key = _build_key(4, "causal", mesh, "bfloat16", total, chunk)
    for grad, name in ((False, "flex_fwd_bf16_cp4_causal"),
                       (True, "flex_grad_bf16_cp4_causal")):
        jaxpr = _trace_calc(key, "bfloat16", total, grad)
        census = upcast_census(jaxpr)
        report[name] = census
        if not grad:
            out_aval, lse_aval = jaxpr.out_avals[0], jaxpr.out_avals[1]
            if str(out_aval.dtype) != "bfloat16":
                errors.append(
                    f"bf16 path out dtype is {out_aval.dtype}, not bfloat16 "
                    "— the kernel silently upcast its output"
                )
            if str(lse_aval.dtype) != "float32":
                errors.append(
                    f"bf16 path lse dtype is {lse_aval.dtype}, not the "
                    "documented float32 accumulator"
                )
        if expectations is not None:
            want = expectations.get(name)
            if want is None:
                errors.append(
                    f"no upcast expectation recorded for {name} — run "
                    "exps/run_static_analysis.py --update"
                )
            elif {k: int(v) for k, v in want.items()} != census:
                errors.append(
                    f"{name}: upcast census {_fmt(census)} drifted from "
                    f"recorded {_fmt(want)} — a new bf16->f32 promotion "
                    "appeared (fix it, or --update after an intentional "
                    "change)"
                )

    # f32 path: everything stays f32 end to end
    key32 = _build_key(4, "causal", mesh, "float32", total, chunk)
    jaxpr32 = _trace_calc(key32, "float32", total, False)
    for i, aval in enumerate(jaxpr32.out_avals[:2]):
        if str(aval.dtype) != "float32":
            errors.append(
                f"f32 path output {i} dtype is {aval.dtype}, not float32"
            )
    return errors, report


def audit_retrace(*, total: int = 512, chunk: int = 64) -> list[str]:
    """Changing plan table VALUES at fixed shapes must not retrace the
    jitted attention program.

    Builds the real local attention program (``dist_attn_local`` inside
    ``shard_map``) with the plan tables as EXPLICIT jit operands —
    exactly how the keyed runtime ships them — executes it once, then
    again with every table value-mutated in place (reversed along its
    last axis: same shapes/dtypes, in-bounds indices). A second trace
    means something in the traced path concretizes on table values
    (a host-sync ``int()``/``.item()``, a value-dependent branch) and
    every new mask would recompile at production QPS."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..api import get_runtime_mgr
    from ..parallel.dist_attn import dist_attn_local, make_attn_params

    mesh = _mesh(4)
    key = _build_key(4, "causal", mesh, "bfloat16", total, chunk)
    plan = get_runtime_mgr(key).plan
    params = make_attn_params(plan, 32, out_dtype="bfloat16")
    tables = plan.device_tables()
    n_tab = len(tables)
    spec = P("cp")
    shard = NamedSharding(mesh, spec)
    q = jax.device_put(jnp.zeros((total, 2, 32), jnp.bfloat16), shard)
    tables = tuple(jax.device_put(t, shard) for t in tables)

    from ..utils.compat import shard_map

    body = count_traces(
        lambda q_, k_, v_, *tabs: dist_attn_local(
            q_, k_, v_, tabs[:n_tab], plan, params, axis_name="cp"
        )[:2]
    )
    f = jax.jit(
        shard_map(
            body,
            mesh=mesh,
            in_specs=(spec,) * (3 + n_tab),
            out_specs=(spec, spec),
            check_vma=False,
        )
    )
    jax.block_until_ready(f(q, q, q, *tables))
    first = body.traces
    if first < 1:
        return ["retrace guard: harness failure — first call never traced"]
    mutated = tuple(t[..., ::-1] for t in tables)
    jax.block_until_ready(f(q, q, q, *mutated))
    if body.traces != first:
        return [
            "retrace guard: value-mutated (same-shape) plan tables "
            f"retraced the attention program ({first} -> {body.traces} "
            "traces) — a table value leaks into trace-time control flow"
        ]
    return []


def audit_decode_retrace() -> list[str]:
    """The serving decode path under the same discipline (ISSUE 16):
    re-executing ``decode_attn_paged`` with value-mutated same-shape
    block tables / seq lens must not grow the trace count.

    The paged cache's block tables are the serving-side analogue of the
    plan tables — every decode tick ships a same-shape table whose
    VALUES churn (page allocation, eviction, CoW splits). A retrace
    here means a table value concretizes at trace time and production
    decode recompiles per tick instead of per geometry."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp

    from ..serving import DecodeBatch, magi_attn_decode
    from ..serving.kv_cache import make_paged_kv_cache

    cache = make_paged_kv_cache(
        num_pages=8, page_size=8, num_kv_heads=2, head_dim=32, max_seqs=2
    )
    cache = _dc.replace(cache, seq_lens=jnp.array([13, 5], jnp.int32))
    batch = DecodeBatch.of([0, 1])
    q = jnp.zeros((2, 2, 32), jnp.bfloat16)

    body = count_traces(
        lambda q_, cache_: magi_attn_decode(
            q_, cache_, batch, num_splits=2
        )
    )
    f = jax.jit(body)
    jax.block_until_ready(f(q, cache)[0])
    first = body.traces
    if first < 1:
        return [
            "decode retrace guard: harness failure — first call never "
            "traced"
        ]
    # same shapes/dtypes, different values: permuted (in-bounds) page
    # indices and shifted valid lengths — one allocator tick's churn
    mutated = _dc.replace(
        cache,
        block_tables=cache.block_tables[..., ::-1],
        seq_lens=jnp.array([12, 6], jnp.int32),
    )
    jax.block_until_ready(f(q, mutated)[0])
    if body.traces != first:
        return [
            "decode retrace guard: value-mutated (same-shape) block "
            "tables retraced decode_attn_paged "
            f"({first} -> {body.traces} traces) — a cache table value "
            "leaks into trace-time control flow and production decode "
            "would recompile every tick"
        ]
    return []


# ---------------------------------------------------------------------------
# post-PR-6 serving surfaces (ISSUE 13 satellite)
# ---------------------------------------------------------------------------


def audit_serving_traces(
    expectations: dict | None = None,
) -> tuple[list[str], dict]:
    """Trace coverage for the serving surfaces added after ISSUE 6.

    - ``tp_decode_attn`` (ISSUE 12): the KV-head-sharded shard_map
      program must trace ZERO collectives at every tp width — the
      jaxpr-asserted structural half of the bitwise-parity claim — and
      keep the decode dtype contract (out bf16, lse f32).
    - cascade decode (ISSUE 9): the two-level shared-prefix decode is
      single-chip math and must also be collective-free, with the same
      dtype contract.

    Both paths contribute upcast censuses to
    ``exps/data/trace_audit_expectations.json`` (recorded via
    ``--update``), so a new silent bf16->f32 promotion on the serving
    hot loops is census drift exactly like the flex entries.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..serving.kv_cache import make_paged_kv_cache
    from ..serving.prefix import CascadeGroup, cascade_decode_attn
    from .spmd_audit import trace_tp_decode

    errors: list[str] = []
    report: dict = {}
    recordable: dict = {}

    for tp in (1, 2, 4):
        jaxpr = trace_tp_decode(tp)
        census = collective_census(jaxpr)
        if census:
            errors.append(
                f"tp_decode_attn tp={tp} traced collectives "
                f"{_fmt(census)} — zero collectives may cross the "
                "head axis (the bitwise-parity contract)"
            )
        out_aval, lse_aval = jaxpr.out_avals[0], jaxpr.out_avals[1]
        if str(out_aval.dtype) != "bfloat16":
            errors.append(
                f"tp_decode tp={tp} out dtype {out_aval.dtype} != bfloat16"
            )
        if str(lse_aval.dtype) != "float32":
            errors.append(
                f"tp_decode tp={tp} lse dtype {lse_aval.dtype} != float32"
            )
        if tp == 2:
            recordable["tp_decode_bf16_tp2"] = upcast_census(jaxpr)

    # cascade decode: one shared-prefix group + one flat remainder row
    import dataclasses as _dc

    cache = make_paged_kv_cache(
        num_pages=8, page_size=8, num_kv_heads=2, head_dim=32, max_seqs=4
    )
    bt = np.zeros((4, 8), np.int32)
    bt[0, :3] = [1, 2, 3]
    bt[1, :3] = [1, 2, 4]  # shares full pages (1, 2) with slot 0
    bt[2, :2] = [5, 6]
    cache = _dc.replace(
        cache,
        block_tables=jnp.asarray(bt),
        seq_lens=jnp.asarray([22, 20, 11, 0], jnp.int32),
    )
    groups = [
        CascadeGroup(shared_pages=(1, 2), prefix_len=16, members=(0, 1))
    ]
    slots = np.array([0, 1, 2])
    q = jnp.zeros((3, 4, 32), jnp.bfloat16)

    def f(q_, cache_):
        return cascade_decode_attn(
            q_, cache_, slots, groups, num_splits=2
        )

    jaxpr = jax.make_jaxpr(f)(q, cache)
    census = collective_census(jaxpr)
    if census:
        errors.append(
            f"cascade decode traced collectives {_fmt(census)} — the "
            "single-chip cascade must be collective-free"
        )
    out_aval, lse_aval = jaxpr.out_avals[0], jaxpr.out_avals[1]
    if str(out_aval.dtype) != "bfloat16":
        errors.append(f"cascade out dtype {out_aval.dtype} != bfloat16")
    if str(lse_aval.dtype) != "float32":
        errors.append(f"cascade lse dtype {lse_aval.dtype} != float32")
    recordable["cascade_decode_bf16"] = upcast_census(jaxpr)

    report.update(
        {k: dict(sorted(v.items())) for k, v in recordable.items()}
    )
    if expectations is not None:
        for name, census in recordable.items():
            want = expectations.get(name)
            if want is None:
                errors.append(
                    f"no upcast expectation recorded for {name} — run "
                    "exps/run_static_analysis.py --update"
                )
            elif {k: int(v) for k, v in want.items()} != census:
                errors.append(
                    f"{name}: upcast census {_fmt(census)} drifted from "
                    f"recorded {_fmt(want)} — a new bf16->f32 promotion "
                    "appeared on a serving hot loop (fix it, or "
                    "--update after an intentional change)"
                )
    return errors, report


def audit_hier_cast_levels() -> tuple[list[str], dict]:
    """Per-level census of the 2-level hierarchical cast (ISSUE 13
    satellite): the inter level is exactly one ``all_to_all`` on the
    dcn axis; the intra level is one ici ``all_to_all`` (a2a impl) or
    exactly the meta's active intra hops as ici ``ppermute``s (hops
    impl). The cross-rank uniformity of the same programs is pass 4's
    job (``analysis/spmd_audit.py``); this pins the level structure
    into the trace-audit gate with one trace per case (``per_rank=
    False`` — the full uniformity sweep is not re-paid here)."""
    from .spmd_audit import audit_hier_matrix

    return audit_hier_matrix(meshes=((2, 2),), per_rank=False)


def audit_sparse_grid(
    expectations: dict | None,
) -> tuple[list[str], dict]:
    """ISSUE 15: the compact sparse-grid flex kernel's trace contract.

    Traces the PALLAS sparse-grid forward (interpret-mode ``pallas_call``
    — the kernel jaxpr is identical to the compiled one at trace level)
    on a small varlen block-causal mask in bf16 and asserts:

    - zero collectives (a single-device kernel must trace none),
    - the dtype contract: out bf16, lse f32 (the AMLA base-2 softmax and
      exponent-add rescaling must not silently upcast the output), and
    - a stable bf16->f32 upcast census vs the checked-in expectations
      (key ``flex_fwd_bf16_sparse_grid_varlen``) — drift = a new silent
      promotion inside the sparse kernel.
    """
    import math

    import jax
    import jax.numpy as jnp

    from ..ops.block_meta import build_block_meta
    from ..ops.flex_attn import (
        FlexAttnParams,
        _flex_attn_core,
        bwd_tables,
        fwd_tables,
    )

    name = "flex_fwd_bf16_sparse_grid_varlen"
    errors: list[str] = []
    qr = [(0, 192), (192, 512)]
    kr = [(0, 192), (192, 512)]
    ts = [1, 1]
    meta = build_block_meta(qr, kr, ts, 512, 512, block_q=64, block_k=64)
    # the differentiable Pallas core directly (head-major operands): the
    # audit must trace the sparse KERNEL regardless of the process-wide
    # MAGI_ATTENTION_KERNEL_BACKEND (the analyze gate pins jnp), and the
    # core is the one layer below that dispatch
    params = FlexAttnParams(
        block_q=64,
        block_k=64,
        scale=1.0 / math.sqrt(64),
        softcap=0.0,
        has_sink=False,
        out_dtype="bfloat16",
        interpret=True,
        grid="sparse",
    )
    qh = jnp.zeros((4, 512, 64), jnp.bfloat16)
    sink2d = jnp.zeros((4, 1), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda q_, k_, v_: _flex_attn_core(
            q_, k_, v_, sink2d, fwd_tables(meta), bwd_tables(meta), params
        )
    )(qh, qh, qh)

    census = collective_census(jaxpr)
    if census:
        errors.append(
            f"sparse-grid flex fwd traced collectives {_fmt(census)} — "
            "the single-device sparse kernel must be collective-free"
        )
    out_aval, lse_aval = jaxpr.out_avals[0], jaxpr.out_avals[1]
    if str(out_aval.dtype) != "bfloat16":
        errors.append(
            f"sparse-grid out dtype {out_aval.dtype} != bfloat16 — the "
            "AMLA epilogue upcast the kernel output"
        )
    if str(lse_aval.dtype) != "float32":
        errors.append(f"sparse-grid lse dtype {lse_aval.dtype} != float32")
    upcasts = upcast_census(jaxpr)
    if expectations is not None:
        want = expectations.get(name)
        if want is None:
            errors.append(
                f"no upcast expectation recorded for {name} — run "
                "exps/run_static_analysis.py --update"
            )
        elif {k: int(v) for k, v in want.items()} != upcasts:
            errors.append(
                f"{name}: upcast census {_fmt(upcasts)} drifted from "
                f"recorded {_fmt(want)} — a new bf16->f32 promotion "
                "appeared in the sparse kernel (fix it, or --update "
                "after an intentional change)"
            )
    return errors, {name: upcasts}
