"""Unified serving-tick gate (``make tick-check``) — CPU.

The ISSUE 17 acceptance surface, device-free, through the REAL
scheduler on a multi-tenant trace (chunked long prompt + short tenants
+ a shared-prefix pair + a zero-gen degenerate):

1. **one launch per tick**: with ``MAGI_ATTENTION_UNIFIED_TICK=on``
   every tick's launch-ledger census holds at most 2 distinct programs
   (the gate bound; the unified path actually lands 1), where the
   per-request path needs one program per prefill chunk plus one per
   decode group;
2. **scheduler-output parity**: the ``on`` trace reproduces the EXACT
   token schedule of ``off`` (same chunks, decode batches, finish
   ticks) and every request's outputs match to float tolerance — the
   max abs deviation is printed, bitwise equality is reported when it
   happens to hold;
3. **per-bucket compile flatness**: re-running the same trace adds ZERO
   compiles under any ``tick[...]`` label the warmup already cataloged
   (the PR 16 compile tracker is the witness) — padded geometry
   buckets, not request mixes, key the traced programs;
4. **demux off-by-one self-test** (``--self-test``): a planted
   one-row demux shift (outputs rolled across tick rows) must be
   caught by the parity gate, proving the oracle actually bites.

Exits non-zero on any violation.
"""

import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["MAGI_ATTENTION_KERNEL_BACKEND"] = "jnp"

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from magiattention_tpu import telemetry  # noqa: E402
from magiattention_tpu.serving import (  # noqa: E402
    Request,
    Scheduler,
    ServingEngine,
)
from magiattention_tpu.telemetry.collectors import (  # noqa: E402
    M_SCHED_LAUNCHES,
)

HQ, HK, D, PS = 4, 2, 16, 8

LAUNCH_GATE = 2  # distinct programs per tick, unified mode
TOL = 5e-5


def fail(msg: str) -> int:
    print(f"FAIL: {msg}")
    return 1


def _req(rng, rid, tokens, gen, priority=0, ids=None):
    return Request(
        rid=rid,
        prompt_q=jnp.asarray(
            rng.standard_normal((tokens, HQ, D)), jnp.float32
        ),
        prompt_k=jnp.asarray(
            rng.standard_normal((tokens, HK, D)), jnp.float32
        ),
        prompt_v=jnp.asarray(
            rng.standard_normal((tokens, HK, D)), jnp.float32
        ),
        decode_q=jnp.asarray(rng.standard_normal((gen, HQ, D)), jnp.float32),
        decode_k=jnp.asarray(rng.standard_normal((gen, HK, D)), jnp.float32),
        decode_v=jnp.asarray(rng.standard_normal((gen, HK, D)), jnp.float32),
        priority=priority,
        tokens=ids,
    )


def _submit_trace(sched: Scheduler) -> None:
    """The canonical mixed trace: every tick shape the unified kernel
    must bucket — N prefill chunks x M decode rows x a shared-prefix
    pair x a zero-gen degenerate."""
    rng = np.random.default_rng(2)
    shared = tuple(int(t) for t in rng.integers(0, 50, 2 * PS))
    sched.submit(_req(rng, 0, 4 * PS, gen=4))  # long chunked prompt
    sched.submit(_req(rng, 1, PS + 3, gen=5, priority=1))
    sched.submit(_req(rng, 2, 2 * PS + 5, gen=3))
    sched.submit(
        _req(rng, 3, 2 * PS + 4, gen=4, ids=shared + (1, 2, 3, 4))
    )
    sched.submit(
        _req(rng, 4, 2 * PS + 2, gen=4, ids=shared + (5, 6))
    )
    sched.submit(_req(rng, 5, 3, gen=0))  # zero-gen degenerate


def _drive(mode: str):
    """Run the canonical trace under ``mode``; returns (schedule
    structure, per-request outputs, per-tick launch counts, per-tick
    program labels)."""
    os.environ["MAGI_ATTENTION_UNIFIED_TICK"] = mode
    os.environ["MAGI_ATTENTION_CASCADE"] = "auto"
    eng = ServingEngine(
        num_pages=128, num_kv_heads=HK, head_dim=D, page_size=PS,
        max_seqs=8, max_pages_per_seq=16, dtype=jnp.float32,
    )
    sched = Scheduler(eng, token_budget=24, chunk=PS)
    _submit_trace(sched)
    schedule, launches, programs = [], [], []
    ticks = 0
    while (sched.waiting or sched.num_active) and ticks < 128:
        rep = sched.step()
        ticks += 1
        schedule.append(
            (
                rep.step,
                rep.decode_batch,
                tuple(rep.prefill_chunks),
                rep.tokens_used,
                tuple(sorted(rep.finished)),
            )
        )
        launches.append(len(set(sched._tick_programs)))
        programs.append(tuple(sched._tick_programs))
    if sched.waiting or sched.num_active:
        raise RuntimeError(f"trace did not drain in {ticks} ticks")
    outs = {}
    for rid, st in sched._finished.items():
        outs[rid] = (
            None
            if st.prefill_out_tail is None
            else np.asarray(st.prefill_out_tail),
            [np.asarray(o) for o in st.decode_outs],
        )
    return schedule, outs, launches, programs


def _compare_outputs(o_off, o_on):
    """(max abs deviation, bitwise?, first mismatch description)."""
    max_err, bitwise, where = 0.0, True, None
    for rid in sorted(o_off):
        pairs = []
        t_off, d_off = o_off[rid]
        t_on, d_on = o_on[rid]
        if (t_off is None) != (t_on is None):
            return float("inf"), False, f"rid {rid}: tail presence differs"
        if t_off is not None:
            pairs.append((f"rid {rid} tail", t_off, t_on))
        if len(d_off) != len(d_on):
            return float("inf"), False, f"rid {rid}: decode count differs"
        pairs += [
            (f"rid {rid} decode[{i}]", a, b)
            for i, (a, b) in enumerate(zip(d_off, d_on))
        ]
        for name, a, b in pairs:
            if not np.array_equal(a, b):
                bitwise = False
            err = float(np.abs(a - b).max()) if a.size else 0.0
            if err > max_err:
                max_err = err
            if err > TOL and where is None:
                where = f"{name}: max abs diff {err:.3e}"
    return max_err, bitwise, where


def check_unified_gate() -> int:
    s_off, o_off, l_off, _ = _drive("off")
    s_on, o_on, l_on, p_on = _drive("on")

    # 1. launches per tick
    worst = max(l_on)
    if worst > LAUNCH_GATE:
        return fail(
            f"unified tick launched {worst} distinct programs in one "
            f"tick (gate: <= {LAUNCH_GATE}); programs per tick: {p_on}"
        )
    if max(l_off) <= 1:
        return fail(
            "the per-request trace never needed > 1 launch per tick — "
            "the scenario is too small to witness the fusion"
        )
    bad = [p for tick in p_on for p in tick if not p.startswith("tick[")]
    if bad:
        return fail(f"non-tick program in the unified ledger: {bad}")

    # 2. scheduler-output parity
    if s_on != s_off:
        drift = next(
            (i, a, b) for i, (a, b) in enumerate(zip(s_off, s_on))
            if a != b
        )
        return fail(f"token schedule drift at tick {drift[0]}: "
                    f"off={drift[1]} on={drift[2]}")
    if set(o_on) != set(o_off):
        return fail(
            f"finished-request sets differ: {sorted(o_off)} vs "
            f"{sorted(o_on)}"
        )
    max_err, bitwise, where = _compare_outputs(o_off, o_on)
    if where is not None:
        return fail(f"output parity broke: {where}")
    print(
        f"tick-check: {len(s_on)} ticks, launches/tick "
        f"{worst} (off path peaked at {max(l_off)}), schedule EXACT, "
        f"outputs {'bitwise' if bitwise else f'max |diff| {max_err:.2e}'}"
    )

    # M_SCHED_LAUNCHES histogram saw the unified ticks
    hist = telemetry.snapshot()["histograms"].get(M_SCHED_LAUNCHES)
    if not hist or hist["count"] < len(s_on):
        return fail(f"{M_SCHED_LAUNCHES} histogram missed the trace")
    return 0


def check_compile_flatness() -> int:
    """Per-bucket compile count flat after warmup: the SAME trace again
    adds zero compiles under every already-cataloged tick label."""
    tracker = telemetry.get_compile_tracker()
    warm = {
        lab: s["count"]
        for lab, s in tracker.stats().items()
        if lab.startswith("tick[")
    }
    if not warm:
        return fail(
            "no tick[...] label in the compile tracker after the warmup "
            f"trace: {sorted(tracker.stats())}"
        )
    _drive("on")  # same trace, same buckets
    for lab, s in tracker.stats().items():
        if not lab.startswith("tick["):
            continue
        if lab in warm and s["count"] != warm[lab]:
            return fail(
                f"per-bucket compile count grew for {lab}: "
                f"{warm[lab]} -> {s['count']} on an identical re-run — "
                "the bucket is not absorbing retraces"
            )
    print(
        f"tick-check: {len(warm)} tick program buckets, per-bucket "
        "compile count flat across an identical re-run"
    )
    return 0


def check_demux_selftest() -> int:
    """--self-test: plant a one-row demux shift and require the parity
    gate to catch it."""
    import magiattention_tpu.serving.engine as engine_mod

    orig = engine_mod.unified_tick_attn

    def shifted(q_rows, cache, tick, **kw):
        out, lse = orig(q_rows, cache, tick, **kw)
        # the planted bug: every request reads its neighbor's rows
        return jnp.roll(out, 1, axis=0), jnp.roll(lse, 1, axis=0)

    engine_mod.unified_tick_attn = shifted
    try:
        _, o_off, _, _ = _drive("off")
        _, o_on, _, _ = _drive("on")
    finally:
        engine_mod.unified_tick_attn = orig
    _max_err, _bitwise, where = _compare_outputs(o_off, o_on)
    if where is None:
        return fail(
            "planted demux off-by-one (rows rolled by 1) was NOT caught "
            "by the parity oracle"
        )
    print(f"tick-check: planted demux off-by-one caught ({where})")
    return 0


def main() -> int:
    self_test = "--self-test" in sys.argv
    env_backup = {
        k: os.environ.get(k)
        for k in (
            "MAGI_ATTENTION_UNIFIED_TICK",
            "MAGI_ATTENTION_CASCADE",
            "MAGI_ATTENTION_PREFILL_CHUNK",
        )
    }
    telemetry.set_enabled(True)
    telemetry.reset()
    telemetry.reset_compile_tracker()
    try:
        checks = [check_unified_gate, check_compile_flatness]
        if self_test:
            checks.append(check_demux_selftest)
        for check in checks:
            rc = check()
            if rc:
                return rc
    finally:
        telemetry.set_enabled(None)
        telemetry.reset()
        telemetry.reset_compile_tracker()
        for k, vv in env_backup.items():
            if vv is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = vv
    print(
        "tick-check OK: one launch per unified tick, exact schedule "
        "parity, per-bucket compile count flat"
        + (", planted demux shift caught" if self_test else "")
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
