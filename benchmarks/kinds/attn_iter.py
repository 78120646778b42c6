"""Traffic kind ``attn_iter``: the keyed attention call, over and over.

One mask, one plan, the public path ``magi_attn_varlen_key`` /
``magi_attn_flex_key`` -> ``dispatch`` -> ``calc_attn`` (and its vjp) ->
``undispatch``. What is timed is what a model pays a layer: ``calc_attn``
forward, and forward + backward, on dispatched q, k, v — a trainer
dispatches token ids once a step, not tensors once a layer. The window
is a forward phase and a forward+backward phase; each is a run of timed
units (``timing.TIMED_UNIT_S`` seconds of back-to-back calls, see
``timing.timed_units``) that end in ``block_until_ready`` on their whole
result. A rate is the FLOPs of the exact mask area of every call of the
phase over the phase's whole time, so a stall or a compile inside the
window moves it; the median call is the per-layer ``*_iter_ms``.

Traffic parameters: ``total_tokens``, ``mask``, ``check`` (how
``correct`` is sampled), and optionally ``chunk_size`` (left out: the
program's choice, as in every real cell).
"""

from __future__ import annotations

import time

import numpy as np

from .. import flops, masks, reference, timing
from ..harness import Observations, key_from_seed, log

# bf16 kernels against the float32 reference, on the sampled rows.
# Rounding the output to bf16 alone is 2**-9 = 2e-3 relative an element;
# on the chip (PR 23, all four cells' runs) the relative L2 error read
# 2.0-2.6e-3 on out, dq, dk, dv and the lse 0.8-1.1e-5 max-abs (float32
# from float32 accumulators). The tolerances are four and a hundred times
# that: they hold bf16 and refuse fp8 operands or a dropped term (> 3e-2).
REL_L2_TOL = 1e-2
LSE_ABS_TOL = 1e-3

# The forward phase's share of the window: a forward+backward call is
# four to seven times a forward call, and a training step is what most
# users pay for.
FWD_SHARE = 0.3


def run(cell, ctx) -> Observations:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from magiattention_tpu import api
    from magiattention_tpu.telemetry import get_compile_tracker

    cfg, tr = cell.config, cell.traffic
    hq, hk, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    dtype = jnp.dtype(cfg["dtype"])
    total = int(tr["total_tokens"])
    mask = masks.build_mask(tr["mask"], total)
    log(f"mask: {mask.describe()}")
    mesh = Mesh(np.array(ctx.devices), ("cp",))
    sharded = NamedSharding(mesh, P("cp"))
    tracker = get_compile_tracker()
    span = ctx.tracer.span

    # -- set-up: plan, inputs, programs, warm-up ---------------------------
    key_args = dict(
        num_heads=(hq, hk), head_dim=d, chunk_size=tr.get("chunk_size"),
        out_dtype=str(dtype),
    )
    t0 = time.perf_counter()
    if mask.doc_lengths:
        key = api.magi_attn_varlen_key(mask.cu_seqlens, total, mesh, **key_args)
    else:
        key = api.magi_attn_flex_key(
            list(mask.q_ranges), list(mask.k_ranges), list(mask.types),
            total, total, mesh, **key_args,
        )
    plan_s = time.perf_counter() - t0
    plan = api.get_runtime_mgr(key).plan
    log(
        f"key built in {plan_s * 1e3:.0f} ms: cp={plan.cp_size}, "
        f"shard={plan.shard_q_len}, overlap degree {plan.overlap_degree}, "
        f"comm impl {plan.comm.impl}, remote rows/rank "
        f"{list(plan.comm.recv_total)}"
    )

    def make(rng, shape, dt):
        """One global tensor from the seed, dispatched (sharded on cp)."""
        x = jax.random.normal(rng, shape, jnp.float32).astype(dt)
        return api.dispatch(jax.lax.with_sharding_constraint(x, sharded), key)

    make_j = jax.jit(make, static_argnums=(1, 2), out_shardings=sharded)
    rq, rk, rv, rdo, rdl = jax.random.split(key_from_seed(ctx.seed), 5)
    q = make_j(rq, (total, hq, d), dtype)
    k = make_j(rk, (total, hk, d), dtype)
    v = make_j(rv, (total, hk, d), dtype)
    d_out = make_j(rdo, (total, hq, d), dtype)  # out's cotangent, its type
    d_lse = jax.block_until_ready(
        make_j(rdl, (total, hq), jnp.dtype("float32"))
    )
    log("inputs made and dispatched")

    def fwd(q, k, v):
        out, meta = api.calc_attn(q, k, v, key)
        return out, meta.lse

    def fwdbwd(q, k, v, d_out, d_lse):
        _res, vjp = jax.vjp(fwd, q, k, v)
        return vjp((d_out, d_lse))

    fwd_exe = jax.jit(fwd).lower(q, k, v).compile()
    bwd_exe = jax.jit(fwdbwd).lower(q, k, v, d_out, d_lse).compile()
    texts = [fwd_exe.as_text(), bwd_exe.as_text()]
    kernels = [t.count("tpu_custom_call") for t in texts]
    mem = bwd_exe.memory_analysis()
    log(
        f"programs: tpu_custom_call fwd {kernels[0]}, fwd+bwd {kernels[1]}; "
        "per-device bytes (arguments, outputs, temp) fwd+bwd "
        f"({mem.argument_size_in_bytes}, {mem.output_size_in_bytes}, "
        f"{mem.temp_size_in_bytes})"
    )
    run_fwd = lambda: fwd_exe(q, k, v)  # noqa: E731
    run_bwd = lambda: bwd_exe(q, k, v, d_out, d_lse)  # noqa: E731
    warm = {"fwd": timing.settle(run_fwd), "fwdbwd": timing.settle(run_bwd)}
    log(
        "warm-up iterations (s): "
        + ", ".join(f"{n} {[round(t, 4) for t in ts]}" for n, ts in warm.items())
    )

    # -- the window --------------------------------------------------------
    phase_s = {
        "fwd": ctx.seconds * FWD_SHARE, "fwdbwd": ctx.seconds * (1 - FWD_SHARE)
    }
    calls = {
        name: timing.calls_per_unit(warm[name][-1], phase_s[name])
        for name in phase_s
    }
    log(
        f"a timed unit: {calls['fwd']} forward calls, {calls['fwdbwd']} "
        "forward+backward calls"
    )
    step_span = lambda: span("step")  # noqa: E731
    ctx.window_opens()
    ctx.tracer.start()
    mark = tracker.mark()
    with ctx.tracer.phase("window"):
        phases = {}
        for name, fn in (("fwd", run_fwd), ("fwdbwd", run_bwd)):
            with ctx.tracer.phase(name):
                phases[name] = timing.timed_units(
                    fn, phase_s[name], inner=calls[name], span=step_span
                )
    compiles, compile_s = tracker.since(mark)
    ctx.tracer.stop()
    medians = {}
    for name, ph in phases.items():
        stats = timing.summary(ph.per_call_s)
        medians[name] = stats["median_s"]
        log(
            f"{name} phase: {ph.calls} calls in {ph.elapsed_s:.4f} s "
            f"({stats['n']} units of {ph.calls_per_unit}); seconds a call "
            f"by unit: {stats}"
        )
    if compiles:
        log(f"WARNING: {compiles} compiles ({compile_s:.2f} s) in the window")

    work = {
        name: fn(mask.area, hq, d) for name, fn in flops.ATTN_FLOPS.items()
    }
    chips = len(ctx.devices)
    end_to_end = {
        f"attn_{name}_tflops_per_chip":
            ph.rate(work[f"attn_{name}"]) / chips / 1e12
        for name, ph in phases.items()
    }
    log(f"rates, all calls over the whole phase: {end_to_end}")

    # -- correct: outside the window ---------------------------------------
    with span("check"):
        ok = _check(
            api, key, mask, tr["check"], ctx.seed, (q, k, v, d_out, d_lse),
            fwd_exe, bwd_exe, sharded,
        )

    from .. import trace_reduce

    scopes = {}
    for t in texts:
        scopes.update(trace_reduce.hlo_scopes(t))
    return Observations(
        end_to_end=end_to_end,
        attempted=sum(ph.calls for ph in phases.values()),
        failed=0,
        correct=ok,
        values={
            "compiles_in_window": float(compiles),
            "fwd_iter_ms": 1e3 * medians["fwd"],
            "fwdbwd_iter_ms": 1e3 * medians["fwdbwd"],
        },
        flops=work,
        iters={name: ph.calls for name, ph in phases.items()},
        hlo_scopes=scopes,
    )


def sample_rows(mask, check: dict, seed: int) -> list[tuple[np.ndarray, np.ndarray]]:
    """The (query rows, context rows) the reference is computed for. In a
    packed mask whole documents are independent: a seeded sample of
    whole documents of at most ``max_doc_tokens``. In any other mask the
    last ``tail_rows`` query rows against the whole context."""
    if mask.doc_lengths:
        cuts = mask.cu_seqlens
        fits = [
            i for i, n in enumerate(mask.doc_lengths)
            if n <= int(check["max_doc_tokens"])
        ]
        rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32])
        picked = rng.choice(
            fits, size=min(int(check["docs"]), len(fits)), replace=False
        )
        return [
            (np.arange(cuts[i], cuts[i + 1]),) * 2 for i in sorted(picked)
        ]
    tail = min(int(check["tail_rows"]), mask.total)
    return [(np.arange(mask.total - tail, mask.total), np.arange(mask.total))]


def _pad(rows: np.ndarray, size: int) -> tuple[np.ndarray, np.ndarray]:
    """Rows padded with -1 to ``size``, and which entries are real."""
    out = np.full(size, -1, np.int64)
    out[: len(rows)] = rows
    return out, out >= 0


def _check(api, key, mask, check, seed, inputs, fwd_exe, bwd_exe, sharded):
    """Compare out, lse, dq, dk, dv with the float32 reference on the
    sampled rows. The loss is restricted to the sampled query rows (the
    cotangents are zero elsewhere), so dk and dv of the context rows are
    those rows' alone — in the dense mask as in the packed ones."""
    import jax
    import jax.numpy as jnp

    q, k, v, d_out, d_lse = inputs
    samples = sample_rows(mask, check, seed)
    pos = np.asarray(api.get_position_ids(key))  # global row of each slot
    slots = np.arange(len(pos))
    slot_of = np.full(mask.total, -1, np.int64)
    real = pos < mask.total
    # first slot of each row wins (an uneven shard's pad slots read 0)
    slot_of[pos[real][::-1]] = slots[real][::-1]
    if (slot_of < 0).any():
        raise AssertionError("position ids do not cover every row")

    in_loss = np.zeros(len(pos), bool)
    for q_rows, _c in samples:
        in_loss[slot_of[q_rows]] = True
    keep = jax.device_put(in_loss, sharded)
    restrict = jax.jit(
        lambda do, dl, m: (
            do * m[:, None, None].astype(do.dtype),
            dl * m[:, None].astype(dl.dtype),
        ),
        donate_argnums=(0, 1),  # the full cotangents are done with
    )
    do_r, dl_r = restrict(d_out, d_lse, keep)

    r_size = max(len(r) for r, _c in samples)
    c_size = max(len(c) for _r, c in samples)
    if mask.doc_lengths:  # one program for every sample, every seed
        r_size = c_size = int(check["max_doc_tokens"])
    padded = []
    for q_rows, c_rows in samples:
        qp, q_real = _pad(q_rows, r_size)
        cp, c_real = _pad(c_rows, c_size)
        padded.append((
            qp, q_real, jnp.asarray(slot_of[np.maximum(qp, 0)], jnp.int32),
            cp, c_real, jnp.asarray(slot_of[np.maximum(cp, 0)], jnp.int32),
        ))

    @jax.jit
    def take(x, slot_idx):
        return jnp.take(x, slot_idx, axis=0)

    # backward first and only its sampled rows kept, then forward: the
    # whole of out and of dq never live at once (13 GB a chip as it is)
    dq, dk, dv = bwd_exe(q, k, v, do_r, dl_r)
    got_bwd = [
        (take(dq, qs), take(dk, cs), take(dv, cs))
        for _qp, _qr, qs, _cp, _cr, cs in padded
    ]
    del dq, dk, dv
    out, lse = fwd_exe(q, k, v)
    lse_global = api.undispatch(lse, key)  # the public way back, whole

    @jax.jit
    def ref(qr, kc, vc, dor, dlr, q_pos, k_pos):
        allow = masks.allowed(mask, q_pos, k_pos)
        allow &= (q_pos >= 0)[:, None] & (k_pos >= 0)[None, :]
        return reference.attention_rows(qr, kc, vc, allow, dor, dlr)

    worst: dict[str, float] = {}
    with jax.default_matmul_precision("highest"):
        for (qp, q_real, qs, cp, c_real, cs), bwd_rows in zip(padded, got_bwd):
            want = ref(
                take(q, qs), take(k, cs), take(v, cs), take(do_r, qs),
                take(dl_r, qs), jnp.asarray(qp, jnp.int32),
                jnp.asarray(cp, jnp.int32),
            )
            got = (
                take(out, qs),
                jnp.take(lse_global, jnp.asarray(np.maximum(qp, 0)), axis=0),
                *bwd_rows,
            )
            reals = (q_real, q_real, q_real, c_real, c_real)
            for name, g, w, keep_rows in zip(
                ("out", "lse", "dq", "dk", "dv"), got, want, reals
            ):
                g = np.asarray(g, np.float32)[keep_rows]
                w = np.asarray(w, np.float32)[keep_rows]
                if not np.isfinite(g).all():
                    err = float("inf")
                elif name == "lse":
                    err = float(np.abs(g - w).max())
                else:
                    err = float(
                        np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-30)
                    )
                worst[name] = max(worst.get(name, 0.0), err)
    ok = all(
        e <= (LSE_ABS_TOL if n == "lse" else REL_L2_TOL)
        for n, e in worst.items()
    )
    log(
        f"correct={ok}: {len(samples)} samples "
        f"({[len(r) for r, _c in samples]} query rows) vs the float32 "
        "reference: "
        + ", ".join(f"{n} {e:.2e}" for n, e in worst.items())
        + f" (tolerance: lse max-abs {LSE_ABS_TOL:g}, others relative L2 "
        f"{REL_L2_TOL:g})"
    )
    return ok
