"""Traffic kind ``train_looped``: ``train_pattern``'s one-mask stream for
a looped decoder (Ouro-2.6B through
``magiattention_tpu/models/pattern.py``: the layer stack applied
``total_ut_steps`` times on shared weights inside one ``lax.scan``, an
exit through the shared head and a gate after every pass).

Closed loop, one packed sequence a step, AdamW; every step brings mask 0
of the stream (``masks.build_mask``: the same documents in every run),
and ``--seed`` makes the weights and the token ids only. Set-up plans
(``build_magi_pattern``: every layer is ``full_attention``, so one
dispatch and one plan), dispatches the token ids and compiles the step;
the window and ``train_tokens_per_s`` are ``train_pattern``'s
(``timing.timed_units``, ``timing.Phase.rate``).

``correct`` is decided outside the window: the loss of one packed
``check_tokens`` sequence (the exits' expected loss less the entropy
term) and its gradient with respect to every parameter, the exit gate's
and the shared head's included, against ``reference_ouro`` in float32 on
the same weights and tokens. The weights are the seed's, made anew after
the window, not the ones the window trained: 22 AdamW steps at 3e-4 on
the window's two batches saturate the gate (the first exit takes 0.987
to 1.000 of every token's mass in 14 seeds of 14, my chip runs, PR 32),
and a model that ran ONE PASS FEWER then read the same errors to three
digits as the sound one, and passed. What the window trained is held
only to a finite loss at its last step. The model has no experts, so
there is no routing to hold.
"""

from __future__ import annotations

import itertools

import numpy as np

from .. import flops_ouro, masks, reference_ouro, timing
from ..harness import Observations, key_from_seed, log
from . import train_pattern
from .train_pattern import CHECK_STEP, check_mask

# bf16 model against the float32 plain decoder, both on the seed's
# weights (module docstring): the loss of one packed sequence and its
# gradient with respect to every parameter by relative L2, the worst
# layer's; ``exit_gate``'s weight and bias as one parameter (the bias's
# gradient alone is one number summed over 4,096 tokens' signed terms:
# its relative error read 5.6e-5 to 1.4 over 24 seeds where the weight's
# read 1.5e-2 to 3.4e-2: a scalar's relative error has no scale of its
# own).
#
# The limits, each from two readings at the published widths (my chip
# runs, PR 32; PERF.md section 6): the largest the check gave over its
# seeds (24, the seed's weights), and the nearest precision below, fp8
# weights (two seeds, rounded on the host, the seed's weights too), which
# has to come out not correct. Gradients: at most 4.52e-2 as the cell
# runs (wq and wk, 3.0e-2 to 4.5e-2, growing with the mass the seed's
# gate gives the last exit, 0.06 to 0.52, because more of the gradient
# then comes through all 24 layer applications; the gate 1.5e-2 to
# 3.4e-2, the head at most 2.3e-2), 2.3e-1 to 5.0e-1 with fp8 weights:
# the dense training kind's 6e-2 (``train_stream``) stands between, 1.3
# times the first and a quarter of the second; it was never moved. The
# loss: 3.3e-6 to 4.54e-5 relative as the cell runs, 4.5e-6 and 3.1e-4
# with fp8 weights: the precision hardly moves it, so its limit is three
# times the first reading alone, 1.5e-4, and what it holds is the
# objective's own arithmetic: ``exit_entropy_weight`` a tenth off (0.055
# for 0.05) moves it 0.005 x H(p) / 11.3 = 5e-4 where the exits are all
# live (H(p) about 1.2 nats on the seed's weights), the entropy's sign
# flipped 1e-2. (The limit was ``train_stream``'s 1e-3, then 7e-4 = three
# times 2.25e-4, the largest of six readings on TRAINED weights; it moved
# with the weights the check reads, which is another comparison.)
LOSS_REL_TOL = 1.5e-4  # 3.3 x 4.54e-5, the largest of 24 seeds
GRAD_REL_L2_TOL = 6e-2  # train_stream's, train_pattern's and train_latent's


class Job(train_pattern.Job):
    """What a run and its check share (``train_pattern.Job``), for an
    ``ouro`` configuration: dense, heads and vocabulary whole.
    ``model_overrides`` replaces fields of the model's ``PatternConfig``
    (the tests' faults; the reference never sees them)."""

    def __init__(self, cfg: dict, tr: dict, seed: int, devices,
                 model_overrides: dict | None = None):
        import dataclasses

        from jax.sharding import Mesh

        from magiattention_tpu.models.pattern import ouro_config

        # the whole vocabulary is here: ``batch_for`` draws ids inside it
        self.cfg = dict(cfg, vocab_here=cfg["vocab_size"])
        self.tr, self.seed = tr, int(seed)
        self.pcfg = ouro_config(
            cfg, dtype=tr["dtype"], remat=bool(tr["remat"])
        )
        if model_overrides:
            self.pcfg = dataclasses.replace(self.pcfg, **model_overrides)
        self.mesh = Mesh(np.array(devices).reshape(1, -1), ("dp", "cp"))


def run(cell, ctx) -> Observations:
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from magiattention_tpu.models.pattern import init_pattern_params
    from magiattention_tpu.telemetry import get_compile_tracker

    cfg, tr = cell.config, cell.traffic
    total = int(tr["total_tokens"])
    job = Job(cfg, tr, ctx.seed, ctx.devices)
    replicated = NamedSharding(job.mesh, P())
    tracker = get_compile_tracker()
    span = ctx.tracer.span
    opt = optax.adamw(float(tr["learning_rate"]))

    state = {}
    state["params"] = jax.jit(
        lambda r: init_pattern_params(r, job.pcfg), out_shardings=replicated
    )(key_from_seed(ctx.seed))
    state["opt"] = jax.jit(opt.init, out_shardings=replicated)(state["params"])
    n_params = sum(x.size for x in jax.tree.leaves(state["params"]))
    log(
        f"model: {job.pcfg.n_layers} layers x {job.pcfg.n_loops} passes on "
        f"shared weights ({job.pcfg.n_heads} query / {job.pcfg.n_kv_heads} "
        f"key-value heads of {job.pcfg.head_dim}), {n_params / 1e6:.1f} M "
        "parameters, fp32 master weights + AdamW = "
        f"{16 * n_params / 1e9:.2f} GB with gradients"
    )

    # -- set-up: mask 0 of the stream ---------------------------------------
    with span("data"):
        mask = masks.build_mask(tr["mask"], total, index=0)
    log(
        f"mask 0: {mask.describe()}; documents of {min(mask.doc_lengths)} to "
        f"{max(mask.doc_lengths)} tokens"
    )
    with span("plan"):
        model, meta = job.build(mask)
        step_fn = model.make_train_step(opt)
    for kind, p in model.attn_params.items():
        log(f"tiles of {kind}: (block_q, block_k, head_block) = "
            f"({p.block_q}, {p.block_k}, {p.head_block}), grid {p.grid}")
    with span("data"):
        _g, tokens, labels, pos = job.batch_for(meta, total, 0)
    warm_batch = (tokens, labels, pos)
    with span("compile"):
        exe = step_fn.lower(
            state["params"], state["opt"], *warm_batch
        ).compile()

    def steady(batch=warm_batch):
        state["params"], state["opt"], state["loss"] = exe(
            state["params"], state["opt"], *batch
        )
        return state["loss"]

    warm_times = timing.settle(steady)
    log(f"warm-up steps on mask 0 (s): {[round(t, 4) for t in warm_times]}")
    mem = exe.memory_analysis()
    log(
        "the step's per-device bytes (arguments, outputs, temp): "
        f"({mem.argument_size_in_bytes}, {mem.output_size_in_bytes}, "
        f"{mem.temp_size_in_bytes})"
    )
    work = {
        "train_step": flops_ouro.train_step_flops(cfg, total, mask.area),
        "attn_full_executed": flops_ouro.attn_executed_flops(cfg, mask.area),
    }
    values, iters = {}, {}

    # -- the window: the same mask, steps enqueued back to back -------------
    per_unit = timing.calls_per_unit(warm_times[-1], ctx.seconds)
    batches = [warm_batch] + [  # prefetched, as a loader would
        job.batch_for(meta, total, k)[1:] for k in range(1, per_unit)
    ]
    jax.block_until_ready(batches)
    upcoming = itertools.cycle(batches)
    ctx.window_opens()
    ctx.tracer.start()
    mark = tracker.mark()
    with ctx.tracer.phase("window"):
        phase = timing.timed_units(
            lambda: (steady(next(upcoming)), state), ctx.seconds,
            inner=per_unit, span=lambda: span("step"),
        )
    compiles, compile_s = tracker.since(mark)
    summary = timing.summary(phase.per_call_s)
    rate = phase.rate(total)
    values["steady_step_s"] = summary["median_s"]
    values["compiles_in_window"] = float(compiles)
    iters["window"] = phase.calls
    log(
        f"window: {phase.calls} steps on mask 0 in {phase.elapsed_s:.4f} s "
        f"({summary['n']} units of {per_unit}), {compiles} compiles taking "
        f"{compile_s:.2f} s; {rate:.2f} tokens/s; seconds a step by unit: "
        f"{summary}"
    )
    ctx.tracer.stop()
    scopes = {}
    if ctx.trace:
        from .. import trace_reduce

        scopes = trace_reduce.hlo_scopes(exe.as_text())

    # -- correct: outside the window ----------------------------------------
    last_loss = float(state["loss"])
    log(f"the window's last step read a loss of {last_loss:.6f}")
    state.clear()  # room for the float32 reference
    del exe, warm_batch, batches, upcoming, steady
    with span("check"):
        # on the seed's weights, not the trained ones (module docstring)
        ok = np.isfinite(last_loss) and _check(job, jax.jit(
            lambda r: init_pattern_params(r, job.pcfg),
            out_shardings=replicated,
        )(key_from_seed(ctx.seed)))

    return Observations(
        end_to_end={"train_tokens_per_s": rate},
        attempted=phase.calls,
        failed=0,
        correct=bool(ok) and phase.calls > 0,
        values=values,
        flops=work,
        iters=iters,
        hlo_scopes=scopes,
    )


def check_errors(job: Job, params, *, model_job: Job | None = None,
                 model_params=None):
    """(relative loss error, {parameter: relative L2 gradient error, the
    worst layer's}) of the model on one packed sequence of
    ``check_tokens`` tokens against ``reference_ouro`` on the same
    weights and tokens. ``model_job`` builds the model from another
    configuration than the reference gets and ``model_params`` hands it
    other weights: the tests' faults."""
    import jax
    import jax.numpy as jnp

    mask = check_mask(job.tr)
    t = mask.total
    model, meta = (model_job or job).build(mask)
    for kind, p in model.attn_params.items():  # beside the window's, above
        log(f"check: tiles of {kind}: (block_q, block_k, head_block) = "
            f"({p.block_q}, {p.block_k}, {p.head_block}), grid {p.grid}")
    tokens_g, tokens, labels, pos = job.batch_for(meta, t, CHECK_STEP)

    got, got_grad = jax.jit(jax.value_and_grad(model.loss_fn))(
        params if model_params is None else model_params,
        tokens, labels, pos, model.sharded_tables(),
    )
    log(f"check: the model's loss and gradients are in ({float(got):.6f})")
    rows = jnp.arange(t)
    with jax.default_matmul_precision("highest"):
        want, want_grad = jax.jit(jax.value_and_grad(
            lambda p, tok, lab: reference_ouro.ouro_loss(
                p, tok, lab, masks.allowed(mask, rows, rows),
                job.cfg, recompute=True,
            )
        ))(
            params, jnp.asarray(tokens_g, jnp.int32),
            jnp.asarray(np.roll(tokens_g, -1), jnp.int32),
        )

    def as_one(grads):
        """The gate's weight and bias as the one affine map they are: the
        bias's gradient is a single number, a sum over tokens of signed
        terms, and its relative error alone has no scale."""
        gate = grads["exit_gate"]
        return dict(grads, exit_gate=jnp.concatenate(
            [gate["w"].ravel(), gate["b"].ravel()]
        ))

    errs = jax.jit(lambda g, w: jax.tree.map(
        lambda a, b: jnp.linalg.norm((a - b).ravel())
        / jnp.maximum(jnp.linalg.norm(b.ravel()), 1e-30),
        as_one(g), as_one(w),
    ))(got_grad, want_grad)
    errs = jax.device_get(errs)
    layers = errs.pop("layers")
    grad_err: dict[str, float] = {n: float(e) for n, e in errs.items()}
    for layer in layers:  # the worst layer, name by name
        for name, e in layer.items():
            e = float(e) if np.isfinite(e) else float("inf")
            grad_err[name] = max(grad_err.get(name, 0.0), e)
    got, want = float(got), float(want)
    log(
        f"check: one packed sequence of {t} tokens (documents "
        f"{list(mask.doc_lengths)}), model loss {got:.6f} vs float32 plain "
        f"looped decoder {want:.6f}"
    )
    return abs(got - want) / abs(want), grad_err


def passes(loss_rel: float, grad_err: dict[str, float]) -> bool:
    return bool(
        loss_rel <= LOSS_REL_TOL
        and all(e <= GRAD_REL_L2_TOL for e in grad_err.values())
    )


def _check(job: Job, params) -> bool:
    rel, grad_err = check_errors(job, params)
    ok = passes(rel, grad_err)
    log(
        f"correct={ok}: loss relative {rel:.2e} (tolerance {LOSS_REL_TOL:g}); "
        "gradient by parameter, relative L2, the worst layer's: "
        + ", ".join(f"{n} {e:.2e}" for n, e in sorted(grad_err.items()))
        + f" (tolerance {GRAD_REL_L2_TOL:g})"
    )
    return ok
