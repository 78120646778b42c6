"""Traffic kind ``train_cca``: ``train_pattern``'s one-mask stream for a
compressed-convolutional-attention decoder with top-1 experts behind an
MLP router (ZAYA1-8B through ``magiattention_tpu/models/pattern.py``:
q and k mixed along a document by two causal convolutions, half the value
heads read from the token before, the router's state handed from layer
to layer).

Closed loop, one packed sequence a step, AdamW; every step brings the
traffic file's mask (``masks.build_mask``: the same documents in every
run), and ``--seed`` makes the weights and the token ids only. Set-up
plans (``build_magi_pattern``: every layer is ``full_attention``, so one
dispatch, one plan, and the documents' shift plan on that dispatch),
dispatches the token ids and compiles the step; the window and
``train_tokens_per_s`` are ``train_pattern``'s (``timing.timed_units``,
``timing.Phase.rate``).

``correct`` is decided outside the window: the loss of one packed
``check_tokens`` sequence (documents ``check_mask``: a boundary off the
chunk grid, so a shift crosses a document's start and a chunk's edge) and
its gradient with respect to every parameter, against ``reference_zaya``
in float32 on the same weights and tokens. The weights are the seed's,
made anew after the window, as ``train_looped`` reads them (PERF.md
section 6, PR 32: what a window's AdamW steps on two memorised batches do
to a gate or a router's margins is not this comparison's business); what
the window trained is held to a finite loss at its last step.
"""

from __future__ import annotations

import dataclasses
import itertools

import numpy as np

from .. import flops_zaya, masks, reference_zaya, timing
from ..harness import Observations, key_from_seed, log
from . import train_pattern
from .train_pattern import CHECK_STEP, check_mask

# bf16 model against the float32 plain decoder, ``train_pattern``'s
# comparison: the loss of one packed sequence and its gradient with
# respect to every parameter by relative L2. A layer's parameter is held
# to its WORST layer's difference, against the norm of the parameter's
# LARGEST layer's gradient: the bf16 error of a layer's gradient is about
# the same in absolute size in every layer (one seed's ``w_router``:
# 0.5e-4 to 3.6e-4 over the five layers, where the gradient's own norm
# runs from 1.3e-3 to 1.1e-2), so a layer's error over its own norm reads
# a small gradient's noise (1.5e-1 in the two small layers of that seed,
# 1e-2 to 3e-2 in the three others; a key head's temperature 5.1e-1 in a
# layer whose gradient is a fifth of the largest), and it does not
# follow the tokens a layer holds here. Not all layers' as one vector:
# that adds a wrong layer's error to the others' agreement. A layer whose
# gradient is all wrong reads its norm's share of the largest's.
#
# Top-1 is discontinuous, so the reference follows the model's expert
# choices and the choices are held to the reference's own router apart
# (the share of tokens its argmax would not have chosen, and the widest
# tie broken in softmax score): see ``train_pattern``. Inside the model
# the router reads the bf16 hidden state whatever its own dtype, so the
# router is also run ALONE, the model's ``route`` on what the
# reference's router read in float32, a layer, and its chosen score and
# the state it hands on are held to the reference's: that is what a
# bfloat16 router fails.
#
# The limits, each between two readings at the published widths on the
# seed's weights (PERF.md section 6, PR 39, has them with their origin:
# four seeds on the chip, twenty-four on the CPU, which agree): as the cell
# runs, and the control, the nearest precision below (fp8 weights; for
# the router alone a bfloat16 router), which has to come out not
# correct. Gradients at most 4.4e-2 (the expert half; the others under
# 2.2e-2) / 1.8e-1 to 2.2e-1: 8e-2. The gradients that come through a sum
# that cancels (``CANCELLING``: the router's six, through ``<dL/dy,
# expert(h)>`` a token, and a key head's temperature, one number a head
# a layer): 2.1e-2 to 1.2e-1 / 1.8e-1 to 3.7e-1 and 1.0e-1 to 8.9e-1:
# 2.5e-1 (``train_latent``'s ``w_router`` has the same), over the
# control's lowest reading because the sound readings' tail is long and
# the control fails four other limits by 2.3x and more; what this limit
# is for is a wrong router, which reads 1. Expert choices: 0.38 to 1.1%
# of the tokens differ, the widest tie 1.9e-3 / 9.4 to 16.9% and 1.9e-2:
# 3e-2 and 6e-3. The router alone: 0, bit-equal on the chip and on the
# CPU / 1.9e-3 to 2.9e-3: 1e-4. The loss hardly moves with the precision
# (at most 2.9e-5 / 1.0e-5 to 2.0e-4): ``train_latent``'s 3e-4, and it is
# the gradients that hold the precision.
LOSS_REL_TOL = 3e-4  # train_latent's
GRAD_REL_L2_TOL = 8e-2
CANCELLING_GRAD_REL_L2_TOL = 2.5e-1
ROUTER = (
    "w_router_down", "router_gamma", "router_norm", "w_router_mlp1",
    "w_router_mlp2", "w_router",
)
CANCELLING = ROUTER + ("cca_temp",)
ROUTE_FLIP_SHARE_TOL = 3e-2
ROUTE_MARGIN_TOL = 6e-3
ROUTER_REL_TOL = 1e-4  # the router alone: chosen score, state handed on


def grad_limit(name: str) -> float:
    """The relative L2 limit of one parameter's gradient."""
    return CANCELLING_GRAD_REL_L2_TOL if name in CANCELLING else GRAD_REL_L2_TOL


class Job(train_pattern.Job):
    """What a run and its check share (``train_pattern.Job``), for a
    ``zaya`` configuration. ``model_overrides`` replaces fields of the
    model's ``PatternConfig`` (the tests' faults; the reference never
    sees them)."""

    def __init__(self, cfg: dict, tr: dict, seed: int, devices,
                 model_overrides: dict | None = None):
        from jax.sharding import Mesh

        from magiattention_tpu.models.pattern import zaya_config

        self.cfg, self.tr, self.seed = cfg, tr, int(seed)
        self.pcfg = zaya_config(
            cfg, dtype=tr["dtype"], remat=bool(tr["remat"]),
            expert_range=tuple(cfg["experts_here"]),
            vocab_size=cfg["vocab_here"],
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

    def seed_params():
        return jax.jit(
            lambda r: init_pattern_params(r, job.pcfg),
            out_shardings=replicated,
        )(key_from_seed(ctx.seed))

    state = {"params": seed_params()}
    state["opt"] = jax.jit(opt.init, out_shardings=replicated)(state["params"])
    n_params = sum(x.size for x in jax.tree.leaves(state["params"]))
    log(
        f"model: {job.pcfg.n_layers} layers of compressed convolutional "
        f"attention ({job.pcfg.n_heads} query / {job.pcfg.n_kv_heads} "
        f"key-value heads of {job.pcfg.head_dim}, convolutions of "
        f"{job.pcfg.conv_taps} taps) and top-{job.pcfg.top_k} of "
        f"{job.pcfg.n_experts} experts, {n_params / 1e6:.1f} M parameters, "
        f"fp32 master weights + AdamW = {16 * n_params / 1e9:.2f} GB with "
        "gradients"
    )

    # -- set-up: the traffic file's mask -------------------------------------
    with span("data"):
        mask = masks.build_mask(tr["mask"], total, index=0)
    log(f"mask: {mask.describe()}; documents {list(mask.doc_lengths)}")
    with span("plan"):
        model, meta = job.build(mask)
        step_fn = model.make_train_step(opt)
    for kind, p in model.attn_params.items():
        log(f"tiles of {kind}: (block_q, block_k, head_block) = "
            f"({p.block_q}, {p.block_k}, {p.head_block}), grid {p.grid}")
    shift = model.shift_plan
    log(
        f"shift: taps {shift.taps} over {shift.documents} documents, "
        f"{shift.remote_rows} rows from another rank"
    )
    with span("data"):
        _g, tokens, labels, pos = job.batch_for(meta, total, 0)
    warm_batch = (tokens, labels, pos)
    routed = total * cfg["num_experts_per_tok"]
    stats_of = jax.jit(
        lambda p, *b: model.loss_fn(
            p, *b, model.sharded_tables(), with_stats=True
        )[1]
    )

    def held_load(when: str):
        """The tokens the experts held here compute in a step on the
        weights as they stand (one forward pass, no gradient)."""
        counts = np.asarray(stats_of(state["params"], *warm_batch)["expert_counts"])
        log(
            f"expert layers, {when}: tokens computed here a layer "
            f"{counts.sum(1).tolist()} of {routed} routed (no held expert: "
            f"{[routed - int(c.sum()) for c in counts]}); busiest held expert "
            "over the mean "
            + str([round(float(c.max() * len(c) / max(c.sum(), 1)), 3)
                   for c in counts])
        )
        return counts

    counts = held_load("the seed's weights")
    model.record_expert_load(counts)
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
    log(f"warm-up steps (s): {[round(t, 4) for t in warm_times]}")
    mem = exe.memory_analysis()
    log(
        "the step's per-device bytes (arguments, outputs, temp): "
        f"({mem.argument_size_in_bytes}, {mem.output_size_in_bytes}, "
        f"{mem.temp_size_in_bytes})"
    )
    # top-1 of 16 on a router that trains: the load the window opens on
    # is not the one it closes on, so the step's FLOPs are counted at the
    # mean of the two readings
    pairs_open = float(held_load("as the window opens").sum())
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
        f"window: {phase.calls} steps in {phase.elapsed_s:.4f} s "
        f"({summary['n']} units of {per_unit}), {compiles} compiles taking "
        f"{compile_s:.2f} s; {rate:.2f} tokens/s; seconds a step by unit: "
        f"{summary}"
    )
    ctx.tracer.stop()
    pairs_close = float(held_load("as the window closes").sum())
    work = {
        "train_step": flops_zaya.train_step_flops(
            cfg, total, mask.area, (pairs_open + pairs_close) / 2
        ),
        "attn_full_executed": flops_zaya.attn_executed_flops(cfg, mask.area),
    }
    scopes = {}
    if ctx.trace:
        from .. import trace_reduce

        scopes = trace_reduce.hlo_scopes(exe.as_text())

    # -- correct: outside the window ----------------------------------------
    last_loss = float(state["loss"])
    log(f"the window's last step read a loss of {last_loss:.6f}")
    state.clear()  # room for the float32 reference
    del exe, warm_batch, batches, upcoming, steady, stats_of
    with span("check"):
        # on the seed's weights, not the trained ones (module docstring)
        ok = np.isfinite(last_loss) and _check(job, seed_params())

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
                 model_params=None, free_routing: bool = False):
    """``train_pattern.check_errors`` against ``reference_zaya``:
    (relative loss error, {parameter: relative L2 gradient error, of a
    layer's parameter the worst of the layers compared}, the routing's
    readings: expert choices against the reference's own router, and the
    model's router against the reference's on the reference's inputs).
    ``model_job`` builds the model from another configuration than the
    reference gets and ``model_params`` hands it other weights: the
    tests' faults. The reference follows the model's expert choices
    unless ``free_routing``."""
    import jax
    import jax.numpy as jnp

    from magiattention_tpu.models.pattern import route

    mask = check_mask(job.tr)
    t = mask.total
    model_job = model_job or job
    model, meta = model_job.build(mask)
    for kind, p in model.attn_params.items():  # beside the window's, above
        log(f"check: tiles of {kind}: (block_q, block_k, head_block) = "
            f"({p.block_q}, {p.block_k}, {p.head_block}), grid {p.grid}")
    tokens_g, tokens, labels, pos = job.batch_for(meta, t, CHECK_STEP)
    if model_params is None:
        model_params = params

    (got, stats), got_grad = jax.jit(jax.value_and_grad(
        lambda p, *batch: model.loss_fn(p, *batch, with_stats=True),
        has_aux=True,
    ))(model_params, tokens, labels, pos, model.sharded_tables())
    log(f"check: the model's loss and gradients are in ({float(got):.6f})")
    # the model's choices are in dispatch order: slot i holds position perm[i]
    perm = np.asarray(meta.perm_idx)
    assert sorted(perm.tolist()) == list(range(t)), "a padded dispatch"
    got_idx = np.zeros_like(np.asarray(stats["expert_idx"])[0])
    got_idx[:, perm] = np.asarray(stats["expert_idx"])[0]  # [layers, t, k]
    rows = jnp.arange(t)
    with jax.default_matmul_precision("highest"):
        (want, (_idx, margins, read, state)), want_grad = jax.jit(
            jax.value_and_grad(
                lambda p, tok, lab, forced: reference_zaya.zaya_loss(
                    p, tok, lab, masks.allowed(mask, rows, rows), job.cfg,
                    with_routing=True, forced_routing=forced,
                ),
                has_aux=True,
            )
        )(
            params, jnp.asarray(tokens_g, jnp.int32),
            jnp.asarray(np.roll(tokens_g, -1), jnp.int32),
            None if free_routing else jnp.asarray(got_idx),
        )

        # the router alone, on what the reference's router read: the
        # model's ``route`` against ``reference_zaya.router``, a layer
        def routers(layers, model_layers, read, state):
            found = []
            for want_w, got_w, g, r in zip(layers, model_layers, read, state):
                _i, score, _m, r_want = reference_zaya.router(
                    g, r, {n: a.astype(jnp.float32) for n, a in want_w.items()},
                    job.cfg,
                )
                _i, got_score, r_got = route(g, got_w, model_job.pcfg, r)
                found.append([
                    jnp.sum((got_score - score) ** 2), jnp.sum(score ** 2),
                    jnp.sum((r_got - r_want) ** 2), jnp.sum(r_want ** 2),
                ])
            return jnp.asarray(found)

        router_sq = np.asarray(jax.jit(routers)(
            params["layers"], model_params["layers"], read, state
        ))
    del read, state

    # squared norms of the difference and of the reference, leaf by leaf
    sq = jax.device_get(jax.jit(lambda g, w: jax.tree.map(
        lambda a, b: jnp.stack([jnp.sum((a - b) ** 2), jnp.sum(b ** 2)]), g, w
    ))(got_grad, want_grad))

    def rel_l2(diff, ref):
        rel = float(np.sqrt(diff) / max(np.sqrt(ref), 1e-30))
        return rel if np.isfinite(rel) else float("inf")

    first, last = job.cfg["experts_here"]
    held = ((got_idx >= first) & (got_idx < last)).sum(axis=(1, 2))
    log(f"check: tokens with a held expert a layer {held.tolist()} of {t}")
    grad_err: dict[str, float] = {
        n: rel_l2(*e) for n, e in sq.items() if n != "layers"
    }
    # a layer's parameter: the worst layer's difference against the
    # largest layer's norm (module comment)
    for name in sq["layers"][0]:
        diffs, refs = zip(*(layer[name] for layer in sq["layers"]))
        grad_err[name] = rel_l2(max(diffs), max(refs))
        log(f"check: {name} a layer, against its own norm alone: "
            + ", ".join(f"{rel_l2(d, r):.2e}" for d, r in zip(diffs, refs))
            + "; the reference's norm: "
            + ", ".join(f"{np.sqrt(r):.2e}" for r in refs))
    grad_err.pop("expert_bias", None)  # a buffer: no gradient on either side
    margins = np.asarray(margins)
    score = [rel_l2(*row[:2]) for row in router_sq]
    handed = [rel_l2(*row[2:]) for row in router_sq]
    routing = {
        "flipped_share": float((margins > 0).mean()),
        "worst_margin": float(margins.max()),
        "router_score_rel": max(score),
        "router_state_rel": max(handed),
    }
    got, want = float(got), float(want)
    log(
        f"check: one packed sequence of {t} tokens (documents "
        f"{list(mask.doc_lengths)}), model loss {got:.6f} vs float32 plain "
        f"decoder {want:.6f}; {100 * routing['flipped_share']:.4f}% of the "
        "tokens routed otherwise than the reference's own router would, the "
        f"widest tie broken {routing['worst_margin']:.3e} in score; the "
        "router alone on the reference's inputs, a layer: chosen score "
        + ", ".join(f"{e:.2e}" for e in score) + "; state "
        + ", ".join(f"{e:.2e}" for e in handed)
    )
    return abs(got - want) / abs(want), grad_err, routing


def passes(loss_rel: float, grad_err: dict[str, float],
           routing: dict[str, float]) -> bool:
    return bool(
        loss_rel <= LOSS_REL_TOL
        and all(e <= grad_limit(n) for n, e in grad_err.items())
        and routing["flipped_share"] <= ROUTE_FLIP_SHARE_TOL
        and routing["worst_margin"] <= ROUTE_MARGIN_TOL
        and routing["router_score_rel"] <= ROUTER_REL_TOL
        and routing["router_state_rel"] <= ROUTER_REL_TOL
    )


def _check(job: Job, params) -> bool:
    rel, grad_err, routing = check_errors(job, params)
    ok = passes(rel, grad_err, routing)
    log(
        f"correct={ok}: loss relative {rel:.2e} (tolerance {LOSS_REL_TOL:g}); "
        "gradient by parameter, relative L2, of a layer's parameter the "
        "worst layer's difference over the largest layer's norm: "
        + ", ".join(f"{n} {e:.2e}" for n, e in sorted(grad_err.items()))
        + f" (tolerance {GRAD_REL_L2_TOL:g}, {CANCELLING_GRAD_REL_L2_TOL:g} "
        f"on {CANCELLING}); routing {routing} (tolerances "
        f"{ROUTE_FLIP_SHARE_TOL:g}, {ROUTE_MARGIN_TOL:g}, {ROUTER_REL_TOL:g} "
        "twice)"
    )
    return ok
