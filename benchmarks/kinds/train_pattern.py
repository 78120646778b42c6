"""Traffic kind ``train_pattern``: ``train_stream``'s one-mask stream for
a pattern-driven decoder (``magiattention_tpu/models/pattern.py``).

Closed loop, one packed sequence a step, AdamW; every step brings mask 0
of the stream (``masks.build_mask``: the same documents in every run),
and ``--seed`` makes the weights and the token ids only. Set-up plans
(``build_magi_pattern``: one dispatch, a plan an attention kind),
dispatches the token ids and compiles the step; the window is a run of
timed units of steps enqueued back to back on a few prefetched batches
(``timing.timed_units``), and ``train_tokens_per_s`` is the tokens of
every step of the window over the window's whole time
(``timing.Phase.rate``).

``correct`` is decided outside the window: the loss of one packed
``check_tokens`` sequence and its gradient with respect to every
parameter, against ``reference_afmoe`` in float32 on the same weights and
tokens. The check's documents are the traffic file's ``check_mask`` where
it has one: the quantile rule caps a document at a quarter of the
sequence, so at 4,096 tokens no document would pass a 2,048 window and
the sliding layers' mask would equal the global layers'.
"""

from __future__ import annotations

import itertools

import numpy as np

from .. import flops_afmoe, masks, reference_afmoe, timing
from ..harness import Observations, key_from_seed, log

SLIDING, FULL = "sliding_attention", "full_attention"

# bf16 model against the float32 plain decoder: the loss of one packed
# sequence and its gradient with respect to every parameter by relative
# L2. The loss at random init is about ln(vocabulary slice) whatever the
# model attends to, so the gradients hold the masks, the kernels, the
# gate, the router and the precision.
#
# Top-k is discontinuous: bf16 activations flip near-ties between the
# 8th and the 9th of 128 scores (about 1% of the token-expert pairs at
# the published widths), and a flipped pair swaps one expert's whole term
# in that token's FFN, which moves every gradient by far more than bf16
# does. So the reference is handed the model's choices
# (``forced_routing``), its weights still its own float32 scores, and the
# choices are held to the reference's router apart: the share of pairs
# the reference's own router would not have chosen, and the widest tie
# any of them broke (how far its float32 score lies under the k-th
# best). A router that chose anything but near-ties fails there.
#
# The limits, each from two readings at the published widths (my chip
# runs, PR 26; PERF.md section 6): the largest the cell gave over its
# seeds, and the nearest precision below, fp8 weights, which has to come
# out not correct. Gradients of the parameters a dense decoder has too:
# up to 3.9e-2 as the cell runs, 8.9e-2 to 2.7e-1 with fp8 weights;
# train_stream's 6e-2 stands between. Parameters on the expert path (the
# router and the held experts) read 2.5 to 3 times the others' error in
# bf16, on the CPU at toy size as on the chip: 6.6e-2 to 1.06e-1 as the
# cell runs, 2.0e-1 to 2.7e-1 with fp8 weights; their limit is 1.5e-1.
# Expert choices: 1.0 to 1.2% of the pairs differ, the widest tie broken
# 6.8e-3 to 1.17e-2 in score; with fp8 weights 10.6% and 9.3e-2; the
# limits are 3e-2 both. What the check cannot tell apart at these widths:
# a bfloat16 router (1.03e-1 on w_router against float32's 9.0e-2, my
# chip run, PR 26); at toy size it fails (test_pattern_check.py).
LOSS_REL_TOL = 1e-3
GRAD_REL_L2_TOL = 6e-2  # train_stream's, for the dense decoder's parameters
EXPERT_GRAD_REL_L2_TOL = 1.5e-1
EXPERT_PATH = ("w_router", "we_gate", "we_up", "we_down")
ROUTE_FLIP_SHARE_TOL = 3e-2
ROUTE_MARGIN_TOL = 3e-2
CHECK_STEP = 1_000_000  # the check's token ids: a step no window reaches


def window_area(doc_lengths, window: int) -> int:
    """Allowed pairs of packed causal documents under a window: a row
    sees min(its place in its document, window) keys."""
    tri = lambda n: n * (n + 1) // 2  # noqa: E731
    return sum(
        tri(n) if n <= window else tri(window) + (n - window) * window
        for n in doc_lengths
    )


class Job:
    """What a run and its check share: the model's sizes, the mesh, how
    documents become a model and a step's token ids a batch.
    ``model_overrides`` replaces fields of the model's ``PatternConfig``
    (the tests' faults; the reference never sees them)."""

    def __init__(self, cfg: dict, tr: dict, seed: int, devices,
                 model_overrides: dict | None = None):
        import dataclasses

        from jax.sharding import Mesh

        from magiattention_tpu.models.pattern import afmoe_config

        self.cfg, self.tr, self.seed = cfg, tr, int(seed)
        self.pcfg = afmoe_config(
            cfg, dtype=tr["dtype"], remat=bool(tr["remat"]),
            expert_range=tuple(cfg["experts_here"]),
            vocab_size=cfg["vocab_here"],
        )
        if model_overrides:
            self.pcfg = dataclasses.replace(self.pcfg, **model_overrides)
        self.mesh = Mesh(np.array(devices).reshape(1, -1), ("dp", "cp"))

    def build(self, mask):
        """(model, dispatch meta) for one packed mask: the plans on the
        host."""
        from magiattention_tpu.models.pattern import build_magi_pattern

        return build_magi_pattern(
            self.pcfg, self.mesh, mask.cu_seqlens,
            chunk_size=int(self.tr["chunk_size"]),
        )

    def areas(self, mask) -> dict[str, int]:
        return {
            FULL: mask.area,
            SLIDING: window_area(mask.doc_lengths, self.cfg["sliding_window"]),
        }

    def batch_for(self, meta, mask_total: int, k: int):
        """Token ids of step ``k`` from the seed, drawn inside this
        rank's vocabulary slice, dispatched, with their next-token labels
        (the distributed roll) and position ids."""
        import jax
        import jax.numpy as jnp

        from magiattention_tpu.parallel import dispatch, roll

        rng = np.random.default_rng(
            [self.seed & 0xFFFFFFFF, self.seed >> 32, k]
        )
        tokens_g = rng.integers(0, self.cfg["vocab_here"], (1, mask_total))
        tokens = jax.vmap(lambda x: dispatch(x, meta))(
            jnp.asarray(tokens_g, jnp.int32)
        )
        labels = roll(tokens, meta, -1, axis=1, mesh=self.mesh, cp_axis="cp")
        pos = jnp.asarray(meta.perm_idx)[None]
        return tokens_g[0], tokens, labels, pos


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
        f"model: {job.pcfg.n_layers} layers "
        f"{list(zip(job.pcfg.layer_types, job.pcfg.ffn_types))}, "
        f"{n_params / 1e6:.1f} M parameters, fp32 master weights + AdamW = "
        f"{16 * n_params / 1e9:.2f} GB with gradients"
    )

    # -- set-up: mask 0 of the stream ---------------------------------------
    with span("data"):
        mask = masks.build_mask(tr["mask"], total, index=0)
    areas = job.areas(mask)
    log(
        f"mask 0: {mask.describe()}; documents of {min(mask.doc_lengths)} to "
        f"{max(mask.doc_lengths)} tokens; under window "
        f"{cfg['sliding_window']}: area {areas[SLIDING]}, "
        f"{sum(n > cfg['sliding_window'] for n in mask.doc_lengths)} "
        "documents longer than the window"
    )
    with span("plan"):
        model, meta = job.build(mask)
        step_fn = model.make_train_step(opt)
    for kind, p in model.attn_params.items():
        log(f"tiles of {kind}: (block_q, block_k, head_block) = "
            f"({p.block_q}, {p.block_k}, {p.head_block})")
    with span("data"):
        _g, tokens, labels, pos = job.batch_for(meta, total, 0)
    warm_batch = (tokens, labels, pos)
    # the pairs the experts held here compute in a step, read from one
    stats = jax.jit(
        lambda p, *b: model.loss_fn(
            p, *b, model.sharded_tables(), with_stats=True
        )[1]
    )(state["params"], *warm_batch)
    counts = np.asarray(stats["expert_counts"])
    pairs_here = float(counts.sum())
    model.record_expert_load(counts)
    log(
        f"expert layers: pairs computed here a layer {counts.sum(1).tolist()} "
        f"of {total * cfg['num_experts_per_tok']} routed; busiest held expert "
        f"over the mean {[round(float(c.max() * len(c) / max(c.sum(), 1)), 3) for c in counts]}"
    )
    del stats
    with span("compile"):
        exe = step_fn.lower(
            state["params"], state["opt"], *warm_batch
        ).compile()

    def steady(batch=warm_batch):
        state["params"], state["opt"], loss = exe(
            state["params"], state["opt"], *batch
        )
        return loss

    warm_times = timing.settle(steady)
    log(f"warm-up steps on mask 0 (s): {[round(t, 4) for t in warm_times]}")
    mem = exe.memory_analysis()
    log(
        "the step's per-device bytes (arguments, outputs, temp): "
        f"({mem.argument_size_in_bytes}, {mem.output_size_in_bytes}, "
        f"{mem.temp_size_in_bytes})"
    )
    work = {
        "train_step": flops_afmoe.train_step_flops(
            cfg, total, areas, pairs_here
        ),
        "attn_sliding_executed": flops_afmoe.attn_executed_flops(
            cfg, SLIDING, areas[SLIDING]
        ),
        "attn_full_executed": flops_afmoe.attn_executed_flops(
            cfg, FULL, areas[FULL]
        ),
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
    state.pop("opt")  # room for the float32 reference
    del exe, warm_batch, batches, upcoming, steady
    with span("check"):
        ok = _check(job, state["params"])

    return Observations(
        end_to_end={"train_tokens_per_s": rate},
        attempted=phase.calls,
        failed=0,
        correct=ok and phase.calls > 0,
        values=values,
        flops=work,
        iters=iters,
        hlo_scopes=scopes,
    )


def check_mask(tr: dict):
    t = int(tr["check_tokens"])
    return masks.build_mask(tr.get("check_mask", tr["mask"]), t, index=0)


def check_errors(job: Job, params, *, model_job: Job | None = None,
                 model_params=None, free_routing: bool = False):
    """(relative loss error, {parameter: relative L2 gradient error, the
    worst layer's}, share of token-expert pairs chosen otherwise than the
    reference chose) of the model on one packed sequence of
    ``check_tokens`` tokens against the plain float32 decoder on the same
    weights and tokens. ``model_job`` builds the model from another
    configuration than the reference gets and ``model_params`` hands it
    other weights: the tests' faults. The reference follows the model's
    expert choices (see ``ROUTE_*`` above) unless ``free_routing``."""
    import jax
    import jax.numpy as jnp

    mask = check_mask(job.tr)
    t = mask.total
    model, meta = (model_job or job).build(mask)
    tokens_g, tokens, labels, pos = job.batch_for(meta, t, CHECK_STEP)

    (got, stats), got_grad = jax.jit(jax.value_and_grad(
        lambda p, *batch: model.loss_fn(p, *batch, with_stats=True),
        has_aux=True,
    ))(
        params if model_params is None else model_params,
        tokens, labels, pos, model.sharded_tables(),
    )
    log(f"check: the model's loss and gradients are in ({float(got):.6f})")
    # the model's choices are in dispatch order: slot i holds position perm[i]
    perm = np.asarray(meta.perm_idx)
    assert sorted(perm.tolist()) == list(range(t)), "a padded dispatch"
    got_idx = np.zeros_like(np.asarray(stats["expert_idx"])[0])
    got_idx[:, perm] = np.asarray(stats["expert_idx"])[0]  # [layers, t, k]
    rows = jnp.arange(t)
    with jax.default_matmul_precision("highest"):
        (want, (want_idx, margins)), want_grad = jax.jit(jax.value_and_grad(
            lambda p, tok, lab, forced: reference_afmoe.afmoe_loss(
                p, tok, lab, masks.allowed(mask, rows, rows), job.cfg,
                with_routing=True, forced_routing=forced,
            ),
            has_aux=True,
        ))(
            params, jnp.asarray(tokens_g, jnp.int32),
            jnp.asarray(np.roll(tokens_g, -1), jnp.int32),
            None if free_routing else jnp.asarray(got_idx),
        )
    errs = jax.jit(lambda g, w: jax.tree.map(
        lambda a, b: jnp.linalg.norm((a - b).ravel())
        / jnp.maximum(jnp.linalg.norm(b.ravel()), 1e-30), g, w,
    ))(got_grad, want_grad)
    errs = jax.device_get(errs)
    grad_err: dict[str, float] = {
        n: float(e) for n, e in errs.items() if n != "layers"
    }
    for layer in errs["layers"]:  # the worst layer, name by name
        for name, e in layer.items():
            e = float(e) if np.isfinite(e) else float("inf")
            grad_err[name] = max(grad_err.get(name, 0.0), e)
    grad_err.pop("expert_bias", None)  # a buffer: no gradient on either side
    margins = np.asarray(margins)
    routing = {
        "flipped_share": float((margins > 0).mean()),
        "worst_margin": float(margins.max()),
    }
    got, want = float(got), float(want)
    log(
        f"check: one packed sequence of {t} tokens (documents "
        f"{list(mask.doc_lengths)}), model loss {got:.6f} vs float32 plain "
        f"decoder {want:.6f}; {100 * routing['flipped_share']:.4f}% of the token-expert "
        "pairs chosen otherwise than the reference's own router would, the "
        f"widest tie broken {routing['worst_margin']:.3e} in score"
    )
    return abs(got - want) / abs(want), grad_err, routing


def passes(loss_rel: float, grad_err: dict[str, float],
           routing: dict[str, float]) -> bool:
    return bool(
        loss_rel <= LOSS_REL_TOL
        and all(
            e <= (EXPERT_GRAD_REL_L2_TOL if n in EXPERT_PATH
                  else GRAD_REL_L2_TOL)
            for n, e in grad_err.items()
        )
        and routing["flipped_share"] <= ROUTE_FLIP_SHARE_TOL
        and routing["worst_margin"] <= ROUTE_MARGIN_TOL
    )


def _check(job: Job, params) -> bool:
    rel, grad_err, routing = check_errors(job, params)
    ok = passes(rel, grad_err, routing)
    log(
        f"correct={ok}: loss relative {rel:.2e} (tolerance {LOSS_REL_TOL:g}); "
        "gradient by parameter, relative L2, the worst layer's: "
        + ", ".join(f"{n} {e:.2e}" for n, e in sorted(grad_err.items()))
        + f" (tolerance {GRAD_REL_L2_TOL:g}, {EXPERT_GRAD_REL_L2_TOL:g} on "
        f"the expert path {EXPERT_PATH}); expert choices {routing} "
        f"(tolerances {ROUTE_FLIP_SHARE_TOL:g}, {ROUTE_MARGIN_TOL:g})"
    )
    return ok
