"""Traffic kind ``train_blockdiff``: ``train_pattern``'s one-mask stream
for a sparse-expert decoder trained by diffusion over blocks (SDAR
through ``magiattention_tpu/models/pattern.py``: the sequence fed twice,
``[noisy ; clean]``, under a mask whose bounds move a block at a time).

Closed loop, one packed sequence a step, AdamW; every step brings the
traffic file's documents (``masks_blockdiff.build_mask``: the same in
every run, so the same mask), and ``--seed`` makes the weights, the token
ids, each block's t and which tokens are masked. A step of
``data_tokens`` tokens feeds twice as many rows; ``train_tokens_per_s``
counts the data tokens (what a user calls trained tokens), the MFU the
FLOPs of all rows (``flops_sdar``). Set-up plans (``build_magi_pattern``:
one dispatch of the doubled sequence, one plan of three slices a
document), dispatches the batch and compiles the step; the window is
``train_pattern``'s (``timing.timed_units``, ``timing.Phase.rate``).

``correct`` is decided outside the window, at the published widths on
the seed's weights made anew after it (as ``train_cca`` and
``train_looped`` read them): the loss of one packed ``check_tokens``
sequence (documents ``check_mask``: each a whole number of blocks, one
boundary off the chunk grid) and its gradient with respect to every
parameter, against ``reference_sdar`` in float32 on the same weights,
ids, masked tokens and weights. The program the window times is held at
its own size by its first call, made on the seed's weights before the
warm-up: its loss against ``reference_sdar``'s forward pass on the
window's documents and step 0's draw (``timed_loss_error``), and how far
it moved the parameters against what AdamW's first step moves them
(``update_share``); what the window trained after that is held to a
finite loss at its last step.
"""

from __future__ import annotations

import dataclasses
import itertools

import numpy as np

from .. import flops_sdar, masks_blockdiff, reference_sdar, timing
from ..harness import Observations, key_from_seed, log
from .train_pattern import CHECK_STEP

# bf16 model against the float32 plain decoder, ``train_pattern``'s
# comparison: the loss of one packed sequence and its gradient with
# respect to every parameter by relative L2; a layer's parameter by its
# WORST layer's difference against the norm of the parameter's LARGEST
# layer's gradient, as ``train_cca`` reads it and for its reason (the
# bf16 error of a layer's gradient is about the same in absolute size in
# every layer). The weighted loss at random init is about ln(vocabulary
# slice) whatever the model attends to, so the gradients hold the mask,
# the kernels, the router and the precision.
#
# Top-8 of 128 is discontinuous, so the reference follows the model's
# expert choices and the choices are held to the reference's own router
# apart (the share of row-expert pairs its top-k would not have chosen,
# and the widest tie broken in softmax score): see ``train_pattern``.
#
# The limits, each from two readings at the published widths on the
# seed's weights (my chip runs, PR 42; PERF.md section 6): the largest
# the cell gave over its seeds (fifteen), and the nearest precision
# below, fp8 weights (two seeds), which has to come out not correct; beside
# them the fault of this mask's own kind, every step set to 1 (a causal
# mask in place of the staircase), which has to come out not correct
# too. The gradients are held by their worst parameter: 1.5e-2 to
# 7.6e-2 as the cell runs (``we_gate``; a seed's readings rise and fall
# together, by five times between seeds: a masked token in a block of
# small t weighs hundreds of times the others and the error of its one
# path does not average out) / 1.8e-1 and 1.9e-1 with fp8 weights (no
# parameter under 1.1e-1) / 5.8e-1 and 6.1e-1 with every step set to 1:
# the limit 1.2e-1. ``w_router``'s gradient is left out by name: it reads
# 1.2e-2 to 8.3e-2 as the cell runs, 1.7 times the attention
# projections' on every seed, and 1.1e-1 and 1.6e-1 with fp8 weights,
# so no limit has room on both sides; the router is held by its choices
# (below) and its gradient at toy size in float32
# (``tests/test_models/test_pattern_blockdiff.py``). Expert choices:
# 0.65 to 0.79% of the pairs differ, the widest tie 6.1e-4 to 9.0e-4 in
# softmax score / 8.4 and 8.8%, 8.2e-3 and 8.8e-3 / 10.6 and 13.6%,
# 2.9e-2 and 3.0e-2: 2.5e-2 and 2.5e-3. The loss hardly moves with the
# precision (3.0e-6 to 6.3e-5 on the check's 4,096 rows, 1.5e-6 to
# 3.6e-5 on the timed step's 16,384 / 4.6e-4 and 7.3e-4 / 4.0e-4 and
# 4.5e-4; the toy's few rows read 6e-4 in bf16): ``train_pattern``'s
# 1e-3, sixteen times the largest reading.
LOSS_REL_TOL = 1e-3  # train_pattern's
GRAD_REL_L2_TOL = 1.2e-1
UNHELD_GRADS = ("w_router",)  # printed with the others, decided by none
ROUTE_FLIP_SHARE_TOL = 2.5e-2
ROUTE_MARGIN_TOL = 2.5e-3
# ``update_share`` of the timed step's first call reads 0.84 to 0.90
# over twelve seeds (the held experts no row chose have no gradient) and
# a state left unchanged 0: the limit on its distance from 1, with the
# more room on the sound side
UPDATE_GAP_TOL = 0.5


class Job:
    """What a run and its check share: the model's sizes, the mesh, how
    documents become a model and a step's ids a batch.
    ``model_overrides`` replaces fields of the model's ``PatternConfig``
    (the tests' faults; the reference never sees them)."""

    def __init__(self, cfg: dict, tr: dict, seed: int, devices,
                 model_overrides: dict | None = None):
        from jax.sharding import Mesh

        from magiattention_tpu.models.pattern import sdar_moe_config

        self.cfg, self.tr, self.seed = cfg, tr, int(seed)
        self.pcfg = sdar_moe_config(
            cfg, dtype=tr["dtype"], remat=bool(tr["remat"]),
            expert_range=tuple(cfg["experts_here"]),
            vocab_size=cfg["vocab_here"],
        )
        if model_overrides:
            self.pcfg = dataclasses.replace(self.pcfg, **model_overrides)
        self.mesh = Mesh(np.array(devices).reshape(1, -1), ("dp", "cp"))

    def mask(self, spec: dict, data_tokens: int):
        return masks_blockdiff.build_mask(
            spec, data_tokens, self.cfg["block_length"]
        )

    def build(self, mask):
        """(model, dispatch meta) for one packed mask: the plan of the
        doubled sequence on the host."""
        from magiattention_tpu.models.pattern import build_magi_pattern

        return build_magi_pattern(
            self.pcfg, self.mesh, mask.cu_seqlens,
            chunk_size=int(self.tr["chunk_size"]),
        )

    def draw(self, mask, k: int) -> dict:
        """Step ``k``'s sequence from the seed, on the host, a data token
        each: the clean ids (inside this rank's vocabulary slice, below
        the mask id), every block's t (uniform on [t_min, 1]) and which
        tokens it masks (each with probability t), the noised ids, a
        masked token's label (its own clean id; -1 where not masked) and
        every token's weight 1/t."""
        rng = np.random.default_rng(
            [self.seed & 0xFFFFFFFF, self.seed >> 32, k]
        )
        n, block = mask.data_tokens, mask.block
        mask_id = int(self.cfg["mask_token_id"])
        clean = rng.integers(0, mask_id, n)
        t = rng.uniform(float(self.tr["t_min"]), 1.0, n // block)
        t = np.repeat(t, block)  # documents are whole blocks: so is n
        masked = rng.uniform(size=n) < t
        return {
            "clean": clean,
            "noisy": np.where(masked, mask_id, clean),
            "labels": np.where(masked, clean, -1),
            "weights": (1.0 / t).astype(np.float32),
        }

    def batch_for(self, meta, mask, k: int):
        """(the host's draw, the model's batch): the doubled rows in
        dispatch order, ``(tokens, labels, pos, weights)``."""
        import jax
        import jax.numpy as jnp

        from magiattention_tpu.parallel import dispatch

        d = self.draw(mask, k)
        n = mask.data_tokens
        doubled = {
            "tokens": (np.concatenate([d["noisy"], d["clean"]]), jnp.int32),
            # the clean half is context: no label, no weight
            "labels": (np.concatenate([d["labels"], np.full(n, -1)]), jnp.int32),
            "pos": (np.concatenate([np.arange(n), np.arange(n)]), jnp.int32),
            "weights": (
                np.concatenate([d["weights"], np.zeros(n, np.float32)]),
                jnp.float32,
            ),
        }
        batch = tuple(
            jax.vmap(lambda x: dispatch(x, meta))(jnp.asarray(a[None], dt))
            for a, dt in doubled.values()
        )
        return d, batch


def run(cell, ctx) -> Observations:
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from magiattention_tpu import telemetry
    from magiattention_tpu.models.pattern import init_pattern_params
    from magiattention_tpu.telemetry import get_compile_tracker

    cfg, tr = cell.config, cell.traffic
    data_tokens = int(tr["data_tokens"])
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
        f"model: {job.pcfg.n_layers} layers ({job.pcfg.n_heads} query / "
        f"{job.pcfg.n_kv_heads} key-value heads of {job.pcfg.head_dim}, "
        f"top-{job.pcfg.top_k} of {job.pcfg.n_experts} experts behind a "
        f"{job.pcfg.router_form} router, {job.pcfg.held_experts} held), "
        f"diffusion over blocks of {job.pcfg.diffusion_block}, "
        f"{n_params / 1e6:.1f} M parameters, fp32 master weights + AdamW = "
        f"{16 * n_params / 1e9:.2f} GB with gradients"
    )

    # -- set-up: the traffic file's documents --------------------------------
    with span("data"):
        mask = job.mask(tr["mask"], data_tokens)
    log(f"mask: {mask.describe()}; documents {list(mask.doc_lengths)}")
    with span("plan"):
        model, meta = job.build(mask)
        step_fn = model.make_train_step(opt)
    for kind, p in model.attn_params.items():
        log(f"tiles of {kind}: (block_q, block_k, head_block) = "
            f"({p.block_q}, {p.block_k}, {p.head_block}), grid {p.grid}, "
            f"mask step {p.mask_step}; plan area {model.plans[kind].total_area}")
    # the gauge is the newest plan's, and the check plans again after the
    # window: the window's reading is put back then
    tile_share = telemetry.snapshot().get("gauges", {}).get(
        "magi_flex_stepped_tile_share"
    )
    with span("data"):
        warm_draw, warm_batch = job.batch_for(meta, mask, 0)
    routed = mask.rows * cfg["num_experts_per_tok"]
    stats_of = jax.jit(
        lambda p, *b: model.loss_fn(
            p, *b[:3], model.sharded_tables(), b[3], with_stats=True
        )[1]
    )

    def held_load(when: str):
        """The pairs the experts held here compute in a step on the
        weights as they stand (one forward pass, no gradient)."""
        counts = np.asarray(
            stats_of(state["params"], *warm_batch)["expert_counts"]
        )
        log(
            f"expert layers, {when}: pairs computed here a layer "
            f"{counts.sum(1).tolist()} of {routed} routed; busiest held "
            "expert over the mean "
            + str([round(float(c.max() * len(c) / max(c.sum(), 1)), 3)
                   for c in counts])
        )
        return counts

    model.record_expert_load(held_load("the seed's weights"))
    # the choices the reference follows when it reads the timed step's loss
    seed_choices = np.asarray(
        stats_of(state["params"], *warm_batch)["expert_idx"]
    )[0]
    with span("compile"):
        exe = step_fn.lower(
            state["params"], state["opt"], *warm_batch
        ).compile()

    def steady(batch=warm_batch):
        state["params"], state["opt"], state["loss"] = exe(
            state["params"], state["opt"], *batch
        )
        return state["loss"]

    # the compiled step's first call, on the seed's weights: what
    # ``correct`` holds of the program the window times
    first_loss = float(steady())
    moved = update_share(
        seed_params(), state["params"], float(tr["learning_rate"])
    )
    warm_times = timing.settle(steady)
    log(f"warm-up steps (s): {[round(t, 4) for t in warm_times]}")
    mem = exe.memory_analysis()
    log(
        "the step's per-device bytes (arguments, outputs, temp): "
        f"({mem.argument_size_in_bytes}, {mem.output_size_in_bytes}, "
        f"{mem.temp_size_in_bytes})"
    )
    # the router trains: the load the window opens on is not the one it
    # closes on, so the step's FLOPs are counted at the mean of the two
    pairs_open = float(held_load("as the window opens").sum())
    values, iters = {}, {}

    # -- the window: the same mask, steps enqueued back to back -------------
    per_unit = timing.calls_per_unit(warm_times[-1], ctx.seconds)
    batches = [warm_batch] + [  # prefetched, as a loader would
        job.batch_for(meta, mask, k)[1] for k in range(1, per_unit)
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
    rate = phase.rate(data_tokens)
    values["steady_step_s"] = summary["median_s"]
    values["compiles_in_window"] = float(compiles)
    iters["window"] = phase.calls
    log(
        f"window: {phase.calls} steps in {phase.elapsed_s:.4f} s "
        f"({summary['n']} units of {per_unit}), {compiles} compiles taking "
        f"{compile_s:.2f} s; {rate:.2f} data tokens/s ({mask.rows} rows a "
        f"step); seconds a step by unit: {summary}"
    )
    ctx.tracer.stop()
    pairs_close = float(held_load("as the window closes").sum())
    work = {
        "train_step": flops_sdar.train_step_flops(
            cfg, data_tokens, mask.area, (pairs_open + pairs_close) / 2
        ),
        "attn_full_executed": flops_sdar.attn_executed_flops(cfg, mask.area),
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
        params = seed_params()
        ok = (
            np.isfinite(last_loss)
            and _check_timed_step(
                job, params, mask, meta, warm_draw, seed_choices,
                first_loss, moved,
            )
            and _check(job, params)
        )
    if tile_share is not None:
        telemetry.record_flex_stepped_tile_share(tile_share)

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


def update_share(before, after, lr: float) -> float:
    """``|after - before| / (lr sqrt(N))`` over the N parameters every
    step hands a gradient: all but the embedding (a row no token of the
    step names has none) and the selection-bias buffer. AdamW's first
    step from zero moments moves an element by ``lr g / (|g| + eps)``
    less ``lr`` x 1e-4 of itself, so a step that ran reads just under 1
    (an expert no row chose stays), a state left unchanged 0, twice the
    rate 2."""
    import jax
    import jax.numpy as jnp

    pairs = [
        (x, y)
        for (path, x), y in zip(
            jax.tree_util.tree_leaves_with_path(after), jax.tree.leaves(before)
        )
        if not {getattr(k, "key", None) for k in path}
        & {"embed", "expert_bias"}
    ]
    total = jax.jit(
        lambda ps: sum(jnp.sum((x - y) ** 2) for x, y in ps)
    )(pairs)
    n = sum(x.size for x, _ in pairs)
    return float(np.sqrt(float(total) / n) / lr)


def timed_loss_error(job: Job, params, mask, meta, draw: dict, choices,
                     got: float) -> float:
    """The relative error of ``got``, the loss the compiled step read on
    ``params`` and ``draw`` (the window's documents), against
    ``reference_sdar``'s forward pass on the same, following the model's
    expert ``choices`` ([layers, rows in dispatch order, k])."""
    import jax
    import jax.numpy as jnp

    perm = np.asarray(meta.perm_idx)
    assert sorted(perm.tolist()) == list(range(mask.rows)), "a padded dispatch"
    forced = np.zeros_like(choices)
    forced[:, perm] = choices
    with jax.default_matmul_precision("highest"):
        want = float(jax.jit(
            lambda p, noisy, clean, lab, w, forced: reference_sdar.sdar_loss(
                p, noisy, clean, lab, w, mask, job.cfg, forced_routing=forced
            )
        )(
            params,
            jnp.asarray(draw["noisy"], jnp.int32),
            jnp.asarray(draw["clean"], jnp.int32),
            jnp.asarray(draw["labels"], jnp.int32),
            jnp.asarray(draw["weights"]),
            jnp.asarray(forced),
        ))
    log(
        f"check: the timed step's first call, {mask.rows} rows on the "
        f"seed's weights: loss {got:.6f} vs float32 plain decoder {want:.6f}"
    )
    return abs(got - want) / abs(want)


def timed_step_passes(loss_rel: float, moved: float) -> bool:
    return bool(
        loss_rel <= LOSS_REL_TOL and abs(1.0 - moved) <= UPDATE_GAP_TOL
    )


def _check_timed_step(job: Job, params, mask, meta, draw, choices,
                      first_loss: float, moved: float) -> bool:
    rel = timed_loss_error(job, params, mask, meta, draw, choices, first_loss)
    ok = timed_step_passes(rel, moved)
    log(
        f"correct={ok} of the timed step: loss relative {rel:.2e} "
        f"(tolerance {LOSS_REL_TOL:g}); its first update moved the "
        f"parameters {moved:.4f} of AdamW's first step (within "
        f"{UPDATE_GAP_TOL:g} of 1)"
    )
    return ok


def check_mask(job: Job):
    return job.mask(
        job.tr.get("check_mask", job.tr["mask"]), int(job.tr["check_tokens"])
    )


def check_errors(job: Job, params, *, model_job: Job | None = None,
                 model_params=None, free_routing: bool = False):
    """(relative loss error, {parameter: relative L2 gradient error, of a
    layer's parameter the worst layer's difference over the largest
    layer's norm}, the expert choices against the reference's own
    router) of the model on one packed sequence of ``check_tokens``
    tokens against ``reference_sdar`` on the same weights and draw.
    ``model_job`` builds the model from another configuration (or
    another mask) than the reference gets and ``model_params`` hands it
    other weights: the tests' faults. The reference follows the model's
    expert choices unless ``free_routing``."""
    import jax
    import jax.numpy as jnp

    mask = check_mask(job)
    rows = mask.rows
    model, meta = (model_job or job).build(mask)
    for kind, p in model.attn_params.items():  # beside the window's
        log(f"check: tiles of {kind}: (block_q, block_k, head_block) = "
            f"({p.block_q}, {p.block_k}, {p.head_block}), grid {p.grid}, "
            f"mask step {p.mask_step}")
    d, (tokens, labels, pos, weights) = job.batch_for(meta, mask, CHECK_STEP)
    log(
        f"check: {int((d['labels'] >= 0).sum())} of {mask.data_tokens} "
        f"tokens masked; weights 1/t from {d['weights'].min():.3f} to "
        f"{d['weights'].max():.3f}"
    )

    (got, stats), got_grad = jax.jit(jax.value_and_grad(
        lambda p, tok, lab, ps, w, tabs: model.loss_fn(
            p, tok, lab, ps, tabs, w, with_stats=True
        ),
        has_aux=True,
    ))(
        params if model_params is None else model_params,
        tokens, labels, pos, weights, model.sharded_tables(),
    )
    log(f"check: the model's loss and gradients are in ({float(got):.6f})")
    # the model's choices are in dispatch order: slot i holds row perm[i]
    perm = np.asarray(meta.perm_idx)
    assert sorted(perm.tolist()) == list(range(rows)), "a padded dispatch"
    got_idx = np.zeros_like(np.asarray(stats["expert_idx"])[0])
    got_idx[:, perm] = np.asarray(stats["expert_idx"])[0]  # [layers, 2L, k]
    with jax.default_matmul_precision("highest"):
        (want, (_idx, margins)), want_grad = jax.jit(jax.value_and_grad(
            lambda p, noisy, clean, lab, w, forced: reference_sdar.sdar_loss(
                p, noisy, clean, lab, w, mask, job.cfg,
                with_routing=True, forced_routing=forced,
            ),
            has_aux=True,
        ))(
            params,
            jnp.asarray(d["noisy"], jnp.int32),
            jnp.asarray(d["clean"], jnp.int32),
            jnp.asarray(d["labels"], jnp.int32),
            jnp.asarray(d["weights"]),
            None if free_routing else jnp.asarray(got_idx),
        )

    # squared norms of the difference and of the reference, leaf by leaf
    sq = jax.device_get(jax.jit(lambda g, w: jax.tree.map(
        lambda a, b: jnp.stack([jnp.sum((a - b) ** 2), jnp.sum(b ** 2)]), g, w
    ))(got_grad, want_grad))

    def rel_l2(diff, ref):
        rel = float(np.sqrt(diff) / max(np.sqrt(ref), 1e-30))
        return rel if np.isfinite(rel) else float("inf")

    grad_err: dict[str, float] = {
        n: rel_l2(*e) for n, e in sq.items() if n != "layers"
    }
    for name in sq["layers"][0]:
        diffs, refs = zip(*(layer[name] for layer in sq["layers"]))
        grad_err[name] = rel_l2(max(diffs), max(refs))
    grad_err.pop("expert_bias", None)  # a buffer: no gradient on either side
    margins = np.asarray(margins)
    routing = {
        "flipped_share": float((margins > 0).mean()),
        "worst_margin": float(margins.max()),
    }
    got, want = float(got), float(want)
    log(
        f"check: one packed sequence of {mask.data_tokens} tokens fed as "
        f"{rows} rows (documents {list(mask.doc_lengths)}, blocks of "
        f"{mask.block}), model loss {got:.6f} vs float32 plain decoder "
        f"{want:.6f}; {100 * routing['flipped_share']:.4f}% of the "
        "row-expert pairs chosen otherwise than the reference's own router "
        f"would, the widest tie broken {routing['worst_margin']:.3e} in score"
    )
    return abs(got - want) / abs(want), grad_err, routing


def passes(loss_rel: float, grad_err: dict[str, float],
           routing: dict[str, float]) -> bool:
    return bool(
        loss_rel <= LOSS_REL_TOL
        and all(
            e <= GRAD_REL_L2_TOL
            for n, e in grad_err.items() if n not in UNHELD_GRADS
        )
        and routing["flipped_share"] <= ROUTE_FLIP_SHARE_TOL
        and routing["worst_margin"] <= ROUTE_MARGIN_TOL
    )


def _check(job: Job, params) -> bool:
    rel, grad_err, routing = check_errors(job, params)
    ok = passes(rel, grad_err, routing)
    log(
        f"correct={ok}: loss relative {rel:.2e} (tolerance {LOSS_REL_TOL:g}); "
        "gradient by parameter, relative L2, of a layer's parameter the "
        "worst layer's difference over the largest layer's norm: "
        + ", ".join(f"{n} {e:.2e}" for n, e in sorted(grad_err.items()))
        + f" (tolerance {GRAD_REL_L2_TOL:g}, {UNHELD_GRADS} held to none); "
        f"expert choices {routing} "
        f"(tolerances {ROUTE_FLIP_SHARE_TOL:g}, {ROUTE_MARGIN_TOL:g})"
    )
    return ok
