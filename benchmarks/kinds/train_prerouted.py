"""Traffic kind ``train_prerouted``: ``train_pattern``'s one-mask stream
for a sparse-expert decoder whose router reads the layer's input before
attention (SmallThinker-21BA3B-Instruct through
``magiattention_tpu/models/pattern.py``: one full-attention layer with no
position encoding to three rotary window layers at 28 query / 4 key-value
heads, ReLU-gated experts behind a softmax router on the attention half's
normed input).

Closed loop, one packed sequence a step, AdamW; every step brings the
traffic file's mask (``masks.build_mask``: the same documents in every
run), and ``--seed`` makes the weights and the token ids only; a
document's last row has no label. Set-up plans (``build_magi_pattern``:
one dispatch, a full plan and a window plan on it), dispatches the token
ids and compiles the step; the window and ``train_tokens_per_s`` are
``train_pattern``'s (``timing.timed_units``, ``timing.Phase.rate``).

``correct`` is decided outside the window, as ``train_blockdiff`` and
``train_mhc`` decide theirs. On the seed's weights made anew: the loss of
one packed ``check_tokens`` sequence (documents ``check_mask``: one
longer than the window) and its gradient with respect to every parameter,
against ``reference_smallthinker`` in float32 at the published widths,
the reference following the model's expert choices and the choices held
to the reference's own router apart. The timed program itself is held at
its own size by its first call, made on the seed's weights before the
window: the loss it read against the reference's forward pass on the same
16,384 rows, and how far it moved the parameters against what AdamW's
first step moves them (``train_blockdiff.update_share``).
"""

from __future__ import annotations

import dataclasses
import itertools

import numpy as np

from .. import flops_smallthinker, masks, reference_smallthinker, timing
from ..harness import Observations, key_from_seed, log
from . import train_pattern
from .train_blockdiff import update_share
from .train_mhc import _in_sequence_order
from .train_pattern import CHECK_STEP, FULL, SLIDING, check_mask, window_area
from .train_sambay import doc_ids

# bf16 model against the float32 plain decoder, ``train_blockdiff``'s
# comparison (the same router form, the same expert form): the loss of one
# packed sequence and its gradient with respect to every parameter by
# relative L2, a layer's parameter by its WORST layer's difference against
# the norm of the parameter's LARGEST layer's gradient (``train_cca`` says
# why: the bf16 error of a layer's gradient is about the same in absolute
# size in every layer). Top-6 of 64 is discontinuous, so the reference
# follows the model's expert choices and the choices are held to the
# reference's own router apart (``train_pattern``).
#
# The limits, each from two readings at the published widths on the seed's
# weights (my chip runs, PR 53; PERF.md section 6 has them with their
# origin): the largest the cell gave over its seeds (eight), and the nearest
# precision below, fp8 weights (three seeds, rounded on the host: the
# chip's compiler folds a float8 round trip inside one program away), which
# has to come out not correct by one of the limits. Gradients, held by
# their worst parameter: 4.7e-2 to 6.3e-2 as the cell runs (``we_gate`` on
# every seed: where bf16 moves a gate across zero ReLU's derivative flips,
# which SiLU's does not; ``mlp_norm`` 3.4e-2 to 4.1e-2, ``wq`` / ``wk``
# 3.1e-2 to 3.5e-2, every other parameter under 2.9e-2) / 2.4e-1 to 2.9e-1
# with fp8 weights (``we_gate``; no parameter under 1.1e-1, eleven of the
# thirteen over the limit on every seed): the limit 1.2e-1,
# ``train_blockdiff``'s, 1.9 times the largest sound reading and half the
# control's. ``w_router`` reads as the others do here (1.2e-2 to 2.9e-2:
# no block of small t weighs a row hundreds of times the others) and is
# held with them. Expert choices: 0.58 to 0.72% of the pairs differ from
# the reference's own router, the widest tie broken 1.2e-3 to 1.9e-3 in
# softmax score / 7.3 to 8.9%, 1.8e-2 to 2.0e-2: the limits 2.5e-2 and
# 6e-3, each three times the sound side's largest and a third of the
# control's smallest. The loss hardly moves with the precision (3.3e-6 to
# 1.4e-5 on the check's 8,192 rows, 1.9e-6 to 1.1e-5 on the timed step's
# 16,384 / 1.1e-4 to 1.7e-4): ``train_pattern``'s 1e-3, seventy times the
# largest sound reading, and it is the gradients and the choices that
# hold the precision. What the check cannot tell apart inside a bf16
# model: a bfloat16 router (toy size, CPU: every reading within a
# seed's spread of the float32 router's; ``train_pattern`` found the
# same at Trinity's widths); float32 holds it at toy size
# (``tests/test_models/test_pattern_prerouted.py``).
LOSS_REL_TOL = 1e-3  # train_pattern's and train_blockdiff's
GRAD_REL_L2_TOL = 1.2e-1
ROUTE_FLIP_SHARE_TOL = 2.5e-2
ROUTE_MARGIN_TOL = 6e-3
# ``update_share`` of the timed step's first call reads 0.917 to 0.935
# over eight seeds (a held expert no row chose has no gradient) and a
# state left unchanged 0 (``train_blockdiff``): the limit on its distance
# from 1, with the more room on the sound side
UPDATE_GAP_TOL = 0.5


class Job(train_pattern.Job):
    """What a run and its check share (``train_pattern.Job``), for a
    ``smallthinker`` configuration. ``model_overrides`` replaces fields of
    the model's ``PatternConfig`` (the tests' faults; the reference never
    sees them)."""

    def __init__(self, cfg: dict, tr: dict, seed: int, devices,
                 model_overrides: dict | None = None):
        from jax.sharding import Mesh

        from magiattention_tpu.models.pattern import smallthinker_config

        self.cfg, self.tr, self.seed = cfg, tr, int(seed)
        self.pcfg = smallthinker_config(
            cfg, dtype=tr["dtype"], remat=bool(tr["remat"]),
            expert_range=tuple(cfg["experts_here"]),
            vocab_size=cfg["vocab_here"],
        )
        if model_overrides:
            self.pcfg = dataclasses.replace(self.pcfg, **model_overrides)
        devices = np.array(devices)
        self.mesh = Mesh(devices.reshape(1, -1), ("dp", "cp"))

    def areas(self, mask) -> dict[str, int]:
        return {
            FULL: mask.area,
            SLIDING: window_area(
                mask.doc_lengths, self.cfg["sliding_window_size"]
            ),
        }

    def token_ids(self, total: int, k: int) -> np.ndarray:
        """Step ``k``'s token ids in sequence order, inside this rank's
        vocabulary slice: ``train_pattern.Job.batch_for``'s draw."""
        rng = np.random.default_rng(
            [self.seed & 0xFFFFFFFF, self.seed >> 32, k]
        )
        return rng.integers(0, self.cfg["vocab_here"], (1, total))[0]

    def batch_for(self, meta, mask, k: int):
        """(step ``k``'s token ids in sequence order, then dispatched:
        the ids, each row's next token, -1 at a document's last row, and
        the position ids)."""
        import jax.numpy as jnp

        from magiattention_tpu.parallel import dispatch

        tokens_g = self.token_ids(mask.total, k)
        labels_g = np.roll(tokens_g, -1)
        labels_g[np.asarray(mask.cu_seqlens[1:]) - 1] = -1
        tokens, labels = (
            dispatch(jnp.asarray(a, jnp.int32), meta, pad_value=pad)[None]
            for a, pad in ((tokens_g, 0), (labels_g, -1))
        )
        return tokens_g, tokens, labels, jnp.asarray(meta.perm_idx)[None]


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
    p = job.pcfg
    log(
        f"model: {p.n_layers} layers {[t.split('_')[0] for t in p.layer_types]}"
        f" (window {p.sliding_window}, rotary on {list(p.rope_kinds)}), "
        f"{p.n_heads} query / {p.n_kv_heads} key-value heads of {p.head_dim}, "
        f"top-{p.top_k} of {p.n_experts} {p.expert_act}-gated experts "
        f"({p.held_experts} held) behind a {p.router_form} router on the "
        f"{p.router_input} half's input, {n_params / 1e6:.1f} M parameters, "
        f"fp32 master weights + AdamW = {16 * n_params / 1e9:.2f} GB with "
        "gradients"
    )

    # -- set-up: the traffic file's mask -------------------------------------
    with span("data"):
        mask = masks.build_mask(tr["mask"], total, index=0)
    areas = job.areas(mask)
    log(
        f"mask: {mask.describe()}; documents {list(mask.doc_lengths)}; under "
        f"window {cfg['sliding_window_size']}: area {areas[SLIDING]}"
    )
    with span("plan"):
        model, meta = job.build(mask)
        step_fn = model.make_train_step(opt)
    for kind, ap in model.attn_params.items():
        log(f"tiles of {kind}: (block_q, block_k, head_block) = "
            f"({ap.block_q}, {ap.block_k}, {ap.head_block}), grid {ap.grid}; "
            f"plan area {model.plans[kind].total_area}")
    with span("data"):
        tokens_g, *warm_batch = job.batch_for(meta, mask, 0)
    warm_batch = tuple(warm_batch)
    routed = total * cfg["moe_num_active_primary_experts"]
    stats_of = jax.jit(
        lambda p, *b: model.loss_fn(
            p, *b, model.sharded_tables(), with_stats=True
        )[1]
    )

    def held_load(when: str):
        """The pairs the experts held here compute in a step on the
        weights as they stand (one forward pass, no gradient), and the
        choices themselves [layers, rows in dispatch order, k]."""
        stats = stats_of(state["params"], *warm_batch)
        counts = np.asarray(stats["expert_counts"])
        log(
            f"expert layers, {when}: pairs computed here a layer "
            f"{counts.sum(1).tolist()} of {routed} routed; busiest held "
            "expert over the mean "
            + str([round(float(c.max() * len(c) / max(c.sum(), 1)), 3)
                   for c in counts])
        )
        return counts, np.asarray(stats["expert_idx"])[0]

    counts, first_choices = held_load("the seed's weights")
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
    pairs_open = float(held_load("as the window opens")[0].sum())
    values, iters = {}, {}

    # -- the window: the same mask, steps enqueued back to back -------------
    per_unit = timing.calls_per_unit(warm_times[-1], ctx.seconds)
    batches = [warm_batch] + [  # prefetched, as a loader would
        tuple(job.batch_for(meta, mask, k)[1:]) for k in range(1, per_unit)
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
    pairs_close = float(held_load("as the window closes")[0].sum())
    work = {
        "train_step": flops_smallthinker.train_step_flops(
            cfg, total, areas, (pairs_open + pairs_close) / 2
        ),
        "attn_sliding_executed": flops_smallthinker.attn_executed_flops(
            cfg, SLIDING, areas[SLIDING]
        ),
        "attn_full_executed": flops_smallthinker.attn_executed_flops(
            cfg, FULL, areas[FULL]
        ),
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
        # on the seed's weights, not the trained ones
        params = seed_params()
        ok = (
            np.isfinite(last_loss)
            and _check_timed_step(
                job, params, mask, meta, tokens_g, first_choices, first_loss,
                moved,
            )
            and _check(job, params)
        )

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


CHECK_ROW_BLOCK = 128  # query rows a block of the reference's softmax


def _reference(job: Job, params, mask, tokens_g, forced, *, grad: bool):
    """``reference_smallthinker``'s (loss, (choices, margins)) of
    ``tokens_g`` under ``mask``'s documents on ``params``, float32 at the
    highest precision, following the expert choices ``forced`` (None: its
    own); with ``grad`` ((loss, aux), gradients). The layers go in stacked
    along a leading axis and their gradients come out so (the reference's
    scan takes them as they are: at the published widths a second copy of
    the weights and of their gradients inside the program does not fit
    beside the first)."""
    import jax
    import jax.numpy as jnp

    # one jitted function a job: a second reading of the same sizes (the
    # tests' faults) compiles nothing
    fns = job.__dict__.setdefault("_reference_fns", {})
    if grad not in fns:
        def loss(p, tok, doc, forced):
            return reference_smallthinker.smallthinker_loss(
                p, tok, doc, job.cfg, with_routing=True,
                forced_routing=forced, row_block=CHECK_ROW_BLOCK,
            )

        fns[grad] = jax.jit(
            jax.value_and_grad(loss, has_aux=True) if grad else loss
        )
    stacked = dict(params, layers=jax.tree.map(
        lambda *a: jnp.stack(a), *params["layers"]
    ))
    with jax.default_matmul_precision("highest"):
        return fns[grad](
            stacked, jnp.asarray(tokens_g, jnp.int32),
            jnp.asarray(doc_ids(mask)),
            None if forced is None else jnp.asarray(forced),
        )


def timed_loss_error(job: Job, params, mask, meta, tokens_g, choices,
                     got: float) -> float:
    """The relative error of ``got``, the loss the compiled step read on
    ``params`` and the window's first batch, against
    ``reference_smallthinker``'s forward pass on the same rows, following
    the model's expert ``choices``."""
    want, _routing = _reference(
        job, params, mask, tokens_g, _in_sequence_order(meta, choices),
        grad=False,
    )
    want = float(want)
    log(
        f"check: the timed step's first call, {mask.total} rows on the "
        f"seed's weights: loss {got:.6f} vs float32 plain decoder {want:.6f}"
    )
    return abs(got - want) / abs(want)


def timed_step_passes(loss_rel: float, moved: float) -> bool:
    return bool(
        loss_rel <= LOSS_REL_TOL and abs(1.0 - moved) <= UPDATE_GAP_TOL
    )


def _check_timed_step(job: Job, params, mask, meta, tokens_g, choices,
                      first_loss: float, moved: float) -> bool:
    rel = timed_loss_error(
        job, params, mask, meta, tokens_g, choices, first_loss
    )
    ok = timed_step_passes(rel, moved)
    log(
        f"correct={ok} of the timed step: loss relative {rel:.2e} "
        f"(tolerance {LOSS_REL_TOL:g}); its first update moved the "
        f"parameters {moved:.4f} of AdamW's first step (within "
        f"{UPDATE_GAP_TOL:g} of 1)"
    )
    return ok


def check_errors(job: Job, params, *, model_job: Job | None = None,
                 model_params=None, free_routing: bool = False):
    """(relative loss error, {parameter: relative L2 gradient error, of a
    layer's parameter the worst layer's difference over the largest
    layer's norm}, the expert choices against the reference's own router)
    of the model on one packed sequence of ``check_tokens`` tokens against
    ``reference_smallthinker`` on the same weights and tokens.
    ``model_job`` builds the model from another configuration than the
    reference gets and ``model_params`` hands it other weights: the tests'
    faults. The reference follows the model's expert choices unless
    ``free_routing``."""
    import jax
    import jax.numpy as jnp

    mask = check_mask(job.tr)
    t = mask.total
    model, meta = (model_job or job).build(mask)
    for kind, ap in model.attn_params.items():  # beside the window's
        log(f"check: tiles of {kind}: (block_q, block_k, head_block) = "
            f"({ap.block_q}, {ap.block_k}, {ap.head_block}), grid {ap.grid}")
    tokens_g, tokens, labels, pos = job.batch_for(meta, mask, CHECK_STEP)
    (got, stats), got_grad = jax.jit(jax.value_and_grad(
        lambda p, *batch: model.loss_fn(p, *batch, with_stats=True),
        has_aux=True,
    ))(
        params if model_params is None else model_params,
        tokens, labels, pos, model.sharded_tables(),
    )
    log(f"check: the model's loss and gradients are in ({float(got):.6f})")
    got_idx = _in_sequence_order(meta, np.asarray(stats["expert_idx"])[0])
    # the model's gradients wait on the host while the reference makes its
    # own: the two programs' temporaries do not fit the chip together
    got_grad = jax.device_get(got_grad)
    got_grad["layers"] = jax.tree.map(
        lambda *a: np.stack(a), *got_grad["layers"]
    )
    del stats, model
    (want, (_idx, margins)), want_grad = _reference(
        job, params, mask, tokens_g, None if free_routing else got_idx,
        grad=True,
    )
    # squared norms of the difference and of the reference, leaf by leaf,
    # a layer's parameter a layer
    over = lambda x: tuple(range(x.ndim - 1, 0, -1))  # noqa: E731
    sq = jax.device_get(jax.jit(lambda g, w: (
        {n: jnp.stack([jnp.sum((g[n] - w[n]) ** 2), jnp.sum(w[n] ** 2)])
         for n in w if n != "layers"},
        {n: jnp.stack([
            jnp.sum((g["layers"][n] - b) ** 2, axis=over(b)),
            jnp.sum(b ** 2, axis=over(b)),
        ]) for n, b in w["layers"].items()},
    ))(got_grad, want_grad))

    def rel_l2(diff, ref):
        rel = float(np.sqrt(diff) / max(np.sqrt(ref), 1e-30))
        return rel if np.isfinite(rel) else float("inf")

    grad_err: dict[str, float] = {n: rel_l2(*e) for n, e in sq[0].items()}
    for name, (diffs, refs) in sq[1].items():
        grad_err[name] = rel_l2(diffs.max(), refs.max())
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
        f"decoder {want:.6f}; {100 * routing['flipped_share']:.4f}% of the "
        "token-expert pairs chosen otherwise than the reference's own router "
        f"would, the widest tie broken {routing['worst_margin']:.3e} in score"
    )
    return abs(got - want) / abs(want), grad_err, routing


def passes(loss_rel: float, grad_err: dict[str, float],
           routing: dict[str, float]) -> bool:
    return bool(
        loss_rel <= LOSS_REL_TOL
        and all(e <= GRAD_REL_L2_TOL for e in grad_err.values())
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
        + f" (tolerance {GRAD_REL_L2_TOL:g}); expert choices {routing} "
        f"(tolerances {ROUTE_FLIP_SHARE_TOL:g}, {ROUTE_MARGIN_TOL:g})"
    )
    return ok
