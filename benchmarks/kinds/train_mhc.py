"""Traffic kind ``train_mhc``: ``train_pattern``'s one-mask stream for a
latent-attention sparse-expert decoder under manifold-constrained
hyper-connections (Xing4.0-29B-A4B through
``magiattention_tpu/models/pattern.py``: four residual streams mixed a
half-layer by a Sinkhorn-projected matrix, keys of 192 beside values of
128 in the flex kernels, YaRN on the rotary lanes).

Closed loop, one packed sequence a step, AdamW; every step brings the
traffic file's mask (``masks.build_mask``: the same documents in every
run), and ``--seed`` makes the weights and the token ids only. Set-up plans
(``build_magi_pattern``: every layer is ``full_attention``, so one dispatch
and one plan), dispatches the token ids and compiles the step; the window
and ``train_tokens_per_s`` are ``train_pattern``'s (``timing.timed_units``,
``timing.Phase.rate``).

``correct`` is decided outside the window, as ``train_cca`` and
``train_sambay`` decide theirs. On the seed's weights made anew: the loss
of one packed ``check_tokens`` sequence (documents ``check_mask``) and its
gradient with respect to every parameter, against ``reference_xing`` in
float32 at the published widths, the reference following the model's
expert choices and the choices held to the reference's own router apart
(``train_latent``'s comparison). The timed program itself is held at its
own size by its first call, made on the seed's weights before the window:
the loss it read against the reference's forward pass on the same 8,192
rows, and how far it moved the parameters against what AdamW's first step
moves them (``train_blockdiff.update_share``).
"""

from __future__ import annotations

import dataclasses
import itertools

import numpy as np

from .. import flops_xing, masks, reference_xing, timing
from ..harness import Observations, key_from_seed, log
from . import train_pattern
from .train_blockdiff import update_share
from .train_pattern import CHECK_STEP, check_mask
from .train_sambay import doc_ids

# bf16 model against the float32 plain decoder, ``train_latent``'s
# comparison: the loss of one packed sequence and its gradient with respect
# to every parameter by relative L2, the worst layer's, a half-layer's
# mixer under ``hc_attn.<name>`` / ``hc_ffn.<name>``.
#
# The limits, each from two readings at the published widths on the seed's
# weights (my chip runs, PR 49; PERF.md section 6 has them with their
# origin): as the cell runs, and the controls, the nearest precision below
# what the configuration states, each of which has to come out not correct
# by one of the limits: fp8 weights (rounded on the host: the chip's
# compiler folds a float8 round trip inside one program away), and the
# coefficient path in bfloat16 (``hc_dtype``).
#
# Gradients: a parameter's worst reads 1.05e-1 to 1.11e-1 as the cell runs
# (``wq_a`` / ``wq_b`` / ``q_a_norm``, eight seeds; every other matrix,
# the held experts, the router and a mixer's ``phi`` 0.078 to 0.097;
# ``final_norm`` 3.5e-2, ``lm_head`` 4.4e-2), twice GLM's 4.8e-2 at the
# same five layers: YaRN's factor doubles the softmax scale (0.145 for
# 0.072), and with it what bf16's rounding of q and k moves a logit. With
# fp8 weights the same three read 0.97 to 1.02 and every matrix above 0.83
# (six seeds): the limit 2.5e-1, 2.3 times the largest sound reading and
# under a third of the control's smallest. One limit serves the experts
# and the router too: they read as every matrix does.
#
# Expert choices: 3.0 to 3.4% of the pairs differ from the reference's own
# router, the widest tie broken 2.2e-2 to 3.5e-2 in score (top-4 of 64 at
# a doubled softmax scale upstream: GLM reads 1.8% and 1.9e-2); with fp8
# weights 29 to 30% and 0.38 to 0.60: the limits 8e-2 and 1.5e-1 (one of
# eleven sound runs broke a tie of 5.1e-2).
#
# The loss hardly moves with the precision (5.5e-5 to 2.4e-4 on the
# check's 2,048 rows, 7e-6 to 1.2e-4 on the timed step's 8,192; with fp8
# weights 4.6e-5 to 1.7e-3: the two overlap): its limit is
# ``train_pattern``'s and ``train_blockdiff``'s 1e-3, four times the
# largest sound reading, and what it holds is the loss's own arithmetic.
#
# ``CANCELLING``: a mixer's ``b``. Its gradient is 24 numbers a half-layer,
# each a sum over every token of terms of both signs: 0.09 to 0.38 as the
# cell runs (the worst of ten half-layers; eight seeds) / 0.11 to 0.51 with
# bfloat16 coefficients: the two overlap, so this limit holds no precision
# and is set for structure: 8.5e-1, 2.2 times the largest sound reading
# (``train_sambay``'s rule for lambda's vectors), under the 1 a gradient
# that is zero reads. ``UNHELD``: a mixer's ``alpha``, three numbers a
# half-layer, each a sum over every token AND every column of a group:
# 0.16 to 2.07 as the cell runs (a layer's sum comes out near zero on some
# seeds and its error is then twice itself), 0.43 to 0.92 with bfloat16
# coefficients: printed with the others, decided by none
# (``train_blockdiff`` leaves its ``w_router`` out for the same reason);
# float32 holds it to 1e-5 at toy size
# (``tests/test_models/test_pattern_mhc.py``,
# ``tests/test_benchmarks/test_mhc_check.py``).
#
# The coefficient path is held apart (``coef_alone_error``), as
# ``train_sambay`` holds its scan: inside the bf16 model a bfloat16
# coefficient path moves no gradient past what bf16 activations already do
# (every matrix 0.09 to 0.11 either way, six seeds), so the model's
# ``_mhc_coef`` is also run ALONE, on a float32 state of the check's size
# (the seed's embedding rows in four streams, a seeded spread between
# them) through the first layer's two mixers, against the reference's
# ``mixer_coefficients``, the relative L2 of ``H_res``: float32 reads
# 4.8e-8 to 5.0e-8 on the chip, bfloat16 2.4e-3 to 2.7e-3 (six seeds each):
# the limit 1e-5, two hundred times the one and under the other by as much.
LOSS_REL_TOL = 1e-3  # train_pattern's and train_blockdiff's
GRAD_REL_L2_TOL = 2.5e-1
CANCELLING_GRAD_REL_L2_TOL = 8.5e-1  # for structure
CANCELLING = ("b",)  # of a mixer
UNHELD = ("alpha",)  # of a mixer: printed, decided by none
COEF_REL_TOL = 1e-5  # the coefficients alone, float32 operands
ROUTE_FLIP_SHARE_TOL = 8e-2
ROUTE_MARGIN_TOL = 1.5e-1
# ``update_share`` of the timed step's first call: a step that ran reads
# just under 1, a state left unchanged 0 (``train_blockdiff``)
UPDATE_GAP_TOL = 0.5


def grad_limit(name: str) -> float:
    """The relative L2 limit of one parameter's gradient (``inf``: printed,
    not decided by)."""
    if name.startswith("hc_"):
        leaf = name.rsplit(".", 1)[-1]
        if leaf in UNHELD:
            return float("inf")
        if leaf in CANCELLING:
            return CANCELLING_GRAD_REL_L2_TOL
    return GRAD_REL_L2_TOL


def model_keys(cfg: dict) -> dict:
    """The configuration as the program and the reference read it: the
    file's keys, with ``first_k_dense_replace`` counting the kept layers
    (``layers_kept``, published indices) that lie under the published
    one."""
    return {**cfg, "first_k_dense_replace": flops_xing.dense_layers(cfg)}


class Job(train_pattern.Job):
    """What a run and its check share (``train_pattern.Job``), for a
    ``xing4_0`` configuration. ``model_overrides`` replaces fields of the
    model's ``PatternConfig`` (the tests' faults; the reference never sees
    them)."""

    def __init__(self, cfg: dict, tr: dict, seed: int, devices,
                 model_overrides: dict | None = None):
        from jax.sharding import Mesh

        from magiattention_tpu.models.pattern import xing4_config

        self.cfg, self.tr, self.seed = cfg, tr, int(seed)
        self.pcfg = xing4_config(
            model_keys(cfg), dtype=tr["dtype"], remat=bool(tr["remat"]),
            expert_range=tuple(cfg["experts_here"]),
            vocab_size=cfg["vocab_here"],
        )
        if model_overrides:
            self.pcfg = dataclasses.replace(self.pcfg, **model_overrides)
        self.mesh = Mesh(np.array(devices).reshape(1, -1), ("dp", "cp"))

    def token_ids(self, total: int, k: int) -> np.ndarray:
        """Step ``k``'s token ids in sequence order: the draw
        ``batch_for`` dispatches."""
        rng = np.random.default_rng(
            [self.seed & 0xFFFFFFFF, self.seed >> 32, k]
        )
        return rng.integers(0, self.cfg["vocab_here"], (1, total))[0]


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
        f"model: {p.n_layers} layers (published {cfg.get('layers_kept')}; "
        f"{list(p.ffn_types)}), {p.hc_mult} residual streams of {p.dim}, "
        f"{p.n_heads} heads with keys of {p.head_dim} (on "
        f"{p.kernel_qk_lanes} lanes) and values of {p.value_dim}, top-"
        f"{p.top_k} of {p.n_experts} experts ({p.held_experts} held), "
        f"{p.n_mtp} MTP modules, {n_params / 1e6:.1f} M parameters, fp32 "
        f"master weights + AdamW = {16 * n_params / 1e9:.2f} GB with gradients"
    )

    # -- set-up: the traffic file's mask -------------------------------------
    with span("data"):
        mask = masks.build_mask(tr["mask"], total, index=0)
    log(f"mask: {mask.describe()}; documents {list(mask.doc_lengths)}")
    with span("plan"):
        model, meta = job.build(mask)
        step_fn = model.make_train_step(opt)
    for kind, ap in model.attn_params.items():
        log(f"tiles of {kind}: (block_q, block_k, head_block) = "
            f"({ap.block_q}, {ap.block_k}, {ap.head_block}), grid {ap.grid}")
    with span("data"):
        tokens_g, tokens, labels, pos = job.batch_for(meta, total, 0)
    warm_batch = (tokens, labels, pos)
    routed = total * cfg["num_experts_per_tok"]
    stats_of = jax.jit(
        lambda p, *b: model.loss_fn(
            p, *b, model.sharded_tables(), with_stats=True
        )[1]
    )

    def held_load(when: str):
        """The tokens the experts held here compute in a step on the
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
    pairs_close = float(held_load("as the window closes")[0].sum())
    work = {
        "train_step": flops_xing.train_step_flops(
            cfg, total, mask.area, (pairs_open + pairs_close) / 2
        ),
        "attn_full_executed": flops_xing.attn_executed_flops(cfg, mask.area),
        "mhc_stream_bytes": flops_xing.mhc_stream_bytes(
            cfg, total, jax.numpy.dtype(tr["dtype"]).itemsize,
            bool(tr["remat"]),
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


def _in_sequence_order(meta, choices):
    """The model's expert choices [layers, slots in dispatch order, k] by
    position: slot i holds position ``perm[i]``."""
    perm = np.asarray(meta.perm_idx)
    assert sorted(perm.tolist()) == list(range(len(perm))), "a padded dispatch"
    out = np.zeros_like(choices)
    out[:, perm] = choices
    return out


def _reference(job: Job, params, mask, tokens_g, forced, *, grad: bool):
    """``reference_xing``'s (loss, (choices, margins)) of ``tokens_g``
    under ``mask``'s documents on ``params``, float32 at the highest
    precision, following the expert choices ``forced`` (None: its own);
    with ``grad`` ((loss, aux), gradients)."""
    import jax
    import jax.numpy as jnp

    cfg = model_keys(job.cfg)

    def loss(p, tok, lab, lab2, doc, forced):
        return reference_xing.xing_loss(
            p, tok, lab, lab2, doc, cfg, with_routing=True,
            forced_routing=forced,
        )

    fn = jax.value_and_grad(loss, has_aux=True) if grad else loss
    with jax.default_matmul_precision("highest"):
        return jax.jit(fn)(
            params, jnp.asarray(tokens_g, jnp.int32),
            jnp.asarray(np.roll(tokens_g, -1), jnp.int32),
            jnp.asarray(np.roll(tokens_g, -2), jnp.int32),
            jnp.asarray(doc_ids(mask)),
            None if forced is None else jnp.asarray(forced),
        )


def timed_loss_error(job: Job, params, mask, meta, tokens_g, choices,
                     got: float) -> float:
    """The relative error of ``got``, the loss the compiled step read on
    ``params`` and the window's first batch, against ``reference_xing``'s
    forward pass on the same rows, following the model's expert
    ``choices``."""
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


def _flat_layer(layer: dict) -> dict:
    """A layer's leaves by name, a mixer's under ``hc_attn.<name>``."""
    flat = {}
    for name, v in layer.items():
        if isinstance(v, dict):
            flat.update({f"{name}.{k}": e for k, e in v.items()})
        else:
            flat[name] = v
    return flat


def check_errors(job: Job, params, *, model_job: Job | None = None,
                 model_params=None, free_routing: bool = False):
    """``train_latent.check_errors`` against ``reference_xing``: (relative
    loss error, {parameter: relative L2 gradient error, the worst layer's,
    an MTP module's own four under ``mtp.<name>``}, expert choices against
    the reference's own router). ``model_job`` builds the model from
    another configuration than the reference gets and ``model_params``
    hands it other weights: the tests' faults."""
    import jax
    import jax.numpy as jnp

    mask = check_mask(job.tr)
    t = mask.total
    model, meta = (model_job or job).build(mask)
    for kind, ap in model.attn_params.items():  # beside the window's, above
        log(f"check: tiles of {kind}: (block_q, block_k, head_block) = "
            f"({ap.block_q}, {ap.block_k}, {ap.head_block}), grid {ap.grid}")
    tokens_g, tokens, labels, pos = job.batch_for(meta, t, CHECK_STEP)
    (got, stats), got_grad = jax.jit(jax.value_and_grad(
        lambda p, *batch: model.loss_fn(p, *batch, with_stats=True),
        has_aux=True,
    ))(
        params if model_params is None else model_params,
        tokens, labels, pos, model.sharded_tables(),
    )
    log(f"check: the model's loss and gradients are in ({float(got):.6f})")
    got_idx = _in_sequence_order(meta, np.asarray(stats["expert_idx"])[0])
    (want, (_idx, margins)), want_grad = _reference(
        job, params, mask, tokens_g, None if free_routing else got_idx,
        grad=True,
    )
    errs = jax.device_get(jax.jit(lambda g, w: jax.tree.map(
        lambda a, b: jnp.linalg.norm((a - b).ravel())
        / jnp.maximum(jnp.linalg.norm(b.ravel()), 1e-30), g, w,
    ))(got_grad, want_grad))
    modules = errs.pop("mtp", [])
    layers = errs.pop("layers") + [m.pop("layer") for m in modules]
    grad_err: dict[str, float] = {n: float(e) for n, e in errs.items()}
    for mod in modules:
        for name, e in mod.items():
            name = "mtp." + name
            grad_err[name] = max(grad_err.get(name, 0.0), float(e))
    for layer in layers:  # the worst layer, name by name
        for name, e in _flat_layer(layer).items():
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
        f"decoder {want:.6f}; {100 * routing['flipped_share']:.4f}% of the "
        "token-expert pairs chosen otherwise than the reference's own router "
        f"would, the widest tie broken {routing['worst_margin']:.3e} in score"
    )
    routing["coef_alone"] = coef_alone_error(job, params, model_job)
    log(f"check: the coefficients alone on a float32 state: "
        f"{routing['coef_alone']:.2e}")
    return abs(got - want) / abs(want), grad_err, routing


def coef_alone_error(job: Job, params, model_job: Job | None = None) -> float:
    """The relative L2 error of ``H_res``, the model's stream-to-stream
    matrices, made alone as ``model_job``'s configuration makes them
    (``_mhc_coef``: ``hc_dtype``, the Sinkhorn rounds), against the
    reference's ``mixer_coefficients``: a float32 state of the check's size
    (the seed's embedding rows of the check's tokens in every stream, a
    seeded N(0, 0.01) between the streams), the first layer's two mixers;
    the worse of the two."""
    import jax
    import jax.numpy as jnp

    from magiattention_tpu.models.pattern import _mhc_coef

    pcfg = (model_job or job).pcfg
    t, n = check_mask(job.tr).total, pcfg.hc_mult
    tokens = jnp.asarray(job.token_ids(t, CHECK_STEP), jnp.int32)
    noise = 0.01 * jax.random.normal(
        jax.random.fold_in(key_from_seed(job.seed), 0xC0EF),
        (t, n * pcfg.dim), jnp.float32,
    )
    x = jnp.tile(params["embed"][tokens].astype(jnp.float32), (1, n)) + noise
    worst = 0.0
    for half in ("hc_attn", "hc_ffn"):
        w = params["layers"][0][half]
        got = jax.jit(lambda x, w: _mhc_coef(x, w, pcfg)[2])(x, w)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda x, w: reference_xing.mixer_coefficients(
                x.reshape(t, n, pcfg.dim), w, model_keys(job.cfg)
            )[2])(x, w)
        got = jnp.moveaxis(got.astype(jnp.float32), -1, 0)  # [t, n, n]
        worst = max(worst, float(
            jnp.linalg.norm(got - want) / jnp.linalg.norm(want)
        ))
    return worst


def passes(loss_rel: float, grad_err: dict[str, float],
           routing: dict[str, float]) -> bool:
    """``routing``: the expert choices against the reference's own router,
    and the coefficient path alone (``coef_alone``)."""
    return bool(
        loss_rel <= LOSS_REL_TOL
        and all(e <= grad_limit(n) for n, e in grad_err.items())
        and routing["flipped_share"] <= ROUTE_FLIP_SHARE_TOL
        and routing["worst_margin"] <= ROUTE_MARGIN_TOL
        and routing["coef_alone"] <= COEF_REL_TOL
    )


def _check(job: Job, params) -> bool:
    rel, grad_err, routing = check_errors(job, params)
    ok = passes(rel, grad_err, routing)
    log(
        f"correct={ok}: loss relative {rel:.2e} (tolerance {LOSS_REL_TOL:g}); "
        "gradient by parameter, relative L2, the worst layer's: "
        + ", ".join(f"{n} {e:.2e}" for n, e in sorted(grad_err.items()))
        + f" (tolerance {GRAD_REL_L2_TOL:g}; {CANCELLING_GRAD_REL_L2_TOL:g} on "
        f"a mixer's b; its alpha decided by none); expert choices and the "
        f"coefficients alone {routing} (tolerances {ROUTE_FLIP_SHARE_TOL:g}, "
        f"{ROUTE_MARGIN_TOL:g}, {COEF_REL_TOL:g})"
    )
    return ok
