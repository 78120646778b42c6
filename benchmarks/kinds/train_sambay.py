"""Traffic kind ``train_sambay``: ``train_pattern``'s one-mask stream for
a decoder-hybrid-decoder (Phi-4-mini-flash-reasoning through
``magiattention_tpu/models/pattern.py`` and ``models/ssm.py``: Mamba-1
mixers whose scan resets at a document's start, window-512 and full
differential attention, gated memory units on the last mixer's scan
output, cross attention on the full layer's keys and values).

Closed loop, one packed sequence a step, AdamW; every step brings the
traffic file's mask (``masks.build_mask``: the same documents in every
run), and ``--seed`` makes the weights and the token ids only. Set-up
plans (``build_magi_pattern``: one dispatch, a window plan and a full
plan on it, the documents' shift plan for the convolution), dispatches
the token ids and compiles the step; the window and
``train_tokens_per_s`` are ``train_pattern``'s (``timing.timed_units``,
``timing.Phase.rate``). The model is dense: the same work on every seed.

``correct`` is decided outside the window, as ``train_cca`` and
``train_blockdiff`` decide theirs. On the seed's weights made anew: the
loss of one packed ``check_tokens`` sequence (documents ``check_mask``:
two longer than the window, a boundary off the chunk grid, so a reset, a
convolution's tail and a window's edge each fall inside a chunk) and its
gradient with respect to every parameter, against
``reference_phi4flash`` in float32 at the published widths. The timed
program itself is held at its own size by its first call, made on the
seed's weights before the window: the loss it read against the
reference's forward pass on the same 16,384 rows (a head and 2,048 query
rows at a time), and how far it moved the parameters against what AdamW's
first step moves them (``train_blockdiff.update_share``).
"""

from __future__ import annotations

import dataclasses
import itertools

import numpy as np

from .. import flops_phi4flash, masks, reference_phi4flash, timing
from ..harness import Observations, key_from_seed, log
from . import train_pattern
from .train_blockdiff import update_share
from .train_pattern import CHECK_STEP, FULL, SLIDING, check_mask, window_area

# bf16 model against the float32 plain decoder, ``train_cca``'s
# comparison: the loss of one packed sequence and its gradient with
# respect to every parameter by relative L2, a layer's parameter held to
# its WORST layer's difference against the norm of the parameter's
# LARGEST layer's gradient (``train_cca`` says why; here a name's layers
# are the layers of the kinds that have it).
#
# The limits, each between two readings at the published widths on the
# seed's weights (my chip runs, PR 46; PERF.md section 6 has them with
# their origin): as the cell runs, and the controls, the nearest precision
# below, which have to come out not correct by one of the limits: fp8
# weights, and a bfloat16 scan state. Forty-eight seeds as the cell runs
# (the check's model and reference each compiled once and called a seed),
# four under fp8 weights, three under the bfloat16 state. Gradients: a
# parameter's worst reads 3.6e-2 to 4.2e-2 as the cell runs (``wq`` /
# ``wk`` / ``diff_norm``; every parameter within a third of that on every
# seed: bf16's error is one size whatever the layer's kind;
# ``final_norm`` 1.4e-2) / 4.9e-1 to 5.1e-1 with fp8 weights (no
# parameter but the dead bias, ``final_norm`` and ``final_norm_b`` under
# 3.7e-1): the limit 1.2e-1, 2.8 times the largest sound reading and a
# quarter of the control's. The loss hardly moves with the precision
# (1.3e-6 to 7.7e-5 on the check's 4,096 rows, 0 to 3.7e-5 on the timed
# step's 16,384 / 4.3e-4 to 1.2e-3): ``train_latent``'s 3e-4, four times
# the largest sound reading, and it is the gradients that hold the
# precision.
#
# Those readings are at the spread the seed gives lambda's vectors,
# ``models.pattern.DIFF_LAMBDA_STD`` = 0.05. Under the Differential
# Transformer's own 0.1 (the same forty-eight seeds, the vectors doubled)
# lambda comes within 0.02 of 1 in a layer on four of them and ``wq``
# reads 8.8e-2, 9.2e-2, 1.5e-1 and 4.3e-1 there (the driver's seed
# 699881616: lambda 0.987 in the window layer), every other seed as
# above. What reads so is one token: a document's second, where the two
# maps of a pair still agree to a hundredth, ``a1 - lambda a2`` is 0.009
# against ``a1``'s 0.64, under bf16's step of ``a1`` and ``a2``, and the
# sub-norm's 1 / rms gives that token more of ``wq``'s gradient than the
# 4,095 others together (two of forty heads double their norm in the
# REFERENCE). The float32 model reads 3e-5 on that seed: the arithmetic is
# right and no bf16 program can read it, the published one included. So
# the seed's spread was halved and no limit moved.
#
# ``CANCELLING``: lambda's four vectors. Their gradient is one number a
# layer, ``dL/d lambda``, times the vectors: a sum over every token, head
# and lane of ``<d out, a2>`` whose terms cancel, so bf16's error is
# large against what is left: 5.2e-3 to 2.7e-1 as the cell runs (a
# layer's sum comes out at a twentieth of its usual size on some seeds,
# and its error is then several times itself and a quarter of the largest
# layer's) / 1.7e-1 to 8.2e-1 with fp8 weights: the two overlap, so this
# limit holds no precision (``train_blockdiff`` leaves its ``w_router``
# out for the same reason; the thirty other parameters hold it) and is
# set for structure: 6e-1, 2.2 times the largest sound reading, under the
# 1 a gradient that is zero or of the wrong sign reads. In float32 the
# four are held to 2e-4 at toy size
# (``tests/test_models/test_pattern_sambay.py``). The scan's ``A_log``
# and ``b_dt`` sum over every token too, but read as every other
# parameter does (3.2e-2 to 3.7e-2): they are held by the common limit.
#
# ``bk``: a key's bias adds the same number to every score of a query row
# and the softmax drops it: its true gradient is zero and what either
# side reads is rounding, so it is held against the QUERY bias's norm
# (what a bias's gradient is when it is not dead), not its own.
#
# The scan's state is held apart (``scan_alone_error``), as ``train_cca``
# holds its router: inside the model a bfloat16 state moves no gradient
# past what bf16 activations already do (3.7e-2 and 3.8e-2 where float32
# reads 3.6e-2 and 3.7e-2), so the model's scan is also run ALONE, on
# float32 operands at the check's size and documents, against the
# reference's token-by-token scan: a float32 state reads 0 on the chip
# (every whole run, twenty-three seeds; the kernels make the
# reference's operations in the reference's order) / a bfloat16 state
# 1.18e-3 to 1.20e-3 (three seeds): the limit 1e-4, a twelfth of the
# control's.
LOSS_REL_TOL = 3e-4  # train_latent's and train_cca's
SCAN_REL_TOL = 1e-4  # the scan alone, float32 operands
GRAD_REL_L2_TOL = 1.2e-1
CANCELLING_GRAD_REL_L2_TOL = 6e-1
CANCELLING = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")
DEAD = {"bk": "bq"}  # a gradient that is zero: whose norm it is held to
# ``update_share`` of the timed step's first call: a step that ran reads
# just under 1, a state left unchanged 0 (``train_blockdiff``)
UPDATE_GAP_TOL = 0.5


def grad_limit(name: str) -> float:
    """The relative L2 limit of one parameter's gradient."""
    return CANCELLING_GRAD_REL_L2_TOL if name in CANCELLING else GRAD_REL_L2_TOL


def model_keys(cfg: dict) -> dict:
    """The configuration as the program and the reference read it: the
    file's keys with its assumed sizes beside them."""
    return {**cfg, **cfg["assumed"]["sizes"]}


class Job(train_pattern.Job):
    """What a run and its check share (``train_pattern.Job``), for a
    ``phi4flash`` configuration. ``model_overrides`` replaces fields of
    the model's ``PatternConfig`` (the tests' faults; the reference never
    sees them)."""

    def __init__(self, cfg: dict, tr: dict, seed: int, devices,
                 model_overrides: dict | None = None):
        from jax.sharding import Mesh

        from magiattention_tpu.models.pattern import phi4flash_config

        self.cfg, self.tr, self.seed = cfg, tr, int(seed)
        self.pcfg = phi4flash_config(
            model_keys(cfg), dtype=tr["dtype"], remat=bool(tr["remat"]),
            vocab_size=cfg["vocab_here"], layers=cfg["layers_kept"],
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


def doc_ids(mask) -> np.ndarray:
    """The rows' document ids, from the mask's documents."""
    return np.repeat(
        np.arange(len(mask.doc_lengths)), np.asarray(mask.doc_lengths)
    ).astype(np.int32)


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
        f"model: {p.n_layers} layers {list(p.layer_types)} (published "
        f"{list(p.layer_index)}), {p.n_heads} query / {p.n_kv_heads} "
        f"key-value heads of {p.head_dim}, {p.ssm_inner} scan channels of "
        f"{p.ssm_state} states, {n_params / 1e6:.1f} M parameters, fp32 "
        f"master weights + AdamW = {16 * n_params / 1e9:.2f} GB with gradients"
    )

    # -- set-up: the traffic file's mask -------------------------------------
    with span("data"):
        mask = masks.build_mask(tr["mask"], total, index=0)
    areas = {
        FULL: mask.area,
        SLIDING: window_area(mask.doc_lengths, cfg["sliding_window"]),
    }
    log(
        f"mask: {mask.describe()}; documents {list(mask.doc_lengths)}; under "
        f"window {cfg['sliding_window']}: area {areas[SLIDING]}"
    )
    with span("plan"):
        model, meta = job.build(mask)
        step_fn = model.make_train_step(opt)
    for kind, ap in model.attn_params.items():
        log(f"tiles of {kind}: (block_q, block_k, head_block) = "
            f"({ap.block_q}, {ap.block_k}, {ap.head_block}), grid {ap.grid}")
    shift = model.shift_plan
    log(
        f"shift: taps {shift.taps} over {shift.documents} documents, "
        f"{shift.remote_rows} rows from another rank"
    )
    with span("data"):
        tokens_g, tokens, labels, pos = job.batch_for(meta, total, 0)
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
    work = {
        "train_step": flops_phi4flash.train_step_flops(cfg, total, areas),
        "attn_sliding_executed": flops_phi4flash.attn_executed_flops(
            cfg, SLIDING, areas[SLIDING]
        ),
        "attn_full_executed": flops_phi4flash.attn_executed_flops(
            cfg, FULL, areas[FULL]
        ),
        "ssm_scan_bytes": flops_phi4flash.ssm_scan_bytes(cfg, total),
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
        f"window: {phase.calls} steps in {phase.elapsed_s:.4f} s "
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
        # on the seed's weights, not the trained ones
        params = seed_params()
        ok = (
            np.isfinite(last_loss)
            and _check_timed_step(job, params, mask, tokens_g, first_loss, moved)
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


def _reference(job: Job, params, mask, tokens_g, *, grad: bool):
    """``reference_phi4flash``'s loss of ``tokens_g`` under ``mask``'s
    documents on ``params``, float32 at the highest precision; with
    ``grad`` (loss, gradients)."""
    import jax
    import jax.numpy as jnp

    def loss(p, tok, lab, doc):
        return reference_phi4flash.phi4flash_loss(
            p, tok, lab, doc, model_keys(job.cfg)
        )

    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(loss) if grad else loss)(
            params, jnp.asarray(tokens_g, jnp.int32),
            jnp.asarray(np.roll(tokens_g, -1), jnp.int32),
            jnp.asarray(doc_ids(mask)),
        )


def timed_loss_error(job: Job, params, mask, tokens_g, got: float) -> float:
    """The relative error of ``got``, the loss the compiled step read on
    ``params`` and the window's first batch, against
    ``reference_phi4flash``'s forward pass on the same rows."""
    want = float(_reference(job, params, mask, tokens_g, grad=False))
    log(
        f"check: the timed step's first call, {mask.total} rows on the "
        f"seed's weights: loss {got:.6f} vs float32 plain decoder {want:.6f}"
    )
    return abs(got - want) / abs(want)


def timed_step_passes(loss_rel: float, moved: float) -> bool:
    return bool(
        loss_rel <= LOSS_REL_TOL and abs(1.0 - moved) <= UPDATE_GAP_TOL
    )


def _check_timed_step(job: Job, params, mask, tokens_g, first_loss: float,
                      moved: float) -> bool:
    rel = timed_loss_error(job, params, mask, tokens_g, first_loss)
    ok = timed_step_passes(rel, moved)
    log(
        f"correct={ok} of the timed step: loss relative {rel:.2e} "
        f"(tolerance {LOSS_REL_TOL:g}); its first update moved the "
        f"parameters {moved:.4f} of AdamW's first step (within "
        f"{UPDATE_GAP_TOL:g} of 1)"
    )
    return ok


def check_reference(job: Job, params):
    """(loss, gradients) of ``reference_phi4flash`` on the check's
    sequence and ``params``: what every reading of :func:`check_errors`
    on them is held against (the tests make it once)."""
    mask = check_mask(job.tr)
    return _reference(
        job, params, mask, job.token_ids(mask.total, CHECK_STEP), grad=True
    )


def scan_alone_error(job: Job, params, model_job: Job | None = None) -> float:
    """The relative L2 error of the model's selective scan, run alone as
    ``model_job``'s configuration runs it, against the reference's
    token-by-token scan: float32 operands of the check's size (the first
    mixer's ``A`` and ``D``, its step bias under a seeded spread, seeded
    ``u``, ``B``, ``C``), the check's documents."""
    import jax
    import jax.numpy as jnp

    from magiattention_tpu.ops.selective_scan import selective_scan

    pcfg = (model_job or job).pcfg
    mask = check_mask(job.tr)
    w = next(layer for layer in params["layers"] if "ssm_a_log" in layer)
    t, (e, n) = mask.total, w["ssm_a_log"].shape
    k = jax.random.split(jax.random.fold_in(key_from_seed(job.seed), 0x5CA7), 4)
    u = jax.random.normal(k[0], (t, e), jnp.float32)
    dt = jax.nn.softplus(w["ssm_dt_b"] + 0.5 * jax.random.normal(k[1], (t, e)))
    b = jax.random.normal(k[2], (t, n), jnp.float32)
    c = jax.random.normal(k[3], (t, n), jnp.float32)
    a = -jnp.exp(w["ssm_a_log"])
    ids = doc_ids(mask)
    start = jnp.asarray(np.r_[True, ids[1:] != ids[:-1]])
    got = jax.jit(lambda *xs: selective_scan(
        *xs, state_dtype=pcfg.scan_state_dtype
    ))(u, dt, a, b, c, w["ssm_d"], start)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(reference_phi4flash.selective_scan)(
            u, dt, a, b, c, w["ssm_d"], start
        )
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def check_errors(job: Job, params, *, model_job: Job | None = None,
                 model_params=None, reference=None):
    """(relative loss error, {parameter: relative L2 gradient error, of a
    layer's parameter the worst of its layers' differences over the
    largest of their norms}, the scan alone: :func:`scan_alone_error`)
    of the model on one packed sequence of
    ``check_tokens`` tokens against ``reference_phi4flash`` on the same
    weights and tokens (``reference``: :func:`check_reference`'s, where
    the caller has it). ``model_job`` builds the model from another
    configuration than the reference gets and ``model_params`` hands it
    other weights: the tests' faults."""
    import jax
    import jax.numpy as jnp

    mask = check_mask(job.tr)
    t = mask.total
    model, meta = (model_job or job).build(mask)
    for kind, ap in model.attn_params.items():  # beside the window's, above
        log(f"check: tiles of {kind}: (block_q, block_k, head_block) = "
            f"({ap.block_q}, {ap.block_k}, {ap.head_block}), grid {ap.grid}")
    _tokens_g, tokens, labels, pos = job.batch_for(meta, t, CHECK_STEP)
    got, got_grad = jax.jit(jax.value_and_grad(model.loss_fn))(
        params if model_params is None else model_params,
        tokens, labels, pos, model.sharded_tables(),
    )
    log(f"check: the model's loss and gradients are in ({float(got):.6f})")
    want, want_grad = reference or check_reference(job, params)
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
    by_name: dict[str, list] = {}
    for layer in sq["layers"]:
        for name, e in layer.items():
            by_name.setdefault(name, []).append(e)
    for name, readings in by_name.items():
        diffs, refs = zip(*readings)
        held_to = max(r[1] for r in by_name[DEAD.get(name, name)])
        grad_err[name] = rel_l2(max(diffs), held_to)
        log(f"check: {name} a layer, against its own norm alone: "
            + ", ".join(f"{rel_l2(d, r):.2e}" for d, r in zip(diffs, refs))
            + "; the reference's norm: "
            + ", ".join(f"{np.sqrt(r):.2e}" for r in refs))
    got, want = float(got), float(want)
    log(
        f"check: one packed sequence of {t} tokens (documents "
        f"{list(mask.doc_lengths)}), model loss {got:.6f} vs float32 plain "
        f"decoder {want:.6f}"
    )
    scan_rel = scan_alone_error(job, params, model_job)
    log(f"check: the scan alone on float32 operands: {scan_rel:.2e}")
    return abs(got - want) / abs(want), grad_err, scan_rel


def passes(loss_rel: float, grad_err: dict[str, float],
           scan_rel: float) -> bool:
    return bool(
        loss_rel <= LOSS_REL_TOL
        and all(e <= grad_limit(n) for n, e in grad_err.items())
        and scan_rel <= SCAN_REL_TOL
    )


def _check(job: Job, params) -> bool:
    rel, grad_err, scan_rel = check_errors(job, params)
    ok = passes(rel, grad_err, scan_rel)
    log(
        f"correct={ok}: loss relative {rel:.2e} (tolerance {LOSS_REL_TOL:g}); "
        "gradient by parameter, relative L2, of a layer's parameter the "
        "worst layer's difference over the largest layer's norm: "
        + ", ".join(f"{n} {e:.2e}" for n, e in sorted(grad_err.items()))
        + f" (tolerance {GRAD_REL_L2_TOL:g}, {CANCELLING_GRAD_REL_L2_TOL:g} "
        f"on {CANCELLING}); the scan alone {scan_rel:.2e} (tolerance "
        f"{SCAN_REL_TOL:g})"
    )
    return ok
