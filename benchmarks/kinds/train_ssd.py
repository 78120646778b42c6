"""Traffic kind ``train_ssd``: ``train_sambay``'s one-mask stream for a
Mamba-2 hybrid (granite-4.0-h-micro through
``magiattention_tpu/models/pattern.py`` and ``models/ssm.py``: Mamba-2
mixers on the state-space-dual scan of ``ops/ssd_scan.py``, whose state
resets at a document's start wherever in a chunk it falls, beside NoPE
GQA attention at 64-wide heads, under Granite's four scalars).

Closed loop, one packed sequence a step, AdamW; every step brings the
traffic file's mask (``masks.build_mask``: the same documents in every
run), and ``--seed`` makes the weights and the token ids only. Set-up
plans (``build_magi_pattern``: one dispatch, the documents' plan, their
shift plan for the convolution), dispatches the token ids and compiles
the step; the window and ``train_tokens_per_s`` are ``train_pattern``'s
(``timing.timed_units``, ``timing.Phase.rate``). The model is dense: the
same work on every seed.

``correct`` is decided outside the window, as ``train_sambay`` decides
its own. On the seed's weights made anew: the loss of one packed
``check_tokens`` sequence (documents ``check_mask``: both boundaries
inside a scan chunk, one document longer than eleven chunks) and its
gradient with respect to every parameter, against ``reference_granite``
in float32 at the published widths (the recurrence a token at a time);
the scan run ALONE on float32 operands against that recurrence. The
timed program itself is held at its own size by its first call, made on
the seed's weights before the window: the loss it read against the
reference's forward pass on the same 16,384 rows, and how far it moved
the parameters against what AdamW's first step moves them
(``train_blockdiff.update_share``).
"""

from __future__ import annotations

import dataclasses
import itertools

import numpy as np

from .. import flops_granite, masks, reference_granite, timing
from ..harness import Observations, key_from_seed, log
from . import train_pattern
from .train_blockdiff import update_share
from .train_pattern import CHECK_STEP, FULL, check_mask
from .train_sambay import Job as _SambayJob, doc_ids

# bf16 model against the float32 plain decoder, ``train_sambay``'s
# comparison: the loss of one packed sequence and its gradient with
# respect to every parameter by relative L2, a layer's parameter held to
# its WORST layer's difference against the norm of the parameter's
# LARGEST layer's gradient (``train_cca`` says why).
#
# The limits, each between two readings at the published widths on the
# seed's weights (my chip runs, PR 55; PERF.md section 6 has them with
# their origin): as the cell runs (seventeen seeds: eight in one process
# that calls ``check_errors`` a seed, nine whole runs of the command), and the
# controls, the nearest precision below, which have to come out not
# correct by one of the limits: fp8 weights (three seeds; rounded on the
# host: the chip's compiler folds a float8 round trip inside one program
# away), and a bfloat16 scan state (three seeds).
#
# Gradients: a parameter's worst reads 3.3e-2 to 5.6e-2 as the cell runs
# (the largest ``ssd_a_log`` and ``ssd_dt_b``, 2.6e-2 to 5.6e-2 and 2.8e-2
# to 4.4e-2: a head's number summed over every token; every matrix 3.0e-2
# to 3.4e-2 on every seed: bf16's error is one size whatever the layer's
# kind; ``final_norm`` 2.2e-2) / 3.8e-1 to 4.3e-1 with fp8 weights, no
# parameter under 2.1e-1: the limit 1.2e-1, ``train_sambay``'s, 2.1 times
# the largest sound reading, 1.8 times under the control's smallest
# parameter and 3.2 under its worst. The loss hardly moves with the
# precision (4.7e-7 to 7.7e-6 on the check's 4,096 rows, 1.1e-6 to
# 2.6e-6 on the timed step's 16,384 / 5.3e-6 to 6.8e-5): ``train_latent``'s
# 3e-4, and it is the gradients that hold the precision.
#
# The scan's state is held apart (``scan_alone_error``), as
# ``train_sambay`` holds its own: inside the model a bfloat16 state at the
# chunks' ends moves no gradient past what bf16 activations already do
# (worst 3.7e-2 to 4.0e-2 where float32 reads 3.6e-2 to 5.6e-2), so the
# model's scan is also run ALONE, on float32 operands at the check's size
# and documents, against the reference's token-at-a-time recurrence: a
# float32 state reads 6.3e-6 to 2.09e-5 on the chip (twenty readings;
# 1.5e-7 on the CPU and the same at a chunk of 128 as of 256: what is read
# is the chip's ``exp`` a step, which the recurrence's 4,096 products
# compound and the chunked form's one exponent of a sum does not) / a
# bfloat16 state 9.7e-5 to 1.55e-4 (six readings: the state is rounded
# once a chunk of 256 rows, not once a token as Mamba-1's control is):
# the limit 4.5e-5, 2.2 times the largest sound reading and 2.1 times
# under the control's smallest.
LOSS_REL_TOL = 3e-4  # train_latent's, train_cca's and train_sambay's
SCAN_REL_TOL = 4.5e-5  # the scan alone, float32 operands
GRAD_REL_L2_TOL = 1.2e-1
# ``update_share`` of the timed step's first call: a step that ran reads
# just under 1, a state left unchanged 0 (``train_blockdiff``)
UPDATE_GAP_TOL = 0.5


def model_keys(cfg: dict) -> dict:
    """The configuration as the program and the reference read it: the
    file's keys with its assumed sizes beside them."""
    return {**cfg, **cfg["assumed"]["sizes"]}


class Job(train_pattern.Job):
    """What a run and its check share (``train_pattern.Job``), for a
    ``granitemoehybrid`` configuration. ``model_overrides`` replaces
    fields of the model's ``PatternConfig`` (the tests' faults and the
    controls; the reference never sees them)."""

    def __init__(self, cfg: dict, tr: dict, seed: int, devices,
                 model_overrides: dict | None = None):
        from jax.sharding import Mesh

        from magiattention_tpu.models.pattern import granitemoehybrid_config

        self.cfg, self.tr, self.seed = cfg, tr, int(seed)
        self.pcfg = granitemoehybrid_config(
            model_keys(cfg), dtype=tr["dtype"], remat=bool(tr["remat"]),
            vocab_size=cfg["vocab_here"],
        )
        if model_overrides:
            self.pcfg = dataclasses.replace(self.pcfg, **model_overrides)
        self.mesh = Mesh(np.array(devices).reshape(1, -1), ("dp", "cp"))

    # step k's token ids in sequence order: the draw ``batch_for`` dispatches
    token_ids = _SambayJob.token_ids


def reset_chunks(doc_lengths, chunk: int) -> int:
    """Scan chunks with a document's start strictly inside them."""
    starts = np.cumsum(doc_lengths)[:-1]
    return len({int(s) // chunk for s in starts if s % chunk})


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
        f"model: {p.n_layers} layers {list(p.layer_types)}, {p.n_heads} "
        f"query / {p.n_kv_heads} key-value heads of {p.head_dim} (softmax "
        f"scale {p.softmax_scale}), {p.ssm_heads} scan heads of "
        f"{p.ssm_inner // p.ssm_heads} channels x {p.ssm_state} states in "
        f"chunks of {p.ssm_chunk}, multipliers embedding {p.embed_scale} "
        f"residual {p.residual_scale} logits 1/{p.logits_scaling}, "
        f"{n_params / 1e6:.1f} M parameters, fp32 master weights + AdamW = "
        f"{16 * n_params / 1e9:.2f} GB with gradients"
    )

    # -- set-up: the traffic file's mask -------------------------------------
    with span("data"):
        mask = masks.build_mask(tr["mask"], total, index=0)
    log(
        f"mask: {mask.describe()}; documents {list(mask.doc_lengths)}; "
        f"{reset_chunks(mask.doc_lengths, p.ssm_chunk)} of "
        f"{-(-total // p.ssm_chunk)} scan chunks hold a reset inside them"
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
        "train_step": flops_granite.train_step_flops(cfg, total, mask.area),
        "attn_full_executed": flops_granite.attn_executed_flops(cfg, mask.area),
        "ssd_scan_executed": flops_granite.ssd_scan_flops(cfg, total),
        "ssd_scan_bytes": flops_granite.ssd_scan_bytes(cfg, total),
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
    """``reference_granite``'s loss of ``tokens_g`` under ``mask``'s
    documents on ``params``, float32 at the highest precision; with
    ``grad`` (loss, gradients)."""
    import jax
    import jax.numpy as jnp

    def loss(p, tok, lab, doc):
        return reference_granite.granite_loss(
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
    ``reference_granite``'s forward pass on the same rows."""
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
    """(loss, gradients) of ``reference_granite`` on the check's sequence
    and ``params``: what every reading of :func:`check_errors` on them is
    held against (the tests make it once)."""
    mask = check_mask(job.tr)
    return _reference(
        job, params, mask, job.token_ids(mask.total, CHECK_STEP), grad=True
    )


def scan_alone_error(job: Job, params, model_job: Job | None = None) -> float:
    """The relative L2 error of the model's state-space-dual scan, run
    alone as ``model_job``'s configuration runs it, against the
    reference's token-by-token recurrence: float32 operands of the
    check's size (the first mixer's ``A`` and ``D``, its step bias under
    a seeded spread, seeded ``x``, ``B``, ``C``), the check's documents."""
    import jax
    import jax.numpy as jnp

    from magiattention_tpu.ops.ssd_scan import ssd_scan

    pcfg = (model_job or job).pcfg
    mask = check_mask(job.tr)
    w = next(layer for layer in params["layers"] if "ssd_a_log" in layer)
    t, h, n = mask.total, pcfg.ssm_heads, pcfg.ssm_state
    k = jax.random.split(jax.random.fold_in(key_from_seed(job.seed), 0x55D), 4)
    x = jax.random.normal(k[0], (t, h, pcfg.ssm_inner // h), jnp.float32)
    dt = jax.nn.softplus(w["ssd_dt_b"] + 0.5 * jax.random.normal(k[1], (t, h)))
    b = jax.random.normal(k[2], (t, n), jnp.float32)
    c = jax.random.normal(k[3], (t, n), jnp.float32)
    a = -jnp.exp(w["ssd_a_log"])
    ids = doc_ids(mask)
    start = jnp.asarray(np.r_[True, ids[1:] != ids[:-1]])
    got = jax.jit(lambda *xs: ssd_scan(
        *xs, chunk=pcfg.ssm_chunk or None, state_dtype=pcfg.scan_state_dtype
    ))(x, dt, a, b, c, w["ssd_d"], start)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(reference_granite.token_recurrence)(
            x, dt, a, b, c, w["ssd_d"], start
        )
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def check_errors(job: Job, params, *, model_job: Job | None = None,
                 model_params=None, reference=None):
    """(relative loss error, {parameter: relative L2 gradient error, of a
    layer's parameter the worst of its layers' differences over the
    largest of their norms}, the scan alone: :func:`scan_alone_error`)
    of the model on one packed sequence of ``check_tokens`` tokens
    against ``reference_granite`` on the same weights and tokens
    (``reference``: :func:`check_reference`'s, where the caller has it).
    ``model_job`` builds the model from another configuration than the
    reference gets and ``model_params`` hands it other weights: the
    tests' faults and the controls."""
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
        grad_err[name] = rel_l2(max(diffs), max(refs))
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
        and all(e <= GRAD_REL_L2_TOL for e in grad_err.values())
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
        + f" (tolerance {GRAD_REL_L2_TOL:g}); the scan alone {scan_rel:.2e} "
        f"(tolerance {SCAN_REL_TOL:g})"
    )
    return ok
