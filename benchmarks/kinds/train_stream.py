"""Traffic kind ``train_stream``: a training job on packed sequences.

Closed loop, one packed sequence a step, AdamW. The masks come from a
stream (``masks.stream_phi``: the same stream in every run) and
``--seed`` makes the weights and the token ids only. For a mask it has
not seen the job does what ``examples/train_llama.py`` does: plan on the
host (``build_magi_llama``), ``make_train_step``, dispatch the token
ids, compile, run (``new_mask_step``). The traffic file's
``new_mask_every_step`` picks one of two streams:

* ``false`` — every step brings mask 0 of the stream. The step program
  is built once in set-up (served by the persistent compilation cache
  after a cell's first run: a mask that repeats is what that cache is
  for) and the window is a run of timed units of steps enqueued back to
  back on a few prefetched batches, as a trainer that reads its loss
  every few steps runs them. ``train_tokens_per_s`` is the tokens of
  every step of the window over the window's whole time
  (``timing.Phase.rate``).
* ``true`` — every step brings the next mask, which today is a new step
  program. The persistent cache is turned off before the first step
  program is built and stays off through the window, so a mask the job
  has never seen compiles as it would in a job, in the first run of a
  cell and in the sixth alike; mask 0, the warm-up, compiles for real in
  every run because the first step program a process compiles takes
  18.5-19.7 s and later ones 9.4-17.9 s (PR 23, chip).
  ``train_tokens_per_s`` is the tokens of the steps completed inside the
  window over the time to the end of the last one
  (``timing.completed_rate``), compile inside; a step is started only
  while the slowest so far still fits. No cell of ``BENCHMARK.json``
  uses this stream yet: XLA's compile of one and the same step program
  took 9.4 to 17.9 s from run to run on the v5e's shared host, the rate
  spread by 12.8%, and no bound the contract allows holds that (PR 23,
  PERF.md). It is here, tested at toy size, because a later PR can claim
  a gain only in a cell that it adds as data files: the PR that makes
  one program serve every mask adds that cell.
"""

from __future__ import annotations

import itertools
import statistics
import time

import numpy as np

from .. import flops, masks, reference, timing
from ..harness import Observations, key_from_seed, log, set_compile_cache

# bf16 model against the float32 plain decoder on the same weights and
# tokens: the loss of one packed sequence, and its gradient with respect
# to every layer's parameters by relative L2, a parameter at a time. The
# loss at random init is about ln(vocab) whatever the model attends to:
# at toy size on the CPU a model that attends across documents moves it
# by 9.6e-4, inside the tolerance, and its gradients by 1.4; fp8 weights
# move the gradients by 0.24, bf16 by 0.025, float32 by 1.3e-6
# (tests/test_benchmarks/test_train_check.py). So the gradients hold the
# mask, the backward kernels and the precision; their tolerance is 2.4
# times what bf16 read there. On the chip (PR 23) the loss differed by
# 2.7e-6 to 1.4e-4 relative over 29 runs.
LOSS_REL_TOL = 1e-3
GRAD_REL_L2_TOL = 6e-2
CHECK_STEP = 1_000_000  # the check's token ids: a step no window reaches


def _llama_config(cfg: dict, tr: dict):
    from magiattention_tpu.models import LlamaConfig

    return LlamaConfig(
        vocab_size=cfg["vocab_size"],
        dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        ffn_hidden=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]),
        dtype=tr["dtype"],
        remat=bool(tr["remat"]),
    )


class Job:
    """What a run and its check share: the model's sizes, the mesh, and
    how a mask becomes a model and a step's token ids a batch."""

    def __init__(self, cfg: dict, tr: dict, seed: int, devices):
        from jax.sharding import Mesh

        self.cfg, self.tr, self.seed = cfg, tr, int(seed)
        self.lcfg = _llama_config(cfg, tr)
        self.mesh = Mesh(np.array(devices).reshape(1, -1), ("dp", "cp"))

    def build(self, mask):
        """(model, dispatch meta) for one mask: the plan on the host."""
        from magiattention_tpu.common import AttnRanges
        from magiattention_tpu.models import build_magi_llama

        return build_magi_llama(
            self.lcfg, self.mesh, mask.total,
            AttnRanges.from_ranges(list(mask.q_ranges)),
            AttnRanges.from_ranges(list(mask.k_ranges)),
            list(mask.types), chunk_size=int(self.tr["chunk_size"]),
        )

    def batch_for(self, meta, mask_total: int, k: int):
        """Token ids of step ``k`` from the seed, dispatched, with their
        next-token labels (the distributed roll) and position ids."""
        import jax
        import jax.numpy as jnp

        from magiattention_tpu.parallel import dispatch, roll

        rng = np.random.default_rng(
            [self.seed & 0xFFFFFFFF, self.seed >> 32, k]
        )
        tokens_g = rng.integers(0, self.cfg["vocab_size"], (1, mask_total))
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

    from magiattention_tpu.models import init_params
    from magiattention_tpu.telemetry import get_compile_tracker

    cfg, tr = cell.config, cell.traffic
    total = int(tr["total_tokens"])
    job = Job(cfg, tr, ctx.seed, ctx.devices)
    build, batch_for = job.build, job.batch_for
    replicated = NamedSharding(job.mesh, P())
    tracker = get_compile_tracker()
    span = ctx.tracer.span
    opt = optax.adamw(float(tr["learning_rate"]))

    state = {}
    state["params"] = jax.jit(
        lambda r: init_params(r, job.lcfg), out_shardings=replicated
    )(key_from_seed(ctx.seed))
    state["opt"] = jax.jit(opt.init, out_shardings=replicated)(state["params"])
    n_params = sum(x.size for x in jax.tree.leaves(state["params"]))
    log(
        f"model: {cfg['num_hidden_layers']} layers, {n_params / 1e6:.1f} M "
        f"parameters, fp32 master weights + AdamW = {16 * n_params / 1e9:.2f} "
        "GB with gradients"
    )

    def new_mask_step(k: int):
        """What a job does when step ``k`` brings a mask it has not seen."""
        t0 = time.perf_counter()
        with span("data"):
            mask = masks.build_mask(tr["mask"], total, index=k)
        with span("plan"):
            t1 = time.perf_counter()
            model, meta = build(mask)
            step_fn = model.make_train_step(opt)
            plan_s = time.perf_counter() - t1
        with span("data"):
            _g, tokens, labels, pos = batch_for(meta, total, k)
        with span("compile"):
            t1 = time.perf_counter()
            exe = step_fn.lower(
                state["params"], state["opt"], tokens, labels, pos
            ).compile()
            compile_s = time.perf_counter() - t1
        with span("step"):
            t1 = time.perf_counter()
            state["params"], state["opt"], loss = jax.block_until_ready(
                exe(state["params"], state["opt"], tokens, labels, pos)
            )
            first_step_s = time.perf_counter() - t1
        end = time.perf_counter()
        rec = {
            "k": k, "docs": len(mask.doc_lengths), "area": mask.area,
            "causal_share_pct": 100.0 * mask.causal_share, "plan_s": plan_s,
            "compile_s": compile_s, "first_step_s": first_step_s,
            "new_mask_s": end - t0, "end": end, "loss": float(loss),
        }
        log(f"new mask: {rec}")
        return rec, exe, (tokens, labels, pos), mask, meta

    # -- set-up: mask 0 of the stream ---------------------------------------
    every_step = bool(tr["new_mask_every_step"])
    if every_step:
        set_compile_cache(False)  # mask 0 too compiles for real (see above)
    _warm, warm_exe, warm_batch, warm_mask, warm_meta = new_mask_step(0)

    def steady(batch=warm_batch):
        state["params"], state["opt"], loss = warm_exe(
            state["params"], state["opt"], *batch
        )
        return loss

    warm_times = timing.settle(steady)
    log(f"warm-up steps on mask 0 (s): {[round(t, 4) for t in warm_times]}")
    mem = warm_exe.memory_analysis()
    log(
        "the step's per-device bytes (arguments, outputs, temp): "
        f"({mem.argument_size_in_bytes}, {mem.output_size_in_bytes}, "
        f"{mem.temp_size_in_bytes})"
    )
    work = {"train_step": flops.train_step_flops(cfg, total, warm_mask.area)}
    values, iters = {}, {}

    if not every_step:
        # -- the window: the same mask, steps enqueued back to back --------
        per_unit = timing.calls_per_unit(warm_times[-1], ctx.seconds)
        batches = [warm_batch] + [  # prefetched, as a loader would
            batch_for(warm_meta, total, k)[1:] for k in range(1, per_unit)
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
        stats = timing.summary(phase.per_call_s)
        rate, attempted, done = phase.rate(total), phase.calls, phase.calls
        values["steady_step_s"] = stats["median_s"]
        iters["window"] = phase.calls
        log(
            f"window: {phase.calls} steps on mask 0 in {phase.elapsed_s:.4f} s "
            f"({stats['n']} units of {per_unit}), {compiles} compiles taking "
            f"{compile_s:.2f} s; {rate:.2f} tokens/s; seconds a step by "
            f"unit: {stats}"
        )
    else:
        # -- the window: cache still off, every step a new mask ------------
        ctx.window_opens()
        ctx.tracer.start()
        mark = tracker.mark()
        recs: list[dict] = []
        with ctx.tracer.phase("window"):
            w0 = time.perf_counter()
            w1 = w0 + ctx.seconds
            k = 1
            while True:
                longest = max((r["new_mask_s"] for r in recs), default=0.0)
                if time.perf_counter() + longest > w1:
                    break
                recs.append(new_mask_step(k)[0])
                k += 1
        compiles, compile_s = tracker.since(mark)
        set_compile_cache(True)
        rate, done = timing.completed_rate(
            [r["end"] for r in recs], total, w0, w1
        )
        attempted = max(len(recs), 1)
        if done:
            inside = [r for r in recs if r["end"] <= w1]
            values.update(
                new_mask_ms=1e3
                * statistics.median(r["new_mask_s"] for r in inside),
                new_mask_compile_s=compile_s / len(recs),
            )
        log(
            f"window: {done} of {len(recs)} new-mask steps completed inside "
            f"{ctx.seconds:g} s, {compiles} compiles taking {compile_s:.2f} "
            f"s; {rate:.2f} tokens/s"
        )
        if ctx.trace:  # steady steps on the warmed mask, after the window
            with ctx.tracer.phase("steady"):
                t_steady = []
                for _ in range(int(tr["steady_steps"])):
                    with span("steady_step"):
                        t0 = time.perf_counter()
                        jax.block_until_ready(steady())
                        t_steady.append(time.perf_counter() - t0)
            values["steady_step_s"] = statistics.median(t_steady)
            iters["steady"] = len(t_steady)
            log(f"steady steps on mask 0: {timing.summary(t_steady)}")
    values["compiles_in_window"] = float(compiles)
    ctx.tracer.stop()
    scopes = {}
    if ctx.trace:
        from .. import trace_reduce

        scopes = trace_reduce.hlo_scopes(warm_exe.as_text())

    # -- correct: outside the window ---------------------------------------
    state.pop("opt")  # room for the float32 reference
    del warm_exe, warm_batch, steady
    with span("check"):
        ok = _check(job, state["params"])

    return Observations(
        end_to_end={"train_tokens_per_s": rate},
        attempted=attempted,
        failed=attempted - done,
        correct=ok and bool(done),
        values=values,
        flops=work,
        iters=iters,
        hlo_scopes=scopes,
    )


def check_errors(job: Job, params, *, model_params=None, model_mask=None):
    """(relative loss error, {layer parameter: relative L2 gradient
    error, the worst layer's}) of the model on one packed sequence of
    ``check_tokens`` tokens against the plain float32 decoder on the same
    weights and tokens. ``model_params`` and ``model_mask`` hand the
    model something else than the reference gets: the tests' faults."""
    import jax
    import jax.numpy as jnp

    t = int(job.tr["check_tokens"])
    mask = masks.build_mask(job.tr["mask"], t, index=0)
    model, meta = job.build(model_mask or mask)
    tokens_g, tokens, labels, pos = job.batch_for(meta, t, CHECK_STEP)

    def split(p):
        return p["layers"], {n: a for n, a in p.items() if n != "layers"}

    got, got_grad = jax.jit(jax.value_and_grad(
        lambda layers, rest, *batch: model.loss_fn(
            {**rest, "layers": layers}, *batch
        )
    ))(
        *split(params if model_params is None else model_params),
        tokens, labels, pos, model.sharded_tables(),
    )
    rows = jnp.arange(t)
    with jax.default_matmul_precision("highest"):
        want, want_grad = jax.jit(jax.value_and_grad(
            lambda layers, rest, tok, lab: reference.decoder_loss(
                {**rest, "layers": layers}, tok, lab,
                masks.allowed(mask, rows, rows), job.cfg,
            )
        ))(
            *split(params), jnp.asarray(tokens_g, jnp.int32),
            jnp.asarray(np.roll(tokens_g, -1), jnp.int32),
        )
    errs = jax.jit(lambda g, w: jax.tree.map(
        lambda a, b: jnp.linalg.norm((a - b).ravel())
        / jnp.maximum(jnp.linalg.norm(b.ravel()), 1e-30), g, w,
    ))(got_grad, want_grad)
    grad_err: dict[str, float] = {}
    for layer in jax.device_get(errs):  # the worst layer, name by name
        for name, e in layer.items():
            e = float(e) if np.isfinite(e) else float("inf")
            grad_err[name] = max(grad_err.get(name, 0.0), e)
    got, want = float(got), float(want)
    log(
        f"check: one packed sequence of {t} tokens ({len(mask.doc_lengths)} "
        f"documents), model loss {got:.6f} vs float32 plain decoder "
        f"{want:.6f}"
    )
    return abs(got - want) / abs(want), grad_err


def passes(loss_rel: float, grad_err: dict[str, float]) -> bool:
    return bool(
        loss_rel <= LOSS_REL_TOL
        and max(grad_err.values()) <= GRAD_REL_L2_TOL
    )


def _check(job: Job, params) -> bool:
    rel, grad_err = check_errors(job, params)
    ok = passes(rel, grad_err)
    log(
        f"correct={ok}: loss relative {rel:.2e} (tolerance {LOSS_REL_TOL:g}); "
        "gradient by layer parameter, relative L2: "
        + ", ".join(f"{n} {e:.2e}" for n, e in sorted(grad_err.items()))
        + f" (tolerance {GRAD_REL_L2_TOL:g})"
    )
    return ok
