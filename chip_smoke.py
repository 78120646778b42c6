"""The standing proof that the main path starts on the chip.

    python chip_smoke.py            # one TPU chip: device, kernel, api,
                                    # train, serve
    python chip_smoke.py --chips 4  # one host, four chips: the train
                                    # phase at cp=4 and at cp=1, nothing else

Packed varlen mask -> keyed plan -> dispatch -> Pallas flex kernels (fwd
and bwd) -> undispatch, inside a trainer that takes optimizer steps at
the published widths of TinyLlama-1.1B, plus the serving engine's
compiled prefill and decode kernels — each checked against the repo's
dense float32 oracle. One process, touches jax once, and fails unless
jax came up on a TPU: the last stdout line is
``{"ok": true, "device": {...}}`` only when every phase passed, and
``{"ok": false, ...}`` with a non-zero exit otherwise. What a phase
prints are smoke observations, not benchmark results.

Each phase is a plain function of its sizes, so
``tests/test_chip_smoke_rehearsal.py`` calls it tiny on the CPU with
``compiled=False`` (interpret kernels); the device check is in
:func:`main` only.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# TinyLlama-1.1B (TinyLlama/TinyLlama-1.1B-Chat-v1.0 config.json): the
# published model nearest LlamaConfig's own defaults, whose small
# vocabulary leaves the chip's memory to sequence length. RMS eps 1e-5
# is models/llama.py's constant.
TINYLLAMA = {
    "dim": 2048, "heads": 32, "kv-heads": 4, "head-dim": 64, "ffn": 5632,
    "vocab": 32000, "rope-theta": 10000.0,
}
PUBLISHED_LAYERS = 22
# fp32 master weights + AdamW state + 16k-token activations in 16 GB
TRAIN_LAYERS = 8
TRAIN_TOKENS = 16384

# stated tolerances, bf16 kernels against the float32 dense oracle
REL_L2_TOL = 2e-2  # out and gradients, relative L2 error
LSE_ABS_TOL = 2e-2  # lse, max abs error
LOSS_REL_TOL = 5e-3  # whole-model loss: pallas vs jnp_online, cp=4 vs cp=1


def _log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


class _CacheEvents:
    """Counts jax's persistent-compile-cache hits and writes."""

    def __init__(self):
        from jax import monitoring

        self.hits = self.writes = 0
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1


def _rand(rng, shape, dtype):
    import jax.numpy as jnp

    return jnp.asarray(rng.standard_normal(shape), dtype)


def _cotangent_loss(attn):
    """(q, k, v, d_out, d_lse) -> scalar whose gradient pulls the
    cotangents back through ``attn(q, k, v) -> (out, lse)``; they are
    arguments, not closure constants the compiler would have to embed."""
    import jax.numpy as jnp

    def loss(q, k, v, d_out, d_lse):
        out, lse = attn(q, k, v)
        return (out.astype(jnp.float32) * d_out).sum() + (lse * d_lse).sum()

    return loss


def _oracle(q, k, v, qr, kr, ts, d_out, d_lse):
    """(out, lse, dq, dk, dv) of the dense float32 reference under the
    cotangents (d_out, d_lse); one kv head at a time so the [h, t, t]
    scores of the full head count never exist at once. True-fp32 matmuls:
    the TPU's default f32 dot is a single bf16 pass."""
    import jax
    import jax.numpy as jnp

    from magiattention_tpu.testing import ref_attn_from_ranges

    group = q.shape[1] // k.shape[1]

    def ref(qg, kg, vg):
        return ref_attn_from_ranges(qg, kg, vg, qr, kr, ts)[:2]

    def one_head(*args):
        grads = jax.grad(_cotangent_loss(ref), argnums=(0, 1, 2))(*args)
        return (*ref(*args[:3]), *grads)

    f32 = [x.astype(jnp.float32) for x in (q, k, v, d_out, d_lse)]
    parts = []
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(one_head)
        for g in range(k.shape[1]):
            qs = slice(g * group, (g + 1) * group)
            parts.append(
                fn(
                    f32[0][:, qs], f32[1][:, g : g + 1], f32[2][:, g : g + 1],
                    f32[3][:, qs], f32[4][:, qs],
                )
            )
    return tuple(jnp.concatenate(p, axis=1) for p in zip(*parts))


_PARITY_NAMES = ("out", "lse", "dq", "dk", "dv")


def _check_finite(phase: str, got, q) -> None:
    """(out, lse, dq, dk, dv): finite values of the expected shapes."""
    import numpy as np

    if got[0].shape != q.shape or got[1].shape != q.shape[:2]:
        raise AssertionError(
            f"{phase}: out {got[0].shape} / lse {got[1].shape} for q "
            f"{q.shape}"
        )
    for name, x in zip(_PARITY_NAMES, got):
        if not np.isfinite(np.asarray(x, np.float32)).all():
            raise AssertionError(f"{phase}: {name} has non-finite values")


def _check_parity(phase: str, got, ref) -> None:
    """Hold (out, lse, dq, dk, dv) to the stated tolerances."""
    from magiattention_tpu.testing import calc_inf_norm, calc_rel_err

    errs = {
        name: calc_inf_norm(g, r) if name == "lse" else calc_rel_err(g, r)
        for name, g, r in zip(_PARITY_NAMES, got, ref)
    }
    _log(
        phase,
        "parity vs float32 dense oracle: "
        + ", ".join(f"{n} {e:.2e}" for n, e in errs.items())
        + f" (tolerance: lse max-abs {LSE_ABS_TOL:g}, others rel-L2 "
        f"{REL_L2_TOL:g})",
    )
    for name, e in errs.items():
        tol = LSE_ABS_TOL if name == "lse" else REL_L2_TOL
        if not e <= tol:
            raise AssertionError(f"{phase}: {name} error {e:.3e} > {tol:g}")


def _assert_compiled(phase: str, lowered_text: str, compiled: bool) -> None:
    """interpret=False is what ran: the Pallas kernel is in the program
    as a TPU custom call (and is not, in an interpret-mode rehearsal)."""
    has = "tpu_custom_call" in lowered_text
    _log(phase, f"interpret={not has} (tpu_custom_call in lowered: {has})")
    if has != compiled:
        raise AssertionError(
            f"{phase}: expected interpret={not compiled}, the lowered "
            f"program says interpret={not has}"
        )


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_device(cache_dir: str) -> None:
    """What the process came up on."""
    from importlib import metadata

    import jax
    import jaxlib

    from magiattention_tpu import csrc, env
    from magiattention_tpu.utils.cost import generation_of_device_kind

    dev = jax.devices()[0]
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    planner = "native (csrc)" if csrc.get_lib() is not None else "python"
    _log(
        "device",
        f"platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(jax.devices())} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu}",
    )
    _log("device", f"compile cache: {cache_dir}; planner: {planner}")
    if dev.platform == "tpu":
        seen = generation_of_device_kind(dev.device_kind)
        if env.tpu_generation() != seen:
            raise AssertionError(
                f"env.tpu_generation()={env.tpu_generation()!r} but the "
                f"device is {dev.device_kind!r} ({seen})"
            )
        _log("device", f"env.tpu_generation()={seen!r} agrees")


def phase_kernel(
    *, total: int, parity_total: int, hq: int, hk: int, d: int,
    compiled: bool, seed: int,
) -> None:
    """flex_flash_attn_func forward and gradient on a packed varlen
    block-causal mask with the autotuner's own choice; parity at a
    length where the dense oracle fits."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from magiattention_tpu.ops import flex_flash_attn_func
    from magiattention_tpu.ops.flex_attn import auto_kernel_config
    from magiattention_tpu.testing.workloads import (
        ranges_of,
        varlen_block_causal,
    )

    def call_twice(what: str, fn, *args):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        res = jax.block_until_ready(fn(*args))
        _log(
            "kernel",
            f"{what}: first call {first:.2f} s (compile included), second "
            f"{(time.perf_counter() - t0) * 1e3:.2f} ms",
        )
        return res

    def run(t: int, oracle: bool):
        qr, kr, ts = ranges_of(varlen_block_causal(t))
        rng = np.random.default_rng([seed, t])
        q = _rand(rng, (t, hq, d), jnp.bfloat16)
        k = _rand(rng, (t, hk, d), jnp.bfloat16)
        v = _rand(rng, (t, hk, d), jnp.bfloat16)
        d_out = _rand(rng, (t, hq, d), jnp.float32)
        d_lse = _rand(rng, (t, hq), jnp.float32)
        cfg = auto_kernel_config(
            qr, kr, hq, hk, attn_type_map=ts, head_dim=d, dtype="bfloat16"
        )
        _log(
            "kernel",
            f"{t} tokens, {len(ts)} docs, {hq}Q/{hk}KV hd{d}: autotuner "
            f"chose (block_q, block_k, head_block, grid)={cfg}",
        )

        def fwd(q, k, v):
            return flex_flash_attn_func(q, k, v, qr, kr, ts)

        jfwd = jax.jit(fwd)
        jgrad = jax.jit(jax.grad(_cotangent_loss(fwd), argnums=(0, 1, 2)))
        _assert_compiled("kernel", jfwd.lower(q, k, v).as_text(), compiled)
        got = (
            *call_twice(f"{t} fwd", jfwd, q, k, v),
            *call_twice(f"{t} fwd+bwd", jgrad, q, k, v, d_out, d_lse),
        )
        _check_finite("kernel", got, q)
        if oracle:
            _check_parity(
                "kernel", got, _oracle(q, k, v, qr, kr, ts, d_out, d_lse)
            )

    if total != parity_total:
        run(total, oracle=False)
    run(parity_total, oracle=True)


def phase_api(
    *, total: int, hq: int, hk: int, d: int, chunk: int, n_docs: int,
    compiled: bool, seed: int, devices,
) -> None:
    """The keyed path a user writes: magi_attn_varlen_key -> dispatch ->
    calc_attn -> undispatch and its gradient, on a cp mesh over
    ``devices``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from magiattention_tpu.api import (
        calc_attn,
        dispatch,
        infer_attn_mask_from_cu_seqlens,
        magi_attn_varlen_key,
        undispatch,
    )

    mesh = Mesh(np.array(devices), ("cp",))
    rng = np.random.default_rng([seed, 7])
    cuts = np.sort(rng.choice(np.arange(1, total), n_docs - 1, replace=False))
    cu_seqlens = [0, *cuts.tolist(), total]
    t0 = time.perf_counter()
    key = magi_attn_varlen_key(
        cu_seqlens, total, mesh, num_heads=(hq, hk), head_dim=d,
        chunk_size=chunk,
    )
    _log(
        "api",
        f"{total} tokens, {n_docs} docs, cp={len(devices)}: key built in "
        f"{(time.perf_counter() - t0) * 1e3:.0f} ms",
    )
    q = _rand(rng, (total, hq, d), jnp.bfloat16)
    k = _rand(rng, (total, hk, d), jnp.bfloat16)
    v = _rand(rng, (total, hk, d), jnp.bfloat16)
    d_out = _rand(rng, (total, hq, d), jnp.float32)
    d_lse = _rand(rng, (total, hq), jnp.float32)

    def fwd(q, k, v):
        out_d, meta = calc_attn(
            dispatch(q, key), dispatch(k, key), dispatch(v, key), key
        )
        return undispatch(out_d, key), undispatch(meta.lse, key)

    jfwd = jax.jit(fwd)
    _assert_compiled("api", jfwd.lower(q, k, v).as_text(), compiled)
    out, lse = jfwd(q, k, v)
    grads = jax.jit(jax.grad(_cotangent_loss(fwd), argnums=(0, 1, 2)))(
        q, k, v, d_out, d_lse
    )
    _check_finite("api", (out, lse, *grads), q)
    qr, kr, ts = infer_attn_mask_from_cu_seqlens(cu_seqlens)
    _check_parity(
        "api", (out, lse, *grads),
        _oracle(q, k, v, qr, kr, [int(t) for t in ts], d_out, d_lse),
    )


def _train_args(widths: dict, *, layers: int, total: int, chunk: int,
                cp: int, masks: int, steps: int, dtype: str, seed: int):
    """examples/train_llama.py's parsed command line for these sizes."""
    from examples import train_llama

    argv = [
        "--cp", str(cp), "--dp", "1", "--layers", str(layers),
        "--total", str(total), "--chunk", str(chunk), "--remat",
        "--masks", str(masks), "--steps", str(steps), "--dtype", dtype,
        "--seed", str(seed),
    ]
    for name, value in widths.items():
        argv += [f"--{name}", str(value)]
    return train_llama.parse_args(argv), argv


def _run_trainer(phase: str, devices, *, compiled: bool, **sizes):
    """Take the steps ``examples/train_llama.py`` takes from this
    command line (the same code) and hold its records to: every step
    ran, losses finite, compiled kernels in the step."""
    import math

    from examples import train_llama

    args, argv = _train_args(**sizes)
    _log(phase, "python examples/train_llama.py " + " ".join(argv))
    if args.layers != PUBLISHED_LAYERS:
        _log(phase, f"reduced: layers {PUBLISHED_LAYERS} -> {args.layers}")
    records = train_llama.train(args, devices)
    if len(records) != args.masks * args.steps:
        raise AssertionError(
            f"{phase}: {len(records)} steps ran, wanted "
            f"{args.masks * args.steps}"
        )
    for r in records:
        if not math.isfinite(r["loss"]):
            raise AssertionError(f"{phase}: step {r['step']} loss {r['loss']}")
        if r["interpret"] == compiled:
            raise AssertionError(
                f"{phase}: step {r['step']} ran interpret={r['interpret']}"
            )
    firsts = [r for r in records if r["compile_s"]]
    if compiled and not all(r["kernel_calls"] for r in firsts):
        raise AssertionError(f"{phase}: no tpu_custom_call in the step")
    _log(
        phase,
        f"{len(firsts)} new masks cost {len(firsts)} compiles: "
        + ", ".join(
            f"mask {r['mask']} plan {r['plan_ms']:.0f} ms + compile "
            f"{r['compile_s']:.1f} s"
            for r in firsts
        ),
    )
    return records


def phase_train(
    *, widths: dict, layers: int, total: int, chunk: int, masks: int,
    steps: int, parity_total: int, dtype: str, compiled: bool, seed: int,
) -> None:
    """The path examples/train_llama.py runs, then the forward loss
    against the jnp_online kernel backend on the same model and weights
    at ``parity_total`` tokens."""
    sizes = dict(
        widths=widths, layers=layers, chunk=chunk, cp=1, dtype=dtype,
        seed=seed,
    )
    _run_trainer(
        "train", None, compiled=compiled, total=total, masks=masks,
        steps=steps, **sizes,
    )
    _loss_parity(_train_args(total=parity_total, masks=1, steps=1, **sizes)[0])


def _loss_parity(args) -> None:
    """Forward loss of one model and weights under the pallas and the
    jnp_online kernel backends."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from examples import train_llama
    from magiattention_tpu.analysis.trace_audit import _pinned_env
    from magiattention_tpu.models import build_magi_llama, init_params
    from magiattention_tpu.parallel import dispatch, roll

    cfg = train_llama.llama_config(args)
    mesh = train_llama.make_mesh(args, jax.devices())
    qr, kr, ts = train_llama.packed_mask(args, 0)
    model, meta = build_magi_llama(
        cfg, mesh, args.total, qr, kr, ts, chunk_size=args.chunk
    )
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    rng = np.random.default_rng([args.seed, 99])
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (1, args.total)), jnp.int32
    )
    tokens = jax.vmap(lambda x: dispatch(x, meta))(tokens)
    labels = roll(tokens, meta, -1, axis=1)
    pos = jnp.asarray(meta.perm_idx)[None]
    tables = model.sharded_tables()
    losses = {}
    for backend in ("pallas", "jnp_online"):
        # the backend is read while tracing: a fresh jit each
        with _pinned_env("MAGI_ATTENTION_KERNEL_BACKEND", backend):
            losses[backend] = float(
                jax.jit(model.loss_fn)(params, tokens, labels, pos, tables)
            )
    a, b = losses["pallas"], losses["jnp_online"]
    rel = abs(a - b) / abs(b)
    _log(
        "train",
        f"forward loss at {args.total} tokens: pallas {a:.5f} vs "
        f"jnp_online {b:.5f} (rel {rel:.2e}, tolerance {LOSS_REL_TOL:g})",
    )
    if not rel <= LOSS_REL_TOL:
        raise AssertionError(f"train: loss parity {rel:.3e} > {LOSS_REL_TOL}")


def phase_serve(
    *, prompts, gen: int, hq: int, hk: int, d: int, pool_tokens: int,
    chunk: int, compiled: bool, seed: int,
) -> None:
    """api.ServingEngine + api.Scheduler under default flags answer a
    few requests through the prefill and decode kernels; every decode
    output is held to the dense oracle over prompt + decoded KV."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from magiattention_tpu import env
    from magiattention_tpu.api import (
        DecodeBatch,
        Request,
        Scheduler,
        ServingEngine,
        magi_attn_decode,
    )
    from magiattention_tpu.common.enum import AttnMaskType
    from magiattention_tpu.testing import calc_rel_err, ref_attn_from_ranges

    n = len(prompts)
    engine = ServingEngine(
        num_pages=pool_tokens // env.page_size(),
        num_kv_heads=hk,
        head_dim=d,
        max_seqs=2 * n,
    )
    # every chunk keeps its full width whatever the decode batch took,
    # so aligned (start, chunk) prefill programs are shared by requests
    sched = Scheduler(engine, token_budget=n * chunk + n, chunk=chunk)
    rng = np.random.default_rng([seed, 11])
    reqs = [
        Request(
            rid=i,
            prompt_q=_rand(rng, (p, hq, d), jnp.bfloat16),
            prompt_k=_rand(rng, (p, hk, d), jnp.bfloat16),
            prompt_v=_rand(rng, (p, hk, d), jnp.bfloat16),
            decode_q=_rand(rng, (gen, hq, d), jnp.bfloat16),
            decode_k=_rand(rng, (gen, hk, d), jnp.bfloat16),
            decode_v=_rand(rng, (gen, hk, d), jnp.bfloat16),
        )
        for i, p in enumerate(prompts)
    ]
    for r in reqs:
        sched.submit(r)
    t0 = time.perf_counter()
    reports = sched.run()
    for r in reqs:
        jax.block_until_ready(sched.result(r.rid).decode_outs)
    wall = time.perf_counter() - t0
    launches = sum(
        int(rep.decode_batch > 0) + sum(1 for _rid, m in rep.prefill_chunks if m)
        for rep in reports
    )
    _log(
        "serve",
        f"{n} requests (prompts {list(prompts)}, {gen} decode tokens each, "
        f"{hq}Q/{hk}KV hd{d}, page {env.page_size()}, pool {pool_tokens} "
        f"tokens): {sum(prompts)} prefill + {n * gen} decode tokens, "
        f"{len(reports)} ticks, {launches} launches, {wall:.1f} s wall "
        "(compiles included)",
    )
    worst = 0.0
    with jax.default_matmul_precision("highest"):
        for r in reqs:
            outs = sched.result(r.rid).decode_outs
            if len(outs) != gen:
                raise AssertionError(
                    f"serve: request {r.rid} decoded {len(outs)}/{gen}"
                )
            # decode row i sees the prompt and decoded keys 0..i
            p = r.prompt_len
            f32 = jnp.float32
            ref, _, _ = ref_attn_from_ranges(
                r.decode_q.astype(f32),
                jnp.concatenate([r.prompt_k, r.decode_k]).astype(f32),
                jnp.concatenate([r.prompt_v, r.decode_v]).astype(f32),
                [(0, gen)], [(0, p + gen)], [int(AttnMaskType.CAUSAL)],
            )
            got = jnp.stack(outs).astype(f32)
            if not np.isfinite(np.asarray(got)).all():
                raise AssertionError(f"serve: request {r.rid} not finite")
            worst = max(worst, calc_rel_err(got, ref))
    _log(
        "serve",
        f"decode outputs vs dense oracle: worst rel-L2 {worst:.2e} "
        f"(tolerance {REL_L2_TOL:g})",
    )
    if not worst <= REL_L2_TOL:
        raise AssertionError(f"serve: decode parity {worst:.3e}")
    # the engine took every default: show which decode program that is
    text = (
        jax.jit(magi_attn_decode)
        .lower(reqs[0].decode_q[:1], engine.cache, DecodeBatch.of([0]))
        .as_text()
    )
    _assert_compiled("serve", text, compiled)


def phase_cp(
    *, widths: dict, layers: int, total: int, chunk: int, masks: int,
    steps: int, dtype: str, compiled: bool, seed: int, devices,
) -> None:
    """The train phase at cp=len(devices) over ``devices``, then what it
    is compared with: the same seed, masks and tokens at cp=1 on one of
    them, in this process."""
    cp = len(devices)
    # cp first: a device's peak_bytes_in_use never comes down again, so
    # read the sharded run's figures before one chip holds a whole step
    runs = {
        n: _run_trainer(
            f"cp{n}", devices[:n], compiled=compiled, widths=widths,
            layers=layers, total=total, chunk=chunk, cp=n, masks=masks,
            steps=steps, dtype=dtype, seed=seed,
        )
        for n in (cp, 1)
    }
    for a, b in zip(runs[cp], runs[1]):
        rel = abs(a["loss"] - b["loss"]) / abs(b["loss"])
        _log(
            f"cp{cp}",
            f"step {a['step']}: loss cp={cp} {a['loss']:.5f} vs cp=1 "
            f"{b['loss']:.5f} (rel {rel:.2e}, tolerance {LOSS_REL_TOL:g})",
        )
        if not rel <= LOSS_REL_TOL:
            raise AssertionError(f"cp{cp}: step {a['step']} losses differ")
    for r in runs[cp]:
        if not r["compile_s"]:
            continue
        # the collectives the plan scheduled are in the compiled step
        op = {"a2a": "all-to-all", "hops": "collective-permute"}[
            r["comm_impl"]
        ]
        _log(
            f"cp{cp}",
            f"mask {r['mask']}: plan scheduled {r['comm_impl']} "
            f"({r['remote_rows']} remote rows/rank), compiled step has "
            f"{r['collectives']}",
        )
        if any(r["remote_rows"]) and not r["collectives"].get(op):
            raise AssertionError(f"cp{cp}: no {op} in the compiled step")
    peaks = runs[cp][-1]["peak_bytes"]
    _log(
        f"cp{cp}",
        f"peak_bytes_in_use per chip: {peaks}; the step's temp bytes per "
        f"chip: cp={cp} {runs[cp][0]['program_bytes']['temp']} vs cp=1 "
        f"{runs[1][0]['program_bytes']['temp']}",
    )
    if compiled and not (
        all(peaks) and max(peaks) <= 1.25 * min(peaks)
    ):
        raise AssertionError(
            f"cp{cp}: per-chip memory is not comparable: {peaks}"
        )


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def fallbacks_fired() -> list[str]:
    """Library fallbacks that may stay as behaviour but fail the smoke:
    a plan build degraded (``magi_degraded_path``) or an autotune
    candidate crashed (``record_autotune_measure_failure``)."""
    from magiattention_tpu import telemetry

    snap = telemetry.snapshot()
    fired = [k for k in snap["gauges"] if k.startswith("magi_degraded_path")]
    fired += [
        k
        for k, n in snap["counters"].items()
        if k.startswith("magi_autotune_measure_failures_total") and n
    ]
    return fired


PHASES = ("device", "kernel", "api", "train", "serve")


def run(args, result: dict) -> None:
    """Every requested phase, in order; raises on the first failure.
    ``result["device"]`` is filled as soon as jax has said what it is."""
    import jax

    from magiattention_tpu import telemetry
    from magiattention_tpu.benchmarking import enable_compile_cache

    cache_dir = enable_compile_cache()  # before the first jit
    events = _CacheEvents()
    device = jax.devices()[0]
    result["device"] = {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": args.chips,  # the chips this run uses
    }
    if device.platform != "tpu":
        raise RuntimeError(
            f"chip_smoke needs a TPU; jax came up on {device.platform!r}"
        )
    if len(jax.devices()) < args.chips:
        raise RuntimeError(
            f"--chips {args.chips} but jax offers {len(jax.devices())}"
        )
    telemetry.set_enabled(True)  # the fallback counters record only then
    hq, hk, d = (TINYLLAMA[n] for n in ("heads", "kv-heads", "head-dim"))
    train = dict(
        widths=TINYLLAMA, layers=TRAIN_LAYERS, total=TRAIN_TOKENS,
        chunk=512, steps=3, dtype="bfloat16", compiled=True, seed=args.seed,
    )
    if args.chips > 1:
        phases = {
            "cp": lambda: phase_cp(
                **train, masks=2, devices=jax.devices()[: args.chips]
            ),
        }
    else:
        phases = {
            "device": lambda: phase_device(cache_dir),
            "kernel": lambda: phase_kernel(
                total=16384, parity_total=4096, hq=hq, hk=hk, d=d,
                compiled=True, seed=args.seed,
            ),
            "api": lambda: phase_api(
                total=4096, hq=hq, hk=hk, d=d, chunk=512, n_docs=6,
                compiled=True, seed=args.seed, devices=jax.devices()[:1],
            ),
            "train": lambda: phase_train(
                **train, masks=2, parity_total=4096
            ),
            "serve": lambda: phase_serve(
                prompts=(2048, 4096, 6144, 8192), gen=32, hq=hq, hk=hk, d=d,
                pool_tokens=131072, chunk=2048, compiled=True,
                seed=args.seed,
            ),
        }
    wanted = args.phases.split(",") if args.phases else list(phases)
    for name in wanted:
        t0 = time.perf_counter()
        hits, writes = events.hits, events.writes
        phases[name]()
        _log(
            name,
            f"passed in {time.perf_counter() - t0:.1f} s (persistent "
            f"compile cache: {events.hits - hits} hits, "
            f"{events.writes - writes} writes)",
        )
    fired = fallbacks_fired()
    if fired:
        raise RuntimeError(f"library fallbacks fired: {fired}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument(
        "--chips", type=int, default=1, choices=(1, 4),
        help="4: only the train phase at cp=4 and its cp=1 comparison",
    )
    p.add_argument("--seed", type=int, default=0, help="weights, masks, data")
    p.add_argument(
        "--phases", default="",
        help=f"comma-separated subset of {','.join(PHASES)} (default all)",
    )
    args = p.parse_args(argv)
    result = {"ok": False, "device": None}
    try:
        run(args, result)
        result["ok"] = True
    finally:  # no phase's exception is caught: it still ends the process
        print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
