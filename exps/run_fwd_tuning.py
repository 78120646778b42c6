"""On-chip block-config tuning for the flex kernel, launch-floor corrected.

A per-call timing carries the fixed host cost of one dispatch. This
harness times kernels two ways:

  raw      — one dispatch per call (the bench.py/_timeit convention)
  chained  — ITERS applications inside ONE jitted lax.fori_loop via
             :func:`magiattention_tpu.benchmarking.chained_ms` (the
             (q, k, v) triple IS the carry: fwd chains (out, k, v), bwd
             chains all three grads so no backward kernel is DCE'd), so
             the dispatch floor divides by ITERS and the quotient is
             true kernel time

Sweeps (block_q, block_k, head_block) for the cases the round-5 bench
flagged:
  * dense-causal 64k fwd — ours 64.2 TF/s raw vs tuned stock flash 100.1:
    the gap to close (VERDICT r4 item 2)
  * dense-causal 64k fwd+bwd — bwd rung choice
  * 16k varlen-block-causal fwd — the >=16k extent threshold (126d1ed)
    forces wide rungs onto a mask whose documents are ~1k tokens; the
    sweep decides the selection fix

Usage: python exps/run_fwd_tuning.py [--case dense64k|varlen16k|bwd64k|all]
                                     [--iters 8] [--out FILE.jsonl]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ITERS_DEFAULT = 8
_OUT_PATH = None


def persist(row):
    """Append-as-you-go: an interrupted sweep keeps completed rows."""
    if _OUT_PATH:
        with open(_OUT_PATH, "a") as f:
            f.write(json.dumps(row) + "\n")


def _time_raw(fn, q, k, v, n=3, batches=3):
    import jax

    jax.block_until_ready(fn(q, k, v))
    outs = []
    for _ in range(batches):
        t0 = time.perf_counter()
        for _ in range(n):
            r = fn(q, k, v)
        jax.block_until_ready(r)
        outs.append((time.perf_counter() - t0) / n)
    outs.sort()
    return outs[len(outs) // 2]


def _qkv(t, hq, hk, d, rng):
    import jax.numpy as jnp

    return (
        jnp.asarray(rng.standard_normal((t, hq, d)), jnp.bfloat16),
        jnp.asarray(rng.standard_normal((t, hk, d)), jnp.bfloat16),
        jnp.asarray(rng.standard_normal((t, hk, d)), jnp.bfloat16),
    )


def sweep_case(name, t, qr, kr, ts, area, configs, rows, iters, grad=False):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from magiattention_tpu.benchmarking import chained_ms
    from magiattention_tpu.ops import flex_flash_attn_func

    rng = np.random.default_rng(0)
    hq = hk = 8
    d = 128
    q, k, v = _qkv(t, hq, hk, d, rng)
    flops = 4 * area * hq * d
    if grad:
        flops = 3.5 * flops  # fwd + 2.5x bwd convention
    for bq, bk, hb in configs:
        label = f"{name} ({bq},{bk},hb{hb})"

        def attn(qq, kk, vv, bq=bq, bk=bk, hb=hb):
            return flex_flash_attn_func(
                qq, kk, vv, qr, kr, ts, block_q=bq, block_k=bk, head_block=hb
            )[0]

        if grad:
            gradf = jax.grad(
                lambda qq, kk, vv: attn(qq, kk, vv)
                .astype(jnp.float32)
                .sum(),
                argnums=(0, 1, 2),
            )

            def step3(c, g=gradf):
                # all three grads ride the carry: the dkv kernel must not
                # be DCE'd out of the timed loop
                return tuple(
                    gg.astype(x.dtype) for gg, x in zip(g(*c), c)
                )

            def raw_fn(qq, kk, vv, g=gradf):
                return g(qq, kk, vv)
        else:

            def step3(c, a=attn):
                return (a(*c), c[1], c[2])

            raw_fn = attn
        try:
            dt_raw = _time_raw(jax.jit(raw_fn), q, k, v)
            dt_ch = chained_ms(step3, (q, k, v), iters=iters) * 1e-3
        except Exception as e:
            print(f"[{label}] FAILED: {type(e).__name__}: {str(e)[:160]}",
                  flush=True)
            row = {"case": name, "cfg": [bq, bk, hb],
                   "error": f"{type(e).__name__}: {str(e)[:200]}"}
            rows.append(row)
            persist(row)
            continue
        row = {
            "case": name,
            "cfg": [bq, bk, hb],
            "raw_ms": round(dt_raw * 1e3, 3),
            "raw_tflops": round(flops / dt_raw / 1e12, 2),
            "chained_ms": round(dt_ch * 1e3, 3),
            "chained_tflops": round(flops / dt_ch / 1e12, 2),
        }
        rows.append(row)
        persist(row)
        print(
            f"[{label}] raw {row['raw_ms']:9.3f} ms {row['raw_tflops']:7.2f}"
            f" TF/s | chained {row['chained_ms']:9.3f} ms "
            f"{row['chained_tflops']:7.2f} TF/s",
            flush=True,
        )


def stock_control(rows, iters, grad=False):
    """Tuned stock flash, raw + chained, same conventions."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes,
        flash_attention,
    )

    from magiattention_tpu.benchmarking import chained_ms

    t = 65536
    hq = 8
    d = 128
    rng = np.random.default_rng(0)
    q, k, v = _qkv(t, hq, hq, d, rng)
    area = t * (t + 1) // 2
    flops = 4 * area * hq * d
    if grad:
        flops = 3.5 * flops
    qb = q.transpose(1, 0, 2)[None]  # [1, h, t, d]
    kb = k.transpose(1, 0, 2)[None]
    vb = v.transpose(1, 0, 2)[None]
    case = "stock64k_fwdbwd" if grad else "stock64k"
    for bq, bk in ((512, 1024), (1024, 1024), (1024, 2048)):
        bs = BlockSizes(
            block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
            block_q_major_dkv=bq, block_k_major_dkv=bk,
            block_q_dkv=bq, block_k_dkv=bk,
            block_q_dq=bq, block_k_dq=bk, block_k_major_dq=bk,
        )

        def fwd(qq, kk, vv, bs=bs):
            return flash_attention(qq, kk, vv, causal=True, block_sizes=bs)

        if grad:
            gradf = jax.grad(
                lambda qq, kk, vv: fwd(qq, kk, vv)
                .astype(jnp.float32)
                .sum(),
                argnums=(0, 1, 2),
            )

            def step3(c, g=gradf):
                return tuple(
                    gg.astype(x.dtype) for gg, x in zip(g(*c), c)
                )

            raw_fn = gradf
        else:

            def step3(c, f=fwd):
                return (f(*c), c[1], c[2])

            raw_fn = fwd
        try:
            dt_raw = _time_raw(jax.jit(raw_fn), qb, kb, vb)
            dt_ch = chained_ms(step3, (qb, kb, vb), iters=iters) * 1e-3
        except Exception as e:
            print(f"[{case} ({bq},{bk})] FAILED: {type(e).__name__}: "
                  f"{str(e)[:160]}", flush=True)
            row = {"case": case, "cfg": [bq, bk],
                   "error": f"{type(e).__name__}: {str(e)[:200]}"}
            rows.append(row)
            persist(row)
            continue
        row = {
            "case": case,
            "cfg": [bq, bk],
            "raw_ms": round(dt_raw * 1e3, 3),
            "raw_tflops": round(flops / dt_raw / 1e12, 2),
            "chained_ms": round(dt_ch * 1e3, 3),
            "chained_tflops": round(flops / dt_ch / 1e12, 2),
        }
        rows.append(row)
        persist(row)
        print(
            f"[{case} ({bq},{bk})] raw {row['raw_ms']:9.3f} ms "
            f"{row['raw_tflops']:7.2f} TF/s | chained "
            f"{row['chained_ms']:9.3f} ms {row['chained_tflops']:7.2f} TF/s",
            flush=True,
        )


def main():
    global _OUT_PATH
    p = argparse.ArgumentParser()
    p.add_argument("--case", default="all",
                   choices=["dense64k", "varlen16k", "bwd64k", "stock",
                            "stockbwd", "all"])
    p.add_argument("--iters", type=int, default=ITERS_DEFAULT)
    p.add_argument("--out", default=None)
    args = p.parse_args()
    if args.out:
        _OUT_PATH = args.out
        open(_OUT_PATH, "w").close()  # fresh file, then append per row

    from magiattention_tpu.benchmarking import enable_compile_cache

    enable_compile_cache()

    rows = []
    if args.case in ("dense64k", "all"):
        t = 65536
        qr, kr, ts = [(0, t)], [(0, t)], [1]
        area = t * (t + 1) // 2
        sweep_case(
            "dense64k_fwd", t, qr, kr, ts, area,
            [
                (512, 2048, 1),   # current auto choice
                (1024, 1024, 1),
                (512, 1024, 1),
                # (1024,2048)/(2048,1024) were refused by the compiler
                # in the July sweep — not in the matrix
                (1024, 512, 1),
            ],
            rows, args.iters,
        )
    if args.case in ("stock", "all"):
        stock_control(rows, args.iters)
    if args.case in ("stockbwd", "all"):
        stock_control(rows, max(args.iters // 2, 2), grad=True)
    if args.case in ("bwd64k", "all"):
        t = 65536
        qr, kr, ts = [(0, t)], [(0, t)], [1]
        area = t * (t + 1) // 2
        sweep_case(
            "dense64k_fwdbwd", t, qr, kr, ts, area,
            [(512, 2048, 1), (1024, 1024, 1), (512, 1024, 1)],
            rows, max(args.iters // 2, 2), grad=True,
        )
    if args.case in ("varlen16k", "all"):
        from magiattention_tpu.common.mask import total_area as slices_area
        from magiattention_tpu.common.ranges import AttnRanges
        from magiattention_tpu.testing.workloads import varlen_block_causal

        t = 16384
        slices = varlen_block_causal(t)
        qr = [(int(s[0]), int(s[1])) for s in slices]
        kr = [(int(s[2]), int(s[3])) for s in slices]
        ts = [int(s[4]) for s in slices]
        area = slices_area(
            AttnRanges.from_ranges(qr), AttnRanges.from_ranges(kr), ts
        )
        sweep_case(
            "varlen16k_fwd", t, qr, kr, ts, area,
            [
                (128, 512, 8),    # the pre-126d1ed (round-2) choice
                (256, 512, 4),
                (256, 1024, 2),   # current auto choice at 16k extent
                (512, 2048, 1),
                (128, 512, 1),    # isolates head-batching from blocking
            ],
            rows, args.iters,
        )
    print(f"{len(rows)} rows" + (f" -> {_OUT_PATH}" if _OUT_PATH else ""))


if __name__ == "__main__":
    main()
