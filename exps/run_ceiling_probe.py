"""Matmul-ceiling probe: measured bf16 MXU throughput on this chip.

An MFU% should divide a kernel's achieved TFLOPs/s by a *measured*
matmul ceiling next to the nameplate (v5e bf16: 197 TFLOPs/s). This
script is the provenance for that ceiling: a bf16 matmul sweep over
square and attention-shaped operands, printing TFLOPs/s per shape and the
max — what dense matmul actually sustains on the chip it runs on. Role of
the reference's explicit peak constants in
``magi_attention/testing/precision.py:40-51`` (it hardcodes per-GPU
peaks).

Run on a real TPU:  python exps/run_ceiling_probe.py [--dtype bfloat16]
Appends nothing.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# Square rungs find the chip's dense ceiling. The skinny shapes mirror
# attention's MXU diet without materializing a 64k x 64k score matrix
# (the original (65536, 128, 65536) probe OOM'd the 16 GB chip: its
# bf16 output alone is 8.6 GB, plus do_bench's live result copies —
# attention never materializes that, so the probe must not either):
# contraction-128 for QK^T, output-128 for PV, both capped so every
# operand/output stays ~1 GB.
SHAPES = [
    (2048, 2048, 2048),
    (4096, 4096, 4096),
    (8192, 8192, 8192),
    (16384, 8192, 8192),
    (65536, 128, 8192),  # QK^T-shaped: d=128 contraction
    (65536, 8192, 128),  # PV-shaped: d=128 output width
]

# One grid step of the 64k kernel at the (256, 1024) rung, batched over
# tiles: what the fwd kernel's two dots actually look like to the MXU.
TILE_BATCH = 512  # 512 tiles x (256x128 @ 128x1024) = 34 GFLOP/call


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--json", action="store_true", help="one JSON line only")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from magiattention_tpu.benchmarking import do_bench, enable_compile_cache

    enable_compile_cache()

    dev = jax.devices()[0]
    dtype = jnp.dtype(args.dtype)
    rng = np.random.default_rng(0)
    rows = []
    best = 0.0

    def probe(label, flops, make):
        """``make`` allocates operands AND runs: allocation-time OOM on a
        fragmented/16 GB chip must land in the same per-rung guard as
        execution-time OOM, or one bad rung loses the whole window's rows."""
        nonlocal best
        try:
            res = make()
        except Exception as e:  # one OOM'd rung must not kill the probe
            msg = f"{type(e).__name__}: {str(e)[:200]}"
            rows.append({"shape": label, "error": msg})
            if not args.json:
                print(f"[{label}]  FAILED: {msg}")
            return
        tf = res.tflops(flops)
        best = max(best, tf)
        rows.append({"shape": label, "ms": round(res.median_ms, 3),
                     "tflops": round(tf, 2)})
        if not args.json:
            print(f"[{label}]  {res.median_ms:8.3f} ms  {tf:7.2f} TFLOPs/s")

    def mm_rung(m, k, n):
        a = jnp.asarray(rng.standard_normal((m, k)), dtype)
        b = jnp.asarray(rng.standard_normal((k, n)), dtype)
        return do_bench(jax.jit(lambda a, b: a @ b), a, b)

    for m, k, n in SHAPES:
        probe(f"{m}x{k}x{n}", 2 * m * k * n, lambda m=m, k=k, n=n: mm_rung(m, k, n))

    # --- chained rungs: ITERS serial matmuls inside ONE jitted fori_loop
    # divide the fixed per-dispatch host cost away; these rungs are the
    # real MFU denominator.
    CHAIN_ITERS = 16

    from magiattention_tpu.benchmarking import chained_ms

    def chained_square(n):
        """(y, b) -> (y @ b, b), square: one dispatch, CHAIN_ITERS serial
        matmuls (b rides the carry, not a closure — HLO-literal limit)."""
        def make():
            b = jnp.asarray(rng.standard_normal((n, n)), dtype)
            y0 = jnp.asarray(rng.standard_normal((n, n)), dtype)
            return chained_ms(
                lambda c: ((c[0] @ c[1]).astype(dtype), c[1]),
                (y0, b),
                iters=CHAIN_ITERS,
            )
        return make

    def chained_attn_pair(t, d, w):
        """y (t,d) -> y @ B (t,w: the QK^T diet) -> @ C (t,d: the PV diet);
        both matmuls per step, exactly attention's alternating MXU shapes."""
        def make():
            B = jnp.asarray(rng.standard_normal((d, w)), dtype)
            C = jnp.asarray(rng.standard_normal((w, d)), dtype)
            y0 = jnp.asarray(rng.standard_normal((t, d)), dtype)
            return chained_ms(
                lambda c: (((c[0] @ c[1]) @ c[2]).astype(dtype), c[1], c[2]),
                (y0, B, C),
                iters=CHAIN_ITERS,
            )
        return make

    def probe_chained(label, flops, make):
        nonlocal best
        try:
            ms = make()
        except Exception as e:
            rows.append({"shape": label, "error":
                         f"{type(e).__name__}: {str(e)[:200]}"})
            if not args.json:
                print(f"[{label}]  FAILED: {type(e).__name__}")
            return
        tf = flops / (ms * 1e-3) / 1e12
        best = max(best, tf)
        rows.append({"shape": label, "ms": round(ms, 3),
                     "tflops": round(tf, 2), "chained": True})
        if not args.json:
            print(f"[{label}]  {ms:8.3f} ms  {tf:7.2f} TFLOPs/s  (chained)")

    for n in (4096, 8192):
        probe_chained(f"chained_{n}x{n}x{n}", 2 * n**3, chained_square(n))
    probe_chained(
        "chained_qkpv_65536x128<->8192",
        2 * 2 * 65536 * 128 * 8192,
        chained_attn_pair(65536, 128, 8192),
    )

    # batched kernel-tile shape (see TILE_BATCH note above)
    bq, d, bk = 256, 128, 1024

    def tile_rung():
        a = jnp.asarray(rng.standard_normal((TILE_BATCH, bq, d)), dtype)
        b = jnp.asarray(rng.standard_normal((TILE_BATCH, d, bk)), dtype)
        return do_bench(jax.jit(jnp.matmul), a, b)

    probe(
        f"tile_{TILE_BATCH}x({bq}x{d}@{d}x{bk})",
        2 * TILE_BATCH * bq * d * bk,
        tile_rung,
    )
    payload = {
        "device": str(dev),
        "dtype": str(dtype),
        # null, never 0.0: a run in which every rung failed must not
        # hand a reader a zero MFU denominator with rc=0
        "ceiling_tflops": round(best, 2) if best > 0 else None,
        "rows": rows,
        "recorded_unix": int(time.time()),
    }
    print(json.dumps(payload))
    if best == 0.0:
        sys.exit(1)  # no rung succeeded: surface failure to the agenda log


if __name__ == "__main__":
    main()
