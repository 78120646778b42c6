"""Decode micro-benchmark: split-KV paged decode throughput.

Measures steady-state continuous-batching decode — tokens/s and
effective KV bandwidth — for a grid of (batch, kv_len, splits) on the
current backend. Runs anywhere: on CPU it uses the jnp reference backend
(numbers are shape-relative, not chip-representative); on TPU the Pallas
kernel. ``bench.py`` embeds a one-line summary of the headline config in
its telemetry block.

Usage::

    python exps/run_decode_bench.py [--json] [--quick]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

HQ, HK, D = 8, 8, 128


def probe_page_size(on_tpu: bool) -> int:
    """The probe's page size: one lane tile on TPU, small on CPU sims."""
    return 128 if on_tpu else 16


def quick_probe_config(on_tpu: bool) -> tuple[int, int, int, int]:
    """The headline (batch, kv_len, page_size, splits) probe — ONE
    definition shared by ``--quick`` and bench.py's decode summary line,
    so the two always report the same workload."""
    ps = probe_page_size(on_tpu)
    return (8, 8 * ps, ps, 2)


def bench_one(
    batch: int,
    kv_len: int,
    page_size: int,
    num_splits: int,
    *,
    reps: int = 20,
    dtype=jnp.bfloat16,
) -> dict:
    """Steady-state decode step time for one configuration."""
    from magiattention_tpu.serving import (
        DecodeBatch,
        append_kv,
        assign_block_table,
        magi_attn_decode,
        make_paged_kv_cache,
        write_prefill_kv,
    )

    # one page of headroom past the prefill: the timed step APPENDS a
    # token, and a table sized to exactly kv_len would saturate the
    # write (silently dropped) — the bench must measure the real step
    mpp = -(-kv_len // page_size) + 1
    while mpp % num_splits:
        mpp += 1  # splits must divide the table width
    cache = make_paged_kv_cache(
        batch * mpp + 1, page_size, HK, D,
        max_seqs=batch, max_pages_per_seq=mpp, dtype=dtype,
    )
    rng = np.random.default_rng(0)
    for b in range(batch):
        cache = assign_block_table(
            cache, b, list(range(1 + b * mpp, 1 + (b + 1) * mpp))
        )
        kv = jnp.asarray(
            rng.standard_normal((kv_len, HK, D)), dtype
        )
        cache = write_prefill_kv(cache, b, kv, kv)
    slots = jnp.arange(batch, dtype=jnp.int32)
    q = jnp.asarray(rng.standard_normal((batch, HQ, D)), dtype)
    kn = jnp.asarray(rng.standard_normal((batch, HK, D)), dtype)

    @jax.jit
    def step(q, cache):
        cache = append_kv(cache, slots, kn, kn)
        out, _ = magi_attn_decode(
            q, cache, DecodeBatch(slots), num_splits=num_splits
        )
        return out, cache

    jax.block_until_ready(step(q, cache))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out, _ = step(q, cache)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / reps
    kv_bytes = 2 * batch * kv_len * HK * D * jnp.dtype(dtype).itemsize
    return {
        "batch": batch,
        "kv_len": kv_len,
        "page_size": page_size,
        "num_splits": num_splits,
        "step_ms": dt * 1e3,
        "tokens_per_s": batch / dt,
        "kv_gbps": kv_bytes / dt / 1e9,
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="one small config (the bench.py summary probe)")
    args = ap.parse_args()

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu:
        os.environ.setdefault("MAGI_ATTENTION_KERNEL_BACKEND", "jnp")
    page_size = probe_page_size(on_tpu)
    if args.quick:
        b, kv, ps, s = quick_probe_config(on_tpu)
        grid = [(b, kv, s)]
        reps = 5
    else:
        grid = [
            (b, n * page_size, s)
            for b in (1, 8, 32)
            for n in (8, 32)
            for s in (1, 2, 4)
        ]
        reps = 20
    rows = []
    for batch, kv_len, splits in grid:
        r = bench_one(batch, kv_len, page_size, splits, reps=reps)
        rows.append(r)
        if not args.json:
            print(
                f"batch {r['batch']:>3}  kv {r['kv_len']:>6}  "
                f"splits {r['num_splits']}  step {r['step_ms']:8.3f} ms  "
                f"{r['tokens_per_s']:10.1f} tok/s  "
                f"{r['kv_gbps']:7.2f} GB/s KV",
                file=sys.stderr if args.quick else sys.stdout,
            )
    if args.json:
        print(json.dumps({
            "backend": jax.default_backend(),
            "kernel_backend": os.environ.get(
                "MAGI_ATTENTION_KERNEL_BACKEND", "pallas"
            ),
            "rows": rows,
        }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
