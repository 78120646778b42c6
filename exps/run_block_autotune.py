"""On-chip autotune sweep for the kernel block-candidate table.

`ops/flex_attn._AUTO_BLOCK_CONFIGS` is now the CANDIDATE SET (and
tie-break preference) of the plan-aware autotuner (`tuning/`,
docs/autotune.md) — per-workload selection happens through the cost
model / measure-mode cache, not a static lookup. This harness re-derives
the candidate table empirically: for each mask family and seqlen it
times fwd and fwd+bwd across candidate rungs and prints the winners, so
recalibrating after a kernel change is one command on the chip (one
TPU process at a time). Feed the
results three ways:

- update `_AUTO_BLOCK_CONFIGS` (candidates + preference order),
- recalibrate the cost-model constants and refresh the drift guard
  (`python exps/run_autotune_check.py --update`),
- or skip the table entirely: run production workloads once under
  ``MAGI_ATTENTION_AUTOTUNE=measure`` with
  ``MAGI_ATTENTION_AUTOTUNE_CACHE_DIR`` set and let the persistent
  tuning cache pin the measured winners per workload fingerprint.

    python exps/run_block_autotune.py --seqlens 16384,65536 [--masks causal]
"""

import argparse
import itertools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CANDIDATES = [
    # (block_q, block_k); head_block candidates are derived per pair
    (128, 512),
    (256, 512),
    (256, 1024),
    (512, 1024),
    (512, 2048),
    # square/wide-q rungs: the round-5 tuned stock-flash control peaked at
    # (1024, 1024), which the table had never tried
    (1024, 1024),
    (1024, 2048),
]
HEAD_BLOCKS = [1, 2, 4, 8]


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--seqlens", default="16384,65536")
    p.add_argument("--masks", default="causal,full,swa_causal")
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--kv-heads", type=int, default=8)
    p.add_argument("--head-dim", type=int, default=128)
    p.add_argument("--out", default="", help="append JSONL rows here")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from magiattention_tpu.benchmarking import do_bench, enable_compile_cache

    enable_compile_cache()
    from magiattention_tpu.ops import flex_flash_attn_func
    from magiattention_tpu.ops.flex_attn import (
        _MAX_SMEM_ENTRIES,
        _auto_head_block,
        _est_entries,
    )
    from run_kernel_bench import mask_families

    group = args.heads // args.kv_heads

    def persist(row):
        print(row, file=sys.stderr, flush=True)
        if args.out:
            import json

            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")

    for total in [int(s) for s in args.seqlens.split(",")]:
        rng = np.random.default_rng(0)
        q = jnp.asarray(
            rng.standard_normal((total, args.heads, args.head_dim)),
            jnp.bfloat16,
        )
        k = jnp.asarray(
            rng.standard_normal((total, args.kv_heads, args.head_dim)),
            jnp.bfloat16,
        )
        v = jnp.asarray(
            rng.standard_normal((total, args.kv_heads, args.head_dim)),
            jnp.bfloat16,
        )
        do = jnp.asarray(
            rng.standard_normal((total, args.heads, args.head_dim)),
            jnp.bfloat16,
        )
        fams = mask_families(total)
        for name in args.masks.split(","):
            qr, kr, ts = fams[name]
            best = {}
            # dedupe prefs through the snap function (GQA groups snap
            # several prefs to one feasible hb; iterate the snapped set)
            hbs = sorted({
                _auto_head_block(p, args.heads, group) for p in HEAD_BLOCKS
            })
            for (bq, bk), hb in itertools.product(CANDIDATES, hbs):
                if _est_entries(qr, kr, bq, bk) > _MAX_SMEM_ENTRIES:
                    continue

                def attn(q, k, v):
                    return flex_flash_attn_func(
                        q, k, v, qr, kr, ts,
                        block_q=bq, block_k=bk, head_block=hb,
                    )[0]

                row = {"mask": name, "seqlen": total, "bq": bq, "bk": bk,
                       "hb": hb}
                try:
                    fwd = jax.jit(attn)
                    r = do_bench(fwd, q, k, v, warmup=1, rep=2, inner=5)
                    row["ms_fwd"] = round(r.median_ms, 2)
                    fb = jax.jit(
                        jax.grad(
                            lambda q, k, v: (attn(q, k, v) * do)
                            .sum()
                            .astype(jnp.float32),
                            argnums=(0, 1, 2),
                        )
                    )
                    rb = do_bench(fb, q, k, v, warmup=1, rep=2, inner=5)
                    row["ms_fb"] = round(rb.median_ms, 2)
                except Exception as e:
                    # keep whatever phase completed (a fwd-only row still
                    # competes for the ms_fwd winner)
                    row["error"] = str(e)[:120]
                persist(row)
                for key in ("ms_fwd", "ms_fb"):
                    if key in row and (
                        key not in best or row[key] < best[key][1]
                    ):
                        best[key] = ((bq, bk, hb), row[key])
            for key, (cfg, ms) in sorted(best.items()):
                print(
                    f"WINNER {name}@{total} {key}: blocks={cfg} {ms} ms",
                    flush=True,
                )


if __name__ == "__main__":
    main()
