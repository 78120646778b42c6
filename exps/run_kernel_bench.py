"""Kernel benchmark sweep: flex-flash-attention across mask types x seqlens.

Role of reference ``exps/attn/run_benchmark.py`` (the kernel sweep behind
cp_benchmark.md:78-86): measures TFLOPs/s of the Pallas flex kernel on the
reference's six headline mask families, against jax's official
flash_attention where it can express the mask (full/causal only — the flex
masks have no official-kernel equivalent, which is the point).

Run on a real TPU:  python exps/run_kernel_bench.py [--seqlens 2048,4096]

``--chained N``: time N serial kernel applications inside ONE jitted
lax.fori_loop (out feeds back in as q — same shape/dtype, serial data
dependency, no CSE) and report per-application time: the fixed host
cost of a dispatch divides by N, so short kernels are not read as their
launch floor.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _block_causal(doc, block):
    qr, kr, ts = [], [], []
    for a, b in zip(doc, doc[1:]):
        c = a
        while c < b:
            e = min(c + block, b)
            qr.append((c, e))
            kr.append((a, e))
            ts.append(0)  # FULL: the block sees its whole own block
            c = e
    return qr, kr, ts


def mask_families(total: int):
    """The six reference mask families (cp_benchmark.md:78-86), as slices."""
    third = total // 3
    doc = [0, third, 2 * third, total]
    w = max(total // 8, 256)
    from magiattention_tpu.api import infer_attn_mask_from_sliding_window

    swa_q, swa_k, swa_t = infer_attn_mask_from_sliding_window(total, w)
    fams = {
        "full": ([(0, total)], [(0, total)], [0]),
        "causal": ([(0, total)], [(0, total)], [1]),
        "varlen_full": (
            [(a, b) for a, b in zip(doc, doc[1:])],
            [(a, b) for a, b in zip(doc, doc[1:])],
            [0] * 3,
        ),
        "varlen_causal": (
            [(a, b) for a, b in zip(doc, doc[1:])],
            [(a, b) for a, b in zip(doc, doc[1:])],
            [1] * 3,
        ),
        # block-causal: causal at block granularity within each doc — every
        # q block attends FULLY from its doc's start through its own block
        # (reference exps block-causal construction: FULL slices per block)
        "varlen_block_causal": _block_causal(doc, max(total // 16, 128)),
        "swa_causal": (
            swa_q.to_naive_ranges(),
            swa_k.to_naive_ranges(),
            [int(t) for t in swa_t],
        ),
    }
    return fams


def main() -> None:
    p = argparse.ArgumentParser()
    # 131072 = the north-star seqlen (BASELINE.md config 3: 128k causal)
    p.add_argument("--seqlens", default="4096,8192,16384,32768,65536,131072")
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--kv-heads", type=int, default=8)
    p.add_argument("--head-dim", type=int, default=128)
    p.add_argument(
        "--block-q", type=int, default=None,
        help="default: kernel auto_block_config per mask",
    )
    p.add_argument("--block-k", type=int, default=None)
    p.add_argument("--head-block", type=int, default=None)
    p.add_argument(
        "--mode",
        default="fwd,bwd",
        help="comma set of {fwd,bwd}: bwd times jit(grad) and derives the "
        "pure-backward cost as (fwd+bwd) - fwd at 2.5x fwd FLOPs "
        "(reference cp_benchmark.md:45)",
    )
    p.add_argument(
        "--masks", default="", help="comma subset of mask families (all if empty)"
    )
    p.add_argument(
        "--sparse",
        action="store_true",
        help="also bench the sparse kernels (block-sparse keeping every "
        "4th/8th causal block per row — ~1/4 and ~1/8 of the causal area "
        "— plus NSA-style top-k index attention), FLOPs over kept blocks",
    )
    p.add_argument(
        "--out",
        default="",
        help="append each completed row as a JSON line to this file (a "
        "partial run still yields data)",
    )
    p.add_argument(
        "--chained",
        type=int,
        default=0,
        metavar="N",
        help="chain N kernel applications per dispatch (launch-floor-free "
        "timing; see module docstring); 0 = raw per-call do_bench",
    )
    args = p.parse_args()
    modes = set(args.mode.split(","))

    def bench_ms(jit_fn, call_args, step3):
        """Raw do_bench median or chained per-application ms.

        ``step3`` maps (q, k, v) to a same-shape/dtype triple — fwd:
        ``(out, k, v)``; bwd: all three grads, so the dkv kernel stays
        live against DCE inside the chained loop. ``call_args`` is the
        same (q, k, v) triple (k/v ride the carry, never closures — see
        :func:`magiattention_tpu.benchmarking.chained_ms`)."""
        if args.chained:
            from magiattention_tpu.benchmarking import chained_ms

            return chained_ms(
                lambda c: step3(*c), tuple(call_args), args.chained
            )
        from magiattention_tpu.benchmarking import do_bench as _db

        return _db(jit_fn, *call_args, warmup=2, rep=3, inner=10).median_ms

    def persist(row):
        print(row, file=sys.stderr, flush=True)
        if args.out:
            import json

            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from magiattention_tpu.benchmarking import (
        enable_compile_cache,
        perf_report,
    )

    enable_compile_cache()
    from magiattention_tpu.common.mask import total_area as slices_area
    from magiattention_tpu.common.ranges import AttnRanges
    from magiattention_tpu.ops import flex_flash_attn_func

    rows = []
    for total in [int(s) for s in args.seqlens.split(",")]:
        rng = np.random.default_rng(0)
        q = jnp.asarray(
            rng.standard_normal((total, args.heads, args.head_dim)), jnp.bfloat16
        )
        k = jnp.asarray(
            rng.standard_normal((total, args.kv_heads, args.head_dim)),
            jnp.bfloat16,
        )
        v = jnp.asarray(
            rng.standard_normal((total, args.kv_heads, args.head_dim)),
            jnp.bfloat16,
        )
        fams = mask_families(total)
        if args.masks:
            fams = {k_: fams[k_] for k_ in args.masks.split(",")}
        for name, (qr, kr, ts) in fams.items():
            area = slices_area(
                AttnRanges.from_ranges(qr), AttnRanges.from_ranges(kr), ts
            )
            flops = 4 * area * args.heads * args.head_dim
            row = {
                "mask": name,
                "seqlen": total,
                "area_frac": round(area / (total * total), 3),
            }

            def attn(q, k, v, qr=qr, kr=kr, ts=ts):
                return flex_flash_attn_func(
                    q,
                    k,
                    v,
                    qr,
                    kr,
                    ts,
                    block_q=args.block_q,
                    block_k=args.block_k,
                    head_block=args.head_block,
                )[0]

            fwd = jax.jit(attn)
            ms_fwd = bench_ms(
                fwd, (q, k, v),
                lambda qq, kk, vv, a=attn: (a(qq, kk, vv), kk, vv),
            )
            row["ms_fwd"] = round(ms_fwd, 2)
            row["tf_fwd"] = round(flops / (ms_fwd * 1e-3) / 1e12, 2)
            if "bwd" in modes:
                # plain .sum() loss: a random-`do` cotangent would ride the
                # HLO as a 134 MB literal; a ones cotangent times
                # identically
                gradf = jax.grad(
                    lambda q, k, v, a=attn: a(q, k, v)
                    .astype(jnp.float32)
                    .sum(),
                    argnums=(0, 1, 2),
                )
                fb = jax.jit(gradf)
                ms_fb = bench_ms(
                    fb, (q, k, v),
                    lambda qq, kk, vv, g=gradf: tuple(
                        gg.astype(x.dtype)
                        for gg, x in zip(g(qq, kk, vv), (qq, kk, vv))
                    ),
                )
                bwd_ms = ms_fb - ms_fwd
                row["ms_fb"] = round(ms_fb, 2)
                # pure backward at 2.5x fwd FLOPs (5 matmuls w/ recompute);
                # None when timing noise makes fwd+bwd <= fwd (unmeasurable)
                row["tf_bwd"] = (
                    round(2.5 * flops / (bwd_ms * 1e-3) / 1e12, 2)
                    if bwd_ms > 0.05 * ms_fwd
                    else None
                )
            rows.append(row)
            persist(row)

        # sparse-kernel rows (reference exps/attn block-sparse/index
        # variants, SURVEY §2.9): block-sparse at two densities + NSA-style
        # top-k index attention. FLOPs are counted over the KEPT blocks.
        if args.sparse:
            from magiattention_tpu.ops import (
                block_sparse_attn_func,
                index_attn_func,
            )

            # 128-token sparse blocks up to 32k, 256 at 64k, 512 at 128k+:
            # the keep-4th pattern at 128 granularity emits ~33k entries at
            # 64k, past the kernels' ~1 MB scalar-prefetch SMEM budget
            # (flex_attn._check_smem_budget rejects it loudly)
            bq = bk = 128 if total <= 32768 else (256 if total <= 65536 else 512)
            nq, nk = total // bq, total // bk
            sparse_cases = []
            for keepth_name, keep in (
                ("block_sparse_keep4th", 4),
                ("block_sparse_keep8th", 8),
            ):
                bm = np.zeros((nq, nk), dtype=bool)
                for i in range(nq):
                    bm[i, i :: -keep] = True  # diagonal + every keep-th back
                    bm[i, i] = True
                sparse_cases.append((keepth_name, bm))
            for sp_name, bm in sparse_cases:
                kept_blocks = int(bm.sum())
                area = kept_blocks * bq * bk
                flops = 4 * area * args.heads * args.head_dim
                def sp_step(qq, kk, vv, bm=bm):
                    return block_sparse_attn_func(
                        qq, kk, vv, bm, block_q=bq, block_k=bk
                    )[0]

                f = jax.jit(sp_step)
                try:  # one refused compile must not kill the sweep
                    ms_sp = bench_ms(
                        f, (q, k, v),
                        lambda qq, kk, vv, sstep=sp_step: (
                            sstep(qq, kk, vv), kk, vv
                        ),
                    )
                except Exception as e:
                    persist({"mask": sp_name, "seqlen": total,
                             "error": f"{type(e).__name__}: {str(e)[:160]}"})
                    continue
                row = {
                    "mask": sp_name,
                    "seqlen": total,
                    "area_frac": round(area / (total * total), 3),
                    "ms_fwd": round(ms_sp, 2),
                    "tf_fwd": round(flops / (ms_sp * 1e-3) / 1e12, 2),
                }
                rows.append(row)
                persist(row)
            # NSA-style top-k: 8 causal blocks per q block (incl. diagonal)
            topk = min(8, nk)
            sel = np.full((nq, topk), -1, dtype=np.int64)
            for i in range(nq):
                cand = list(range(max(0, i - topk + 1), i + 1))
                sel[i, : len(cand)] = cand
            area = int((sel >= 0).sum()) * bq * bk
            flops = 4 * area * args.heads * args.head_dim
            def ix_step(qq, kk, vv):
                return index_attn_func(
                    qq, kk, vv, sel, causal=False, block_q=bq, block_k=bk
                )[0]

            f = jax.jit(ix_step)
            try:
                ms_ix = bench_ms(
                    f, (q, k, v),
                    lambda qq, kk, vv: (ix_step(qq, kk, vv), kk, vv),
                )
            except Exception as e:
                persist({"mask": f"index_top{topk}", "seqlen": total,
                         "error": f"{type(e).__name__}: {str(e)[:160]}"})
                ms_ix = None
            row = None if ms_ix is None else {
                "mask": f"index_top{topk}",
                "seqlen": total,
                "area_frac": round(area / (total * total), 3),
                "ms_fwd": round(ms_ix, 2),
                "tf_fwd": round(flops / (ms_ix * 1e-3) / 1e12, 2),
            }
            if row is not None:
                rows.append(row)
                persist(row)

        # official-kernel reference points (full + causal only)
        try:
            from jax.experimental.pallas.ops.tpu.flash_attention import (
                flash_attention,
            )

            qb = q.transpose(1, 0, 2)[None]
            kb = k.transpose(1, 0, 2)[None]
            vb = v.transpose(1, 0, 2)[None]
            for causal in (False, True):
                area = total * (total + 1) // 2 if causal else total * total
                flops = 4 * area * args.heads * args.head_dim
                row = {
                    "mask": f"jax_flash_{'causal' if causal else 'full'}",
                    "seqlen": total,
                    "area_frac": 0.5 if causal else 1.0,
                }
                def ref_step(qq, kk, vv, c=causal):
                    return flash_attention(qq, kk, vv, causal=c)

                ref = jax.jit(ref_step)
                ms_ref = bench_ms(
                    ref, (qb, kb, vb),
                    lambda qq, kk, vv: (ref_step(qq, kk, vv), kk, vv),
                )
                row["ms_fwd"] = round(ms_ref, 2)
                row["tf_fwd"] = round(flops / (ms_ref * 1e-3) / 1e12, 2)
                if "bwd" in modes:
                    ref_grad = jax.grad(
                        lambda q, k, v, c=causal: flash_attention(
                            q, k, v, causal=c
                        )
                        .astype(jnp.float32)
                        .sum(),
                        argnums=(0, 1, 2),
                    )
                    fb = jax.jit(ref_grad)
                    ms_refb = bench_ms(
                        fb, (qb, kb, vb),
                        lambda qq, kk, vv, g=ref_grad: tuple(
                            gg.astype(x.dtype)
                            for gg, x in zip(g(qq, kk, vv), (qq, kk, vv))
                        ),
                    )
                    bwd_ms = ms_refb - ms_ref
                    row["ms_fb"] = round(ms_refb, 2)
                    row["tf_bwd"] = (
                        round(2.5 * flops / (bwd_ms * 1e-3) / 1e12, 2)
                        if bwd_ms > 0.05 * ms_ref
                        else None
                    )
                rows.append(row)
                persist(row)
        except Exception as e:  # pragma: no cover
            print(f"jax reference kernel failed: {e}", file=sys.stderr)

    print(perf_report(rows))


if __name__ == "__main__":
    main()
