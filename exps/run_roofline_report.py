"""Mask-aware roofline report for one workload (ISSUE 10 acceptance).

Default: the 16k varlen block-causal headline — the workload stuck at
8.4 TF/s while dense paths run 101-113 (ROADMAP item 1). The report:

- resolves the rung the autotuner actually picks for the workload
  (``auto_block_config`` — pricing what executes, not a hypothetical),
- pulls the newest measured TF/s for the workload's metric from
  ``BENCH_HISTORY.jsonl`` (override with ``--measured-tflops``),
- prints the mask-aware roofline decomposition (achieved fraction of
  peak, gap attribution, dominant waste term) and the block-occupancy
  ASCII heatmap,
- dumps the occupancy JSON artifact — per-q-block active-k-block lists
  in exactly the shape a splash-style block-sparse grid consumes
  (default ``exps/data/occupancy_<workload>_<total>.json``).

Host-side only (exact numpy counting; no devices).

Usage:
  python exps/run_roofline_report.py
  python exps/run_roofline_report.py --total 16384 \
      --workload varlen_block_causal --measured-tflops 8.44
Exit codes: 0 = report produced (and self-consistent), 1 = error.
"""

import argparse
import json
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DATA = os.path.join(_ROOT, "exps", "data")

# workload -> the BENCH_HISTORY metric whose TF/s measures it
_METRIC_FOR = {
    ("varlen_block_causal", 16384):
        "flex_attn_fwd_tflops_16k_varlen_block_causal_bf16",
    ("dense_causal", 65536): "flex_attn_fwd_tflops_64k_causal_bf16",
    ("dense_causal", 131072): "flex_attn_fwd_tflops_128k_causal_bf16",
}


def _newest_measurement(metric: str):
    from magiattention_tpu.telemetry import baseline

    return baseline.newest_metric_value(
        baseline.load_history(
            os.path.join(_ROOT, baseline.HISTORY_FILENAME)
        ),
        metric,
    )


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--total", type=int, default=16384)
    p.add_argument(
        "--workload", default="varlen_block_causal",
        help="a magiattention_tpu.testing.workloads builder name",
    )
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--kv-heads", type=int, default=8)
    p.add_argument("--head-dim", type=int, default=128)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument(
        "--measured-tflops", type=float, default=None,
        help="override the TF/s pulled from BENCH_HISTORY.jsonl",
    )
    p.add_argument(
        "--generation", default=None,
        help="peak-table key (default MAGI_ATTENTION_TPU_GENERATION)",
    )
    p.add_argument(
        "--occupancy-out", default=None,
        help="occupancy JSON path (default exps/data/occupancy_*.json)",
    )
    p.add_argument(
        "--seed-history",
        action="store_true",
        help="append the sparse-grid step-reduction metric to "
        "BENCH_HISTORY.jsonl (TF/s carried forward from the newest "
        "entry) so run_perf_gate.py gates it",
    )
    args = p.parse_args()

    from magiattention_tpu.telemetry.occupancy import block_occupancy_map
    from magiattention_tpu.telemetry.roofline import profile_roofline
    from magiattention_tpu.testing import workloads

    builder = getattr(workloads, args.workload, None)
    if builder is None:
        print(f"unknown workload {args.workload!r}; see testing/workloads.py")
        return 1
    slices = builder(args.total)
    qr = [(int(a), int(b)) for a, b, *_ in slices]
    kr = [(int(s[2]), int(s[3])) for s in slices]
    ts = [int(s[4]) for s in slices]

    measured, provenance = args.measured_tflops, "--measured-tflops"
    if measured is None:
        metric = _METRIC_FOR.get((args.workload, args.total))
        if metric is not None:
            measured, provenance = _newest_measurement(metric)
    rep = profile_roofline(
        qr, kr, ts,
        num_heads_q=args.heads,
        num_heads_kv=args.kv_heads,
        head_dim=args.head_dim,
        dtype=args.dtype,
        generation=args.generation,
        workload=f"{args.workload}_{args.total}",
        measured_tflops=measured,
        record=False,  # standalone report: no registry side effects
    )
    print(rep.report())
    if measured is not None:
        print(f"  (measured TF/s source: {provenance})")
        # self-consistency: the achieved fraction IS measured/peak under
        # the mask-FLOPs convention — drift here means the accounting broke
        if abs(rep.efficiency - measured / rep.peak_tflops) > 1e-9:
            print("FAIL: efficiency != measured/peak — accounting drift")
            return 1
    print()

    occ = block_occupancy_map(qr, kr, ts, rep.block_q, rep.block_k)
    print(occ.ascii_heatmap())
    out = args.occupancy_out or os.path.join(
        _DATA, f"occupancy_{args.workload}_{args.total}.json"
    )
    os.makedirs(os.path.dirname(out), exist_ok=True)
    occ.dump(out)
    # prove the artifact loads back as per-q-block active-k-block lists
    with open(out) as f:
        loaded = json.load(f)
    lists = loaded["active_k_blocks"]
    assert len(lists) == occ.num_q_blocks and all(
        isinstance(row, list) for row in lists
    )
    print(
        f"\noccupancy artifact -> {out} "
        f"({occ.num_q_blocks} q-blocks, {occ.active_blocks_total} active "
        f"tiles, block density {occ.block_density:.4f}; the block-sparse "
        "grid input of ROADMAP item 1)"
    )

    # ISSUE 15 acceptance on the headline workload: the autotuner must
    # resolve it to the compact sparse grid — dead-step fraction ~0 and
    # a >= 6x grid-step reduction over the best row-major candidate
    # (the configuration the 8.44 TF/s was measured on)
    headline = (
        args.workload == "varlen_block_causal" and args.total == 16384
    )
    if headline:
        from magiattention_tpu.tuning import rank_candidates

        if rep.grid != "sparse":
            print(
                f"FAIL: headline workload resolved to grid={rep.grid!r}, "
                "not the block-sparse grid (ISSUE 15 regression)"
            )
            return 1
        dead_frac = rep.gap_fractions()["dead_steps"]
        if rep.dead_slots != 0 or dead_frac > 1e-9:
            print(
                f"FAIL: headline dead-step fraction {dead_frac:.2%} "
                f"({rep.dead_slots} dead slots) != ~0 on the sparse grid"
            )
            return 1
        rm = rank_candidates(
            qr, kr, ts, args.heads, args.kv_heads,
            head_dim=args.head_dim, generation=args.generation,
            include_sparse=False,
        )[0]
        rm_slots = rm.grid_slots
        sparse_slots = rep.live_slots + rep.dead_slots
        reduction = rm_slots / max(sparse_slots, 1)
        print(
            f"sparse-grid step reduction: {rm_slots} row-major slots "
            f"({rm.block_q}x{rm.block_k}x{rm.head_block}) -> "
            f"{sparse_slots} sparse slots "
            f"({rep.block_q}x{rep.block_k}x{rep.head_block}) = "
            f"{reduction:.2f}x (dead-step fraction {dead_frac:.1%})"
        )
        if reduction < 6.0:
            print(
                f"FAIL: step reduction {reduction:.2f}x < the 6x "
                "acceptance floor (ISSUE 15)"
            )
            return 1
        if args.seed_history:
            _seed_history(reduction)
    elif args.seed_history:
        print("--seed-history only applies to the 16k varlen headline")
        return 1
    return 0


STEP_REDUCTION_METRIC = (
    "flex_attn_sparse_grid_step_reduction_16k_varlen_block_causal"
)


def _seed_history(reduction: float) -> None:
    """Append a BENCH_HISTORY entry carrying the sparse-grid
    step-reduction ratio (a model-derived, higher-is-better metric the
    perf gate windows like a TF/s: a cost-model or rung regression that
    shrinks it trips the gate). TF/s metrics are carried forward from
    the newest entry — this is NOT an on-chip measurement and says so in
    its source string (the run_comm_check --seed-history convention)."""
    from magiattention_tpu.telemetry import baseline

    path = os.path.join(_ROOT, baseline.HISTORY_FILENAME)
    history = baseline.load_history(path)
    metrics = {
        k: v
        for k, v in baseline.newest_metrics(history).items()
        if k.startswith("flex_attn_")
    }
    metrics[STEP_REDUCTION_METRIC] = round(float(reduction), 3)
    rung = next(
        (
            e["autotune_rung"]
            for e in reversed(history)
            if e.get("autotune_rung")
        ),
        None,
    )
    entry = baseline.make_history_entry(
        source=(
            "exps/run_roofline_report.py --seed-history (sparse-grid "
            "step reduction from the cost model; TF/s carried forward "
            "from the newest entry)"
        ),
        metrics=metrics,
        autotune_rung=rung,
    )
    baseline.append_history(path, entry)
    print(
        f"history appended -> {path} ({STEP_REDUCTION_METRIC} = "
        f"{metrics[STEP_REDUCTION_METRIC]})"
    )


if __name__ == "__main__":
    sys.exit(main())
