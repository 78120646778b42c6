"""Autotuner drift guard (``make autotune-check``).

Mirrors ``make telemetry-check``: asserts the cost model's rung choice on
three canonical workloads — 64k dense causal (the headline bench), 16k
varlen-block-causal (the 8.4 TF/s regression ISSUE 2 exists to fix), and
16k sliding-window causal (the VERDICT non-monotonicity) — against the
checked-in expectation file ``exps/data/autotune_expectations.json``. A
cost-model or candidate-table change that silently flips a canonical
winner fails CI until the expectation file (and the perf claim behind it)
is consciously updated.

Also asserts the structural invariants the expectations encode:
- 16k varlen-block-causal must NOT select a long-seq dense rung (the
  original regression), and
- 64k causal must keep the measured (1024, 1024) square rung.

Exits non-zero on drift. ``--update`` rewrites the expectation file from
the current model (for intentional recalibrations; diff it in review).
"""

import argparse
import json
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

EXPECTATIONS = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "data",
    "autotune_expectations.json",
)

# the ranking is generation-dependent (eff_flops vs the fixed grid-step
# overhead), so the guard pins the generation the checked-in expectations
# were taken on — a developer's exported
# MAGI_ATTENTION_TPU_GENERATION must neither fail the check spuriously nor
# bake another chip's ranking into the file via --update
PINNED_GENERATION = "v5e"


def canonical_workloads():
    from magiattention_tpu.testing.workloads import (
        mask_families,
        varlen_block_causal,
    )

    # the varlen entry is the 16k packed mask the ISSUE 15 invariants
    # below were stated on — they must guard that mask, not a
    # near-relative with a different skew profile
    sl = varlen_block_causal(16384)
    varlen = (
        [(int(a), int(b)) for a, b, *_ in sl],
        [(int(s[2]), int(s[3])) for s in sl],
        [int(s[4]) for s in sl],
    )
    fams16 = mask_families(16384)
    out = {
        "64k_causal": ([(0, 65536)], [(0, 65536)], [1]),
        "16k_varlen_block_causal": varlen,
        "16k_swa_causal": fams16["swa_causal"],
    }
    return out


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument(
        "--update",
        action="store_true",
        help="rewrite the expectation file from the current cost model",
    )
    args = p.parse_args()

    from magiattention_tpu.tuning import rank_candidates

    got = {"_generation": PINNED_GENERATION}
    for name, (qr, kr, ts) in canonical_workloads().items():
        best = rank_candidates(
            qr, kr, ts, 8, 8, head_dim=128, generation=PINNED_GENERATION
        )[0]
        got[name] = {
            "block_q": best.block_q,
            "block_k": best.block_k,
            "head_block": best.head_block,
            "grid": best.grid,
            "entries": best.entries,
            "steps": best.steps,
            "grid_slots": best.grid_slots,
            "dead_slots": best.dead_slots,
            "predicted_ms": round(best.cost_seconds * 1e3, 3),
        }

    if args.update:
        with open(EXPECTATIONS, "w") as f:
            json.dump(got, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {EXPECTATIONS}")
        return 0

    with open(EXPECTATIONS) as f:
        want = json.load(f)

    failures = []
    if want.get("_generation", PINNED_GENERATION) != PINNED_GENERATION:
        failures.append(
            f"expectation file was written for generation "
            f"{want['_generation']!r}, the guard pins {PINNED_GENERATION!r}"
        )
    for name, exp in want.items():
        if name == "_generation":
            continue
        g = got.get(name)
        if g is None:
            failures.append(f"{name}: workload missing from the check")
            continue
        for field in ("block_q", "block_k", "head_block", "grid"):
            if g[field] != exp[field]:
                failures.append(
                    f"{name}: {field} drifted {exp[field]} -> {g[field]} "
                    f"(full choice now {g})"
                )

    # structural invariants, independent of the expectation file
    vbc = got["16k_varlen_block_causal"]
    if vbc["block_q"] * vbc["block_k"] >= 1024 * 1024:
        failures.append(
            "16k varlen-block-causal selected a long-seq dense rung "
            f"({vbc['block_q']}x{vbc['block_k']}) — the exact regression "
            "ISSUE 2 fixed (8.4 TF/s)"
        )
    # ISSUE 15 (ROADMAP item 1): the heterogeneous-mask headline must
    # resolve to the compact sparse grid — zero dead slots and a >= 6x
    # grid-step reduction over the row-major (128, 512, 8), the
    # configuration the 8.44 TF/s was measured on (the row-major ranking's
    # own winner at these 8 q = 8 kv heads is (256, 512, 8) since ISSUE 35
    # priced the bytes a step streams)
    if vbc["grid"] != "sparse":
        failures.append(
            "16k varlen-block-causal left the sparse grid "
            f"(grid={vbc['grid']!r}) — the ISSUE 15 block-sparse rung "
            "regressed to the dead-step row-major layout"
        )
    if vbc["dead_slots"] != 0:
        failures.append(
            f"16k varlen-block-causal winner has {vbc['dead_slots']} dead "
            "grid slots — the sparse grid must have none by construction"
        )
    rm_best = next(
        s
        for s in rank_candidates(
            *canonical_workloads()["16k_varlen_block_causal"], 8, 8,
            head_dim=128, generation=PINNED_GENERATION, include_sparse=False,
        )
        if (s.block_q, s.block_k) == (128, 512)
    )
    reduction = rm_best.grid_slots / max(vbc["grid_slots"], 1)
    if reduction < 6.0:
        failures.append(
            "16k varlen-block-causal grid-step reduction "
            f"{reduction:.2f}x < 6x (row-major {rm_best.grid_slots} slots "
            f"vs sparse {vbc['grid_slots']}) — the ISSUE 15 acceptance "
            "floor"
        )
    c64 = got["64k_causal"]
    if (c64["block_q"], c64["block_k"]) != (1024, 1024):
        failures.append(
            "64k causal left the measured (1024, 1024) square rung: "
            f"({c64['block_q']}, {c64['block_k']}) — re-measure before "
            "accepting (guards the 101.1 TF/s headline)"
        )

    if failures:
        print("FAIL: autotuner rung-choice drift:")
        for f_ in failures:
            print(f"  - {f_}")
        print(
            "If intentional (recalibration backed by fresh on-chip "
            "numbers), run: python exps/run_autotune_check.py --update"
        )
        return 1
    n = len([k for k in want if k != "_generation"])
    print(
        f"autotune-check OK: {n} canonical workloads match "
        f"{os.path.relpath(EXPECTATIONS)} ({PINNED_GENERATION}); "
        f"16k varlen sparse-grid step reduction {reduction:.2f}x, "
        "0 dead slots"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
