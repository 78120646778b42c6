"""Reference-style mask workloads shared by benches and regression tests.

One definition of the three dynamic-solver evaluation workloads
(docs/dynamic_solver.md; shapes mirror the reference's pipeline
scenarios, tests/test_pipeline.py: full_attn, varlen_block_causal,
bi_causal_with_q_overlap) so `exps/run_dynsolver_bench.py` and
`tests/test_meta/test_dynsolver_quality.py` cannot silently diverge.

Each builder returns a list of (q_start, q_end, k_start, k_end, type)
slices in global coordinates.
"""

from __future__ import annotations

import numpy as np


def dense_causal(total: int):
    return [(0, total, 0, total, 1)]


def varlen_block_causal(total: int, n_docs: int = 12, seed: int = 7):
    """Docs of pseudo-random length, each causal over itself."""
    rng = np.random.default_rng(seed)
    cuts = np.sort(rng.choice(np.arange(1, total), n_docs - 1, replace=False))
    bounds = [0, *[int(c) for c in cuts], total]
    return [(a, b, a, b, 1) for a, b in zip(bounds, bounds[1:])]


def shared_question_q_overlap(total: int, n_answers: int = 8):
    """Reference bi_causal_with_q_overlap shape: a shared question prefix
    (first quarter) that EVERY answer segment attends fully, plus each
    answer causal over itself — answer q rows appear in two slices."""
    q_len = total // 4
    rest = total - q_len
    seg = rest // n_answers
    slices = [(0, q_len, 0, q_len, 1)]  # the question itself, causal
    for i in range(n_answers):
        a = q_len + i * seg
        b = q_len + (i + 1) * seg if i < n_answers - 1 else total
        slices.append((a, b, 0, q_len, 0))  # full attention to question
        slices.append((a, b, a, b, 1))  # causal over itself
    return slices


def ranges_of(slices):
    """``(q_ranges, k_ranges, attn_type_map)`` lists of a slice list —
    the argument form of ``flex_flash_attn_func`` and the oracle."""
    return (
        [(int(s[0]), int(s[1])) for s in slices],
        [(int(s[2]), int(s[3])) for s in slices],
        [int(s[4]) for s in slices],
    )


DYNSOLVER_WORKLOADS = {
    "dense_causal": dense_causal,
    "varlen_block_causal": varlen_block_causal,
    "shared_question": shared_question_q_overlap,
}
