"""Reference-style mask workloads shared by gates, examples and tests.

The three dynamic-solver evaluation workloads (docs/dynamic_solver.md;
shapes mirror the reference's pipeline scenarios, tests/test_pipeline.py:
full_attn, varlen_block_causal, bi_causal_with_q_overlap) that
`tests/test_meta/test_dynsolver_quality.py` and `test_snf_solver.py`
hold the solvers to; each builder returns a list of (q_start, q_end,
k_start, k_end, type) slices in global coordinates.

Beside them: the reference's six kernel-benchmark mask families
(`mask_families`) and its document-length sampler (`sample_doc_cuts`),
which the autotune gate, the tuner's tests and the example trainer share.
"""

from __future__ import annotations

import functools
import os

import numpy as np

_DOC_DIST_CSV = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "data",
    "doc_length_distribution.csv",
)


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


@functools.cache
def _load_doc_length_histogram() -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(lo, hi, prob) bins of the reference's real document-length
    distribution (data imported verbatim from
    exps/dist_attn/benchmark/datasets/default/doc_length_distribution.csv
    — the corpus histogram its dist benchmark samples from)."""
    lo, hi, cnt = [], [], []
    with open(_DOC_DIST_CSV) as f:
        next(f)  # header
        for line in f:
            rng_part, rest = line.strip().split('",')
            a, b = rng_part.strip('"[]').split(",")
            lo.append(int(a))
            hi.append(int(b.strip().rstrip("]")))
            cnt.append(int(rest.split(",")[0]))
    cnt_arr = np.asarray(cnt, np.float64)
    return (
        np.asarray(lo, np.int64),
        np.asarray(hi, np.int64),
        cnt_arr / cnt_arr.sum(),
    )


def sample_doc_cuts(total: int, rng: np.random.Generator) -> list[int]:
    """Document cut points drawn from the reference's REAL doc-length
    histogram (uniform within the chosen bin), each sample capped at
    total/4 (cp_benchmark.md:63-76)."""
    cuts = [0]
    lo, hi, p = _load_doc_length_histogram()
    while cuts[-1] < total:
        b = rng.choice(len(p), p=p)
        ln = int(np.clip(rng.integers(lo[b], hi[b] + 1), 1, total // 4))
        cuts.append(min(cuts[-1] + ln, total))
    return cuts
