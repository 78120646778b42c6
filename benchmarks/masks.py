"""Masks as data: slices, exact areas, and the quantile document rule.

A mask is a JSON object ``{"type": ..., parameters}`` in a traffic file.
``build_mask`` turns it into the (q_ranges, k_ranges, types) slice list
the program's keyed API takes, plus the exact number of allowed
(query, key) pairs — the *area* every FLOP count rests on. Nothing here
draws a random length: the structure of a mask is the same in every run,
whatever ``--seed`` is.

Mask type codes are the program's ABI (``common/enum.py``): 0 FULL,
1 CAUSAL (bottom-right aligned), 2 INVCAUSAL (top-left), 3 BICAUSAL.
"""

from __future__ import annotations

import csv
import dataclasses
import math
import os

import numpy as np

FULL, CAUSAL, INVCAUSAL, BICAUSAL = 0, 1, 2, 3
GOLDEN = (math.sqrt(5.0) - 1.0) / 2.0  # 0.618..., the stream's phase step

_HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Mask:
    """One attention mask over ``total`` tokens (self-attention)."""

    type: str
    total: int
    q_ranges: tuple[tuple[int, int], ...]
    k_ranges: tuple[tuple[int, int], ...]
    types: tuple[int, ...]
    area: int  # allowed (q, k) pairs, exact
    doc_lengths: tuple[int, ...] = ()  # packed masks only
    params: dict = dataclasses.field(default_factory=dict)

    @property
    def causal_share(self) -> float:
        """Area as a share of the dense causal triangle over ``total``."""
        return self.area / (self.total * (self.total + 1) // 2)

    @property
    def cu_seqlens(self) -> list[int]:
        return [0, *np.cumsum(self.doc_lengths).tolist()]

    def describe(self) -> dict:
        return {
            "type": self.type,
            "total": self.total,
            "slices": len(self.types),
            "docs": len(self.doc_lengths),
            "area": self.area,
            "causal_share_pct": 100.0 * self.causal_share,
        }


# ---------------------------------------------------------------------------
# the document-length histogram and its quantile function
# ---------------------------------------------------------------------------


def load_histogram(path: str) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(lo, hi, cumulative probability) of the reference's document-length
    histogram (``exps/dist_attn/benchmark/datasets/default``; a copy of
    the repo's ``exps/data/doc_length_distribution.csv``)."""
    lo, hi, cnt = [], [], []
    with open(path, newline="") as f:
        rows = csv.reader(f)
        next(rows)  # header
        for row in rows:
            a, b = row[0].strip("[] ").split(",")
            lo.append(int(a))
            hi.append(int(b))
            cnt.append(int(row[1]))
    p = np.asarray(cnt, np.float64)
    return (
        np.asarray(lo, np.int64),
        np.asarray(hi, np.int64),
        np.cumsum(p / p.sum()),
    )


def quantile_length(u: float, hist, cap: int) -> int:
    """The histogram's quantile at ``u`` in [0, 1): the bin holding ``u``,
    uniform inside it by the same fraction, capped at ``cap``."""
    lo, hi, cum = hist
    b = min(int(np.searchsorted(cum, u, side="right")), len(cum) - 1)
    below = cum[b - 1] if b else 0.0
    frac = (u - below) / (cum[b] - below)
    length = int(lo[b]) + int(frac * (int(hi[b]) - int(lo[b]) + 1))
    return max(1, min(length, int(hi[b]), cap))


def quantile_doc_lengths(
    total: int, phi: float, hist, order_seed: int
) -> list[int]:
    """Document lengths that fill ``total`` exactly, with no random draw.

    Lengths are the histogram's quantiles at ``(i + phi) / n`` for
    ``i < n``, each capped at ``total // 4`` as the reference caps its
    samples (cp_benchmark.md:63-76); ``n`` is the smallest count whose
    lengths reach ``total``; the excess is cut from the longest
    documents; the order is the permutation ``order_seed`` gives.
    """
    if not 0.0 <= phi < 1.0:
        raise ValueError(f"phi must be in [0, 1), got {phi}")
    cap = max(total // 4, 1)
    n = 1
    while True:
        lengths = [
            quantile_length((i + phi) / n, hist, cap) for i in range(n)
        ]
        if sum(lengths) >= total:
            break
        n += 1
    excess = sum(lengths) - total
    order = sorted(range(n), key=lambda i: -lengths[i])
    for i in order:  # longest first; one cut is nearly always enough
        cut = min(excess, lengths[i] - 1)
        lengths[i] -= cut
        excess -= cut
        if not excess:
            break
    perm = np.random.default_rng(order_seed).permutation(n)
    return [lengths[i] for i in perm]


def stream_phi(k: int) -> float:
    """Phase of the ``k``-th mask of a stream: frac(0.5 + k * 0.618...)."""
    return (0.5 + k * GOLDEN) % 1.0


# ---------------------------------------------------------------------------
# mask types
# ---------------------------------------------------------------------------


def _tri(n: int) -> int:
    return n * (n + 1) // 2


def _causal(total: int, spec: dict, index: int = 0) -> Mask:
    return Mask(
        "causal", total, ((0, total),), ((0, total),), (CAUSAL,), _tri(total)
    )


def _varlen_block_causal(total: int, spec: dict, index: int = 0) -> Mask:
    """Packed documents, causal inside each. ``lengths`` gives them
    outright, or ``rule: quantile`` with ``histogram``, ``order_seed``
    and (optionally) ``phi``; in a stream, mask ``index`` takes
    ``stream_phi(index)`` and the order seed ``[order_seed, index]``."""
    if "lengths" in spec:
        lengths = [int(x) for x in spec["lengths"]]
    elif spec.get("rule") == "quantile":
        hist = load_histogram(os.path.join(_HERE, spec["histogram"]))
        phi = float(spec["phi"]) if "phi" in spec else stream_phi(index)
        lengths = quantile_doc_lengths(
            total, phi, hist, [int(spec["order_seed"]), index]
        )
    else:
        raise ValueError(
            "varlen_block_causal needs 'lengths' or 'rule': 'quantile'"
        )
    if sum(lengths) != total or min(lengths) < 1:
        raise ValueError(
            f"document lengths sum to {sum(lengths)}, not {total}"
        )
    cuts = np.concatenate([[0], np.cumsum(lengths)]).tolist()
    ranges = tuple(zip(cuts, cuts[1:]))
    return Mask(
        "varlen_block_causal", total, ranges, ranges,
        (CAUSAL,) * len(lengths), sum(_tri(n) for n in lengths),
        doc_lengths=tuple(lengths),
    )


def _swa_causal(total: int, spec: dict, index: int = 0) -> Mask:
    """Row q sees keys (q - window, q]: a causal head of ``window`` rows
    and one bicausal band under it."""
    w = int(spec["window"])
    if not 1 <= w:
        raise ValueError(f"window must be >= 1, got {w}")
    if w >= total:
        return dataclasses.replace(
            _causal(total, spec), type="swa_causal", params={"window": w}
        )
    return Mask(
        "swa_causal", total,
        ((0, w), (w, total)), ((0, w), (1, total)), (CAUSAL, BICAUSAL),
        _tri(w) + (total - w) * w, params={"window": w},
    )


def _chunk_causal(total: int, spec: dict, index: int = 0) -> Mask:
    """Bidirectional inside a chunk, causal across chunks (the Magi-1
    mask): chunk c sees every key of chunks 0..c."""
    c = int(spec["chunk"])
    if c < 1:
        raise ValueError(f"chunk must be >= 1, got {c}")
    cuts = list(range(0, total, c)) + [total]
    q_ranges = tuple(zip(cuts, cuts[1:]))
    return Mask(
        "chunk_causal", total, q_ranges,
        tuple((0, b) for _a, b in q_ranges), (FULL,) * len(q_ranges),
        sum((b - a) * b for a, b in q_ranges), params={"chunk": c},
    )


MASK_TYPES = {
    "causal": _causal,
    "varlen_block_causal": _varlen_block_causal,
    "swa_causal": _swa_causal,
    "chunk_causal": _chunk_causal,
}


def build_mask(spec: dict, total: int, index: int = 0) -> Mask:
    """The mask a traffic file describes. ``index`` is the position in a
    stream of masks (only packed masks under the quantile rule use it)."""
    try:
        make = MASK_TYPES[spec["type"]]
    except KeyError:
        raise ValueError(
            f"unknown mask type {spec.get('type')!r}; known: "
            f"{sorted(MASK_TYPES)}"
        ) from None
    return make(total, spec, index)


def allowed(mask: Mask, q_pos, k_pos):
    """Boolean [len(q_pos), len(k_pos)] of the mask, from global
    positions, by the definition of each type and not from the slices —
    the references use it (numpy or jax.numpy arrays alike)."""
    q = q_pos[:, None]
    k = k_pos[None, :]
    if mask.type == "causal":
        return k <= q
    if mask.type == "swa_causal":
        return (k <= q) & (k > q - mask.params["window"])
    if mask.type == "chunk_causal":
        c = mask.params["chunk"]
        return (k // c) <= (q // c)
    if mask.type == "varlen_block_causal":
        cuts = np.asarray(mask.cu_seqlens[1:])
        if isinstance(q_pos, np.ndarray):
            xp = np
        else:
            import jax.numpy as xp
        doc_q = xp.searchsorted(xp.asarray(cuts), q_pos, side="right")
        doc_k = xp.searchsorted(xp.asarray(cuts), k_pos, side="right")
        return (k <= q) & (doc_q[:, None] == doc_k[None, :])
    raise ValueError(mask.type)


def slices_to_dense(mask: Mask) -> np.ndarray:
    """Brute-force boolean [total, total] from the slice list and the
    type codes' definitions (tests hold ``area`` and ``allowed`` to it;
    small totals only)."""
    t = mask.total
    out = np.zeros((t, t), bool)
    for (qs, qe), (ks, ke), ty in zip(mask.q_ranges, mask.k_ranges, mask.types):
        q = np.arange(qs, qe)[:, None]
        k = np.arange(ks, ke)[None, :]
        ok = np.ones((qe - qs, ke - ks), bool)
        if ty & CAUSAL:
            ok &= (k - ke) <= (q - qe)
        if ty & INVCAUSAL:
            ok &= (k - ks) >= (q - qs)
        if out[qs:qe, ks:ke][ok].any():
            raise ValueError("slices overlap")
        out[qs:qe, ks:ke] |= ok
    return out
