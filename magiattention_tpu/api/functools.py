"""Mask-construction helpers (reference ``magi_attention/api/functools.py``).

Pure host-side utilities that turn common training-data descriptions
(batches, cu_seqlens, sliding windows) into (q_ranges, k_ranges, mask types)
plus padding helpers for the chunked dispatch layout.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..common.enum import AttnMaskType
from ..common.ranges import AttnRanges


def compute_pad_size(
    total_seqlen_q: int, cp_size: int, chunk_size: int
) -> int:
    """Tokens to append so the sequence splits into whole chunks per rank
    (reference api/functools.py compute_pad_size)."""
    block = cp_size * chunk_size
    return (-total_seqlen_q) % block


def pad_at_dim(
    x: jax.Array, dim: int, pad_size: int, value: float = 0.0
) -> jax.Array:
    if pad_size <= 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[dim] = (0, pad_size)
    return jnp.pad(x, cfg, constant_values=value)


def unpad_at_dim(x: jax.Array, dim: int, orig_size: int) -> jax.Array:
    return jax.lax.slice_in_dim(x, 0, orig_size, axis=dim)


def apply_padding(
    q_ranges: AttnRanges,
    k_ranges: AttnRanges,
    attn_mask_type: Sequence[AttnMaskType],
    total_seqlen: int,
    pad_size: int,
):
    """Extend the mask description over padded tokens: pad rows attend
    nothing (no new slices; the kernel yields out=0 / lse=-inf there)."""
    return (
        q_ranges,
        k_ranges,
        list(attn_mask_type),
        total_seqlen + pad_size,
    )


def squash_batch_dim(x: jax.Array) -> jax.Array:
    """[b, s, ...] -> [b*s, ...] token-major packing (reference squash)."""
    return x.reshape((-1,) + x.shape[2:])


def full_attention_mask(total_seqlen: int):
    q = AttnRanges.from_ranges([(0, total_seqlen)])
    return q, q.clone(), [AttnMaskType.FULL]


def infer_varlen_mask_from_batch(
    batch_seqlens: Sequence[int], causal: bool = True
):
    """Per-sample (self-)attention ranges from a list of sample lengths."""
    cu = np.concatenate([[0], np.cumsum(np.asarray(batch_seqlens))])
    return infer_attn_mask_from_cu_seqlens(cu.tolist(), causal=causal)


def infer_attn_mask_from_segment_ids(
    segment_ids: Sequence[int] | np.ndarray,
    causal: bool = True,
):
    """Slices for a flat segment-id vector (the convention of jax's
    flash-attention ``segment_ids``): each maximal run of one id is a
    sample; ids < 0 mark padding rows that attend nothing (covered by no
    slice -> out=0, lse=-inf).
    """
    seg = np.asarray(segment_ids)
    assert seg.ndim in (1, 2), f"segment_ids must be [t] or [b, s], {seg.shape}"
    rows = seg[None, :] if seg.ndim == 1 else seg
    s = rows.shape[1]
    ranges = []
    for i, row in enumerate(rows):
        if s == 0:
            continue
        # runs never merge across batch rows: each row is offset into the
        # squashed [b*s] coordinate space and processed independently
        starts = np.concatenate(
            ([0], np.flatnonzero(np.diff(row) != 0) + 1, [s])
        )
        ranges.extend(
            (i * s + int(a), i * s + int(b))
            for a, b in zip(starts, starts[1:])
            if row[a] >= 0
        )
    q = AttnRanges.from_ranges(ranges)
    mt = AttnMaskType.CAUSAL if causal else AttnMaskType.FULL
    return q, q.clone(), [mt] * len(q)


def infer_varlen_mask_from_padded_batch(
    attention_mask: np.ndarray,
    causal: bool = True,
):
    """Slices for a right-padded [batch, seq] 0/1 attention mask (the HF
    convention), to be used after :func:`squash_batch_dim`: sample ``i``
    occupies rows ``[i*s, i*s + valid_i)``; pad rows attend nothing.
    """
    am = np.asarray(attention_mask)
    assert am.ndim == 2, f"attention_mask must be [batch, seq], got {am.shape}"
    b, s = am.shape
    lens = am.astype(bool).sum(axis=1)
    # right-padding check: all valid tokens must be a prefix
    for i in range(b):
        if not am[i, : lens[i]].all():
            raise ValueError(
                f"attention_mask row {i} is not right-padded (holes are "
                "not expressible as one varlen sample); build explicit "
                "ranges instead"
            )
    ranges = [
        (i * s, i * s + int(L)) for i, L in enumerate(lens) if L > 0
    ]
    q = AttnRanges.from_ranges(ranges)
    mt = AttnMaskType.CAUSAL if causal else AttnMaskType.FULL
    return q, q.clone(), [mt] * len(q)


def infer_window_mask_per_range(
    q_range: Sequence[int],
    k_range: Sequence[int],
    window_size: tuple[int, int],
    global_window_size: int = 0,
):
    """Decompose one bidirectional sliding-window region into exact slices.

    Role of the reference's per-range ``infer_attn_mask_from_sliding_window``
    (api/functools.py:180) with its cu_seqlens caller's global-window
    extension (:335); the case analysis here is re-derived from this
    repo's slice conventions (common/mask.py:28-42) rather than ported.

    Semantics (flash-attn window convention, bottom-right aligned): with
    ``Lq = min(len(q_range), len(k_range))`` valid trailing query rows
    (earlier rows attend nothing), row ``r`` sits at key-local position
    ``pk = Lk - Lq + r`` and attends keys ``[pk - wl, pk + wr]``
    intersected with the key range; ``-1`` means unbounded on that side.
    ``global_window_size`` additionally lets every row attend the first
    ``G`` keys of the range, capped at ``pk - wl`` per row so no key ahead
    of the row's own window leaks in (reference leakage guard
    ``min(G, i + wr + 1)`` — the two caps coincide because the band
    already covers ``[pk - wl, pk + wr]``).

    The band is at most three slices — a CAUSAL head while the lower edge
    clips at the range start, a BICAUSAL (or FULL, when the window spans
    the whole range) middle, an INVCAUSAL tail while the upper edge clips
    at the range end — plus at most two more for the global prefix.
    """
    qs, qe = int(q_range[0]), int(q_range[1])
    ks, ke = int(k_range[0]), int(k_range[1])
    lk = ke - ks
    lq = min(qe - qs, lk)
    out_q, out_k, out_t = [], [], []
    if lq <= 0 or lk <= 0:
        return out_q, out_k, out_t
    q0 = qe - lq  # first valid query row (global)
    wl, wr = window_size
    wl = lk if (wl == -1 or wl >= lk - 1) else int(wl)
    wr = lk if (wr == -1 or wr >= lk - 1) else int(wr)
    assert wl >= 0 and wr >= 0, f"bad window {window_size}"
    # key-local visible interval of row r: [max(0, a + r), min(lk, b + r))
    a = lk - lq - wl
    b = lk - lq + wr + 1

    def clamp(x, lo, hi):
        return max(lo, min(x, hi))

    r1 = clamp(-a, 0, lq)  # rows below r1: lower edge clipped to 0
    r2 = clamp(lk - b + 1, 0, lq)  # rows from r2 on: upper edge clipped

    def emit(r_lo, r_hi, k_lo, k_hi, mt):
        if r_hi > r_lo and k_hi > k_lo:
            out_q.append((q0 + r_lo, q0 + r_hi))
            out_k.append((ks + k_lo, ks + k_hi))
            out_t.append(mt)

    if r1 <= r2:
        # causal head: rows [max(0, 1-b), r1), keys [0, b + r - 1 .. )
        ra = clamp(1 - b, 0, r1)
        emit(ra, r1, 0, b + r1 - 1, AttnMaskType.CAUSAL)
        emit(r1, r2, a + r1, b + r2 - 1, AttnMaskType.BICAUSAL)
        emit(r2, lq, a + r2, lk, AttnMaskType.INVCAUSAL)
    else:
        ra = clamp(1 - b, 0, r2)
        emit(ra, r2, 0, b + r2 - 1, AttnMaskType.CAUSAL)
        emit(r2, r1, 0, lk, AttnMaskType.FULL)
        emit(r1, lq, a + r1, lk, AttnMaskType.INVCAUSAL)

    g = min(int(global_window_size), lk)
    if g > 0:
        # extra prefix for rows whose band lower edge is past the start:
        # row r adds keys [0, min(g, a + r)) — the a + r cap subsumes the
        # reference's min(G, pk + wr + 1) guard since a < b
        rg0 = clamp(max(r1, 1 - a), 0, lq)
        rg1 = clamp(g - a, rg0, lq)
        emit(rg0, rg1, 0, a + rg1 - 1, AttnMaskType.CAUSAL)
        emit(rg1, lq, 0, g, AttnMaskType.FULL)
    return out_q, out_k, out_t


def infer_attn_mask_from_cu_seqlens(
    cu_seqlens: Sequence[int],
    causal: bool = True,
    *,
    cu_seqlens_k: Sequence[int] | None = None,
    window_size: tuple[int, int] = (-1, -1),
    global_window_size: int = 0,
):
    """(q_ranges, k_ranges, types) for a packed varlen batch.

    Reference parity (api/functools.py:335): ``cu_seqlens_k`` supports
    varlen cross-attention (per-sample q/k lengths may differ);
    ``window_size=(left, right)`` applies a bidirectional sliding window
    per sample (requires ``causal=False``), optionally with
    ``global_window_size`` leading keys per sample. Unlike the reference
    this returns the 3-tuple only — totals are ``cu_seqlens[-1]`` /
    ``cu_seqlens_k[-1]``, which the caller already has. ``causal``
    defaults True (the reference defaults False)."""
    cu_q = [int(c) for c in cu_seqlens]
    cu_k = cu_q if cu_seqlens_k is None else [int(c) for c in cu_seqlens_k]
    assert len(cu_q) == len(cu_k), "cu_seqlens_q/k must pair samples"
    for name, cu in (("cu_seqlens", cu_q), ("cu_seqlens_k", cu_k)):
        if cu[0] != 0 or any(a > b for a, b in zip(cu, cu[1:])):
            raise ValueError(
                f"invalid {name}: must start at 0 and be non-decreasing, "
                f"got {cu}"
            )
    if tuple(window_size) == (-1, -1):
        assert global_window_size == 0, (
            "global_window_size needs a bounded window_size"
        )
        q = AttnRanges.from_ranges(list(zip(cu_q[:-1], cu_q[1:])))
        k = AttnRanges.from_ranges(list(zip(cu_k[:-1], cu_k[1:])))
        mt = AttnMaskType.CAUSAL if causal else AttnMaskType.FULL
        return q, k, [mt] * len(q)
    assert not causal, (
        f"causal must be False with a bounded window, got {window_size=}"
    )
    qr, kr, ts = [], [], []
    for qs, qe, ks, ke in zip(cu_q, cu_q[1:], cu_k, cu_k[1:]):
        sq, sk, st = infer_window_mask_per_range(
            (qs, qe), (ks, ke), tuple(window_size), global_window_size
        )
        qr.extend(sq)
        kr.extend(sk)
        ts.extend(st)
    return (
        AttnRanges.from_ranges(qr),
        AttnRanges.from_ranges(kr),
        ts,
    )


def infer_attn_mask_from_sliding_window(
    total_seqlen: int,
    window_size: int,
    causal: bool = True,
    global_tokens: int = 0,
):
    """Exact causal sliding-window attention as slices: row q attends keys
    [q - window_size + 1, q] (+ optional ``global_tokens`` prefix).

    Delegates to :func:`infer_window_mask_per_range` with
    ``window = (window_size - 1, 0)`` — the general bidirectional
    decomposition emits at most five slices (causal head + one bicausal
    band + global-prefix pair) instead of one slice per window-width band,
    shrinking planner input and kernel entry tables at long seqlen.
    """
    assert causal, (
        "for bidirectional SWA use infer_window_mask_per_range / "
        "infer_attn_mask_from_cu_seqlens(window_size=(l, r))"
    )
    assert window_size >= 1, (
        f"window_size must be >= 1, got {window_size} (a 0-wide window "
        "would collide with the -1 'unbounded' sentinel)"
    )
    qr, kr, ts = infer_window_mask_per_range(
        (0, total_seqlen),
        (0, total_seqlen),
        (window_size - 1, 0),
        global_tokens,
    )
    return AttnRanges.from_ranges(qr), AttnRanges.from_ranges(kr), ts


def infer_block_diffusion_mask(
    cu_seqlens: Sequence[int],
    block: int,
    *,
    total_seqlen: int | None = None,
):
    """(q_ranges, k_ranges, types) of block-diffusion training (BD3-LM,
    arXiv:2503.09573; SDAR, arXiv:2510.06303) over packed documents, on
    the DOUBLED sequence ``[noisy ; clean]``: rows ``[0, L)`` are the
    noised copy, rows ``[L, 2L)`` the clean copy, token ``i`` of either at
    ``i`` resp. ``L + i`` (``L = total_seqlen``, default ``cu_seqlens[-1]``;
    larger where the sequence is padded: pad rows attend nothing).

    Inside a document cut into blocks of ``block`` tokens, a noisy row of
    block b sees the noisy rows of block b (both ways) and the clean rows
    of the blocks before b; a clean row of block b sees the clean rows of
    blocks up to and with b, and no noisy key. With stepped bounds
    (``AttnMaskType.with_step``) that is three slices a document:

    - clean -> clean: CAUSAL at step ``block`` on the document;
    - noisy -> clean: CAUSAL at step ``block``, the query range starting
      one block in and the key range ending one block early (none for a
      document of one block);
    - noisy -> noisy: BICAUSAL at step ``block`` (the block diagonal).

    The four unstepped types would take one FULL rectangle a block and
    kind, ``3 n / block - 1`` a document of n tokens
    (``common.mask.unstepped_slice_count``). Every document is a whole
    number of blocks (padding one to a
    block is the loader's) and ``block`` a power of two, else ValueError.
    """
    cu = [int(c) for c in cu_seqlens]
    if not cu or cu[0] != 0 or any(a > b for a, b in zip(cu, cu[1:])):
        raise ValueError(
            f"invalid cu_seqlens: must start at 0 and be non-decreasing, "
            f"got {cu}"
        )
    block = int(block)
    causal = AttnMaskType.CAUSAL.with_step(block)  # checks the power of two
    diagonal = AttnMaskType.BICAUSAL.with_step(block)
    ragged = [(a, b) for a, b in zip(cu, cu[1:]) if (b - a) % block]
    if ragged:
        raise ValueError(
            f"documents {ragged} are no whole number of blocks of {block} "
            "tokens: pad each to a block before packing"
        )
    total = cu[-1] if total_seqlen is None else int(total_seqlen)
    if total < cu[-1]:
        raise ValueError(f"total_seqlen {total} < cu_seqlens[-1] {cu[-1]}")
    qr, kr, ts = [], [], []

    def add(q, k, t):
        qr.append(q)
        kr.append(k)
        ts.append(t)

    for c0, c1 in zip(cu, cu[1:]):
        if c1 == c0:
            continue
        add((total + c0, total + c1), (total + c0, total + c1), causal)
        if c1 - c0 > block:
            add((c0 + block, c1), (total + c0, total + c1 - block), causal)
        add((c0, c1), (c0, c1), diagonal)
    return AttnRanges.from_ranges(qr), AttnRanges.from_ranges(kr), ts
