"""Dense attention-mask materialization from (q_range, k_range, mask_type) slices.

The dense [total_q, total_k] boolean mask is the ground-truth semantics of the
whole framework (reference: magi_attention/common/mask.py and the mask-type
doc at functional/flex_flash_attn.py:1247-1341). Used by the jnp oracle, the
sanity checkers, and the area accounting — never on the hot path.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

from .enum import AttnMaskType
from .ranges import AttnRanges


def slice_mask(
    q_start: int,
    q_end: int,
    k_start: int,
    k_end: int,
    mask_type: AttnMaskType | int,
    total_q: int,
    total_k: int,
) -> np.ndarray:
    """Dense bool mask [total_q, total_k] contributed by one attention slice.

    CAUSAL is bottom-right aligned: allow iff (k - k_end) <= (q - q_end).
    INVCAUSAL is top-left aligned: allow iff (k - k_start) >= (q - q_start).
    BICAUSAL is their intersection; FULL is the whole rectangle. Under a
    step s (``AttnMaskType.with_step``) the two bounds compare block
    indices counted from the aligned corner; s = 1 is the line above.
    """
    mt = AttnMaskType(int(mask_type))
    s = mt.step
    q = np.arange(total_q)[:, None]
    k = np.arange(total_k)[None, :]
    m = (q >= q_start) & (q < q_end) & (k >= k_start) & (k < k_end)
    if mt.is_causal_bound:
        m &= (k_end - 1 - k) // s >= (q_end - 1 - q) // s
    if mt.is_inv_causal_bound:
        m &= (k - k_start) // s >= (q - q_start) // s
    return m


def row_key_bounds(
    q: np.ndarray,
    q_start: int,
    q_end: int,
    k_start: int,
    k_end: int,
    mask_type: AttnMaskType | int,
) -> tuple[np.ndarray, np.ndarray]:
    """The key interval [lo, hi) each absolute row ``q`` of the slice
    attends (rows are taken to lie in the q range; hi <= lo: none).

    This is the one place the stepped bound is written for the host: a
    causal bound ends the interval at ``k_end - ((q_end - 1 - q) // s) * s``,
    an inv-causal bound starts it at ``k_start + ((q - q_start) // s) * s``.
    """
    mt = AttnMaskType(int(mask_type))
    s = mt.step
    q = np.asarray(q, dtype=np.int64)
    lo = np.full_like(q, k_start)
    hi = np.full_like(q, k_end)
    if mt.is_causal_bound:
        hi = k_end - (q_end - 1 - q) // s * s
    if mt.is_inv_causal_bound:
        lo = k_start + (q - q_start) // s * s
    return lo, hi


def slice_rows(
    q_start: int,
    q_end: int,
    k_start: int,
    k_end: int,
    mask_type: AttnMaskType | int,
    a: int,
    b: int,
) -> list[tuple[int, int, int, int, AttnMaskType]]:
    """Rows [a, b) of a slice as slices of their own, exactly:
    ``(q_start, q_end, k_start, k_end, type)`` each, in row order.

    A causal bound moves the key end with the bottom row and an
    inv-causal bound the key start with the top row (reference
    slice_maker.py), so at step 1 the rows are one slice of the same
    type. A stepped bound counts its blocks from the slice's corner: where
    the cut leaves part of a block at that corner, those rows (fewer than
    the step) share one bound and come off as a piece without it, and the
    rest keeps the type from a corner a whole number of blocks away.
    Pieces that attend no key are left out.
    """
    mt = AttnMaskType(int(mask_type))
    s = mt.step
    assert q_start <= a < b <= q_end, (q_start, q_end, a, b)
    inv, causal = mt.is_inv_causal_bound, mt.is_causal_bound

    def inv_edge(x):  # first row at or after x that starts an inv block
        return q_start + -(-(x - q_start) // s) * s

    def causal_edge(y):  # last row end at or before y that ends a causal block
        return q_end - -(-(q_end - y) // s) * s

    # cut where the first whole inv block starts and the last whole causal
    # block ends; the fewer-than-s rows outside either may still straddle
    # one block edge of the other bound
    cuts = {a, b}
    ci = inv_edge(a) if inv else a
    cc = causal_edge(b) if causal else b
    cuts.update((ci, cc))
    if inv and causal:
        cuts.update((inv_edge(max(cc, a)), causal_edge(min(ci, b))))
    cuts = sorted(c for c in cuts if a <= c <= b)
    out = []
    for x, y in zip(cuts[:-1], cuts[1:]):
        ks, ke = k_start, k_end
        inv_alive = causal_alive = False
        if inv:
            m, r = divmod(x - q_start, s)
            ks = k_start + m * s
            inv_alive = r == 0
            assert inv_alive or y <= q_start + (m + 1) * s
        if causal:
            m, r = divmod(q_end - y, s)
            ke = k_end - m * s
            causal_alive = r == 0
            assert causal_alive or x >= q_end - (m + 1) * s
        if ke > ks:
            piece = AttnMaskType(int(causal_alive) | int(inv_alive) << 1)
            out.append((x, y, ks, ke, piece.with_step(s)))
    return out


def unstepped_slice_count(
    q_ranges, k_ranges, attn_type_map: Sequence[AttnMaskType | int]
) -> int:
    """Slices the same mask takes from the four unstepped types: a slice
    at step 1 is itself; a stepped one is a rectangle for every run of
    rows that share a non-empty key interval (a block of the staircase)."""
    n = 0
    for (qs, qe), (ks, ke), mt in zip(q_ranges, k_ranges, attn_type_map):
        mt = AttnMaskType(int(mt))
        if mt.step == 1:
            n += 1
            continue
        lo, hi = row_key_bounds(np.arange(qs, qe), qs, qe, ks, ke, mt)
        live = hi > lo
        new = np.ones(live.shape, bool)
        new[1:] = (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1])
        n += int((live & new).sum())
    return n


def _stepped_area(q_start, q_end, k_start, k_end, mt, pos=None) -> int:
    """Area of a stepped slice (left of ``k = pos`` if given), a row at a
    time: vectorized, host-side, and only where the step is above 1."""
    lo, hi = row_key_bounds(
        np.arange(q_start, q_end), q_start, q_end, k_start, k_end, mt
    )
    if pos is not None:
        hi = np.minimum(hi, pos)
    return int(np.maximum(hi - lo, 0).sum())


def _tri_sum(lo: int, hi: int) -> int:
    """Sum of integers lo..hi inclusive (0 if hi < lo)."""
    if hi < lo:
        return 0
    return (hi + lo) * (hi - lo + 1) // 2


def _sum_clamp_linear(n: int, b: int, cap: int) -> int:
    """sum_{i=0}^{n-1} clamp(b + i, 0, cap) in closed form."""
    if cap <= 0 or n <= 0:
        return 0
    n0 = min(max(-b, 0), n)  # below: clamped to 0
    n1 = min(max(cap - b, 0), n)  # from here on: saturated at cap
    return _tri_sum(b + n0, b + n1 - 1) + (n - n1) * cap


def slice_area(
    q_start: int, q_end: int, k_start: int, k_end: int, mask_type: AttnMaskType | int
) -> int:
    """Exact number of unmasked (q, k) pairs in one slice — the FLOPs proxy.

    Closed forms per mask type (reference _make_dispatch_meta.py:541-619
    trapezoid/parallelogram/rectangle formulas, re-derived):

    - FULL: sq * sk.
    - CAUSAL (bottom-right): row q (relative, 0-based) attends
      ``clamp(sk - sq + q + 1, 0, sk)`` keys — a trapezoid/triangle.
    - INVCAUSAL (top-left): row q attends ``clamp(sk - q, 0, sk)`` keys.
    - BICAUSAL: row q attends ``clamp(min(sk-sq+q+1, sk) - max(q, 0), 0, .)``
      intersection band.
    - A stepped type: the rows' intervals summed (:func:`row_key_bounds`).
    """
    sq = q_end - q_start
    sk = k_end - k_start
    if sq <= 0 or sk <= 0:
        return 0
    mt = AttnMaskType(int(mask_type))
    if mt.step > 1:
        return _stepped_area(q_start, q_end, k_start, k_end, mt)
    if mt == AttnMaskType.FULL:
        return sq * sk

    if mt == AttnMaskType.CAUSAL:
        # per-row key count c(q) = clamp(sk - sq + q + 1, 0, sk), q in [0, sq)
        if sk >= sq:
            return _tri_sum(sk - sq + 1, sk)  # trapezoid
        return _tri_sum(1, sk)  # triangle; rows [0, sq - sk) are fully masked
    if mt == AttnMaskType.INVCAUSAL:
        # per-row key count c(q) = clamp(sk - q, 0, sk)
        n_pos = min(sq, sk)
        return _tri_sum(sk - n_pos + 1, sk)
    # BICAUSAL: row band [q, sk - sq + q] in relative coords → constant width
    width = sk - sq + 1
    return sq * width if width > 0 else 0


def slice_area_left_of_k(
    q_start: int,
    q_end: int,
    k_start: int,
    k_end: int,
    mask_type: AttnMaskType | int,
    pos: int,
) -> int:
    """Unmasked (q, k) pairs of the slice with ``k < pos`` — closed form.

    The dynamic solver's k-cut binary search probes this O(log range)
    times per level; the closed forms keep each probe O(1) per rectangle
    (the reference's C++ `magi_attn_ext` accelerates the same loop).

    Per absolute row q (i = q - q_start): the visible keys are
    [lo_i, hi_i) with lo_i = k_start (+ i for inv-causal bounds) and
    hi_i = k_end (- sq + i + 1 for causal bounds); the left-of-pos count
    is ``max(0, min(hi_i, pos) - lo_i)``, summed in closed form.
    """
    sq = q_end - q_start
    sk = k_end - k_start
    if sq <= 0 or sk <= 0 or pos <= k_start:
        return 0
    mt = AttnMaskType(int(mask_type))
    if mt.step > 1:
        return _stepped_area(q_start, q_end, k_start, k_end, mt, pos)
    if mt == AttnMaskType.FULL:
        return sq * (min(pos, k_end) - k_start)
    if mt == AttnMaskType.CAUSAL:
        # hi linear: cnt_i = clamp((sk - sq + 1) + i, 0, pos - k_start)
        return _sum_clamp_linear(sq, sk - sq + 1, pos - k_start)
    if mt == AttnMaskType.INVCAUSAL:
        # lo linear: cnt_i = max(0, P - i), P = min(pos, k_end) - k_start
        p = min(pos, k_end) - k_start
        n_pos = min(p, sq)
        return _tri_sum(p - n_pos + 1, p)
    # BICAUSAL: constant band width w above the pos-crossing row, then a
    # decreasing tail
    w = sk - sq + 1
    if w <= 0:
        return 0
    h0 = k_end - sq + 1  # absolute exclusive hi of row i=0
    n_const = min(max(pos - h0 + 1, 0), sq)  # rows fully left of pos
    total = n_const * w
    p2 = pos - k_start
    hi_idx = min(sq, p2)  # rows i < p2 have a positive partial count
    if hi_idx > n_const:
        total += _tri_sum(p2 - hi_idx + 1, p2 - n_const)
    return total


def make_attn_mask_from_ranges(
    q_ranges: AttnRanges | Sequence[Sequence[int]],
    k_ranges: AttnRanges | Sequence[Sequence[int]],
    attn_type_map: Sequence[AttnMaskType | int],
    total_q: int,
    total_k: int,
) -> np.ndarray:
    """Union of all slice masks — the dense ground-truth mask [total_q, total_k]."""
    q_list = (
        q_ranges.to_naive_ranges() if isinstance(q_ranges, AttnRanges) else q_ranges
    )
    k_list = (
        k_ranges.to_naive_ranges() if isinstance(k_ranges, AttnRanges) else k_ranges
    )
    assert len(q_list) == len(k_list) == len(attn_type_map)
    mask = np.zeros((total_q, total_k), dtype=bool)
    for (qs, qe), (ks, ke), mt in zip(q_list, k_list, attn_type_map):
        mask |= slice_mask(qs, qe, ks, ke, mt, total_q, total_k)
    return mask


def total_area(
    q_ranges: AttnRanges,
    k_ranges: AttnRanges,
    attn_type_map: Sequence[AttnMaskType | int],
) -> int:
    """Sum of per-slice areas (assumes slices do not double-count pairs)."""
    return sum(
        slice_area(q.start, q.end, k.start, k.end, mt)
        for q, k, mt in zip(q_ranges, k_ranges, attn_type_map)
    )
