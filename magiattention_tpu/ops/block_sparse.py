"""Block-sparse attention: the shared block-enumeration primitive plus
the block-mask driver for the flex kernel.

Role of reference block-sparse / sparse-load modes (flex_flash_attn.py
sparse options :1110-1123, utils/sparse_utils.py, tests/
test_block_sparse_attn.py): attention where a boolean block mask
[num_q_blocks, num_k_blocks] says which tiles compute. The entry-table
kernel is natively block-sparse — each True block becomes one kernel
ENTRY whose run window bounds exactly that tile, against at most TWO
global slices (FULL for interior tiles; a CAUSAL slice aligned to the
global token diagonal for diagonal-crossing tiles). Entries are emitted
directly — the earlier one-*slice*-per-tile construction put the whole
kept-block list into the kernel's SMEM bounds table (~33k slices x 20 B
at 64k keep-4th: past the ~1 MB SMEM budget, crashing compilation);
per-entry windows cost nothing extra because every entry carries them
anyway.

:class:`BlockEnumeration` is the ONE sparse-core primitive under
prefill, decode, and cascade (ROADMAP item 1): a flattened major->minor
block walk — per major row, the sorted list of minor blocks it touches —
with the row tables and clamped entry lookup every sparse consumer
needs. The flex kernels' compact sparse grid walks it over the entry
tables (``ops/flex_attn.py``) and the split-KV decode kernel walks it
over the paged block table (``serving/decode_attn.py``).
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

from .block_meta import (
    FlexAttnBlockMeta,
    _sub_area,
    assemble_block_meta,
)


# ---------------------------------------------------------------------------
# the shared block-enumeration primitive
# ---------------------------------------------------------------------------


def row_tables(major, num_rows: int):
    """Per-major-row ``(start, count)`` over a SORTED major array.

    Works on numpy arrays and traced jax arrays alike (searchsorted) —
    these are the two extra scalar-prefetch operands of every sparse
    consumer: the flex kernels' compact grid uses them to detect the
    first/last entry of an output row, the row-major kernels to clamp
    dead steps, the decode kernel to locate a (sequence, split) row's
    pages.
    """
    import jax.numpy as jnp

    if isinstance(major, np.ndarray):
        idx = np.arange(num_rows, dtype=major.dtype)
        rs = np.searchsorted(major, idx, side="left").astype(np.int32)
        re = np.searchsorted(major, idx, side="right").astype(np.int32)
        return rs, (re - rs).astype(np.int32)
    idx = jnp.arange(num_rows, dtype=major.dtype)
    rs = jnp.searchsorted(major, idx, side="left").astype(jnp.int32)
    re = jnp.searchsorted(major, idx, side="right").astype(jnp.int32)
    return rs, re - rs


def clamped_entry(row_start, row_count, i, j):
    """Entry index for step j of major row i: the row's entries occupy
    ``row_start[i] .. row_start[i]+row_count[i]``; steps past the count
    clamp to the last live entry (same minor block -> no fresh DMA) and
    the caller skips compute via ``j < row_count[i]``. Shared by the
    kernel bodies and the launchers' minor-side index maps — the two
    MUST agree or the DMA'd block and the entry the kernel evaluates
    silently diverge."""
    import jax.numpy as jnp

    if isinstance(row_start, np.ndarray):
        return row_start[i] + min(j, max(int(row_count[i]) - 1, 0))
    return row_start[i] + jnp.minimum(j, jnp.maximum(row_count[i] - 1, 0))


@dataclasses.dataclass(frozen=True, eq=False)
class BlockEnumeration:
    """Flattened major->minor block walk: entry e pairs major row
    ``major[e]`` with minor block ``minor[e]``; ``major`` is sorted
    ascending so each row's entries are consecutive —
    ``row_start[i] .. row_start[i]+row_count[i]``. Arrays may be host
    numpy (kernel planning) or traced jax values (the decode block
    table); a row with no entries has ``row_count == 0``."""

    num_rows: int
    major: np.ndarray  # [E] sorted row id per entry
    minor: np.ndarray  # [E] minor block id per entry
    row_start: np.ndarray  # [num_rows]
    row_count: np.ndarray  # [num_rows]

    @property
    def num_entries(self) -> int:
        return int(self.major.shape[0])

    def entry(self, i, j):
        """Clamped entry index of step j in row i (see
        :func:`clamped_entry`)."""
        return clamped_entry(self.row_start, self.row_count, i, j)

    @staticmethod
    def from_sorted(major, minor, num_rows: int) -> "BlockEnumeration":
        """Wrap already-sorted (major, minor) arrays — the flex entry
        tables' orientation (numpy or traced jax)."""
        rs, rc = row_tables(major, num_rows)
        return BlockEnumeration(
            num_rows=int(num_rows),
            major=major,
            minor=minor,
            row_start=rs,
            row_count=rc,
        )

    @staticmethod
    def from_block_table(
        block_table, num_splits: int, *, num_pages: int | None = None
    ) -> "BlockEnumeration":
        """The split-KV decode walk: rows are (sequence, split) pairs,
        minors the page ids of the paged block table ``[b, MPP]``
        (traced jax values at decode time). Row counts are uniform
        (``MPP // num_splits`` pages per split), so the clamped lookup
        degenerates to plain flat indexing — the same primitive, fully
        occupied.

        ``num_pages`` (ISSUE 17 hardening): the page-pool size. When
        given, every table entry is validated against ``[0, num_pages)``
        and an out-of-pool id raises a typed ``ValueError`` naming the
        slot row and the offending page id — a wider table used to be
        accepted silently and the kernel's page DMA would read another
        sequence's KV (or out of bounds). Validation needs host values:
        pass it from host-side builders (the unified-tick path); the
        traced decode-time call leaves it ``None``.
        """
        import jax.numpy as jnp

        b, mpp = block_table.shape
        if mpp % num_splits:
            raise ValueError(
                f"block enumeration: table width {mpp} is not divisible "
                f"by num_splits {num_splits}"
            )
        if num_pages is not None:
            host = block_table
            if not isinstance(host, np.ndarray):
                try:
                    host = np.asarray(host)
                except Exception:
                    raise ValueError(
                        "block enumeration: num_pages validation needs a "
                        "host-side block table (numpy or concrete); a "
                        "traced table cannot be checked — drop num_pages "
                        "on the traced decode path"
                    ) from None
            bad = (host < 0) | (host >= int(num_pages))
            if bad.any():
                r, c = (int(x) for x in np.argwhere(bad)[0])
                raise ValueError(
                    f"block enumeration: slot row {r} entry {c} "
                    f"references page {int(host[r, c])}, outside the "
                    f"{int(num_pages)}-page pool — the block table is "
                    "wider than the pool it indexes"
                )
        pps = mpp // num_splits
        num_rows = b * num_splits
        flat = block_table.reshape(-1).astype(jnp.int32)
        rows = jnp.arange(num_rows, dtype=jnp.int32)
        return BlockEnumeration(
            num_rows=int(num_rows),
            major=jnp.repeat(rows, pps),
            minor=flat,
            row_start=rows * pps,
            row_count=jnp.full((num_rows,), pps, jnp.int32),
        )


# ---------------------------------------------------------------------------
# the unified serving tick enumeration (ISSUE 17)
# ---------------------------------------------------------------------------


def _pow2_bucket(n: int, lo: int = 1) -> int:
    """Next power of two >= max(n, lo) — the tick geometry's capacity
    bucket (log2 quantization at one step per octave, the coarse end of
    the tuning fingerprint's ``_log2_bucket`` family). Padding to the
    bucket is what keeps the traced tick program count bounded: geometry
    follows the tick budget's bucket, never the request mix."""
    n = max(int(n), int(lo))
    return 1 << (n - 1).bit_length()


@dataclasses.dataclass(frozen=True)
class TickSegment:
    """One request's row span inside a :class:`TickEnumeration`.

    - ``kind``: ``"decode"`` (one q row) or ``"prefill"`` (one row per
      chunk token).
    - ``key``: the caller's demux handle (opaque; the engine uses the
      item index).
    - ``row_lo .. row_hi``: the request's MAIN rows, in q-row order.
    - ``prefix_row``: a cascade member's shared-prefix partial row
      (merged into the single main row through ``ops/correction``), or
      -1 when the request has no in-tick prefix phase.
    """

    kind: str
    key: object
    row_lo: int
    row_hi: int
    prefix_row: int = -1

    @property
    def num_rows(self) -> int:
        return self.row_hi - self.row_lo


class TickEnumeration:
    """Composer of ONE serving tick's attention work into a single
    block-sparse enumeration (ISSUE 17 tentpole).

    Every tick row is ONE query token against a page-table prefix:

    - a **decode** step is one row — pages = the slot's block-table
      prefix, valid = the post-append sequence length;
    - a **prefill chunk** token ``i`` (chunk start offset ``start``) is
      one row — pages = the history's page prefix, valid =
      ``start + i + 1``. Causal masking IS prefix-length masking, so
      chunked prefill needs no mask machinery beyond what split-KV
      decode already has;
    - a **cascade** member contributes a suffix main row (pages past the
      shared prefix, table-relative valid) plus a ``prefix_row`` over
      the shared pages — group members' prefix rows carry identical
      page lists inside the one launch (the batched-prefix read), and
      each member's two partials merge through ``correct_attn_out_lse``
      at demux.

    ``finalize()`` pads rows/entries to power-of-two capacity buckets
    (``_pow2_bucket``): padding rows have ``valid = 0`` (the split-KV
    uncovered convention makes them exact ``(0, -inf)`` no-ops) and
    padding entries use page id 0 (always pool-valid, compute-masked by
    the valid length). The padded table is what
    :meth:`BlockEnumeration.from_block_table` turns into the ONE
    enumeration the sparse kernel walks.
    """

    def __init__(self, page_size: int, *, min_rows: int = 8):
        self.page_size = int(page_size)
        self.min_rows = int(min_rows)
        self._pages: list[tuple[int, ...]] = []  # per-row page prefix
        self._valid: list[int] = []  # per-row covered tokens
        self._segments: list[TickSegment] = []
        self._capacity: tuple[int, int] | None = None

    # -- composition --

    def _add_row(self, pages, valid: int, what: str, key) -> int:
        pages = tuple(int(p) for p in pages)
        valid = int(valid)
        if valid < 0 or valid > len(pages) * self.page_size:
            raise ValueError(
                f"tick enumeration: {what} row for {key!r} covers "
                f"{valid} tokens but its {len(pages)} pages hold at most "
                f"{len(pages) * self.page_size} — the page prefix does "
                "not cover the row's history"
            )
        self._capacity = None
        self._pages.append(pages)
        self._valid.append(valid)
        return len(self._pages) - 1

    def add_decode(
        self,
        key,
        pages,
        valid_len: int,
        *,
        prefix_pages=(),
        prefix_len: int = 0,
    ) -> TickSegment:
        """One decode row: q = the step's single token, KV = ``pages``
        covering ``valid_len`` tokens (the post-append length). With
        ``prefix_pages`` the row is a cascade member: ``pages`` then
        holds only the SUFFIX pages with ``valid_len`` table-relative
        (sequence length minus ``prefix_len``), and a second row over
        the shared ``prefix_pages`` is added for the prefix partial."""
        prefix_row = -1
        if prefix_pages:
            prefix_row = self._add_row(
                prefix_pages, prefix_len, "cascade-prefix", key
            )
        lo = self._add_row(pages, valid_len, "decode", key)
        seg = TickSegment(
            kind="decode", key=key, row_lo=lo, row_hi=lo + 1,
            prefix_row=prefix_row,
        )
        self._segments.append(seg)
        return seg

    def add_prefill(
        self, key, pages, start: int, tokens: int
    ) -> TickSegment:
        """One prefill chunk: ``tokens`` rows sharing one page prefix
        (which must cover ``start + tokens``); row ``i`` attends
        ``start + i + 1`` tokens — exactly token ``start + i`` of a
        single-shot causal prefill."""
        start, tokens = int(start), int(tokens)
        if tokens <= 0:
            raise ValueError(
                f"tick enumeration: prefill chunk for {key!r} has "
                f"{tokens} tokens; zero-token chunks never enumerate "
                "(the engine's fully-cached early return handles them)"
            )
        pages = tuple(int(p) for p in pages)
        lo = None
        for i in range(tokens):
            r = self._add_row(pages, start + i + 1, "prefill", key)
            lo = r if lo is None else lo
        seg = TickSegment(
            kind="prefill", key=key, row_lo=lo, row_hi=lo + tokens
        )
        self._segments.append(seg)
        return seg

    # -- geometry --

    @property
    def num_rows(self) -> int:
        return len(self._pages)

    @property
    def segments(self) -> tuple[TickSegment, ...]:
        return tuple(self._segments)

    def finalize(self) -> tuple[int, int]:
        """Freeze the capacity buckets; returns ``(row_capacity,
        entry_capacity)``. Idempotent until the next ``add_*``."""
        if self._capacity is None:
            rows = _pow2_bucket(len(self._pages), self.min_rows)
            entries = _pow2_bucket(
                max((len(p) for p in self._pages), default=1), 1
            )
            n_pairs = sum(1 for s in self._segments if s.prefix_row >= 0)
            if n_pairs and rows == len(self._pages):
                # merge-pair padding scatters into a dead row — make
                # sure at least one exists
                rows *= 2
            self._capacity = (rows, entries)
        return self._capacity

    @property
    def row_capacity(self) -> int:
        return self.finalize()[0]

    @property
    def entry_capacity(self) -> int:
        return self.finalize()[1]

    def block_tables(self) -> np.ndarray:
        """Padded ``[row_capacity, entry_capacity]`` int32 page table.
        Dead entries are page id 0: always a valid DMA target, and the
        valid length masks their compute (entry ``j`` starts at token
        ``j * page_size >= valid``)."""
        rows, entries = self.finalize()
        bt = np.zeros((rows, entries), dtype=np.int32)
        for r, pages in enumerate(self._pages):
            if pages:
                bt[r, : len(pages)] = pages
        return bt

    def valid_lens(self) -> np.ndarray:
        """Padded ``[row_capacity]`` int32 covered-token counts (0 for
        padding rows — exact ``(0, -inf)`` partials)."""
        rows, _ = self.finalize()
        sl = np.zeros((rows,), dtype=np.int32)
        sl[: len(self._valid)] = self._valid
        return sl

    def merge_pairs(self) -> np.ndarray:
        """``[pair_capacity, 2]`` (main_row, prefix_row) cascade merge
        pairs, padded to a power-of-two capacity with dead-row self
        pairs (merging two ``(0, -inf)`` partials is a no-op written
        back to the dead row). Empty ``[0, 2]`` when no tick member has
        an in-tick prefix phase — the 0-vs-some pair-shape bit is part
        of the bucketed geometry."""
        rows, _ = self.finalize()
        pairs = [
            (s.row_lo, s.prefix_row)
            for s in self._segments
            if s.prefix_row >= 0
        ]
        if not pairs:
            return np.zeros((0, 2), dtype=np.int32)
        cap = _pow2_bucket(len(pairs), 1)
        dead = rows - 1  # finalize() guarantees it is a padding row
        out = np.full((cap, 2), dead, dtype=np.int32)
        out[: len(pairs)] = pairs
        return out

    def enumeration(self, num_splits: int = 1) -> BlockEnumeration:
        """The ONE :class:`BlockEnumeration` this tick's kernel walks:
        the padded table's (row, split) x page-entry walk, entries
        validated against nothing here (padding ids are 0; callers with
        a pool bound pass ``num_pages`` to ``from_block_table``
        directly). The Pallas launcher rebuilds the identical walk from
        the device-side copy of the same table."""
        return BlockEnumeration.from_block_table(
            self.block_tables(), num_splits
        )


def build_block_meta_from_block_mask(
    block_mask: np.ndarray,  # [nq, nk] bool: which tiles attend
    total_q: int,
    total_k: int,
    *,
    block_q: int = 128,
    block_k: int = 128,
    causal: bool = False,
) -> FlexAttnBlockMeta:
    """One kernel entry per True tile; with ``causal``, tiles strictly
    above the token diagonal are dropped and diagonal-crossing tiles
    reference the global CAUSAL slice (bottom-right aligned: keep
    (q, k) iff k <= q + (total_k - total_q) — standard block-causal
    semantics for square masks)."""
    bm = np.asarray(block_mask, dtype=bool)
    nq = -(-total_q // block_q)
    nk = -(-total_k // block_k)
    if bm.ndim != 2 or bm.shape != (nq, nk):
        # typed error with the full shape context (was a bare assert):
        # the usual way to get here is a block mask built for a
        # different blocking or a transposed (k, q) layout, and a bare
        # assert stripped under ``python -O`` would silently build a
        # corrupt plan
        raise ValueError(
            f"block_sparse: block_mask shape {bm.shape} does not match "
            f"the ({nq}, {nk}) = (ceil({total_q}/{block_q}), "
            f"ceil({total_k}/{block_k})) tile grid of a "
            f"({total_q}, {total_k})-token problem at blocking "
            f"({block_q}, {block_k}) — check the mask's blocking and "
            "that it is laid out [num_q_blocks, num_k_blocks]"
        )
    off = total_k - total_q
    # at most two slices, both spanning the whole problem
    slices = np.asarray(
        [
            (0, total_q, 0, total_k, 0),  # sid 0: FULL
            (0, total_q, 0, total_k, 1),  # sid 1: CAUSAL on the diagonal
        ],
        dtype=np.int64,
    )
    iq, jk = np.nonzero(bm)
    q0 = iq * block_q
    q1 = np.minimum(q0 + block_q, total_q)
    k0 = jk * block_k
    k1 = np.minimum(k0 + block_k, total_k)
    if causal:
        keep = k0 <= (q1 - 1 + off)  # drop tiles fully above the diagonal
        iq, jk, q0, q1, k0, k1 = (
            a[keep] for a in (iq, jk, q0, q1, k0, k1)
        )
        crossing = (k1 - 1) > (q0 + off)  # diagonal passes through tile
        sid = np.where(crossing, 1, 0)
    else:
        sid = np.zeros(iq.shape[0], dtype=np.int64)
    entries = np.stack(
        [iq, jk, sid, q0, q1, k0, k1,
         np.zeros_like(iq), np.zeros_like(iq)],
        axis=1,
    ).astype(np.int64)

    # exact kept area (the bench FLOPs convention counts kept pairs):
    # interior tiles contribute rows*cols vectorized; only the ~nq
    # diagonal-crossing tiles need the per-row causal count (_sub_area)
    rows = q1 - q0
    cols = k1 - k0
    area = int((rows * cols)[sid == 0].sum()) if len(sid) else 0
    if causal:
        for a, b, c, d in zip(
            q0[sid == 1], q1[sid == 1], k0[sid == 1], k1[sid == 1]
        ):
            area += _sub_area(
                int(a), int(b), int(c), int(d), 0, total_q, 0, total_k, 1
            )

    return assemble_block_meta(
        entries, slices, total_q, total_k, block_q, block_k, area
    )


@functools.lru_cache(maxsize=128)
def _cached_bm_meta(mask_bytes, nq, nk, total_q, total_k, bq, bk, causal):
    return build_block_meta_from_block_mask(
        np.frombuffer(mask_bytes, dtype=bool).reshape(nq, nk),
        total_q,
        total_k,
        block_q=bq,
        block_k=bk,
        causal=causal,
    )


def block_sparse_attn_func(
    q,
    k,
    v,
    block_mask: np.ndarray,  # [nq, nk] host bool array — static per mask
    *,
    causal: bool = False,
    scale: float | None = None,
    softcap: float = 0.0,
    sink=None,
    out_dtype=None,
    block_q: int = 128,
    block_k: int = 128,
    head_block: int = 1,
    interpret: bool | None = None,
):
    """Single-device block-sparse attention (reference block-sparse mode).

    q [tq, hq, d], k/v [tk, hk, d]; the block mask is host-side and the
    plan is cached per unique mask.
    """
    from .flex_attn import flex_attn_with_meta

    bm = np.ascontiguousarray(np.asarray(block_mask, dtype=bool))
    meta = _cached_bm_meta(
        bm.tobytes(),
        bm.shape[0],
        bm.shape[1],
        int(q.shape[0]),
        int(k.shape[0]),
        int(block_q),
        int(block_k),
        bool(causal),
    )
    return flex_attn_with_meta(
        q,
        k,
        v,
        meta,
        scale=scale,
        softcap=softcap,
        sink=sink,
        out_dtype=out_dtype,
        head_block=head_block,
        interpret=interpret,
    )
