"""Paged KV cache: static-shape page pool + per-sequence block tables.

The serving subsystem's storage layer (ISSUE 4 tentpole, after FlashInfer's
block-sparse KV formats, arxiv 2501.01005): decode-time KV history lives in
a fixed pool of fixed-size pages so the jitted decode step sees ONE static
shape regardless of how long any sequence has grown — growth changes only
the *values* of ``seq_lens``/``block_tables``, never an array shape, which
is what keeps the jit re-trace count constant across a sequence's lifetime
(asserted by ``tests/test_serving/test_kv_cache.py``).

Layout:

- page pool  ``k_pages`` / ``v_pages``: ``[num_pages, page_size, kv_heads,
  head_dim]`` — a page is the unit of allocation AND the decode kernel's
  K-side DMA granularity (one block per grid step).
- block tables ``[max_seqs, max_pages_per_seq]`` int32: sequence slot ->
  ordered page ids (unallocated entries are 0 — harmless, reads beyond
  ``seq_lens`` are masked everywhere).
- ``seq_lens`` ``[max_seqs]`` int32: tokens currently stored per slot.

All update ops are functional (``x.at[...]``) so callers can donate the
cache buffers through jit (``jax.jit(step, donate_argnums=...)``) and XLA
updates the pool in place; they are index-arithmetic only, so ``vmap``
over a leading batch axis composes (``append_kv`` is already batched).

Page bookkeeping (which pages are free, which slot owns what) is
host-side Python in :class:`PageAllocator` — allocation decisions happen
at admission time, not inside jitted code, mirroring how real serving
engines split host scheduling from device compute.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np


class PageAllocatorError(RuntimeError):
    """Typed base of every :class:`PageAllocator` failure (ISSUE 9): a
    caller that wants to treat resource pressure as backpressure catches
    THIS, not bare RuntimeError — and bookkeeping-corruption bugs get
    their own subclasses so they can never be mistaken for pressure."""


class InvalidFreeError(PageAllocatorError, KeyError):
    """``free()`` (or a ref release) on a slot/page the allocator does
    not currently own — a double-free or a never-allocated id. Raised
    BEFORE any free-list mutation: the historical failure mode here is
    silent free-list corruption (the same page handed to two sequences),
    so misuse is loud and state-preserving. Subclasses ``KeyError`` for
    callers of the pre-ISSUE-9 contract."""


class PageShareError(PageAllocatorError):
    """Refcount misuse on the copy-on-write sharing surface
    (``retain``/``release_pages``/``cow_page``): the page named is not
    resident, or a CoW split was requested on an unshared page."""


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PagedKVCache:
    """Device state of the paged cache (a pytree of four arrays)."""

    k_pages: jax.Array  # [num_pages, page_size, kv_heads, head_dim]
    v_pages: jax.Array  # same shape
    block_tables: jax.Array  # [max_seqs, max_pages_per_seq] int32 page ids
    seq_lens: jax.Array  # [max_seqs] int32 tokens stored per slot

    # -- static geometry (derived from shapes; no aux data needed) --
    @property
    def num_pages(self) -> int:
        return self.k_pages.shape[0]

    @property
    def page_size(self) -> int:
        return self.k_pages.shape[1]

    @property
    def num_kv_heads(self) -> int:
        return self.k_pages.shape[2]

    @property
    def head_dim(self) -> int:
        return self.k_pages.shape[3]

    @property
    def max_seqs(self) -> int:
        return self.block_tables.shape[0]

    @property
    def max_pages_per_seq(self) -> int:
        return self.block_tables.shape[1]

    @property
    def max_seq_len(self) -> int:
        return self.max_pages_per_seq * self.page_size

    def tree_flatten(self):
        return (
            (self.k_pages, self.v_pages, self.block_tables, self.seq_lens),
            None,
        )

    @classmethod
    def tree_unflatten(cls, _aux, children):
        return cls(*children)


def make_paged_kv_cache(
    num_pages: int,
    page_size: int,
    num_kv_heads: int,
    head_dim: int,
    *,
    max_seqs: int,
    max_pages_per_seq: int | None = None,
    dtype=jnp.bfloat16,
) -> PagedKVCache:
    """Zero-initialized cache. ``max_pages_per_seq`` bounds a sequence's
    KV history (block-table width); defaults to the whole pool."""
    if page_size % 8 != 0:
        raise ValueError(
            f"page_size {page_size} must be a multiple of 8 (TPU sublane "
            "tiling of the page's token axis); got "
            f"{page_size} % 8 == {page_size % 8}"
        )
    if max_pages_per_seq is None:
        max_pages_per_seq = num_pages
    shape = (num_pages, page_size, num_kv_heads, head_dim)
    return PagedKVCache(
        k_pages=jnp.zeros(shape, dtype),
        v_pages=jnp.zeros(shape, dtype),
        block_tables=jnp.zeros((max_seqs, max_pages_per_seq), jnp.int32),
        seq_lens=jnp.zeros((max_seqs,), jnp.int32),
    )


def append_kv(
    cache: PagedKVCache,
    slots: jax.Array,  # [b] int32 sequence slots (must be distinct)
    k_new: jax.Array,  # [b, kv_heads, head_dim] this step's K per sequence
    v_new: jax.Array,
) -> PagedKVCache:
    """Append ONE token of KV per sequence (the decode-step write).

    Static shapes in, static shapes out — the positions come from
    ``seq_lens``, so a growing sequence re-runs the SAME traced program.
    Slots must be distinct within the batch (two writes to one slot in a
    single step would race in the scatter).

    The caller must have INSTALLED enough pages for the new position
    (``PageAllocator.extend`` + :func:`assign_block_table`): unreserved
    block-table entries read 0, so a write past the slot's reservation
    would land on page 0 — which may belong to another live sequence.
    :class:`~magiattention_tpu.serving.engine.ServingEngine` grows
    reservations automatically before each step; only the saturating
    ``max_seq_len`` bound is enforced device-side (shapes are static,
    the reservation is host state).
    """
    ps = cache.page_size
    pos = cache.seq_lens[slots]  # [b]
    page_slot = jnp.minimum(pos // ps, cache.max_pages_per_seq - 1)
    page = jnp.take_along_axis(
        cache.block_tables[slots], page_slot[:, None], axis=1
    )[:, 0]
    off = pos % ps
    # a full slot (pos == max_seq_len) must not wrap onto page 0: drop it
    page = jnp.where(pos < cache.max_seq_len, page, cache.num_pages)
    return PagedKVCache(
        k_pages=cache.k_pages.at[page, off].set(
            k_new.astype(cache.k_pages.dtype), mode="drop"
        ),
        v_pages=cache.v_pages.at[page, off].set(
            v_new.astype(cache.v_pages.dtype), mode="drop"
        ),
        block_tables=cache.block_tables,
        seq_lens=cache.seq_lens.at[slots].add(
            jnp.where(pos < cache.max_seq_len, 1, 0).astype(jnp.int32)
        ),
    )


def write_prefill_kv(
    cache: PagedKVCache,
    slot,  # scalar int sequence slot
    k: jax.Array,  # [t, kv_heads, head_dim] (t static; may be padded)
    v: jax.Array,
    length=None,  # traced valid token count (None = all t rows)
) -> PagedKVCache:
    """Write a prefill's KV into the slot's pages starting at its current
    ``seq_lens`` position. ``t`` is the static (padded) row count;
    ``length`` masks the tail, so one traced program serves every prompt
    length up to ``t``."""
    t = k.shape[0]
    ps = cache.page_size
    if length is None:
        length = t
    length = jnp.asarray(length, jnp.int32)
    start = cache.seq_lens[slot]
    pos = start + jnp.arange(t, dtype=jnp.int32)
    valid = (jnp.arange(t) < length) & (pos < cache.max_seq_len)
    page_slot = jnp.minimum(pos // ps, cache.max_pages_per_seq - 1)
    page = jnp.take(cache.block_tables[slot], page_slot)
    page = jnp.where(valid, page, cache.num_pages)  # OOB -> dropped
    off = pos % ps
    return PagedKVCache(
        k_pages=cache.k_pages.at[page, off].set(
            k.astype(cache.k_pages.dtype), mode="drop"
        ),
        v_pages=cache.v_pages.at[page, off].set(
            v.astype(cache.v_pages.dtype), mode="drop"
        ),
        block_tables=cache.block_tables,
        seq_lens=cache.seq_lens.at[slot].add(
            jnp.minimum(length, cache.max_seq_len - start)
        ),
    )


def gather_kv(
    cache: PagedKVCache,
    slot,  # scalar int sequence slot
    max_len: int | None = None,  # static row count of the result
) -> tuple[jax.Array, jax.Array]:
    """Contiguous ``[max_len, kv_heads, head_dim]`` K/V for one sequence
    (rows past ``seq_lens[slot]`` are zeroed). The round-trip oracle for
    the paged layout — ``append``/``write_prefill`` followed by ``gather``
    must equal the contiguous KV stream (tested property)."""
    if max_len is None:
        max_len = cache.max_seq_len
    ps = cache.page_size
    pos = jnp.arange(max_len, dtype=jnp.int32)
    page_slot = jnp.minimum(pos // ps, cache.max_pages_per_seq - 1)
    page = jnp.take(cache.block_tables[slot], page_slot)
    off = pos % ps
    valid = (pos < cache.seq_lens[slot])[:, None, None]
    k = jnp.where(valid, cache.k_pages[page, off], 0)
    v = jnp.where(valid, cache.v_pages[page, off], 0)
    return k, v


def assign_block_table(
    cache: PagedKVCache,
    slot: int,
    pages: Sequence[int],
    *,
    keep_len: bool | int = False,
) -> PagedKVCache:
    """Install a slot's page list (host-side admission; ``pages`` come
    from :class:`PageAllocator`).

    ``keep_len`` sets the slot's stored-token count:

    - ``False`` (default): reset to 0 — a fresh admission.
    - ``True``: keep the current value — a growth re-assignment
      extending a live sequence's reservation.
    - an ``int`` N: set to exactly N — the prefix-fork path installs a
      shared prefix whose first N tokens are ALREADY materialized in the
      shared pages (``keep_len=0`` is therefore identical to ``False``).
      N past the installed pages' capacity is REJECTED: a fork claiming
      tokens beyond its page list would decode block-table padding
      (page 0 — possibly another live sequence's data) as its own KV.
    """
    if len(pages) > cache.max_pages_per_seq:
        raise ValueError(
            f"block table for slot {slot} would overflow: {len(pages)} "
            f"pages > max_pages_per_seq {cache.max_pages_per_seq} "
            f"(block_tables shape {tuple(cache.block_tables.shape)}, "
            f"pages {list(pages)[:8]}{'...' if len(pages) > 8 else ''})"
        )
    row = np.zeros((cache.max_pages_per_seq,), np.int32)
    row[: len(pages)] = np.asarray(pages, np.int32)
    if keep_len is True:
        seq_lens = cache.seq_lens
    else:
        n = 0 if keep_len is False else int(keep_len)
        if not 0 <= n <= len(pages) * cache.page_size:
            raise ValueError(
                f"keep_len={n} out of range for slot {slot}: the "
                f"{len(pages)}-page installed list holds at most "
                f"{len(pages) * cache.page_size} tokens "
                f"(page_size {cache.page_size}); a fork claiming tokens "
                "beyond its pages would decode block-table padding "
                "(page 0) as its own KV"
            )
        seq_lens = cache.seq_lens.at[slot].set(n)
    return PagedKVCache(
        k_pages=cache.k_pages,
        v_pages=cache.v_pages,
        block_tables=cache.block_tables.at[slot].set(jnp.asarray(row)),
        seq_lens=seq_lens,
    )


def copy_page(cache: PagedKVCache, src_page: int, dst_page: int) -> PagedKVCache:
    """Device-side page copy (the data half of a copy-on-write split):
    ``dst_page``'s K/V payload becomes a bit-copy of ``src_page``'s.
    Functional like every cache update — pair with
    :func:`swap_block_table_page` to point the writing slot at its
    private copy."""
    return PagedKVCache(
        k_pages=cache.k_pages.at[dst_page].set(cache.k_pages[src_page]),
        v_pages=cache.v_pages.at[dst_page].set(cache.v_pages[src_page]),
        block_tables=cache.block_tables,
        seq_lens=cache.seq_lens,
    )


def swap_block_table_page(
    cache: PagedKVCache, slot: int, page_idx: int, new_page: int
) -> PagedKVCache:
    """Point one block-table entry of ``slot`` at ``new_page`` (the
    table half of a copy-on-write split; lengths untouched)."""
    return PagedKVCache(
        k_pages=cache.k_pages,
        v_pages=cache.v_pages,
        block_tables=cache.block_tables.at[slot, page_idx].set(
            jnp.int32(new_page)
        ),
        seq_lens=cache.seq_lens,
    )


def reset_slot(cache: PagedKVCache, slot: int) -> PagedKVCache:
    """Logical free of a slot's stored tokens (page recycling is the
    allocator's job; stale page contents are never read once the length
    is 0)."""
    return PagedKVCache(
        k_pages=cache.k_pages,
        v_pages=cache.v_pages,
        block_tables=cache.block_tables,
        seq_lens=cache.seq_lens.at[slot].set(0),
    )


# ---------------------------------------------------------------------------
# device-sharded storage (ISSUE 12): the pool's arrays live on a mesh,
# the allocator below stays host-side — one logical free list over
# device-sharded pages
# ---------------------------------------------------------------------------


def kv_head_sharding(mesh, axis_name: str = "tp"):
    """The TP decode layout for the page pools (after FlashInfer's /
    SNIPPETS' ``sharded_paged_attention``): pages split across
    ``axis_name`` on the **KV-head axis** — every chip holds every page,
    but only its head slice, so a decode step reads its local heads with
    zero collectives (softmax is per-head; no LSE ever crosses the
    axis)."""
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec(None, None, axis_name, None))


def shard_kv_cache(
    cache: PagedKVCache, mesh, axis_name: str = "tp"
) -> PagedKVCache:
    """Pin a cache's device storage to ``mesh``: ``k_pages``/``v_pages``
    sharded on the KV-head axis (:func:`kv_head_sharding`), block tables
    and ``seq_lens`` replicated (they are host-written control state
    every shard needs whole). The :class:`PageAllocator` is untouched —
    allocation stays ONE host-side logical free list regardless of how
    many chips store the pages, which is the disaggregated-serving
    contract (ISSUE 12): admission decisions are global, storage is not.

    A one-device mesh degenerates to plain placement (how the tiered
    engine pins each tier's pool to its own mesh slice)."""
    from jax.sharding import NamedSharding, PartitionSpec

    from ..utils.instrument import named_scope

    tp = int(mesh.shape.get(axis_name, 1)) if axis_name else 1
    if tp > 1 and cache.num_kv_heads % tp:
        raise ValueError(
            f"shard_kv_cache: kv_heads {cache.num_kv_heads} not divisible "
            f"by the {axis_name}={tp} mesh axis — the KV-head-sharded "
            "layout needs equal head slices per chip"
        )
    pages = kv_head_sharding(mesh, axis_name)
    host = NamedSharding(mesh, PartitionSpec())
    with named_scope("magi_kvcache_shard"):
        # re-pinning moves pool storage across chips: a wire hop on
        # real hardware, scoped so a device trace attributes it
        return PagedKVCache(
            k_pages=jax.device_put(cache.k_pages, pages),
            v_pages=jax.device_put(cache.v_pages, pages),
            block_tables=jax.device_put(cache.block_tables, host),
            seq_lens=jax.device_put(cache.seq_lens, host),
        )


class PageAllocator:
    """Host-side page bookkeeping: free list, slot ownership, occupancy.

    Pure Python by design — admission control and page recycling are
    scheduler decisions made between device steps, and keeping them off
    the device means the jitted decode step never depends on pool state.
    Occupancy numbers feed the ``magi_kvcache_*`` telemetry gauges
    (``telemetry.record_kvcache_state``).

    ISSUE 9 adds **per-page refcounts**: a resident page may be
    referenced by several sequences (a copy-on-write shared prefix) and
    by the prefix cache itself, yet it occupies pool capacity exactly
    once — ``pages_in_use`` counts residency, not references, which is
    the memory win shared system prompts buy. ``fork`` admits a sequence
    onto existing shared pages, ``cow_page`` splits one page the moment
    a writer needs it private, and ``free``/``release_pages`` decrement
    refs, recycling a page only when its last reference drops.
    """

    def __init__(
        self,
        num_pages: int,
        page_size: int,
        max_seqs: int,
        max_pages_per_seq: int,
    ):
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.max_seqs = int(max_seqs)
        self.max_pages_per_seq = int(max_pages_per_seq)
        self._free_pages: list[int] = list(range(num_pages - 1, -1, -1))
        self._free_slots: list[int] = list(range(max_seqs - 1, -1, -1))
        self._slot_pages: dict[int, list[int]] = {}
        # refcount per RESIDENT page (absent key = page is on the free
        # list); every owner — sequence slot or prefix cache — holds one
        self._page_refs: dict[int, int] = {}
        # high-water mark of pages_in_use over this allocator's lifetime
        # (ISSUE 14 pool forensics; updated on every page pop)
        self._peak_pages_in_use = 0

    def pages_needed(self, num_tokens: int) -> int:
        return -(-max(int(num_tokens), 0) // self.page_size)

    def can_admit(self, num_tokens: int) -> bool:
        from ..resilience import chaos

        if chaos.pool_exhausted():
            return False
        need = max(self.pages_needed(num_tokens), 1)
        return (
            bool(self._free_slots)
            and need <= len(self._free_pages)
            and need <= self.max_pages_per_seq
        )

    def allocate(self, num_tokens: int) -> tuple[int, list[int]]:
        """Admit a sequence needing ``num_tokens`` of KV (rounded up to
        whole pages; at least one). Returns (slot, page list).

        Atomic: every failure (and every chaos injector —
        ``alloc_fail`` / ``pool_exhaust``) raises BEFORE any free-list
        mutation, so a failed admission never leaks state."""
        from ..resilience import chaos

        chaos.maybe_fail("alloc_fail")
        need = max(self.pages_needed(num_tokens), 1)
        if chaos.pool_exhausted() or need > len(self._free_pages):
            raise PageAllocatorError(
                f"PageAllocator: {need} pages requested, "
                f"{0 if chaos.pool_exhausted() else len(self._free_pages)}"
                " free"
            )
        if not self._free_slots:
            raise PageAllocatorError("PageAllocator: no free sequence slot")
        if need > self.max_pages_per_seq:
            raise PageAllocatorError(
                f"PageAllocator: {num_tokens} tokens need {need} pages > "
                f"max_pages_per_seq {self.max_pages_per_seq}"
            )
        slot = self._free_slots.pop()
        pages = [self._pop_free_page() for _ in range(need)]
        self._slot_pages[slot] = pages
        return slot, list(pages)

    def _pop_free_page(self) -> int:
        page = self._free_pages.pop()
        self._page_refs[page] = 1
        in_use = self.num_pages - len(self._free_pages)
        if in_use > self._peak_pages_in_use:
            self._peak_pages_in_use = in_use
        return page

    def _decref(self, page: int) -> bool:
        """Drop one reference; returns True when the page was recycled
        to the free list (last reference gone)."""
        refs = self._page_refs.get(page)
        if refs is None:
            raise InvalidFreeError(
                f"PageAllocator: page {page} is not resident (double "
                "release or never-allocated id)"
            )
        if refs > 1:
            self._page_refs[page] = refs - 1
            return False
        del self._page_refs[page]
        self._free_pages.append(page)
        return True

    def page_ref(self, page: int) -> int:
        """Current reference count of a page (0 if free)."""
        return self._page_refs.get(page, 0)

    def retain(self, pages: Sequence[int]) -> None:
        """Add one reference to each resident page (sharing: a prefix
        fork, or the prefix cache pinning its resident copy). All-or-
        nothing: validation runs before any count moves."""
        for p in pages:
            if p not in self._page_refs:
                raise PageShareError(
                    f"PageAllocator: cannot retain non-resident page {p}"
                )
        for p in pages:
            self._page_refs[p] += 1

    def release_pages(self, pages: Sequence[int]) -> int:
        """Drop one reference per page (the prefix cache's eviction
        path); returns how many pages actually went back to the free
        list."""
        return sum(1 for p in pages if self._decref(p))

    def can_fork(self, shared_pages: Sequence[int], num_tokens: int) -> bool:
        """Would :meth:`fork` succeed right now?"""
        from ..resilience import chaos

        if chaos.pool_exhausted():
            return False
        need = max(self.pages_needed(num_tokens), len(shared_pages), 1)
        grow = need - len(shared_pages)
        return (
            bool(self._free_slots)
            and need <= self.max_pages_per_seq
            and grow <= len(self._free_pages)
            and all(p in self._page_refs for p in shared_pages)
        )

    def fork(
        self, shared_pages: Sequence[int], num_tokens: int
    ) -> tuple[int, list[int]]:
        """Admit a sequence whose first ``len(shared_pages)`` pages are
        an already-resident shared prefix: the shared pages gain one
        reference each (NO copy), and only the pages covering the
        remaining tokens are newly popped. Returns (slot, full page
        list). Atomic like :meth:`allocate` — every check runs before
        any free-list or refcount mutation."""
        from ..resilience import chaos

        chaos.maybe_fail("alloc_fail")
        shared = list(shared_pages)
        need = max(self.pages_needed(num_tokens), len(shared), 1)
        if need > self.max_pages_per_seq:
            raise PageAllocatorError(
                f"PageAllocator: {num_tokens} tokens need {need} pages > "
                f"max_pages_per_seq {self.max_pages_per_seq}"
            )
        grow = need - len(shared)
        if chaos.pool_exhausted() or grow > len(self._free_pages):
            raise PageAllocatorError(
                f"PageAllocator: fork needs {grow} fresh pages, "
                f"{0 if chaos.pool_exhausted() else len(self._free_pages)}"
                " free"
            )
        if not self._free_slots:
            raise PageAllocatorError("PageAllocator: no free sequence slot")
        for p in shared:
            if p not in self._page_refs:
                raise PageShareError(
                    f"PageAllocator: shared prefix page {p} is not resident"
                )
        slot = self._free_slots.pop()
        for p in shared:
            self._page_refs[p] += 1
        pages = shared + [self._pop_free_page() for _ in range(grow)]
        self._slot_pages[slot] = pages
        return slot, list(pages)

    def cow_page(self, slot: int, page_idx: int) -> tuple[int, int]:
        """Copy-on-write split: give ``slot`` a private replacement for
        the SHARED page at ``page_idx`` of its page list. Returns
        ``(old_page, new_page)`` — the caller copies the payload
        (:func:`copy_page`) and swaps the block-table entry
        (:func:`swap_block_table_page`). The old page keeps its other
        references; a refused split (pool exhausted) mutates nothing."""
        pages = self._slot_pages.get(slot)
        if pages is None:
            raise InvalidFreeError(f"PageAllocator: slot {slot} not allocated")
        old = pages[page_idx]
        if self._page_refs.get(old, 0) < 2:
            raise PageShareError(
                f"PageAllocator: page {old} is not shared (ref "
                f"{self._page_refs.get(old, 0)}) — nothing to split"
            )
        if not self._free_pages:
            raise PageAllocatorError(
                "PageAllocator: page pool exhausted (CoW split)"
            )
        new = self._pop_free_page()
        self._page_refs[old] -= 1
        pages[page_idx] = new
        return old, new

    def extend(self, slot: int, total_tokens: int) -> list[int]:
        """Grow a slot's reservation to cover ``total_tokens``; returns the
        FULL page list (existing + newly granted). The grant check runs
        before any page is popped, so a refused extension leaves both
        the pool and the slot's reservation exactly as they were."""
        from ..resilience import chaos

        pages = self._slot_pages.get(slot)
        if pages is None:
            raise InvalidFreeError(f"PageAllocator: slot {slot} not allocated")
        need = max(self.pages_needed(total_tokens), 1)
        if need > self.max_pages_per_seq:
            raise PageAllocatorError(
                f"PageAllocator: {total_tokens} tokens exceed "
                f"max_pages_per_seq {self.max_pages_per_seq}"
            )
        grow = need - len(pages)
        if grow > 0 and (
            chaos.pool_exhausted() or grow > len(self._free_pages)
        ):
            raise PageAllocatorError("PageAllocator: page pool exhausted")
        for _ in range(max(grow, 0)):
            pages.append(self._pop_free_page())
        return list(pages)

    def free(self, slot: int) -> None:
        """Retire a slot: one reference dropped per page (a page shared
        with other sequences or the prefix cache stays resident), slot
        id reusable.

        A double-free — or a never-allocated slot — raises a typed
        :class:`InvalidFreeError` BEFORE anything mutates (ISSUE 9
        satellite): the pre-refcount failure mode was handing the same
        page to two sequences via a corrupted free list."""
        pages = self._slot_pages.get(slot)
        if pages is None:
            raise InvalidFreeError(
                f"PageAllocator: slot {slot} not allocated (double free?)"
            )
        del self._slot_pages[slot]
        for p in reversed(pages):
            self._decref(p)
        self._free_slots.append(slot)

    def reserved_pages(self, slot: int) -> int:
        """Pages currently installed for a slot (0 if unallocated)."""
        return len(self._slot_pages.get(slot, ()))

    def slot_pages(self, slot: int) -> list[int]:
        """The slot's current page list (a copy; host bookkeeping)."""
        pages = self._slot_pages.get(slot)
        if pages is None:
            raise InvalidFreeError(f"PageAllocator: slot {slot} not allocated")
        return list(pages)

    @property
    def pages_in_use(self) -> int:
        return self.num_pages - len(self._free_pages)

    @property
    def peak_pages_in_use(self) -> int:
        """Lifetime high-water mark of resident pages (ISSUE 14): what
        the pool ACTUALLY needed at its worst, next to what it holds
        now — the capacity-planning number."""
        return self._peak_pages_in_use

    @property
    def shared_pages(self) -> int:
        """Resident pages with more than one reference (CoW-shared)."""
        return sum(1 for r in self._page_refs.values() if r > 1)

    @property
    def active_seqs(self) -> int:
        return len(self._slot_pages)

    def page_states(self) -> dict[str, tuple[int, ...]]:
        """Exact ownership class of every page (ISSUE 14 forensics):

        - ``free``: on the free list;
        - ``live``: owned by exactly one sequence slot (ref 1);
        - ``shared``: slot-owned with >1 reference (a CoW-shared prefix
          page, and/or additionally pinned by the prefix trie);
        - ``trie``: resident but owned by NO slot — the prefix cache's
          reference is the only thing keeping it warm.

        The four classes partition ``range(num_pages)`` (asserted by the
        ledger parity tests); a page appears ONCE no matter how many
        references it holds — residency, not reference, is what costs
        pool capacity."""
        slot_owned: set[int] = set()
        for pages in self._slot_pages.values():
            slot_owned.update(pages)
        resident = set(self._page_refs)
        live = tuple(sorted(
            p for p in slot_owned if self._page_refs.get(p, 0) == 1
        ))
        shared = tuple(sorted(
            p for p in slot_owned if self._page_refs.get(p, 0) > 1
        ))
        trie = tuple(sorted(resident - slot_owned))
        free = tuple(sorted(self._free_pages))
        return {"free": free, "live": live, "shared": shared, "trie": trie}

    def occupancy(self) -> dict:
        """Plain-dict pool state (the telemetry payload)."""
        return {
            "pages_total": self.num_pages,
            "pages_in_use": self.pages_in_use,
            "free_pages": self.num_pages - self.pages_in_use,
            "peak_pages_in_use": self._peak_pages_in_use,
            "occupancy_ratio": self.pages_in_use / max(self.num_pages, 1),
            "active_seqs": self.active_seqs,
            "shared_pages": self.shared_pages,
            "page_size": self.page_size,
        }
