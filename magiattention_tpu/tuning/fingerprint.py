"""Workload fingerprints: the tuning cache's key space.

A fingerprint captures everything the cost model's answer depends on —
problem extents, head configuration, dtype, and mask-shape statistics
derived from the slice ranges — as INTEGERS ONLY (log2 / milli buckets),
so the stable hash is reproducible across processes and platforms and
nearly-identical workloads (a few tokens of drift in a varlen batch)
share a cache entry instead of re-tuning.

The per-rung entry-count estimates are part of the fingerprint: two masks
with similar aggregate statistics but different tiling behavior (e.g. an
aligned vs misaligned block-causal layout) must not share a winner.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math


def _log2_bucket(x: float, per_octave: int = 8) -> int:
    """log2 of ``x`` quantized to ``per_octave`` steps per octave (0 for
    x <= 0): a ~9% relative bucket — coarse enough to absorb token-count
    jitter, fine enough to separate genuinely different shapes."""
    if x <= 0:
        return 0
    return int(round(math.log2(x) * per_octave))


@dataclasses.dataclass(frozen=True)
class WorkloadFingerprint:
    """Hashable workload identity for the tuning cache."""

    version: int
    generation: str  # TPU generation — winners are chip-specific
    backend: str  # kernel backend @ jax platform — a jnp/CPU-measured
    # winner must never be served to a pallas/TPU run sharing the cache dir
    total_q: int
    total_k: int
    num_heads_q: int
    num_heads_kv: int
    head_dim: int
    dtype: str
    num_slices: int
    covered_frac_milli: int  # unmasked area / (tq * tk), in 1/1000
    mean_k_width_bucket: int  # log2 bucket of the mean slice k-width
    max_k_width_bucket: int
    mean_q_width_bucket: int
    causal_frac_milli: int  # slices with a causal/inv-causal bound
    max_block_q: int  # caller shard constraint (0 = unconstrained)
    max_block_k: int
    entry_est: tuple[tuple[int, int, int], ...]  # (bq, bk, bucketed E)
    # v3: the sparse-grid rung axes (ISSUE 15). ``step_est`` buckets the
    # per-rung static steps extent (max entries on any q block) — the
    # row-skew statistic that decides sparse-vs-row-major, absent from
    # every other field; ``sparse_entry_est`` covers the sparse-only
    # small-tile blockings. Two workloads whose sparse ranking differs
    # can no longer alias one cached winner, and the version bump alone
    # retires every pre-sparse cache entry (a dense winner recorded
    # before the sparse rungs existed must not be served to a workload
    # the new ranking would send to the sparse grid).
    step_est: tuple[tuple[int, int, int], ...] = ()
    sparse_entry_est: tuple[tuple[int, int, int], ...] = ()
    # whether sparse rungs were in the ranking this key describes: a
    # row-major-only decision (``include_sparse=False`` — the
    # distributed builder, ``auto_block_config``) and a full-ranking
    # decision for the SAME mask are different answers and must not
    # share a cache slot in either direction
    sparse_rungs: int = 1
    # the value heads' width where it is not ``head_dim`` (latent
    # attention's 128 beside keys of 192); 0: one width, and the key's
    # payload leaves the field out, so a key made before the kernels took
    # a value width reads as it did
    v_head_dim: int = 0

    # v4 (ISSUE 33): at cp = 1 the SMEM test reads the exact entry count,
    # which admits small rungs for band masks; a (1024, 1024, 1) cached for
    # a sliding window because nothing smaller was allowed is not served.
    # v5 (ISSUE 35): the price holds the bytes a step streams from HBM; a
    # (128, 512, hb) cached for a GQA group 1 mask when no bytes were priced
    # is not served
    # v6 (ISSUE 54): under ``SPARSE_DENSITY_THRESHOLD`` the tie is broken by
    # the table's own order at any extent; a (1024, 1024, 1) cached for a
    # packed mask of a few long documents by the long-sequence lead is not
    # served
    # v7 (ISSUE 56): the tie between (128, 512, hb) and (256, 512, hb) is
    # broken by their price; a (128, 512, hb) cached by the table's order
    # for a mask whose 256 rung is the cheaper is not served
    FINGERPRINT_VERSION = 7

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        if not d["v_head_dim"]:
            del d["v_head_dim"]
        d["entry_est"] = [list(e) for e in self.entry_est]
        d["step_est"] = [list(e) for e in self.step_est]
        d["sparse_entry_est"] = [list(e) for e in self.sparse_entry_est]
        return d

    def stable_hash(self) -> str:
        """Process-independent content hash (the disk cache's file key)."""
        payload = json.dumps(
            self.as_dict(), sort_keys=True, separators=(",", ":")
        )
        return hashlib.sha256(payload.encode()).hexdigest()[:24]


def make_fingerprint(
    q_ranges,
    k_ranges,
    attn_type_map,
    hq: int,
    hk: int,
    *,
    head_dim: int = 128,
    dtype: str = "bfloat16",
    max_block_q: int | None = None,
    max_block_k: int | None = None,
    include_sparse: bool = True,
    v_head_dim: int | None = None,
) -> WorkloadFingerprint:
    """Derive the fingerprint from host-side slice ranges.

    Area uses the exact per-slice closed forms (``common.mask.slice_area``)
    — the same FLOPs proxy the dispatch solver balances on. The per-rung
    entry estimates come from the cost model's exact tile counting, log2-
    bucketed like every other statistic.

    Degenerate (empty) slices are dropped before any statistic is taken —
    the same filter the cost model applies — so sentinel-padded range
    lists fingerprint identically to their clean equivalents. The
    derivation is memoized on the canonical slice bytes: repeat plans pay
    a dict hit, not a per-slice recount (the tuning cache then serves the
    decision itself).
    """
    import jax

    from .. import env
    from .cost_model import _normalize_slices, slices_digest

    q, k, t = _normalize_slices(q_ranges, k_ranges, attn_type_map)
    key = (
        slices_digest(q, k, t),
        env.tpu_generation(),
        f"{env.kernel_backend()}@{jax.default_backend()}",
        int(hq),
        int(hk),
        int(head_dim),
        str(dtype),
        int(max_block_q or 0),
        int(max_block_k or 0),
        int(bool(include_sparse)),
        0 if v_head_dim in (None, head_dim) else int(v_head_dim),
    )
    fp = _FP_MEMO.get(key)
    if fp is None:
        if len(_FP_MEMO) >= _FP_MEMO_CAP:  # crude bound, never grows
            _FP_MEMO.clear()
        fp = _FP_MEMO[key] = _make_fingerprint_impl(q, k, t, *key[1:])
    return fp


# digest-keyed (32 bytes/entry, not the raw range blobs) so dynamic varlen
# jobs with per-batch-unique masks cannot pin large arrays as memo keys
_FP_MEMO: dict = {}
_FP_MEMO_CAP = 512


@dataclasses.dataclass(frozen=True)
class DecodeFingerprint:
    """Workload identity for the ``decode`` tuning kind (ISSUE 4).

    Split-KV decode has no mask-slice statistics — its shape is fully
    described by (batch, page geometry, head config, dtype). Buckets
    follow the same log2 quantization as the flex fingerprint so jittery
    continuous-batching batch sizes share an entry. The ``kind`` field
    keeps decode records disjoint from flex records in the shared tuning
    cache (the file key is the stable hash of the WHOLE payload,
    ``kind`` included).
    """

    kind: str
    version: int
    generation: str
    backend: str  # kernel backend @ jax platform (same rule as flex)
    batch_bucket: int  # log2 bucket of the decode batch size
    num_heads_q: int
    num_heads_kv: int
    head_dim: int
    dtype: str
    page_size: int
    max_pages_bucket: int  # log2 bucket of max_pages_per_seq
    # cascade prefix-group axis (ISSUE 9): 0 = flat decode; otherwise
    # the log2 bucket of the shared-prefix group count — the cascade
    # prefix phase reads ONE hot page set for the whole batch, a
    # different bandwidth profile than flat decode at the same geometry
    prefix_groups_bucket: int = 0

    DECODE_FINGERPRINT_VERSION = 2

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def stable_hash(self) -> str:
        payload = json.dumps(
            self.as_dict(), sort_keys=True, separators=(",", ":")
        )
        return hashlib.sha256(payload.encode()).hexdigest()[:24]


def make_decode_fingerprint(
    batch: int,
    max_pages_per_seq: int,
    page_size: int,
    hq: int,
    hk: int,
    *,
    head_dim: int = 128,
    dtype: str = "bfloat16",
    prefix_groups: int = 0,
) -> DecodeFingerprint:
    """Derive the decode-kind fingerprint (host-side integers only).
    ``prefix_groups > 0`` marks a cascade shared-prefix phase (v2 axis);
    its bucket keeps cascade winners disjoint from flat-decode ones."""
    import jax

    from .. import env

    return DecodeFingerprint(
        kind="decode",
        version=DecodeFingerprint.DECODE_FINGERPRINT_VERSION,
        generation=env.tpu_generation(),
        backend=f"{env.kernel_backend()}@{jax.default_backend()}",
        batch_bucket=_log2_bucket(batch),
        num_heads_q=int(hq),
        num_heads_kv=int(hk),
        head_dim=int(head_dim),
        dtype=str(dtype),
        page_size=int(page_size),
        max_pages_bucket=_log2_bucket(max_pages_per_seq),
        prefix_groups_bucket=(
            0 if prefix_groups <= 0 else 1 + _log2_bucket(prefix_groups)
        ),
    )


@dataclasses.dataclass(frozen=True)
class TickFingerprint:
    """Workload identity for the ``tick`` tuning kind (ISSUE 17).

    The unified serving tick runs the split-KV kernel over a PADDED
    per-row page table whose geometry is the tick budget's capacity
    buckets, not the request mix — so the fingerprint's shape axes are
    exactly those buckets (row capacity, entry capacity) plus the
    head/dtype/page config. ``prefill_rows_bucket`` separates
    decode-dominated from prefill-dominated ticks: the same padded
    geometry reads very different live-KV fractions in the two regimes,
    and their tuned split counts must not alias. ``kind="tick"`` keeps
    the records disjoint from flex/decode in the shared cache."""

    kind: str
    version: int
    generation: str
    backend: str  # kernel backend @ jax platform (same rule as decode)
    row_bucket: int  # log2 bucket of the padded row capacity
    entry_bucket: int  # log2 bucket of the padded entry capacity
    num_heads_q: int
    num_heads_kv: int
    head_dim: int
    dtype: str
    page_size: int
    prefill_rows_bucket: int  # 0 = decode-only; else 1 + log2 bucket

    TICK_FINGERPRINT_VERSION = 1

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def stable_hash(self) -> str:
        payload = json.dumps(
            self.as_dict(), sort_keys=True, separators=(",", ":")
        )
        return hashlib.sha256(payload.encode()).hexdigest()[:24]


def make_tick_fingerprint(
    row_capacity: int,
    entry_capacity: int,
    page_size: int,
    hq: int,
    hk: int,
    *,
    head_dim: int = 128,
    dtype: str = "bfloat16",
    prefill_rows: int = 0,
) -> TickFingerprint:
    """Derive the tick-kind fingerprint (host-side integers only). The
    capacities arrive already power-of-two padded (``TickEnumeration``
    buckets), so the log2 bucket is exact, not lossy."""
    import jax

    from .. import env

    return TickFingerprint(
        kind="tick",
        version=TickFingerprint.TICK_FINGERPRINT_VERSION,
        generation=env.tpu_generation(),
        backend=f"{env.kernel_backend()}@{jax.default_backend()}",
        row_bucket=_log2_bucket(row_capacity),
        entry_bucket=_log2_bucket(entry_capacity),
        num_heads_q=int(hq),
        num_heads_kv=int(hk),
        head_dim=int(head_dim),
        dtype=str(dtype),
        page_size=int(page_size),
        prefill_rows_bucket=(
            0 if prefill_rows <= 0 else 1 + _log2_bucket(prefill_rows)
        ),
    )


def _make_fingerprint_impl(
    q,
    k,
    t,
    generation: str,
    backend: str,
    hq: int,
    hk: int,
    head_dim: int,
    dtype: str,
    max_block_q: int,
    max_block_k: int,
    sparse_rungs: int,
    v_head_dim: int = 0,
) -> WorkloadFingerprint:
    import numpy as np

    from ..common.mask import slice_area
    from ..ops.flex_attn import _AUTO_BLOCK_CONFIGS
    from .cost_model import SPARSE_ONLY_CONFIGS, estimate_entries

    total_q = int(q[:, 1].max()) if q.size else 0
    total_k = int(k[:, 1].max()) if k.size else 0
    area = sum(
        slice_area(int(a), int(b), int(c), int(d), int(mt))
        for (a, b), (c, d), mt in zip(q.tolist(), k.tolist(), t.tolist())
    )
    denom = max(total_q * total_k, 1)
    k_widths = (k[:, 1] - k[:, 0]) if k.size else np.zeros(1, np.int64)
    q_widths = (q[:, 1] - q[:, 0]) if q.size else np.zeros(1, np.int64)
    n = max(int(t.shape[0]), 1)
    causal = int(((t & 1) | ((t & 2) >> 1)).sum())

    entry_est = tuple(
        (bq, bk, _log2_bucket(estimate_entries(q, k, t, bq, bk)[0]))
        for bq, bk, _hb in _AUTO_BLOCK_CONFIGS
    )
    step_est = tuple(
        (bq, bk, _log2_bucket(estimate_entries(q, k, t, bq, bk)[1]))
        for bq, bk, _hb in _AUTO_BLOCK_CONFIGS
    )
    sparse_entry_est = tuple(
        (bq, bk, _log2_bucket(estimate_entries(q, k, t, bq, bk)[0]))
        for bq, bk, _hb in SPARSE_ONLY_CONFIGS
    )
    return WorkloadFingerprint(
        version=WorkloadFingerprint.FINGERPRINT_VERSION,
        generation=generation,
        backend=backend,
        total_q=_log2_bucket(total_q),
        total_k=_log2_bucket(total_k),
        num_heads_q=int(hq),
        num_heads_kv=int(hk),
        head_dim=int(head_dim),
        dtype=str(dtype),
        num_slices=_log2_bucket(n),
        covered_frac_milli=int(round(1000.0 * area / denom)),
        mean_k_width_bucket=_log2_bucket(float(k_widths.mean())),
        max_k_width_bucket=_log2_bucket(float(k_widths.max())),
        mean_q_width_bucket=_log2_bucket(float(q_widths.mean())),
        causal_frac_milli=int(round(1000.0 * causal / n)),
        max_block_q=max_block_q,
        max_block_k=max_block_k,
        entry_est=entry_est,
        step_est=step_est,
        sparse_entry_est=sparse_entry_est,
        sparse_rungs=sparse_rungs,
        v_head_dim=v_head_dim,
    )
