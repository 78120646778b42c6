"""User-facing key-cached interface.

Role of reference ``magi_attention/api/magi_attn_interface.py`` +
``dist_attn_runtime_mgr.py``: all expensive planning (dispatch solve, hole
ranges, comm routing, kernel entry tables, pjit tracing) happens once per
unique (mask, shapes, mesh, flags) under a frozen hashable
:class:`DistAttnRuntimeKey`; the hot path is dictionary lookups + jitted
calls.

Typical flow::

    key = magi_attn_varlen_key(cu_seqlens, total, mesh, num_heads=(hq, hk),
                               head_dim=d)
    xq = dispatch(x, key)                       # global -> cp-sharded layout
    out = calc_attn(q, k, v, key)[0]            # distributed flex attention
    y = undispatch(out, key)                    # back to natural order
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import time
from collections import OrderedDict
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import env, telemetry
from ..common.enum import AttnMaskType
from ..common.ranges import AttnRanges
from ..meta.dispatch_meta import DispatchMeta, make_dispatch_meta_from_qk_ranges
from ..meta.plan_fingerprint import (
    PlanReuseCache,
    ReuseEntry,
    canonicalize_mask,
    make_plan_fingerprint,
    try_incremental_update,
)
from ..meta.solver.dispatch_solver import DispatchConfig
from ..parallel.dist_attn import (
    DistAttnPlan,
    build_dist_attn_plan,
    make_attn_params,
    make_dist_attn_fn,
)
from ..parallel.dispatch import dispatch as _dispatch_op
from ..parallel.dispatch import undispatch as _undispatch_op
from .functools import compute_pad_size, pad_at_dim

logger = logging.getLogger("magiattention_tpu")

# reference api/magi_attn_interface.py:157 — mask types may be given as
# one scalar (broadcast to every slice) or a sequence of AttnMaskType
# members / ints / case-insensitive names ("causal", "bi_causal", ...)
GeneralAttnMaskType = str | AttnMaskType | Sequence[str | AttnMaskType]


def _one_mask_type(t) -> int:
    if isinstance(t, str):
        name = t.strip().upper().replace("-", "_")
        # reference spells INVCAUSAL/BICAUSAL with underscores
        name = {"INV_CAUSAL": "INVCAUSAL", "BI_CAUSAL": "BICAUSAL"}.get(
            name, name
        )
        return int(AttnMaskType[name])
    return int(t)


def _coerce_mask_types(attn_type_map, n_slices: int) -> tuple:
    """Accept every GeneralAttnMaskType spelling; a scalar broadcasts to
    all slices (reference wrap_to_list, magi_attn_interface.py:604)."""
    if isinstance(attn_type_map, (str, int, AttnMaskType)):
        return (int(_one_mask_type(attn_type_map)),) * n_slices
    return tuple(_one_mask_type(t) for t in attn_type_map)


def check_flag_comb(
    *,
    cp_axis="cp",
    uneven_shard: bool = False,
    xattn: bool = False,
) -> None:
    """Central validator of illegal env-flag / argument combinations
    (reference ``check_flag_comb``, dist_attn_runtime_mgr.py:452-481).

    Raises ``ValueError`` with an explanation instead of letting an
    unsupported combination fail deep inside planning or — worse —
    silently compute the wrong thing.
    """
    qo = env.is_qo_comm_enable()
    hier_flag = env.is_hierarchical_comm_enable()
    hier_axis = isinstance(cp_axis, (tuple, list))
    backend = env.kernel_backend()

    if backend not in ("pallas", "jnp", "jnp_online"):
        raise ValueError(
            f"MAGI_ATTENTION_KERNEL_BACKEND={backend!r} is not one of "
            "('pallas', 'jnp', 'jnp_online')"
        )
    from ..tuning.autotuner import AUTOTUNE_MODES

    if env.autotune_mode() not in AUTOTUNE_MODES:
        raise ValueError(
            f"MAGI_ATTENTION_AUTOTUNE={env.autotune_mode()!r} is not one "
            f"of {AUTOTUNE_MODES}"
        )
    if env.group_coll_impl() not in env.GROUP_COLL_IMPLS:
        raise ValueError(
            f"MAGI_ATTENTION_GROUP_COLL_IMPL={env.group_coll_impl()!r} is "
            f"not one of {env.GROUP_COLL_IMPLS}"
        )
    env.comm_pad_to()  # raises on a non-power-of-two rung
    env.guard_mode()  # raises on an unknown guard mode
    env.chaos_spec()  # raises on a malformed chaos spec
    if hier_flag and not hier_axis:
        raise ValueError(
            "MAGI_ATTENTION_HIERARCHICAL_COMM=1 requires a 2-D "
            "(inter, intra) cp_axis tuple — hierarchical comm is selected "
            "structurally on TPU (pass cp_axis=('dcn', 'ici') over a 2-D "
            "mesh)"
        )
    if qo and hier_axis:
        raise ValueError(
            "qo-comm cannot be combined with hierarchical comm (reference "
            "check_flag_comb forbids MAGI_ATTENTION_QO_COMM x "
            "MAGI_ATTENTION_HIERARCHICAL_COMM)"
        )
    if qo and uneven_shard:
        raise ValueError(
            "qo-comm requires an even contiguous shard "
            "(uneven_shard=False): the dynamic plane partition is built "
            "over equal per-rank token shards"
        )
    if xattn and (qo or hier_axis or uneven_shard):
        raise ValueError(
            "cross-attention keys support the flat group-cast runtime "
            "only: qo-comm, hierarchical cp_axis and uneven_shard are "
            "all self-attention features (reference limits xattn the "
            "same way via get_xattn_args)"
        )


@dataclasses.dataclass(frozen=True)
class DistAttnRuntimeKey:
    """Frozen hash key for one planned runtime
    (reference dist_attn_runtime_mgr.py:61-119; env flags folded in)."""

    q_ranges: tuple[tuple[int, int], ...]
    k_ranges: tuple[tuple[int, int], ...]
    attn_type_map: tuple[int, ...]
    total_seqlen_q: int
    total_seqlen_k: int
    pad_size: int
    chunk_size: int
    cp_size: int
    cp_axis: str
    num_heads_q: int
    num_heads_kv: int
    head_dim: int
    softcap: float
    has_sink: bool
    sink_fingerprint: int  # hash of the sink values (0 when no sink)
    out_dtype: str
    dispatch_config_repr: str  # planning algorithm choice
    interpret: Optional[bool]
    mesh_id: int  # id() of the mesh (meshes aren't hashable by value)
    flags: tuple
    # autotuned (block_q, block_k, head_block) the plan was built with
    # (ISSUE 2); None = legacy env-flag blocking. Part of the key so a
    # re-tuned winner (e.g. a fresh measure-mode result) plans its own
    # runtime instead of silently reusing one built for another blocking.
    block_config: Optional[tuple[int, int, int]] = None


@dataclasses.dataclass(frozen=True)
class XAttnArgs:
    """Everything a cross-attention module needs about a planned key
    (role of reference ``get_xattn_args``, dist_attn_runtime_mgr.py — the
    cross-attn argument derivation; here host planning is global, so the
    args are read straight off the two dispatch metas)."""

    total_seqlen_q: int  # padded q length (dispatch layout rows)
    total_seqlen_k: int  # padded kv length
    shard_q_len: int  # per-rank q rows
    shard_k_len: int  # per-rank kv rows
    q_position_ids: jax.Array  # [total_q_padded] global pos per slot
    k_position_ids: jax.Array  # [total_k_padded]


class DistAttnRuntimeMgr:
    """Holds everything planned for one key: dispatch meta, plan, jitted fns
    (reference DistAttnRuntimeMgr, :122-407)."""

    def __init__(
        self,
        key: DistAttnRuntimeKey,
        mesh: jax.sharding.Mesh,
        dispatch_meta: DispatchMeta,
        plan: DistAttnPlan,
        attn_fn,
        dist_attn_config=None,
        kv_dispatch_meta: DispatchMeta | None = None,
        pad_size_k: int = 0,
    ):
        self.key = key
        self.mesh = mesh
        self.dispatch_meta = dispatch_meta
        self.kv_dispatch_meta = kv_dispatch_meta  # cross-attn only
        self.pad_size_k = pad_size_k
        self.plan = plan
        self.dist_attn_config = dist_attn_config
        self._attn_fn = attn_fn

    @property
    def is_cross_attn(self) -> bool:
        return self.kv_dispatch_meta is not None

    # -- data movement -----------------------------------------------------

    def dispatch(self, x: jax.Array, pad_value: float = 0.0) -> jax.Array:
        """Global natural-order [total, ...] -> dispatched order (pad+permute).

        Shard the result P(cp_axis) along tokens for the rank-local layout.
        ``pad_value`` fills both the chunk-multiple tail and (uneven shard)
        the per-rank physical pad slots.
        """
        if self.key.pad_size:
            x = pad_at_dim(x, 0, self.key.pad_size, pad_value)
        return _dispatch_op(x, self.dispatch_meta, pad_value=pad_value)

    def undispatch(self, y: jax.Array) -> jax.Array:
        """Dispatched order -> global natural order (pad rows dropped)."""
        out = _undispatch_op(y, self.dispatch_meta)
        if self.key.pad_size:
            out = out[: self.key.total_seqlen_q - self.key.pad_size]
        return out

    def get_position_ids(self) -> jax.Array:
        """Global position of each dispatched slot [cp*shard] int32 (pad
        slots of an uneven shard read 0; their values are never used)."""
        from ..parallel.dispatch import position_ids as _position_ids

        return _position_ids(self.dispatch_meta)

    # -- cross-attention (kv side; reference get_xattn_args role) ----------

    def dispatch_kv(self, x: jax.Array, pad_value: float = 0.0) -> jax.Array:
        """Cross-attn: natural-order memory [total_k, ...] -> the kv
        dispatch layout expected by ``calc_attn``'s k/v arguments."""
        assert self.is_cross_attn, "dispatch_kv needs a cross-attn key"
        if self.pad_size_k:
            x = pad_at_dim(x, 0, self.pad_size_k, pad_value)
        return _dispatch_op(x, self.kv_dispatch_meta, pad_value=pad_value)

    def undispatch_kv(self, y: jax.Array) -> jax.Array:
        """Cross-attn: kv dispatch layout -> natural order (e.g. for
        gradients inspected on the memory side)."""
        assert self.is_cross_attn, "undispatch_kv needs a cross-attn key"
        out = _undispatch_op(y, self.kv_dispatch_meta)
        if self.pad_size_k:
            out = out[: self.key.total_seqlen_k - self.pad_size_k]
        return out

    def get_xattn_args(self) -> XAttnArgs:
        """Derive the cross-attention call arguments for this key
        (reference ``get_xattn_args``)."""
        assert self.is_cross_attn, "get_xattn_args needs a cross-attn key"
        from ..parallel.dispatch import position_ids as _position_ids

        return XAttnArgs(
            total_seqlen_q=self.key.total_seqlen_q,
            total_seqlen_k=self.key.total_seqlen_k,
            shard_q_len=self.dispatch_meta.shard_seqlen,
            shard_k_len=self.kv_dispatch_meta.shard_seqlen,
            q_position_ids=_position_ids(self.dispatch_meta),
            k_position_ids=_position_ids(self.kv_dispatch_meta),
        )

    # -- attention ---------------------------------------------------------

    def calc_attn(self, q, k, v, sink=None):
        """Distributed flex attention on dispatched tensors.

        q [total_padded, hq, d], k/v [total_padded, hk, d] in dispatch order
        (sharded P(cp_axis) or to-be-sharded). Returns
        ``(out, AttnForwardMeta(lse=...))`` in the same layout (reference
        calc_attn returns the forward meta alongside out).

        ``sink``: optional [hq] array overriding the sink captured at
        key-creation time. It is a *traced* argument — pass the live
        (trainable) sink here each step so gradients flow to it without
        re-keying; requires the key to have been created with a sink.

        The forward meta carries the lse and the globally max-reduced
        per-head max logit (reference reduce_max_logits — Muon QK-Clip).
        """
        from ..common.forward_meta import AttnForwardMeta

        if isinstance(q, jax.core.Tracer):
            # jax is tracing the caller: this Python (the runtime's and
            # the Pallas kernels') runs once a trace, and is what the
            # span measures
            with telemetry.span("calc_attn.trace", key=self.key):
                out, lse, max_logits = self._attn_fn(q, k, v, sink)
        else:
            out, lse, max_logits = self._attn_fn(q, k, v, sink)
        return out, AttnForwardMeta(lse=lse, max_logits=max_logits)


class BucketedDistAttnRuntimeMgr(DistAttnRuntimeMgr):
    """Adapter runtime serving a request-shaped mask off a CANONICAL
    (bucket-padded) plan (ISSUE 20, fingerprint-bucketed plan reuse).

    Shares the canonical mgr's dispatch meta, plan, and jitted attn_fn —
    zero solver/trace work per served request. Only the three
    data-movement surfaces are overridden, each one gather built from the
    canonical<->real row maps:

    - ``dispatch``: a single ``take(..., mode="fill")`` from the REAL
      (unpadded) global tensor straight into the canonical dispatched
      layout. Every pad class — the request's chunk pad, the bucket pad,
      uneven-shard physical slots — is an out-of-range index the fill mode
      materializes as ``pad_value``; no pre-padding pass.
    - ``undispatch``: plain gather of the real rows back out (its
      transpose scatter-adds, dropping pad cotangents — gradients flow).
    - ``get_position_ids``: canonical position table with pad slots at 0.

    NOTE the dispatched shapes are the CANONICAL ones (>= the request's
    ``key.total_seqlen_q``); size buffers off the dispatch output, not the
    key fields. ``roll`` and the after-dispatch re-key entry points reject
    bucketed keys with typed errors: both reason in request coordinates,
    which the bucketed layout does not preserve globally.
    """

    def __init__(
        self,
        key: DistAttnRuntimeKey,
        canonical_mgr: DistAttnRuntimeMgr,
        dispatch_idx: np.ndarray,
        undispatch_idx: np.ndarray,
        position_ids: np.ndarray,
    ):
        super().__init__(
            key,
            canonical_mgr.mesh,
            canonical_mgr.dispatch_meta,
            canonical_mgr.plan,
            canonical_mgr._attn_fn,
            dist_attn_config=canonical_mgr.dist_attn_config,
        )
        self.canonical_key = canonical_mgr.key
        self._bucket_dispatch_idx = np.asarray(dispatch_idx, np.int32)
        self._bucket_undispatch_idx = np.asarray(undispatch_idx, np.int32)
        self._bucket_position_ids = np.asarray(position_ids, np.int32)

    def dispatch(self, x: jax.Array, pad_value: float = 0.0) -> jax.Array:
        # x is the REAL [total_real, ...] tensor — every pad slot is an
        # out-of-range source index the fill mode resolves to pad_value
        return jnp.take(
            x,
            jnp.asarray(self._bucket_dispatch_idx),
            axis=0,
            mode="fill",
            fill_value=pad_value,
        )

    def undispatch(self, y: jax.Array) -> jax.Array:
        return jnp.take(y, jnp.asarray(self._bucket_undispatch_idx), axis=0)

    def get_position_ids(self) -> jax.Array:
        return jnp.asarray(self._bucket_position_ids)


class DistAttnRuntimeDict:
    """LRU key -> mgr cache (reference DistAttnRuntimeDict :410-449 +
    the manager interface of DistAttnRuntimeDictManager,
    api/magi_attn_interface.py:64-134: get(key, default), item access,
    keys; ``max_size_per_group`` accepted as the reference's constructor
    spelling)."""

    def __init__(
        self, maxsize: int | None = None, *, max_size_per_group: int | None = None
    ):
        if maxsize is None:
            maxsize = (
                max_size_per_group
                if max_size_per_group is not None
                else env.runtime_dict_size()
            )
        self.maxsize = maxsize
        self._d: OrderedDict[DistAttnRuntimeKey, DistAttnRuntimeMgr] = (
            OrderedDict()
        )

    def get(
        self, key: DistAttnRuntimeKey, default=None
    ) -> Optional[DistAttnRuntimeMgr]:
        mgr = self._d.get(key)
        if mgr is None:
            return default
        self._d.move_to_end(key)
        return mgr

    def put(self, key: DistAttnRuntimeKey, mgr: DistAttnRuntimeMgr) -> None:
        self._d[key] = mgr
        self._d.move_to_end(key)
        while len(self._d) > self.maxsize:
            self._d.popitem(last=False)
            telemetry.record_plan_cache_eviction(cache="runtime")

    def __getitem__(self, key: DistAttnRuntimeKey) -> DistAttnRuntimeMgr:
        mgr = self.get(key)
        if mgr is None:
            raise KeyError(key)
        return mgr

    def __setitem__(self, key, mgr) -> None:
        self.put(key, mgr)

    def keys(self):
        return self._d.keys()

    def __contains__(self, key) -> bool:
        return key in self._d

    def __len__(self) -> int:
        return len(self._d)

    def clear(self, mesh_id: Optional[int] = None) -> None:
        """Drop all entries, or only those planned over the given mesh."""
        if mesh_id is None:
            self._d.clear()
            return
        for k in [k for k in self._d if k.mesh_id == mesh_id]:
            del self._d[k]


_runtime_dict = DistAttnRuntimeDict(maxsize=env.runtime_dict_size())

# reference api surface: the manager class + its live singleton
# (api/magi_attn_interface.py:64 DistAttnRuntimeDictManager +
# dist_attn_runtime_dict_mgr)
DistAttnRuntimeDictManager = DistAttnRuntimeDict
dist_attn_runtime_dict_mgr = _runtime_dict
_most_recent_key: Optional[DistAttnRuntimeKey] = None

# -- fingerprint-bucketed plan reuse (ISSUE 20) ---------------------------
# second-level cache consulted between an exact-key LRU miss and the cold
# solver: PlanFingerprint -> the canonical key whose planned runtime can
# serve every mask in the bucket through a row-map adapter
_plan_reuse_cache = PlanReuseCache()
# reentrancy guard: while resolving a canonical mask we are INSIDE one
# logical cache miss — the nested magi_attn_flex_key call must not record
# a second interface-level cache access (its cold build still records
# record_plan_solver, pricing the ms-saved credit)
_in_canonical_resolve = False


def _resolve_overlap_config(oc, hq, hkv, head_dim, *, hier: bool = False):
    """ONE definition of overlap-config defaulting for every key type:
    None -> env-default knobs (reference env/general.py defaults); then
    auto-degree with untouched factors -> the real hardware cost model
    (reference get_calc/comm_cost_factor, utils/_utils.py)."""
    from ..meta.solver.overlap_solver import OverlapConfig

    if oc is None:
        oc = OverlapConfig(
            degree=env.overlap_degree_default(),
            min_stage_rows=env.min_stage_rows(),
            dynamic_max_degree=env.dynamic_max_degree(),
        )
    if (
        oc.degree is None
        and oc.calc_cost_factor == 1.0
        and oc.comm_cost_factor == 1.0
    ):
        from ..utils.cost import get_calc_cost_factor, get_comm_cost_factor

        gen = env.tpu_generation()
        oc = dataclasses.replace(
            oc,
            calc_cost_factor=get_calc_cost_factor(hq, head_dim, gen),
            comm_cost_factor=get_comm_cost_factor(hkv, head_dim, gen),
            comm_cost_factor_inter=(
                get_comm_cost_factor(hkv, head_dim, gen, link="dcn")
                if hier and oc.comm_cost_factor_inter is None
                else oc.comm_cost_factor_inter
            ),
        )
    return oc


# plan-aware block resolution lives with the tuner (tuning/autotuner.py);
# the keyed-runtime call sites below use it through this alias
from ..tuning.autotuner import resolve_block_config


def _resolve_block_config(*args):
    """The autotuner's block, head-block and grid choice, as the
    ``tile_choice`` span of the key being built."""
    with telemetry.span("tile_choice"):
        return resolve_block_config(*args)


def _key_build_span(build):
    """One ``key_build`` span round a key builder's whole call (ISSUE
    24): the body says how the runtime cache answered
    (``telemetry.annotate_span(cache=...)``), and the key's id, known
    only once the key exists, reaches every span recorded under it."""

    @functools.wraps(build)
    def wrapper(*args, **kwargs):
        with telemetry.span("key_build") as live:
            key = build(*args, **kwargs)
            if live is not None:
                live.set(key=key)
            return key

    return wrapper


def _blocking_from(
    block_config: "tuple[int, int, int] | None", hq: int, hkv: int
) -> tuple[int, int, int]:
    """(block_q, block_k, head_block) for a keyed runtime: the tuner's
    decision, or the legacy env-flag blocking when the tuner stepped
    aside (``block_config`` None). The single fallback rule for every
    keyed entry point — flex, cross, and the after-dispatch re-key."""
    if block_config is not None:
        return block_config
    from ..ops.flex_attn import _auto_head_block

    return (
        env.block_q(),
        env.block_k(),
        _auto_head_block(env.head_block(), hq, max(hq // max(hkv, 1), 1)),
    )


def get_runtime_mgr(key: DistAttnRuntimeKey) -> DistAttnRuntimeMgr:
    mgr = _runtime_dict.get(key)
    if mgr is None:
        raise KeyError(
            f"no runtime planned for this key (cache evicted?): {key}"
        )
    return mgr


def get_most_recent_key() -> DistAttnRuntimeKey:
    """The key most recently created (reference get_most_recent_key — the
    HF-integration hook where the attention module can't thread the key)."""
    assert _most_recent_key is not None, "no key has been created yet"
    return _most_recent_key


def _make_bucketed_mgr(
    key: DistAttnRuntimeKey, canonical_mgr: DistAttnRuntimeMgr, maps
) -> BucketedDistAttnRuntimeMgr:
    """Build the request->canonical adapter runtime: three index tables
    composed host-side from the canonical dispatch meta + row maps."""
    from ..parallel.dispatch import (
        padded_dispatch_indices,
        padded_position_ids,
        padded_undispatch_indices,
    )

    meta = canonical_mgr.dispatch_meta
    real_total = key.total_seqlen_q - key.pad_size
    return BucketedDistAttnRuntimeMgr(
        key,
        canonical_mgr,
        padded_dispatch_indices(meta, maps.canon_to_real, real_total),
        padded_undispatch_indices(meta, maps.real_to_canon),
        padded_position_ids(meta, maps.canon_to_real),
    )


def _try_plan_reuse(
    key: DistAttnRuntimeKey,
    t_lookup: float,
    *,
    mesh,
    sink,
    out_dtype,
    dispatch_config,
    dist_attn_config,
    interpret,
) -> Optional[DistAttnRuntimeKey]:
    """Fingerprint-bucketed second-level lookup (ISSUE 20).

    Called only after an exact-key LRU miss — exact hits stay byte-for-byte
    identical to the reuse-off path. Returns the exact key with a bucketed
    adapter runtime installed, either from a live canonical plan (bucket
    hit: zero solver work, O(total) — or on a pure tail extend O(delta) —
    row-map work) or after cold-solving the canonical mask once
    (fingerprint miss: one solve now serves the whole bucket). Returns
    ``None`` when reuse is off or inapplicable; the caller then records
    the miss and runs the ordinary cold path.
    """
    global _in_canonical_resolve
    if _in_canonical_resolve or env.plan_reuse_mode() != "bucket":
        return None
    if env.is_qo_comm_enable():
        # qo-comm plans a dynamic plane partition exact to the mask —
        # there is no static bucketed dispatch table to adapt onto
        return None
    real_total = key.total_seqlen_q - key.pad_size
    canon = canonicalize_mask(
        key.q_ranges, key.k_ranges, key.attn_type_map, real_total
    )
    if canon is None:
        # unbucketable structure, or already exactly on bucket boundaries —
        # the exact LRU is the right (and only) cache for this mask
        return None
    new_sig = (key.q_ranges, key.k_ranges, key.attn_type_map, real_total)
    fp = make_plan_fingerprint(
        canon,
        chunk_size=key.chunk_size,
        cp_size=key.cp_size,
        cp_axis=key.cp_axis,
        num_heads_q=key.num_heads_q,
        num_heads_kv=key.num_heads_kv,
        head_dim=key.head_dim,
        softcap=key.softcap,
        has_sink=key.has_sink,
        sink_fingerprint=key.sink_fingerprint,
        out_dtype=key.out_dtype,
        dispatch_config_repr=key.dispatch_config_repr,
        interpret=key.interpret,
        mesh_id=key.mesh_id,
        flags=key.flags,
    )
    entry = _plan_reuse_cache.get(fp)
    canonical_mgr = (
        _runtime_dict.get(entry.canonical_key) if entry is not None else None
    )
    if canonical_mgr is not None:
        # bucket hit: the canonical plan is live — no solver, no retrace
        maps = None
        if entry.last_sig is not None and entry.last_maps is not None:
            if try_incremental_update(
                entry.last_sig, new_sig, entry.last_maps
            ):
                maps = entry.last_maps
                telemetry.record_plan_incremental(patched=True)
            else:
                telemetry.record_plan_incremental(patched=False)
        if maps is None:
            maps = canon.build_row_maps()
        mgr = _make_bucketed_mgr(key, canonical_mgr, maps)
        _runtime_dict.put(key, mgr)
        entry.last_sig = new_sig
        entry.last_maps = maps
        telemetry.record_cache_access(hit=True)
        telemetry.record_plan_solver(
            time.perf_counter() - t_lookup, cache_hit=True
        )
        telemetry.record_plan_bucket(hit=True)
        return key
    # fingerprint miss (or the canonical runtime was LRU-evicted): cold-
    # solve the CANONICAL mask once, then adapt this request onto it
    telemetry.record_cache_access(hit=False)
    telemetry.record_plan_bucket(hit=False)
    _in_canonical_resolve = True
    try:
        canonical_key = magi_attn_flex_key(
            canon.q_ranges,
            canon.k_ranges,
            canon.attn_type_map,
            canon.total_seqlen,
            canon.total_seqlen,
            mesh,
            num_heads=(key.num_heads_q, key.num_heads_kv),
            head_dim=key.head_dim,
            cp_axis=key.cp_axis,
            chunk_size=key.chunk_size,
            softcap=key.softcap,
            has_sink=key.has_sink,
            sink=sink,
            out_dtype=out_dtype,
            dispatch_config=dispatch_config,
            dist_attn_config=dist_attn_config,
            interpret=interpret,
        )
    finally:
        _in_canonical_resolve = False
    canonical_mgr = _runtime_dict[canonical_key]
    maps = canon.build_row_maps()
    mgr = _make_bucketed_mgr(key, canonical_mgr, maps)
    _runtime_dict.put(key, mgr)
    _plan_reuse_cache.put(fp, ReuseEntry(canonical_key, new_sig, maps))
    return key


@_key_build_span
def magi_attn_flex_key(
    q_ranges: AttnRanges | Sequence[Sequence[int]],
    k_ranges: AttnRanges | Sequence[Sequence[int]],
    attn_type_map: GeneralAttnMaskType,
    total_seqlen_q: int,
    total_seqlen_k: int,
    mesh: jax.sharding.Mesh,
    *,
    num_heads: tuple[int, int],  # (hq, hkv)
    head_dim: int,
    cp_axis: "str | tuple[str, str]" = "cp",  # (inter, intra) -> hier comm
    chunk_size: int | None = None,
    softcap: float = 0.0,
    has_sink: bool = False,
    sink: jax.Array | None = None,
    out_dtype="bfloat16",
    dispatch_config: DispatchConfig | None = None,
    dist_attn_config: "DistAttnConfig | None" = None,
    interpret: bool | None = None,
    is_same_source: bool = True,
    is_q_permutable: bool = True,
    is_k_permutable: bool = True,
) -> DistAttnRuntimeKey:
    """Plan (or fetch from cache) a distributed flex-attention runtime
    (reference magi_attn_flex_key, api/magi_attn_interface.py:440).

    The mask may have any (q_range, k_range, mask_type) slice list with
    disjoint (q, k) coverage. The sequence is padded so chunks divide evenly
    (reference compute_pad_size/apply_padding, :663-676).

    ``is_same_source`` / ``is_q_permutable`` / ``is_k_permutable`` keep the
    reference signature: this entry point is the self-attention case
    (all three True); for cross-attention sources (reference case 2/3,
    api:505-516) use :func:`magi_attn_cross_key`, which owns the
    separate q/k dispatch planning here.
    """
    if not (is_same_source and is_q_permutable and is_k_permutable):
        raise NotImplementedError(
            "cross-source masks (is_same_source=False or non-permutable "
            "roles) are served by magi_attn_cross_key in this framework"
        )
    assert total_seqlen_q == total_seqlen_k, (
        "self-attention interface requires equal q/k seqlens"
    )
    global _most_recent_key
    from ..config import DistAttnConfig

    hq, hkv = num_heads
    if dist_attn_config is None:
        dist_attn_config = DistAttnConfig(
            overlap_config=_resolve_overlap_config(
                None, hq, hkv, head_dim,
                hier=isinstance(cp_axis, (tuple, list)),
            )
        )
    else:
        dist_attn_config = dataclasses.replace(
            dist_attn_config,
            overlap_config=_resolve_overlap_config(
                dist_attn_config.overlap_config, hq, hkv, head_dim,
                hier=isinstance(cp_axis, (tuple, list)),
            ),
        )
    if dispatch_config is None:
        dispatch_config = dist_attn_config.dispatch_config
    if not isinstance(q_ranges, AttnRanges):
        q_ranges = AttnRanges.from_ranges(q_ranges)
    if not isinstance(k_ranges, AttnRanges):
        k_ranges = AttnRanges.from_ranges(k_ranges)
    types = _coerce_mask_types(attn_type_map, len(q_ranges))
    if env.is_auto_range_merge_enable():
        # canonicalize the slice list before keying/planning (reference
        # AUTO_RANGE_MERGE path, flex_flash_attn.py:79-178)
        from ..ops.range_merge import merge_ranges

        qa, ka, ta = merge_ranges(
            np.asarray(q_ranges.to_naive_ranges(), np.int64),
            np.asarray(k_ranges.to_naive_ranges(), np.int64),
            np.asarray(types, np.int64),
        )
        q_ranges = AttnRanges.from_ranges([tuple(r) for r in qa.tolist()])
        k_ranges = AttnRanges.from_ranges([tuple(r) for r in ka.tolist()])
        types = tuple(int(t) for t in ta)
    if env.is_sanity_check_enabled():
        from ..common.sanity import check_slices_non_overlapping

        check_slices_non_overlapping(q_ranges, k_ranges, types)
    if isinstance(cp_axis, (tuple, list)):
        # 2-D cp mesh (inter, intra) -> hierarchical 2-level comm
        # (reference env/comm.py:31-41 + api:617-637)
        cp_axis = tuple(cp_axis)
        assert len(cp_axis) == 2, "hierarchical cp needs (inter, intra) axes"
        cp_mesh_shape = tuple(int(mesh.shape[a]) for a in cp_axis)
        cp_size = cp_mesh_shape[0] * cp_mesh_shape[1]
    else:
        cp_mesh_shape = None
        cp_size = mesh.shape[cp_axis]

    if chunk_size is None:
        # auto: total / (min_chunks_per_rank * cp), floored to a sane block
        chunk_size = max(
            total_seqlen_q // (env.min_chunks_per_rank() * cp_size), 128
        )
    # uneven shard (reference api:639-676): pad only to a chunk multiple —
    # ranks absorb the chunk-count remainder via per-rank valid lengths
    pad = compute_pad_size(
        total_seqlen_q,
        1 if dispatch_config.uneven_shard else cp_size,
        chunk_size,
    )
    has_sink = has_sink or sink is not None
    assert not (has_sink and sink is None), (
        "has_sink=True requires the sink array at key-creation time"
    )
    check_flag_comb(
        cp_axis=cp_axis,
        uneven_shard=dispatch_config.uneven_shard,
    )
    sink_fp = (
        hash(np.asarray(jax.device_get(sink), np.float32).tobytes())
        if sink is not None
        else 0
    )
    # plan-aware block config (ISSUE 2): resolved BEFORE the LRU lookup —
    # the decision is part of the key, and the tuning cache (not the LRU)
    # is what makes the repeat-call path cheap. qo-comm keeps the env
    # blocking: its dynamic plane partition has its own kernel geometry.
    block_config = (
        None
        if env.is_qo_comm_enable()
        else _resolve_block_config(
            q_ranges.to_naive_ranges(),
            k_ranges.to_naive_ranges(),
            types,
            total_seqlen_q + pad,
            total_seqlen_k + pad,
            cp_size,
            hq,
            hkv,
            head_dim,
            str(jnp.dtype(out_dtype)),
        )
    )
    plan_block_q, plan_block_k, plan_head_block = _blocking_from(
        block_config, hq, hkv
    )

    key = DistAttnRuntimeKey(
        q_ranges=tuple(q_ranges.to_naive_ranges()),
        k_ranges=tuple(k_ranges.to_naive_ranges()),
        attn_type_map=types,
        total_seqlen_q=total_seqlen_q + pad,
        total_seqlen_k=total_seqlen_k + pad,
        pad_size=pad,
        chunk_size=chunk_size,
        cp_size=cp_size,
        cp_axis=cp_axis,
        num_heads_q=hq,
        num_heads_kv=hkv,
        head_dim=head_dim,
        softcap=float(softcap),
        has_sink=has_sink,
        sink_fingerprint=sink_fp,
        out_dtype=str(jnp.dtype(out_dtype)),
        dispatch_config_repr=repr((dispatch_config, dist_attn_config.overlap_config)),
        interpret=interpret,
        mesh_id=id(mesh),
        flags=env.flags_fingerprint(),
        block_config=block_config,
    )
    _t_lookup = time.perf_counter()
    if key in _runtime_dict:
        telemetry.annotate_span(cache="hit")
        if not _in_canonical_resolve:
            telemetry.record_cache_access(hit=True)
            # ISSUE 16: the hit's solver cost is the lookup itself; the
            # ms-saved credit is priced against the measured build mean
            telemetry.record_plan_solver(
                time.perf_counter() - _t_lookup, cache_hit=True
            )
        _most_recent_key = key
        return key
    # ISSUE 20: fingerprint-bucketed second-level lookup sits between the
    # exact-key miss and the cold solver (exact hits above stay untouched)
    reuse_key = _try_plan_reuse(
        key,
        _t_lookup,
        mesh=mesh,
        sink=sink,
        out_dtype=out_dtype,
        dispatch_config=dispatch_config,
        dist_attn_config=dist_attn_config,
        interpret=interpret,
    )
    if reuse_key is not None:
        telemetry.annotate_span(cache="reuse")
        _most_recent_key = reuse_key
        return reuse_key
    telemetry.annotate_span(cache="miss")
    if not _in_canonical_resolve:
        telemetry.record_cache_access(hit=False)

    # cold path: full planning
    mq, _, bucket = make_dispatch_meta_from_qk_ranges(
        q_ranges,
        k_ranges,
        [AttnMaskType(t) for t in types],
        total_seqlen_q + pad,
        total_seqlen_k + pad,
        chunk_size=chunk_size,
        cp_size=cp_size,
        dispatch_config=dispatch_config,
    )
    if env.is_qo_comm_enable():
        # qo-comm mode (reference _make_attn_meta.py:40: DynamicAttnSolver
        # iff MAGI_ATTENTION_QO_COMM): dynamic plane partition moving Q/O
        # as well as KV. Token ownership is the dispatch meta built above
        # with the configured (default MinHeap-balanced) algorithm — the
        # plane partition composes with area-balanced sharding, casts
        # routed over the permuted ownership.
        from ..parallel.qo_comm import (
            build_qo_comm_plan,
            make_qo_comm_attn_fn,
        )

        slices = np.array(
            [
                (qr_.start, qr_.end, kr_.start, kr_.end, int(t))
                for qr_, kr_, t in zip(q_ranges, k_ranges, types)
            ],
            dtype=np.int64,
        )
        qo_plan = build_qo_comm_plan(
            slices,
            total_seqlen_q + pad,
            cp_size,
            block_q=env.block_q(),
            block_k=env.block_k(),
            dispatch_meta=mq,
        )
        with telemetry.span("attn_fn_build"):
            params = make_attn_params(
                qo_plan,
                head_dim,
                softcap=softcap,
                out_dtype=out_dtype,
                interpret=interpret,
            )
            qo_fn = make_qo_comm_attn_fn(
                qo_plan, mesh, params, axis_name=cp_axis, sink=sink
            )

        def attn_fn(q, k, v, sink_override=None):
            out, lse = qo_fn(q, k, v, sink_override)
            return out, lse, None

        mgr = DistAttnRuntimeMgr(
            key, mesh, mq, qo_plan, attn_fn, dist_attn_config=dist_attn_config
        )
        _runtime_dict.put(key, mgr)
        _most_recent_key = key
        return key
    plan = build_dist_attn_plan(
        mq,
        bucket,
        block_q=plan_block_q,
        block_k=plan_block_k,
        overlap_config=dist_attn_config.overlap_config,
        cp_mesh_shape=cp_mesh_shape,
    )
    telemetry.record_runtime_costs(
        plan,
        num_heads_q=hq,
        num_heads_kv=hkv,
        head_dim=head_dim,
        bytes_per_elt=jnp.dtype(out_dtype).itemsize,
        generation=env.tpu_generation(),
    )
    if logger.isEnabledFor(logging.INFO):
        logger.info(
            "planned runtime for mask with %d slices, total=%d:\n%s",
            len(types),
            total_seqlen_q + pad,
            plan.describe(),
        )
    with telemetry.span("attn_fn_build"):
        params = make_attn_params(
            plan,
            head_dim,
            softcap=softcap,
            has_sink=has_sink,
            out_dtype=out_dtype,
            interpret=interpret,
            head_block=plan_head_block,
        )
        attn_fn = make_dist_attn_fn(
            plan, mesh, params, axis_name=cp_axis, sink=sink,
            with_max_logits=True,
        )
    mgr = DistAttnRuntimeMgr(
        key, mesh, mq, plan, attn_fn, dist_attn_config=dist_attn_config
    )
    _runtime_dict.put(key, mgr)
    _most_recent_key = key
    return key


def magi_attn_varlen_key(
    cu_seqlens: Sequence[int],
    total_seqlen: int,
    mesh: jax.sharding.Mesh,
    *,
    causal: bool = True,
    window_size: tuple[int, int] = (-1, -1),
    global_window_size: int = 0,
    **kwargs,
) -> DistAttnRuntimeKey:
    """Varlen (packed-batch) convenience key
    (reference magi_attn_varlen_key :160). ``window_size=(left, right)``
    applies a per-sample bidirectional sliding window (requires
    ``causal=False``), optionally with ``global_window_size`` leading
    keys per sample (reference :314-316 window semantics)."""
    from .functools import infer_attn_mask_from_cu_seqlens

    q_ranges, k_ranges, types = infer_attn_mask_from_cu_seqlens(
        list(cu_seqlens),
        causal=causal,
        window_size=tuple(window_size),
        global_window_size=global_window_size,
    )
    return magi_attn_flex_key(
        q_ranges,
        k_ranges,
        types,
        total_seqlen,
        total_seqlen,
        mesh,
        **kwargs,
    )


@_key_build_span
def magi_attn_cross_key(
    q_ranges: AttnRanges | Sequence[Sequence[int]],
    k_ranges: AttnRanges | Sequence[Sequence[int]],
    attn_type_map: GeneralAttnMaskType,
    total_seqlen_q: int,
    total_seqlen_k: int,
    mesh: jax.sharding.Mesh,
    *,
    num_heads: tuple[int, int],  # (hq, hkv)
    head_dim: int,
    cp_axis: str = "cp",
    chunk_size_q: int | None = None,
    chunk_size_k: int | None = None,
    softcap: float = 0.0,
    out_dtype="bfloat16",
    dispatch_config: DispatchConfig | None = None,
    overlap_config=None,
    interpret: bool | None = None,
) -> DistAttnRuntimeKey:
    """Plan (or fetch) a keyed CROSS-attention runtime: queries and memory
    are different sequences (tq != tk allowed).

    Role of the reference's cross-attn path (``get_xattn_args`` +
    dispatch_qo/dispatch_kv, dist_attn_runtime_mgr.py): queries are
    chunk-balanced by mask area, keys/values get their own sequential
    partition, and the group-cast plan routes the remote memory rows. Use
    the returned key with ``dispatch`` / ``dispatch_kv`` / ``calc_attn`` /
    ``undispatch``, and ``get_xattn_args(key)`` for layout/position info.

    No sink, qo-comm, hierarchical or uneven-shard composition — those are
    self-attention features (``check_flag_comb(xattn=True)``).
    """
    global _most_recent_key

    if dispatch_config is None:
        dispatch_config = DispatchConfig()
    hq, hkv = num_heads
    overlap_config = _resolve_overlap_config(
        overlap_config, hq, hkv, head_dim
    )
    check_flag_comb(
        cp_axis=cp_axis,
        uneven_shard=dispatch_config.uneven_shard,
        xattn=True,
    )
    if not isinstance(q_ranges, AttnRanges):
        q_ranges = AttnRanges.from_ranges(q_ranges)
    if not isinstance(k_ranges, AttnRanges):
        k_ranges = AttnRanges.from_ranges(k_ranges)
    types = _coerce_mask_types(attn_type_map, len(q_ranges))
    if env.is_auto_range_merge_enable():
        # canonicalize before keying, same as magi_attn_flex_key
        from ..ops.range_merge import merge_ranges

        qa, ka, ta = merge_ranges(
            np.asarray(q_ranges.to_naive_ranges(), np.int64),
            np.asarray(k_ranges.to_naive_ranges(), np.int64),
            np.asarray(types, np.int64),
        )
        q_ranges = AttnRanges.from_ranges([tuple(r) for r in qa.tolist()])
        k_ranges = AttnRanges.from_ranges([tuple(r) for r in ka.tolist()])
        types = tuple(int(t) for t in ta)
    if env.is_sanity_check_enabled():
        from ..common.sanity import check_slices_non_overlapping

        check_slices_non_overlapping(q_ranges, k_ranges, types)
    cp_size = mesh.shape[cp_axis]
    if chunk_size_q is None:
        chunk_size_q = max(
            total_seqlen_q // (env.min_chunks_per_rank() * cp_size), 128
        )
    if chunk_size_k is None:
        chunk_size_k = max(
            total_seqlen_k // (env.min_chunks_per_rank() * cp_size), 128
        )
    pad_q = compute_pad_size(total_seqlen_q, cp_size, chunk_size_q)
    pad_k = compute_pad_size(total_seqlen_k, cp_size, chunk_size_k)
    block_config = _resolve_block_config(
        q_ranges.to_naive_ranges(),
        k_ranges.to_naive_ranges(),
        types,
        total_seqlen_q + pad_q,
        total_seqlen_k + pad_k,
        cp_size,
        hq,
        hkv,
        head_dim,
        str(jnp.dtype(out_dtype)),
    )
    plan_block_q, plan_block_k, plan_head_block = _blocking_from(
        block_config, hq, hkv
    )

    key = DistAttnRuntimeKey(
        q_ranges=tuple(q_ranges.to_naive_ranges()),
        k_ranges=tuple(k_ranges.to_naive_ranges()),
        attn_type_map=types,
        total_seqlen_q=total_seqlen_q + pad_q,
        total_seqlen_k=total_seqlen_k + pad_k,
        pad_size=pad_q,
        chunk_size=chunk_size_q,
        cp_size=cp_size,
        cp_axis=cp_axis,
        num_heads_q=hq,
        num_heads_kv=hkv,
        head_dim=head_dim,
        softcap=float(softcap),
        has_sink=False,
        sink_fingerprint=0,
        out_dtype=str(jnp.dtype(out_dtype)),
        dispatch_config_repr=repr(
            # pad_k must key the cache: two k-side totals that pad to the
            # same multiple would otherwise collide and reuse a stale
            # pad_size_k in dispatch_kv/undispatch_kv
            ("xattn", chunk_size_k, pad_k, dispatch_config, overlap_config)
        ),
        interpret=interpret,
        mesh_id=id(mesh),
        flags=env.flags_fingerprint(),
        block_config=block_config,
    )
    _t_lookup = time.perf_counter()
    if key in _runtime_dict:
        telemetry.annotate_span(cache="hit")
        telemetry.record_cache_access(hit=True)
        telemetry.record_plan_solver(
            time.perf_counter() - _t_lookup, cache_hit=True
        )
        _most_recent_key = key
        return key
    telemetry.annotate_span(cache="miss")
    telemetry.record_cache_access(hit=False)

    from ..meta.dispatch_meta import make_cross_attn_dispatch_meta

    mq, mk, bucket = make_cross_attn_dispatch_meta(
        q_ranges,
        k_ranges,
        [AttnMaskType(t) for t in types],
        total_seqlen_q + pad_q,
        total_seqlen_k + pad_k,
        chunk_size_q=chunk_size_q,
        chunk_size_k=chunk_size_k,
        cp_size=cp_size,
        dispatch_config=dispatch_config,
    )
    plan = build_dist_attn_plan(
        mq,
        bucket,
        kv_dispatch_meta=mk,
        block_q=plan_block_q,
        block_k=plan_block_k,
        overlap_config=overlap_config,
    )
    telemetry.record_runtime_costs(
        plan,
        num_heads_q=hq,
        num_heads_kv=hkv,
        head_dim=head_dim,
        bytes_per_elt=jnp.dtype(out_dtype).itemsize,
        generation=env.tpu_generation(),
    )
    with telemetry.span("attn_fn_build"):
        params = make_attn_params(
            plan,
            head_dim,
            softcap=softcap,
            out_dtype=out_dtype,
            interpret=interpret,
            head_block=plan_head_block,
        )
        attn_fn = make_dist_attn_fn(
            plan, mesh, params, axis_name=cp_axis, with_max_logits=True
        )
    mgr = DistAttnRuntimeMgr(
        key,
        mesh,
        mq,
        plan,
        attn_fn,
        kv_dispatch_meta=mk,
        pad_size_k=pad_k,
    )
    _runtime_dict.put(key, mgr)
    _most_recent_key = key
    return key


def dispatch(x: jax.Array, key: DistAttnRuntimeKey, pad_value: float = 0.0):
    """Reference api.dispatch :887."""
    with telemetry.span("dispatch", key=key):
        return get_runtime_mgr(key).dispatch(x, pad_value)


def undispatch(y: jax.Array, key: DistAttnRuntimeKey):
    """Reference api.undispatch :924."""
    with telemetry.span("undispatch", key=key):
        return get_runtime_mgr(key).undispatch(y)


def calc_attn(q, k, v, key: DistAttnRuntimeKey, sink=None):
    """Reference api.calc_attn :1041 — returns (out, AttnForwardMeta).

    ``sink`` (optional, traced): overrides the key's captured sink so a
    learned sink receives gradients (the reference's sink is trainable).
    """
    return get_runtime_mgr(key).calc_attn(q, k, v, sink=sink)


def get_position_ids(key: DistAttnRuntimeKey):
    """Reference api.get_position_ids :1112."""
    return get_runtime_mgr(key).get_position_ids()


def dispatch_kv(x: jax.Array, key: DistAttnRuntimeKey, pad_value: float = 0.0):
    """Cross-attn memory-side dispatch (key from ``magi_attn_cross_key``)."""
    return get_runtime_mgr(key).dispatch_kv(x, pad_value)


def undispatch_kv(y: jax.Array, key: DistAttnRuntimeKey):
    """Cross-attn memory-side undispatch."""
    return get_runtime_mgr(key).undispatch_kv(y)


def get_xattn_args(key: DistAttnRuntimeKey) -> XAttnArgs:
    """Reference ``get_xattn_args``: cross-attn layout/position arguments."""
    return get_runtime_mgr(key).get_xattn_args()


@_key_build_span
def make_flex_key_for_new_mask_after_dispatch(
    q_ranges: AttnRanges | Sequence[Sequence[int]],
    k_ranges: AttnRanges | Sequence[Sequence[int]],
    attn_type_map: GeneralAttnMaskType,
    old_key: DistAttnRuntimeKey,
) -> DistAttnRuntimeKey:
    """Plan a NEW mask on the EXISTING dispatch of ``old_key``
    (reference make_varlen_key_for_new_mask_after_dispatch,
    api/magi_attn_interface.py:1167 — hybrid attention: several masks per
    layer stack reuse one token permutation, so dispatched activations are
    shared and only the attention plan differs).

    The chunk->rank partition (and thus dispatch/undispatch/position_ids)
    is inherited; the comm routing and kernel tables are re-planned for the
    new mask.
    """
    global _most_recent_key
    old_mgr = get_runtime_mgr(old_key)
    if old_key.has_sink:
        raise ValueError(
            "key reuse with an attention sink is not supported: re-key "
            "with magi_attn_flex_key(sink=...) instead "
            f"(old_key has sink_fingerprint={old_key.sink_fingerprint})"
        )
    if isinstance(old_mgr, BucketedDistAttnRuntimeMgr):
        raise ValueError(
            "key reuse after dispatch is not supported on a bucketed "
            "(plan-reuse) key: its dispatch layout belongs to the "
            "canonical plan "
            f"(canonical total={old_mgr.dispatch_meta.total_seqlen}, "
            f"request total={old_key.total_seqlen_q}), so a new mask in "
            "request coordinates cannot be planned on it — create a fresh "
            "key with magi_attn_flex_key"
        )
    from ..parallel.qo_comm import QoCommPlan

    if isinstance(old_mgr.plan, QoCommPlan):
        raise ValueError(
            "key reuse is not supported for qo-comm keys: the dynamic "
            "plane partition is mask-specific, so there is no dispatch to "
            "share — create a fresh key with magi_attn_flex_key"
        )
    if not isinstance(q_ranges, AttnRanges):
        q_ranges = AttnRanges.from_ranges(q_ranges)
    if not isinstance(k_ranges, AttnRanges):
        k_ranges = AttnRanges.from_ranges(k_ranges)
    types = _coerce_mask_types(attn_type_map, len(q_ranges))
    if env.is_sanity_check_enabled():
        from ..common.sanity import check_slices_non_overlapping

        check_slices_non_overlapping(q_ranges, k_ranges, types)
    # re-tune for the NEW mask on the inherited dispatch geometry — the
    # whole point of the plan-aware tuner is that a hybrid layer stack's
    # masks (e.g. dense causal + SWA sharing one dispatch) may want
    # different rungs
    block_config = _resolve_block_config(
        q_ranges.to_naive_ranges(),
        k_ranges.to_naive_ranges(),
        types,
        old_key.total_seqlen_q,
        old_key.total_seqlen_k,
        old_key.cp_size,
        old_key.num_heads_q,
        old_key.num_heads_kv,
        old_key.head_dim,
        old_key.out_dtype,
    )
    new_key = dataclasses.replace(
        old_key,
        q_ranges=tuple(q_ranges.to_naive_ranges()),
        k_ranges=tuple(k_ranges.to_naive_ranges()),
        attn_type_map=types,
        block_config=block_config,
    )
    _t_lookup = time.perf_counter()
    if new_key in _runtime_dict:
        telemetry.annotate_span(cache="hit")
        telemetry.record_cache_access(hit=True)
        telemetry.record_plan_solver(
            time.perf_counter() - _t_lookup, cache_hit=True
        )
        _most_recent_key = new_key
        return new_key
    telemetry.annotate_span(cache="miss")
    telemetry.record_cache_access(hit=False)

    from ..meta.dispatch_meta import make_global_bucket_from_qk_ranges

    meta = old_mgr.dispatch_meta
    bucket = make_global_bucket_from_qk_ranges(
        q_ranges,
        k_ranges,
        [AttnMaskType(t) for t in types],
        new_key.total_seqlen_q,
        meta.chunk_size,
    )
    old_cfg = old_mgr.dist_attn_config
    overlap = old_cfg.overlap_config if old_cfg is not None else None
    plan_block_q, plan_block_k, plan_head_block = _blocking_from(
        block_config, new_key.num_heads_q, new_key.num_heads_kv
    )
    plan = build_dist_attn_plan(
        meta,
        bucket,
        block_q=plan_block_q,
        block_k=plan_block_k,
        overlap_config=overlap,
        cp_mesh_shape=old_mgr.plan.hier,
    )
    telemetry.record_runtime_costs(
        plan,
        num_heads_q=new_key.num_heads_q,
        num_heads_kv=new_key.num_heads_kv,
        head_dim=new_key.head_dim,
        bytes_per_elt=jnp.dtype(new_key.out_dtype).itemsize,
        generation=env.tpu_generation(),
    )
    with telemetry.span("attn_fn_build"):
        params = make_attn_params(
            plan,
            new_key.head_dim,
            softcap=new_key.softcap,
            has_sink=False,
            out_dtype=new_key.out_dtype,
            interpret=new_key.interpret,
            head_block=plan_head_block,
        )
        attn_fn = make_dist_attn_fn(
            plan, old_mgr.mesh, params, axis_name=new_key.cp_axis,
            with_max_logits=True,
        )
    _runtime_dict.put(
        new_key,
        DistAttnRuntimeMgr(
            new_key, old_mgr.mesh, meta, plan, attn_fn, dist_attn_config=old_cfg
        ),
    )
    _most_recent_key = new_key
    return new_key


def make_varlen_key_for_new_mask_after_dispatch(
    cu_seqlens: Sequence[int],
    old_key: DistAttnRuntimeKey,
    *,
    causal: bool = True,
    window_size: tuple[int, int] = (-1, -1),
    global_window_size: int = 0,
) -> DistAttnRuntimeKey:
    """Varlen-style flavor of :func:`make_flex_key_for_new_mask_after_dispatch`
    (reference api/magi_attn_interface.py:1167): plan a new packed-batch
    mask described by ``cu_seqlens`` on the EXISTING dispatch of
    ``old_key`` (hybrid-attention layer stacks sharing one permutation).
    ``causal`` defaults to True, matching ``magi_attn_varlen_key`` (the
    reference defaults both of its varlen entry points to False; here the
    two stay consistent with each other instead). ``window_size`` /
    ``global_window_size`` follow ``magi_attn_varlen_key``."""
    from .functools import infer_attn_mask_from_cu_seqlens

    q_ranges, k_ranges, types = infer_attn_mask_from_cu_seqlens(
        list(cu_seqlens),
        causal=causal,
        window_size=tuple(window_size),
        global_window_size=global_window_size,
    )
    return make_flex_key_for_new_mask_after_dispatch(
        q_ranges, k_ranges, types, old_key
    )


def magi_attn_flex_dispatch(
    x: jax.Array,
    q_ranges,
    k_ranges,
    attn_type_map,
    total_seqlen_q: int,
    total_seqlen_k: int,
    mesh: jax.sharding.Mesh,
    **kwargs,
) -> tuple[jax.Array, DistAttnRuntimeKey]:
    """Key + dispatch in one call (reference magi_attn_flex_dispatch,
    api/magi_attn_interface.py:725): plans the runtime for the mask and
    returns ``(local_x, key)``."""
    key = magi_attn_flex_key(
        q_ranges, k_ranges, attn_type_map,
        total_seqlen_q, total_seqlen_k, mesh, **kwargs,
    )
    return dispatch(x, key), key


def magi_attn_varlen_dispatch(
    x: jax.Array,
    cu_seqlens: Sequence[int],
    total_seqlen: int,
    mesh: jax.sharding.Mesh,
    *,
    causal: bool = True,
    **kwargs,
) -> tuple[jax.Array, DistAttnRuntimeKey]:
    """Key + dispatch in one call, flash-attn-varlen style (reference
    magi_attn_varlen_dispatch, api/magi_attn_interface.py:305)."""
    key = magi_attn_varlen_key(
        cu_seqlens, total_seqlen, mesh, causal=causal, **kwargs
    )
    return dispatch(x, key), key


def get_telemetry_snapshot() -> dict:
    """Plain-dict snapshot of the runtime telemetry registry (ISSUE 1):
    plan/comm/solver introspection recorded while
    ``MAGI_ATTENTION_TELEMETRY`` (or ``telemetry.set_enabled(True)``) was
    on — per-rank comm rows/bytes, chunk imbalance, overlap degree,
    kernel step counts, modeled FLOP/comm cost, cache hit rates. Always
    JSON-serializable; empty sections while telemetry is disabled. See
    ``docs/observability.md`` for the metric catalog."""
    return telemetry.snapshot()


def aggregate_telemetry_across_mesh(snapshot: dict | None = None) -> dict:
    """Mesh-wide telemetry aggregate (ISSUE 3): gather every process's
    registry snapshot and merge — counters summed, gauges with per-rank
    values plus min/max/mean/argmax skew stats, histograms bucket-merged.
    Loopback (single merged snapshot, same schema) in a single process.
    Host-side only; never call inside traced code."""
    return telemetry.aggregate_across_mesh(snapshot)


def clear_cache(mesh: "jax.sharding.Mesh | None" = None) -> None:
    """Drop cached runtime plans (reference clear_cache,
    api/magi_attn_interface.py:1157). With a ``mesh``, only keys planned
    over that mesh are dropped; otherwise the whole cache is cleared.
    Keys stay valid to re-plan — the cache is rebuildable by design."""
    global _most_recent_key
    if mesh is None:
        _runtime_dict.clear()
        _plan_reuse_cache.clear()
        _most_recent_key = None
        return
    _runtime_dict.clear(mesh_id=id(mesh))
    _plan_reuse_cache.clear(mesh_id=id(mesh))
    if _most_recent_key is not None and _most_recent_key.mesh_id == id(mesh):
        _most_recent_key = None


def roll(x: jax.Array, key: DistAttnRuntimeKey, shift: int, axis: int = 0):
    """Distributed roll along the global sequence of a dispatched tensor
    (reference api.roll :960 — MTP label shifting).

    Routed through the O(N/P) shard_map point-to-point path (local gather
    + one padded all-to-all of the rank-crossing rows — the XLA analogue
    of the reference's ``batch_isend_irecv``, roll.py:448); degenerate
    exchanges fall back to the static global gather."""
    from ..parallel.dispatch import roll as _roll

    mgr = get_runtime_mgr(key)
    if isinstance(mgr, BucketedDistAttnRuntimeMgr):
        raise ValueError(
            "roll is not supported on a bucketed (plan-reuse) key: the "
            "shared canonical dispatch meta describes canonical "
            f"coordinates (total={mgr.dispatch_meta.total_seqlen}), so a "
            f"global roll of the request's {key.total_seqlen_q} rows "
            "would shift through bucket-pad slots — undispatch, roll in "
            "natural order, and re-dispatch instead"
        )
    return _roll(
        x,
        mgr.dispatch_meta,
        shift,
        axis=axis,
        mesh=mgr.mesh,
        cp_axis=key.cp_axis,
    )


def make_shift_plan(key: DistAttnRuntimeKey, taps=(1, 2), *, cu_seqlens=None):
    """Plan the forward shift along the documents of a key's dispatch
    (``parallel.dispatch.make_shift_plan``): inside a ``shard_map`` over
    the key's cp axis, ``shift_local(x, tables, plan, cp_axis)`` then
    gives a rank's rows ``x`` shifted by every ``j`` of ``taps`` in
    global order, ``y_j[p] = x[p - j]``, zero at a document's first ``j``
    tokens; the rank-crossing rows of all taps ride one exchange, and the
    backward is the shift by ``-j``. ``cu_seqlens``: the documents; by
    default every start of the key's q ranges starts one (a packed
    varlen key's q ranges are its documents). ``plan.device_tables()``
    are sharded on the cp axis like a plan's tables."""
    from ..parallel.dispatch import make_shift_plan as _plan

    mgr = get_runtime_mgr(key)
    if isinstance(mgr, BucketedDistAttnRuntimeMgr):
        raise ValueError(
            "a shift is not supported on a bucketed (plan-reuse) key: its "
            "dispatch meta describes canonical coordinates, as for roll"
        )
    if cu_seqlens is None:
        starts = sorted({0, *(s for s, _e in key.q_ranges)})
        cu_seqlens = [*starts, key.total_seqlen_q]
    return _plan(mgr.dispatch_meta, cu_seqlens, taps)


def roll_simple(
    x: jax.Array, key: DistAttnRuntimeKey, shift: int, axis: int = 0
):
    """Alias of :func:`roll` (reference roll_simple,
    api/magi_attn_interface.py:1004 — its only difference is plain vs
    batched P2P issue order; here both ride the same P2P exchange)."""
    return roll(x, key, shift, axis=axis)


def init_dist_attn_runtime_key(
    q_ranges,
    k_ranges,
    attn_mask_type,
    total_seqlen_q: int,
    total_seqlen_k: int,
    num_heads_q: int,
    num_heads_kv: int,
    head_dim: int,
    chunk_size: int,
    mesh: jax.sharding.Mesh,
    *,
    cp_axis="cp",
    dist_attn_config=None,
    **kwargs,
) -> DistAttnRuntimeKey:
    """Low-level key constructor (reference
    dist_attn_runtime_mgr.py:484 ``init_dist_attn_runtime_key``): build
    + plan a runtime key without the convenience-entry sugar. The
    reference's ``cp_group``/``cp_mesh`` pair collapses to the jax mesh
    (+ cp_axis); reference-only kwargs (``pad_size`` — padding is
    auto-resolved here — and the torch-distributed handles) are accepted
    and ignored."""
    for ref_only in ("pad_size", "cp_group", "cp_mesh"):
        kwargs.pop(ref_only, None)
    return magi_attn_flex_key(
        q_ranges, k_ranges, attn_mask_type,
        total_seqlen_q, total_seqlen_k, mesh,
        num_heads=(num_heads_q, num_heads_kv), head_dim=head_dim,
        chunk_size=chunk_size, cp_axis=cp_axis,
        dist_attn_config=dist_attn_config, **kwargs,
    )


def init_dist_attn_runtime_mgr(
    q_ranges,
    k_ranges,
    attn_mask_type,
    total_seqlen_q: int,
    total_seqlen_k: int,
    num_heads_q: int,
    num_heads_kv: int,
    head_dim: int,
    chunk_size: int,
    mesh: jax.sharding.Mesh,
    *,
    cp_axis="cp",
    dist_attn_config=None,
    **kwargs,
) -> DistAttnRuntimeMgr:
    """Low-level manager constructor (reference
    dist_attn_runtime_mgr.py:545 ``init_dist_attn_runtime_mgr``):
    the planned manager for the key, directly."""
    return get_runtime_mgr(
        init_dist_attn_runtime_key(
            q_ranges, k_ranges, attn_mask_type,
            total_seqlen_q, total_seqlen_k,
            num_heads_q, num_heads_kv, head_dim, chunk_size, mesh,
            cp_axis=cp_axis, dist_attn_config=dist_attn_config, **kwargs,
        )
    )
