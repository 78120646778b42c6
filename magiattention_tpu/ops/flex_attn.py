"""Pallas TPU flex-flash-attention: fwd + bwd kernels over attention slices.

TPU-native equivalent of the reference FFA CUDA kernel
(csrc/flexible_flash_attention/, SURVEY.md §2.7 module A): attention over an
arbitrary list of (q_range, k_range, mask_type) slices with online softmax,
GQA, softcap, attention sink, LSE + per-row max-logit outputs, and a
one-kernel backward needing no atomics: the sequential TPU grid walks a
host-precomputed entry table (ops/block_meta.py) so tiles of the same
output block are consecutive and accumulate in VMEM scratch. The forward
walks the q-major table. The backward walks the k-major one and computes
S, the mask, P, dP and dS once a tile (five matmuls: S, dP, dV, dK, dQ):
dk and dv accumulate in VMEM per k block, and dq, whose q block comes
back once a key column, keeps its float32 sums in a scratch buffer in HBM
that the steps move by their own DMAs, a tile's read started a step ahead:
the table marks each q block's first and last visit, so the first reads
nothing and the last writes the result in the inputs' dtype (no rounding
pass after the kernel; ``_dq_accumulate`` keeps the orderings;
docs/block_sparse.md has the bytes a step moves and why the walk is
k-major).

Entries carry run fields (local window + local->global offset), so the same
kernels serve the distributed runtime where each rank's Q/KV buffers are
permuted concatenations of global segments: table arrays may be traced jax
arrays (stacked per-rank, sharded on the cp mesh axis), not just constants.

Layout inside kernels: head-major [num_heads, tokens, head_dim]. Public
wrappers accept the reference layout [tokens, heads, head_dim].
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.custom_derivatives import SymbolicZero
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .block_meta import (
    FIRST_VISIT,
    LAST_VISIT,
    RUN_FIELDS,
    SLICE_FIELDS,
    FlexAttnBlockMeta,
    build_block_meta,
    q_visit_counts,
)
from .block_sparse import clamped_entry, row_tables
from ..utils.compat import tpu_compiler_params
from ..utils.instrument import named_scope

NEG_INF = float("-inf")
LANES = 128
# What the forward's mask select writes inside a step, in place of -inf: a
# finite value far under any logit, so the online-softmax update needs no
# -inf guard (``_fwd_update``). -inf is restored where a q block is written
# (``_fwd_finalize``); outside the forward kernels nothing sees this value.
_MASK = -0.7 * float(np.finfo(np.float32).max)
# Scoped-VMEM limit handed to Mosaic with every flex kernel. Under the
# compiler's own default (16 MiB on v5e, of 128 MiB physical) the TPU
# compiler refuses most head-batched sparse rungs and some backward rungs
# the autotuner may pick — tests/test_aot_compile_tpu.py; an AOT sweep of
# every rung at five head geometries peaked at 26.5 MiB (48Q/8KV hd128,
# (512, 768, 6) sparse). The tuner has no VMEM feasibility test of its
# own, so the kernels ask for room instead.
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# What the four f32 (head_block * block_q, block_k) intermediates of a
# head-batched backward step may take of that (``_bwd_head_block``). The
# count is a proxy for what Mosaic allocates, set by compiling for a v5e:
# steps of 64 MiB compile ((512, 1024, 8) at 64Q/8KV hd128, (1024, 1024, 4)
# at 32Q/8KV), steps of 128 MiB are refused, and the largest step a
# row-major rung of the tuner asks for, (256, 1024, 2) snapped to
# head_block 8 at group 8, is 32 MiB.
_BWD_HB_LIVE_BYTES = _VMEM_LIMIT_BYTES // 2


def _flex_pallas_call(
    role: str, heads_per_step: int, grid: str, body, form=None, **kwargs
):
    """Where every flex ``pallas_call`` is built (trace time). The call is
    named by role, not by grid kind: magi_flex_fwd_kernel,
    magi_flex_bwd_kernel. The name enters the custom
    call's jax scope (.../magi_flex_bwd_kernel/pallas_call), which is what a
    device trace and the benchmark's per-kernel metrics read; keep it
    matching magi_\\w*kernel, the roofline metrics' pattern. The build is
    counted with the q heads one grid step takes and the grid it walks
    (``magi_flex_kernel_build_total{kernel=, heads_per_step=, grid=}``), so
    a snapshot says which of the per-head and head-batched forms ran, and
    on which of :data:`GRID_KINDS`. ``form``: the labels that say in which
    form a side operand crosses this kernel's boundary (``stats=compact|
    lanes`` the forward's, :func:`stats_form`; the backward's is always
    ``compact``, beside its ``delta=xla`` and ``dq=visits|zero_filled``,
    :func:`dq_form`), and
    ``v_head_dim`` where v is not as wide as k."""
    from .. import telemetry

    telemetry.record_flex_kernel_build(
        role, heads_per_step, grid, **(form or {})
    )
    return pl.pallas_call(body, name=f"magi_flex_{role}_kernel", **kwargs)


def _value_width_label(d: int, dv: int) -> dict:
    """The build counter's ``v_head_dim`` label: there only where the value
    width is not the key width, so every series of a plan at one width is
    the series it was."""
    return {} if dv == d else {"v_head_dim": str(dv)}


def _compiler_params(*dimension_semantics: str):
    return tpu_compiler_params(
        dimension_semantics=dimension_semantics,
        vmem_limit_bytes=_VMEM_LIMIT_BYTES,
    )
# the two kernel grid layouts (FlexAttnParams.grid / the autotuner's
# rung axis): "row_major" = the static (heads, num_blocks, steps) grid,
# whose rows shorter than the longest pad with dead steps; "sparse" = the
# compact entry-walk grid (heads, entries) that visits ONLY the entries
# of the table — no dead steps (ROADMAP S2). One step body a kernel
# serves both (:class:`_Walk`); the keyed runtime picks per plan
# (``parallel/dist_attn.make_attn_params``)
GRID_KINDS = ("row_major", "sparse")
# the form of every plan's backward (the ``bwd_form`` of the ``attn_fn_build``
# span and of ``magi_flex_bwd_form_total``): one k-major kernel. The split
# form it replaced (dq q-major, then dkv; PR 43) ran seven matmuls a tile
BWD_FORM = "fused"


@dataclasses.dataclass(frozen=True)
class FlexAttnParams:
    """Static parameters closed over by the kernels (hashable).

    ``head_block``: q heads processed per grid step (1 = head-per-step),
    by the forward and the backward on both grids (the backward takes
    the ``head_block // group`` kv heads' groups, so the per-head form's
    ``group`` grid dimension is inside the step). Batching heads amortizes per-step grid
    overhead — the dominant cost on small tiles — and fetches a K/V tile
    once for the group, at the price of head_block x VMEM: a backward
    step too large for it stays per head (``_bwd_head_block``, a test on
    shapes that holds on both grids). Must be 1 or a multiple of the GQA
    group size.

    ``grid`` (:data:`GRID_KINDS`): ``"row_major"`` launches (heads,
    num_blocks, steps) with static q-side index maps; ``"sparse"``
    launches (heads, entries), one step an entry of the table, its q-side
    maps read from the table (non-decreasing, so a block stays where it is
    across its entries). Same step bodies, same tables, same results.
    Measured on a v5e (PR 27, PERF.md section 6; the "round 5" reading of
    a flat grid at 76 against 132 TF/s on dense 64k has no record and did
    not repeat): a dead row-major step costs 0.21-0.30 us at 8 heads a
    step, and 2.1 us in a per-head k-major step, where it still moves data; the
    compact walk adds -0.015 to +0.035 us to a live step. On the packed
    64k cell, 79% dead, the compact grid took the forward from 131.8 to
    109.0 ms, the then two backward kernels from 107.6 to 74.7 and from
    128.4 to 96.3; on a window
    mask with 1% dead it changed nothing (+0.4 / -0.2 / +0.2 ms of 48 /
    32 / 40). ``make_attn_params`` counts both grids' steps for a plan
    and prices them with those two costs
    (``tuning/cost_model.choose_grid``); direct callers default to
    ``"row_major"``.

    ``fwd_steps``/``bwd_steps``: the row-major grid's static inner
    extents — the max entries on any q block (forward) resp. k block
    (backward). 0 = derive from concrete tables at launch; traced (per-rank
    stacked) tables require the plan builder to set them host-side. The
    compact grid has no such extent.
    """

    block_q: int
    block_k: int
    scale: float
    softcap: float
    has_sink: bool
    out_dtype: str
    interpret: bool
    head_block: int = 1
    fwd_steps: int = 0
    bwd_steps: int = 0
    # "row_major" (static steps grid) or "sparse" (compact entry walk)
    grid: str = "row_major"
    # the largest step of any slice the tables hold (bounds_mask_step);
    # 1 compiles the mask arithmetic of a key a row
    mask_step: int = 1
    # q blocks that the k-major tables name in no entry, over every rank
    # and table set these params serve, counted by the plan builder on
    # the host beside bwd_steps (dq_form). None = not counted
    bwd_unnamed_q: int | None = None
    # the attention kind of a layer whose ``jax.checkpoint`` keeps this
    # call's out and lse [hq, tqp] (models/_common.layer_under_remat, the
    # only place that sets it, beside the policy that saves KEPT_NAMES);
    # "": the call stands alone (the same residual, under no name)
    kept: str = ""

    @property
    def out_jnp_dtype(self):
        return jnp.dtype(self.out_dtype)


def bounds_mask_step(bounds) -> int:
    """The largest step among the slices of a (concrete, host-side)
    ``slice_bounds`` table of any leading shape: what
    ``FlexAttnParams.mask_step`` must be for the kernels to read it."""
    words = np.asarray(bounds).reshape(-1, SLICE_FIELDS)[:, 4]
    return 1 << (int(words.max()) >> 2) if words.size else 1


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def fwd_tables(meta: FlexAttnBlockMeta):
    return (
        jnp.asarray(meta.fwd_q_block),
        jnp.asarray(meta.fwd_k_block),
        jnp.asarray(meta.fwd_slice_id),
        jnp.asarray(meta.fwd_runs),
        jnp.asarray(meta.slice_bounds),
    )


def bwd_tables(meta: FlexAttnBlockMeta):
    return (
        jnp.asarray(meta.bwd_k_block),
        jnp.asarray(meta.bwd_q_block),
        jnp.asarray(meta.bwd_slice_id),
        jnp.asarray(meta.bwd_runs),
        jnp.asarray(meta.slice_bounds),
    )


def _row_tables(major, num_major: int):
    """Per-major-block [start, count] over a sorted (possibly traced)
    major array — the kernels' two extra scalar-prefetch operands
    (``block_sparse.row_tables``, the shared enumeration primitive; the
    decode kernel derives the same tables from its block table)."""
    maj = major if not isinstance(major, np.ndarray) else jnp.asarray(major)
    with named_scope("magi_layout"):  # round the kernel, not of it
        return row_tables(maj, num_major)


# the shared clamped lookup (``block_sparse.clamped_entry``): kernel
# bodies and launcher index maps resolve steps through ONE function
_clamped_entry = clamped_entry


def _resolve_steps(explicit: int, major, num_major: int) -> int:
    """Static inner-grid extent: explicit params value, or derived from a
    concrete major array (traced tables MUST carry it in params)."""
    if isinstance(major, jax.core.Tracer):
        if explicit:
            return int(explicit)
        raise ValueError(
            "flex-attn: traced kernel tables need FlexAttnParams.fwd_steps/"
            "bwd_steps (static max entries per q/k block); the plan builder "
            "computes them host-side via FlexAttnBlockMeta.fwd_steps"
        )
    from .block_meta import max_row_count

    derived = max_row_count(np.asarray(major), num_major)
    if explicit:
        # a stale params value smaller than the table's true extent would
        # silently drop entries (never visited by any j) — make it loud
        if explicit < derived:
            raise ValueError(
                f"flex-attn: params steps={explicit} < the table's max "
                f"entries per block ({derived}); entries would be silently "
                "skipped — rebuild params for these tables"
            )
        return int(explicit)
    return derived


_BIG = 1 << 30


def _entry_interval_mask(
    bounds, runs, sid_e, e, row0, col0, bq, bk, stepped: bool = False,
    transposed: bool = False,
):
    """Boolean [bq, bk] mask for one entry via per-row k-intervals
    ([bk, bq], keys along sublanes, under ``transposed``).

    Every mask condition an entry can impose — run window, slice bounds,
    causal (bit0), inv-causal (bit1) — is an affine k-interval in the row:
    allowed iff lo(r) <= cl < hi(r). Computing lo/hi as [bq, 1] columns
    costs vector math on bq elements; the tile then pays ONE iota and two
    compares. Cheap enough to apply unconditionally, which is the point:
    a per-entry ``lax.cond`` on needs_mask and the full 2-D
    ``_entry_mask`` applied unconditionally were both slower on
    dense-causal 64k in an earlier round whose records are gone (not
    measured on this tree).

    ``stepped`` (static: ``FlexAttnParams.mask_step > 1``): the plan has a
    slice whose bounds move in blocks (``AttnMaskType.with_step``). The
    slice's type word carries log2 of its step above the two bound bits,
    and the block index counted from the aligned corner is one AND of the
    row column with ``-step``; no operand, no table. Off, the arithmetic
    below is what it was before steps existed, to the instruction.

    ``transposed`` (static: the backward, whose logits are ``K Q^T``): the
    same predicate with the q rows along lanes, so lo/hi are [1, bq] rows
    (bq / 128 vregs where the columns are bq / 8) and the iota runs down
    the sublanes. There the interval test is ONE unsigned
    compare, ``cl - lo < hi - lo``: a key under ``lo`` wraps to a huge
    value, an empty interval has width 0. The same values as the two
    compares and their AND, which on the chip cost this orientation 4.6%
    of the dense 64k backward against the parent orientation's 1.0%; the
    one compare costs it 0.9% (PERF.md section 6, PR 58). The forward
    keeps the two compares: its program is not this change's to move.
    """
    lo, hi = _entry_intervals(
        bounds, runs, sid_e, e, row0, bq, stepped, transposed
    )
    tile, col_axis = ((bk, bq), 0) if transposed else ((bq, bk), 1)
    cl = col0 + jax.lax.broadcasted_iota(jnp.int32, tile, col_axis)  # local cols
    if transposed:
        unsigned = lambda x: jax.lax.bitcast_convert_type(x, jnp.uint32)  # noqa: E731
        return unsigned(cl - lo) < unsigned(jnp.maximum(hi - lo, 0))
    return (cl >= lo) & (cl < hi)


def _entry_intervals(
    bounds, runs, sid_e, e, row0, bq, stepped: bool, transposed: bool
):
    """(lo, hi) of :func:`_entry_interval_mask`: the first local key a q
    row of the entry may see and the one past the last, int32 [bq, 1]
    columns ([1, bq] rows under ``transposed``); an empty interval
    (``_BIG``, ``-_BIG``) on a row the entry does not reach."""
    rows, row_axis = ((1, bq), 1) if transposed else ((bq, 1), 0)
    rbase = e * RUN_FIELDS
    ql0 = runs[rbase + 0]
    ql1 = runs[rbase + 1]
    kl0 = runs[rbase + 2]
    kl1 = runs[rbase + 3]
    qoff = runs[rbase + 4]
    koff = runs[rbase + 5]
    sbase = sid_e * SLICE_FIELDS
    q0 = bounds[sbase + 0]
    q1 = bounds[sbase + 1]
    k0 = bounds[sbase + 2]
    k1 = bounds[sbase + 3]
    typ = bounds[sbase + 4]
    is_causal = (typ & 1) == 1
    is_inv = (typ & 2) == 2

    rl = row0 + jax.lax.broadcasted_iota(jnp.int32, rows, row_axis)  # local rows
    row_ok = (rl >= ql0) & (rl < ql1) & (rl + qoff >= q0) & (rl + qoff < q1)
    if stepped:
        # floor(x / step) * step, also below zero (rows outside the slice)
        whole = -(jnp.int32(1) << (typ >> 2))

    def inv_lo():
        if stepped:
            return (k0 - koff) + ((rl + (qoff - q0)) & whole)
        return rl + (qoff - q0 + k0 - koff)

    def causal_hi():
        if stepped:
            return (k1 - koff) - (((q1 - 1 - qoff) - rl) & whole)
        return rl + (qoff - q1 + k1 - koff + 1)

    # (called in place: at step 1 the operations come in the order they
    # always came in, and the compiler schedules them as it did)
    lo = jnp.maximum(kl0, k0 - koff)
    lo = jnp.where(is_inv, jnp.maximum(lo, inv_lo()), lo)
    hi = jnp.minimum(kl1, k1 - koff)
    hi = jnp.where(is_causal, jnp.minimum(hi, causal_hi()), hi)
    lo = jnp.where(row_ok, lo, _BIG)
    hi = jnp.where(row_ok, hi, -_BIG)
    return lo, hi


def _entry_mask(bounds, runs, sid_e, e, row0, col0, bq, bk):
    """Boolean [bq, bk] mask for one entry.

    Local coordinates come from the grid (row0/col0 block origins + iota);
    run fields translate them to global coordinates where the slice's
    original mask semantics (bit0 causal / bit1 inv-causal) are evaluated.
    Used by the dense jnp backends; the Pallas kernels use the cheaper
    row-interval form (:func:`_entry_interval_mask` — same predicate).
    """
    rbase = e * RUN_FIELDS
    ql0 = runs[rbase + 0]
    ql1 = runs[rbase + 1]
    kl0 = runs[rbase + 2]
    kl1 = runs[rbase + 3]
    qoff = runs[rbase + 4]
    koff = runs[rbase + 5]
    sbase = sid_e * SLICE_FIELDS
    q0 = bounds[sbase + 0]
    q1 = bounds[sbase + 1]
    k0 = bounds[sbase + 2]
    k1 = bounds[sbase + 3]
    typ = bounds[sbase + 4]

    rl = row0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)  # local rows
    cl = col0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)  # local cols
    mask = (rl >= ql0) & (rl < ql1) & (cl >= kl0) & (cl < kl1)
    gq = rl + qoff
    gk = cl + koff
    mask &= (gq >= q0) & (gq < q1) & (gk >= k0) & (gk < k1)
    is_causal = (typ & 1) == 1
    is_inv = (typ & 2) == 2
    ls = typ >> 2  # log2 of the slice's step: block indices from the corner
    # CAUSAL (bottom-right aligned): allow iff (gk - k1) <= (gq - q1)
    mask &= jnp.logical_or(
        ~is_causal, ((k1 - 1 - gk) >> ls) >= ((q1 - 1 - gq) >> ls)
    )
    # INVCAUSAL (top-left aligned): allow iff (gk - k0) >= (gq - q0)
    mask &= jnp.logical_or(~is_inv, ((gk - k0) >> ls) >= ((gq - q0) >> ls))
    return mask


def _scores(q, k, scale, softcap):
    """Scaled (and optionally softcapped) logits, f32 [bq, bk]."""
    z = jax.lax.dot_general(
        q,
        k,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    z = z * jnp.float32(scale)
    if softcap > 0.0:
        return jnp.float32(softcap) * jnp.tanh(z / jnp.float32(softcap))
    return z


def _scores_hb(q_ref, k_ref, params: FlexAttnParams, group: int):
    """Head-batched logits, f32 (HB, G*bq, bk): the (HBG, bq, d) q block's
    rows are stacked per kv head, so QK^T over the HB kv heads of the step
    is one batched MXU call."""
    hb = k_ref.shape[0]
    q = q_ref[...].reshape(hb, group * params.block_q, q_ref.shape[2])
    s = jax.lax.dot_general(
        q,
        k_ref[...],
        dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) * jnp.float32(params.scale)
    if params.softcap > 0.0:
        s = jnp.float32(params.softcap) * jnp.tanh(
            s / jnp.float32(params.softcap)
        )
    return s


def _mask_hb(s, mask, group: int, fill: float = NEG_INF):
    """``fill`` (-inf; the forward's ``_MASK``) off the entry's (bq, bk)
    mask, computed once per tile and broadcast over the (HB, G) heads of
    head-batched logits."""
    hb, rows, bk = s.shape
    s4 = s.reshape(hb, group, rows // group, bk)
    s4 = jnp.where(mask[None, None], s4, fill)
    return s4.reshape(hb, rows, bk)


def _check_head_block(hbg: int, hq: int, group: int) -> None:
    assert hbg % group == 0 and hq % hbg == 0, (
        f"head_block {hbg} must be a multiple of the GQA group {group} and "
        f"divide hq {hq}"
    )


# ---------------------------------------------------------------------------
# the two grids: one step body a kernel, two walks
# ---------------------------------------------------------------------------


class _Walk:
    """Where a step stands in its entry table, on either grid
    (``params.grid``, :data:`GRID_KINDS`). The step bodies of the forward
    and the backward, per head and head-batched, read four scalars from it
    and nothing else of the grid: the entry ``e``, its major block ``i`` (a q
    block for the forward, a k block for the backward), whether the step is
    the block's ``first()`` (initialize the accumulators) and its
    ``last()`` (write the block).

    ``row_major``: grid (heads, blocks, steps); step j of block i is
    entry ``rs[i] + j``, clamped past the block's count, and such a dead
    step takes its slot but skips compute (``when_live``).
    ``sparse``: grid (heads, entries); the step IS the entry, its block
    read from the major table, and every step is live — padded entries
    too, whose mask is empty.

    ``inner``: the grid has one more dimension inside the walk (the
    per-head backward's GQA group), read as ``g``. The predicates are made
    where they are asked for, so the row-major program is traced in the
    order it always was."""

    def __init__(self, grid: str, major, rs, rc, inner: bool = False):
        self.rs, self.rc = rs, rc
        self.compact = grid == "sparse"
        if self.compact:
            self.e = pl.program_id(1)
            self.i = major[self.e]
            self.g = pl.program_id(2) if inner else None
        else:
            self.i = pl.program_id(1)
            self.j = pl.program_id(2)
            self.g = pl.program_id(3) if inner else None
            self.steps = pl.num_programs(2)
            self.e = _clamped_entry(rs, rc, self.i, self.j)

    def first(self):
        return self.e == self.rs[self.i] if self.compact else self.j == 0

    def last(self):
        if self.compact:
            return self.e == self.rs[self.i] + self.rc[self.i] - 1
        return self.j == self.steps - 1

    def when_live(self, compute) -> None:
        if self.compact:
            compute()
        else:
            pl.when(self.j < self.rc[self.i])(compute)


def _walk_grid(
    grid: str, heads: int, major, num_major: int, steps: int,
    stream_head=lambda h: h, inner: tuple[int, ...] = (),
    blocks: str = "parallel",
):
    """(grid, index map of the blocks that stay per major block, index map
    of the blocks streamed per entry, dimension semantics) of a launcher.
    The seven scalar-prefetch operands arrive as (major table, minor
    table, slice ids, runs, bounds, row starts, row counts): q-major for
    the forward, k-major for the backward. ``steps`` is the params' static
    extent (``fwd_steps`` / ``bwd_steps``), which only the row-major grid
    has. ``inner``: extents of grid dimensions inside the walk, and
    ``stream_head(h, *their ids)`` the head block of the streamed operand
    (the same as the resident one's in the head-batched layout).
    ``blocks``: the row-major grid's semantics of the axis that walks the
    major blocks; ``"arbitrary"`` for the backward, whose k blocks add
    into one dq tile (the compact grid's entry axis always is)."""
    n = len(inner)
    if grid == "sparse":

        def stay(h, e, *rest):
            return (h, rest[n][e], 0)

        def stream(h, e, *rest):
            return (stream_head(h, *rest[:n]), rest[n + 1][e], 0)

        return (
            (heads, major.shape[0], *inner), stay, stream,
            ("parallel", "arbitrary", *["arbitrary"] * n),
        )

    def stay(h, i, j, *rest):
        return (h, i, 0)

    def stream(h, i, j, *rest):
        e = _clamped_entry(rest[n + 5], rest[n + 6], i, j)
        return (stream_head(h, *rest[:n]), rest[n + 1][e], 0)

    return (
        (heads, num_major, _resolve_steps(steps, major, num_major), *inner),
        stay, stream, ("parallel", blocks, *["arbitrary"] * (n + 1)),
    )


# ---------------------------------------------------------------------------
# forward: the online-softmax state, one copy for both bodies
# ---------------------------------------------------------------------------


def _probs(s, m):
    """``p = exp(s - m)`` of a (..., rows, bk) logit tile against the
    lane-replicated row maximum ``m`` (..., rows, LANES), and ``p``'s
    per-lane partial row sums (..., rows, LANES): lane c holds the sum of
    columns c, c + 128, ... The tile is taken in static, vreg-aligned
    slices of 128 lanes, each of the shape of ``m``: no lane broadcast of
    ``m`` and no cross-lane reduction, element-wise work only. A tile that
    is not a multiple of a vreg's lanes (small test blocks) broadcasts
    lane 0 of ``m`` and puts its whole row sum in lane 0."""
    bk = s.shape[-1]
    if bk % LANES:
        p = jnp.exp(s - m[..., :1])
        lane = jax.lax.broadcasted_iota(jnp.int32, m.shape, m.ndim - 1)
        return p, jnp.where(lane == 0, jnp.sum(p, axis=-1, keepdims=True), 0.0)
    chunks = [jnp.exp(s[..., c : c + LANES] - m) for c in range(0, bk, LANES)]
    return jnp.concatenate(chunks, axis=-1), functools.reduce(jnp.add, chunks)


def _per_row(x, like):
    """A lane-replicated per-row value (..., rows, LANES), shaped to
    multiply the (..., rows, d) array ``like``: itself at head_dim 128,
    else its lane 0 as a column."""
    return x if like.shape[-1] == LANES else x[..., :1]


def _fwd_update(s, v, m_scr, l_scr, acc_scr):
    """One live step of the forward's online softmax, for both forward
    bodies (per head: 2-D ``s`` (bq, bk) and ``v`` (bk, d); head-batched:
    (HB, G*bq, bk) and (HB, bk, d)), as :func:`_bwd_tile` is the one copy
    of the backward's. ``s`` holds :data:`_MASK` off the mask.

    What a step pays per row, not per logit, is this function. On a v5e
    the older form (one-lane ``m`` and ``l`` columns, two cross-lane
    reductions, four ``-inf`` guards) made the forward cost 11.0 cycles a
    logit vreg at block_k 512 and 6.1 at 1024 against the MXU's 4, where
    the backward's kernels cost the same at either width (PERF.md section 6, PR 29;
    docs/block_sparse.md). So the state is kept cheap, and the chip priced
    each piece (packed 64k cell, forward kernel 109.0 ms before):

    - no ``-inf`` inside the step: ``m`` starts at the finite ``_MASK``,
      so ``alpha = exp(m_prev - m_new)`` and ``p = exp(s - m_new)`` need
      no guard. A row that has met no live column yet carries garbage in
      ``l`` and ``acc`` (``p`` = 1 on its masked columns); the first step
      that brings it a live column multiplies that by
      ``exp(_MASK - m_new)``, which is exactly 0, and a row no entry
      covers is set right where the block is written;
    - the row sum is lazy: ``l_scr`` holds per-lane partial sums in all
      its 128 lanes (:func:`_probs`), rescaled by ``alpha`` as the
      accumulator is, and is reduced across lanes once a q block, in
      :func:`_fwd_finalize`. Only the order of the float32 additions
      differs from a per-step row sum (with the guards: 79.5 ms);
    - the running maximum is reduced across lanes every step and is
      exact; it is kept replicated in all 128 lanes of ``m_scr`` and
      used at that shape: whole-vreg loads and stores (71.2 ms), and no
      lane broadcast into the logit tile, which is taken 128 lanes at a
      time (62.1 ms)."""
    nb = s.ndim - 2  # leading batch dims: 0 per head, 1 head-batched
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p, lane_sums = _probs(s, m_new)
    l_scr[...] = l_scr[...] * alpha + lane_sums
    acc_scr[...] = acc_scr[...] * _per_row(alpha, acc_scr) + jax.lax.dot_general(
        p.astype(v.dtype),
        v,
        dimension_numbers=(
            ((nb + 1,), (nb,)),
            (tuple(range(nb)), tuple(range(nb))),
        ),
        preferred_element_type=jnp.float32,
    )
    m_scr[...] = m_new


def _fwd_init(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, _MASK)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _fwd_finalize(m_scr, l_scr, acc_scr, sinks):
    """What a q block holds once its last entry is done: (out f32
    (..., rows, d), lse (..., rows, LANES), rowmax (..., rows, LANES)), the
    two statistics replicated over lanes, which is the form the state is
    kept in. What leaves the kernel crosses the boundary with rows along
    lanes instead, 1/128 of the bytes (:func:`_write_stats`).
    ``sinks``: the rows' sink logits, (..., rows, 1) or one scalar, or None.

    The lazy row sum is reduced across lanes here, and the public
    convention comes back here: a row that met no live column (its ``m``
    is still ``_MASK``) gets ``l = 0``, so ``out = 0`` and ``lse = -inf``
    (``lse = sink`` under a sink), and ``rowmax = -inf``; the garbage in
    its accumulator is finite and is multiplied by 0. Like the update it
    works on (rows, LANES) values, not (rows, 1) columns (packed 64k cell:
    62.1 -> 59.6 ms; the window cell, two entries a q block: 33.5 -> 31.0)."""
    m = m_scr[...]
    live = m > _MASK / 2
    l = jnp.where(live, jnp.sum(l_scr[...], axis=-1, keepdims=True), 0.0)
    acc = acc_scr[...]
    if sinks is not None:
        m_tot = jnp.maximum(m, sinks)
        resc = jnp.exp(m - m_tot)
        l = l * resc + jnp.exp(sinks - m_tot)
        acc = acc * _per_row(resc, acc)
    else:
        m_tot = m
    covered = l > 0.0
    l_safe = jnp.where(covered, l, 1.0)
    out = acc * _per_row(jnp.where(covered, 1.0 / l_safe, 0.0), acc)
    lse = jnp.where(covered, m_tot + jnp.log(l_safe), NEG_INF)
    return out, lse, jnp.where(live, m, NEG_INF)


def stats_form(block_q: int) -> str:
    """The form in which lse and the row maximum leave the forward kernel:
    ``"compact"``, rows along lanes, 4 bytes a row, where a q block is
    whole vregs of rows (the kernel turns its lane-replicated state 128
    rows at a time, :func:`_store_rows_along_lanes`); ``"lanes"``, every
    row's value in all 128 lanes, 512 bytes a row, for small test blocks.
    The backward reads lse and delta compact at every block
    (:func:`_bwd_pallas`): it turns nothing."""
    return "compact" if block_q % LANES == 0 else "lanes"


def _diag():
    row = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    return row == jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)


def _store_rows_along_lanes(ref, stats):
    """Write the lane-replicated (heads, bq, LANES) statistics ``stats``
    to one ``(1, 1, len(stats), heads, bq)`` block, rows along lanes: of
    every (128, 128) tile the diagonal, reduced over sublanes against
    ``-inf``. A select and a maximum with ``-inf``: each value arrives as
    it is, ``-inf`` rows too."""
    diag = _diag()
    for n, x in enumerate(stats):
        for h in range(x.shape[0]):
            for c in range(0, x.shape[1], LANES):
                ref[0, 0, n, h : h + 1, c : c + LANES] = jnp.max(
                    jnp.where(diag, x[h, c : c + LANES, :], NEG_INF),
                    axis=0,
                    keepdims=True,
                )


def _write_stats(lse, rowmax, refs, compact: bool):
    """Store a q block's (heads, bq, LANES) lse and row maximum. ``lanes``
    form: as they are, into two (heads, bq, LANES) blocks. ``compact``
    form: both with rows along lanes into ONE ``(1, 1, 2, heads, bq)``
    block (on the compact grid every blocked operand costs a table lookup
    a step: 0.9 ms a call of 233 k steps, docs/block_sparse.md)."""
    if compact:
        _store_rows_along_lanes(refs[0], (lse, rowmax))
    else:
        refs[0][...], refs[1][...] = lse, rowmax


def _fwd_kernel_hb(
    qblk,
    kblk,
    sid,
    runs,
    bounds,
    rs,
    rc,
    q_ref,  # (HBG, bq, d)
    k_ref,  # (HB, bk, d)
    v_ref,
    sink_ref,
    out_ref,
    *refs,  # statistics (_write_stats), then m (HB, G*bq, LANES), l, acc
    params: FlexAttnParams,
    group: int,
    compact: bool,
):
    """Head-batched forward: HB kv heads x their G q heads per grid step.

    q rows of the G heads sharing one kv head are stacked ((HB, G*bq, d))
    so the QK^T and PV products are single batched MXU calls; the mask is
    computed once per tile and broadcast over (HB, G).

    Both grids (:class:`_Walk`): on the row-major one i walks q blocks
    statically, j walks that block's entries (rs[i]..rs[i]+rc[i]), steps
    past the count clamp their k index (no DMA) and skip compute; on the
    compact one a step is an entry.

    The body keeps its loads, reshapes and the mask; the softmax state is
    :func:`_fwd_update` (a lazy, per-lane row sum; the finite ``_MASK`` in
    place of ``-inf`` inside a step) and :func:`_fwd_finalize`, where the
    row sum is reduced and ``-inf`` comes back for rows no entry covers.
    """
    *stat_refs, m_scr, l_scr, acc_scr = refs
    bq, bk = params.block_q, params.block_k
    hbg = q_ref.shape[0]
    hb = k_ref.shape[0]
    h = pl.program_id(0)
    w = _Walk(params.grid, qblk, rs, rc)
    i, e = w.i, w.e

    @pl.when(w.first())
    def _init():
        _fwd_init(m_scr, l_scr, acc_scr)

    @w.when_live
    def _compute():
        s = _scores_hb(q_ref, k_ref, params, group)
        mask = _entry_interval_mask(
            bounds, runs, sid[e], e, i * bq, kblk[e] * bk, bq, bk,
            params.mask_step > 1,
        )
        _fwd_update(
            _mask_hb(s, mask, group, _MASK), v_ref[...], m_scr, l_scr, acc_scr
        )

    @pl.when(w.last())
    def _finalize():
        sinks = None
        if params.has_sink:
            # per-q-head sink: rows of q head (h*hbg + hh) use sink[hh]
            sinks = jnp.stack(
                [
                    jnp.full((bq, 1), sink_ref[h * hbg + hh, 0], jnp.float32)
                    for hh in range(hbg)
                ],
                axis=0,
            ).reshape(hb, group * bq, 1)
        out, lse, rowmax = _fwd_finalize(m_scr, l_scr, acc_scr, sinks)
        out_ref[...] = out.reshape(hbg, bq, out_ref.shape[2]).astype(
            out_ref.dtype
        )
        _write_stats(
            lse.reshape(hbg, bq, LANES), rowmax.reshape(hbg, bq, LANES),
            stat_refs, compact,
        )


def _fwd_kernel(
    qblk,
    kblk,
    sid,
    runs,
    bounds,
    rs,
    rc,
    q_ref,
    k_ref,
    v_ref,
    sink_ref,
    out_ref,
    *refs,  # statistics (_write_stats), then m (bq, LANES), l, acc (bq, d)
    params: FlexAttnParams,
    compact: bool,
):
    """Per-head forward: one q head a grid step, on both grids; the
    softmax state is the head-batched body's (:func:`_fwd_update`,
    :func:`_fwd_finalize`)."""
    *stat_refs, m_scr, l_scr, acc_scr = refs
    bq, bk = params.block_q, params.block_k
    h = pl.program_id(0)
    w = _Walk(params.grid, qblk, rs, rc)
    i, e = w.i, w.e

    @pl.when(w.first())
    def _init():
        _fwd_init(m_scr, l_scr, acc_scr)

    @w.when_live
    def _compute():
        s = _scores(q_ref[0], k_ref[0], params.scale, params.softcap)
        mask = _entry_interval_mask(
            bounds, runs, sid[e], e, i * bq, kblk[e] * bk, bq, bk,
            params.mask_step > 1,
        )
        _fwd_update(
            jnp.where(mask, s, _MASK), v_ref[0], m_scr, l_scr, acc_scr
        )

    @pl.when(w.last())
    def _finalize():
        sink = sink_ref[h, 0] if params.has_sink else None
        out, lse, rowmax = _fwd_finalize(m_scr, l_scr, acc_scr, sink)
        out_ref[0] = out.astype(out_ref.dtype)
        _write_stats(lse[None], rowmax[None], stat_refs, compact)


def _compact_spec(n: int, hbg: int, bq: int, qmap):
    """Block of ``n`` per-row statistics with rows along lanes: the q
    block's ``(n, HBG, bq)``, where the (heads, q block, 0) map of the
    q-side operands points. The block's last two dimensions are the
    array's, which is legal at any head block."""
    return pl.BlockSpec(
        (1, 1, n, hbg, bq), lambda *args: (*qmap(*args)[:2], 0, 0, 0)
    )


def _rows_from_compact(x, hq: int, tqp: int):
    """(hq / HBG, nq, n, HBG, bq) -> n arrays [hq, tqp]."""
    x = jnp.transpose(x, (2, 0, 3, 1, 4))
    return tuple(x.reshape(x.shape[0], hq, tqp))


def _rows_to_compact(stats, hbg: int, bq: int):
    """n arrays [hq, tqp] -> (hq / HBG, nq, n, HBG, bq): the blocks of
    :func:`_compact_spec` at a head block and a q block of the caller's
    (the backward's head block may be 1 where the forward's was 8)."""
    x = jnp.stack(stats)
    n, hq, tqp = x.shape
    x = x.reshape(n, hq // hbg, hbg, tqp // bq, bq)
    return jnp.transpose(x, (1, 3, 0, 2, 4))


def _fwd_pallas(q, k, v, sink2d, tables, params: FlexAttnParams):
    """q [hq, tqp, d]; k [hk, tkp, d]; v [hk, tkp, dv]; tables from
    fwd_tables(). Returns (out [hq, tqp, dv], lse [hq, tqp], rowmax
    [hq, tqp]), differentiated or not: the backward's residual is that
    lse (:func:`_bwd_pallas` makes its kernel's operand). The value width
    ``dv`` is v's own (latent attention's 128 beside keys of 192): v, out
    and the accumulator take it, q and k the key width ``d``; a step's
    ``P V`` is then ``dv`` lanes wide and its ``Q K^T`` ``d`` deep.

    The two statistics leave the kernel in :func:`stats_form`'s form. In
    the ``compact`` one the kernel writes one ``(hq / HBG, nq, 2, HBG, bq)``
    array, rows along lanes (:func:`_compact_spec`), which XLA turns to
    two [hq, tqp] on 4 bytes a row. In the ``lanes`` one it writes both
    replicated and XLA takes lane 0.

    Row-major grid (hq/HBG, nq, steps): the q/out/lse index maps are
    static in the inner dimension; dead steps (j >= row count) clamp the K
    index — no fresh DMA — and skip compute. Compact grid (hq/HBG, E): the
    q-side maps read the table (``qblk[e]``, non-decreasing, so a q block
    stays where it is across its entries) and no step is dead.
    """
    qblk, kblk, sid, runs, bounds = tables
    hq, tqp, d = q.shape
    hk, dv = k.shape[0], v.shape[2]
    group = hq // hk
    hbg = params.head_block
    bq, bk = params.block_q, params.block_k
    E = qblk.shape[0]
    nq = tqp // bq
    rs, rc = _row_tables(qblk, nq)
    form = stats_form(bq)
    compact = form == "compact"

    if hbg > 1:
        # head-batched: HB kv heads and their G q heads each a step
        _check_head_block(hbg, hq, group)
        hb = hbg // group
        body = functools.partial(
            _fwd_kernel_hb, params=params, group=group, compact=compact
        )
        rows = (hb, group * bq)
        k_head = lambda h: h  # noqa: E731
        cost = None
    else:
        hb = 1
        body = functools.partial(_fwd_kernel, params=params, compact=compact)
        rows = (bq,)
        k_head = lambda h: h // group  # noqa: E731
        cost = pl.CostEstimate(
            flops=2 * int(E) * bq * bk * (d + dv) * hq,
            bytes_accessed=q.size * q.dtype.itemsize
            + (k.size + v.size) * k.dtype.itemsize,
            transcendentals=int(E) * bq * bk * hq,
        )
    grid, qmap, kmap, semantics = _walk_grid(
        params.grid, hq // hbg, qblk, nq, params.fwd_steps, k_head
    )

    if compact:
        stat_specs = [_compact_spec(2, hbg, bq, qmap)]
        stat_shapes = [
            jax.ShapeDtypeStruct((hq // hbg, nq, 2, hbg, bq), jnp.float32)
        ]
    else:
        stat_specs = [pl.BlockSpec((hbg, bq, LANES), qmap) for _ in range(2)]
        stat_shapes = [
            jax.ShapeDtypeStruct((hq, tqp, LANES), jnp.float32)
        ] * 2

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=grid,
        in_specs=[
            pl.BlockSpec((hbg, bq, d), qmap),
            pl.BlockSpec((hb, bk, d), kmap),
            pl.BlockSpec((hb, bk, dv), kmap),
            pl.BlockSpec(memory_space=pltpu.SMEM),  # sink [hq, 1]
        ],
        out_specs=[pl.BlockSpec((hbg, bq, dv), qmap), *stat_specs],
        scratch_shapes=[
            pltpu.VMEM((*rows, LANES), jnp.float32),
            pltpu.VMEM((*rows, LANES), jnp.float32),
            pltpu.VMEM((*rows, dv), jnp.float32),
        ],
    )
    out, *stats = _flex_pallas_call(
        "fwd",
        hbg,
        params.grid,
        body,
        form={"stats": form, **_value_width_label(d, dv)},
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((hq, tqp, dv), params.out_jnp_dtype),
            *stat_shapes,
        ],
        interpret=params.interpret,
        compiler_params=_compiler_params(*semantics),
        cost_estimate=cost,
    )(qblk, kblk, sid, runs, bounds, rs, rc, q, k, v, sink2d)
    with named_scope("magi_layout"):
        if compact:
            return (out, *_rows_from_compact(stats[0], hq, tqp))
        return (out, *(x[:, :, 0] for x in stats))


# ---------------------------------------------------------------------------
# backward: one k-major walk; dk / dv in VMEM, dq added into HBM
# ---------------------------------------------------------------------------


def _p_ds(s, dp, lse, delta, softcap: float):
    """``p = exp(s - lse)`` and ``ds = p * (dP - delta)`` with the softcap
    derivative and the off-mask NaN guard, element-wise on float32 values
    of one shape up to broadcasting: the backward's numerically delicate
    block. ``s`` keeps ``-inf`` off the mask: ``exp(-inf - lse)`` is
    exactly 0 there."""
    # rows no entry covers: lse = -inf, and s = -inf all along them, so
    # any finite value here makes p exactly 0 (-inf - -inf would be nan)
    lse = jnp.maximum(lse, jnp.float32(jnp.finfo(jnp.float32).min))
    p = jnp.exp(s - lse)
    ds = p * (dp - delta)
    if softcap > 0.0:
        ds = ds * (1.0 - (s / jnp.float32(softcap)) ** 2)
        ds = jnp.where(jnp.isneginf(s), 0.0, ds)  # nan guard off-mask
    return p, ds


def _matmul_dims(nb: int, lhs_t: bool = False, rhs_t: bool = False):
    """Dimension numbers of a matmul under ``nb`` leading batch dimensions,
    either operand taken transposed: ``A B``, ``A^T B``, ``A B^T``."""
    batch = tuple(range(nb))
    return (((nb + (not lhs_t),), (nb + rhs_t,)), (batch, batch))


def _stat_rows(stats_ref, hb, group: int):
    """lse and delta of a step's q heads out of its compact
    ``(1, 1, 2, HBG, bq)`` block, each as the rows the transposed tile
    takes them in: ``(1, bq)`` per head, ``(HB, 1, G*bq)`` head-batched (a
    kv head's ``G`` q heads side by side along the lanes, as its stacked q
    rows are). One sublane a head is loaded; nothing crosses lanes."""
    if hb is None:
        return stats_ref[0, 0, 0], stats_ref[0, 0, 1]
    return tuple(
        jnp.stack([
            jnp.concatenate(
                [
                    stats_ref[0, 0, n, h : h + 1, :]
                    for h in range(b * group, (b + 1) * group)
                ],
                axis=-1,
            )
            for b in range(hb)
        ])
        for n in (0, 1)
    )


def _bwd_tile(
    q_ref, k_ref, v_ref, do_ref, stats_ref, entry, params: FlexAttnParams,
    group: int, hb,
):
    """The five matmuls of one live backward tile, for both bodies (per
    head: ``hb=None``, (1, rows, .) blocks; head-batched: ``hb=HB``, the
    q-side blocks (HBG, bq, .) stacked per kv head to (HB, G*bq, .)).
    Returns (this tile's ``P^T dO`` (..., bk, dv), its ``dS^T Q`` (..., bk,
    d) before the scale, and a function that gives ``scale * dS K`` as
    (heads, bq, d)).

    The tile is computed transposed, ``S^T = K Q^T`` and ``dP^T = V dO^T``
    (..., bk, rows), keys down the sublanes and q rows along the lanes. A
    head's lse and delta then are ``(1, bq)`` rows that broadcast down the
    sublanes, which is the form they cross the boundary in (4 bytes a row,
    :func:`_stat_rows`), ``P^T dO`` and ``dS^T Q`` are plain contractions,
    and only ``dS K`` contracts a transposed left operand. The tile's mask
    is built in that orientation too; ``entry``: the leading arguments of
    :func:`_entry_interval_mask`, up to the tile's corner. One body at
    every ``block_q``: a block under a vreg's 128 lanes (a pinned block,
    the CPU tests') takes the same steps on part of a vreg."""
    nb = 0 if hb is None else 1
    bq = params.block_q

    def rows(ref):
        if hb is None:
            return ref[0]
        return ref[...].reshape(hb, group * bq, ref.shape[2])

    def cols(ref):
        return ref[0] if hb is None else ref[...]

    def matmul(a, b, **transposed):
        return jax.lax.dot_general(
            a, b, dimension_numbers=_matmul_dims(nb, **transposed),
            preferred_element_type=jnp.float32,
        )

    q, do, k, v = rows(q_ref), rows(do_ref), cols(k_ref), cols(v_ref)
    s = matmul(k, q, rhs_t=True) * jnp.float32(params.scale)
    if params.softcap > 0.0:
        s = jnp.float32(params.softcap) * jnp.tanh(
            s / jnp.float32(params.softcap)
        )
    mask = _entry_interval_mask(
        *entry, bq, params.block_k, params.mask_step > 1, transposed=True
    )
    if hb is None:
        s = jnp.where(mask, s, NEG_INF)
    else:  # a kv head's G q heads lie side by side along the lanes
        s = jnp.concatenate(
            [
                jnp.where(mask[None], s[..., g * bq : (g + 1) * bq], NEG_INF)
                for g in range(group)
            ],
            axis=-1,
        )
    lse, delta = _stat_rows(stats_ref, hb, group)
    p, ds = _p_ds(s, matmul(v, do, rhs_t=True), lse, delta, params.softcap)
    ds = ds.astype(q.dtype)

    def dq():
        x = jnp.float32(params.scale) * matmul(ds, k, lhs_t=True)
        return x[None] if hb is None else x.reshape(q_ref.shape[0], bq, -1)

    return matmul(p.astype(do.dtype), do), matmul(ds, q), dq


def _dq_step(qblk, runs, e, head0, g=0, group: int = 1):
    """Where a live backward step stands in dq's walk, as
    :func:`_dq_accumulate` takes it: the step of entry ``e`` of the k-major
    table ``(qblk, runs)`` whose dq tile starts at head ``head0 + g``. The
    per-head body walks a kv head's ``group`` q heads innermost, so each
    entry is ``group`` steps on ``group`` tiles, and its visit bits are
    theirs all; the head-batched body's step is the entry. The next live
    step is the next entry's on both grids: a dead row-major step runs
    none of this, and every k block has an entry."""
    n = qblk.shape[0]
    e_next = jnp.minimum(e + 1, n - 1)
    if group == 1:
        wraps, at, head_next = True, e_next, head0
    else:
        wraps = g == group - 1
        at = jnp.where(wraps, e_next, e)
        head_next = head0 + jnp.where(wraps, 0, g + 1)
    flag_word = lambda entry: runs[entry * RUN_FIELDS + 6]  # noqa: E731
    return dict(
        tile=(head0 + g, qblk[e]),
        nxt=(head_next, qblk[at]),
        flags=flag_word(e),
        nxt_flags=flag_word(at),
        first=(e == 0) & (g == 0),
        last=(e == n - 1) & wraps,
    )


def _bwd_head_block(params: FlexAttnParams, hq: int, group: int) -> int:
    """q heads one row-major backward step takes: ``params.head_block``
    (what the forward takes, and what the tuner's cost model prices every
    kernel at), or 1 where the batched step does not fit the VMEM the
    kernels ask for. The test is on shapes alone: the step keeps four f32
    (head_block * block_q, block_k) intermediates live (s, p, dP, dS). The
    (head_block, block, head_dim) operand tiles are not counted: at
    head_dim 256 they double, and every rung the tuner can return still
    compiles under this test as it is (20 q = 20 kv heads, both grids)."""
    hbg = params.head_block
    if hbg <= 1:
        return 1
    _check_head_block(hbg, hq, group)
    live = 4 * 4 * hbg * params.block_q * params.block_k
    return hbg if live <= _BWD_HB_LIVE_BYTES else 1


def _dq_accumulate(
    dq_acc, dq_out, buf, stage, sem, st, contribution, *, tile, nxt, flags,
    nxt_flags, first, last, bq, d,
):
    """One live step's share of dq, on a walk that does not keep a q block
    in place. ``tile = (first head, q block)`` names this step's tile of
    the two ``[hq, tqp, d]`` buffers in HBM: ``dq_acc``, float32 scratch
    that only this walk reads, and ``dq_out``, the result in the inputs'
    dtype. ``flags`` is the entry's flag word of the k-major runs table
    (``block_meta.mark_q_visits``): whether this is the FIRST_VISIT and
    whether it is the LAST_VISIT of the q block in table order, which is
    the order in time, because the steps of one core run in order (and so
    the buffers need no atomics). ``nxt`` / ``nxt_flags``: the same of the
    next live step. What a step does:

    - the tile's sums so far come into one of ``buf``'s two float32 VMEM
      slots: on a first visit there are none and nothing is read
      (``dq_acc`` is read only where this walk has written); else the read
      that the step before started is waited for;
    - ``contribution()``, this step's ``scale * dS K`` (heads, bq, ``d``),
      is added to them in the slot: stored as it is on a first visit,
      which as a float32 value is the ``0 + x`` a zero-filled tile gave;
    - on a last visit the slot is rounded to the inputs' dtype into one of
      ``stage``'s two slots, whose write to ``dq_out`` is started, and
      nothing goes back to ``dq_acc`` (a tile visited once never touches
      it); else the slot's float32 write-back is started;
    - the read of ``nxt`` is started into the other slot, unless its visit
      is a first one.

    The orderings the step keeps itself:

    - a read of a tile never starts while a write to the same tile is in
      flight: where ``nxt == tile`` (a column boundary, two slices on one
      tile, a mask with one q block) the tile stays in its slot and makes
      no round trip; a tile written a step earlier has been waited for
      (below) before any read starts;
    - a slot of ``buf`` is read or stored into only when its last
      write-back has landed, and a slot of ``stage`` is rounded into only
      when the write started from it two last visits ago has.

    ``first`` / ``last``: the first and the last live step of this head
    block's walk. The first entry of a table is a first visit, so the walk
    starts without a read; the last step waits for every write still in
    flight: a head block begins and ends with nothing in flight, so the
    head axis may be ``parallel``. ``st`` (SMEM, 4 words) carries the
    slot, whether the tile is already in it, whether the other slot's
    write-back is in flight, and the count of writes to ``dq_out`` started
    (its parity is the staging slot); ``sem`` is ``DMA((3, 2))``: reads,
    write-backs, result writes x slot."""
    heads = buf.shape[1]

    def rows(hbm, t):
        head0, qb = t
        return hbm.at[
            pl.ds(head0, heads), pl.ds(pl.multiple_of(qb * bq, bq), bq)
        ]

    def read(t, slot):
        return pltpu.make_async_copy(
            rows(dq_acc, t), buf.at[slot], sem.at[0, slot]
        )

    def write_back(t, slot):
        return pltpu.make_async_copy(
            buf.at[slot], rows(dq_acc, t), sem.at[1, slot]
        )

    def write_out(t, slot):
        return pltpu.make_async_copy(
            stage.at[slot], rows(dq_out, t), sem.at[2, slot]
        )

    @pl.when(first)
    def _start():
        for word in range(4):
            st[word] = 0

    cur = st[0]
    first_visit = (flags & FIRST_VISIT) != 0
    last_visit = (flags & LAST_VISIT) != 0

    @pl.when(jnp.logical_not(first_visit) & (st[1] == 0))
    def _arrive():  # (a first visit is never a tile kept from the step before)
        read(tile, cur).wait()

    # One pass over the tile's vregs whatever the bits are, in line with the
    # step's other matmuls: a first visit's sums are the product itself (as
    # a float32 value the ``0 + x`` a zero-filled tile gave) and the select
    # drops whatever the slot held. On the chip the select costs nothing,
    # a slot zeroed first or the sum inside a branch does (PERF.md section
    # 6, PR 44). The tile's lanes past ``d`` are padding that nobody reads:
    # the result is cut to ``d`` where it leaves the launcher
    sums = (cur, slice(None), slice(None), slice(0, d))
    x = contribution()
    buf[sums] = jnp.where(first_visit, x, buf[sums] + x)
    # the next step on the same tile is a later visit, so this is no last
    keep = jnp.logical_not(last) & (nxt[0] == tile[0]) & (nxt[1] == tile[1])
    st[1] = keep.astype(jnp.int32)
    leaving = jnp.logical_not(keep)

    @pl.when(leaving & jnp.logical_not(last_visit))
    def _back():
        write_back(tile, cur).start()

    @pl.when(leaving & last_visit)
    def _result():
        n_out = st[3]
        slot = n_out & 1

        @pl.when(n_out >= 2)
        def _staged():
            write_out(tile, slot).wait()

        stage[slot] = buf[cur].astype(stage.dtype)
        write_out(tile, slot).start()
        st[3] = n_out + 1

    @pl.when(leaving)
    def _leave():
        @pl.when(st[2] == 1)
        def _landed():
            write_back(tile, 1 - cur).wait()

        @pl.when(last)
        def _drain():
            # (the table's last entry is a last visit: no write-back here)
            for back in (1, 2):  # the result writes not yet waited for

                @pl.when(st[3] >= back)
                def _out():
                    write_out(tile, (st[3] - back) & 1).wait()

        @pl.when(jnp.logical_not(last) & ((nxt_flags & FIRST_VISIT) == 0))
        def _ahead():
            read(nxt, 1 - cur).start()

        st[0] = 1 - cur
        st[2] = jnp.logical_not(last_visit).astype(jnp.int32)


def _bwd_kernel(
    kblk,
    qblk,
    sid,
    runs,
    bounds,
    rs,
    rc,
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    stats_ref,  # (1, 1, 2, 1, bq): lse, delta (:func:`_bwd_pallas`)
    dk_ref,
    dv_ref,
    dq_out,  # [hq, tqp, d] in the inputs' dtype, in HBM
    dq_acc,  # [hq, tqp, d] float32, in HBM: scratch of this walk
    dk_scr,
    dv_scr,
    dq_buf,  # (2, 1, bq, d) float32
    dq_stage,  # (2, 1, bq, d) in the inputs' dtype
    dq_sem,
    dq_st,
    *,
    params: FlexAttnParams,
    group: int,
):
    """The whole backward of one tile, per head: a k-major walk with the
    GQA group innermost, row grid (hk, nk, steps, group) or compact (hk,
    E2, group). The K/V blocks and the dk/dv accumulators stay resident per
    k block while Q/dO/lse/delta stream through the entry lookups, and
    ``scale * dS K`` is added to the tile's q rows of dq in HBM
    (:func:`_dq_accumulate`): S, the mask, P, dP and dS are computed once
    a tile (:func:`_bwd_tile`)."""
    bq, bk = params.block_q, params.block_k
    w = _Walk(params.grid, kblk, rs, rc, inner=True)
    i, e, g = w.i, w.e, w.g
    h = pl.program_id(0)

    @pl.when(w.first() & (g == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @w.when_live
    def _compute():
        dv, dk, dq = _bwd_tile(
            q_ref, k_ref, v_ref, do_ref, stats_ref,
            (bounds, runs, sid[e], e, qblk[e] * bq, i * bk), params, group,
            None,
        )
        dv_scr[...] += dv
        dk_scr[...] += jnp.float32(params.scale) * dk
        _dq_accumulate(
            dq_acc, dq_out, dq_buf, dq_stage, dq_sem, dq_st, dq, bq=bq,
            d=k_ref.shape[2],
            **_dq_step(qblk, runs, e, h * group, g, group),
        )

    @pl.when(w.last() & (g == group - 1))
    def _write():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_kernel_hb(
    kblk,
    qblk,
    sid,
    runs,
    bounds,
    rs,
    rc,
    q_ref,  # (HBG, bq, d)
    k_ref,  # (HB, bk, d)
    v_ref,
    do_ref,  # (HBG, bq, d)
    stats_ref,  # (1, 1, 2, HBG, bq): lse, delta (:func:`_bwd_pallas`)
    dk_ref,  # (HB, bk, d)
    dv_ref,
    dq_out,  # [hq, tqp, d] in the inputs' dtype, in HBM
    dq_acc,  # [hq, tqp, d] float32, in HBM: scratch of this walk
    dk_scr,
    dv_scr,
    dq_buf,  # (2, HBG, bq, d) float32
    dq_stage,  # (2, HBG, bq, d) in the inputs' dtype
    dq_sem,
    dq_st,
    *,
    params: FlexAttnParams,
    group: int,
):
    """Head-batched form of :func:`_bwd_kernel`, the layout of
    :func:`_fwd_kernel_hb`, row grid (hk/HB, nk, steps) or compact (hk/HB,
    E2): the group is inside the step. dv += P^T dO and dk += dS^T Q
    contract over the G*bq stacked rows of each kv head's group, dS K is
    one batched MXU call over the same rows; K, V and the two accumulators
    stay resident per k block."""
    bq, bk = params.block_q, params.block_k
    hb = k_ref.shape[0]
    hbg = q_ref.shape[0]
    w = _Walk(params.grid, kblk, rs, rc)
    i, e = w.i, w.e
    h = pl.program_id(0)

    @pl.when(w.first())
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @w.when_live
    def _compute():
        dv, dk, dq = _bwd_tile(
            q_ref, k_ref, v_ref, do_ref, stats_ref,
            (bounds, runs, sid[e], e, qblk[e] * bq, i * bk), params, group,
            hb,
        )
        dv_scr[...] += dv
        dk_scr[...] += jnp.float32(params.scale) * dk
        _dq_accumulate(
            dq_acc, dq_out, dq_buf, dq_stage, dq_sem, dq_st, dq, bq=bq,
            d=k_ref.shape[2],
            **_dq_step(qblk, runs, e, h * hbg),
        )

    @pl.when(w.last())
    def _write():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def dq_form(params: FlexAttnParams, q_block, num_q_blocks: int) -> str:
    """Where the backward's dq output gets the rows that no entry of the
    k-major table names (a remote stage at cp > 1, rows outside every
    slice): ``"visits"``, the table names every q block, and each is
    written by its last visit alone; ``"zero_filled"``, some block is left
    out, and the output is aliased to a zero fill in the inputs' dtype.
    A fact of the plan, read on the host: ``params.bwd_unnamed_q`` where
    the plan builder counted it (per-rank tables are traced here), else
    the concrete table; a traced table nobody counted takes the fill."""
    unnamed = params.bwd_unnamed_q
    if unnamed is None:
        if isinstance(q_block, jax.core.Tracer):
            return "zero_filled"
        unnamed = q_visit_counts(np.asarray(q_block), num_q_blocks)[1]
    return "zero_filled" if unnamed else "visits"


def _bwd_pallas(q, k, v, do, lse, delta, tables, params: FlexAttnParams):
    """(dq, dk, dv in their inputs' dtype) from one kernel over the k-major
    table. ``lse`` and ``delta`` are [hq, tqp] float32 and cross the
    boundary as ONE operand ``(hq / HBG, nq, 2, HBG, bq)``, lse at index 0
    and delta at 1, rows along lanes at this kernel's own head block
    (:func:`_bwd_head_block` may give 1 where the forward had 8), 4 bytes
    a row each, which the step uses as it lies (:func:`_bwd_tile`), at
    every ``block_q``: the block's last two dimensions are the array's.
    dq is two buffers ``[hq, tqp, d]`` in HBM (``memory_space=ANY``)
    that the steps move by their own DMAs (:func:`_dq_accumulate`): the
    float32 sums, scratch of the walk (written on a q block's visits but
    the last, read on all but the first, dropped here; aliased to an
    operand nobody has written, see below), and the result in the inputs'
    dtype, which each q block's last visit writes: aliased to dO where the
    table names every q block, to a zero fill of its own under
    :func:`dq_form` ``"zero_filled"``. The axis that walks k blocks is
    ``arbitrary`` on both grids (two k blocks add into one dq tile). A tile
    is copied whole vregs of lanes at a time, so at a head_dim that is no
    multiple of 128 the buffers are that much wider and the result is cut
    here. v, dO and dv are ``v.shape[2]`` wide, q, k, dq and dk ``d``
    (:func:`_fwd_pallas`): dP = dO V^T contracts the value width, and dO's
    buffer serves dq's result only where the two widths are one."""
    kblk, qblk, sid, runs, bounds = tables
    hq, tqp, d = q.shape
    hk, tkp, _ = k.shape
    dv = v.shape[2]
    group = hq // hk
    hbg = _bwd_head_block(params, hq, group)
    bq, bk = params.block_q, params.block_k
    nk = tkp // bk
    rs, rc = _row_tables(kblk, nk)

    kernel = functools.partial(
        _bwd_kernel_hb if hbg > 1 else _bwd_kernel, params=params,
        group=group,
    )
    if hbg > 1:
        hb = hbg // group
        kv_scratch = [
            pltpu.VMEM((hb, bk, w), jnp.float32) for w in (d, dv)
        ]
        grid, kmap, qmap, semantics = _walk_grid(
            params.grid, hk // hb, kblk, nk, params.bwd_steps,
            blocks="arbitrary",
        )
    else:
        hb = 1
        kv_scratch = [pltpu.VMEM((bk, w), jnp.float32) for w in (d, dv)]
        grid, kmap, qmap, semantics = _walk_grid(
            params.grid, hk, kblk, nk, params.bwd_steps,
            lambda h, g: h * group + g, inner=(group,), blocks="arbitrary",
        )
    dq_shape = (hq, tqp, -(-d // LANES) * LANES)
    form = dq_form(params, qblk, tqp // bq)
    operands = [kblk, qblk, sid, runs, bounds, rs, rc, q, k, v, do]
    with named_scope("magi_layout"):
        operands.append(_rows_to_compact((lse, delta), hbg, bq))
    n_blocked = len(operands)
    # Operands in HBM that only give two outputs their buffers (aliased,
    # never read through these refs: the body is not handed them). The
    # float32 sums get a buffer nobody has written (``lax.empty``: XLA's
    # AllocateBuffer on the chip, no pass; zeros under the interpreter):
    # no step reads what this walk has not written. An operand all the
    # same, and not a bare output: with a bare output the SDAR cell's
    # check compiles to another program throughout (memory-space
    # assignment, the forward's too) that reads its gradients 7x further
    # off on the chip whatever the step does; with this operand the
    # program is the zero-filled one's but for the fill (PERF.md section
    # 6, PR 44)
    with named_scope("magi_layout"):
        operands.append(jax.lax.empty(dq_shape, jnp.float32))
        aliases = {n_blocked: 3}
        if form == "zero_filled":
            operands.append(jnp.zeros(dq_shape, q.dtype))
            aliases[n_blocked + 1] = 2
    if form == "visits" and do.shape == dq_shape:
        # nothing to fill: the result takes dO's place in HBM. A q block's
        # last visit is the last step that reads its dO tile (fetched
        # before the step, never again: no later entry names the block),
        # and dO is dead after the kernel, so the two never meet; without
        # it the program holds the float32 sums, the result and every
        # operand at once, one dq in the inputs' dtype more than before
        aliases[10] = 2  # (the seven tables, q, k, v, then dO)
    n_fills = len(operands) - n_blocked
    body = lambda *refs: kernel(*refs[:n_blocked], *refs[n_blocked + n_fills :])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=grid,
        in_specs=[
            pl.BlockSpec((hbg, bq, d), qmap),
            pl.BlockSpec((hb, bk, d), kmap),
            pl.BlockSpec((hb, bk, dv), kmap),
            pl.BlockSpec((hbg, bq, dv), qmap),
            _compact_spec(2, hbg, bq, qmap),
            *[pl.BlockSpec(memory_space=pl.ANY)] * n_fills,
        ],
        out_specs=[
            pl.BlockSpec((hb, bk, d), kmap),
            pl.BlockSpec((hb, bk, dv), kmap),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            *kv_scratch,
            pltpu.VMEM((2, hbg, bq, dq_shape[2]), jnp.float32),
            pltpu.VMEM((2, hbg, bq, dq_shape[2]), q.dtype),
            pltpu.SemaphoreType.DMA((3, 2)),
            pltpu.SMEM((4,), jnp.int32),
        ],
    )
    dk, dv, dq, _scratch = _flex_pallas_call(
        "bwd",
        hbg,
        params.grid,
        body,
        form={
            "stats": "compact", "delta": "xla", "dq": form,
            **_value_width_label(d, dv),
        },
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((hk, tkp, d), k.dtype),
            jax.ShapeDtypeStruct((hk, tkp, dv), v.dtype),
            jax.ShapeDtypeStruct(dq_shape, q.dtype),
            jax.ShapeDtypeStruct(dq_shape, jnp.float32),
        ],
        input_output_aliases=aliases,
        interpret=params.interpret,
        compiler_params=_compiler_params(*semantics),
    )(*operands)
    return dq[:, :, :d], dk, dv


def _bwd_delta(do, out, dlse):
    """``delta = rowsum(dO * out) - dlse`` [hq, tqp] float32 (the lse
    cotangent folds into it: with out = softmax(s) @ v and lse =
    logsumexp(s), dL/ds = p * (dP - (delta - dlse)), which is what makes
    multi-stage lse-merging differentiable with stage-local lse). On the
    k-major walk a q block has no first step to make it in, so it is made
    before the kernel."""
    with named_scope("magi_bwd_delta"):
        delta = jnp.sum(
            do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
        )
        if dlse is not None:
            delta = delta - dlse.astype(jnp.float32)
        return delta


# ---------------------------------------------------------------------------
# differentiable core (head-major, padded)
# ---------------------------------------------------------------------------


def _zero_tangents(tables):
    return tuple(
        np.zeros(t.shape, dtype=jax.dtypes.float0) for t in tables
    )


def _fwd_dispatch(q, k, v, sink2d, ftab, params: FlexAttnParams):
    if params.grid not in GRID_KINDS:
        raise ValueError(
            f"flex-attn: params.grid={params.grid!r} must be one of "
            f"{GRID_KINDS}"
        )
    return _fwd_pallas(q, k, v, sink2d, ftab, params)


# The names a kept call gives its out and its lse [hq, tqp]
# (``jax.ad_checkpoint.checkpoint_name``): a ``jax.checkpoint`` whose policy
# is ``save_only_these_names(*KEPT_NAMES)`` saves those two and nothing else
# of the layer, so its recomputation remakes q, k and v and finds the
# forward kernel's outputs there: that ``pallas_call`` is dead code in it
KEPT_NAMES = ("magi_flex_out", "magi_flex_lse")


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _flex_attn_core(q, k, v, sink2d, ftab, btab, params: FlexAttnParams):
    """(out [hq, tqp, d], lse [hq, tqp], rowmax [hq, tqp]): the same
    forward kernel differentiated or not."""
    return _fwd_dispatch(q, k, v, sink2d, ftab, params)


def _flex_attn_core_fwd(q, k, v, sink2d, ftab, btab, params: FlexAttnParams):
    """The residual beside q, k, v and out: the lse [hq, tqp], 4 bytes a
    row, which under ``params.kept`` the checkpoint round the layer saves
    with out by name."""
    # symbolic_zeros: every argument arrives as a CustomVJPPrimal
    q, k, v, sink2d = q.value, k.value, v.value, sink2d.value
    ftab, btab = (tuple(t.value for t in tab) for tab in (ftab, btab))
    out, lse, rowmax = _fwd_dispatch(q, k, v, sink2d, ftab, params)
    if params.kept:
        from .. import telemetry

        telemetry.record_flex_forward_kept(params.kept)
        # the primal outputs are the named arrays too: what reads out
        # after the call reads the saved one in the recomputation
        out, lse = map(checkpoint_name, (out, lse), KEPT_NAMES)
    return (out, lse, rowmax), (q, k, v, sink2d, out, lse, ftab, btab)


def _flex_attn_core_bwd(params: FlexAttnParams, residuals, grads):
    q, k, v, sink2d, out, lse, ftab, btab = residuals
    # The lse cotangent is first-class (it folds into delta, _bwd_delta);
    # a model that never reads lse hands a symbolic zero, and then nothing
    # is subtracted. rowmax stays non-diff.
    dout, dlse, _dmax = grads
    with named_scope("magi_layout"):
        if isinstance(dout, SymbolicZero):
            do = jnp.zeros(out.shape, q.dtype)
        else:
            do = dout.astype(q.dtype)
    if isinstance(dlse, SymbolicZero):
        dlse = None
    delta = _bwd_delta(do, out, dlse)
    dq, dk, dv = _bwd_pallas(q, k, v, do, lse, delta, btab, params)
    with named_scope("magi_bwd_delta"):
        if params.has_sink:
            # dL/dsink_h = -sum_q exp(sink_h - lse_hq) * delta_eff_hq
            sink = sink2d[:, :1]
            w = jnp.where(lse == NEG_INF, 0.0, jnp.exp(sink - lse))
            dsink = -(w * delta).sum(axis=1, keepdims=True)
            dsink2d = jnp.broadcast_to(dsink, sink2d.shape).astype(
                sink2d.dtype
            )
        else:
            dsink2d = jnp.zeros_like(sink2d)
    return dq, dk, dv, dsink2d, _zero_tangents(ftab), _zero_tangents(btab)


_flex_attn_core.defvjp(
    _flex_attn_core_fwd, _flex_attn_core_bwd, symbolic_zeros=True
)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def _pad_tokens(x, target, axis):
    pad = target - x.shape[axis]
    if pad <= 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


def _dense_mask_from_tables(ftab, tqp, tkp, bq, bk):
    """Materialize the [tqp, tkp] boolean mask the forward entry table
    describes — the jnp-backend analogue of the kernel's per-tile
    ``_entry_mask`` walk. Entries of different slices touching the same
    tile OR together; dummy entries point at the all-zero sentinel slice
    and contribute nothing."""
    qblk, kblk, sid, runs, bounds = ftab
    E = qblk.shape[0]

    def body(e, dense):
        row0 = qblk[e] * bq
        col0 = kblk[e] * bk
        tile = _entry_mask(bounds, runs, sid[e], e, row0, col0, bq, bk)
        cur = jax.lax.dynamic_slice(dense, (row0, col0), (bq, bk))
        return jax.lax.dynamic_update_slice(dense, cur | tile, (row0, col0))

    return jax.lax.fori_loop(
        0, E, body, jnp.zeros((tqp, tkp), jnp.bool_)
    )


def _fwd_jnp(q, k, v, sink2d, ftab, params: FlexAttnParams):
    """Reference-backend forward (MAGI_ATTENTION_KERNEL_BACKEND=jnp): dense
    attention over the mask the entry table encodes, in plain jnp.

    Role of the reference's SDPA/SDPA-online backends
    (functional/sdpa.py, :145/:379): an any-platform, any-dtype (fp64 with
    jax_enable_x64) path through the *distributed* runtime for precision
    auditing — it consumes the same tables, casts, and LSE-merge as the
    Pallas path, swapping only the kernel. Differentiable by construction
    (no custom vjp), mirroring the Pallas epilogue's exact semantics:
    uncovered rows read out=0 / lse=-inf (lse=sink when has_sink);
    rowmax excludes the sink and is non-differentiable.
    """
    hq, tqp, d = q.shape
    hk = k.shape[0]
    tkp = k.shape[1]
    group = hq // hk
    mask = _dense_mask_from_tables(ftab, tqp, tkp, params.block_q, params.block_k)

    acc_t = jnp.promote_types(q.dtype, jnp.float32)
    kf = jnp.repeat(k, group, axis=0)  # GQA: kv head = h // group
    vf = jnp.repeat(v, group, axis=0)
    z = jnp.einsum(
        "hqd,hkd->hqk", q.astype(acc_t), kf.astype(acc_t)
    ) * jnp.asarray(params.scale, acc_t)
    if params.softcap > 0.0:
        cap = jnp.asarray(params.softcap, acc_t)
        z = cap * jnp.tanh(z / cap)

    neg = jnp.asarray(NEG_INF, acc_t)
    s = jnp.where(mask[None], z, neg)
    m = jnp.max(s, axis=-1)  # [hq, tqp]; -inf where uncovered
    m_safe = jax.lax.stop_gradient(jnp.where(jnp.isneginf(m), 0.0, m))
    p = jnp.where(mask[None], jnp.exp(s - m_safe[..., None]), 0.0)
    l = p.sum(axis=-1)
    acc = jnp.einsum("hqk,hkd->hqd", p, vf.astype(acc_t))
    return _jnp_epilogue(m, m_safe, l, acc, sink2d, params)


def _jnp_epilogue(m, m_safe, l, acc, sink2d, params):
    """Shared dense/online jnp epilogue: sink fold, uncovered rows
    (out=0 / lse=-inf, lse=sink when has_sink); lse and rowmax [hq, tqp]."""
    acc_t = m.dtype
    neg = jnp.asarray(NEG_INF, acc_t)
    if params.has_sink:
        sinkc = sink2d[:, :1].astype(acc_t)  # [hq, 1]
        m_tot = jnp.maximum(m, sinkc)
        m_tot_safe = jax.lax.stop_gradient(
            jnp.where(jnp.isneginf(m_tot), 0.0, m_tot)
        )
        resc = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m_safe - m_tot_safe))
        l_tot = l * resc + jnp.exp(sinkc - m_tot_safe)
        acc = acc * resc[..., None]
    else:
        m_tot_safe = m_safe
        l_tot = l
    covered = l_tot > 0.0
    inv = jnp.where(covered, 1.0 / jnp.where(covered, l_tot, 1.0), 0.0)
    out = acc * inv[..., None]
    lse = jnp.where(
        covered,
        m_tot_safe + jnp.log(jnp.where(covered, l_tot, 1.0)),
        neg,
    )
    rowmax = jax.lax.stop_gradient(m).astype(jnp.float32)
    return out.astype(params.out_jnp_dtype), lse, rowmax


def _fwd_jnp_online(q, k, v, sink2d, ftab, params: FlexAttnParams):
    """Online-softmax jnp backend (MAGI_ATTENTION_KERNEL_BACKEND=
    jnp_online): block-wise lax.scan over k with running (m, l, acc),
    O(hq * tq * block_k) live scores instead of the dense path's
    O(hq * tq * tk) float score tensor; GQA K/V stay at hk heads.

    Role of reference ``functional/sdpa_online.py`` (1-326): the
    lower-memory any-platform runtime alternative for long-seqlen
    precision debugging — numerically the online recurrence the Pallas
    kernel itself implements, in plain differentiable jnp.

    Memory honesty: the block mask is still materialized densely
    ([tqp, tkp] bool — 64x smaller than the dense backend's fp32 scores
    at hq=8, but O(tq*tk) nonetheless), and reverse-mode through the
    scan saves the (m, l, acc) carry per step; use the Pallas kernel
    (or this backend fwd-only) where those bounds matter."""
    hq, tqp, d = q.shape
    hk, tkp = k.shape[0], k.shape[1]
    group = hq // hk
    bk = params.block_k
    mask = _dense_mask_from_tables(ftab, tqp, tkp, params.block_q, bk)

    acc_t = jnp.promote_types(q.dtype, jnp.float32)
    qf = q.astype(acc_t).reshape(hk, group, tqp, d)
    kf = k.astype(acc_t)
    vf = v.astype(acc_t)
    neg = jnp.asarray(NEG_INF, acc_t)
    scale = jnp.asarray(params.scale, acc_t)

    @jax.checkpoint
    def step(carry, idx):
        m, l, acc = carry
        c0 = idx * bk
        kb = jax.lax.dynamic_slice_in_dim(kf, c0, bk, axis=1)  # [hk, bk, d]
        vb = jax.lax.dynamic_slice_in_dim(vf, c0, bk, axis=1)
        mb = jax.lax.dynamic_slice_in_dim(mask, c0, bk, axis=1)  # [tqp, bk]
        z = (
            jnp.einsum("hgqd,hkd->hgqk", qf, kb) * scale
        ).reshape(hq, tqp, bk)
        if params.softcap > 0.0:
            cap = jnp.asarray(params.softcap, acc_t)
            z = cap * jnp.tanh(z / cap)
        s = jnp.where(mb[None], z, neg)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        m_new_safe = jax.lax.stop_gradient(
            jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        )
        # rescale of the running sums; rows still uncovered contribute 0.
        # CRITICAL: the rescale is built from stop-gradiented maxima only —
        # then the telescoped weight of every score is exactly
        # exp(s - m_final_safe) with s as the sole live input, identical
        # to the dense path's gradient. A live max here would inject a
        # spurious gradient path per step (measured: dq ~(steps+1)x off).
        m_prev_safe = jax.lax.stop_gradient(
            jnp.where(jnp.isneginf(m), 0.0, m)
        )
        resc = jnp.where(
            jnp.isneginf(m), 0.0, jnp.exp(m_prev_safe - m_new_safe)
        ).astype(acc_t)
        p = jnp.where(mb[None], jnp.exp(s - m_new_safe[..., None]), 0.0)
        l_new = l * resc + p.sum(axis=-1)
        pv = jnp.einsum(
            "hgqk,hkd->hgqd", p.reshape(hk, group, tqp, bk), vb
        ).reshape(hq, tqp, vf.shape[2])
        acc_new = acc * resc[..., None] + pv
        return (m_new, l_new, acc_new), None

    init = (
        jnp.full((hq, tqp), neg, acc_t),
        jnp.zeros((hq, tqp), acc_t),
        jnp.zeros((hq, tqp, vf.shape[2]), acc_t),
    )
    (m, l, acc), _ = jax.lax.scan(
        step, init, jnp.arange(tkp // bk, dtype=jnp.int32)
    )
    m_safe = jax.lax.stop_gradient(jnp.where(jnp.isneginf(m), 0.0, m))
    # l/acc left the last step rebased to its m_new_safe, and the last
    # step's m_new IS the global max — so they are already relative to
    # m_safe here, exactly what the epilogue expects
    return _jnp_epilogue(m, m_safe, l, acc, sink2d, params)


def flex_attn_headmajor(
    q: jax.Array,  # [hq, tq_pad, d] (block-multiple padded)
    k: jax.Array,  # [hk, tk_pad, d]
    v: jax.Array,
    ftab,
    btab,
    params: FlexAttnParams,
    sink: jax.Array | None = None,  # [hq]
):
    """Head-major differentiable core for the distributed runtime.

    Returns (out [hq, tqp, d], lse [hq, tqp], rowmax [hq, tqp], the row
    maximum of the masked logits, non-differentiable), from every backend.
    Table arrays may be traced (per-rank, sharded) values.

    ``MAGI_ATTENTION_KERNEL_BACKEND=jnp`` swaps the Pallas kernels for the
    dense jnp reference path (:func:`_fwd_jnp`), ``jnp_online`` for the
    block-wise online-softmax one (:func:`_fwd_jnp_online`) — same
    tables, same semantics, plain-autodiff backward (reference SDPA
    backend switch, functional/dist_attn.py:1215 + sdpa_online.py).
    """
    from .. import env

    hq = q.shape[0]
    with named_scope("magi_layout"):
        if sink is not None:
            sink2d = sink.astype(jnp.float32).reshape(hq, 1)
        else:
            sink2d = jnp.zeros((hq, 1), jnp.float32)
    if env.kernel_backend() == "jnp":
        return _fwd_jnp(q, k, v, sink2d, tuple(ftab), params)
    if env.kernel_backend() == "jnp_online":
        return _fwd_jnp_online(q, k, v, sink2d, tuple(ftab), params)
    _check_smem_budget(ftab, btab, q.shape[1], k.shape[1], params)
    ftab, btab = tuple(ftab), tuple(btab)
    try:
        return _flex_attn_core(q, k, v, sink2d, ftab, btab, params)
    except NotImplementedError:
        # a shard_map evaluated eagerly (no jit round it, nothing to
        # differentiate) runs a custom_vjp's primal and drops its rules,
        # but jax 0.9 refuses one registered with symbolic zeros there
        return _fwd_dispatch(q, k, v, sink2d, ftab, params)


def flex_attn_with_meta(
    q: jax.Array,  # [tq, hq, d]
    k: jax.Array,  # [tk, hk, d]
    v: jax.Array,
    meta: FlexAttnBlockMeta,
    *,
    scale: float | None = None,
    softcap: float = 0.0,
    sink: jax.Array | None = None,
    out_dtype=None,
    head_block: int = 1,
    grid: str = "row_major",
    return_max_logits: bool = False,
    interpret: bool | None = None,
):
    """Flex attention with a prebuilt block plan. Differentiable in q/k/v/sink.

    ``grid`` selects the kernel grid layout (:data:`GRID_KINDS`):
    ``"sparse"`` walks the compact occupied-entry enumeration (no dead
    steps); ``"row_major"`` the static steps grid.

    Returns (out [tq, hq, d], lse [tq, hq]) plus max_logits [hq] when
    ``return_max_logits`` (max_logits is non-differentiable).
    """
    tq, hq, d = q.shape
    tk, hk, _ = k.shape
    assert meta.total_q == tq and meta.total_k == tk, (
        f"meta built for ({meta.total_q},{meta.total_k}), got ({tq},{tk})"
    )
    assert hq % hk == 0
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = _default_interpret()
    out_dtype = jnp.dtype(out_dtype) if out_dtype is not None else q.dtype

    tqp = meta.num_q_blocks * meta.block_q
    tkp = meta.num_k_blocks * meta.block_k
    with named_scope("magi_layout"):
        qh = _pad_tokens(jnp.transpose(q, (1, 0, 2)), tqp, 1)
        kh = _pad_tokens(jnp.transpose(k, (1, 0, 2)), tkp, 1)
        vh = _pad_tokens(jnp.transpose(v, (1, 0, 2)), tkp, 1)

    params = FlexAttnParams(
        block_q=meta.block_q,
        block_k=meta.block_k,
        scale=float(scale),
        softcap=float(softcap),
        has_sink=sink is not None,
        out_dtype=str(out_dtype),
        interpret=bool(interpret),
        head_block=int(head_block),
        fwd_steps=meta.fwd_steps,
        bwd_steps=meta.bwd_steps,
        grid=str(grid),
        mask_step=bounds_mask_step(meta.slice_bounds),
        bwd_unnamed_q=q_visit_counts(meta.bwd_q_block, meta.num_q_blocks)[1],
    )
    out_h, lse_h, rowmax = flex_attn_headmajor(
        qh, kh, vh, fwd_tables(meta), bwd_tables(meta), params, sink=sink
    )
    with named_scope("magi_layout"):
        out = jnp.transpose(out_h, (1, 0, 2))[:tq]
        lse = jnp.transpose(lse_h, (1, 0))[:tq]
        if return_max_logits:
            return out, lse, jnp.max(rowmax, axis=1)
    return out, lse


# Per-kernel SMEM budget for the scalar-prefetch tables. The v5e scalar
# core has ~1 MB of SMEM; past it the compiler refuses the kernel
# ("Ran out of memory in memory space smem", observed at ~33k entries
# x 40 B), so fail loudly host-side first. Sized
# so plans at _MAX_SMEM_ENTRIES (the auto-config escalation bound,
# 24000 x 40 B = 960 KB) stay inside it.
_SMEM_BUDGET_BYTES = 1_048_576


def _check_smem_budget(ftab, btab, tqp: int, tkp: int, params) -> None:
    """Reject plans whose scalar-prefetch tables exceed the chip's SMEM.

    Runs on every compiled launch (table SHAPES are static even when the
    contents are traced per-rank slices, so the distributed path is
    covered too); interpret mode has no SMEM and skips the check.
    """
    if params.interpret:
        return
    per_entry = 4 * (3 + RUN_FIELDS)  # major+minor+sid + run fields, int32
    fixed = int(ftab[4].shape[0]) * 4 + 4 * 2 * (
        tqp // params.block_q + tkp // params.block_k
    )
    worst = max(int(ftab[0].shape[0]), int(btab[0].shape[0]))
    est = worst * per_entry + fixed
    if est > _SMEM_BUDGET_BYTES:
        raise ValueError(
            f"flex-attn plan needs ~{est // 1024} KiB of scalar-prefetch "
            f"SMEM ({worst} entries x {per_entry} B + {fixed} B bounds/row "
            f"tables), past the ~{_SMEM_BUDGET_BYTES // 1024} KiB budget — "
            "the backend compiler crashes opaquely beyond it. Use larger "
            "block_q/block_k (fewer, bigger tiles), a coarser sparse block "
            "granularity, or merge adjacent mask slices."
        )


_AUTO_BLOCK_CONFIGS: tuple[tuple[int, int, int], ...] = (
    # (block_q, block_k, head_block) in preference order, all compiled for a
    # v5e at head_dim 128 under the scoped VMEM the kernels ask for
    # (_VMEM_LIMIT_BYTES, 64 MiB; the compiler's own default of 16 MiB
    # refuses the head-batched rungs). Larger block_k shrinks
    # the entry table (the scalar-prefetch smem arrays are ~40 B/entry
    # against a 1 MB smem budget) and amortizes grid-step overhead.
    # At head_dim 256 and GQA group 1 (latent attention after its
    # up-projection: 20 q = 20 kv heads, head_block snapped to 5, 4, 2) every
    # rung here and in tuning's SPARSE_ONLY_CONFIGS compiles for a v5e on
    # both grids under _VMEM_LIMIT_BYTES (tests/test_aot_compile_tpu.py).
    # Fitting is not fast: at group 1 a step's K and V tiles serve block_q
    # rows of ONE head, so a block_q of 128 reads a byte per 128 FLOPs where
    # the chip's balance is 240, and the kernels run at the HBM's pace
    # (PERF.md section 6, PR 30). The tuner prices those bytes
    # (tuning/cost_model.step_bytes, ISSUE 35) and passes an HBM-bound rung
    # over for one whose block_q clears the balance.
    # What this order decides for the tuner: which of the rungs priced
    # within TIE_TOLERANCE of the cheapest wins, EXCEPT between
    # (128, 512, hb) and (256, 512, hb) at one head_block, which stand in
    # the order of their prices where 256 is the cheaper
    # (tuning/cost_model.PAIR_PRICE_MARGIN, ISSUE 56: a packed mask of a few
    # long documents or a band takes 256, one of many short documents keeps
    # 128), and below the long-sequence lead for a mask at a quarter of its
    # square and more. The static table (MAGI_ATTENTION_AUTOTUNE=off) still
    # reads it top down.
    (128, 512, 8),
    # block_q 256 at eight heads a step, for GQA groups under 8 (at group 8
    # the next rung is this one already: head_block snaps to the group). On
    # the 16k packed mask at group 1 it beat (256, 512, 4) forward + dq + dkv
    # by 7.7% at 16 q = 16 kv x 128 and, snapped to 5 heads, by 1.5% at
    # 20 = 20 x 256; (512, 512, 4) lost to both (PERF.md section 6, PR 35)
    (256, 512, 8),
    (256, 512, 4),
    (256, 1024, 2),
    # square long-seq rung: best measured dense blocking on the row-major
    # grid (round-5 chained sweep: fwd 108.5 / fwd+bwd 106.9 TF/s at 64k
    # causal vs 105.0/106.8 for (512, 2048))
    (1024, 1024, 1),
    # entry-budget escalation: k-wide tiles halve the entry count for
    # 128k+ dense masks while staying within scoped vmem head-per-step
    (512, 2048, 1),
)
_MAX_SMEM_ENTRIES = 24000


def _est_entries(q_ranges, k_ranges, bq: int, bk: int) -> int:
    """Upper bound on kernel entries: per-slice tile-grid coverage."""
    total = 0
    for (q0, q1), (k0, k1) in zip(q_ranges, k_ranges):
        nq = -(-(max(q1 - q0, 0)) // bq) + 1  # +1 for block misalignment
        nk = -(-(max(k1 - k0, 0)) // bk) + 1
        total += nq * nk
    return total


def _auto_head_block(pref: int, hq: int, group: int) -> int:
    """Largest head_block <= pref that divides hq and is a multiple of the
    GQA group (falls back to the group itself). pref=1 is always honored:
    head-per-step is valid for any group and is the vmem floor the large-
    block escalation rung is sized against."""
    if pref <= 1:
        return 1
    best = group if hq % group == 0 else 1
    c = group
    while c <= min(pref, hq):
        if hq % c == 0:
            best = c
        c += group
    return best


_LONG_SEQ_BLOCK_THRESHOLD = 16384
# >= 16k tokens: the big-tile rungs lead the static table — the round-5
# chained sweep measured (1024, 1024) fastest for both fwd (108.5 TF/s)
# and fwd+bwd (106.9) at 64k causal, with (512, 2048) within 2-4% as the
# entry-budget escalation; small rungs are grid-bound at this scale. One
# dense slice at 64 / 8 heads: the tuner's tie order takes the lead only
# for a mask at a quarter of its square and more
# (tuning/cost_model._preference_order, ISSUE 54).
_LONG_SEQ_CONFIGS = tuple(
    c for c in _AUTO_BLOCK_CONFIGS if c[0] * c[1] >= 1024 * 1024
)
# head_block preference keyed by the blocking the kernel will actually
# run (so caller-fixed block sizes get the hb measured for THAT rung).
# For mixed pairs (only one of block_q/block_k fixed by the caller) the
# fallback keys on block_k alone: the K/V double-buffer footprint
# (block_k x head_block x d) is what the measured hb values are sized
# against, so the k-width determines the sound head_block.
_HB_FOR_BLOCKS = {(bq, bk): hb for bq, bk, hb in _AUTO_BLOCK_CONFIGS}
# min() per bk: several rungs share a block_k; an unmeasured mixed pair
# must take the most conservative measured head_block for that k-width
# (vmem-safe regardless of the caller's block_q).
_HB_FOR_BK: dict[int, int] = {}
for _bq, _bk, _hb in _AUTO_BLOCK_CONFIGS:
    _HB_FOR_BK[_bk] = min(_hb, _HB_FOR_BK.get(_bk, _hb))


def _static_block_config(
    q_ranges,
    k_ranges,
    hq: int,
    hk: int,
    *,
    fixed_block_q: int | None = None,
    fixed_block_k: int | None = None,
) -> tuple[int, int, int]:
    """LEGACY seqlen-keyed preference table (MAGI_ATTENTION_AUTOTUNE=off,
    and the fallback for caller-fixed block dims): the fastest measured
    config whose entry-table estimate fits the smem scalar-prefetch budget.

    At >= 16k tokens (queries or keys) the (1024, 1024, 1) rung is
    preferred: the round-5 chained on-chip sweep measured it fastest for
    both fwd and fwd+bwd at 64k causal on the row-major grid, with
    (512, 2048, 1) as the entry-budget escalation within a few percent;
    below 16k the small rungs' lower latency and head batching win.

    Caller-fixed block sizes are honored: the entry estimate and head_block
    choice are computed against the blocking the kernel will actually use.

    Blind by construction to mask sparsity and slice shape — the gap the
    plan-aware cost model (``tuning/``) closes; see
    :func:`auto_block_config`.
    """
    group = max(hq // max(hk, 1), 1)
    extent = max(
        max((int(r[1]) for r in q_ranges), default=0),
        max((int(r[1]) for r in k_ranges), default=0),
    )
    configs = (
        _LONG_SEQ_CONFIGS
        if extent >= _LONG_SEQ_BLOCK_THRESHOLD
        else _AUTO_BLOCK_CONFIGS
    )
    last = None
    for bq, bk, hb in configs:
        bq = fixed_block_q if fixed_block_q is not None else bq
        bk = fixed_block_k if fixed_block_k is not None else bk
        hb = _HB_FOR_BLOCKS.get((bq, bk), _HB_FOR_BK.get(bk, hb))
        last = (bq, bk, _auto_head_block(hb, hq, group))
        if _est_entries(q_ranges, k_ranges, bq, bk) <= _MAX_SMEM_ENTRIES:
            return last
    return last


def auto_kernel_config(
    q_ranges,
    k_ranges,
    hq: int,
    hk: int,
    *,
    fixed_block_q: int | None = None,
    fixed_block_k: int | None = None,
    attn_type_map=None,
    head_dim: int = 128,
    dtype: str = "bfloat16",
    measure_fn=None,
    grid: str | None = None,
) -> tuple[int, int, int, str]:
    """Pick (block_q, block_k, head_block, grid) for a mask.

    Default path: the plan-aware autotuner (``tuning/``) — workload
    fingerprint, analytic cost model pricing tile-occupancy waste /
    grid-step overhead / SMEM pressure across BOTH grid layouts
    (row-major and the compact sparse entry walk), persistent winner
    cache, optional on-device microbenchmark
    (``MAGI_ATTENTION_AUTOTUNE=measure`` with a ``measure_fn``).
    ``MAGI_ATTENTION_AUTOTUNE=off`` or caller-fixed block dims restore
    the legacy seqlen-keyed table (:func:`_static_block_config`) exactly
    (always row-major).

    ``grid`` (caller pin, else ``MAGI_ATTENTION_GRID``) pins the grid
    layout. A ``"row_major"`` pin restricts the RANKING to row-major
    rungs too — a sparse-only small-tile winner launched on the
    static-steps grid would be exactly the grid-step-bound
    configuration the row-major rung table excludes. A ``"sparse"`` pin
    keeps the full ranking's blocking (every row-major rung is also a
    valid sparse blocking — the A/B lever compares grids at one rung).

    ``attn_type_map`` (mask type per slice) sharpens the cost model's
    entry counting; omitted, slices are priced as FULL — uniformly
    conservative across candidates, so the ranking stays sound.
    """
    from .. import env

    grid_pin = grid if grid is not None else env.grid_override()

    def _pin(cfg: tuple[int, int, int], chosen: str):
        return (*cfg, grid_pin if grid_pin is not None else chosen)

    if fixed_block_q is not None or fixed_block_k is not None:
        # explicit user blocking: honored verbatim, measured hb mapping
        return _pin(
            _static_block_config(
                q_ranges,
                k_ranges,
                hq,
                hk,
                fixed_block_q=fixed_block_q,
                fixed_block_k=fixed_block_k,
            ),
            "row_major",
        )
    if env.autotune_mode() == "off":
        return _pin(
            _static_block_config(q_ranges, k_ranges, hq, hk), "row_major"
        )
    if grid_pin == "row_major":
        return (
            *auto_block_config(
                q_ranges,
                k_ranges,
                hq,
                hk,
                attn_type_map=attn_type_map,
                head_dim=head_dim,
                dtype=dtype,
                measure_fn=measure_fn,
            ),
            "row_major",
        )
    from ..tuning import select_block_config

    decision = select_block_config(
        q_ranges,
        k_ranges,
        attn_type_map,
        hq,
        hk,
        head_dim=head_dim,
        dtype=dtype,
        measure_fn=measure_fn,
    )
    if decision is None:  # unconstrained call: cannot happen, but stay safe
        return _pin(
            _static_block_config(q_ranges, k_ranges, hq, hk), "row_major"
        )
    return (
        decision.kernel_config
        if grid_pin is None
        else (*decision.config, grid_pin)
    )


def auto_block_config(
    q_ranges,
    k_ranges,
    hq: int,
    hk: int,
    *,
    fixed_block_q: int | None = None,
    fixed_block_k: int | None = None,
    attn_type_map=None,
    head_dim: int = 128,
    dtype: str = "bfloat16",
    measure_fn=None,
) -> tuple[int, int, int]:
    """Historical (block_q, block_k, head_block) triple for callers that
    run the row-major grid regardless (the distributed plan builder,
    rung benches): the ranking is restricted to row-major rungs
    (``include_sparse=False``), so the returned blocking was priced for
    the grid the caller will actually launch — a sparse-only small-tile
    winner would be exactly the grid-step-bound configuration the
    row-major rung table excludes. Row-major-only decisions live under
    their own fingerprint axis, so they never collide with
    :func:`auto_kernel_config`'s full-ranking cache entries."""
    if fixed_block_q is not None or fixed_block_k is not None:
        return _static_block_config(
            q_ranges,
            k_ranges,
            hq,
            hk,
            fixed_block_q=fixed_block_q,
            fixed_block_k=fixed_block_k,
        )
    from .. import env

    if env.autotune_mode() == "off":
        return _static_block_config(q_ranges, k_ranges, hq, hk)
    from ..tuning import select_block_config

    decision = select_block_config(
        q_ranges,
        k_ranges,
        attn_type_map,
        hq,
        hk,
        head_dim=head_dim,
        dtype=dtype,
        measure_fn=measure_fn,
        include_sparse=False,
    )
    if decision is None:
        return _static_block_config(q_ranges, k_ranges, hq, hk)
    return decision.config


@functools.lru_cache(maxsize=256)
def _cached_meta(
    q_ranges_b: bytes,
    k_ranges_b: bytes,
    types_b: bytes,
    n_slices: int,
    total_q: int,
    total_k: int,
    block_q: int,
    block_k: int,
) -> FlexAttnBlockMeta:
    return build_block_meta(
        np.frombuffer(q_ranges_b, dtype=np.int64).reshape(n_slices, 2),
        np.frombuffer(k_ranges_b, dtype=np.int64).reshape(n_slices, 2),
        np.frombuffer(types_b, dtype=np.int64),
        total_q,
        total_k,
        block_q=block_q,
        block_k=block_k,
    )


def _make_measure_fn(
    q, k, v, q_arr, k_arr, t_arr, *, scale, softcap, sink, out_dtype,
    interpret, warmup: int = 1, reps: int = 3,
):
    """Microbenchmark closure for MAGI_ATTENTION_AUTOTUNE=measure: time
    the forward under one candidate blocking on the caller's actual
    operands (compile excluded via warmup). Plans ride the same
    ``_cached_meta`` LRU as the real call, so the winning candidate's
    plan is already built when the tuned call follows."""
    import time

    def measure(bq: int, bk: int, hb: int, grid: str = "row_major") -> float:
        meta = _cached_meta(
            q_arr.tobytes(),
            k_arr.tobytes(),
            t_arr.tobytes(),
            int(t_arr.shape[0]),
            int(q.shape[0]),
            int(k.shape[0]),
            int(bq),
            int(bk),
        )

        def run():
            return jax.block_until_ready(
                flex_attn_with_meta(
                    q, k, v, meta,
                    scale=scale, softcap=softcap, sink=sink,
                    out_dtype=out_dtype, head_block=hb, grid=grid,
                    interpret=interpret,
                )[0]
            )

        for _ in range(warmup):
            run()
        t0 = time.perf_counter()
        for _ in range(reps):
            run()
        return (time.perf_counter() - t0) / reps

    return measure


def flex_flash_attn_func(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_ranges,  # [S, 2] host values (numpy / lists) — static per mask
    k_ranges,
    attn_type_map,
    *,
    scale: float | None = None,
    softcap: float = 0.0,
    sink: jax.Array | None = None,
    out_dtype=None,
    block_q: int | None = None,
    block_k: int | None = None,
    head_block: int | None = None,
    grid: str | None = None,
    return_max_logits: bool = False,
    interpret: bool | None = None,
):
    """Single-device flex-flash-attention (reference flex_flash_attn.py:1066).

    The ranges are host-side values: the kernel plan is built once per unique
    (mask, shape, blocking) and cached — the TPU-idiomatic replacement for the
    reference's runtime q_ranges device tensors + persistent-kernel scheduler.

    ``block_q``/``block_k``/``head_block``/``grid`` default to an automatic
    choice (:func:`auto_kernel_config`) keyed on the mask and head counts —
    heterogeneous masks resolve to the compact sparse grid, dense ones to
    the measured row-major rungs.
    """
    q_arr = np.ascontiguousarray(np.asarray(q_ranges, dtype=np.int64).reshape(-1, 2))
    k_arr = np.ascontiguousarray(np.asarray(k_ranges, dtype=np.int64).reshape(-1, 2))
    t_arr = np.ascontiguousarray(np.asarray(attn_type_map, dtype=np.int64).reshape(-1))
    from .. import env as _env

    if _env.is_auto_range_merge_enable():
        from .range_merge import merge_ranges

        q_arr, k_arr, t_arr = (
            np.ascontiguousarray(a)
            for a in merge_ranges(q_arr, k_arr, t_arr)
        )
    if block_q is None or block_k is None or head_block is None:
        measure_fn = None
        if (
            head_block is None
            and interpret is not True
            and _env.autotune_mode() == "measure"
            and not isinstance(q, jax.core.Tracer)
        ):
            # on-device microbenchmark of one candidate on the REAL
            # operands (concrete values only — under jit tracing the
            # tuner degrades to the cost model and records why). A
            # caller-pinned head_block also degrades to the model:
            # candidates would otherwise be timed at THEIR head_block
            # while the real call runs the pinned one, and the persisted
            # winner would describe a configuration that never executes
            measure_fn = _make_measure_fn(
                q, k, v, q_arr, k_arr, t_arr,
                scale=scale, softcap=softcap, sink=sink,
                out_dtype=out_dtype, interpret=interpret,
            )
        abq, abk, ahb, agrid = auto_kernel_config(
            q_arr.tolist(),
            k_arr.tolist(),
            int(q.shape[1]),
            int(k.shape[1]),
            fixed_block_q=block_q,
            fixed_block_k=block_k,
            attn_type_map=t_arr.tolist(),
            head_dim=int(q.shape[2]),
            dtype=str(q.dtype),
            measure_fn=measure_fn,
            grid=grid,  # a caller pin also restricts the ranking
        )
        block_q, block_k = abq, abk
        head_block = ahb if head_block is None else head_block
        grid = agrid
    if grid is None:
        override = _env.grid_override()
        grid = override if override is not None else "row_major"
    meta = _cached_meta(
        q_arr.tobytes(),
        k_arr.tobytes(),
        t_arr.tobytes(),
        int(t_arr.shape[0]),
        int(q.shape[0]),
        int(k.shape[0]),
        int(block_q),
        int(block_k),
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
        grid=grid,
        return_max_logits=return_max_logits,
        interpret=interpret,
    )
