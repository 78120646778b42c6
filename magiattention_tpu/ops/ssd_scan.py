"""State-space-dual scan (Mamba-2, arXiv:2405.21060) along a packed
sequence, the state zeroed at every document's first token.

``ssd_scan(x, delta, a, b, c, d, start)`` on one rank's rows in sequence
order, a head ``h`` of ``P`` channels and ``N`` states a channel, one
group (every head reads the same ``b`` and ``c``)::

    S_t[h] = exp(delta_t[h] a[h]) S_{t-1}[h] + delta_t[h] x_t[h] (x) b_t
                                              # S = 0 before a start row
    y_t[h] = S_t[h] c_t + d[h] x_t[h]

``x`` [T, H, P], ``delta`` [T, H] (after the softplus), ``a`` [H]
(negative: the decay is a scalar a head, which is what lets a chunk be
matmuls), ``b`` and ``c`` [T, N], ``d`` [H], ``start`` [T] bool.

The chunked form (``chunk`` rows, the published ``mamba_chunk_size``).
With ``l_t = delta_t a`` and ``cum`` its running sum inside a chunk,
float32, and ``doc`` a row's document::

    G        = C B^T, zero where j > i or doc_j != doc_i   # one a chunk
    y_i     += sum_j G_ij exp(cum_i - cum_j) delta_j x_j   # inside
    y_i     += [no start in the chunk up to i] exp(cum_i) R c_i
    R       <- [no start in the chunk] exp(cum_end) R
               + sum_j [no start after j] exp(cum_end - cum_j)
                 delta_j x_j (x) b_j

``R`` [H, P, N] float32 is carried from chunk to chunk in VMEM and
reaches HBM at chunk boundaries only (what the backward recomputes a
chunk from, as ``selective_scan``'s does). A reset is a mask on the
tile ``G`` and on two row vectors: a document may start anywhere in a
chunk. Every exponent is a difference of two ``cum`` (never a ratio of
exponentials) and at most 0.

Two backends, chosen as ``selective_scan`` chooses
(``MAGI_ATTENTION_KERNEL_BACKEND``): ``pallas``, in interpret mode off
the TPU, with a ``custom_vjp`` backward kernel, or ``jnp`` /
``jnp_online``: the same chunked form in ``jax.numpy``, a chunk a
``checkpoint``, differentiated by ``jax``.

The kernels (docs/selective_scan.md): grid (chunks, head blocks), both
``arbitrary``; ``x`` and ``y`` are ``[T, H P]``, a block's heads side by
side on the lanes, and heads that share one 128-lane tile (two at P =
64) go through the MXU as two products with the other head's lanes
zeroed. The state of all head blocks ``[blocks, N, block P]`` float32
(2 MB at 64 x 64 x 128) and the masked ``G`` stay in VMEM scratch. The
backward walks the chunks from the last to the first with the cotangent
of the carried state in scratch; ``cum``'s cotangent a row is ``<dy_i,
y_i> - <delta_i x_i, d(delta x)_i>`` and, at a chunk's last row, ``<dR,
R>``, so no ``[chunk, chunk]`` matrix a head reaches HBM in either pass.
The operands' dtype is the MXU's (bfloat16 in the model; float32
operands run at the highest precision); accumulators, ``cum`` and the
state are float32 whatever the operands are.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.compat import tpu_compiler_params
from .selective_scan import (
    F32, LANES, _VMEM_LIMIT_BYTES, _default_interpret, _rounded, on_jnp_backend,
)

CHUNK, HEAD_BLOCK = 256, 8  # Mamba-2's chunk; heads of one grid step
_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_TN = (((0,), (0,)), ((), ()))  # a^T @ b


@dataclasses.dataclass(frozen=True)
class SsdParams:
    chunk: int
    head_block: int  # heads of one grid step
    tile_heads: int  # heads that share one tile of lanes
    interpret: bool
    state_dtype: str  # float32; bfloat16 is the benchmark's control


def make_ssd_params(
    rows: int, heads: int, head_dim: int, *, chunk: int | None = None,
    head_block: int | None = None, interpret: bool | None = None,
    state_dtype="float32",
) -> SsdParams:
    """The kernels' blocking for ``rows`` x ``heads`` x ``head_dim``: a
    chunk divides the rows (the caller pads), a head block the heads, and
    a block is whole tiles of at most 128 lanes."""
    if head_block is None:
        head_block = min(HEAD_BLOCK, heads)
        while heads % head_block:
            head_block -= 1
    if heads % head_block:
        raise ValueError(
            f"a head block of {head_block} does not cut {heads} heads into "
            "whole blocks"
        )
    # the heads of a block that share one tile of lanes: as many as fit
    tile_heads = max(1, min(head_block, LANES // head_dim))
    while head_block % tile_heads:
        tile_heads -= 1
    chunk = int(chunk or min(CHUNK, rows + -rows % 8))
    if chunk % 8:
        raise ValueError(f"a chunk of {chunk} rows is no multiple of 8")
    interpret = _default_interpret() if interpret is None else interpret
    if not interpret and chunk % LANES:
        raise ValueError(
            f"a chunk of {chunk} rows: on the chip a chunk's rows lie along "
            f"the lanes of cum's transpose, whole tiles of {LANES}"
        )
    return SsdParams(
        chunk=chunk, head_block=int(head_block), tile_heads=tile_heads,
        interpret=interpret,
        state_dtype=str(jnp.dtype(state_dtype)),
    )


def _precision(dtype):
    """float32 operands are multiplied as float32 (the check's scan
    alone); the model's bfloat16 in one MXU pass."""
    return jax.lax.Precision.HIGHEST if dtype == F32 else None


def _dot(a, b, dims=None):
    if dims is None:
        dims = (((a.ndim - 1,), (0,)), ((), ()))
    return jax.lax.dot_general(
        a, b, dims, precision=_precision(a.dtype), preferred_element_type=F32
    )


# ---------------------------------------------------------------------------
# the jax.numpy backend
# ---------------------------------------------------------------------------


def _ssd_jnp(x2, delta, cum, b, c, d, doc, p: SsdParams):
    """The chunked form in ``jax.numpy``; a chunk is one ``checkpoint``."""
    t, h = delta.shape
    q, pd, n = p.chunk, x2.shape[1] // h, b.shape[1]
    dt = x2.dtype
    prec = _precision(dt)
    ein = functools.partial(
        jnp.einsum, precision=prec, preferred_element_type=F32
    )
    causal = jnp.tril(jnp.ones((q, q), bool))

    @jax.checkpoint
    def one_chunk(r, xs):
        xc, dl, cm, bc, cc, dc, prev = xs
        xd = (dl[:, :, None] * xc.astype(F32)).astype(dt)  # [q, h, pd]
        same = causal & (dc[:, None] == dc[None, :])
        g = jnp.where(same, ein("in,jn->ij", cc, bc), 0.0)
        decay = jnp.exp(jnp.minimum(cm[:, None, :] - cm[None, :, :], 0.0))
        y = ein("ijh,jhp->ihp", (g[:, :, None] * decay).astype(dt), xd)
        from_before = jnp.where(dc == prev, 1.0, 0.0)[:, None] * jnp.exp(cm)
        y = y + from_before[:, :, None] * ein("in,hpn->ihp", cc, r.astype(dt))
        tail = jnp.where(dc == dc[-1], 1.0, 0.0)[:, None] * jnp.exp(
            cm[-1][None, :] - cm
        )
        s = ein("jn,jhp->hpn", bc, (xd.astype(F32) * tail[:, :, None]).astype(dt))
        keep = jnp.where(dc[-1] == prev, 1.0, 0.0) * jnp.exp(cm[-1])
        return _rounded(keep[:, None, None] * r + s, p), y

    chunks = lambda v: v.reshape(t // q, q, *v.shape[1:])  # noqa: E731
    prev, _last = _chunk_edges(doc, q)
    _, y = jax.lax.scan(
        one_chunk, jnp.zeros((h, pd, n), F32),
        (chunks(x2).reshape(t // q, q, h, pd), chunks(delta), chunks(cum),
         chunks(b), chunks(c), chunks(doc), prev),
    )
    y = y.reshape(t, h, pd) + d[None, :, None] * x2.reshape(t, h, pd).astype(F32)
    return y.reshape(t, h * pd).astype(dt)


def _chunk_edges(doc, q: int):
    """(the document of the row before a chunk, -1 before the first; the
    document of a chunk's last row), [chunks] int32 each."""
    last = doc.reshape(-1, q)[:, -1]
    prev = jnp.concatenate([jnp.full((1,), -1, doc.dtype), last[:-1]])
    return prev, last


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------


def _compiler_params():
    return tpu_compiler_params(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES,
    )


def _column(v, head):
    """Column ``head`` (a traced index) of a [chunk, H] block, [chunk, 1]:
    a masked sum along the lanes, since a lane cannot be indexed by a
    traced number."""
    at = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    return jnp.sum(jnp.where(at == head, v, 0.0), axis=1, keepdims=True)


def _masked(g, docc_ref, docr_ref):
    """``g`` [chunk, chunk], zero above the diagonal and across a
    document boundary: the reset, as a mask on the tile."""
    rows = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)
    same = (docc_ref[...] == docr_ref[...]) & (cols <= rows)
    return jnp.where(same, g, 0.0)


def _masked_g(c_ref, bt_ref, docc_ref, docr_ref):
    """``C B^T`` of a chunk under the mask: the one [chunk, chunk]
    product all heads share."""
    return _masked(_dot(c_ref[...], bt_ref[...]), docc_ref, docr_ref)


class _Tile:
    """What one tile of lanes (``tile_heads`` heads side by side) reads of
    the per-head row vectors: the step, ``cum`` and the chunk's last
    ``cum``, each spread over its head's lanes."""

    def __init__(self, delta, cum, cumr_ref, first_head, p: SsdParams,
                 head_dim: int):
        """``delta``, ``cum`` [chunk, H]: the chunk's blocks, loaded once
        a grid step; ``cumr_ref`` [H, chunk]: ``cum``'s transpose."""
        q = cum.shape[0]
        width = p.tile_heads * head_dim
        self.of = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) // head_dim
        self.heads = [first_head + k for k in range(p.tile_heads)]
        self.cum_cols = [_column(cum, hd) for hd in self.heads]
        self.cum_rows = [cumr_ref[pl.ds(hd, 1), :] for hd in self.heads]
        zero = jnp.zeros((q, width), F32)
        self.delta = self.spread([_column(delta, hd) for hd in self.heads], zero)
        self.cum = self.spread(self.cum_cols, zero)
        self.cum_end = self.spread(
            [row[:, q - 1 :] for row in self.cum_rows], zero[:1]
        )

    def spread(self, per_head, zero):
        out = zero
        for k, v in enumerate(per_head):
            out = jnp.where(self.of == k, v, out)
        return out

    def decay(self, k: int):
        """``exp(cum_i - cum_j)`` of head ``k``, [chunk, chunk] float32;
        1 where j > i (``G`` is zero there)."""
        return jnp.exp(jnp.minimum(self.cum_cols[k] - self.cum_rows[k], 0.0))

    def only(self, k: int, v):
        return jnp.where(self.of == k, v, jnp.zeros_like(v))

    def head_sums(self, v, into):
        """``into`` [chunk, H] + every head's sum of ``v`` [chunk, tile]
        over its own lanes, at the head's column."""
        at = jax.lax.broadcasted_iota(jnp.int32, (1, into.shape[1]), 1)
        for k, hd in enumerate(self.heads):
            s = jnp.sum(self.only(k, v), axis=1, keepdims=True)
            into = into + jnp.where(at == hd, s, 0.0)
        return into


def _fwd_kernel(prev_ref, last_ref, x_ref, dlc_ref, cumc_ref, cumr_ref,
                bt_ref, c_ref, docc_ref, docr_ref, d_ref,
                y_ref, bound_ref, s_ref, g_ref, *, p: SsdParams, head_dim):
    ci, blk = pl.program_id(0), pl.program_id(1)

    @pl.when(ci == 0)
    def _():
        s_ref[blk] = jnp.zeros(s_ref.shape[1:], F32)

    @pl.when(blk == 0)
    def _():
        g_ref[...] = _masked_g(c_ref, bt_ref, docc_ref, docr_ref)

    dt = x_ref.dtype
    g, state = g_ref[...], s_ref[blk]
    bound_ref[0, 0] = state  # the state this chunk starts from
    prev, last = prev_ref[ci], last_ref[ci]
    docc = docc_ref[...]
    from_before = jnp.where(docc == prev, 1.0, 0.0)  # no start up to the row
    to_end = jnp.where(docc == last, 1.0, 0.0)  # no start after the row
    keep = jnp.where(last == prev, 1.0, 0.0)
    width = p.tile_heads * head_dim
    delta, cum = dlc_ref[...], cumc_ref[...]
    for j in range(p.head_block // p.tile_heads):
        sl = slice(j * width, (j + 1) * width)
        tile = _Tile(
            delta, cum, cumr_ref,
            blk * p.head_block + j * p.tile_heads, p, head_dim,
        )
        xt = x_ref[:, sl].astype(F32)
        xd = tile.delta * xt
        xdc = xd.astype(dt)
        acc = _dot(c_ref[...], state[:, sl].astype(dt)) * (
            jnp.exp(tile.cum) * from_before
        )
        for k in range(p.tile_heads):
            m = (g * tile.decay(k)).astype(dt)
            acc = acc + _dot(m, tile.only(k, xdc))
        y_ref[:, sl] = (acc + d_ref[:, sl] * xt).astype(y_ref.dtype)
        w = jnp.exp(tile.cum_end - tile.cum) * to_end
        s_new = _dot(bt_ref[...], (xd * w).astype(dt)) + (
            keep * jnp.exp(tile.cum_end)
        ) * state[:, sl]
        s_ref[blk, :, sl] = _rounded(s_new, p)


def _bwd_kernel(prev_ref, last_ref, x_ref, dlc_ref, cumc_ref, cumr_ref,
                b_ref, bt_ref, c_ref, ct_ref, docc_ref, docr_ref, d_ref,
                dy_ref, bound_ref, after_ref,
                dx_ref, ddl_ref, dcum_ref, db_ref, dc_ref, dend_ref, dd_ref,
                dr_ref, g_ref, dg_ref, *, p: SsdParams, head_dim):
    i, blk = pl.program_id(0), pl.program_id(1)  # chunks, last to first
    ci = pl.num_programs(0) - 1 - i

    @pl.when(i == 0)
    def _():
        dr_ref[blk] = jnp.zeros(dr_ref.shape[1:], F32)

    @pl.when(blk == 0)
    def _():
        g_ref[...] = _masked_g(c_ref, bt_ref, docc_ref, docr_ref)
        dg_ref[...] = jnp.zeros_like(dg_ref)
        for ref in (ddl_ref, dcum_ref, db_ref, dc_ref):
            ref[...] = jnp.zeros_like(ref)

    dt = x_ref.dtype
    g = g_ref[...]
    state, d_state = bound_ref[0, 0], dr_ref[blk]  # R before, dR after
    prev, last = prev_ref[ci], last_ref[ci]
    docc = docc_ref[...]
    from_before = jnp.where(docc == prev, 1.0, 0.0)
    to_end = jnp.where(docc == last, 1.0, 0.0)
    keep = jnp.where(last == prev, 1.0, 0.0)
    width = p.tile_heads * head_dim
    delta, cum = dlc_ref[...], cumc_ref[...]
    dg = dg_ref[...]
    ddl, dcum = ddl_ref[...], dcum_ref[...]
    db, dc = db_ref[...], dc_ref[...]
    for j in range(p.head_block // p.tile_heads):
        sl = slice(j * width, (j + 1) * width)
        tile = _Tile(
            delta, cum, cumr_ref,
            blk * p.head_block + j * p.tile_heads, p, head_dim,
        )
        xt = x_ref[:, sl].astype(F32)
        dyc = dy_ref[:, sl]
        dyf = dyc.astype(F32)
        xdc = (tile.delta * xt).astype(dt)
        xd = xdc.astype(F32)  # as the products read it
        r, dr = state[:, sl], d_state[:, sl]
        into = jnp.exp(tile.cum) * from_before
        y = _dot(c_ref[...], r.astype(dt)) * into
        w = jnp.exp(tile.cum_end - tile.cum) * to_end
        xw = (xd * w).astype(dt)
        dxd = w * _dot(b_ref[...], dr.astype(dt))
        for k in range(p.tile_heads):
            decay = tile.decay(k)
            m = (g * decay).astype(dt)
            xk, dyk = tile.only(k, xdc), tile.only(k, dyc)
            y = y + _dot(m, xk)
            dxd = dxd + _dot(m, dyk, _TN)
            dg = dg + _dot(dyk, xk, _NT) * decay
        # cum's cotangent a row: what the row reads less what reads the row
        dcum = tile.head_sums(dyf * y - xd * dxd, dcum)
        ddl = tile.head_sums(dxd * xt, ddl)
        dx_ref[:, sl] = (
            dxd * tile.delta + dyf * d_ref[:, sl]
        ).astype(dx_ref.dtype)
        dd_ref[0, :, sl] = jnp.sum(dyf * xt, axis=0, keepdims=True)
        du = (into * dyf).astype(dt)
        dc = dc + _dot(du, r.astype(dt), _NT)
        db = db + _dot(xw, dr.astype(dt), _NT)
        # at the chunk's last row cum_end's cotangent: <dR, R> after it
        dend_ref[0, :, sl] = jnp.sum(
            dr * after_ref[0, 0][:, sl], axis=0, keepdims=True
        )
        dr_ref[blk, :, sl] = _dot(ct_ref[...], du) + (
            keep * jnp.exp(tile.cum_end)
        ) * dr
    dg_ref[...] = dg
    ddl_ref[...], dcum_ref[...] = ddl, dcum
    dc_ref[...], db_ref[...] = dc, db

    @pl.when(blk == pl.num_programs(1) - 1)
    def _():  # every head's dG is in: what G hands to C and to B
        dgc = _masked(dg, docc_ref, docr_ref).astype(dt)
        dc_ref[...] += _dot(dgc, b_ref[...])
        db_ref[...] += _dot(dgc, c_ref[...], _TN)


def _specs(p: SsdParams, h: int, pd: int, n: int, chunk_of):
    """Block specs by what they hold, grid step ``(i, blk)`` walking chunk
    ``chunk_of(i)``."""
    q, wide = p.chunk, p.head_block * pd
    at = lambda f: (lambda i, blk, *_: f(chunk_of(i), blk))  # noqa: E731
    return {
        "wide": pl.BlockSpec((q, wide), at(lambda c, blk: (c, blk))),
        "cols": pl.BlockSpec((q, h), at(lambda c, blk: (c, 0))),
        "rows": pl.BlockSpec((h, q), at(lambda c, blk: (0, c))),
        "bc": pl.BlockSpec((q, n), at(lambda c, blk: (c, 0))),
        "bc_t": pl.BlockSpec((n, q), at(lambda c, blk: (0, c))),
        "doc_c": pl.BlockSpec((q, 1), at(lambda c, blk: (c, 0))),
        "doc_r": pl.BlockSpec((1, q), at(lambda c, blk: (0, c))),
        "d": pl.BlockSpec((1, wide), at(lambda c, blk: (0, blk))),
        "bound": pl.BlockSpec(
            (1, 1, n, wide), at(lambda c, blk: (c, blk, 0, 0))
        ),
        "end": pl.BlockSpec((1, 1, wide), at(lambda c, blk: (c, 0, blk))),
    }


def _laid_out(delta, cum, b, c, d, doc, p: SsdParams, pd: int):
    """The small operands as the kernels read them: per-head vectors a
    row on the sublanes and on the lanes, ``b`` and ``c`` with their
    transposes, the documents likewise, ``d`` spread over a head's lanes,
    the chunks' edge documents (scalar prefetch)."""
    prev, last = _chunk_edges(doc, p.chunk)
    return dict(
        edges=(prev, last), dlc=delta, cumc=cum, cumr=cum.T, b=b, bt=b.T,
        c=c, ct=c.T, docc=doc[:, None], docr=doc[None, :],
        d=jnp.broadcast_to(
            d.astype(F32)[:, None], (d.shape[0], pd)
        ).reshape(1, -1),
    )


def _fwd_pallas(x2, delta, cum, b, c, d, doc, p: SsdParams):
    """(y [T, H P] in ``x2``'s dtype, the state at every chunk's start
    [T / chunk, blocks, N, block P] float32)."""
    t, h = delta.shape
    pd, n = x2.shape[1] // h, b.shape[1]
    grid = (t // p.chunk, h // p.head_block)
    s = _specs(p, h, pd, n, lambda i: i)
    o = _laid_out(delta, cum, b, c, d, doc, p, pd)
    wide = p.head_block * pd
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            s["wide"], s["cols"], s["cols"], s["rows"], s["bc_t"], s["bc"],
            s["doc_c"], s["doc_r"], s["d"],
        ],
        out_specs=[s["wide"], s["bound"]],
        scratch_shapes=[
            pltpu.VMEM((grid[1], n, wide), F32),
            pltpu.VMEM((p.chunk, p.chunk), F32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_fwd_kernel, p=p, head_dim=pd),
        name="magi_ssd_scan_fwd_kernel",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(x2.shape, x2.dtype),
            jax.ShapeDtypeStruct((*grid, n, wide), F32),
        ],
        interpret=p.interpret,
        compiler_params=_compiler_params(),
    )(*o["edges"], x2, o["dlc"], o["cumc"], o["cumr"], o["bt"], o["c"],
      o["docc"], o["docr"], o["d"])


def _bwd_pallas(x2, delta, cum, b, c, d, doc, bound, dy, p: SsdParams):
    """The cotangents of (x2, delta, cum, b, c, d): delta's through
    ``delta x`` alone (``cum`` is the caller's function of it), b's and
    c's in float32."""
    t, h = delta.shape
    pd, n = x2.shape[1] // h, b.shape[1]
    chunks, blocks = t // p.chunk, h // p.head_block
    back = lambda i: chunks - 1 - i  # noqa: E731
    s = _specs(p, h, pd, n, back)
    # the state after a chunk is the next chunk's first (after the last
    # chunk nothing reads it: its cotangent is zero)
    after = _specs(p, h, pd, n, lambda i: jnp.minimum(back(i) + 1, chunks - 1))
    o = _laid_out(delta, cum, b, c, d, doc, p, pd)
    wide = p.head_block * pd
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(chunks, blocks),
        in_specs=[
            s["wide"], s["cols"], s["cols"], s["rows"], s["bc"], s["bc_t"],
            s["bc"], s["bc_t"], s["doc_c"], s["doc_r"], s["d"],
            s["wide"], s["bound"], after["bound"],
        ],
        out_specs=[
            s["wide"], s["cols"], s["cols"], s["bc"], s["bc"], s["end"],
            s["end"],
        ],
        scratch_shapes=[
            pltpu.VMEM((blocks, n, wide), F32),
            pltpu.VMEM((p.chunk, p.chunk), F32),
            pltpu.VMEM((p.chunk, p.chunk), F32),
        ],
    )
    dx, ddl, dcum, db, dc, dend, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, p=p, head_dim=pd),
        name="magi_ssd_scan_bwd_kernel",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(x2.shape, x2.dtype),
            jax.ShapeDtypeStruct((t, h), F32),
            jax.ShapeDtypeStruct((t, h), F32),
            jax.ShapeDtypeStruct((t, n), F32),
            jax.ShapeDtypeStruct((t, n), F32),
            jax.ShapeDtypeStruct((chunks, 1, h * pd), F32),
            jax.ShapeDtypeStruct((chunks, 1, h * pd), F32),
        ],
        interpret=p.interpret,
        compiler_params=_compiler_params(),
    )(*o["edges"], x2, o["dlc"], o["cumc"], o["cumr"], o["b"], o["bt"],
      o["c"], o["ct"], o["docc"], o["docr"], o["d"], dy, bound, bound)
    # a head's sums over its lanes; cum_end's cotangent at the last row
    at_end = jnp.pad(
        dend.reshape(chunks, 1, h, pd).sum(axis=-1),
        ((0, 0), (p.chunk - 1, 0), (0, 0)),
    )
    dcum = (dcum.reshape(chunks, p.chunk, h) + at_end).reshape(t, h)
    return dx, ddl, dcum, db, dc, dd.reshape(chunks, h, pd).sum(axis=(0, 2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _ssd_pallas(x2, delta, cum, b, c, d, doc, p: SsdParams):
    return _fwd_pallas(x2, delta, cum, b, c, d, doc, p)[0]


def _ssd_pallas_fwd(x2, delta, cum, b, c, d, doc, p):
    _record("fwd", delta.shape, x2.shape[1], b.shape[1], p)
    y, bound = _fwd_pallas(x2, delta, cum, b, c, d, doc, p)
    return y, (x2, delta, cum, b, c, d, doc, bound)


def _ssd_pallas_bwd(p, res, dy):
    x2, delta, cum, b, c, d, doc, bound = res
    _record("bwd", delta.shape, x2.shape[1], b.shape[1], p)
    dx, ddl, dcum, db, dc, dd = _bwd_pallas(
        x2, delta, cum, b, c, d, doc, bound, dy, p
    )
    return (
        dx, ddl, dcum, db.astype(b.dtype), dc.astype(c.dtype),
        dd.astype(d.dtype), None,
    )


_ssd_pallas.defvjp(_ssd_pallas_fwd, _ssd_pallas_bwd)


def _record(phase: str, shape, channels: int, n: int, p: SsdParams) -> None:
    from .. import telemetry

    t, h = shape
    telemetry.record_ssd_scan(
        phase, heads=h, chunks=t // p.chunk,
        state_bytes=t // p.chunk * n * channels * 4,
    )


def ssd_scan(
    x, delta, a, b, c, d, start, *, chunk: int | None = None,
    head_block: int | None = None, interpret: bool | None = None,
    state_dtype="float32",
):
    """``y`` [T, H, P] in ``x``'s dtype (module docstring). ``start``
    marks the rows at which a document starts."""
    t, h, pd = x.shape
    p = make_ssd_params(
        t, h, pd, chunk=chunk, head_block=head_block, interpret=interpret,
        state_dtype=state_dtype,
    )
    pad = -t % p.chunk  # rows past the end: no step, no input
    rows = lambda v: jnp.pad(v, ((0, pad), (0, 0)))  # noqa: E731
    delta = rows(delta.astype(F32))
    # a document's number a row; the rows past the end are each their own
    doc = jnp.cumsum(
        jnp.pad(start.astype(jnp.int32), (0, pad), constant_values=1)
    )
    step = delta * a.astype(F32)[None, :]
    cum = jnp.cumsum(
        step.reshape(-1, p.chunk, h), axis=1, dtype=F32
    ).reshape(-1, h)
    scan = _ssd_jnp if on_jnp_backend() else _ssd_pallas
    y = scan(
        rows(x.reshape(t, h * pd)), delta, cum, rows(b), rows(c),
        d.astype(F32), doc, p,
    )
    return y[:t].reshape(t, h, pd)
