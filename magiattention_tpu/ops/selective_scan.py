"""Selective scan (Mamba-1, arXiv:2312.00752) along a packed sequence,
the state zeroed at every document's first token.

``selective_scan(u, delta, a, b, c, d, start)`` on one rank's rows in
sequence order::

    s_t[ch, n] = exp(delta_t[ch] a[ch, n]) s_{t-1}[ch, n]
                 + delta_t[ch] b_t[n] u_t[ch]        # s = 0 before a start row
    y_t[ch]    = sum_n c_t[n] s_t[ch, n] + d[ch] u_t[ch]

``u`` [T, C], ``delta`` [T, C] (after the softplus), ``a`` [C, N]
(negative), ``b`` and ``c`` [T, N], ``d`` [C], ``start`` [T] bool (a
document's first row). The state is float32 whatever the operands are
and never reaches HBM: ``T x C x N`` float32 is 5.4 GB at 16,384 rows of
5,120 channels. The gate ``silu(z)`` stays outside, so a caller may hand
``y`` on before it.

Two backends, chosen as the flex kernels choose theirs
(``MAGI_ATTENTION_KERNEL_BACKEND``: ``pallas``, in interpret mode off
the TPU, or ``jnp`` / ``jnp_online``, a ``lax.scan`` a token). Both are
differentiated chunk by chunk: the forward keeps the state at every
chunk's start (``T / chunk x N x C`` float32, 42 MB at a chunk of 128),
and the backward recomputes a chunk's states from it.

The kernels (docs/selective_scan.md): grid (channel blocks, chunks), the
chunk axis ``arbitrary``; a block's state ``(N, channels)`` float32, the
state index on sublanes and the channels on lanes, goes from chunk to
chunk in VMEM scratch and is multiplied by 0 at a start row. A token's
``b`` and ``c`` reach the kernel already spread along the 128 lanes
(``[T, N, 128]``, made by XLA in the operands' dtype), so every
operation of the token loop is a whole-register one. The backward walks
the chunks from the last to the first: it recomputes a chunk's states
into VMEM, then runs the adjoint recurrence down the chunk; ``b``'s and
``c``'s gradients, sums over every channel, leave a channel block as
lane sums made on the MXU, and XLA adds the blocks. ``d u`` and its
gradients are XLA's.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.compat import tpu_compiler_params

F32 = jnp.float32
LANES = 128
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# rows of one chunk, channels of one block: the backward holds a chunk's
# states, chunk x N x channels float32 = 8 MB at (128, 16, 1024)
CHUNK, CHANNEL_BLOCK = 128, 1024


@dataclasses.dataclass(frozen=True)
class ScanParams:
    chunk: int
    channel_block: int
    lane: int  # the lanes b and c are spread along
    interpret: bool
    state_dtype: str  # float32; bfloat16 is the benchmark's control

    @property
    def tiles(self) -> int:
        return self.channel_block // self.lane


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def on_jnp_backend() -> bool:
    """Whether ``MAGI_ATTENTION_KERNEL_BACKEND`` asks for the
    ``jax.numpy`` form of a scan (``ops/ssd_scan.py`` asks here too)."""
    from .. import env

    return env.kernel_backend() in ("jnp", "jnp_online")


def make_scan_params(
    rows: int, channels: int, *, chunk: int | None = None,
    channel_block: int | None = None, interpret: bool | None = None,
    state_dtype="float32",
) -> ScanParams:
    """The kernels' blocking for ``rows`` x ``channels``: a chunk divides
    the rows (the caller pads), a channel block the channels."""
    lane = LANES if channels % LANES == 0 else channels
    if channel_block is None:
        channel_block = min(CHANNEL_BLOCK, channels)
        while channels % channel_block:
            channel_block -= lane
    if channels % channel_block or channel_block % lane:
        raise ValueError(
            f"channel block {channel_block} does not cut {channels} "
            f"channels into whole blocks of {lane}-lane tiles"
        )
    chunk = int(chunk or min(CHUNK, rows + -rows % ROWS))
    if chunk % ROWS:
        raise ValueError(f"a chunk of {chunk} rows is no multiple of {ROWS}")
    return ScanParams(
        chunk=chunk,
        channel_block=int(channel_block),
        lane=lane,
        interpret=_default_interpret() if interpret is None else interpret,
        state_dtype=str(jnp.dtype(state_dtype)),
    )


def _rounded(s, p: ScanParams):
    """The state as it is kept: float32, or rounded where a control asks."""
    if p.state_dtype == "float32":
        return s
    return s.astype(p.state_dtype).astype(F32)


# ---------------------------------------------------------------------------
# the jax.numpy backend
# ---------------------------------------------------------------------------


def _scan_jnp(u, delta, a_t, b, c, keep, p: ScanParams):
    """The recurrence a token at a time; a chunk is one ``checkpoint``,
    so autodiff keeps the chunks' first states and recomputes the rest."""
    t, ch = u.shape
    n = a_t.shape[0]

    def token(s, x):
        ut, dt, bt, ct, kt = x
        s = jnp.exp(dt[None, :] * a_t) * kt * s + bt[:, None] * (dt * ut)[None, :]
        s = _rounded(s, p)
        return s, (ct[:, None] * s).sum(axis=0)

    @jax.checkpoint
    def one_chunk(s, xs):
        return jax.lax.scan(token, s, xs)

    xs = jax.tree.map(
        lambda x: x.reshape(t // p.chunk, p.chunk, *x.shape[1:]),
        (u, delta, b.astype(F32), c.astype(F32), keep.astype(F32)),
    )
    _, y = jax.lax.scan(one_chunk, jnp.zeros((n, ch), F32), xs)
    return y.reshape(t, ch)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------


def _compiler_params():
    return tpu_compiler_params(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES,
    )


def _spread(x, lane: int):
    """[T, N] -> [T, N, lane]: a token's N values, each along the lanes."""
    return jnp.broadcast_to(x[:, :, None], (*x.shape, lane))


def _tile_slices(p: ScanParams):
    return [slice(j * p.lane, (j + 1) * p.lane) for j in range(p.tiles)]


ROWS = 8  # a register's sublanes: the token loop reads and writes 8 rows


def _stack_rows(rows):
    """Eight [1, lane] rows -> [8, lane] (a row a sublane)."""
    at = jax.lax.broadcasted_iota(jnp.int32, (ROWS, rows[0].shape[1]), 0)
    out = jnp.zeros(at.shape, F32)
    for r, row in enumerate(rows):
        out = jnp.where(at == r, row, out)
    return out


def _groups(p: ScanParams, body, init, *, reverse: bool = False):
    """``body(first row of a group of 8, carry) -> carry`` over a chunk's
    groups; a dynamic row index has to be a multiple of 8 on the chip."""
    n = p.chunk // ROWS

    def group(k, carry):
        k = n - 1 - k if reverse else k
        return body(pl.multiple_of(k * ROWS, ROWS), carry)

    return jax.lax.fori_loop(0, n, group, init)


def _fwd_kernel(keep_ref, u_ref, dt_ref, a_ref, b_ref, c_ref, y_ref,
                bound_ref, s_ref, *, p: ScanParams):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    bound_ref[0] = s_ref[...]  # the state this chunk starts from
    base = i * p.chunk
    for sl in _tile_slices(p):  # a tile of lanes at a time, down the chunk
        a = a_ref[:, sl]

        def group(t0, s, sl=sl, a=a):
            dt8 = dt_ref[pl.ds(t0, ROWS), sl]
            x8 = dt8 * u_ref[pl.ds(t0, ROWS), sl]
            ys = []
            for r in range(ROWS):
                keep = keep_ref[base + t0 + r].astype(F32)
                decay = jnp.exp(dt8[r : r + 1] * a) * keep
                s = decay * s + b_ref[t0 + r].astype(F32) * x8[r : r + 1]
                s = _rounded(s, p)
                ys.append(jnp.sum(
                    c_ref[t0 + r].astype(F32) * s, axis=0, keepdims=True
                ))
            y_ref[pl.ds(t0, ROWS), sl] = _stack_rows(ys)
            return s

        s_ref[:, sl] = _groups(p, group, s_ref[:, sl])


def _lane_sums(acc):
    """[rows, lane] float32 -> [8, rows]: every row's sum over its lanes,
    the rows along the lanes of the result (eight equal rows: the MXU's
    ones . acc^T, exact in float32 at the highest precision)."""
    ones = jnp.ones((8, acc.shape[1]), F32)
    return jax.lax.dot_general(
        ones, acc, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=F32,
    )


def _bwd_kernel(keep_ref, u_ref, dt_ref, a_ref, b_ref, c_ref, dy_ref,
                bound_ref, du_ref, ddt_ref, da_ref, db_ref, dc_ref,
                states_ref, g_ref, dbacc_ref, dcacc_ref, *, p: ScanParams):
    i = pl.program_id(1)  # chunks from the last to the first
    n_chunks = pl.num_programs(1)

    @pl.when(i == 0)
    def _():
        g_ref[...] = jnp.zeros_like(g_ref)
        da_ref[...] = jnp.zeros_like(da_ref)

    base = (n_chunks - 1 - i) * p.chunk
    dbacc_ref[...] = jnp.zeros_like(dbacc_ref)
    dcacc_ref[...] = jnp.zeros_like(dcacc_ref)
    for sl in _tile_slices(p):
        a = a_ref[:, sl]

        # the chunk's states again: states_ref[t] is the state BEFORE row t
        def again(t0, s, sl=sl, a=a):
            dt8 = dt_ref[pl.ds(t0, ROWS), sl]
            x8 = dt8 * u_ref[pl.ds(t0, ROWS), sl]
            for r in range(ROWS):
                states_ref[t0 + r, :, sl] = s
                keep = keep_ref[base + t0 + r].astype(F32)
                decay = jnp.exp(dt8[r : r + 1] * a) * keep
                s = decay * s + b_ref[t0 + r].astype(F32) * x8[r : r + 1]
                s = _rounded(s, p)
            return s

        states_ref[p.chunk, :, sl] = _groups(p, again, bound_ref[0, :, sl])

        # the adjoint recurrence, up the chunk
        def adjoint(t0, carry, sl=sl, a=a):
            g, da_sum = carry  # g: the cotangent of the state after a row
            dt8 = dt_ref[pl.ds(t0, ROWS), sl]
            u8 = u_ref[pl.ds(t0, ROWS), sl]
            dy8 = dy_ref[pl.ds(t0, ROWS), sl]
            x8 = dt8 * u8
            dus, ddts = [None] * ROWS, [None] * ROWS
            for r in reversed(range(ROWS)):
                t = t0 + r
                keep = keep_ref[base + t].astype(F32)
                dt, dy = dt8[r : r + 1], dy8[r : r + 1]
                decay = jnp.exp(dt * a)
                g = g + c_ref[t].astype(F32) * dy
                dcacc_ref[t] += dy * states_ref[t + 1, :, sl]
                dbacc_ref[t] += g * x8[r : r + 1]
                d_x = jnp.sum(  # the cotangent of delta u
                    g * b_ref[t].astype(F32), axis=0, keepdims=True
                )
                d_decay = g * (states_ref[t, :, sl] * keep) * decay
                dus[r] = d_x * dt
                ddts[r] = (
                    jnp.sum(d_decay * a, axis=0, keepdims=True)
                    + d_x * u8[r : r + 1]
                )
                da_sum = da_sum + d_decay * dt
                g = g * decay * keep
            du_ref[pl.ds(t0, ROWS), sl] = _stack_rows(dus)
            ddt_ref[pl.ds(t0, ROWS), sl] = _stack_rows(ddts)
            return g, da_sum

        g, da_sum = _groups(
            p, adjoint, (g_ref[:, sl], jnp.zeros(a.shape, F32)), reverse=True
        )
        g_ref[:, sl] = g
        da_ref[:, sl] += da_sum
    rows = p.chunk * a_ref.shape[0]
    db_ref[...] = _lane_sums(dbacc_ref[...].reshape(rows, p.lane))
    dc_ref[...] = _lane_sums(dcacc_ref[...].reshape(rows, p.lane))


def _specs(p: ScanParams, n: int, chunk_of):
    """Block specs by what they hold, the grid's step ``i`` walking chunk
    ``chunk_of(i)``: a chunk's rows of a channel block (u, delta, y and
    their cotangents), a channel block's ``a``, a chunk's spread ``b`` /
    ``c``, a chunk's boundary state."""
    return {
        "rows": pl.BlockSpec(
            (p.chunk, p.channel_block), lambda cb, i, keep: (chunk_of(i), cb)
        ),
        "a": pl.BlockSpec((n, p.channel_block), lambda cb, i, keep: (0, cb)),
        "spread": pl.BlockSpec(
            (p.chunk, n, p.lane), lambda cb, i, keep: (chunk_of(i), 0, 0)
        ),
        "bound": pl.BlockSpec(
            (1, n, p.channel_block), lambda cb, i, keep: (chunk_of(i), 0, cb)
        ),
    }


def _fwd_pallas(u, delta, a_t, b, c, keep, p: ScanParams):
    """(y [T, C] float32, the state at every chunk's start [T / chunk, N,
    C] float32)."""
    t, ch = u.shape
    n = a_t.shape[0]
    grid = (ch // p.channel_block, t // p.chunk)
    s = _specs(p, n, lambda i: i)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[s["rows"], s["rows"], s["a"], s["spread"], s["spread"]],
        out_specs=[s["rows"], s["bound"]],
        scratch_shapes=[pltpu.VMEM((n, p.channel_block), F32)],
    )
    return pl.pallas_call(
        functools.partial(_fwd_kernel, p=p),
        name="magi_ssm_scan_fwd_kernel",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((t, ch), F32),
            jax.ShapeDtypeStruct((grid[1], n, ch), F32),
        ],
        interpret=p.interpret,
        compiler_params=_compiler_params(),
    )(keep, u, delta, a_t, _spread(b, p.lane), _spread(c, p.lane))


def _bwd_pallas(u, delta, a_t, b, c, keep, bound, dy, p: ScanParams):
    """The cotangents of (u, delta, a_t, b, c), b's and c's in float32."""
    t, ch = u.shape
    n = a_t.shape[0]
    blocks, chunks = ch // p.channel_block, t // p.chunk
    back = lambda i: chunks - 1 - i  # noqa: E731
    s = _specs(p, n, back)
    # b's and c's cotangents: a channel block's share, eight equal rows
    sums = pl.BlockSpec(
        (8, p.chunk * n), lambda cb, i, keep: (cb, back(i))
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(blocks, chunks),
        in_specs=[
            s["rows"], s["rows"], s["a"], s["spread"], s["spread"],
            s["rows"], s["bound"],  # y's cotangent, the chunks' first states
        ],
        out_specs=[s["rows"], s["rows"], s["a"], sums, sums],
        scratch_shapes=[
            pltpu.VMEM((p.chunk + 1, n, p.channel_block), F32),
            pltpu.VMEM((n, p.channel_block), F32),
            pltpu.VMEM((p.chunk, n, p.lane), F32),
            pltpu.VMEM((p.chunk, n, p.lane), F32),
        ],
    )
    du, ddt, da, db, dc = pl.pallas_call(
        functools.partial(_bwd_kernel, p=p),
        name="magi_ssm_scan_bwd_kernel",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((t, ch), F32),
            jax.ShapeDtypeStruct((t, ch), F32),
            jax.ShapeDtypeStruct((n, ch), F32),
            jax.ShapeDtypeStruct((8 * blocks, t * n), F32),
            jax.ShapeDtypeStruct((8 * blocks, t * n), F32),
        ],
        interpret=p.interpret,
        compiler_params=_compiler_params(),
    )(keep, u, delta, a_t, _spread(b, p.lane), _spread(c, p.lane), dy, bound)
    # a block's eight rows are equal: the first of each, summed over blocks
    db, dc = (x[::8].sum(axis=0).reshape(t, n) for x in (db, dc))
    return du, ddt, da, db, dc


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan_pallas(u, delta, a_t, b, c, keep, p: ScanParams):
    return _fwd_pallas(u, delta, a_t, b, c, keep, p)[0]


def _scan_pallas_fwd(u, delta, a_t, b, c, keep, p):
    _record("fwd", u.shape, a_t.shape[0], p)
    y, bound = _fwd_pallas(u, delta, a_t, b, c, keep, p)
    return y, (u, delta, a_t, b, c, keep, bound)


def _scan_pallas_bwd(p, res, dy):
    u, delta, a_t, b, c, keep, bound = res
    _record("bwd", u.shape, a_t.shape[0], p)
    du, ddt, da, db, dc = _bwd_pallas(u, delta, a_t, b, c, keep, bound, dy, p)
    return du, ddt, da, db.astype(b.dtype), dc.astype(c.dtype), None


_scan_pallas.defvjp(_scan_pallas_fwd, _scan_pallas_bwd)


def _record(phase: str, shape, n: int, p: ScanParams) -> None:
    from .. import telemetry

    t, ch = shape
    telemetry.record_ssm_scan(
        phase, chunks=t // p.chunk, state_bytes=t // p.chunk * n * ch * 4
    )


def selective_scan(
    u, delta, a, b, c, d, start, *, chunk: int | None = None,
    channel_block: int | None = None, interpret: bool | None = None,
    state_dtype="float32",
):
    """``y`` [T, C] in ``u``'s dtype (module docstring). ``start`` marks
    the rows at which a document starts; a row past the sequence's last
    document may be marked too (padding: it reads nothing)."""
    t, ch = u.shape
    p = make_scan_params(
        t, ch, chunk=chunk, channel_block=channel_block,
        interpret=interpret, state_dtype=state_dtype,
    )
    pad = -t % p.chunk  # rows past the end: no step, no input
    uf, dtf, bp, cp = (
        jnp.pad(x, ((0, pad), (0, 0)))
        for x in (u.astype(F32), delta.astype(F32), b, c)
    )
    keep = jnp.pad(1 - start.astype(jnp.int32), (0, pad))
    a_t = a.astype(F32).T
    if on_jnp_backend():
        y = _scan_jnp(uf, dtf, a_t, bp, cp, keep, p)
    else:
        y = _scan_pallas(uf, dtf, a_t, bp, cp, keep, p)
    return (y[:t] + d.astype(F32) * uf[:t]).astype(u.dtype)
