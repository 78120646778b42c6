"""One table of kernel cases, each run once a process (ISSUE 45).

Every test of ``tests/test_ops`` that needs the flex kernels' results names
a :class:`KernelCase` and reads :func:`run`: the Pallas forward and backward
through ``flex_attn_headmajor`` in interpret mode, the dense jnp backend
(``_fwd_jnp``, plain autodiff) on the same tables as the oracle, and what
crossed the backward kernel's boundary. Both sides are memoised, the oracle
apart and on what it depends on alone (not the head block, the grid, the
dtype or the tables' padding), so a configuration that several tests assert
on is traced once. ``--dist loadfile`` hands a file to one worker: tests
that share cases live in one file.

A new kernel test adds a mask to :data:`MASKS` and cases to its own
parametrisation; it does not write a builder. A test that patches the
kernel module takes its patched side from :func:`trace` (never cached) and
the unpatched side from :func:`run`. The kernel module is left as it is
unless a case asks to have the backward kernel's boundary recorded
(``watch``: the few tests that assert on it). Every array handed out is
read-only: the next reader of a cached case gets what the first one got.
Not collected by pytest (no ``test_`` prefix);
``tests/test_ops/test_kernel_cases.py`` tests this module. The memos live
as long as the process, which is one file's worker.
"""

import collections
import contextlib
import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from magiattention_tpu.common import AttnMaskType
from magiattention_tpu.ops import build_block_meta
from magiattention_tpu.ops import flex_attn as fa
from magiattention_tpu.ops.block_meta import pad_block_meta
from magiattention_tpu.testing import assert_close

F = AttnMaskType.FULL
C = AttnMaskType.CAUSAL
I = AttnMaskType.INVCAUSAL  # noqa: E741
B = AttnMaskType.BICAUSAL
C4 = C.with_step(4)


def _stepped_mixed(s):
    """The three bounded types at step ``s`` and a FULL slice, over ragged
    ranges that share q rows and k columns."""
    return (
        128, 128,
        [(0, 40), (40, 83), (83, 126), (5, 33)],
        [(3, 61), (10, 115), (70, 128), (64, 100)],
        [C.with_step(s), I.with_step(s), B.with_step(s), F],
    )


# name -> (tq, tk, q_ranges, k_ranges, types)
MASKS = {
    # -- mask scenarios of the first kernel tests (test_flex_attn.py)
    "dense_full_256": (256, 256, [(0, 256)], [(0, 256)], [F]),
    "dense_causal_256": (256, 256, [(0, 256)], [(0, 256)], [C]),
    "unaligned_causal": (200, 200, [(0, 200)], [(0, 200)], [C]),
    "varlen_causal": (
        320, 320,
        [(0, 100), (100, 256), (256, 320)],
        [(0, 100), (100, 256), (256, 320)],
        [C, C, C],
    ),
    "varlen_full": (256, 256, [(0, 96), (96, 256)], [(0, 96), (96, 256)], [F, F]),
    "mixed_types": (
        256, 256,
        [(0, 64), (64, 128), (128, 192), (192, 256)],
        [(0, 128), (0, 64), (64, 200), (100, 256)],
        [C, F, I, B],
    ),
    # two slices share q rows (multi-k attention)
    "q_overlap": (128, 256, [(0, 128), (32, 96)], [(0, 128), (128, 256)], [C, F]),
    "uncovered_rows": (256, 256, [(0, 100)], [(0, 100)], [C]),
    "cross_attn_rect": (128, 384, [(0, 128)], [(0, 384)], [C]),
    "sliding_window_ish": (
        256, 256,
        [(0, 64), (64, 128), (128, 192), (192, 256)],
        [(0, 64), (32, 128), (96, 192), (160, 256)],
        [C, C, C, C],
    ),
    # -- the head-batched backward (ISSUE 25): four documents, one of each
    # mask type, none aligned to blocks of 64; rows 300..384 attend to
    # nothing, so q block 5 has no entry at all
    "four_docs": (
        384, 384,
        [(0, 90), (90, 170), (170, 250), (250, 300)],
        [(0, 90), (90, 170), (150, 250), (230, 300)],
        [F, C, I, B],
    ),
    # -- the forward's softmax state (ISSUE 29): every kind of row in one q
    # block of 64 (see test_flex_fwd_state.py)
    "state": (
        192, 512,
        [(0, 32), (32, 64), (100, 128)],
        [(0, 128), (256, 384), (384, 512)],
        [F, F, C],
    ),
    # -- what crosses the kernels' boundary (ISSUE 40):
    #   rows   0..150  full against k [0, 300);
    #   rows 150..260  causal against k [100, 512);
    #   rows 260..300  no slice at all, in a q block that has entries;
    #   rows 300..500  full against k [0, 512);
    #   rows 500..768  nothing: the tail of a block, then blocks with no entry
    "edge": (
        768, 512,
        [(0, 150), (150, 260), (300, 500)],
        [(0, 300), (100, 512), (0, 512)],
        [F, C, F],
    ),
    # -- the fused backward (ISSUE 43): one slice of each bounded type on
    # ranges that are no multiple of a block, and two slices at a step of 4
    # that share q rows with the first
    "full": (256, 256, [(0, 250)], [(6, 256)], [F]),
    "causal": (256, 256, [(0, 250)], [(0, 250)], [C]),
    "invcausal": (256, 256, [(3, 200)], [(0, 256)], [I]),
    "bicausal": (256, 256, [(0, 180)], [(10, 256)], [B]),
    "stepped": (
        256, 256,
        [(0, 128), (128, 256), (16, 80)],
        [(0, 128), (0, 256), (128, 200)],
        [C4, C4, F],
    ),
    # one q block: every entry of the k-major walk names the same dq tile
    "one_q_block": (64, 256, [(0, 60)], [(0, 256)], [F]),
    # two slices split q block 0 against every key, the other q blocks see
    # the first k block alone: a column boundary where the next entry names
    # the same q block, and inside a column two entries on one tile
    "column_boundary": (
        256, 256,
        [(0, 30), (30, 64), (64, 256)],
        [(0, 256), (0, 256), (0, 64)],
        [F, F, F],
    ),
    # a short causal document in 256 tokens: blocks 2 and 3 unnamed
    "short_doc": (256, 256, [(0, 100)], [(0, 100)], [C]),
    # a padded tail (250 of 256 tokens), two documents, and q blocks 2 and 3
    # (rows 128 to 255 at block_q 64) without a key; then the same with a
    # key for every block
    "holes": (250, 250, [(0, 100), (100, 128)], [(0, 100), (60, 128)], [C, F]),
    "holes_filled": (
        250, 250,
        [(0, 100), (100, 128), (128, 250)],
        [(0, 100), (60, 128), (0, 250)],
        [C, F, C],
    ),
    # -- the stepped bound (ISSUE 42)
    **{f"stepped_mixed_s{s}": _stepped_mixed(s) for s in (1, 2, 4, 8)},
}


def uncovered_rows(mask: str) -> np.ndarray:
    """The q rows of ``mask`` that no slice covers."""
    tq, _tk, qr, _kr, _ts = MASKS[mask]
    covered = np.zeros(tq, bool)
    for a, b in qr:
        covered[a:b] = True
    return np.flatnonzero(~covered)


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One configuration of the flex kernels (hashable: the memo's key)."""

    mask: str
    hq: int = 4
    hk: int = 2
    d: int = 32
    # the value heads' width (v, out, dO, dv), where it is not the key
    # heads' ``d`` (q, k, dq, dk): latent attention's 128 beside 192; 0: ``d``
    dv: int = 0
    block_q: int = 64
    block_k: int = 64
    head_block: int = 1
    grid: str = "row_major"
    softcap: float = 0.0
    sink: bool = True
    dtype: str = "float32"
    # the loss reads lse too (a non-zero lse cotangent), else out alone (a
    # symbolic zero)
    use_lse: bool = True
    # the tables are jit arguments and the row-major extents come from the
    # meta, as on the keyed path; else concrete, and the step runs eagerly
    traced: bool = False
    # more entries a table, as ``StageTables.from_rank_metas`` pads the
    # ranks' tables to the longest (``pad_block_meta``)
    pad: int = 0
    entry_pad: int = 8
    # record what crosses the backward kernel's boundary (``Run.seen``): the
    # one thing that patches the kernel module, so only the tests that read
    # it ask. A file's tests that share configurations all ask, or none
    watch: bool = False
    # ``FlexAttnParams.kept``: the form a checkpointed layer's call takes
    # (out and the lse [hq, tq] are named for the checkpoint's policy). The
    # values are the bare call's
    kept: str = ""
    # q and k scaled by ``amp``; ``sign`` -1 makes every logit negative
    amp: float = 1.0
    sign: int = 0
    seed: int = 0

    @property
    def tokens(self):
        return MASKS[self.mask][:2]

    @property
    def value_width(self) -> int:
        return self.dv or self.d

    def oracle_key(self) -> "KernelCase":
        """The case with what the dense jnp backend cannot see set to its
        default: which body walks which grid, the dtype the kernels round
        to, the sentinel entries the tables are padded with, whether
        anyone watches the kernels, and which residual the call keeps."""
        return dataclasses.replace(
            self, head_block=1, grid="row_major", dtype="float32",
            traced=False, pad=0, entry_pad=8, watch=False, kept="",
        )


class Run(NamedTuple):
    """``got`` / ``ref``: out [hq, tq, dv], lse, rowmax [hq, tq], dq
    [hq, tq, d], dk [hk, tk, d], dv [hk, tk, dv] and, under a sink, dsink
    [hq], of the Pallas kernels
    and of ``_fwd_jnp``, as numpy. ``seen``: what crossed the backward
    kernel's boundary on a case that has ``watch`` set (``lse`` and
    ``delta`` [hq, tqp] as the launcher was handed them, ``stats``: the
    statistics among the kernel's own operands, one compact array or two
    lane-replicated ones, ``dlse`` or None, ``dq_kernel`` as the launcher
    returned it, ``dq_form``), padded to whole blocks; else empty. All
    read-only."""

    got: dict
    ref: dict
    seen: dict


# how often each side was computed: the memo's own test reads it
TRACES = collections.Counter()


@functools.lru_cache(maxsize=None)
def block_meta(case: KernelCase):
    tq, tk, qr, kr, ts = MASKS[case.mask]
    meta = build_block_meta(
        qr, kr, [int(t) for t in ts], tq, tk, block_q=case.block_q,
        block_k=case.block_k, entry_pad=case.entry_pad,
    )
    if case.pad:
        meta = pad_block_meta(
            meta, meta.num_fwd_entries + case.pad,
            meta.num_bwd_entries + case.pad, meta.num_slices + 2,
        )
    return meta


def operands(case: KernelCase) -> dict:
    """q [hq, tq, d], k [hk, tk, d], v [hk, tk, dv], do [hq, tq, dv], w
    [hq, tq] (the lse cotangent), sink [hq]: float32, head-major, drawn
    from ``case.seed``."""
    tq, tk = case.tokens
    return _operands(
        tq, tk, case.hq, case.hk, case.d, case.value_width, case.seed,
        case.amp, case.sign,
    )


@functools.lru_cache(maxsize=64)
def _operands(tq, tk, hq, hk, d, dv, seed, amp, sign):
    rng = np.random.default_rng(seed)
    make = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa: E731
    x = dict(
        q=make(hq, tq, d), k=make(hk, tk, d), v=make(hk, tk, dv),
        do=make(hq, tq, dv), w=make(hq, tq), sink=make(hq),
    )
    if sign:
        x["q"], x["k"] = np.abs(x["q"]), sign * np.abs(x["k"])
    x["q"], x["k"] = x["q"] * np.float32(amp), x["k"] * np.float32(amp)
    return {n: _frozen(a) for n, a in x.items()}


def _params(case: KernelCase, meta):
    return fa.FlexAttnParams(
        block_q=case.block_q, block_k=case.block_k, scale=case.d**-0.5,
        softcap=float(case.softcap), has_sink=case.sink, out_dtype=case.dtype,
        interpret=True, head_block=case.head_block,
        fwd_steps=meta.fwd_steps, bwd_steps=meta.bwd_steps, grid=case.grid,
        mask_step=fa.bounds_mask_step(meta.slice_bounds), kept=case.kept,
    )


def _frozen(x) -> np.ndarray:
    """``x`` as a numpy array nobody can write to: a cached case has many
    readers."""
    x = np.asarray(x)
    x.flags.writeable = False
    return x


def _padded(x, rows):
    """``x`` [heads, tokens, ...] with zero rows up to ``rows`` tokens."""
    widths = [(0, 0), (0, rows - x.shape[1])] + [(0, 0)] * (x.ndim - 2)
    return jnp.pad(jnp.asarray(x), widths)


def launch_args(case: KernelCase):
    """(q, k, v [heads, tokens padded to whole blocks, d] in the case's
    dtype; sink [hq]; the forward and the backward tables; the params):
    what ``flex_attn_headmajor`` and the launchers under it take."""
    meta = block_meta(case)
    tqp, tkp = meta.num_q_blocks * case.block_q, meta.num_k_blocks * case.block_k
    x = operands(case)
    q, k, v = (
        _padded(x[n], rows).astype(case.dtype)
        for n, rows in (("q", tqp), ("k", tkp), ("v", tkp))
    )
    return (
        q, k, v, jnp.asarray(x["sink"]), fa.fwd_tables(meta),
        fa.bwd_tables(meta), _params(case, meta),
    )


def _differentiate(case: KernelCase, attn, q, k, v, sink, tables, jit: bool):
    """out, lse, rowmax and the gradients of the case's loss through
    ``attn(q, k, v, sink, *tables)``, cut back to the mask's tokens."""
    tq, tk = case.tokens
    x = operands(case)
    # the cotangents as a model hands them: in the outputs' dtype
    do = _padded(x["do"], q.shape[1]).astype(q.dtype).astype(jnp.float32)
    w = _padded(x["w"], q.shape[1])

    def loss(q, k, v, sink, *tables):
        out, lse, rowmax = attn(q, k, v, sink, *tables)
        res = (out.astype(jnp.float32) * do).sum()
        if case.use_lse:
            res += (jnp.where(jnp.isneginf(lse), 0.0, lse) * w).sum()
        return res, (out, lse, rowmax)

    step = jax.value_and_grad(loss, argnums=(0, 1, 2, 3), has_aux=True)
    (_, (out, lse, rowmax)), grads = (jax.jit(step) if jit else step)(
        q, k, v, sink, *tables
    )
    res = dict(
        out=out[:, :tq], lse=lse[:, :tq], rowmax=rowmax[:, :tq],
        dq=grads[0][:, :tq], dk=grads[1][:, :tk], dv=grads[2][:, :tk],
    )
    if case.sink:
        res["dsink"] = grads[3]
    return {n: _frozen(a) for n, a in res.items()}


@contextlib.contextmanager
def _watch_the_boundary(seen: dict):
    """Record what the backward kernel is handed and what it hands back
    (an eager step: the values are concrete)."""
    bwd_delta, bwd_pallas, build = (
        fa._bwd_delta, fa._bwd_pallas, fa._flex_pallas_call
    )

    def delta_spy(do, out, dlse):
        seen.update(dlse=None if dlse is None else _frozen(dlse))
        return bwd_delta(do, out, dlse)

    def pallas_spy(q, k, v, do, lse, delta, tables, params):
        res = bwd_pallas(q, k, v, do, lse, delta, tables, params)
        seen.update(
            lse=_frozen(lse), delta=_frozen(delta), dq_kernel=_frozen(res[0]),
            dq_form=fa.dq_form(params, tables[1], q.shape[1] // params.block_q),
        )
        return res

    def build_spy(role, heads, grid, body, form=None, **kwargs):
        call = build(role, heads, grid, body, form, **kwargs)
        if role != "bwd":
            return call

        def launch(*operands):
            # the seven tables, q, k, v, dO; then the statistics, up to
            # the buffers in HBM that only give dq its places
            blocked = sum(
                s.block_shape is not None for s in kwargs["grid_spec"].in_specs
            )
            seen.update(stats=[_frozen(x) for x in operands[11 : 7 + blocked]])
            return call(*operands)

        return launch

    fa._bwd_delta, fa._bwd_pallas, fa._flex_pallas_call = (
        delta_spy, pallas_spy, build_spy
    )
    try:
        yield
    finally:
        fa._bwd_delta, fa._bwd_pallas, fa._flex_pallas_call = (
            bwd_delta, bwd_pallas, build
        )


def kernel_stats(case: KernelCase, seen: dict):
    """(lse, delta) [hq, tqp] as the backward kernel's own statistic
    operand holds them at every ``block_q``: out of the one compact ``(hq /
    HBG, nq, 2, HBG, bq)`` array, whose head block is the backward's
    own."""
    (x,) = seen["stats"]
    hq, tqp = seen["lse"].shape
    assert x.dtype == np.float32
    assert x.shape[2:] == (2, hq // x.shape[0], case.block_q), x.shape
    return tuple(np.asarray(r) for r in fa._rows_from_compact(x, hq, tqp))


def _attn(case: KernelCase, params):
    """The case's kernels as a function of (q, k, v, sink, ftab, btab)."""

    def attn(q, k, v, sink, ftab, btab):
        return fa.flex_attn_headmajor(
            q, k, v, ftab, btab, params, sink=sink if case.sink else None
        )

    return attn


def trace(case: KernelCase):
    """(got, seen) of the Pallas kernels, computed now: the side of a test
    that has patched the kernel module. :func:`run` caches this."""
    TRACES["kernel"] += 1
    q, k, v, sink, ftab, btab, params = launch_args(case)
    attn = _attn(case, params)
    if case.traced:
        assert not case.watch, "a traced step hands the spies tracers"
        return _differentiate(case, attn, q, k, v, sink, (ftab, btab), True), {}
    seen = {}
    with _watch_the_boundary(seen) if case.watch else contextlib.nullcontext():
        got = _differentiate(
            case, lambda *x: attn(*x, ftab, btab), q, k, v, sink, (), False
        )
    return got, seen


_kernel = functools.lru_cache(maxsize=None)(trace)


def forward_alone(case: KernelCase) -> dict:
    """out, lse, rowmax of the case's forward with nothing differentiating
    it: the build that writes no residual for a backward (what serving
    runs), where :func:`run`'s forward is the differentiated one. Computed
    now; a forward alone is the cheap program."""
    q, k, v, sink, ftab, btab, params = launch_args(case)
    attn = _attn(case, params)
    res = (jax.jit(attn) if case.traced else attn)(q, k, v, sink, ftab, btab)
    tq = case.tokens[0]
    return {
        n: _frozen(a[:, :tq]) for n, a in zip(("out", "lse", "rowmax"), res)
    }


@functools.lru_cache(maxsize=None)
def _oracle(case: KernelCase):
    TRACES["oracle"] += 1
    q, k, v, sink, ftab, _btab, params = launch_args(case)

    def attn(q, k, v, sink):
        return fa._fwd_jnp(q, k, v, sink.reshape(case.hq, 1), ftab, params)

    return _differentiate(case, attn, q, k, v, sink, (), True)


def oracle(case: KernelCase) -> dict:
    """``_fwd_jnp``'s results on the case's tables and float32 operands."""
    return _oracle(case.oracle_key())


def run(case: KernelCase) -> Run:
    """The case's kernels, its oracle and its boundary, each computed once
    a process."""
    got, seen = _kernel(case)
    return Run(got, oracle(case), seen)


def assert_grads(case: KernelCase, tol: float = 1e-4) -> None:
    """Every gradient of the case's kernels is finite and the oracle's."""
    got, ref, _ = run(case)
    for nm in ("dq", "dk", "dv", "dsink")[: 3 + case.sink]:
        assert np.isfinite(got[nm]).all(), nm
        assert_close(got[nm], ref[nm], atol=tol, rtol=tol, msg=nm)
