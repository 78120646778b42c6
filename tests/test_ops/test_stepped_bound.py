"""The stepped bound (``AttnMaskType.with_step``): a slice whose causal /
inv-causal bound moves s keys every s rows, in blocks counted from the
slice's aligned corner. Host geometry, the entry tables (Python and C++),
the tuner's primitives and the kernels, each against the definition
written out here; and step 1 is what it was before steps existed."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from magiattention_tpu.common import AttnMaskType as T
from magiattention_tpu.common.mask import (
    make_attn_mask_from_ranges, slice_area, slice_area_left_of_k, slice_mask,
    slice_rows, unstepped_slice_count,
)
from magiattention_tpu.common.range import AttnRange
from magiattention_tpu.common.rectangle import AttnRectangle, AttnRectangles
from magiattention_tpu.ops import block_meta as bm
from magiattention_tpu.tuning.cost_model import (
    exact_mask_area, slice_block_k_spans, slices_digest,
)

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
N = 48
STEPS = (1, 2, 4, 8)
RANGES = {
    "aligned": (0, 32, 0, 32),
    "aligned-wide": (8, 24, 0, 48),
    "ragged": (3, 29, 5, 38),
    "ragged-tall": (1, 47, 10, 23),
}
CASES = [
    pytest.param(s, base, r, id=f"s{s}-{T(base).name.lower()}-{r}")
    for s in STEPS for base in range(4) for r in RANGES
]


def definition(qs, qe, ks, ke, base, s, n=N):
    """ISSUE 42's predicate, a pair at a time."""
    m = np.zeros((n, n), bool)
    for q in range(qs, qe):
        for k in range(ks, ke):
            ok = True
            if base & 1:
                ok &= (ke - 1 - k) // s >= (qe - 1 - q) // s
            if base & 2:
                ok &= (k - ks) // s >= (q - qs) // s
            m[q, k] = ok
    return m


def test_the_type_word_and_its_members():
    c4 = T.CAUSAL.with_step(4)
    assert int(c4) == 1 | 2 << 2 and c4.step == 4 and c4.base is T.CAUSAL
    assert c4.is_causal_bound and not c4.is_inv_causal_bound
    assert T(int(c4)) is c4 and T(np.int64(9)) is c4 and c4 != T.CAUSAL
    assert T.CAUSAL.with_step(1) is T.CAUSAL and T.FULL.with_step(8) is T.FULL
    assert list(T) == [T.FULL, T.CAUSAL, T.INVCAUSAL, T.BICAUSAL]
    assert c4.name == "CAUSAL_STEP4"
    for bad in (0, 3, 6, 1 << 16):
        with pytest.raises(ValueError, match="power of two"):
            T.CAUSAL.with_step(bad)
    with pytest.raises(ValueError):
        T(1 << 10)


@pytest.mark.parametrize("s,base,r", CASES)
def test_host_geometry_is_the_definition(s, base, r):
    qs, qe, ks, ke = RANGES[r]
    mt = T(base).with_step(s)
    want = definition(qs, qe, ks, ke, base, s)
    assert (slice_mask(qs, qe, ks, ke, mt, N, N) == want).all()
    assert slice_area(qs, qe, ks, ke, mt) == want.sum()
    assert exact_mask_area([(qs, qe)], [(ks, ke)], [int(mt)]) == want.sum()
    for pos in (0, 7, 16, 33, N):
        assert slice_area_left_of_k(qs, qe, ks, ke, mt, pos) == want[:, :pos].sum()
    # the tuner's per-block spans hold every allowed pair of their rows
    idx, lo, hi, k_lo, k_hi = slice_block_k_spans(qs, qe, ks, ke, int(mt), 16)
    for a, b, c, d in zip(lo, hi, k_lo, k_hi):
        cols = np.flatnonzero(want[a:b].any(axis=0))
        if cols.size:
            assert c <= cols[0] and cols[-1] < d
        if mt.base != T.BICAUSAL and d > c:  # tight where one bound rules
            assert (c, d) == (cols[0], cols[-1] + 1)
    # how many rectangles the four unstepped types take
    rows = [tuple(np.flatnonzero(r)) for r in want if r.any()]
    blocks = sum(a != b for a, b in zip(rows, [None] + rows[:-1]))
    assert unstepped_slice_count([(qs, qe)], [(ks, ke)], [mt]) == (
        1 if s == 1 or base == 0 else blocks
    )


@pytest.mark.parametrize("s,base,r", CASES)
def test_cuts_keep_every_pair_once(s, base, r):
    """The dynamic solver's cuts and the dispatch's chunk slicing, at
    every line: the pieces tile the slice's mask, each on its side."""
    qs, qe, ks, ke = RANGES[r]
    mt = T(base).with_step(s)
    want = definition(qs, qe, ks, ke, base, s).astype(int)
    rect = AttnRectangle(AttnRange(qs, qe), AttnRange(ks, ke), mt)
    rects = AttnRectangles()
    rects.append(rect)

    def dense(pieces):
        m = np.zeros((N, N), int)
        for p in pieces:
            m += slice_mask(p.q_range.start, p.q_range.end, p.k_range.start,
                            p.k_range.end, p.mask_type, N, N)
        return m

    for pos in range(0, N + 1, 3):
        top, bottom = rect.cut_q_multi(pos)
        assert (dense(top + bottom) == want).all()
        assert all(p.q_range.end <= pos for p in top)
        assert all(p.q_range.start >= pos for p in bottom)
        if s == 1:
            assert len(top) <= 1 and len(bottom) <= 1
        left, right = rect.cut_k_multi(pos)
        assert (dense(left + right) == want).all()
        assert all(p.k_range.end <= pos for p in left)
        assert all(p.k_range.start >= pos for p in right)
        assert rects.area_left_of_q(pos) == want[:pos].sum()
        assert rects.area_left_of_k(pos) == want[:, :pos].sum()
    for a, b in ((qs, qe), (qs + 1, qe - 2), (qs + 5, qs + 7)):
        pieces = slice_rows(qs, qe, ks, ke, mt, a, b)
        m = np.zeros((N, N), int)
        for p in pieces:
            m += slice_mask(*p, N, N)
        assert (m[a:b] == want[a:b]).all() and m[:a].sum() == m[b:].sum() == 0


def _random_slices(rng, n, count):
    out = []
    for _ in range(count):
        qs = rng.integers(0, n - 8)
        qe = rng.integers(qs + 1, n + 1)
        ks = rng.integers(0, n - 8)
        ke = rng.integers(ks + 1, n + 1)
        mt = T(int(rng.integers(0, 4))).with_step(int(2 ** rng.integers(0, 4)))
        out.append((qs, qe, ks, ke, int(mt)))
    return np.asarray(out, np.int64)


@pytest.mark.parametrize("seed", range(6))
def test_entry_tables_python_and_native(seed):
    """Which tiles a stepped slice touches and which of them are whole,
    over swapped runs: the Python emitter, the C++ one and a brute-force
    count agree, and so do the two native area sums."""
    from magiattention_tpu.csrc import (
        area_left_native, emit_entries_native, slice_area_runs_native,
    )

    rng = np.random.default_rng(seed)
    n, bq, bk = 96, 16, 32
    sl = _random_slices(rng, n, 3)
    q_runs = [bm.Run(0, 48, 48), bm.Run(48, 0, 48)]
    k_runs = [bm.Run(0, 0, n)]
    py = np.asarray(
        bm._emit_entries(sl, q_runs, k_runs, bq, bk), np.int64
    ).reshape(-1, 9)
    qa = np.asarray([(0, 48, 48), (48, 0, 48)], np.int64)
    ka = np.asarray([(0, 0, n)], np.int64)
    native = emit_entries_native(sl, qa, ka, bq, bk)
    if native is not None:
        assert (native == py).all()
    dense = [slice_mask(*r[:4], int(r[4]), n, n) for r in sl]
    for sid, m in enumerate(dense):
        cover = np.zeros((n, n), bool)
        for e in py[py[:, 2] == sid]:
            cover[e[3] + e[7]:e[4] + e[7], e[5] + e[8]:e[6] + e[8]] = True
        assert not (m & ~cover).any()
    area = sum(int(m.sum()) for m in dense)
    got = slice_area_runs_native(sl, qa, ka)
    assert got is None or got == area
    meta = bm.build_block_meta(
        sl[:, :2], sl[:, 2:4], sl[:, 4], n, n, block_q=bq, block_k=bk
    )
    assert meta.total_area == area
    runs = meta.fwd_runs.reshape(-1, bm.RUN_FIELDS)
    for e in range(meta.num_fwd_entries):
        sid = meta.fwd_slice_id[e]
        if sid < len(sl):
            i, j = meta.fwd_q_block[e], meta.fwd_k_block[e]
            whole = dense[sid][i * bq:(i + 1) * bq, j * bk:(j + 1) * bk].all()
            assert (runs[e, 6] == 0) == bool(whole)
    rects = AttnRectangles.from_ranges(
        sl[:, :2].tolist(), sl[:, 2:4].tolist(), sl[:, 4].tolist()
    )
    for pos in (0, 5, 17, 40, 77, n):
        for axis_q, py_area in ((True, rects.area_left_of_q),
                                (False, rects.area_left_of_k)):
            got = area_left_native(rects.to_array(), axis_q, pos)
            assert got is None or got == py_area(pos)


def test_a_bad_type_word_is_a_typed_error():
    """ISSUE 42: the bare ``assert 0 <= t <= 3`` of ``build_block_meta``
    is a ValueError that names the slice, its type and its step; the
    stepped type words pass."""
    with pytest.raises(ValueError, match=r"slice 1 .*q \[8, 16\).*bad mask "
                       r"type word 77 \(type 1, step 2\*\*19"):
        bm.build_block_meta([[0, 8], [8, 16]], [[0, 8], [0, 16]], [1, 77], 16, 16)
    with pytest.raises(ValueError, match="bad mask type word -1"):
        bm.build_block_meta([[0, 8]], [[0, 8]], [-1], 8, 8)
    meta = bm.build_block_meta(
        [[0, 16]], [[0, 16]], [int(T.CAUSAL.with_step(4))], 16, 16,
        block_q=8, block_k=8,
    )
    assert meta.total_area == 16 * 16 // 2 + 16 * 4 // 2


@pytest.mark.parametrize("s,base,r", CASES)
def test_the_kernels_two_masks_agree(s, base, r):
    """``_entry_interval_mask`` (the Pallas kernels') == ``_entry_mask``
    (the jnp backends') == the definition, tile by tile; and the form the
    backward's transposed tile takes it in (ISSUE 58: keys down the
    sublanes, one unsigned compare) is its transpose."""
    from magiattention_tpu.ops.flex_attn import (
        _entry_interval_mask, _entry_mask, bounds_mask_step,
    )

    qs, qe, ks, ke = RANGES[r]
    mt = T(base).with_step(s)
    want = definition(qs, qe, ks, ke, base, s)
    bq = bk = 16
    meta = bm.build_block_meta(
        [(qs, qe)], [(ks, ke)], [int(mt)], N, N, block_q=bq, block_k=bk
    )
    assert bounds_mask_step(meta.slice_bounds) == (1 if base == 0 else s)
    bounds, runs = jnp.asarray(meta.slice_bounds), jnp.asarray(meta.fwd_runs)
    got = np.zeros((N, N), bool)
    for e in range(meta.num_fwd_entries):
        i, j = int(meta.fwd_q_block[e]), int(meta.fwd_k_block[e])
        args = (bounds, runs, int(meta.fwd_slice_id[e]), e, i * bq, j * bk,
                bq, bk)
        a = np.asarray(_entry_interval_mask(*args, stepped=s > 1))
        assert (a == np.asarray(_entry_mask(*args))).all()
        t = _entry_interval_mask(*args, stepped=s > 1, transposed=True)
        assert t.shape == (bk, bq) and (np.asarray(t).T == a).all()
        got[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk] |= a
    assert (got == want).all()


@pytest.mark.parametrize("backend,grid,head_block", [
    ("pallas", "row_major", 1), ("pallas", "sparse", 2),
    ("pallas", "row_major", 4), ("jnp", "row_major", 1),
    ("jnp_online", "row_major", 1),
])
@pytest.mark.parametrize("s", STEPS)
def test_kernels_against_the_dense_softmax(s, backend, grid, head_block,
                                           monkeypatch):
    """out / lse / dq / dk / dv of the Pallas kernels (interpret, both
    grids, heads batched or not) and of both jnp backends, on one mask of
    the three bounded types at step ``s`` and a FULL slice
    (``kernel_cases.MASKS``). The Pallas kernels against the dense jnp
    backend on the same tables (``kernel_cases.run``), and that backend,
    as the online one, against the dense softmax over the ranges: the
    chain has no link that is not compared. The Pallas forward alone (the
    build without the backward's residual) against the differentiated one
    and against the dense softmax directly."""
    from magiattention_tpu.testing import ref_attn_from_ranges

    from .kernel_cases import (
        MASKS, KernelCase, forward_alone, operands, run,
    )

    mask = f"stepped_mixed_s{s}"
    if backend == "pallas":
        case = KernelCase(
            mask, hq=4, hk=2, d=16, block_q=32, block_k=32, grid=grid,
            head_block=head_block, sink=False, seed=s,
        )
        got, want, _ = run(case)
        # the forward nobody differentiates (no residual is built) is the
        # differentiated one bit for bit, and the dense softmax over the
        # ranges, which reads no table
        alone, ops = forward_alone(case), operands(case)
        for nm in ("out", "lse"):
            np.testing.assert_array_equal(alone[nm], got[nm], err_msg=nm)
        _, _tk, qr, kr, ts = MASKS[mask]
        dense_out, dense_lse, _ = ref_attn_from_ranges(
            *(jnp.transpose(ops[n], (1, 0, 2)) for n in "qkv"), qr, kr, ts
        )
        dense_out, dense_lse = jnp.transpose(dense_out, (1, 0, 2)), dense_lse.T
        dense_live = ~np.isneginf(np.asarray(dense_lse))
        np.testing.assert_allclose(alone["out"], dense_out, atol=3e-5, rtol=3e-5)
        assert (np.isneginf(alone["lse"]) == ~dense_live).all(), mask
        np.testing.assert_allclose(
            alone["lse"][dense_live], np.asarray(dense_lse)[dense_live],
            atol=3e-5, rtol=3e-5,
        )
        (o, lse), (ro, rlse) = (
            [x[n] for n in ("out", "lse")] for x in (got, want)
        )
        grads = [[x[n] for n in ("dq", "dk", "dv")] for x in (got, want)]
    else:
        from magiattention_tpu.ops import flex_flash_attn_func

        monkeypatch.setenv("MAGI_ATTENTION_KERNEL_BACKEND", backend)
        rng = np.random.default_rng(s)
        t, _tk, qr, kr, ts = MASKS[mask]
        q = jnp.asarray(rng.standard_normal((t, 4, 16)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((t, 2, 16)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((t, 2, 16)), jnp.float32)

        def ours(q, k, v):
            return flex_flash_attn_func(
                q, k, v, qr, kr, ts, block_q=32, block_k=32, grid=grid,
                head_block=head_block,
            )[:2]

        def dense(q, k, v):
            return ref_attn_from_ranges(q, k, v, qr, kr, ts)[:2]

        def loss(fn):
            def f(q, k, v):
                o, lse = fn(q, k, v)
                lse = jnp.where(jnp.isneginf(lse), 0.0, lse)
                return (o * jnp.cos(o)).sum() + 0.3 * lse.sum()
            return f

        (o, lse), (ro, rlse) = ours(q, k, v), dense(q, k, v)
        grads = [
            jax.grad(loss(fn), argnums=(0, 1, 2))(q, k, v)
            for fn in (ours, dense)
        ]
    np.testing.assert_allclose(o, ro, atol=3e-5, rtol=3e-5, err_msg=mask)
    live = ~np.isneginf(np.asarray(rlse))
    assert (np.isneginf(np.asarray(lse)) == ~live).all(), mask
    np.testing.assert_allclose(
        np.asarray(lse)[live], np.asarray(rlse)[live], atol=3e-5, rtol=3e-5
    )
    for a, b, which in zip(*grads, "qkv"):
        np.testing.assert_allclose(
            a, b, atol=2e-4, rtol=2e-4, err_msg=f"{mask} d{which}"
        )


def test_overlapping_stepped_slices_are_refused():
    from magiattention_tpu.common.sanity import check_slices_non_overlapping

    c4 = T.CAUSAL.with_step(4)
    # the staircase of step 4 reaches 3 keys past the diagonal's
    check_slices_non_overlapping([(0, 16), (0, 16)], [(0, 16), (16, 32)], [c4, T.FULL])
    with pytest.raises(ValueError, match="slices 0 and 1 overlap"):
        check_slices_non_overlapping(
            [(0, 16), (0, 16)], [(0, 16), (0, 16)],
            [c4, T.INVCAUSAL.with_step(4)],
        )
    # at step 1 the two triangles share the diagonal; at step 4, blocks
    with pytest.raises(ValueError, match="overlap"):
        check_slices_non_overlapping(
            [(0, 16), (4, 16)], [(0, 16), (0, 12)], [c4.base, T.INVCAUSAL]
        )


# ---------------------------------------------------------------------------
# step 1 is what it was
# ---------------------------------------------------------------------------


def _fingerprint(cell_name, pinned=None):
    """What the planner and the tuner make of a cell's masks: the slices'
    digest, the tuner's rung (``pinned[kind]`` in its place, where
    given), the entry tables' bytes at that rung."""
    from benchmarks import harness, masks
    from magiattention_tpu.api.functools import infer_attn_mask_from_cu_seqlens
    from magiattention_tpu.tuning.autotuner import resolve_block_config
    from magiattention_tpu.tuning.cost_model import _normalize_slices

    cell = harness.load_cell(REPO, cell_name)
    cfg, tr = cell.config, cell.traffic
    total = int(tr["total_tokens"])
    m = masks.build_mask(tr["mask"], total, index=0)
    found = {"full": (m.q_ranges, m.k_ranges, m.types)}
    if "sliding_attention" in cfg.get("layer_types", ()):
        q, k, t = infer_attn_mask_from_cu_seqlens(
            m.cu_seqlens, causal=False,
            window_size=(cfg["sliding_window"] - 1, 0),
        )
        found["sliding"] = (
            q.to_naive_ranges(), k.to_naive_ranges(), [int(x) for x in t]
        )
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]  # latent
    )
    out = {}
    for kind, (q, k, t) in found.items():
        rung = (pinned or {}).get(kind) or resolve_block_config(
            [tuple(x) for x in q], [tuple(x) for x in k],
            tuple(int(x) for x in t), total, total, cell.chips, hq, hk, d,
            "bfloat16",
        )
        meta = bm.build_block_meta(
            q, k, t, total, total, block_q=rung[0], block_k=rung[1]
        )
        h = hashlib.sha256()
        # ISSUE 44 put each q block's first / last visit into bits 1 and 2
        # of the k-major table's flag word: without them the tables are the
        # parent's to the byte (bit 0, "needs mask", is what it was)
        bwd_runs = meta.bwd_runs.reshape(-1, bm.RUN_FIELDS).copy()
        assert (bwd_runs[:, 6] >> 3 == 0).all()
        bwd_runs[:, 6] &= bm.NEEDS_MASK
        for a in (meta.fwd_q_block, meta.fwd_k_block, meta.fwd_slice_id,
                  meta.fwd_runs, meta.bwd_k_block, meta.bwd_q_block,
                  meta.bwd_slice_id, bwd_runs.reshape(-1), meta.slice_bounds):
            h.update(np.ascontiguousarray(a).tobytes())
            h.update(b"|")
        out[f"{cell_name}/{kind}"] = {
            "slices_digest": slices_digest(*_normalize_slices(q, k, t)).hex(),
            "rung": list(rung),
            "tables": h.hexdigest(),
            "entries": [meta.num_fwd_entries, meta.num_bwd_entries],
            "area": int(meta.total_area),
            "exact_mask_area": int(exact_mask_area(q, k, t)),
        }
    return out


with open(os.path.join(HERE, "data", "step1_goldens.json")) as _f:
    GOLDENS = json.load(_f)  # written by the parent commit (fadb98e)


KERNELS = GOLDENS.pop("_kernels")
# plans whose rung the tuner has moved since the goldens were written: what
# it gives now, and the tables at that rung. The planner is still held to
# the golden, at the golden's own rung. ISSUE 54: the cp=4 packed mask (3.7%
# of its square) no longer gets the long-sequence lead of the tie order.
# ISSUE 56: four plans that sat on (128, 512, 8) take (256, 512, 8), the
# cheaper of the pair by its own price
MOVED = {
    "magi64x8-attn-cp4-256k-varlen/full": {
        "rung": [256, 512, 8],
        "entries": [20656, 20656],
        "tables": (
            "1b99fb4a94e224ea076d79457e6d660a516f7f6f5b62f77eba2acf99101c6e55"
        ),
    },
    "magi64x8-attn-64k-varlen/full": {
        "rung": [256, 512, 8],
        "entries": [1872, 1872],
        "tables": (
            "09f670da18c5ab6defa7c983187928393a2ab41ed2e98d9aecb697a8f1fb6e38"
        ),
    },
    "magi64x8-attn-64k-swa1024/full": {
        "rung": [256, 512, 8],
        "entries": [768, 768],
        "tables": (
            "c2d85951ffb9a760740e48a348d93dd1bed56f75bab785ad8538d0af3cd9fa30"
        ),
    },
    "trinitymini-train-32k-packed/full": {
        "rung": [256, 512, 8],
        "entries": [608, 608],
        "tables": (
            "24712b4cd25a6fb9e46d2d36fa778cad695fbc69274e74ce276623da225a796d"
        ),
    },
    "zaya1-train-16k-traces/full": {
        "rung": [256, 512, 8],
        "entries": [384, 384],
        "tables": (
            "9e9872cfbdc6e25c2296912116dfc67cb475bce8902d2503b4613a398211abe3"
        ),
    },
}


def _step_one_programs(differentiated: bool) -> list[str]:
    from magiattention_tpu.ops import flex_flash_attn_func
    from magiattention_tpu.testing.workloads import (
        ranges_of, varlen_block_causal,
    )

    t = 4096
    qr, kr, ts = ranges_of(varlen_block_causal(t))
    texts = []
    with jax.enable_x64(False):
        q = jnp.zeros((t, 32, 128), jnp.bfloat16)
        k = jnp.zeros((t, 4, 128), jnp.bfloat16)
        for grid, rung in (("sparse", (128, 512, 8)),
                           ("row_major", (128, 512, 8)),
                           ("row_major", (128, 128, 1))):
            def loss(q, k, v):
                out, lse = flex_flash_attn_func(
                    q, k, v, qr, kr, ts, grid=grid, block_q=rung[0],
                    block_k=rung[1], head_block=rung[2], interpret=False,
                )
                return out.astype(jnp.float32).sum() + lse.sum()

            if differentiated:
                loss = jax.value_and_grad(loss, argnums=(0, 1, 2))
            texts.append(str(jax.make_jaxpr(loss)(q, k, k)))
    return texts


@pytest.mark.parametrize("program", ["fwd", "fwd_bwd"])
def test_step_one_traces_the_parents_kernels(program):
    """The kernels on a packed 4,096-token mask at 32 / 4 heads of 128, on
    the compact grid and the row-major one, heads batched and per head:
    the traced program (the kernels' bodies are in it) is the golden's
    character for character, so at step 1 the chip's compiler is handed
    what it was handed before steps existed. ``fwd``: the forward, still
    the program of the commit before steps (fadb98e). ``fwd_bwd``: with
    the backward, whose golden is ISSUE 58's tree: ISSUE 43 made dq and dkv
    one kernel, ISSUE 44 changed that kernel's dq protocol (and took two
    XLA passes from round it) and ISSUE 58 its statistics' boundary and
    the orientation of its tile, so the older texts cannot come back. That
    the forward's golden still holds under ISSUE 58 is the point of it:
    the differentiated forward is the forward nobody differentiates."""
    texts = _step_one_programs(program == "fwd_bwd")
    assert sum(map(len, texts)) == KERNELS[program]["chars"]
    assert hashlib.sha256("\n".join(texts).encode()).hexdigest() == (
        KERNELS[program]["jaxpr_sha256"]
    )


@pytest.mark.parametrize("cell", sorted({k.split("/")[0] for k in GOLDENS}))
def test_step_one_is_the_parents_plan(cell, monkeypatch):
    """Every mask of the eleven cells that stood before steps existed:
    the digest, the tuner's decision and the entry tables, byte for byte
    what the parent commit built (the file holds the parent's). Where a
    later tuner change moved a plan's rung (``MOVED``) the decision and the
    tables are held to what it gives now, and the tables at the golden's
    own rung to the golden."""
    for var in ("MAGI_ATTENTION_BLOCK_Q", "MAGI_ATTENTION_BLOCK_K",
                "MAGI_ATTENTION_AUTOTUNE", "MAGI_ATTENTION_GRID"):
        monkeypatch.delenv(var, raising=False)
    want = {k: v for k, v in GOLDENS.items() if k.split("/")[0] == cell}
    with jax.enable_x64(False):
        assert _fingerprint(cell) == {
            k: {**v, **MOVED.get(k, {})} for k, v in want.items()
        }
        for k in want.keys() & MOVED.keys():
            at_the_goldens = {k.split("/")[1]: tuple(want[k]["rung"])}
            assert _fingerprint(cell, at_the_goldens)[k] == want[k]
