"""``kernel_cases`` itself (ISSUE 45): a case is computed once a process;
the tables every case is built on hold the mask its ranges state, and the
oracle every kernel test leans on (``_fwd_jnp`` on those tables) is the
dense softmax over the ranges; and pairs of factors that the one table
reaches and no file of its own builder did."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from magiattention_tpu.common.mask import make_attn_mask_from_ranges
from magiattention_tpu.ops import flex_attn as fa
from magiattention_tpu.testing import assert_close, ref_attn_from_ranges

from . import kernel_cases
from .kernel_cases import (
    MASKS, KernelCase, assert_grads, block_meta, operands, oracle, run,
)


def test_a_case_is_computed_once(monkeypatch):
    """The second ``run`` of a case returns the first one's arrays,
    read-only, and traces nothing; a case that differs in what the dense backend cannot
    see (the body, the grid) computes its kernels and takes the oracle as
    it is."""
    case = KernelCase("one_q_block", hq=2, hk=1, d=16, seed=45, watch=True)
    before = dict(kernel_cases.TRACES)
    first = run(case)
    assert kernel_cases.TRACES["kernel"] == before.get("kernel", 0) + 1
    assert kernel_cases.TRACES["oracle"] == before.get("oracle", 0) + 1

    def refuse(*args, **kwargs):
        raise AssertionError("a cached case was traced again")

    with monkeypatch.context() as m:
        m.setattr(fa, "flex_attn_headmajor", refuse)
        m.setattr(fa, "_fwd_jnp", refuse)
        again = run(dataclasses.replace(case))  # an equal case, not the same
    for a, b in zip(first, again):
        assert a.keys() == b.keys()
        assert all(a[n] is b[n] for n in a)
        assert not any(  # what many read nobody writes
            x.flags.writeable for x in a.values() if isinstance(x, np.ndarray)
        )
    other = run(dataclasses.replace(case, head_block=2, grid="sparse"))
    assert kernel_cases.TRACES["kernel"] == before.get("kernel", 0) + 2
    assert kernel_cases.TRACES["oracle"] == before.get("oracle", 0) + 1
    assert other.ref is first.ref and other.got is not first.got
    assert first.seen["dq_form"] == "visits"


# The block pairs the files that lean on the oracle build a mask's tables
# at, beyond the three every mask is read back at.
_BLOCKS_IN_USE = [
    *(("state", b) for b in [(64, 128), (64, 256)]),
    *(("edge", b) for b in [(64, 128), (128, 128), (256, 128)]),
    *((f"stepped_mixed_s{s}", (32, 32)) for s in (1, 2, 4, 8)),
]


@pytest.mark.parametrize(
    "mask,blocks",
    [(m, b) for b in [(64, 64), (32, 128), (128, 32)] for m in MASKS]
    + _BLOCKS_IN_USE,
    ids=lambda x: x if isinstance(x, str) else "{}x{}".format(*x),
)
def test_both_tables_hold_the_mask_the_ranges_state(mask, blocks):
    """The q-major and the k-major entry table of every mask of the table,
    read back tile by tile (``_entry_mask``, what the jnp backends read),
    are the dense mask of the ranges: the oracle shares its tables with
    the kernels, so the tables are held to the ranges here."""
    tq, tk, qr, kr, ts = MASKS[mask]
    case = KernelCase(mask, block_q=blocks[0], block_k=blocks[1])
    meta = block_meta(case)
    want = make_attn_mask_from_ranges(qr, kr, ts, tq, tk)
    tqp, tkp = meta.num_q_blocks * blocks[0], meta.num_k_blocks * blocks[1]
    ftab, btab = fa.fwd_tables(meta), fa.bwd_tables(meta)
    for name, tab in (("q-major", ftab), ("k-major", (btab[1], btab[0], *btab[2:]))):
        got = np.asarray(fa._dense_mask_from_tables(tab, tqp, tkp, *blocks))
        assert (got[:tq, :tk] == want).all(), name
        assert not got[tq:].any() and not got[:, tk:].any(), name
    assert meta.total_area == want.sum()


def _geometries():
    """The points the files that lean on the oracle put their cases at,
    as those files build them (heads, head_dim, block pair, softcap, sink,
    lse cotangent, seed): every value of every axis a file has at least
    once, not the product. A kernel test that brings a geometry no case
    here has adds it."""
    g = {}
    # test_flex_bwd_fused.py, _d256: 5 masks x group 1 / 4 / 8 x d 128 / 256
    for mask, group, d in [
        ("full", 1, 128), ("causal", 4, 256), ("invcausal", 8, 128),
        ("bicausal", 1, 256), ("stepped", 4, 128), ("stepped", 8, 256),
    ]:
        g[f"fused-{mask}-g{group}-d{d}"] = KernelCase(
            mask, hq=2 * group, hk=2, d=d)
    # test_flex_head_batched.py: group 1 / 4 / 8 x softcap
    for group, softcap in [(1, 8.0), (4, 0.0), (8, 8.0)]:
        g[f"four_docs-g{group}-cap{softcap:g}"] = KernelCase(
            "four_docs", hq=2 * group, hk=2, softcap=softcap, seed=11)
    # test_flex_fwd_state.py: block_k 64 / 128 / 256 x d 128 / 256 x sink x
    # softcap, and logits of 1e4
    for bk, d, sink, softcap in [
        (64, 256, False, 8.0), (128, 128, True, 0.0), (128, 256, False, 0.0),
        (256, 128, True, 8.0), (256, 128, False, 0.0),
    ]:
        g[f"state-64x{bk}-d{d}-sink{sink:d}-cap{softcap:g}"] = KernelCase(
            "state", d=d, block_k=bk, sink=sink, softcap=softcap, seed=29)
    for sign in (0, -1):
        g[f"state-64x256-amp50-sign{sign}"] = KernelCase(
            "state", block_k=256, sink=False, seed=29, amp=50.0, sign=sign)
    # test_flex_attn_boundary.py: three head geometries x block_q 64 / 128 /
    # 256 against block_k 128 x sink x lse cotangent
    for (hq, hk), bq, sink, use_lse in [
        ((4, 2), 64, True, True), ((5, 5), 128, False, True),
        ((8, 2), 256, True, False), ((5, 5), 256, True, True),
        ((8, 2), 64, True, True), ((4, 2), 128, True, False),
    ]:
        g[f"edge-{hq}q{hk}kv-{bq}x128-sink{sink:d}-lse{use_lse:d}"] = (
            KernelCase("edge", hq=hq, hk=hk, block_q=bq, block_k=128,
                       sink=sink, use_lse=use_lse))
    # test_stepped_bound.py: blocks of 32, head_dim 16, no sink
    for s in (1, 2, 4, 8):
        g[f"stepped_mixed_s{s}-32x32-d16"] = KernelCase(
            f"stepped_mixed_s{s}", d=16, block_q=32, block_k=32, sink=False,
            seed=s)
    # test_flex_attn.py (out alone feeds the loss), test_flex_bwd_dq_form.py
    # (head_dim 64, group 1 / 4 / 8), test_flex_bwd_protocol.py
    g["mixed_types-d64-out-alone"] = KernelCase(
        "mixed_types", d=64, sink=False, use_lse=False, seed=1)
    for (hq, hk), mask in [((2, 2), "holes"), ((4, 1), "holes_filled"),
                           ((8, 1), "holes")]:
        g[f"{mask}-{hq}q{hk}kv-d64"] = KernelCase(
            mask, hq=hq, hk=hk, d=64, sink=False, use_lse=False)
    g["column_boundary-8q2kv"] = KernelCase("column_boundary", hq=8, hk=2)
    g["one_q_block-4q1kv"] = KernelCase("one_q_block", hq=4, hk=1)
    return g


BRIDGED = {
    **{mask: KernelCase(mask, softcap=6.0) for mask in MASKS}, **_geometries()
}


@pytest.mark.parametrize("name", list(BRIDGED))
def test_the_oracle_is_the_dense_softmax_over_the_ranges(name):
    """out, lse and every gradient of ``kernel_cases.oracle`` against
    ``ref_attn_from_ranges``, which reads the ranges and no table: on every
    mask of the table (with softcap, a sink and an lse cotangent), and at
    the heads, head_dims, block pairs, softcaps and seeds the files that
    lean on the oracle use (:func:`_geometries`)."""
    case = BRIDGED[name]
    _tq, _tk, qr, kr, ts = MASKS[case.mask]
    got = oracle(case)
    x = {n: jnp.asarray(a) for n, a in operands(case).items()}

    def loss(q, k, v, sink):
        out, lse, _ = ref_attn_from_ranges(
            *(jnp.transpose(a, (1, 0, 2)) for a in (q, k, v)), qr, kr, ts,
            sink=sink if case.sink else None, softcap=case.softcap,
        )
        out, lse = jnp.transpose(out, (1, 0, 2)), lse.T
        res = (out * x["do"]).sum()
        if case.use_lse:
            res += (jnp.where(jnp.isneginf(lse), 0.0, lse) * x["w"]).sum()
        return res, (out, lse)

    (_, (out, lse)), grads = jax.jit(
        jax.value_and_grad(loss, argnums=(0, 1, 2, 3), has_aux=True)
    )(x["q"], x["k"], x["v"], x["sink"])
    np.testing.assert_array_equal(np.isneginf(got["lse"]), np.isneginf(lse))
    if case.amp != 1.0:  # logits of 1e4: the tolerance of the test that leans
        assert_close(got["out"], out, atol=1e-4, rtol=2e-5, msg="out")
        assert_close(got["lse"], lse, atol=1e-4, rtol=2e-5, msg="lse")
        return
    assert_close(got["out"], out, atol=2e-5, rtol=2e-5, msg="out")
    assert_close(got["lse"], lse, atol=2e-5, rtol=2e-5, msg="lse")
    for nm, g in zip(("dq", "dk", "dv", "dsink")[: 3 + case.sink], grads):
        assert_close(got[nm], g, atol=5e-5, rtol=5e-5, msg=nm)


def _both_bodies_both_grids(mask, group=4, **more):
    return [
        KernelCase(mask, hq=2 * group, hk=2, head_block=hb, grid=grid, **more)
        for hb, grid in ((1, "row_major"), (group, "sparse"))
    ]


# Pairs of factors that each had a builder of its own until ISSUE 45 and
# never met: the fused-backward file knew no softcap, the head-batched file
# one mask, the stepped file no sink, no lse cotangent and blocks of 32, the
# dq-form file no traced table, and rows no slice covers met a softcap at
# group 4 alone. (A case here costs what any kernel case costs, about 3 s:
# add one for a pair a change makes reachable, not for the count.)
PAIRS = {
    "softcap on stepped slices that share q rows": _both_bodies_both_grids(
        "stepped", softcap=8.0),
    "softcap on padded traced tables": _both_bodies_both_grids(
        "four_docs", softcap=8.0, traced=True, pad=13),
    "bf16 under softcap and a sink": _both_bodies_both_grids(
        "causal", d=64, softcap=8.0, dtype="bfloat16"),
    "padded lanes on traced tables that leave q blocks out":
        _both_bodies_both_grids("holes", d=64, traced=True),
    "stepped bounds at step 4 under a sink, an lse cotangent, head_dim 128":
        _both_bodies_both_grids("stepped_mixed_s4", d=128),
    "rows no slice covers under softcap, group 1": _both_bodies_both_grids(
        "uncovered_rows", group=1, softcap=8.0)[:1] + [
        KernelCase("uncovered_rows", hq=2, hk=2, head_block=2, grid="sparse",
                   softcap=8.0)],
}


@pytest.mark.parametrize("body", ["per-head-row_major", "batched-sparse"])
@pytest.mark.parametrize("pair", list(PAIRS))
def test_a_pair_of_factors_no_file_reached(pair, body):
    case = PAIRS[pair][body == "batched-sparse"]
    assert (case.head_block > 1) == (body == "batched-sparse") == (case.grid == "sparse")
    assert_grads(case, tol=6e-2 if case.dtype == "bfloat16" else 1e-4)
    got, ref, _ = run(case)
    if case.dtype == "float32":
        live = ~np.isneginf(ref["lse"])
        assert (np.isneginf(got["lse"]) == ~live).all()
        assert_close(got["out"], ref["out"], atol=3e-5, rtol=3e-5, msg="out")
        assert_close(got["lse"][live], ref["lse"][live], atol=3e-5, rtol=3e-5, msg="lse")


# A value width of its own (ISSUE 49): latent attention's keys of 192 (as
# they are, and on 256 lanes) beside values of 128, per head on the
# row-major grid and head-batched on the compact one, forward and backward.
VALUE_WIDTHS = {
    f"{d}|{dv}-{body}": KernelCase(
        "four_docs", hq=4, hk=4, d=d, dv=dv, head_block=hb, grid=grid,
        sink=False, seed=49,
    )
    for d, dv in ((192, 128), (256, 128))
    for body, hb, grid in (
        ("per-head-row_major", 1, "row_major"), ("batched-sparse", 4, "sparse"),
    )
}


@pytest.mark.parametrize("name", list(VALUE_WIDTHS))
def test_a_value_width_of_its_own_against_the_plain_reference(name):
    """out [.., dv], lse and dq, dk [.., d], dv [.., dv] of the kernels
    against ``benchmarks/reference.py``'s ``attention_rows``, which reads
    the ranges' dense mask and no table, and against the jnp backend on the
    same tables."""
    from benchmarks import reference

    case = VALUE_WIDTHS[name]
    tq, tk, qr, kr, ts = MASKS[case.mask]
    got, ref, _ = run(case)
    assert got["out"].shape == (case.hq, tq, case.dv)
    assert got["dq"].shape == (case.hq, tq, case.d)
    assert got["dk"].shape == (case.hk, tk, case.d)
    assert got["dv"].shape == (case.hk, tk, case.dv)
    assert_grads(case)
    x = operands(case)
    allow = jnp.asarray(make_attn_mask_from_ranges(qr, kr, ts, tq, tk))
    rows = lambda a: jnp.transpose(jnp.asarray(a), (1, 0, 2))  # noqa: E731
    with jax.default_matmul_precision("highest"):
        out, lse, dq, dk, dv = reference.attention_rows(
            rows(x["q"]), rows(x["k"]), rows(x["v"]), allow, rows(x["do"]),
            jnp.asarray(x["w"]).T,
        )
    live = ~np.isneginf(np.asarray(lse).T)
    assert (np.isneginf(got["lse"]) == ~live).all()
    for nm, want in (("out", out), ("dq", dq), ("dk", dk), ("dv", dv)):
        assert_close(
            got[nm], np.transpose(np.asarray(want), (1, 0, 2)),
            atol=1e-4, rtol=1e-4, msg=nm,
        )
    assert_close(got["lse"][live], np.asarray(lse).T[live], atol=3e-5,
                 rtol=3e-5, msg="lse")
