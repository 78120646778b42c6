"""The flex kernels' softmax state and the backward's P / dS block on one
mask that puts every kind of row into one q block, CPU interpret mode.
Cases come from ``kernel_cases.run``; the tests of this file share them
(``--dist loadfile`` hands the file to one worker)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from magiattention_tpu.testing import assert_close

from .kernel_cases import (
    KernelCase, kernel_stats, operands, run, trace, uncovered_rows,
)

# -- the forward's softmax state (ISSUE 29) ---------------------------------
# ``_fwd_update`` keeps no -inf inside a step (a finite mask value, a lazy
# per-lane row sum) and ``_fwd_finalize`` restores the public convention.
# The mask ("state" in kernel_cases.MASKS) puts every kind of row into one q
# block of 64 (blocks of 64 x 128 and 64 x 256, so the row sum's
# lane-aligned path runs too):
#   rows   0..32   live in k block 0, fully masked in every later entry;
#   rows  32..64   fully masked in their first entry (entries), live in k
#                  [256, 384) only: what they gathered before is garbage
#                  and must be multiplied by exactly 0;
#   rows  64..100  no slice at all, in a q block that has entries;
#   rows 100..128  causal against k [384, 512);
#   rows 128..192  a q block with no entry of its own.
_STATE_T = 192
_STATE_UNCOVERED = uncovered_rows("state")
assert (_STATE_UNCOVERED == np.r_[64:100, 128:192]).all()


def _state(head_block, grid, block_k, with_sink, softcap, d=128, **more):
    """The state mask at 4 q heads over 2 kv heads, seed 29. head_dim 128
    unless a test says otherwise, so that the tests of this file meet on
    the same points. The boundary is recorded on the eight points where
    ``test_lse_and_delta_arrive_with_rows_along_lanes_at_a_small_block``
    reads it,
    whichever test comes to them first."""
    watch = (block_k, softcap, d) == (128, 0.0, 128) and not more
    return KernelCase(
        "state", hq=4, hk=2, d=d, block_q=64, block_k=block_k,
        head_block=head_block, grid=grid, softcap=softcap, sink=with_sink,
        seed=29, watch=watch, **more,
    )


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("head_block", [1, 4], ids=["per-head", "hb=4"])
@pytest.mark.parametrize("block_k", [128, 256])
@pytest.mark.parametrize("softcap", [0.0, 8.0], ids=["nocap", "softcap"])
@pytest.mark.parametrize("with_sink", [False, True], ids=["nosink", "sink"])
def test_fwd_softmax_state_rows(with_sink, softcap, block_k, head_block, grid):
    """Rows that are masked first and live later, live first and masked
    later, covered by nothing inside a block that has entries, and in a
    block with none: out, lse, rowmax and every gradient are
    ``_fwd_jnp``'s, and -inf stands exactly where the oracle has it."""
    case = _state(head_block, grid, block_k, with_sink, softcap)
    got, ref, _ = run(case)
    sink = operands(case)["sink"]
    for nm in ref:
        assert np.isfinite(got[nm][np.isfinite(ref[nm])]).all(), nm
        np.testing.assert_array_equal(
            np.isneginf(got[nm]), np.isneginf(ref[nm]), err_msg=nm
        )
        fin = np.isfinite(ref[nm])
        assert_close(got[nm][fin], ref[nm][fin], atol=5e-5, rtol=5e-5, msg=nm)
    un = _STATE_UNCOVERED
    assert not got["out"][:, un].any() and not got["dq"][:, un].any()
    assert np.isneginf(got["rowmax"][:, un]).all()
    if with_sink:  # a row that attends to nothing but the sink
        np.testing.assert_array_equal(
            got["lse"][:, un], np.broadcast_to(sink[:, None], (4, un.size))
        )
    else:
        assert np.isneginf(got["lse"][:, un]).all()


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("head_block", [1, 4], ids=["per-head", "hb=4"])
@pytest.mark.parametrize("sign", [0, -1], ids=["mixed", "all-negative"])
def test_fwd_finite_mask_value_never_meets_a_logit(sign, head_block, grid):
    """Logits of about +-1e4 after the scale, the useful edge of float32
    for a softmax: the finite in-step mask value (-2.4e38) stays far under
    them, so a row whose every live logit is hugely negative still counts
    as covered, and masked columns weigh exactly nothing."""
    got, ref, _ = run(
        _state(head_block, grid, 256, False, 0.0, d=32, amp=50.0, sign=sign)
    )
    assert 3e3 < np.abs(ref["rowmax"][np.isfinite(ref["rowmax"])]).max() < 1e5
    for nm in ("out", "lse", "rowmax"):
        np.testing.assert_array_equal(
            np.isneginf(got[nm]), np.isneginf(ref[nm]), err_msg=nm
        )
        fin = np.isfinite(ref[nm])
        assert_close(got[nm][fin], ref[nm][fin], atol=1e-4, rtol=2e-5, msg=nm)
    np.testing.assert_array_equal(  # the running maximum stays exact
        got["rowmax"], ref["rowmax"]
    )


# -- the backward's P / dS block (ISSUE 31, ISSUE 58) ------------------------
# ``_p_ds`` is the one copy of ``p = exp(s - lse)``, ``dS = p (dP - delta)``
# with its guards, against lse and delta as ``(1, rows)`` rows that
# broadcast down the transposed tile's sublanes. The same float32 operations
# on the same values as the plain form, which stays here as the reference.


def _p_ds_plain(s, dp, lse, delta, softcap):
    """The block written the plain way: the ``lse == -inf`` rows guarded
    by a select (``_p_ds``: one maximum), everything broadcast by numpy's
    rules."""
    from magiattention_tpu.ops.flex_attn import NEG_INF

    p = jnp.exp(s - jnp.where(lse == NEG_INF, 0.0, lse))
    ds = p * (dp - delta)
    if softcap > 0.0:
        ds = ds * (1.0 - (s / jnp.float32(softcap)) ** 2)
        ds = jnp.where(jnp.isneginf(s), 0.0, ds)
    return p, ds


# The factors that reach ``_p_ds``, read from ``_bwd_tile``
# (``ops/flex_attn.py``): ``block_k`` is the tile's sublanes (64, 128, 256:
# under, at and over a vreg's lanes, where the replaced ``lanes`` body took
# three different paths); ``softcap`` is the one field of ``params`` it
# reads; ``hb`` picks the per-head or the stacked (HB, bk, G*bq) layout; the
# head_dim is the contraction of dP^T = V dO^T. Two factors of the 96 cases
# this test ran until PR 45 cannot reach it, and went:
#   grid     ``params.grid`` is read by ``_Walk`` and ``_walk_grid`` alone,
#            which decide WHICH entries run a step; a live step hands
#            ``_bwd_tile`` the same refs on either grid, and both sides
#            of this comparison walk the same grid. (The sparse grid stays:
#            it has no dead step to hide a difference behind.)
#   sink     ``params.has_sink`` is read where the forward bodies finalize
#            a q block and by ``_flex_attn_core_bwd`` (dsink, made outside
#            the kernel from lse and delta); inside the block a sink changes the VALUE of
#            lse on covered rows and replaces ``-inf`` by the sink on
#            uncovered ones, which switches the ``lse == -inf`` guard, the
#            one place the two forms are written differently, OFF. So the
#            cases keep no sink: rows 64..100 and 128..192 have
#            ``lse = -inf`` and run the guard.
# What each went to: CHANGES.md, PR 45.
@pytest.mark.parametrize("head_block", [1, 4], ids=["per-head", "hb=4"])
@pytest.mark.parametrize("d", [128, 256])
@pytest.mark.parametrize("block_k", [64, 128, 256])
@pytest.mark.parametrize("softcap", [0.0, 8.0], ids=["nocap", "softcap"])
def test_the_bwd_block_is_the_plain_form(
    softcap, block_k, d, head_block, monkeypatch
):
    """dq, dk, dv of the two backward bodies (per head and head-batched)
    bit for bit what the plain form of the block gives, at tiles of 64,
    128 and 256 keys. The loss reads lse too (``delta - dlse``), rows
    64..100 and 128..192 have ``lse = -inf``: their dq is exactly zero and
    nothing is non-finite. And all of it within the oracle's tolerances."""
    from magiattention_tpu.ops import flex_attn as fa

    case = _state(head_block, "sparse", block_k, False, softcap, d=d)
    got, ref, _ = run(case)
    traced = []

    def plain_form(*args):
        traced.append(1)
        return _p_ds_plain(*args)

    monkeypatch.setattr(fa, "_p_ds", plain_form)
    old, _ = trace(case)
    assert traced  # the bodies did trace the reference, not a cached program
    grads = [nm for nm in got if nm.startswith("d")]
    assert grads == ["dq", "dk", "dv"]
    for nm in grads:
        assert np.isfinite(got[nm]).all(), nm
        np.testing.assert_array_equal(got[nm], old[nm], err_msg=nm)
        assert_close(got[nm], ref[nm], atol=5e-5, rtol=5e-5, msg=nm)
    assert not got["dq"][:, _STATE_UNCOVERED].any()


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("head_block", [1, 4], ids=["per-head", "hb=4"])
@pytest.mark.parametrize("with_sink", [False, True], ids=["nosink", "sink"])
def test_lse_and_delta_arrive_with_rows_along_lanes_at_a_small_block(
    with_sink, head_block, grid
):
    """The contract ``_bwd_tile`` leans on, at a q block under a vreg's
    lanes (64; blocks of 128 and 256: test_flex_attn_boundary.py): the
    launcher is handed lse (the forward's residual, from either forward
    body on either grid, which at this block leaves its kernel replicated
    over lanes) and delta (made before the kernel, ``_bwd_delta``, the lse
    cotangent folded in) as [hq, tqp], and what it hands the kernel is
    those two in ONE compact operand, bit for bit, on covered rows and on
    rows no entry covers (``-inf``, or the sink)."""
    from magiattention_tpu.ops import flex_attn as fa

    case = _state(head_block, grid, 128, with_sink, 0.0)
    got, _, seen = run(case)
    sink = operands(case)["sink"]
    assert fa.stats_form(case.block_q) == "lanes" and len(seen["stats"]) == 1
    for nm, x in zip(("lse", "delta"), kernel_stats(case, seen)):
        assert seen[nm].shape == (4, _STATE_T) and x.dtype == np.float32
        np.testing.assert_array_equal(x, seen[nm], err_msg=nm)
    np.testing.assert_array_equal(seen["lse"], got["lse"])
    un = _STATE_UNCOVERED
    covered = np.setdiff1d(np.arange(_STATE_T), un)
    assert np.isfinite(seen["lse"][:, covered]).all()
    assert np.isfinite(seen["delta"]).all() and seen["delta"].any()
    np.testing.assert_array_equal(
        seen["lse"][:, un],
        np.broadcast_to(
            sink[:, None] if with_sink else -np.inf, (4, un.size)
        ),
    )
