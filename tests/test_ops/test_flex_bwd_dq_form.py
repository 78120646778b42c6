"""Where the fused backward's dq result lives (ISSUE 44): in the inputs'
dtype, written by each q block's last visit, aliased to dO or to a zero
fill of its own where the table leaves q blocks out. CPU, interpret mode,
cases from ``kernel_cases``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from magiattention_tpu.ops import flex_flash_attn_func
from magiattention_tpu.testing import assert_close

from .kernel_cases import MASKS, KernelCase, kernel_stats, oracle, run, trace

TOKENS = 256


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
def test_dq_leaves_the_kernel_in_the_inputs_dtype(grid):
    """ISSUE 44: dq is the launcher's first result, [hq, tqp, d] in q's
    dtype, written by each q block's last visit; the caller gets it as it
    is. Rows past the slice inside a named block are exact zeros, and so
    are the blocks no entry names (tokens 128 on), which come from the
    zero fill the output is aliased to: the form reads ``zero_filled``."""
    case = KernelCase(
        "short_doc", head_block=2, grid=grid, dtype="bfloat16", watch=True
    )
    got, _, seen = run(case)
    dq = got["dq"]
    assert seen["dq_kernel"].dtype == dq.dtype == jnp.bfloat16
    assert seen["dq_kernel"].shape == (4, TOKENS, 32)
    assert seen["dq_form"] == "zero_filled"
    np.testing.assert_array_equal(
        np.asarray(dq, np.float32), np.asarray(seen["dq_kernel"], np.float32)
    )
    assert np.asarray(dq, np.float32)[:, :100].any()
    assert not np.asarray(dq, np.float32)[:, 100:].any()
    # what _bwd_tile reads, rows along lanes at this block of 64 too
    for nm, stat in zip(("lse", "delta"), kernel_stats(case, seen)):
        assert stat.shape == (4, TOKENS) and seen[nm].dtype == np.float32
        np.testing.assert_array_equal(stat, seen[nm], err_msg=nm)


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize(
    "hq,hk,head_block,dtype",
    [(2, 2, 1, "float32"), (2, 2, 2, "float32"), (4, 1, 1, "float32"),
     (4, 1, 4, "float32"), (8, 1, 1, "float32"), (8, 1, 8, "float32"),
     (4, 1, 1, "bfloat16"), (8, 1, 8, "bfloat16")],
    ids=["g1-per-head", "g1-batched", "g4-per-head", "g4-batched",
         "g8-per-head", "g8-batched", "g4-per-head-bf16", "g8-batched-bf16"],
)
def test_q_blocks_without_a_key_come_back_as_zeros(
    hq, hk, head_block, dtype, grid
):
    """ISSUE 44, head_dim 64 (the tile's padding lanes): a mask that leaves
    whole q blocks unnamed takes the zero-filled form, says so on the build
    counter, and returns exact zeros there; the same mask with a key for
    every block takes ``visits`` and fills nothing. dq, dk, dv of both
    against the float32 oracle. The kernels' side is traced here, under
    telemetry (the counter counts builds); the oracle comes from the
    cache."""
    from magiattention_tpu import telemetry

    was = telemetry.enabled()
    telemetry.set_enabled(True)
    reg = telemetry.get_registry()
    labels = dict(
        kernel="bwd", grid=grid, heads_per_step=head_block, delta="xla",
        stats="compact",  # at this block_q of 64 too: the one body
    )
    try:
        for mask, form in (("holes", "zero_filled"), ("holes_filled", "visits")):
            case = KernelCase(
                mask, hq=hq, hk=hk, d=64, head_block=head_block, grid=grid,
                dtype=dtype, sink=False, use_lse=False, watch=True,
            )
            before = reg.counter_value(
                "magi_flex_kernel_build_total", dq=form, **labels
            )
            got, seen = trace(case)
            assert seen["dq_form"] == form
            assert reg.counter_value(
                "magi_flex_kernel_build_total", dq=form, **labels
            ) == before + 1
            want = oracle(case)
            tol = 1e-4 if dtype == "float32" else 6e-2
            for nm in ("dq", "dk", "dv"):
                a = np.asarray(got[nm], np.float32)
                assert np.isfinite(a).all(), nm
                assert_close(a, want[nm], atol=tol, rtol=tol, msg=f"{form} {nm}")
            if form == "zero_filled":
                assert not np.asarray(got["dq"], np.float32)[:, 128:].any()
    finally:
        telemetry.set_enabled(was)


@pytest.mark.parametrize(
    "d,mask,aliases",
    [(128, "causal", {12: 3, 10: 2}), (64, "causal", {12: 3}),
     (128, "holes", {12: 3, 13: 2})],
    ids=["result-in-dO's-place", "padded-lanes-own-buffer", "zero-fill"],
)
def test_where_the_result_lives(d, mask, aliases):
    """ISSUE 44: the backward's dq result is no buffer more than before.
    Where the table names every q block it is aliased to dO (operand 10:
    a block's last visit is the last step to read its dO tile), unless the
    tile's lanes are padded (head_dim 64: the shapes differ); where blocks
    are left out it is aliased to a zero fill of its own (operand 13). The
    float32 sums' buffer is always aliased to an operand nobody has written
    (12, after the one statistic operand: ``lax.empty``, no fill)."""
    tq, _tk, qr, kr, ts = MASKS[mask]
    x = jnp.zeros((tq, 4, d), jnp.bfloat16)

    def loss(q, k, v):
        out, _ = flex_flash_attn_func(
            q, k, v, qr, kr, ts, block_q=64, block_k=64, head_block=2,
            interpret=True,
        )
        return out.astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x[:, :2], x[:, :2])

    def calls(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    (bwd,) = [e for e in calls(jaxpr.jaxpr) if e.params["name"] == "magi_flex_bwd_kernel"]
    assert dict(bwd.params["input_output_aliases"]) == aliases
