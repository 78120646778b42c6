"""The kept form of a flex-attention call (ISSUE 48,
``FlexAttnParams.kept``: what a checkpointed layer's call is told): out
and the lse [hq, tqp] are named for the checkpoint's policy. Same kernels,
same values, and since ISSUE 58 the same program as the bare call, whose
residual is that lse too: no lane-replicated statistic is made for the
backward kernel, and none by the forward where ``stats_form`` is
``compact``. CPU, interpret mode, cases from ``kernel_cases``."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from magiattention_tpu.ops import flex_attn as fa

from .kernel_cases import KernelCase, assert_grads, launch_args, run
from .test_boundary_program import _outside_kernels

# a sink and a non-zero lse cotangent in every case (``KernelCase``'s
# defaults): dsink reads the compact lse, delta takes the cotangent in.
# Block 64 leaves the forward kernel in the ``lanes`` form of the
# statistics, 128 in the ``compact`` one; "edge" has rows no key covers (lse -inf) and q
# blocks no entry names (dq from the zero fill)
CASES = {
    "lanes-per-head": KernelCase("mixed_types", kept="full", watch=True),
    "compact-batched-sparse-bf16": KernelCase(
        "edge", hq=4, hk=1, block_q=128, block_k=128, head_block=4,
        grid="sparse", dtype="bfloat16", kept="full",
    ),
    "compact-softcap-no-lse": KernelCase(
        "four_docs", block_q=128, block_k=64, softcap=5.0, use_lse=False,
        kept="sliding",
    ),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_kept_form_gives_the_bare_calls_values_to_the_bit(name):
    case = CASES[name]
    kept, bare = run(case), run(dataclasses.replace(case, kept=""))
    assert sorted(kept.got) == sorted(bare.got)
    for nm, x in kept.got.items():  # out, lse, rowmax, dq, dk, dv, dsink
        np.testing.assert_array_equal(
            np.asarray(x, np.float32), np.asarray(bare.got[nm], np.float32),
            err_msg=nm,
        )
    if case.dtype == "float32":
        assert_grads(case)
    if case.watch:  # the backward kernel's own statistic operands
        assert len(kept.seen["stats"]) == len(bare.seen["stats"]) == 1
        for a, b in zip(kept.seen["stats"], bare.seen["stats"]):
            np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(kept.seen["delta"], bare.seen["delta"])


def _program(case):
    """(the forward kernel's outputs, the backward kernel's statistic
    operands, every float32 array replicated over lanes [hq, tqp, 128]
    that any equation outside the kernels makes or reads, by the scope of
    the equation that makes it) of the case's forward+backward through
    ``flex_attn_headmajor``."""
    q, k, v, sink, ftab, btab, params = launch_args(case)

    def fwdbwd(q, k, v, sink, d_out, d_lse):
        _res, vjp = jax.vjp(
            lambda *x: fa.flex_attn_headmajor(
                *x[:3], ftab, btab, params, sink=x[3]
            )[:2],
            q, k, v, sink,
        )
        return vjp((d_out, d_lse))

    jaxpr = jax.make_jaxpr(fwdbwd)(
        q, k, v, sink, q, jnp.ones(q.shape[:2], jnp.float32)
    ).jaxpr
    fwd, bwd = (
        [
            e for e in _outside_kernels(jaxpr)
            if e.primitive.name == "pallas_call"
            and e.params["name"] == f"magi_flex_{role}_kernel"
        ]
        for role in ("fwd", "bwd")
    )
    assert len(fwd) == len(bwd) == 1
    aliased = dict(bwd[0].params["input_output_aliases"])
    # dq's padded lanes are no statistic, nor are its float32 sums
    dq_buffers = [*bwd[0].outvars[2:], *(bwd[0].invars[i] for i in aliased)]
    lanes = sorted(
        # a kernel's name, else the innermost scope:
        # "transpose(jvp(magi_layout))" -> magi_layout
        e.params["name"] if e.primitive.name == "pallas_call"
        else re.findall(r"magi_\w+", str(e.source_info.name_stack))[-1]
        for e in _outside_kernels(jaxpr)
        for x in e.outvars
        if x.aval.shape == (*q.shape[:2], fa.LANES)
        and x.aval.dtype == jnp.float32
        and x not in dq_buffers
        and (  # makes it, not hands it on (a custom_vjp's call)
            e.primitive.name == "pallas_call"
            or not list(jax.core.jaxprs_in_params(e.params))
        )
    )
    # the seven tables, q, k, v, dO; then the statistics, up to the buffers
    # in HBM that only give dq its places (aliased to outputs)
    stats = [
        x.aval.shape for i, x in enumerate(bwd[0].invars)
        if i > 10 and i not in aliased
    ]
    return [x.aval.shape for x in fwd[0].outvars], stats, lanes


@pytest.mark.parametrize("kept", ["", "full"], ids=["bare", "kept"])
def test_no_lane_replicated_statistic_in_a_differentiated_call(kept):
    """ISSUE 58, where ``stats_form`` is ``compact``: the differentiated
    forward kernel writes out and ONE ``(hq / HBG, nq, 2, HBG, bq)`` array
    (lse and the row maximum), as the forward nobody differentiates; the
    backward kernel reads one array of that layout (lse and delta, at its
    own head block); and no [hq, tqp, 128] float32 array exists anywhere in
    the program, kept or not: XLA broadcasts nothing (the parent made delta
    so under ``magi_bwd_delta`` and a kept layer's lse under
    ``magi_layout``) and the forward writes no residual (the parent's bare
    call wrote a third output)."""
    case = dataclasses.replace(CASES["compact-batched-sparse-bf16"], kept=kept)
    hq, tqp, bq = case.hq, 768, case.block_q
    compact = (hq // case.head_block, tqp // bq, 2, case.head_block, bq)
    outs, stats, lanes = _program(case)
    assert outs == [(hq, tqp, case.d), compact]
    assert stats == [compact]
    assert lanes == []


def test_a_block_under_a_vregs_lanes_replicates_in_the_forward_alone():
    """``stats_form`` ``lanes`` (block_q 64, the CPU tests' small blocks):
    the forward kernel writes lse and the row maximum replicated (it has no
    whole (128, 128) tile to turn), and the backward takes the [hq, tqp]
    lse and delta as its one compact operand all the same: XLA replicates
    nothing under ``magi_layout`` or ``magi_bwd_delta``."""
    case = CASES["lanes-per-head"]
    hq, tqp, bq = case.hq, 256, case.block_q
    rep = (hq, tqp, fa.LANES)
    outs, stats, lanes = _program(case)
    assert outs == [(hq, tqp, case.d), rep, rep]
    assert stats == [(hq, tqp // bq, 2, 1, bq)]
    assert lanes == ["magi_flex_fwd_kernel"] * 2
