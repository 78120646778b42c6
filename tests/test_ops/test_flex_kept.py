"""The kept form of a flex-attention call (ISSUE 48,
``FlexAttnParams.kept``: what a checkpointed layer's call is told): the
residual is the compact lse, named with out for the checkpoint's policy,
and the backward makes the lanes its kernel reads. Same kernels, same
values as the bare call, whose program stays what it was. CPU, interpret
mode, cases from ``kernel_cases``."""

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
# Block 64 leaves the kernel in the ``lanes`` form of the statistics, 128
# in the ``compact`` one; "edge" has rows no key covers (lse -inf) and q
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
    if case.watch:  # the lanes made in the backward are the kernel's own
        np.testing.assert_array_equal(
            kept.seen["lse_lanes"], bare.seen["lse_lanes"]
        )
        np.testing.assert_array_equal(kept.seen["delta"], bare.seen["delta"])


def _program(case):
    """(the forward kernel's outputs, the scope of every XLA broadcast that
    makes a lane-replicated [hq, tqp, 128] array) of the case's
    forward+backward through ``flex_attn_headmajor``."""
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
    (fwd,) = [
        e for e in _outside_kernels(jaxpr)
        if e.primitive.name == "pallas_call"
        and e.params["name"] == "magi_flex_fwd_kernel"
    ]
    to_lanes = sorted(
        # the innermost scope: "transpose(jvp(magi_layout))" -> magi_layout
        re.findall(r"magi_\w+", str(e.source_info.name_stack))[-1]
        for e in _outside_kernels(jaxpr)
        if e.primitive.name == "broadcast_in_dim"
        and e.invars[0].aval.ndim  # (a scalar's fill reformats nothing)
        and e.outvars[0].aval.shape == (*q.shape[:2], fa.LANES)
    )
    return [v.aval.shape for v in fwd.outvars], to_lanes


def test_the_bare_calls_program_has_no_broadcast_of_the_lse():
    """``kept=""`` traces the program of before ISSUE 48: the differentiated
    forward kernel writes the lane-replicated lse as its third output and
    XLA makes one lane-replicated array, delta, under ``magi_bwd_delta``:
    nothing under ``magi_layout`` (the attention cells' program; the keyed
    call's is held by test_boundary_program.py). The kept form's forward
    writes no lanes, as the forward nobody differentiates, and its backward
    makes them from the compact lse under ``magi_layout``: one pass at the
    HBM's pace a layer, where the second forward kernel was."""
    case = dataclasses.replace(CASES["compact-batched-sparse-bf16"], kept="")
    hq, tqp = case.hq, 768
    outs, to_lanes = _program(case)
    assert outs[0] == (hq, tqp, case.d) and len(outs) == 3
    assert outs[2] == (hq, tqp, fa.LANES)
    assert to_lanes == ["magi_bwd_delta"]
    outs, to_lanes = _program(dataclasses.replace(case, kept="full"))
    assert outs[0] == (hq, tqp, case.d) and len(outs) == 2
    assert to_lanes == ["magi_bwd_delta", "magi_layout"]
