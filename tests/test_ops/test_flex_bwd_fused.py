"""The fused backward (ISSUE 43): one kernel over the k-major table that
keeps dk / dv in VMEM and adds dq into a float32 buffer in HBM, a tile's
read started a step ahead. CPU, interpret mode, cases from
``kernel_cases.run``: dq, dk, dv and the sink's gradient against the jnp
oracle over mask type, GQA group, head block, head_dim and both grids, with
a non-zero lse cotangent and a sink. 120 cases, no two alike and none
shared with another test, are more than one worker's file may take
(docs/testing.md), so head_dim 128 is here and head_dim 256, the latent
form's width, in ``test_flex_bwd_fused_d256.py``, which makes its test
from this file's :func:`fused_backward_test`: the oracle's key holds the
head_dim, so the split cuts no sharing. The orderings the step keeps
and its DMA protocol replayed on the host: ``test_flex_bwd_protocol.py``;
where dq's result lives: ``test_flex_bwd_dq_form.py``."""

import pytest

from .kernel_cases import KernelCase, assert_grads


def fused_backward_test(head_dim):
    """``test_fused_backward_matches_the_reference`` at ``head_dim``: the
    one parametrisation both files' tests are made from."""

    @pytest.mark.parametrize("grid", ["row_major", "sparse"])
    @pytest.mark.parametrize("d", [head_dim])
    @pytest.mark.parametrize("heads", ["per-head", "batched"])
    @pytest.mark.parametrize("group", [1, 4, 8])
    @pytest.mark.parametrize(
        "mask", ["full", "causal", "invcausal", "bicausal", "stepped"]
    )
    def test_fused_backward_matches_the_reference(mask, group, heads, d, grid):
        """2 kv heads of ``group`` q heads each; the batched body takes a
        kv head's group a step (two kv heads at group 1)."""
        head_block = 1 if heads == "per-head" else max(group, 2)
        assert_grads(KernelCase(
            mask, hq=2 * group, hk=2, d=d, head_block=head_block, grid=grid
        ))

    return test_fused_backward_matches_the_reference


test_fused_backward_matches_the_reference = fused_backward_test(128)
