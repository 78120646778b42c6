"""The head-batched backward (ISSUE 25) against the per-head body and the
jnp oracle, on concrete and on traced, padded tables, CPU interpret mode.
Cases come from ``kernel_cases.run`` (mask ``four_docs``: four documents,
one of each mask type, none aligned to the 64-token blocks; rows 300..384
attend to nothing, so q block 5 has no entry at all)."""

import numpy as np
import pytest

from magiattention_tpu.testing import assert_close

from .kernel_cases import KernelCase, run

GRADS = ["dq", "dk", "dv", "dsink"]


def _four_docs(group, head_block, softcap, traced, grid, pad=0):
    """A loss that reads out AND lse (a non-zero lse cotangent) through the
    Pallas kernels at ``head_block`` on ``grid``, 2 kv heads. ``traced``:
    the tables are jit arguments and the grid extents come from
    ``FlexAttnParams.fwd_steps`` / ``bwd_steps``, as on the keyed path."""
    return KernelCase(
        "four_docs", hq=2 * group, hk=2, d=32, head_block=head_block,
        grid=grid, softcap=softcap, traced=traced, pad=pad, seed=11,
    )


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("traced", [False, True], ids=["concrete", "traced"])
@pytest.mark.parametrize("softcap", [0.0, 8.0], ids=["nocap", "softcap"])
@pytest.mark.parametrize("heads", [1, 2], ids=["hb=g", "hb=2g"])
@pytest.mark.parametrize("group", [1, 4, 8])
def test_head_batched_bwd_matches_per_head_and_oracle(
    group, heads, softcap, traced, grid
):
    """dq, dk, dv, dsink of the head-batched backward body, on the
    row-major and on the compact grid, against the per-head body and
    against the jnp oracle."""
    got, oracle, _ = run(_four_docs(group, heads * group, softcap, traced, grid))
    per_head = run(_four_docs(group, 1, softcap, traced, grid)).got
    for nm in GRADS:
        assert np.isfinite(got[nm]).all(), nm
        assert_close(got[nm], per_head[nm], atol=2e-5, rtol=2e-5, msg=f"{nm} vs per-head")
        assert_close(got[nm], oracle[nm], atol=5e-5, rtol=5e-5, msg=f"{nm} vs oracle")
    # rows 300.. attend to nothing: their dq is exactly zero
    assert not got["dq"][:, 300:].any()


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("group,heads", [(1, 2), (4, 1), (8, 1)])
def test_head_batched_kernels_on_per_rank_padded_tables(group, heads, grid):
    """Tables padded as the ranks' tables are stacked (``pad_block_meta``:
    sentinel-slice entries levelled over the blocks): a padded entry is a
    live step of the compact grid, and its empty mask adds nothing."""
    got, oracle, _ = run(_four_docs(group, heads * group, 0.0, True, grid, pad=13))
    unpadded = run(_four_docs(group, heads * group, 0.0, True, grid)).got
    for nm in GRADS:
        assert_close(got[nm], unpadded[nm], atol=2e-6, rtol=2e-6, msg=f"{nm} vs unpadded")
        assert_close(got[nm], oracle[nm], atol=5e-5, rtol=5e-5, msg=f"{nm} vs oracle")
