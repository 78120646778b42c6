"""Head batches at geometries that are no power of two: latent attention
hands the kernels GQA group 1 and 20 heads (GLM-4.7-Flash), where every
benchmark cell before it had group 4 or 8 and a power-of-two head count.
``_auto_head_block`` snaps the rung's preference to a divisor of the head
count that is a multiple of the group; ``_bwd_head_block`` keeps the
backward per head where the batched step's logit tiles pass the VMEM the
kernels ask for. Host only."""

import pytest

from magiattention_tpu.ops.flex_attn import (
    _AUTO_BLOCK_CONFIGS, _BWD_HB_LIVE_BYTES, FlexAttnParams,
    _auto_head_block, _bwd_head_block, _check_head_block,
)


@pytest.mark.parametrize(
    "pref,hq,group,want",
    [
        (8, 20, 1, 5), (4, 20, 1, 4), (2, 20, 1, 2), (1, 20, 1, 1),
        (8, 20, 5, 5), (8, 20, 4, 4),
        (8, 20, 20, 20),  # no multiple of the group fits: the group itself
        (8, 7, 1, 7), (4, 7, 1, 1), (8, 12, 3, 6), (8, 64, 8, 8),
        (8, 32, 4, 8), (8, 32, 8, 8), (4, 6, 2, 2), (16, 20, 1, 10),
    ],
)
def test_auto_head_block_divides_the_heads_and_holds_whole_groups(
    pref, hq, group, want
):
    got = _auto_head_block(pref, hq, group)
    assert got == want
    assert hq % got == 0 and (got == 1 or got % group == 0)
    if got > 1:
        _check_head_block(got, hq, group)  # what the kernels accept


@pytest.mark.parametrize(
    "hq,group", [(20, 1), (16, 1), (20, 4), (12, 3), (64, 8), (32, 4)]
)
def test_every_rung_of_the_table_snaps_to_a_valid_head_batch(hq, group):
    for _bq, _bk, pref in _AUTO_BLOCK_CONFIGS:
        hb = _auto_head_block(pref, hq, group)
        assert 1 <= hb <= max(pref, group) and hq % hb == 0


def _params(bq, bk, hb):
    return FlexAttnParams(
        block_q=bq, block_k=bk, scale=1.0, softcap=0.0, has_sink=False,
        out_dtype="bfloat16", interpret=True, head_block=hb,
        fwd_steps=1, bwd_steps=1,
    )


@pytest.mark.parametrize(
    "rung,hq,group,want",
    [
        ((128, 512, 5), 20, 1, 5),     # the GLM cell's before ISSUE 35: 5 MiB
        ((256, 512, 5), 20, 1, 5),     # the GLM cell's: 10 MiB of logit tiles
        ((256, 512, 4), 20, 1, 4),     # 8 MiB
        ((128, 512, 8), 16, 1, 8),     # the Ouro cell's before ISSUE 35: 8 MiB
        ((256, 512, 8), 16, 1, 8),     # the Ouro cell's: 16 MiB
        ((512, 512, 4), 16, 1, 4),     # the probe's block_q-512 rung: 16 MiB
        ((256, 1024, 2), 20, 1, 2),    # 8 MiB
        ((512, 768, 4), 20, 1, 4),     # 24 MiB
        ((1024, 1024, 5), 20, 1, 1),   # 80 MiB: stays per head
        ((1024, 1024, 2), 20, 1, 2),   # 32 MiB: the limit itself fits
        ((128, 512, 1), 20, 1, 1),
    ],
)
def test_the_backwards_vmem_test_at_group_one(rung, hq, group, want):
    assert _bwd_head_block(_params(*rung), hq, group) == want
    live = 4 * 4 * rung[2] * rung[0] * rung[1]
    assert (live <= _BWD_HB_LIVE_BYTES) == (want == rung[2])


def test_a_head_batch_that_splits_a_group_is_refused():
    with pytest.raises(Exception):
        _bwd_head_block(_params(128, 512, 6), 20, 4)
    with pytest.raises(Exception):
        _bwd_head_block(_params(128, 512, 8), 20, 1)  # 8 does not divide 20
