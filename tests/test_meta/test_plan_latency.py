"""Cold-plan scaling guard.

Planning is host-side and runs once per unique mask; its cost bounds
how often masks can change mid-training. What keeps a 1M-token cp=32
plan at seconds (vectorized run compression, native entry emission,
interval arithmetic for contiguous ownership) is that no Python-level
work is done per token. That is what is asserted here, on a count a
loaded machine cannot move: the Python and C calls the 1M-token plan
makes beyond the 256k-token plan's, at the same cp and chunks a rank.
The host-clock cost of planning is a benchmark metric (``key_build_ms``,
``attn_plan_build_ms``).
"""

import cProfile
import pstats

from magiattention_tpu.common.enum import AttnMaskType
from magiattention_tpu.common.ranges import AttnRanges
from magiattention_tpu.meta.dispatch_meta import (
    make_dispatch_meta_from_qk_ranges,
)
from magiattention_tpu.parallel.dist_attn import build_dist_attn_plan

SMALL, LARGE, CP = 1 << 18, 1 << 20, 32
# a scan of the added tokens, even one call per 8 of them, is over this
MAX_ADDED_CALLS = (LARGE - SMALL) // 8


def _calls(fn, *args):
    """(result, calls made under the profiler) of ``fn(*args)``."""
    prof = cProfile.Profile()
    result = prof.runcall(fn, *args)
    return result, pstats.Stats(prof).total_calls


def _dense_plan(total):
    qr = AttnRanges.from_ranges([(0, total)])
    mq, _, bucket = make_dispatch_meta_from_qk_ranges(
        qr, qr.clone(), [AttnMaskType.CAUSAL], total, total,
        total // (8 * CP), CP,
    )
    return build_dist_attn_plan(mq, bucket, block_q=512, block_k=2048)


def _qo_plan(total):
    import numpy as np

    from magiattention_tpu.parallel.qo_comm import build_qo_comm_plan

    sl = np.asarray([(0, total, 0, total, 1)], np.int64)
    return build_qo_comm_plan(sl, total, CP, block_q=512, block_k=2048)


def _check_no_token_scan(build, area_of):
    small, calls_small = _calls(build, SMALL)
    large, calls_large = _calls(build, LARGE)
    assert area_of(small) == SMALL * (SMALL + 1) // 2
    assert area_of(large) == LARGE * (LARGE + 1) // 2
    assert calls_large - calls_small < MAX_ADDED_CALLS, (
        f"the 1M-token plan made {calls_large} calls, the 256k one "
        f"{calls_small}: planning scans tokens in Python again"
    )


def test_dense_1m_plan_under_bound():
    _check_no_token_scan(_dense_plan, lambda plan: plan.total_area)


def test_qo_plan_1m_under_bound():
    """qo-comm planning at MTP scale (1M tokens, cp=32): the dynamic
    plane partition + send-map build must not grow with the tokens
    (contiguous ownership uses interval arithmetic, no row
    materialization)."""
    _check_no_token_scan(_qo_plan, lambda plan: sum(plan.rank_areas))
