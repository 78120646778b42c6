"""What ``auto_block_config`` chooses (the static ladder under the tuner)
and that its widest rung computes what the default blocking does. Moved
from ``tests/test_ops/test_flex_attn.py`` (ISSUE 45): they test the
tuner's choice, not a kernel."""

import jax.numpy as jnp
import numpy as np

from magiattention_tpu.common import AttnMaskType
from magiattention_tpu.ops import flex_flash_attn_func
from magiattention_tpu.testing import assert_close, ref_attn_from_ranges

C = AttnMaskType.CAUSAL


def test_large_block_escalation_config():
    """The (512, 2048) escalation rung (128k-dense smem fit) computes the
    same results as default blocking."""
    t, hq, hk, d = 4096, 2, 2, 32
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((t, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((t, hk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((t, hk, d)), jnp.float32)
    qr, kr, ts = [(0, t)], [(0, t)], [C]
    out, lse = flex_flash_attn_func(
        q, k, v, qr, kr, ts, block_q=512, block_k=2048, head_block=1
    )[:2]
    ref, ref_lse, _ = ref_attn_from_ranges(q, k, v, qr, kr, ts)
    assert_close(out, ref, atol=3e-5, rtol=3e-5)
    assert_close(lse, ref_lse, atol=3e-5, rtol=3e-5)


def test_auto_block_config_prefers_large_blocks_at_long_seq():
    """>= 16k tokens: the (1024, 1024) square rung is preferred (round-5
    chained on-chip winner for fwd AND fwd+bwd at 64k causal on the
    row-major grid); below 16k the low-latency (., 512) rungs stay first:
    of the pair (128, 512, 8) and (256, 512, 8) the cheaper by its own
    price (ISSUE 56: a dense mask's tiles are the same area in half the
    steps at 256), and at GQA group 1, where 128's steps stream K and V at
    the HBM's pace, 256 by the bytes (ISSUE 35); oversized masks still
    escalate to (512, 2048)."""
    from magiattention_tpu.ops.flex_attn import auto_block_config

    # short dense -> small rung
    assert auto_block_config([(0, 8192)], [(0, 8192)], 64, 8) == (256, 512, 8)
    assert auto_block_config([(0, 8192)], [(0, 8192)], 8, 8) == (256, 512, 8)
    # long dense causal -> measured winner
    assert auto_block_config([(0, 32768)], [(0, 32768)], 8, 8)[:2] == (
        1024,
        1024,
    )
    # 256k dense: only the k-wide escalation rung fits the entry budget
    assert auto_block_config([(0, 262144)], [(0, 262144)], 8, 8)[:2] == (
        512,
        2048,
    )
    # fixed blocks are always honored
    assert auto_block_config(
        [(0, 32768)], [(0, 32768)], 8, 8, fixed_block_q=128, fixed_block_k=512
    )[:2] == (128, 512)


def test_auto_block_config_fixed_blocks_keep_their_head_block():
    """Caller-fixed small blocks at long seqlen keep the hb measured for
    that blocking (8), not the long-seq rung's hb."""
    from magiattention_tpu.ops.flex_attn import auto_block_config

    assert auto_block_config(
        [(0, 32768)], [(0, 32768)], 8, 8,
        fixed_block_q=128, fixed_block_k=512,
    ) == (128, 512, 8)


def test_auto_block_config_partially_fixed_blocks_key_hb_on_block_k():
    """When only one block dimension is fixed, the mixed (bq, bk) pair is
    not a measured rung; head_block falls back to the hb measured for the
    effective block_k (the K/V double-buffer width the hb values are
    sized against)."""
    from magiattention_tpu.ops.flex_attn import auto_block_config

    # fixed small block_k at long seqlen: bq iterates to 1024 (square
    # rung first); (1024, 512) is unmeasured, so hb keys on block_k -> 4
    assert auto_block_config(
        [(0, 32768)], [(0, 32768)], 8, 8, fixed_block_k=512
    ) == (1024, 512, 4)
    # a mixed pair no rung measures (bq=512 fixed, bk=512): hb keys on
    # block_k alone -> 4, not the iterating wide rung's 2/1
    assert auto_block_config(
        [(0, 32768)], [(0, 32768)], 8, 8, fixed_block_q=512, fixed_block_k=512
    )[2] == 4
    # fixed small block_q at long seqlen: bk iterates to 1024; the
    # (128, 1024) pair is unmeasured, so hb keys on block_k -> the most
    # conservative measured hb for bk=1024 (min of 2 and 1 = 1)
    assert auto_block_config(
        [(0, 32768)], [(0, 32768)], 8, 8, fixed_block_q=128
    ) == (128, 1024, 1)


def test_auto_block_config_long_keys_short_queries():
    """Cross-attn mask: 4k queries over 128k keys is in the grid-bound
    regime and must use a wide rung."""
    from magiattention_tpu.ops.flex_attn import auto_block_config

    assert auto_block_config([(0, 4096)], [(0, 131072)], 8, 8)[:2] == (
        1024,
        1024,
    )
