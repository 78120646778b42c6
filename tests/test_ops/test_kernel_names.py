"""Every flex kernel carries its role's name (ISSUE 24): the lowered
text of a forward+backward holds the three names, whatever the grid
kind and the head block. (The scopes and instruction names of the
compiled TPU program: tests/test_aot_compile_tpu.py.)"""

import re

import jax
import jax.numpy as jnp
import pytest

from magiattention_tpu.ops import flex_flash_attn_func
from magiattention_tpu.testing.workloads import ranges_of, varlen_block_causal

NAMES = {"magi_flex_fwd_kernel", "magi_flex_dq_kernel", "magi_flex_dkv_kernel"}


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("head_block", [1, 2])
def test_lowered_fwd_bwd_holds_the_three_kernel_names(grid, head_block):
    t, hq, hk, d = 512, 4, 2, 64
    qr, kr, ts = ranges_of(varlen_block_causal(t))

    def loss(q, k, v):
        out, lse = flex_flash_attn_func(
            q, k, v, qr, kr, ts, grid=grid, head_block=head_block,
            block_q=128, block_k=128, interpret=True,
        )
        return out.astype(jnp.float32).sum() + lse.sum()

    q = jnp.ones((t, hq, d), jnp.float32)
    kv = jnp.ones((t, hk, d), jnp.float32)
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv)
    text = lowered.as_text(debug_info=True)
    assert set(re.findall(r"magi_flex_\w+_kernel", text)) == NAMES
    # all of them match the roofline metrics' kernel pattern
    assert all(re.fullmatch(r"magi_\w*kernel", name) for name in NAMES)
