"""Pallas flex-flash-attention vs jnp oracle (fwd + bwd), CPU interpret mode.

Model: reference tests/test_attn/test_flex_flash_attn.py — kernel vs oracle
over a grid of mask scenarios × head configs × features. The public entry
point (``flex_flash_attn_func``) against the dense reference; the kernels'
own cases live beside it, by subject: ``test_flex_fwd_state.py`` (the
softmax state, the backward's P / dS block), ``test_flex_head_batched.py``,
``test_flex_bwd_fused.py``, ``test_flex_attn_boundary.py``, all on
``kernel_cases.run``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from magiattention_tpu.common import AttnMaskType
from magiattention_tpu.ops import build_block_meta, flex_flash_attn_func
from magiattention_tpu.ops.block_meta import SLICE_FIELDS
from magiattention_tpu.testing import assert_close, ref_attn_from_ranges

from .kernel_cases import MASKS, KernelCase, run

C = AttnMaskType.CAUSAL

# mask scenarios: (name, tq, tk, q_ranges, k_ranges, types), from the one
# table of masks
SCENARIOS = [
    (name, *MASKS[name]) for name in (
        "dense_full_256", "dense_causal_256", "unaligned_causal",
        "varlen_causal", "varlen_full", "mixed_types", "q_overlap",
        "uncovered_rows", "cross_attn_rect", "sliding_window_ish",
    )
]


def _rand_qkv(tq, tk, hq, hk, d, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((tq, hq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((tk, hk, d)), dtype)
    v = jnp.asarray(rng.standard_normal((tk, hk, d)), dtype)
    return q, k, v


@pytest.mark.parametrize("name,tq,tk,qr,kr,ts", SCENARIOS, ids=[s[0] for s in SCENARIOS])
@pytest.mark.parametrize("hq,hk", [(2, 2), (4, 2)])
def test_fwd_matches_oracle(name, tq, tk, qr, kr, ts, hq, hk):
    d = 128
    q, k, v = _rand_qkv(tq, tk, hq, hk, d)
    out, lse = flex_flash_attn_func(q, k, v, qr, kr, ts, block_q=64, block_k=64)
    ref_out, ref_lse, _ = ref_attn_from_ranges(q, k, v, qr, kr, ts)
    assert_close(out, ref_out, atol=2e-5, rtol=2e-5, msg=f"{name} out")
    # lse: compare only finite entries; -inf rows must agree exactly
    np.testing.assert_array_equal(
        np.isneginf(np.asarray(lse)), np.isneginf(np.asarray(ref_lse))
    )
    finite = ~np.isneginf(np.asarray(ref_lse))
    assert_close(
        np.asarray(lse)[finite], np.asarray(ref_lse)[finite], atol=2e-5, rtol=2e-5,
        msg=f"{name} lse",
    )


@pytest.mark.parametrize(
    "name",
    ["dense_causal_256", "unaligned_causal", "varlen_causal", "mixed_types",
     "q_overlap", "uncovered_rows"],
)
def test_bwd_matches_oracle(name):
    """dq, dk, dv of a loss on out alone (per head, row-major grid, no
    sink) against the jnp oracle on the same tables."""
    got, ref, _ = run(
        KernelCase(name, hq=4, hk=2, d=64, sink=False, use_lse=False, seed=1)
    )
    for nm in ("dq", "dk", "dv"):
        assert_close(got[nm], ref[nm], atol=5e-5, rtol=5e-5, msg=f"{name} {nm}")


def test_softcap_fwd_bwd():
    qr, kr, ts = [(0, 128)], [(0, 128)], [C]
    q, k, v = _rand_qkv(128, 128, 2, 2, 64, seed=3)
    do = jnp.asarray(np.random.default_rng(4).standard_normal((128, 2, 64)), jnp.float32)
    out, lse = flex_flash_attn_func(q, k, v, qr, kr, ts, softcap=30.0, block_q=64, block_k=64)
    ref_out, ref_lse, _ = ref_attn_from_ranges(q, k, v, qr, kr, ts, softcap=30.0)
    assert_close(out, ref_out, atol=2e-5, rtol=2e-5)

    g = jax.grad(
        lambda q, k, v: (
            flex_flash_attn_func(q, k, v, qr, kr, ts, softcap=30.0, block_q=64, block_k=64)[0] * do
        ).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    gr = jax.grad(
        lambda q, k, v: (
            ref_attn_from_ranges(q, k, v, qr, kr, ts, softcap=30.0)[0] * do
        ).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b, n in zip(g, gr, "qkv"):
        assert_close(a, b, atol=5e-5, rtol=5e-5, msg=f"softcap d{n}")


def test_sink_fwd_bwd():
    qr, kr, ts = [(0, 128)], [(0, 128)], [C]
    hq = 4
    q, k, v = _rand_qkv(128, 128, hq, 2, 64, seed=5)
    sink = jnp.asarray([0.5, -0.3, 1.2, 0.0], jnp.float32)
    out, lse = flex_flash_attn_func(q, k, v, qr, kr, ts, sink=sink, block_q=64, block_k=64)
    ref_out, ref_lse, _ = ref_attn_from_ranges(q, k, v, qr, kr, ts, sink=sink)
    assert_close(out, ref_out, atol=2e-5, rtol=2e-5)
    assert_close(lse, ref_lse, atol=2e-5, rtol=2e-5)

    do = jnp.asarray(np.random.default_rng(6).standard_normal((128, hq, 64)), jnp.float32)
    g = jax.grad(
        lambda q, k, v, s: (
            flex_flash_attn_func(q, k, v, qr, kr, ts, sink=s, block_q=64, block_k=64)[0] * do
        ).sum(),
        argnums=(0, 1, 2, 3),
    )(q, k, v, sink)
    gr = jax.grad(
        lambda q, k, v, s: (
            ref_attn_from_ranges(q, k, v, qr, kr, ts, sink=s)[0] * do
        ).sum(),
        argnums=(0, 1, 2, 3),
    )(q, k, v, sink)
    for a, b, n in zip(g, gr, ["dq", "dk", "dv", "dsink"]):
        assert_close(a, b, atol=5e-5, rtol=5e-5, msg=f"sink {n}")


def test_max_logits():
    qr, kr, ts = [(0, 128)], [(0, 128)], [C]
    q, k, v = _rand_qkv(128, 128, 2, 2, 64, seed=7)
    out, lse, ml = flex_flash_attn_func(
        q, k, v, qr, kr, ts, return_max_logits=True, block_q=64, block_k=64
    )
    _, _, ref_ml = ref_attn_from_ranges(q, k, v, qr, kr, ts)
    assert_close(ml, ref_ml, atol=2e-5, rtol=2e-5)


def test_block_meta_tables():
    meta = build_block_meta([(0, 256)], [(0, 256)], [C.value], 256, 256, block_q=64, block_k=64)
    # causal 4x4 blocks → lower-triangular block pattern: 4+3+2+1 = 10 entries
    assert meta.num_fwd_entries >= 10
    real = meta.fwd_slice_id < meta.num_slices
    assert int(real.sum()) == 10
    # every q block covered, monotone q-major order
    assert set(meta.fwd_q_block.tolist()) == {0, 1, 2, 3}
    assert (np.diff(meta.fwd_q_block) >= 0).all()
    assert (np.diff(meta.bwd_k_block) >= 0).all()
    assert meta.total_area == 256 * 257 // 2
    assert meta.slice_bounds.shape[0] == 2 * SLICE_FIELDS


def test_bf16_reasonable():
    qr, kr, ts = [(0, 256)], [(0, 256)], [C]
    q, k, v = _rand_qkv(256, 256, 2, 2, 64, seed=8)
    out16, _ = flex_flash_attn_func(
        q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v.astype(jnp.bfloat16),
        qr, kr, ts, block_q=64, block_k=64,
    )
    ref_out, _, _ = ref_attn_from_ranges(q, k, v, qr, kr, ts)
    assert_close(out16.astype(jnp.float32), ref_out, atol=3e-2, rtol=3e-2)


# the all-8 shape re-tiered slow for the 870s tier-1 budget (ISSUE 16);
# (8,2,4) keeps GQA head-batching live and (4,4,2) the partial block
@pytest.mark.parametrize(
    "hq,hk,hb",
    [pytest.param(8, 8, 8, marks=pytest.mark.slow), (8, 2, 4), (4, 4, 2)],
)
def test_head_batched_kernel(hq, hk, hb):
    """head_block>1 path (batched MXU calls) vs oracle, incl. bwd."""
    tq = 256
    d = 64
    q, k, v = _rand_qkv(tq, tq, hq, hk, d, seed=9)
    qr, kr, ts = [(0, 100), (100, 256)], [(0, 100), (100, 256)], [C, C]
    out, lse = flex_flash_attn_func(
        q, k, v, qr, kr, ts, block_q=64, block_k=64, head_block=hb
    )
    ref_out, ref_lse, _ = ref_attn_from_ranges(q, k, v, qr, kr, ts)
    assert_close(out, ref_out, atol=2e-5, rtol=2e-5, msg=f"hb{hb}")
    finite = ~np.isneginf(np.asarray(ref_lse))
    assert_close(
        np.asarray(lse)[finite], np.asarray(ref_lse)[finite],
        atol=2e-5, rtol=2e-5,
    )
    do = jnp.asarray(
        np.random.default_rng(10).standard_normal((tq, hq, d)), jnp.float32
    )
    g = jax.grad(
        lambda q, k, v: (
            flex_flash_attn_func(
                q, k, v, qr, kr, ts, block_q=64, block_k=64, head_block=hb
            )[0] * do
        ).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    gr = jax.grad(
        lambda q, k, v: (ref_attn_from_ranges(q, k, v, qr, kr, ts)[0] * do).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b, nm in zip(g, gr, "qkv"):
        assert_close(a, b, atol=5e-5, rtol=5e-5, msg=f"hb{hb} d{nm}")
