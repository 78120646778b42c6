"""MAGI_ATTENTION_KERNEL_BACKEND=jnp: the reference-backend switch through
the distributed runtime (reference SDPA backend, functional/dist_attn.py:1215
+ the sdpa-fp64 pipeline variants of tests/test_pipeline.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from magiattention_tpu.common.ranges import AttnRanges
from magiattention_tpu.meta.dispatch_meta import make_dispatch_meta_from_qk_ranges
from magiattention_tpu.meta.solver.dispatch_solver import (
    DispatchConfig,
    MinHeapDispatchAlg,
)
from magiattention_tpu.parallel.dist_attn import (
    build_dist_attn_plan,
    make_attn_params,
    make_dist_attn_fn,
)
from magiattention_tpu.parallel.dispatch import dispatch, undispatch
from magiattention_tpu.testing import assert_close, ref_attn_from_ranges


def _mesh(cp):
    return Mesh(np.array(jax.devices()[:cp]), ("cp",))


SCENARIOS = [
    ("causal", 512, [(0, 512)], [(0, 512)], [1]),
    (
        "varlen_mixed",
        768,
        [(0, 256), (256, 640), (640, 768)],
        [(0, 256), (0, 640), (256, 768)],
        [1, 1, 0],
    ),
]


def _pipeline(total, qr, kr, ts, cp, dtype, out_dtype):
    hq, hk, d = 4, 2, 32
    chunk = total // (4 * cp)
    mesh = _mesh(cp)
    mq, _, bucket = make_dispatch_meta_from_qk_ranges(
        AttnRanges.from_ranges(qr), AttnRanges.from_ranges(kr), ts,
        total, total, chunk_size=chunk, cp_size=cp,
        dispatch_config=DispatchConfig(alg=MinHeapDispatchAlg()),
    )
    plan = build_dist_attn_plan(mq, bucket, block_q=64, block_k=64)
    params = make_attn_params(plan, d, out_dtype=out_dtype)
    attn_fn = make_dist_attn_fn(plan, mesh, params)
    shard = NamedSharding(mesh, P("cp"))

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((total, hq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((total, hk, d)), dtype)
    v = jnp.asarray(rng.standard_normal((total, hk, d)), dtype)

    def full_fwd(q, k, v):
        qd = jax.lax.with_sharding_constraint(dispatch(q, mq), shard)
        kd = jax.lax.with_sharding_constraint(dispatch(k, mq), shard)
        vd = jax.lax.with_sharding_constraint(dispatch(v, mq), shard)
        out_d, lse_d = attn_fn(qd, kd, vd)
        return undispatch(out_d, mq), undispatch(lse_d, mq)

    out, lse = jax.jit(full_fwd)(q, k, v)

    def loss(q, k, v):
        o, l_ = full_fwd(q, k, v)
        finite = ~jnp.isneginf(l_)
        return (o.astype(jnp.float32) ** 2).sum() + (
            jnp.where(finite, l_, 0.0).astype(jnp.float32) ** 2
        ).sum()

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    return q, k, v, out, lse, g


# ISSUE 7 budget re-tier: resurrected in CI; heaviest params are
# slow-tier to keep tier-1 inside its 870s budget (docs/testing.md)
@pytest.mark.parametrize(
    "backend",
    ["jnp", pytest.param("jnp_online", marks=pytest.mark.slow)],
)
@pytest.mark.parametrize(
    "name,total,qr,kr,ts", SCENARIOS, ids=[s[0] for s in SCENARIOS]
)
@pytest.mark.parametrize("cp", [1, 4])
def test_jnp_backend_matches_pallas(
    name, total, qr, kr, ts, cp, backend, monkeypatch
):
    q, k, v, out_p, lse_p, g_p = _pipeline(
        total, qr, kr, ts, cp, jnp.float32, "float32"
    )
    monkeypatch.setenv("MAGI_ATTENTION_KERNEL_BACKEND", backend)
    _, _, _, out_j, lse_j, g_j = _pipeline(
        total, qr, kr, ts, cp, jnp.float32, "float32"
    )
    assert_close(out_j, out_p, atol=2e-5, rtol=2e-5, msg=f"{name} out")
    np.testing.assert_array_equal(
        np.isneginf(np.asarray(lse_j)), np.isneginf(np.asarray(lse_p))
    )
    fin = ~np.isneginf(np.asarray(lse_p))
    assert_close(
        np.asarray(lse_j)[fin], np.asarray(lse_p)[fin], atol=2e-5, rtol=2e-5
    )
    for gj, gp, nm in zip(g_j, g_p, "qkv"):
        assert_close(gj, gp, atol=5e-5, rtol=5e-5, msg=f"{name} d{nm}")


@pytest.mark.parametrize("backend", ["jnp", "jnp_online"])
def test_jnp_backend_fp64_pipeline(backend, monkeypatch):
    """fp64 end-to-end through the distributed path (reference
    sdpa_varlen_* fp64 scenarios; sdpa_online.py for the online variant):
    the jnp backends carry float64 where the Pallas kernel cannot, giving
    a high-precision distributed oracle — the online one at O(tq*block_k)
    live scores, for long-seqlen precision debugging."""
    monkeypatch.setenv("MAGI_ATTENTION_KERNEL_BACKEND", backend)
    total, cp = 512, 4
    qr, kr, ts = [(0, 512)], [(0, 512)], [1]
    q, k, v, out, lse, _ = _pipeline(
        total, qr, kr, ts, cp, jnp.float64, "float64"
    )
    assert out.dtype == jnp.float64
    ref_out, ref_lse, _ = ref_attn_from_ranges(
        q, k, v, qr, kr, ts, compute_dtype=jnp.float64
    )
    assert_close(out, ref_out, atol=1e-12, rtol=1e-12)
    fin = ~np.isneginf(np.asarray(ref_lse))
    assert_close(
        np.asarray(lse)[fin], np.asarray(ref_lse)[fin], atol=1e-12, rtol=1e-12
    )


def test_online_backend_uncovered_rows_and_sink(monkeypatch):
    """Direct headmajor check of the online backend's edge semantics:
    uncovered q rows give out=0 / lse=-inf without a sink and lse=sink
    with one — identical to the dense jnp and Pallas epilogues."""
    from magiattention_tpu.ops.block_meta import Run, build_block_meta_general
    from magiattention_tpu.ops.flex_attn import (
        FlexAttnParams,
        bwd_tables,
        flex_attn_headmajor,
        fwd_tables,
    )

    total, hq, d, blk = 256, 2, 32, 64
    # rows [128, 192) covered by nothing
    slices = np.asarray(
        [(0, 128, 0, 128, 1), (192, 256, 0, 256, 0)], np.int64
    )
    runs = [Run(local_start=0, global_start=0, length=total)]
    meta = build_block_meta_general(
        slices, runs, runs, total, total, block_q=blk, block_k=blk
    )
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((hq, total, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((hq, total, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((hq, total, d)), jnp.float32)

    for has_sink, sink in ((False, None), (True, jnp.asarray([0.3, -0.2]))):
        params = FlexAttnParams(
            block_q=blk, block_k=blk, scale=1.0 / np.sqrt(d), softcap=0.0,
            has_sink=has_sink, out_dtype="float32", interpret=True,
        )
        results = {}
        for backend in ("jnp", "jnp_online"):
            monkeypatch.setenv("MAGI_ATTENTION_KERNEL_BACKEND", backend)
            out, lse_h, rowmax = flex_attn_headmajor(
                q, k, v, fwd_tables(meta), bwd_tables(meta), params,
                sink=sink,
            )
            assert lse_h.shape == rowmax.shape == (hq, total)
            results[backend] = (out, lse_h)
        out_d, lse_d = results["jnp"]
        out_o, lse_o = results["jnp_online"]
        assert_close(out_o, out_d, atol=2e-6, rtol=2e-6)
        assert_close(lse_o, lse_d, atol=2e-6, rtol=2e-6)
        dead = np.asarray(out_o)[:, 128:192]
        np.testing.assert_array_equal(dead, 0.0)
        lse_dead = np.asarray(lse_o)[:, 128:192]
        if has_sink:
            np.testing.assert_allclose(
                lse_dead,
                np.broadcast_to(np.asarray(sink)[:, None], lse_dead.shape),
                rtol=1e-6,
            )
        else:
            assert np.all(np.isneginf(lse_dead))
