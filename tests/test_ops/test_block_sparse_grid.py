"""Sparse-grid kernel parity oracle + the shared block-enumeration
primitive (ISSUE 15).

Three-way parity on random heterogeneous masks — the compact sparse
grid == the row-major grid == the dense reference — for fwd
out/lse/max-logits AND grads, on both kernel backends
(pallas-interpret and the jnp dense reference). Plus:

- ``BlockEnumeration`` (flex entry tables and decode block tables walk
  through ONE primitive).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from magiattention_tpu.ops import (
    BlockEnumeration,
    flex_flash_attn_func,
)
from magiattention_tpu.testing import assert_close, ref_attn_from_ranges


def _rand_qkv(tq, tk, hq, hk, d, seed, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return (
        jnp.asarray(rng.standard_normal((tq, hq, d)), dtype),
        jnp.asarray(rng.standard_normal((tk, hk, d)), dtype),
        jnp.asarray(rng.standard_normal((tk, hk, d)), dtype),
    )


def _varlen_causal(total, n_docs, seed):
    """Docs of random length, each causal over itself (+ a dead gap)."""
    rng = np.random.default_rng(seed)
    cuts = np.sort(
        rng.choice(np.arange(1, total // 8), n_docs - 1, replace=False)
    ) * 8
    bounds = [0, *[int(c) for c in cuts], total]
    sl = [(a, b, a, b, 1) for a, b in zip(bounds, bounds[1:])]
    return sl[:-1] + [sl[-1]]  # keep shape; gaps come from block pads


def _block_causal(total, n_docs, seed):
    """Varlen block-causal: each doc attends FULL to its whole prefix."""
    rng = np.random.default_rng(seed)
    cuts = np.sort(
        rng.choice(np.arange(1, total // 8), n_docs - 1, replace=False)
    ) * 8
    bounds = [0, *[int(c) for c in cuts], total]
    return [(a, b, 0, b, 0) for a, b in zip(bounds, bounds[1:])]


def _swa_causal(total, window):
    """Sliding-window causal: bicausal band slices."""
    return [(0, total, 0, total, 3)] if window >= total else [
        (i, min(i + window, total), max(i - window, 0), min(i + window, total), 1)
        for i in range(0, total, window)
    ]


_MASKS = {
    "varlen_causal": lambda: _varlen_causal(512, 5, 3),
    "block_causal": lambda: _block_causal(512, 4, 9),
    "swa_causal": lambda: _swa_causal(512, 128),
}


def _split(slices):
    qr = [(a, b) for a, b, *_ in slices]
    kr = [(s[2], s[3]) for s in slices]
    ts = [s[4] for s in slices]
    return qr, kr, ts


@pytest.mark.parametrize("mask", sorted(_MASKS))
@pytest.mark.parametrize("hq,hk", [(4, 4), (4, 2)])
def test_sparse_grid_matches_row_major_and_oracle(mask, hq, hk):
    """fwd out/lse: sparse grid == row-major grid == dense reference."""
    qr, kr, ts = _split(_MASKS[mask]())
    q, k, v = _rand_qkv(512, 512, hq, hk, 64, seed=hash(mask) % 100)
    outs = {}
    for grid in ("row_major", "sparse"):
        outs[grid] = flex_flash_attn_func(
            q, k, v, qr, kr, ts, block_q=64, block_k=128, grid=grid
        )[:2]
    ref_out, ref_lse, _ = ref_attn_from_ranges(q, k, v, qr, kr, ts)
    for grid, (out, lse) in outs.items():
        assert_close(
            out, ref_out, atol=3e-5, rtol=3e-5, msg=f"{mask} {grid} out"
        )
        fin = ~np.isneginf(np.asarray(ref_lse))
        assert_close(
            np.asarray(lse)[fin],
            np.asarray(ref_lse)[fin],
            atol=3e-5,
            rtol=3e-5,
            msg=f"{mask} {grid} lse",
        )
        # uncovered rows keep the (0, -inf) convention on both grids
        assert np.all(np.isneginf(np.asarray(lse)[~fin]))
        assert np.all(np.asarray(out)[~fin] == 0.0)


@pytest.mark.parametrize("mask", ["varlen_causal", "block_causal"])
def test_sparse_grid_grads_match_oracle(mask):
    """grad parity through the sparse grid's custom vjp (dq, dk, dv)."""
    qr, kr, ts = _split(_MASKS[mask]())
    q, k, v = _rand_qkv(512, 512, 4, 2, 64, seed=11)
    do = jnp.asarray(
        np.random.default_rng(5).standard_normal(q.shape), jnp.float32
    )

    def loss(fn):
        def f(q_, k_, v_):
            return (fn(q_, k_, v_) * do).sum()

        return jax.grad(f, argnums=(0, 1, 2))

    gs = loss(
        lambda q_, k_, v_: flex_flash_attn_func(
            q_, k_, v_, qr, kr, ts, block_q=64, block_k=128, grid="sparse"
        )[0]
    )(q, k, v)
    gr = loss(
        lambda q_, k_, v_: ref_attn_from_ranges(q_, k_, v_, qr, kr, ts)[0]
    )(q, k, v)
    for got, want, name in zip(gs, gr, ("dq", "dk", "dv")):
        assert_close(
            got, want, atol=2e-4, rtol=2e-4, msg=f"{mask} sparse {name}"
        )


def test_sparse_grid_sink_softcap_gqa_max_logits():
    """Feature product on the sparse grid: sink x softcap x GQA x
    head-batched, incl. the exact (non-quantized) max-logit output."""
    qr, kr, ts = _split(_block_causal(384, 3, 2))
    hq, hk = 8, 4
    q, k, v = _rand_qkv(384, 384, hq, hk, 64, seed=21)
    sink = jnp.asarray(
        np.random.default_rng(3).standard_normal(hq), jnp.float32
    )
    ref = ref_attn_from_ranges(q, k, v, qr, kr, ts, softcap=9.0, sink=sink)
    for hb in (1, 2, 8):
        out, lse, ml = flex_flash_attn_func(
            q, k, v, qr, kr, ts,
            block_q=64, block_k=64, grid="sparse", head_block=hb,
            softcap=9.0, sink=sink, return_max_logits=True,
        )
        assert_close(out, ref[0], atol=3e-5, rtol=3e-5, msg=f"hb={hb} out")
        fin = ~np.isneginf(np.asarray(ref[1]))
        assert_close(
            np.asarray(lse)[fin], np.asarray(ref[1])[fin],
            atol=3e-5, rtol=3e-5, msg=f"hb={hb} lse",
        )
        if ref[2] is not None:
            # max logits must be EXACT (the natural-scale running max)
            assert_close(ml, ref[2], atol=1e-6, rtol=1e-6, msg=f"hb={hb}")


def test_sparse_grid_jnp_backend_parity(monkeypatch):
    """The jnp reference backend consumes the same tables regardless of
    grid — pallas-sparse output must match it (the 'both backends' leg
    of the parity oracle)."""
    qr, kr, ts = _split(_varlen_causal(512, 4, 7))
    q, k, v = _rand_qkv(512, 512, 4, 4, 64, seed=13)
    sparse = flex_flash_attn_func(
        q, k, v, qr, kr, ts, block_q=64, block_k=128, grid="sparse"
    )[0]
    monkeypatch.setenv("MAGI_ATTENTION_KERNEL_BACKEND", "jnp")
    dense = flex_flash_attn_func(
        q, k, v, qr, kr, ts, block_q=64, block_k=128, grid="sparse"
    )[0]
    assert_close(sparse, dense, atol=3e-5, rtol=3e-5, msg="pallas vs jnp")


def test_sparse_grid_bitwise_deterministic():
    """No atomics anywhere: identical sparse-grid calls bit-match."""
    qr, kr, ts = _split(_block_causal(256, 3, 1))
    q, k, v = _rand_qkv(256, 256, 4, 4, 64, seed=17)
    a = flex_flash_attn_func(
        q, k, v, qr, kr, ts, block_q=64, block_k=64, grid="sparse"
    )[0]
    b = flex_flash_attn_func(
        q, k, v, qr, kr, ts, block_q=64, block_k=64, grid="sparse"
    )[0]
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_grid_env_override(monkeypatch):
    """MAGI_ATTENTION_GRID pins the grid; bad values raise."""
    from magiattention_tpu import env

    monkeypatch.setenv("MAGI_ATTENTION_GRID", "sparse")
    assert env.grid_override() == "sparse"
    monkeypatch.setenv("MAGI_ATTENTION_GRID", "auto")
    assert env.grid_override() is None
    monkeypatch.setenv("MAGI_ATTENTION_GRID", "diagonal")
    with pytest.raises(ValueError, match="MAGI_ATTENTION_GRID"):
        env.grid_override()


def test_bad_grid_value_raises():
    q, k, v = _rand_qkv(128, 128, 2, 2, 64, seed=0)
    with pytest.raises(ValueError, match="grid"):
        flex_flash_attn_func(
            q, k, v, [(0, 128)], [(0, 128)], [1],
            block_q=64, block_k=64, grid="diagonal",
        )


# ---------------------------------------------------------------------------
# the shared block-enumeration primitive
# ---------------------------------------------------------------------------


def test_enumeration_from_block_table_matches_flat_indexing():
    """The decode walk: clamped lookup over a block table == the direct
    ``b * mpp + s * pps + p`` flat indexing it replaced."""
    rng = np.random.default_rng(4)
    b, mpp, splits = 3, 8, 2
    bt = jnp.asarray(rng.integers(0, 100, (b, mpp)), jnp.int32)
    enum = BlockEnumeration.from_block_table(bt, splits)
    pps = mpp // splits
    flat = np.asarray(bt).reshape(-1)
    for b_ in range(b):
        for s_ in range(splits):
            for p_ in range(pps):
                e = enum.entry(b_ * splits + s_, p_)
                assert int(np.asarray(enum.minor)[int(e)]) == int(
                    flat[b_ * mpp + s_ * pps + p_]
                )


def test_enumeration_from_block_table_rejects_bad_splits():
    bt = jnp.zeros((2, 6), jnp.int32)
    with pytest.raises(ValueError, match="divisible"):
        BlockEnumeration.from_block_table(bt, 4)


def test_enumeration_clamps_past_row_end():
    enum = BlockEnumeration.from_sorted(
        np.array([0, 0, 2], np.int32), np.array([3, 5, 7], np.int32), 3
    )
    assert enum.num_rows == 3 and enum.num_entries == 3
    # step past the row count clamps to the last live entry
    assert int(enum.entry(0, 5)) == 1
    # empty rows have count 0 and clamp onto their (empty) start
    assert int(enum.row_count[1]) == 0


def test_row_major_pin_restricts_ranking_to_row_major_rungs():
    """Pinning grid="row_major" on a heterogeneous mask must NOT launch
    a sparse-only blocking on the static-steps grid: the ranking is
    restricted to row-major rungs, matching the row-major-only winner."""
    from magiattention_tpu.ops.flex_attn import (
        auto_block_config,
        auto_kernel_config,
    )
    from magiattention_tpu.testing.workloads import varlen_block_causal

    sl = varlen_block_causal(16384)
    qr = [(a, b) for a, b, *_ in sl]
    kr = [(s[2], s[3]) for s in sl]
    ts = [s[4] for s in sl]
    full = auto_kernel_config(qr, kr, 8, 8, attn_type_map=ts)
    assert full[3] == "sparse"  # the headline resolves sparse unpinned
    pinned = auto_kernel_config(
        qr, kr, 8, 8, attn_type_map=ts, grid="row_major"
    )
    assert pinned == (*auto_block_config(qr, kr, 8, 8, attn_type_map=ts),
                      "row_major")
    assert pinned[:2] != full[:2]  # the sparse-only blocking is excluded
