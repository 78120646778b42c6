"""O(N/P) distributed roll: correctness vs the gather path + HLO lowering.

The reference keeps roll P2P (batch_isend_irecv, functional/roll.py:448)
so MTP label shifting never all-gathers the sequence; here the shard_map
path (local gather + padded a2a of rank-crossing rows) must (a) agree
with the global-gather roll everywhere, and (b) compile with no
all-gather and only shard-sized buffers. Full-scale (1M/cp=32) evidence:
exps/run_roll_proof.py.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from magiattention_tpu.common.enum import AttnMaskType
from magiattention_tpu.common.ranges import AttnRanges
from magiattention_tpu.meta.dispatch_meta import (
    make_dispatch_meta_from_qk_ranges,
)
from magiattention_tpu.meta.solver.dispatch_solver import DispatchConfig
from magiattention_tpu.parallel.dispatch import dispatch, roll, undispatch

CP, CHUNK = 8, 32


def _meta(total, uneven=False):
    qr = AttnRanges.from_ranges([(0, total)])
    cfg = DispatchConfig(uneven_shard=True) if uneven else None
    meta, _, _ = make_dispatch_meta_from_qk_ranges(
        qr, qr.clone(), [AttnMaskType.CAUSAL], total, total, CHUNK, CP, cfg
    )
    return meta


def _mesh():
    return Mesh(np.array(jax.devices()[:CP]).reshape(CP), ("cp",))


@pytest.mark.parametrize("shift", [-8, -1, 0, 1, 5, 31, 32, 100, -512])
def test_p2p_matches_gather_and_global_roll(shift):
    total = 1024
    meta, mesh = _meta(total), _mesh()
    xd = dispatch(jnp.arange(total, dtype=jnp.float32), meta)
    ref = np.asarray(roll(xd, meta, shift))
    got = np.asarray(roll(xd, meta, shift, mesh=mesh, cp_axis="cp"))
    np.testing.assert_array_equal(got, ref)
    und = np.asarray(undispatch(jnp.asarray(got), meta))
    np.testing.assert_array_equal(und, np.roll(np.arange(total), shift))


def test_p2p_batched_axis1_and_hier_axis_pair():
    total = 1024
    meta, mesh = _meta(total), _mesh()
    xd = dispatch(jnp.arange(total, dtype=jnp.float32), meta)
    xb = jnp.stack([xd, xd * 2])
    for shift in (-1, 7):
        for ax in (1, -1):  # negative axis must normalize, not mis-shard
            np.testing.assert_array_equal(
                np.asarray(
                    roll(xb, meta, shift, axis=ax, mesh=mesh, cp_axis="cp")
                ),
                np.asarray(roll(xb, meta, shift, axis=ax)),
            )
    mesh2 = Mesh(np.array(jax.devices()[:CP]).reshape(2, 4), ("cpo", "cpi"))
    for shift in (-1, 9):
        np.testing.assert_array_equal(
            np.asarray(
                roll(xd, meta, shift, mesh=mesh2, cp_axis=("cpo", "cpi"))
            ),
            np.asarray(roll(xd, meta, shift)),
        )


def test_p2p_uneven_shard_pads_keep_value():
    total = 1024 - 64  # 30 chunks over 8 ranks -> trailing pad slots
    meta, mesh = _meta(total, uneven=True), _mesh()
    xd = dispatch(jnp.arange(total, dtype=jnp.float32), meta, pad_value=-1)
    for shift in (-3, 1, 64):
        ref = np.asarray(roll(xd, meta, shift))
        got = np.asarray(roll(xd, meta, shift, mesh=mesh, cp_axis="cp"))
        np.testing.assert_array_equal(got, ref, err_msg=f"shift={shift}")


def test_p2p_lowering_has_no_all_gather():
    """Compiled HLO: zero all-gathers, buffers bounded by the shard."""
    total, hidden = 4096, 4
    meta, mesh = _meta(total), _mesh()
    sh = NamedSharding(mesh, P("cp"))
    x = jax.ShapeDtypeStruct((total, hidden), jnp.bfloat16, sharding=sh)
    fn = jax.jit(
        lambda x: roll(x, meta, -1, mesh=mesh, cp_axis="cp"),
        in_shardings=sh,
        out_shardings=sh,
    )
    txt = fn.lower(x).compile().as_text()
    assert " all-gather" not in txt
    sizes = [
        int(s) for s in re.findall(rf"(?:bf16|f32)\[(\d+),{hidden}\]", txt)
    ]
    assert sizes and max(sizes) <= 2 * meta.shard_seqlen, sizes


def test_api_roll_routes_p2p():
    """api.roll (key-based) rides the P2P path: its jaxpr/HLO has no
    all-gather either, and values still match the pure-gather roll."""
    from magiattention_tpu.api import magi_attn_flex_key, roll as api_roll
    from magiattention_tpu.api.interface import get_runtime_mgr

    total = 1024
    mesh = _mesh()
    key = magi_attn_flex_key(
        [(0, total)], [(0, total)], [1], total, total, mesh,
        chunk_size=CHUNK, cp_axis="cp", num_heads=(2, 2), head_dim=16,
    )
    meta = get_runtime_mgr(key).dispatch_meta
    xd = dispatch(jnp.arange(total, dtype=jnp.float32), meta)
    got = np.asarray(api_roll(xd, key, -1))
    np.testing.assert_array_equal(got, np.asarray(roll(xd, meta, -1)))
    sh = NamedSharding(mesh, P("cp"))
    x = jax.ShapeDtypeStruct((total,), jnp.float32, sharding=sh)
    txt = (
        jax.jit(lambda x: api_roll(x, key, -1), in_shardings=sh,
                out_shardings=sh)
        .lower(x)
        .compile()
        .as_text()
    )
    assert " all-gather" not in txt


def test_p2p_preserves_other_axis_sharding():
    """Partial-manual shard_map: a hidden dim sharded over another mesh
    axis (tp) must pass through the roll untouched — not be forced
    replicated (memory blow-up) or stripped (silent reshard)."""
    total = 1024
    qr = AttnRanges.from_ranges([(0, total)])
    meta, _, _ = make_dispatch_meta_from_qk_ranges(
        qr, qr.clone(), [AttnMaskType.CAUSAL], total, total, CHUNK, 4
    )
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("cp", "tp"))
    sh = NamedSharding(mesh, P("cp", "tp"))
    x = jax.device_put(
        jnp.arange(total * 8, dtype=jnp.float32).reshape(total, 8), sh
    )
    y = roll(x, meta, -1, mesh=mesh, cp_axis="cp")
    np.testing.assert_array_equal(np.asarray(y), np.asarray(roll(x, meta, -1)))
    assert y.sharding.spec == P("cp", "tp"), y.sharding
