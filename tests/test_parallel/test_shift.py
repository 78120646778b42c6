"""The forward shift along a document (``parallel/dispatch.shift_local``
on ``make_shift_plan``): a token reads the rows 1 and 2 before it in
GLOBAL order wherever dispatch put them, zero before its document's
first token, against a dense shift matrix; forward and gradient; the
rank-crossing rows in one exchange, counted."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from magiattention_tpu import api, telemetry
from magiattention_tpu.common.enum import AttnMaskType
from magiattention_tpu.common.ranges import AttnRanges
from magiattention_tpu.meta.dispatch_meta import (
    make_dispatch_meta_from_qk_ranges,
)
from magiattention_tpu.meta.solver.dispatch_solver import DispatchConfig
from magiattention_tpu.parallel.dispatch import (
    dispatch, make_shift_plan, shift_local, shift_valid, undispatch,
)
from magiattention_tpu.utils.compat import shard_map

# documents that end off the chunk grid, one shorter than a tap is long
DOCS = [150, 1, 40, 2, 63]
TOTAL, CHUNK = sum(DOCS), 32
CU = [0, *np.cumsum(DOCS).tolist()]


def _meta(cp, uneven=False, total=TOTAL, cu=CU):
    ranges = AttnRanges.from_ranges(list(zip(cu, cu[1:])))
    cfg = DispatchConfig(uneven_shard=True) if uneven else None
    meta, _, _ = make_dispatch_meta_from_qk_ranges(
        ranges, ranges.clone(), [AttnMaskType.CAUSAL] * (len(cu) - 1),
        total, total, CHUNK, cp, cfg,
    )
    return meta


def _mesh(cp):
    return Mesh(np.array(jax.devices()[:cp]).reshape(cp), ("cp",))


def _dense(taps, cu=CU, total=TOTAL):
    """S_j [total, total]: S_j[p, p - j] = 1 inside p's document."""
    pos = np.arange(total)
    doc = np.searchsorted(np.asarray(cu[1:]), pos, side="right")
    out = []
    for j in taps:
        s = np.zeros((total, total), np.float32)
        ok = (pos - j >= 0) & (doc[np.maximum(pos - j, 0)] == doc)
        s[pos[ok], pos[ok] - j] = 1.0
        out.append(jnp.asarray(s))
    return out


def _shifted(x, meta, plan, mesh):
    """The shift through dispatch, shard_map and undispatch."""
    tables = plan.device_tables()

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P("cp"), (P("cp"),) * len(tables)),
        out_specs=((P("cp"),) * len(plan.taps), P(None, "cp")),
        check_vma=False,
    )
    def local(x_l, tabs):
        return (
            shift_local(x_l, tabs, plan, "cp"), shift_valid(tabs)
        )

    ys, valid = local(dispatch(x, meta), tables)
    return [undispatch(y, meta) for y in ys], undispatch(valid, meta, axis=1)


@pytest.mark.parametrize("cp", [1, 2, 4, 8])
@pytest.mark.parametrize("taps", [(1, 2), (1,), (3, 1)], ids=str)
def test_the_shift_is_the_dense_shift_matrix(cp, taps):
    meta, mesh = _meta(cp), _mesh(cp)
    plan = make_shift_plan(meta, CU, taps)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((TOTAL, 3, 5)), jnp.float32)
    weights = [
        jnp.asarray(rng.standard_normal((TOTAL, 3, 5)), jnp.float32)
        for _ in taps
    ]
    dense = _dense(taps)

    def got(x):
        return _shifted(x, meta, plan, mesh)[0]

    def want(x):
        return [jnp.einsum("pc,c...->p...", s, x) for s in dense]

    def loss(f):
        return lambda x: sum((y * w).sum() for y, w in zip(f(x), weights))

    with jax.enable_x64(False):
        ys, valid = jax.jit(lambda x: _shifted(x, meta, plan, mesh))(x)
        for y, w, s in zip(ys, want(x), dense):
            np.testing.assert_array_equal(np.asarray(y), np.asarray(w))
        np.testing.assert_array_equal(
            np.asarray(valid), np.stack([np.asarray(s).sum(1) > 0 for s in dense])
        )
        # the backward: the shift by -j with the same zeroing
        np.testing.assert_allclose(
            jax.jit(jax.grad(loss(got)))(x), jax.grad(loss(want))(x),
            rtol=0, atol=1e-6,
        )
    # cp = 1 reads nothing from another rank and slices; past it a chunk's
    # first rows do, a row once whatever the taps that read it
    assert (plan.remote_rows == 0) == (cp == 1)
    if cp == 1:
        assert plan.fwd.offsets == taps
        assert plan.bwd.offsets == tuple(-j for j in taps)
    else:
        assert plan.fwd.width > 0 and None in plan.fwd.offsets


def test_neighbouring_chunks_on_other_ranks_cost_a_row_a_tap():
    """At cp = 4 the dispatch puts neighbouring chunks on different
    ranks: every chunk edge inside a document hands over its last
    ``max(taps)`` rows, once."""
    meta = _meta(4)
    chunk_rank = np.empty(meta.num_chunks, int)
    for rank, chunks in enumerate(meta.partitions):
        chunk_rank[list(chunks)] = rank
    edges = np.arange(CHUNK, TOTAL, CHUNK)  # first rows of chunks 1..
    crossing = edges[chunk_rank[1:] != chunk_rank[:-1]]
    assert crossing.size >= 4
    start = np.asarray(CU)[np.searchsorted(CU, crossing, side="right") - 1]
    for taps in ((1,), (1, 2)):
        plan = make_shift_plan(meta, CU, taps)
        want = sum(int(min(max(taps), e - s)) for e, s in zip(crossing, start))
        assert plan.remote_rows == want > 0


def test_an_uneven_shard_and_one_document():
    n = 5 * CHUNK  # five chunks over four ranks
    cu = [0, n]
    meta, mesh = _meta(4, uneven=True, total=n, cu=cu), _mesh(4)
    assert meta.is_uneven
    plan = make_shift_plan(meta, cu, (1, 2))
    x = jnp.arange(1.0, n + 1.0, dtype=jnp.float32)[:, None]
    with jax.enable_x64(False):
        ys, _valid = _shifted(x, meta, plan, mesh)
    for j, y in zip((1, 2), ys):
        want = np.concatenate([np.zeros(j), np.arange(1.0, n + 1.0 - j)])
        np.testing.assert_array_equal(np.asarray(y)[:n, 0], want)


def test_the_counter_and_the_event():
    telemetry.set_enabled(True)
    reg = telemetry.get_registry()
    reg.clear_metric("magi_shift_remote_rows_total")
    try:
        local = make_shift_plan(_meta(1), CU, (1, 2))
        assert reg.counter_value("magi_shift_remote_rows_total") == 0.0
        far = make_shift_plan(_meta(4), CU, (1, 2))
        counted = reg.counter_value("magi_shift_remote_rows_total")
        events = [
            {k: e["args"][k] for k in ("rows", "taps", "documents")}
            for e in telemetry.get_event_buffer().events()
            if e["name"] == "shift"
        ][-2:]
    finally:
        reg.clear_metric("magi_shift_remote_rows_total")
        telemetry.set_enabled(None)
    assert local.remote_rows == 0 < far.remote_rows == counted
    assert events == [
        {"rows": 0, "taps": [1, 2], "documents": len(DOCS)},
        {"rows": far.remote_rows, "taps": [1, 2], "documents": len(DOCS)},
    ]


def test_what_a_plan_refuses():
    meta = _meta(2)
    with pytest.raises(ValueError, match="predecessors"):
        make_shift_plan(meta, CU, (0, 1))
    with pytest.raises(ValueError, match="predecessors"):
        make_shift_plan(meta, CU, ())
    with pytest.raises(ValueError, match="documents"):
        make_shift_plan(meta, [0, 100], (1,))
    with pytest.raises(ValueError, match="documents"):
        make_shift_plan(meta, [0, 100, 100, TOTAL], (1,))


def test_the_keyed_entry_reads_the_documents_from_the_key():
    mesh = _mesh(4)
    key = api.magi_attn_varlen_key(
        CU, TOTAL, mesh, num_heads=(2, 2), head_dim=16, chunk_size=CHUNK,
        out_dtype="float32",
    )
    plan = api.make_shift_plan(key, (1, 2))
    meta = api.get_runtime_mgr(key).dispatch_meta
    pad = meta.total_seqlen - TOTAL
    assert plan.taps == (1, 2)
    assert plan.documents == len(DOCS) and plan.remote_rows > 0
    x = jnp.asarray(
        np.random.default_rng(1).standard_normal((TOTAL, 4)), jnp.float32
    )
    with jax.enable_x64(False):
        ys, _valid = _shifted(jnp.pad(x, ((0, pad), (0, 0))), meta, plan, mesh)
        for y, s in zip(ys, _dense((1, 2))):
            np.testing.assert_array_equal(
                np.asarray(y)[:TOTAL], np.asarray(s @ x)
            )
    assert api.shift_local is shift_local
