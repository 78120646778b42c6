"""The fused backward (ISSUE 43): one kernel over the k-major table that
keeps dk / dv in VMEM and adds dq into a float32 buffer in HBM, a tile's
read started a step ahead. CPU, interpret mode: dq, dk, dv and the sink's
gradient against the jnp reference backward over mask type, GQA group,
head block, head_dim and both grids, with a non-zero lse cotangent and a
sink; and the orderings the step keeps, each by name."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from magiattention_tpu.common import AttnMaskType
from magiattention_tpu.ops import build_block_meta, flex_flash_attn_func
from magiattention_tpu.ops import flex_attn as fa
from magiattention_tpu.ops.block_meta import pad_block_meta
from magiattention_tpu.testing import assert_close, ref_attn_from_ranges

T = AttnMaskType
TOKENS = 256
# one slice of each bounded type on ranges that are no multiple of a block,
# and two slices at a step of 4 that share q rows with the first
MASKS = {
    "full": ([(0, 250)], [(6, 256)], [T.FULL]),
    "causal": ([(0, 250)], [(0, 250)], [T.CAUSAL]),
    "invcausal": ([(3, 200)], [(0, 256)], [T.INVCAUSAL]),
    "bicausal": ([(0, 180)], [(10, 256)], [T.BICAUSAL]),
    "stepped": (
        [(0, 128), (128, 256), (16, 80)],
        [(0, 128), (0, 256), (128, 200)],
        [T.CAUSAL.with_step(4), T.CAUSAL.with_step(4), T.FULL],
    ),
}


def _operands(hq, hk, d, seed=5):
    rng = np.random.default_rng(seed)
    make = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape), jnp.float32
    )
    return dict(
        q=make(TOKENS, hq, d), k=make(TOKENS, hk, d), v=make(TOKENS, hk, d),
        sink=make(hq), do=make(TOKENS, hq, d), w=make(TOKENS, hq),
    )


def _grads(attn, x):
    """dq, dk, dv, dsink of a loss that reads out and lse (a non-zero lse
    cotangent) through ``attn(q, k, v, sink) -> (out, lse)``."""

    def loss(q, k, v, sink):
        out, lse = attn(q, k, v, sink)
        lse = jnp.where(jnp.isneginf(lse), 0.0, lse)
        return (out * x["do"]).sum() + (lse * x["w"]).sum()

    return jax.grad(loss, argnums=(0, 1, 2, 3))(
        x["q"], x["k"], x["v"], x["sink"]
    )


@functools.lru_cache(maxsize=None)
def _reference(mask, hq, hk, d):
    qr, kr, ts = MASKS[mask]
    return _grads(
        lambda q, k, v, sink: ref_attn_from_ranges(
            q, k, v, qr, kr, ts, sink=sink
        )[:2],
        _operands(hq, hk, d),
    )


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("d", [128, 256])
@pytest.mark.parametrize("heads", ["per-head", "batched"])
@pytest.mark.parametrize("group", [1, 4, 8])
@pytest.mark.parametrize("mask", list(MASKS))
def test_fused_backward_matches_the_reference(mask, group, heads, d, grid):
    hk = 2
    hq = hk * group
    head_block = 1 if heads == "per-head" else max(group, 2)
    qr, kr, ts = MASKS[mask]
    got = _grads(
        lambda q, k, v, sink: flex_flash_attn_func(
            q, k, v, qr, kr, ts, sink=sink, block_q=64, block_k=64,
            head_block=head_block, grid=grid, interpret=True,
        ),
        _operands(hq, hk, d),
    )
    for a, b, nm in zip(got, _reference(mask, hq, hk, d), ["dq", "dk", "dv", "dsink"]):
        assert np.isfinite(np.asarray(a)).all(), nm
        assert_close(a, b, atol=1e-4, rtol=1e-4, msg=nm)


def _walk(meta):
    """(k block, q block) of the backward table's entries, in the order
    the kernel walks them."""
    return list(zip(meta.bwd_k_block.tolist(), meta.bwd_q_block.tolist()))


def _table_grads(meta, hq, hk, head_block, grid, d=32):
    """The same gradients through ``flex_attn_headmajor`` on the tables of
    ``meta`` as jit arguments (traced, as a plan's are)."""
    x = _operands(hq, hk, d)
    params = fa.FlexAttnParams(
        block_q=meta.block_q, block_k=meta.block_k, scale=d**-0.5,
        softcap=0.0, has_sink=True, out_dtype="float32", interpret=True,
        head_block=head_block, fwd_steps=meta.fwd_steps,
        bwd_steps=meta.bwd_steps, grid=grid,
    )

    def grads(ftab, btab):
        def attn(q, k, v, sink):
            out, lse, _ = fa.flex_attn_headmajor(
                jnp.transpose(q, (1, 0, 2)), jnp.transpose(k, (1, 0, 2)),
                jnp.transpose(v, (1, 0, 2)), ftab, btab, params, sink=sink,
            )
            return jnp.transpose(out, (1, 0, 2)), lse.T

        return _grads(attn, x)

    return jax.jit(grads)(fa.fwd_tables(meta), fa.bwd_tables(meta))


def _check(meta, mask, hq, hk, head_block, grid):
    qr, kr, ts = mask
    x = _operands(hq, hk, 32)
    want = _grads(
        lambda q, k, v, sink: ref_attn_from_ranges(
            q, k, v, qr, kr, ts, sink=sink
        )[:2],
        x,
    )
    got = _table_grads(meta, hq, hk, head_block, grid)
    for a, b, nm in zip(got, want, ["dq", "dk", "dv", "dsink"]):
        assert_close(a, b, atol=1e-4, rtol=1e-4, msg=nm)


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("hq,hk,head_block", [(2, 2, 1), (4, 1, 4), (2, 2, 2)])
def test_one_q_block_every_step_revisits_the_tile(hq, hk, head_block, grid):
    """A mask with one q block: every entry of the k-major walk names the
    same dq tile, so (head-batched, and per head at group 1) the tile
    stays in its VMEM slot from the first step to the last and makes one
    round trip."""
    mask = ([(0, 60)], [(0, 256)], [T.FULL])
    meta = build_block_meta(
        *mask[:2], [t.value for t in mask[2]], 64, TOKENS, block_q=64,
        block_k=64,
    )
    assert {q for _k, q in _walk(meta)} == {0} and len(_walk(meta)) >= 4
    x = _operands(hq, hk, 32)
    short = {n: (a[:64] if n in ("q", "do", "w") else a) for n, a in x.items()}
    qr, kr, ts = mask
    want = _grads(
        lambda q, k, v, sink: ref_attn_from_ranges(
            q, k, v, qr, kr, ts, sink=sink
        )[:2],
        short,
    )
    got = _grads(
        lambda q, k, v, sink: flex_flash_attn_func(
            q, k, v, qr, kr, ts, sink=sink, block_q=64, block_k=64,
            head_block=head_block, grid=grid, interpret=True,
        ),
        short,
    )
    for a, b, nm in zip(got, want, ["dq", "dk", "dv", "dsink"]):
        assert_close(a, b, atol=1e-4, rtol=1e-4, msg=nm)


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("hq,hk,head_block", [(2, 2, 1), (8, 2, 4), (2, 2, 2)])
def test_a_column_boundary_where_the_next_entry_names_the_same_q_block(
    hq, hk, head_block, grid
):
    """Two slices that split q block 0 against every key, and one of the
    other q blocks against the first k block: column 0 walks every q
    block, the columns after it q block 0 alone, twice. So the walk holds
    a column boundary where ``qblk[e + 1] == qblk[e]`` and, inside a
    column, two entries on one tile. The tile is kept in VMEM, not read
    while its write is in flight."""
    mask = (
        [(0, 30), (30, 64), (64, 256)],
        [(0, 256), (0, 256), (0, 64)],
        [T.FULL, T.FULL, T.FULL],
    )
    meta = build_block_meta(
        *mask[:2], [t.value for t in mask[2]], TOKENS, TOKENS, block_q=64,
        block_k=64,
    )
    walk = _walk(meta)
    same_q = [a for a, b in zip(walk, walk[1:]) if a[1] == b[1]]
    assert any(a[0] != b[0] and a[1] == b[1] for a, b in zip(walk, walk[1:]))
    assert any(a == b for a, b in zip(walk, walk[1:])) and same_q
    _check(meta, mask, hq, hk, head_block, grid)


@pytest.mark.parametrize("hq,hk,head_block", [(2, 2, 1), (8, 2, 4), (2, 2, 2)])
def test_a_row_major_plan_with_dead_steps_touches_nothing_in_them(
    hq, hk, head_block
):
    """Columns of 1 to 4 entries on the row-major grid, padded as a rank's
    tables are (levelled sentinel entries): a dead step starts and waits
    for no copy, the tile read ahead in a column's last live step is the
    next column's first, and the padded entries add zero to q block 0."""
    mask = ([(0, 250)], [(0, 250)], [T.CAUSAL])
    meta = build_block_meta(
        *mask[:2], [t.value for t in mask[2]], TOKENS, TOKENS, block_q=64,
        block_k=64, entry_pad=1,
    )
    counts = np.bincount(meta.bwd_k_block)
    assert counts.min() < meta.bwd_steps  # the grid has dead steps
    padded = pad_block_meta(
        meta, meta.num_fwd_entries + 3, meta.num_bwd_entries + 3,
        meta.num_slices + 1,
    )
    for tables in (meta, padded):
        _check(tables, mask, hq, hk, head_block, "row_major")


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
def test_dq_leaves_the_kernel_in_the_inputs_dtype(grid, monkeypatch):
    """ISSUE 44: dq is the launcher's first result, [hq, tqp, d] in q's
    dtype, written by each q block's last visit; the caller gets it as it
    is. Rows past the slice inside a named block are exact zeros, and so
    are the blocks no entry names (tokens 128 on), which come from the
    zero fill the output is aliased to: the form reads ``zero_filled``."""
    seen = {}
    bwd_pallas = fa._bwd_pallas

    def spy(q, k, v, do, lse, delta, tables, params):
        dq, dk, dv = bwd_pallas(q, k, v, do, lse, delta, tables, params)
        seen.update(
            dq=dq, delta=delta, lse=lse,
            form=fa.dq_form(params, tables[1], q.shape[1] // params.block_q),
        )
        return dq, dk, dv

    monkeypatch.setattr(fa, "_bwd_pallas", spy)
    qr, kr, ts = [(0, 100)], [(0, 100)], [T.CAUSAL]
    x = _operands(4, 2, 32)
    x = {n: a.astype(jnp.bfloat16) if n in "qkv" else a for n, a in x.items()}
    dq, _dk, _dv, _ds = _grads(
        lambda q, k, v, sink: flex_flash_attn_func(
            q, k, v, qr, kr, ts, sink=sink, block_q=64, block_k=64,
            head_block=2, grid=grid, interpret=True,
        ),
        x,
    )
    assert seen["dq"].dtype == dq.dtype == jnp.bfloat16
    assert seen["dq"].shape == (4, TOKENS, 32) and seen["form"] == "zero_filled"
    np.testing.assert_array_equal(
        np.asarray(dq, np.float32),
        np.asarray(jnp.transpose(seen["dq"], (1, 0, 2)), np.float32),
    )
    assert np.asarray(seen["dq"], np.float32)[:, :100].any()
    assert not np.asarray(seen["dq"], np.float32)[:, 100:].any()
    for nm in ("lse", "delta"):  # what _bwd_p_ds reads at that shape
        stat = np.asarray(seen[nm])
        assert stat.shape == (4, TOKENS, fa.LANES) and stat.dtype == np.float32
        np.testing.assert_array_equal(
            stat, np.broadcast_to(stat[..., :1], stat.shape), err_msg=nm
        )


# ---------------------------------------------------------------------------
# ISSUE 44: the visit bits and the step's DMA protocol, replayed on the host
# ---------------------------------------------------------------------------


class _Ref:
    """A numpy array seen as a Pallas ref: ``ref[i]``, ``ref[i] = x``,
    ``ref.at[...]`` (a view that remembers which memory and which region
    it names)."""

    def __init__(self, mem, name, region=()):
        self.mem, self.name, self.region = mem, name, tuple(region)

    @property
    def view(self):
        return self.mem[self.region]

    shape = property(lambda self: self.view.shape)
    dtype = property(lambda self: self.view.dtype)

    @property
    def at(self):
        ref = self

        class _At:
            def __getitem__(self, idx):
                idx = idx if isinstance(idx, tuple) else (idx,)
                assert not ref.region  # only whole memories are cut
                return _Ref(ref.mem, ref.name, tuple(_plain(i) for i in idx))

        return _At()

    def __getitem__(self, idx):
        return self.view[_plain(idx)]

    def __setitem__(self, idx, value):
        self.view[_plain(idx)] = np.asarray(value)

    def key(self):
        return (self.name, tuple(
            (i.start, i.stop) if isinstance(i, slice) else int(i)
            for i in self.region
        ))


def _plain(i):
    if isinstance(i, tuple):
        return tuple(_plain(j) for j in i)
    return i if isinstance(i, slice) or i is Ellipsis else int(i)


class _Replay:
    """The memories ``_dq_accumulate`` moves data between, with DMAs that
    land when they are waited for (the latest a chip may land them), and
    the rules a chip would punish silently checked at every start and
    wait."""

    def __init__(self, heads_all, blocks, heads, bq=2, d=1):
        nan = lambda *s: np.full(s, np.nan, np.float32)  # noqa: E731
        self.acc = _Ref(nan(heads_all, blocks * bq, d), "acc")
        self.out = _Ref(nan(heads_all, blocks * bq, d), "out")
        self.buf = _Ref(nan(2, heads, bq, d), "buf")
        self.stage = _Ref(nan(2, heads, bq, d), "stage")
        self.st = np.full(4, -7, np.int32)  # garbage until the first step
        self.flying = {}  # semaphore -> [(src, dst, what src held)]
        self.out_writes = {}

    class _Sem:
        at = property(lambda self: self)

        def __getitem__(self, idx):
            return tuple(int(i) for i in idx)

    def copy(self, src, dst, sem):
        replay = self

        class _Dma:
            def start(self):
                pending = [c for q in replay.flying.values() for c in q]
                for s, t, _ in pending:
                    assert t.key() != src.key(), f"read of {src.key()} while its write is in flight"
                    assert t.key() != dst.key(), f"two copies into {dst.key()}"
                    assert s.key() != dst.key(), f"{dst.key()} refilled while it is being written out"
                if src.name == "acc":
                    assert not np.isnan(src.view).any(), f"{src.key()} read before it was written"
                replay.flying.setdefault(sem, []).append((src, dst, src.view.copy()))

            def wait(self):
                queue = replay.flying.get(sem)
                assert queue, f"wait on {sem} with nothing started: the chip would hang"
                s, t, held = queue.pop(0)
                assert s.view.shape == src.view.shape  # the bytes waited for
                np.testing.assert_array_equal(
                    s.view, held, err_msg=f"{s.key()} changed under its copy"
                )
                t.view[...] = held
                if t.name == "out":
                    replay.out_writes[t.key()] = replay.out_writes.get(t.key(), 0) + 1

        return _Dma()


def _replay(q_blocks, *, group=1, heads=1, num_q_blocks=None, per_head=False):
    """Run the real ``_dq_accumulate`` (through ``_dq_step``, as both
    bodies do) over one head block's walk of a k-major table whose entries
    name ``q_blocks``, with numpy for the chip's memories, and hold the
    result against the sums by q block. ``per_head``: the per-head body's
    walk, ``group`` steps an entry; else one step of ``heads`` heads."""
    import types

    from magiattention_tpu.ops.block_meta import RUN_FIELDS, mark_q_visits

    qblk = np.asarray(q_blocks, np.int32)
    runs = mark_q_visits(qblk, np.zeros(len(qblk) * RUN_FIELDS, np.int32))
    blocks = num_q_blocks or int(qblk.max()) + 1
    bq = 2
    mem = _Replay(group if per_head else heads, blocks, 1 if per_head else heads, bq)
    want = np.zeros(mem.out.shape, np.float32)
    rng = np.random.default_rng(len(qblk))
    saved = fa.pl, fa.pltpu
    fa.pl = types.SimpleNamespace(
        ds=lambda start, size: slice(int(start), int(start) + size),
        multiple_of=lambda x, m: x,
        when=lambda cond: (lambda fn: fn() if bool(cond) else None),
    )
    fa.pltpu = types.SimpleNamespace(make_async_copy=mem.copy)
    try:
        for e in range(len(qblk)):
            for g in range(group if per_head else 1):
                step = fa._dq_step(qblk, runs, e, 0, g, group if per_head else 1)
                head0, qb = (int(x) for x in step["tile"])
                x = rng.integers(1, 4, (mem.buf.shape[1], bq, 1)).astype(np.float32)
                rows = slice(qb * bq, (qb + 1) * bq)
                want[head0 : head0 + x.shape[0], rows] += x

                fa._dq_accumulate(
                    mem.acc, mem.out, mem.buf, mem.stage, _Replay._Sem(),
                    mem.st, lambda x=x: x, bq=bq, d=1, **step,
                )
    finally:
        fa.pl, fa.pltpu = saved
    assert not any(mem.flying.values()), "copies in flight at the walk's end"
    named = np.zeros(blocks, bool)
    named[qblk] = True
    got = mem.out.view.reshape(want.shape[0], blocks, bq)
    np.testing.assert_array_equal(
        got[:, named], want.reshape(got.shape)[:, named]
    )
    assert np.isnan(got[:, ~named]).all()  # never touched: the fill's rows
    # one result write a visited tile (its last visit), each head block
    assert set(mem.out_writes.values()) == {1}
    assert len(mem.out_writes) == named.sum() * (group if per_head else 1)
    return mem


# q blocks named by the entries of a k-major table, in table order
CORNERS = {
    "one q block": [0, 0, 0, 0, 0],
    "one entry": [0],
    "a tile visited once between others": [0, 1, 2, 1, 0],
    "every tile visited once": [0, 1, 2, 3],
    "two slices on one tile": [0, 1, 1, 2, 0, 1, 1, 2],
    "a first visit straight after a last one": [0, 0, 1, 0, 2, 2, 3],
    "first and last alternate over both slots": [0, 1, 0, 2, 1, 3, 2, 3],
    "pads on block 0 before and after its real visits": [0, 0, 1, 2, 0, 1, 0, 0, 2, 0],
    "a block no entry names": [0, 3, 1, 3, 0],
    "many last visits in a row": [0, 1, 2, 3, 4, 5, 0, 1, 2, 3, 4, 5],
}


@pytest.mark.parametrize("form", ["per-head group 1", "per-head group 4", "batched"])
@pytest.mark.parametrize("corner", list(CORNERS))
def test_the_steps_protocol_replayed_on_a_corner_table(corner, form):
    """No tile is read before it was written nor while its write is in
    flight, no slot is refilled under its write, every wait has a start,
    every visited tile's result is written once, by its last visit, and
    the walk ends with nothing in flight."""
    if form == "batched":
        _replay(CORNERS[corner], heads=2)
    else:
        _replay(CORNERS[corner], group=int(form[-1]), per_head=True)


def test_the_replay_catches_a_table_without_its_bits(monkeypatch):
    """The replay is no rubber stamp: with the first-visit bit dropped the
    step reads a tile nobody wrote."""
    from magiattention_tpu.ops import block_meta

    monkeypatch.setattr(block_meta, "FIRST_VISIT", 0)
    with pytest.raises(AssertionError):
        _replay(CORNERS["a tile visited once between others"], heads=1)


def _cells():
    import json

    from ..test_tuning.test_grid_choice import ROOT

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("cell", _cells())
def test_the_steps_protocol_replayed_on_a_benchmark_cells_tables(cell, monkeypatch):
    """The same replay over the k-major tables of every plan of every
    cell of BENCHMARK.json (merged, host and stage tables, every rank's:
    padded per rank, then marked), head-batched; and per head with the
    group innermost on the tables short enough for it."""
    from magiattention_tpu import api
    from magiattention_tpu.parallel.dist_attn import StageTables

    from ..test_tuning.test_grid_choice import _build_cell

    built = []
    stack = StageTables.from_rank_metas

    def spy(metas, kv_pad):
        built.append(stack(metas, kv_pad))
        return built[-1]

    monkeypatch.setattr(StageTables, "from_rank_metas", staticmethod(spy))
    api.clear_cache()
    _build_cell(cell)
    api.clear_cache()
    assert built
    for tables in built:
        entries, named, unnamed = tables.q_visits()
        assert named + unnamed == tables.bwd_qblk.shape[0] * tables.num_q_blocks
        ranks = tables.bwd_qblk
        if ranks.shape[1] > 8000:  # the cp=4 dense cell: 24,784 a rank
            ranks = ranks[[0, -1]]
        for rank in ranks:
            _replay(rank, heads=1, num_q_blocks=tables.num_q_blocks)
        if entries <= 4000:
            _replay(
                tables.bwd_qblk[0], group=2, per_head=True,
                num_q_blocks=tables.num_q_blocks,
            )


# a padded tail (250 of 256 tokens), two documents, and q blocks 2 and 3
# (rows 128 to 255 at block_q 64) without a key: their dq must be zeros
HOLES = ([(0, 100), (100, 128)], [(0, 100), (60, 128)], [T.CAUSAL, T.FULL])


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize(
    "hq,hk,head_block,dtype",
    [(2, 2, 1, "float32"), (2, 2, 2, "float32"), (4, 1, 1, "float32"),
     (4, 1, 4, "float32"), (8, 1, 1, "float32"), (8, 1, 8, "float32"),
     (4, 1, 1, "bfloat16"), (8, 1, 8, "bfloat16")],
    ids=["g1-per-head", "g1-batched", "g4-per-head", "g4-batched",
         "g8-per-head", "g8-batched", "g4-per-head-bf16", "g8-batched-bf16"],
)
def test_q_blocks_without_a_key_come_back_as_zeros(
    hq, hk, head_block, dtype, grid
):
    """ISSUE 44, head_dim 64 (the tile's padding lanes): a mask that leaves
    whole q blocks unnamed takes the zero-filled form, says so on the build
    counter, and returns exact zeros there; the same mask with a key for
    every block takes ``visits`` and fills nothing. dq, dk, dv of both
    against the float32 reference."""
    from magiattention_tpu import telemetry

    d, tokens = 64, 250
    rng = np.random.default_rng(11)
    make = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)  # noqa: E731
    q, k, v, do = (
        make(tokens, hq, d), make(tokens, hk, d), make(tokens, hk, d),
        make(tokens, hq, d),
    )
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    reg = telemetry.get_registry()
    try:
        for mask, form in (
            (HOLES, "zero_filled"),
            (tuple(x + y for x, y in zip(HOLES, ([(128, 250)], [(0, 250)], [T.CAUSAL]))), "visits"),
        ):
            qr, kr, ts = mask

            def grads(attn, cast):
                def loss(q, k, v):
                    out = attn(q.astype(cast), k.astype(cast), v.astype(cast))
                    return (out.astype(jnp.float32) * do).sum()

                return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

            before = reg.counter_value(
                "magi_flex_kernel_build_total", kernel="bwd", grid=grid,
                heads_per_step=head_block, delta="xla", dq=form,
            )
            got = grads(
                lambda q, k, v: flex_flash_attn_func(
                    q, k, v, qr, kr, ts, block_q=64, block_k=64,
                    head_block=head_block, grid=grid, interpret=True,
                )[0],
                dtype,
            )
            assert reg.counter_value(
                "magi_flex_kernel_build_total", kernel="bwd", grid=grid,
                heads_per_step=head_block, delta="xla", dq=form,
            ) == before + 1
            want = grads(
                lambda q, k, v: ref_attn_from_ranges(q, k, v, qr, kr, ts)[0],
                "float32",
            )
            tol = 1e-4 if dtype == "float32" else 6e-2
            for a, b, nm in zip(got, want, ["dq", "dk", "dv"]):
                assert np.isfinite(np.asarray(a)).all(), nm
                assert_close(a, b, atol=tol, rtol=tol, msg=f"{form} {nm}")
            if form == "zero_filled":
                assert not np.asarray(got[0])[128:].any()
    finally:
        telemetry.set_enabled(was)


@pytest.mark.parametrize(
    "d,mask,aliases",
    [(128, "causal", {13: 3, 10: 2}), (64, "causal", {13: 3}),
     (128, "holes", {13: 3, 14: 2})],
    ids=["result-in-dO's-place", "padded-lanes-own-buffer", "zero-fill"],
)
def test_where_the_result_lives(d, mask, aliases):
    """ISSUE 44: the backward's dq result is no buffer more than before.
    Where the table names every q block it is aliased to dO (operand 10:
    a block's last visit is the last step to read its dO tile), unless the
    tile's lanes are padded (head_dim 64: the shapes differ); where blocks
    are left out it is aliased to a zero fill of its own (operand 14). The
    float32 sums' buffer is always aliased to an operand nobody has written
    (13: ``lax.empty``, no fill)."""
    qr, kr, ts = HOLES if mask == "holes" else MASKS[mask]
    x = jnp.zeros((TOKENS if mask != "holes" else 250, 4, d), jnp.bfloat16)

    def loss(q, k, v):
        out, _ = flex_flash_attn_func(
            q, k, v, qr, kr, ts, block_q=64, block_k=64, head_block=2,
            interpret=True,
        )
        return out.astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x[:, :2], x[:, :2])

    def calls(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    (bwd,) = [e for e in calls(jaxpr.jaxpr) if e.params["name"] == "magi_flex_bwd_kernel"]
    assert dict(bwd.params["input_output_aliases"]) == aliases
