"""The fused backward's dq protocol (ISSUE 44), replayed on the host: the
visit bits of the k-major table and the step's DMAs, run through the real
``_dq_accumulate`` with numpy for the chip's memories, over corner tables
and over every plan of every cell of BENCHMARK.json; and the orderings the
step keeps, each by name, on masks built to hold them, through the kernels
(``kernel_cases.run``) against the jnp oracle."""

import dataclasses
import os

import numpy as np
import pytest

from magiattention_tpu.ops import flex_attn as fa

from .kernel_cases import KernelCase, assert_grads, block_meta


def _walk(meta):
    """(k block, q block) of the backward table's entries, in the order
    the kernel walks them."""
    return list(zip(meta.bwd_k_block.tolist(), meta.bwd_q_block.tolist()))


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("hq,hk,head_block", [(2, 2, 1), (4, 1, 4), (2, 2, 2)])
def test_one_q_block_every_step_revisits_the_tile(hq, hk, head_block, grid):
    """A mask with one q block: every entry of the k-major walk names the
    same dq tile, so (head-batched, and per head at group 1) the tile
    stays in its VMEM slot from the first step to the last and makes one
    round trip."""
    case = KernelCase(
        "one_q_block", hq=hq, hk=hk, head_block=head_block, grid=grid
    )
    walk = _walk(block_meta(case))
    assert {q for _k, q in walk} == {0} and len(walk) >= 4
    assert_grads(case)


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
    while its write is in flight. The tables are jit arguments (traced, as
    a plan's are)."""
    case = KernelCase(
        "column_boundary", hq=hq, hk=hk, head_block=head_block, grid=grid,
        traced=True,
    )
    walk = _walk(block_meta(case))
    same_q = [a for a, b in zip(walk, walk[1:]) if a[1] == b[1]]
    assert any(a[0] != b[0] and a[1] == b[1] for a, b in zip(walk, walk[1:]))
    assert any(a == b for a, b in zip(walk, walk[1:])) and same_q
    assert_grads(case)


@pytest.mark.parametrize("hq,hk,head_block", [(2, 2, 1), (8, 2, 4), (2, 2, 2)])
def test_a_row_major_plan_with_dead_steps_touches_nothing_in_them(
    hq, hk, head_block
):
    """Columns of 1 to 4 entries on the row-major grid, padded as a rank's
    tables are (levelled sentinel entries): a dead step starts and waits
    for no copy, the tile read ahead in a column's last live step is the
    next column's first, and the padded entries add zero to q block 0."""
    case = KernelCase(
        "causal", hq=hq, hk=hk, head_block=head_block, traced=True,
        entry_pad=1,
    )
    meta = block_meta(case)
    counts = np.bincount(meta.bwd_k_block)
    assert counts.min() < meta.bwd_steps  # the grid has dead steps
    for tables in (case, dataclasses.replace(case, pad=3)):
        assert_grads(tables)



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
