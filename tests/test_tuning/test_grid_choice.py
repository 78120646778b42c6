"""The grid of a built plan (ISSUE 27): ``make_attn_params`` counts, for the
table sets it already walks, the steps the row-major and the compact grid
would launch over forward, dq and dkv, prices them with the two per-step
costs of ``tuning/cost_model.py`` and sets ``FlexAttnParams.grid``. Host
only: the plans of the benchmark's eleven cells are built from their own
traffic files, nothing runs on a device."""

import dataclasses
import importlib
import os

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from magiattention_tpu import api, telemetry
from magiattention_tpu.ops import build_block_meta
from magiattention_tpu.parallel.dist_attn import StageTables
from magiattention_tpu.testing.workloads import ranges_of, varlen_block_causal
from magiattention_tpu.tuning import (
    TuningRecord,
    WorkloadFingerprint,
    cost_model,
    get_tuning_cache,
    make_fingerprint,
    resolve_block_config,
)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture
def telemetry_on():
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    api.clear_cache()
    yield
    api.clear_cache()
    telemetry.set_enabled(was)


def _decisions(build, event: str = "attn_fn_build") -> list[dict]:
    """What ``make_attn_params`` left on the ``attn_fn_build`` spans that
    ``build()`` opened: rung, grid, the three counts, the two prices (or
    the tuner on its ``autotune_decision`` events)."""
    seen = len(telemetry.get_event_buffer().events())
    build()
    return [
        ev["args"]
        for ev in telemetry.get_event_buffer().events()[seen:]
        if ev["name"] == event
    ]


def _build_cell(name: str):
    """The plan(s) of one cell of BENCHMARK.json, as its traffic kind
    builds them (keyed API for the attention cells, the model builders
    for the training cells)."""
    from benchmarks import harness, masks

    cell = harness.load_cell(ROOT, name)
    cfg, tr = cell.config, cell.traffic
    devices = jax.devices()[: cell.chips]
    if tr["kind"] == "train_blockdiff":  # its mask is over the doubled rows
        kind = importlib.import_module("benchmarks.kinds." + tr["kind"])
        job = kind.Job(cfg, tr, 0, devices)
        return job.build(job.mask(tr["mask"], int(tr["data_tokens"])))
    total = int(tr["total_tokens"])
    mask = masks.build_mask(tr["mask"], total)
    if tr["kind"] != "attn_iter":
        kind = importlib.import_module("benchmarks.kinds." + tr["kind"])
        return kind.Job(cfg, tr, 0, devices).build(mask)
    mesh = Mesh(np.array(devices), ("cp",))
    args = dict(
        num_heads=(cfg["num_attention_heads"], cfg["num_key_value_heads"]),
        head_dim=cfg["head_dim"], chunk_size=tr.get("chunk_size"),
        out_dtype=cfg["dtype"], interpret=False,
    )
    if mask.doc_lengths:
        return api.magi_attn_varlen_key(mask.cu_seqlens, total, mesh, **args)
    return api.magi_attn_flex_key(
        list(mask.q_ranges), list(mask.k_ranges), list(mask.types),
        total, total, mesh, **args,
    )


# cell -> a plan each: (rung, grid, steps a head group launches over
# forward + dq + dkv on the row-major grid, on the compact grid, and the
# entries of a non-empty slice among them). ISSUE 27's table counts with
# build_block_meta: 512 x 34 and 128 x 132 for the packed cell, 3,640
# entries. The plan has one k block more (the merged KV buffer ends in the
# group cast's receive pad: 129 x 132) and 5 of the 3,640 are padding.
# ISSUE 56: the pair's price puts (256, 512, 8) ahead: 256 q blocks of at
# most 34 entries, 129 k blocks of at most 67, 1,865 entries padded to 1,872
CELLS = {
    "magi64x8-attn-64k-varlen": [
        ((256, 512, 8), "sparse", 2 * 256 * 34 + 129 * 67, 3 * 1872, 3 * 1865),
    ],
    "magi64x8-attn-64k-causal": [
        ((1024, 1024, 1), "sparse", 2 * 64 * 64 + 65 * 64, 6248, 3 * 2080),
    ],
    "mistral7b-train-16k-onemask": [
        ((128, 512, 8), "sparse", 2 * 128 * 9 + 33 * 31, 3 * 400, 1182),
    ],
    # ISSUE 54: 3.7% of the square, so the tie is broken by the table's own
    # order and not by the 64k dense slice's lead: (256, 512, 8), the first
    # tied rung whose per-rank bound fits, where it had (1024, 1024, 1)
    # (12,060 / 2,448 / 2,306.25)
    "magi64x8-attn-cp4-256k-varlen": [
        ((256, 512, 8), "sparse", 91556, 16416, 15894.75),
    ],
    # ISSUE 56: the global plan's 256 rung is the cheaper by 0.2% (602
    # entries for 1,121) and leads; the sliding plan's is 2.6% dearer and
    # the table's order stands
    "trinitymini-train-32k-packed": [
        ((256, 512, 8), "sparse", 2 * 128 * 16 + 65 * 33, 3 * 608, 3 * 602),
        ((128, 512, 8), "sparse", 6680, 2568, 2562),
    ],
    # ISSUE 33: the SMEM test counts the band's own table, so the band gets
    # (128, 512, 8) where it had (1024, 1024, 1). The CAUSAL head's 8 q
    # blocks meet 1, 1, 1, 1, 2, 2, 2, 2 k blocks and each of the band's 504
    # meets 3 (1,151 keys in 512-key tiles): 12 + 1,512 = 1,524 entries, 3 a
    # q block at most and 12 a k block; the plan's 129th k block adds a
    # dummy, and both tables pad to 1,528. 36 of 4,620 row-major steps are
    # dead: under the flip margin
    # ISSUE 56: (256, 512, 8) by the pair's price: 762 entries, 3 a q block
    # and 6 a k block at most, padded to 768; 24 of 2,310 steps dead
    "magi64x8-attn-64k-swa1024": [
        ((256, 512, 8), "row_major", 2 * 256 * 3 + 129 * 6, 3 * 768, 3 * 762),
    ],
    # the Mistral cell's mask at 20 query = 20 key-value heads of width
    # 256 (ISSUE 30). ISSUE 35: at GQA group 1 a step of (128, 512, 5)
    # streams K and V at the HBM's pace, the price says so and the tuner
    # returns (256, 512, 8) snapped to 5 heads a step: 64 q blocks of at
    # most 9 entries, 33 k blocks of at most 17, 213 entries padded to 216
    "glm47flash-train-16k-packed": [
        ((256, 512, 5), "sparse", 2 * 64 * 9 + 33 * 17, 3 * 216, 3 * 213),
    ],
    # 16 chunks of 4 blocks: q block r meets 4 (r // 4 + 1) k blocks
    "magi64x8-attn-64k-chunkcausal": [
        ((1024, 1024, 1), "sparse", 2 * 64 * 64 + 65 * 64, 6536, 3 * 16 * 136),
    ],
    # the dense mask at cp=4 (ISSUE 32): a rank's 65,536 rows against the
    # merged buffer of up to 262,144; the tuner leaves the one-chip dense
    # cell's (1024, 1024, 1) for (512, 2048, 1)
    "magi64x8-attn-cp4-256k-causal": [
        ((512, 2048, 1), "sparse", 49152, 24784, 24768),
    ],
    # the Mistral cell's mask at 16 query = 16 key-value heads of width
    # 128 (ISSUE 32), eight key-value heads a step; group 1 again, so
    # since ISSUE 35 (256, 512, 8) and the GLM cell's tables
    "ouro26b-train-16k-looped": [
        ((256, 512, 8), "sparse", 2 * 64 * 9 + 33 * 17, 3 * 216, 3 * 213),
    ],
    # five long documents at 8 query / 2 key-value heads of 128 (ISSUE 39):
    # GQA group 4, Mistral's, so (128, 512, 8) and the whole head group a
    # step; the 8,192-token document's last q block meets 16 k blocks and
    # its first k block 64 q blocks; 764 entries padded to 768
    # ISSUE 56: (256, 512, 8) by the pair's price, half the entries on the
    # same tile area (every document a multiple of 256 rows): 382, padded to
    # 384, its first k block meeting 32 q blocks
    "zaya1-train-16k-traces": [
        ((256, 512, 8), "sparse", 2 * 64 * 16 + 33 * 32, 3 * 384, 3 * 382),
    ],
    # ZAYA's mask halved at 32 query = 32 key-value heads, keys of 192
    # beside values of 128 (ISSUE 49): group 1, the GLM and
    # Ouro cells' (256, 512, 8), here with no cheaper rung passed over for
    # its bytes: 32 q blocks of at most 8 entries, 17 k blocks of at most 16, 105
    # entries padded to 112
    "xing4-train-8k-traces": [
        ((256, 512, 8), "sparse", 2 * 32 * 8 + 17 * 16, 3 * 112, 3 * 105),
    ],
}


@pytest.mark.parametrize("cell", list(CELLS))
def test_every_cell_keeps_its_rung_and_gets_the_expected_grid(
    telemetry_on, cell, monkeypatch
):
    monkeypatch.delenv("MAGI_ATTENTION_GRID", raising=False)
    got = _decisions(lambda: _build_cell(cell))
    assert len(got) == len(CELLS[cell])
    for args, (rung, grid, row_major, compact, live) in zip(got, CELLS[cell]):
        assert tuple(args["rung"]) == rung
        assert (args["row_major_steps"], args["compact_steps"]) == (
            row_major, compact,
        )
        assert args["live_steps"] == pytest.approx(live)
        assert args["grid"] == cost_model.choose_grid(row_major, compact)
        if grid is not None:
            assert args["grid"] == grid
        # ISSUE 40: at every cell's block_q the statistics cross the
        # forward's boundary with rows along lanes. ISSUE 43: the backward
        # is one k-major kernel, and delta is made before it
        assert (args["stats"], args["delta"]) == ("compact", "xla")
        assert args["bwd_form"] == "fused"
    # the gauge holds the newest plan's share, on the grid it was given
    launched = args["compact_steps" if args["grid"] == "sparse" else "row_major_steps"]
    assert telemetry.snapshot()["gauges"][
        "magi_flex_dead_step_share"
    ] == pytest.approx(100.0 * (1.0 - args["live_steps"] / launched))


# heads and head_dim at the kernels of the two cells at GQA group 1
GROUP_ONE = {
    "glm47flash-train-16k-packed": (20, 20, 256),
    "ouro26b-train-16k-looped": (16, 16, 128),
}


@pytest.mark.parametrize("cell", list(CELLS))
def test_every_cells_decision_says_which_roof_binds_its_rung(
    telemetry_on, cell, monkeypatch
):
    """ISSUE 35: the ``autotune_decision`` event of every plan of every cell
    carries what the operand-bytes term read. In the nine cells at GQA
    group 4 or 8 nothing streams: ``bound`` is ``mxu``, no rung was passed
    over, and at the chip's own peaks the forward's HBM time is under its
    MXU time. In the two at group 1 the chosen rung is MXU-bound too, one
    cheaper-by-tiles-and-steps rung was passed over as HBM-bound, and the
    chosen rung's K and V stream is under the parent's rung's."""
    from magiattention_tpu.utils.cost import TPU_PEAK_SPECS

    monkeypatch.delenv("MAGI_ATTENTION_GRID", raising=False)
    def counted_mxu():  # the counter's series, one a tie order (ISSUE 54, 56)
        return sum(
            telemetry.get_registry().counter_value(
                "magi_autotune_decisions_total", bound="mxu", tie_order=o
            )
            for o in ("long_seq", "measured", "priced_pair")
        )

    counted = counted_mxu()
    got = _decisions(lambda: _build_cell(cell), "autotune_decision")
    assert len(got) == len(CELLS[cell])
    for args, (rung, *_rest) in zip(got, CELLS[cell]):
        assert args["rung"] == "x".join(map(str, rung))
        assert args["bound"] == "mxu"
        assert args["rejected_bytes"] == (1 if cell in GROUP_ONE else 0)
        assert (
            args["hbm_seconds"] * cost_model.HBM_PRICE_SHARE
            < args["mxu_seconds"] * TPU_PEAK_SPECS["v5e"].mfu
        )
    assert counted_mxu() == counted + len(got)
    if cell in GROUP_ONE:
        from benchmarks import harness, masks

        hq, hk, d = GROUP_ONE[cell]
        tr = harness.load_cell(ROOT, cell).traffic
        m = masks.build_mask(tr["mask"], int(tr["total_tokens"]), index=0)
        (old,) = [
            s
            for s in cost_model.rank_candidates(
                m.q_ranges, m.k_ranges, m.types, hq, hk, head_dim=d,
                include_sparse=False,
            )
            if (s.block_q, s.block_k) == (128, 512)
        ]
        assert old.bound == "hbm" and args["hbm_seconds"] < old.hbm_seconds


@pytest.mark.parametrize(
    "kind,cell",
    [
        ("train_latent", "glm47flash-train-16k-packed"),
        ("train_looped", "ouro26b-train-16k-looped"),
    ],
)
def test_the_check_plans_the_windows_rung_and_grid(kind, cell):
    """``correct`` of a training cell is decided on a plan of the check's
    own at 4,096 tokens; it walks the rung and the grid the window's 16,384
    do. (The benchmark's own tests of this name pin the rung's literal,
    which ISSUE 35 moved; this one compares the two plans with each other.)"""
    from benchmarks import harness, masks

    job_of = importlib.import_module("benchmarks.kinds." + kind)
    c = harness.load_cell(ROOT, cell)
    job = job_of.Job(c.config, c.traffic, 1, jax.devices()[:1])
    chosen = []
    for mask in (
        job_of.check_mask(c.traffic),
        masks.build_mask(c.traffic["mask"], 16384, index=0),
    ):
        (p,) = job.build(mask)[0].attn_params.values()
        chosen.append((p.block_q, p.block_k, p.head_block, p.grid))
    assert chosen[0] == chosen[1]
    assert chosen[1] == (*CELLS[cell][0][0], CELLS[cell][0][1])


def test_a_version_3_record_for_a_band_mask_is_not_served(
    telemetry_on, monkeypatch, tmp_path
):
    """A cache directory from before ISSUE 33 holds (1024, 1024, 1) for the
    window mask, chosen when the SMEM test allowed nothing smaller. Its
    record is a version-3 fingerprint's: under its own hash the version-4
    key never opens it, and planted under the new key's name the stored
    fingerprint does not match. Either way the mask is ranked anew."""
    from benchmarks import masks

    assert WorkloadFingerprint.FINGERPRINT_VERSION == 7
    monkeypatch.setenv("MAGI_ATTENTION_AUTOTUNE_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv("MAGI_ATTENTION_AUTOTUNE", raising=False)
    total = 65536
    m = masks.build_mask({"type": "swa_causal", "window": 1024}, total)
    fp = make_fingerprint(
        m.q_ranges, m.k_ranges, m.types, 64, 8, max_block_q=total,
        max_block_k=total, include_sparse=False,
    )
    old_fp = dataclasses.replace(fp, version=3)
    assert old_fp.stable_hash() != fp.stable_hash()
    stale = TuningRecord(1024, 1024, 1, "model", 46.74, None, ())
    cache = get_tuning_cache()
    cache._store_disk(old_fp.stable_hash(), old_fp, stale)
    cache._store_disk(fp.stable_hash(), old_fp, stale)  # planted
    assert cache.get(fp) == (None, "miss")

    def resolve():
        with telemetry.span("tile_choice"):
            return resolve_block_config(
                m.q_ranges, m.k_ranges, m.types, total, total, 1, 64, 8,
                128, "bfloat16",
            )

    seen = len(telemetry.get_event_buffer().events())
    # a small rung again, since ISSUE 56 the pair's cheaper one
    assert resolve() == (256, 512, 8)
    assert resolve() == (256, 512, 8)  # from the cache now: the same record
    events = telemetry.get_event_buffer().events()[seen:]
    # the decision's record, the ``tile_choice`` span's child, says what the
    # SMEM test read, on a miss and on a hit: 762 tiles, padded to 8, and
    # which order broke the tie
    got = [ev["args"] for ev in events if ev["name"] == "autotune_decision"]
    assert len(got) == 2
    for args in got:
        assert (args["smem_entries"], args["smem_count"]) == (768, "exact")
        assert args["rejected_smem"] == 0
        assert args["tie_order"] == "priced_pair"
    assert [
        ev["args"]["cache_layer"]
        for ev in events
        if ev["name"] == "autotune_decision"
    ] == ["none", "memory"]
    # and the record that replaced the planted one is a version-4 one
    rec = get_tuning_cache()._load_disk(fp.stable_hash(), fp)
    assert (rec.block_q, rec.block_k, rec.head_block) == (256, 512, 8)


def test_the_packed_cells_dead_share_on_both_grids(telemetry_on, monkeypatch):
    """79% of the row-major grid's steps do nothing in the packed cell
    (78.97% at (128, 512, 8), 78.52% at ISSUE 56's (256, 512, 8)), and what
    is left on the compact one is the 7 padding entries a table;
    ``MAGI_ATTENTION_GRID`` pins the grid here as in ``auto_kernel_config``."""
    shares = {}
    for grid in ("row_major", "sparse"):
        monkeypatch.setenv("MAGI_ATTENTION_GRID", grid)
        api.clear_cache()
        (args,) = _decisions(
            lambda: _build_cell("magi64x8-attn-64k-varlen")
        )
        assert args["grid"] == grid
        shares[grid] = telemetry.snapshot()["gauges"]["magi_flex_dead_step_share"]
    assert shares["row_major"] == pytest.approx(78.52, abs=0.01)
    assert shares["sparse"] == pytest.approx(100 * 7 / 1872, abs=0.01)


def test_stacked_per_rank_tables_count_padded_entries_as_launched():
    """Two ranks' tables stacked to the longer one's length: the compact
    grid launches the padded length on every rank, the row-major grid
    blocks x the longest row of any rank, and the live count is the mean
    of the ranks' own entries."""
    metas = [
        build_block_meta(*ranges_of(varlen_block_causal(t)), 2048, 2048,
                         block_q=128, block_k=128)
        for t in (2048, 1024)
    ]
    tables = StageTables.from_rank_metas(metas, 2048)
    fs, bs = tables.kernel_steps()
    row_major, compact, live = tables.grid_steps(fs, bs)
    e = max(m.num_fwd_entries for m in metas)
    e2 = max(m.num_bwd_entries for m in metas)
    assert e > min(m.num_fwd_entries for m in metas)  # rank 1 is padded
    assert compact == 2 * e + e2
    assert row_major == 2 * 16 * fs + 16 * bs

    def works(meta):
        b = meta.slice_bounds.reshape(-1, 5)
        ok = (b[:, 1] > b[:, 0]) & (b[:, 3] > b[:, 2])
        return 2 * ok[meta.fwd_slice_id].sum() + ok[meta.bwd_slice_id].sum()

    assert live == pytest.approx(np.mean([works(m) for m in metas]))
    assert live < compact


def test_the_flip_needs_the_saving_to_pass_the_error_bar():
    dead, fee = cost_model.DEAD_ROW_MAJOR_STEP_S, cost_model.COMPACT_STEP_EXTRA_S
    assert cost_model.price_grids(1000, 400) == (600 * dead, 400 * fee)
    assert cost_model.price_grids(400, 400) == (0.0, 400 * fee)
    assert cost_model.choose_grid(400, 400) == "row_major"
    # break-even: dead * DEAD = MARGIN * entries * FEE
    entries = 10_000
    even = entries * cost_model.GRID_FLIP_MARGIN * fee / dead
    assert cost_model.choose_grid(entries + int(even * 0.98), entries) == "row_major"
    assert cost_model.choose_grid(entries + int(even * 1.02) + 1, entries) == "sparse"
