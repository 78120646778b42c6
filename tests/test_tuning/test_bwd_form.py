"""The backward's form (ISSUE 43): one k-major kernel that computes S, dP
and dS once a tile, keeps dk / dv in VMEM and adds dq into a float32 buffer
in HBM. What the cost model says a step of it moves, at the rungs the
benchmark's cells run; and the form each cell's plan records, read
from the plan's own span (host only: nothing runs on a device)."""

import json
import os

import pytest

from magiattention_tpu import api, telemetry
from magiattention_tpu.ops.flex_attn import BWD_FORM
from magiattention_tpu.tuning import cost_model
from magiattention_tpu.utils.cost import TPU_PEAK_SPECS

from .test_grid_choice import ROOT, _build_cell, _decisions, telemetry_on  # noqa: F401

# (rung, GQA group, head_dim) -> bytes a live step moves (lse and delta cross
# compact since ISSUE 58: 8 bytes a row and head, not 1,024)
RUNGS = {
    "dense 64k, chunk-causal": ((1024, 1024, 1), 8, 128, 1_581_056),
    # cp4 packed since ISSUE 54; the others since ISSUE 56 (the pair's price)
    "cp4 packed, packed 64k, window, Trinity global, SDAR": (
        (256, 512, 8), 8, 128, 3_162_112,
    ),
    "Trinity sliding": ((128, 512, 8), 8, 128, 1_581_056),
    "Mistral": ((128, 512, 8), 4, 128, 1_581_056),
    "ZAYA": ((256, 512, 8), 4, 128, 3_162_112),  # since ISSUE 56
    "SmallThinker": ((256, 512, 7), 7, 128, 2_766_848),  # since ISSUE 56
    "Ouro": ((256, 512, 8), 1, 128, 3_162_112),
    "GLM": ((256, 512, 5), 1, 256, 3_942_400),
    "cp4 dense": ((512, 2048, 1), 8, 128, 790_528),
}


@pytest.mark.parametrize("cells", list(RUNGS))
def test_step_bytes_of_the_fused_backward_at_the_rungs_in_use(cells):
    """A q row and head: q and dO in bf16 (4d bytes), lse and delta in
    float32 with rows along lanes (8), the float32 dq tile in and out (8d):
    1,544 bytes at head_dim 128 where the lane-replicated pair made it
    2,560, so 0.83 x block_k FLOPs a byte for 0.5, whatever the GQA group
    (10 x rows x block_k x d FLOPs a step)."""
    (bq, bk, hb), group, d, want = RUNGS[cells]
    got = cost_model.step_bytes("bwd", bq, bk, hb, group, d, 2)
    assert got == want == hb * bq * (4 * d + 8 + 8 * d)
    assert 10 * hb * bq * bk * d / got == pytest.approx(
        bk * 10 * d / (12 * d + 8)
    )
    # what it adds to the k-major step it replaced: the dq tile, both ways
    assert got - cost_model.step_bytes("dkv", bq, bk, hb, group, d, 2) == (
        hb * bq * 8 * d
    )
    assert cost_model.KERNEL_FLOP_WEIGHTS["bwd"] == 2.5


def test_the_ranking_prices_what_it_was_calibrated_on():
    """``bwd`` is priced by ``step_bytes`` and weighed, and enters no
    rung's ranking: that re-ranks every mask (ROADMAP D4)."""
    assert cost_model.RANKED_KERNELS == ("fwd", "dq", "dkv")
    assert set(cost_model.RANKED_KERNELS) < set(cost_model.KERNEL_FLOP_WEIGHTS)


# visits a q tile gets in the mean on each plan's k-major tables (entries,
# pads and dummies with them, over the q blocks they name): each moves the
# tile's float32 sums once; the first reads nothing, the last writes dq
DQ_VISITS = {
    # ISSUE 56 moved seven cells' plans to block_q 256 by the pair's price:
    # a q tile of twice the rows meets the k blocks of both halves
    "magi64x8-attn-64k-varlen": [7.3125],  # 7.109 at (128, 512, 8)
    "magi64x8-attn-64k-causal": [32.625],
    "mistral7b-train-16k-onemask": [3.125],
    "magi64x8-attn-cp4-256k-varlen": [21.375],  # (256, 512, 8) since ISSUE 54
    "trinitymini-train-32k-packed": [4.75, 3.344],  # the global plan: 4.406
    "magi64x8-attn-64k-swa1024": [3.0],  # 2.984
    "glm47flash-train-16k-packed": [3.375],
    "magi64x8-attn-64k-chunkcausal": [34.125],
    "magi64x8-attn-cp4-256k-causal": [64.625],
    "ouro26b-train-16k-looped": [3.375],
    "zaya1-train-16k-traces": [6.0],
    "sdar30b-train-16k-blockdiff": [5.875],
    # the full plan (ZAYA's mask), then the window of 512: one block_k
    "phi4flash-train-16k-traces": [6.0, 2.0],  # the window: 1.9375 at 128
    "xing4-train-8k-traces": [3.5],  # ZAYA's mask halved, at block_q 256
    # four long documents: the full plan, then the window of 4,096, both at
    # (256, 512), 7 heads a step (ISSUE 53; the full plan ran per head at
    # (1024, 1024) and 8.5 visits until ISSUE 54 took the long-sequence lead
    # off masks under the density line, then both at (128, 512, 7) and
    # 7.9375 / 5.9375 until ISSUE 56)
    "smallthinker-train-16k-traces": [8.0, 6.0],
    # five documents off the block grid, 32 / 8 heads of 64 (ISSUE 55);
    # 6.4375 at (128, 512, 8)
    "granite4hmicro-train-packed-traces": [6.625],
}


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def _heads(cell):
    """(q heads, kv heads, head_dim) at the kernels of a cell's plan, from
    its ``plan_flex_attn`` / ``key_build`` span's own attributes where the
    model has them, else from the cell's configuration."""
    from benchmarks import harness

    cfg = harness.load_cell(ROOT, cell).config
    hq = cfg["num_attention_heads"]
    hk = cfg.get("num_key_value_heads", hq)
    if "qk_nope_head_dim" in cfg:  # the latent form: 20 = 20 heads of 256
        return hq, hq, cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    if cfg.get("model_type") == "phi4flash":  # 64-wide heads on 128 lanes
        return hq, hk, 2 * (cfg["hidden_size"] // hq)
    if "cca_num_q_heads" in cfg:
        return cfg["cca_num_q_heads"], cfg["cca_num_kv_heads"], cfg["head_dim"]
    return hq, hk, cfg["head_dim"] if "head_dim" in cfg else cfg["hidden_size"] // hq


@pytest.mark.parametrize("cell", _cells())
def test_every_cells_plan_takes_the_fused_backward(telemetry_on, cell, monkeypatch):
    """Every cell: the ``attn_fn_build`` span of every plan carries
    ``bwd_form`` and the counter counts the plan. And from the plan's own
    rung: a fused step's larger of MXU and HBM time, at the chip's peaks,
    is under the sum of the two steps it replaced (dq's and dkv's), which
    is why one form serves every geometry the cells bring."""
    monkeypatch.delenv("MAGI_ATTENTION_GRID", raising=False)
    reg = telemetry.get_registry()
    before = reg.counter_value("magi_flex_bwd_form_total", form=BWD_FORM)
    got = _decisions(lambda: _build_cell(cell))
    assert got and BWD_FORM == "fused"
    assert reg.counter_value(
        "magi_flex_bwd_form_total", form=BWD_FORM
    ) == before + len(got)
    hq, hk, d = _heads(cell)
    spec = TPU_PEAK_SPECS["v5e"]
    for args in got:
        assert args["bwd_form"] == BWD_FORM
        # ISSUE 44: what dq's protocol meets on the plan's k-major tables.
        # Every row of every cell's masks has a key (and both cp=4 cells
        # run the merged path), so no rank's table leaves a q block out
        # and no cell's backward fills anything: dq=visits everywhere
        assert args["dq_unnamed_q_blocks"] == 0
        assert args["dq_visits_per_tile"] == pytest.approx(
            DQ_VISITS[cell][got.index(args)], abs=5e-4
        )
        bq, bk, hb = args["rung"]
        group = hq // hk
        tile_s = 4 * hb * bq * bk * d / (spec.bf16_tflops * 1e12)

        def step_s(kernel):
            return max(
                cost_model.KERNEL_FLOP_WEIGHTS[kernel] * tile_s,
                cost_model.step_bytes(kernel, bq, bk, hb, group, d, 2)
                / (spec.hbm_gbps * 1e9),
            )

        assert step_s("bwd") < 0.85 * (step_s("dq") + step_s("dkv")), args
    if cell == "sdar30b-train-16k-blockdiff":
        # the share's one reader of the flag word takes bit 0 alone: the
        # k-major word's visit bits left the cell's reading where it was
        assert telemetry.snapshot()["gauges"][
            "magi_flex_stepped_tile_share"
        ] == pytest.approx(25.806, abs=5e-4)


def test_the_dense_cells_plan_reads_fused(telemetry_on):
    """64 / 8 heads of 128, 65,536 tokens, causal: the claimed cell."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]), ("cp",))
    total = 65536
    got = _decisions(
        lambda: api.magi_attn_flex_key(
            [(0, total)], [(0, total)], [1], total, total, mesh,
            num_heads=(64, 8), head_dim=128, out_dtype="bfloat16",
            interpret=False,
        )
    )
    (args,) = got
    assert args["bwd_form"] == "fused" and tuple(args["rung"]) == (1024, 1024, 1)
    (tuned,) = _decisions(
        lambda: (api.clear_cache(), api.magi_attn_flex_key(
            [(0, total)], [(0, total)], [1], total, total, mesh,
            num_heads=(64, 8), head_dim=128, out_dtype="bfloat16",
            interpret=False,
        )),
        "autotune_decision",
    )
    assert tuned["bwd_form"] == "fused"
