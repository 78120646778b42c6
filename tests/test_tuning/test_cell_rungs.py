"""Every plan of every cell of BENCHMARK.json, the timed ones and the
checks', rung for rung (ISSUE 54, ISSUE 56). Host only: each plan is built
from the cell's own traffic and configuration files as its kind builds it,
so the rung is what ``tuning/autotuner.resolve_block_config`` returned, read
from the ``autotune_decision`` event it leaves; nothing runs on a device.

Inside the 15% tie pool the preference order decides
(``cost_model._preference_order``: the long-sequence lead for a mask at a
quarter of its square and more, ISSUE 54; ``_AUTO_BLOCK_CONFIGS``' own order
under it), but for one pair: since ISSUE 56 (128, 512, hb) and (256, 512, hb)
stand in the order of their prices where 256 is the cheaper by
``PAIR_PRICE_MARGIN`` (``cost_model._lead_pairs_by_price``). The plans that
move are exactly those that sat on 128 and whose 256 rung the price puts
ahead; every other plan's rung is the parent's."""

import importlib

import jax
import pytest

from magiattention_tpu import telemetry
from magiattention_tpu.tuning import cost_model, get_tuning_cache

from .test_bwd_form import _cells
from .test_grid_choice import ROOT, _build_cell, _decisions, telemetry_on  # noqa: F401

# cell -> (the timed plans' rungs, the check's plans' rungs) on the parent
# (PR 55's tree: `git archive 01f7932`, this file's `_rungs` run there). An
# attention cell's check samples rows of the timed plan: it plans nothing.
# A model with two kinds of layer plans `full`, then `sliding`.
PARENT = {
    "magi64x8-attn-64k-varlen": (["128x512x8"], []),
    "magi64x8-attn-64k-causal": (["1024x1024x1"], []),
    "mistral7b-train-16k-onemask": (["128x512x8"], ["128x512x8"]),
    "magi64x8-attn-cp4-256k-varlen": (["256x512x8"], []),  # since ISSUE 54
    "trinitymini-train-32k-packed": (
        ["128x512x8", "128x512x8"], ["128x512x8", "128x512x8"],
    ),
    "magi64x8-attn-64k-swa1024": (["128x512x8"], []),
    "glm47flash-train-16k-packed": (["256x512x5"], ["256x512x5"]),
    "magi64x8-attn-64k-chunkcausal": (["1024x1024x1"], []),
    "magi64x8-attn-cp4-256k-causal": (["512x2048x1"], []),
    "ouro26b-train-16k-looped": (["256x512x8"], ["256x512x8"]),
    "zaya1-train-16k-traces": (["128x512x8"], ["128x512x8"]),
    "sdar30b-train-16k-blockdiff": (["128x512x8"], ["128x512x8"]),
    "phi4flash-train-16k-traces": (
        ["128x512x8", "128x512x8"], ["128x512x8", "128x512x8"],
    ),
    "xing4-train-8k-traces": (["256x512x8"], ["256x512x8"]),
    "smallthinker-train-16k-traces": (  # since ISSUE 54
        ["128x512x7", "128x512x7"], ["128x512x7", "128x512x7"],
    ),
    "granite4hmicro-train-packed-traces": (["128x512x8"], ["128x512x8"]),
}
# the plans ISSUE 56 moves, each with the pair's prices in ms, (128, 512, hb)
# then (256, 512, hb), at the plan's own heads (host run of this tree,
# `generation="v5e"`). Where a cell's other plan stays, its 256 is dearer:
# Trinity's sliding plans +2.6% timed and +0.7% in the check, SDAR's check
# +0.5%, phi4's check window +3.9%; Mistral's two +1.1% and +10.0%
MOVED = {
    "magi64x8-attn-64k-varlen": (["256x512x8"], []),  # 93.483 88.533
    "magi64x8-attn-64k-swa1024": (["256x512x8"], []),  # 36.888 35.057
    "trinitymini-train-32k-packed": (  # full 14.160 14.136, check 1.145 1.114
        ["256x512x8", "128x512x8"], ["256x512x8", "128x512x8"],
    ),
    # timed 2.375 2.229, check 0.286 0.278
    "zaya1-train-16k-traces": (["256x512x8"], ["256x512x8"]),
    # timed 9.187 8.649
    "sdar30b-train-16k-blockdiff": (["256x512x8"], ["128x512x8"]),
    "phi4flash-train-16k-traces": (  # 11.877 11.144, 3.634 3.509; 1.431 1.392
        ["256x512x8", "256x512x8"], ["256x512x8", "128x512x8"],
    ),
    "smallthinker-train-16k-traces": (  # 11.177 10.415, 8.377 7.874;
        # check 3.737 3.490, 3.619 3.435
        ["256x512x7", "256x512x7"], ["256x512x7", "256x512x7"],
    ),
    # timed 5.839 5.329, check 0.667 0.658 (32 / 8 heads of 64)
    "granite4hmicro-train-packed-traces": (["256x512x8"], ["256x512x8"]),
}
# the plans whose tie the lead still breaks: the masks it was measured on
LONG_SEQ = {
    "magi64x8-attn-64k-causal",
    "magi64x8-attn-64k-chunkcausal",
    "magi64x8-attn-cp4-256k-causal",
}


def _build_check(name: str):
    """The plan(s) a training cell's check builds for its own, shorter
    sequence; ``None`` for an attention cell."""
    from benchmarks import harness, masks

    cell = harness.load_cell(ROOT, name)
    tr = cell.traffic
    if tr["kind"] == "attn_iter":
        return None
    kind = importlib.import_module("benchmarks.kinds." + tr["kind"])
    job = kind.Job(cell.config, tr, 0, jax.devices()[: cell.chips])
    if tr["kind"] == "train_stream":  # its check builds the mask in line
        mask = masks.build_mask(tr["mask"], int(tr["check_tokens"]), index=0)
    elif tr["kind"] == "train_blockdiff":
        mask = kind.check_mask(job)
    else:
        mask = kind.check_mask(tr)
    return job.build(mask)


def _rungs(name: str) -> tuple[list[dict], list[dict]]:
    """(timed, check): each plan's ``autotune_decision`` event."""
    timed = _decisions(lambda: _build_cell(name), "autotune_decision")
    check = _decisions(lambda: _build_check(name), "autotune_decision")
    return timed, check


def _pair_prices(decision: dict) -> tuple[float, float] | None:
    """(128's, 256's) ``cost_seconds`` in ms of the ranking a decision came
    from, the pair at one ``block_k`` and ``head_block``, read from the
    record the tuning cache keeps under the decision's fingerprint; ``None``
    where the ranking does not hold both."""
    rec = get_tuning_cache()._mem[decision["fingerprint"]]
    rungs = {
        (c["block_q"], c["block_k"], c["head_block"]): c["cost_seconds"] * 1e3
        for c in rec.candidates
        if c["grid"] == "row_major"
    }
    for (bq, bk, hb), small in rungs.items():
        if bq == 128 and (256, bk, hb) in rungs:
            return small, rungs[256, bk, hb]
    return None


def test_the_table_names_every_cell():
    assert list(PARENT) == _cells() and set(MOVED) < set(PARENT)


@pytest.mark.parametrize("cell", list(PARENT))
def test_the_plans_that_move_are_those_whose_256_rung_is_the_cheaper(
    telemetry_on, cell, monkeypatch
):
    for var in ("BLOCK_Q", "BLOCK_K", "HEAD_BLOCK", "AUTOTUNE"):
        monkeypatch.delenv("MAGI_ATTENTION_" + var, raising=False)
    timed, check = _rungs(cell)
    got = ([d["rung"] for d in timed], [d["rung"] for d in check])
    prices = [_pair_prices(d) for d in timed + check]
    shown = [p and tuple(round(x, 3) for x in p) for p in prices]
    # PERF.md section 6's row
    print(f"| `{cell}` | {PARENT[cell]} | {got} | {shown} |")
    assert got == MOVED.get(cell, PARENT[cell])
    was = PARENT[cell][0] + PARENT[cell][1]
    for d, parent, pair in zip(timed + check, was, prices):
        moved = d["rung"] != parent
        # a plan moves only off (128, 512, hb), only to (256, 512, hb), and
        # exactly where the price puts 256 ahead by the margin
        cheaper = (
            parent.startswith("128x512x")
            and pair[1] < pair[0] * (1 - cost_model.PAIR_PRICE_MARGIN)
        )
        assert moved == cheaper, (d["rung"], parent, pair)
        if moved:
            assert d["rung"] == parent.replace("128x", "256x")
        # the lead breaks the tie of the dense masks at 65,536 rows and
        # more, the pair's price that of the moved plans, the table's own
        # order every other; the cp=4 dense plan has no tie to break (no
        # rung fits: the escalation order), but its ranking names the lead
        # all the same
        assert d["tie_order"] == (
            "long_seq" if cell in LONG_SEQ
            else "priced_pair" if moved else "measured"
        )
    if cell == "smallthinker-train-16k-traces":
        # the timed and the checked full kernels are one rung (ISSUE 54)
        assert got[0][0] == got[1][0]


def test_the_counter_says_which_order_broke_the_tie(telemetry_on):
    """``magi_autotune_decisions_total{tie_order=}`` and the decision's
    event: ``priced_pair`` for SmallThinker's two plans, ``measured`` for
    Mistral's, ``long_seq`` for the dense causal plan."""
    reg = telemetry.get_registry()
    orders = ("measured", "long_seq", "priced_pair")

    def counts():
        return [
            reg.counter_value(
                "magi_autotune_decisions_total", bound="mxu", tie_order=o
            )
            for o in orders
        ]

    def after(cell, rungs, order):
        before = counts()
        got = _decisions(lambda: _build_cell(cell), "autotune_decision")
        assert [(d["rung"], d["tie_order"]) for d in got] == [
            (r, order) for r in rungs
        ]
        return [b - a for a, b in zip(before, counts())]

    assert after(
        "smallthinker-train-16k-traces", ["256x512x7"] * 2, "priced_pair"
    ) == [0, 0, 2]
    assert after(
        "mistral7b-train-16k-onemask", ["128x512x8"], "measured"
    ) == [1, 0, 0]
    assert after(
        "magi64x8-attn-64k-causal", ["1024x1024x1"], "long_seq"
    ) == [0, 1, 0]
