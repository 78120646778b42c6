"""Every plan of every cell of BENCHMARK.json, the timed ones and the
checks', rung for rung (ISSUE 54). Host only: each plan is built from the
cell's own traffic and configuration files as its kind builds it, so the
rung is what ``tuning/autotuner.resolve_block_config`` returned, read from
the ``autotune_decision`` event it leaves; nothing runs on a device.

The long-sequence lead of the tie order (``cost_model._preference_order``)
was measured on one dense 64k slice. Since ISSUE 54 a mask under
``SPARSE_DENSITY_THRESHOLD`` does not get it, and two plans left the dense
cells' per-head rung for a head-batched one. Every other plan's rung is
the parent's: its winner was no long-sequence rung, so the lead never
decided it."""

import importlib

import jax
import pytest

from magiattention_tpu import telemetry

from .test_bwd_form import _cells
from .test_grid_choice import ROOT, _build_cell, _decisions, telemetry_on  # noqa: F401

# cell -> (the timed plans' rungs, the check's plans' rungs) on the parent
# (PR 53's tree: `git archive 559e74a`, this file's `_rungs` run there). An
# attention cell's check samples rows of the timed plan: it plans nothing.
# A model with two kinds of layer plans `full`, then `sliding`.
PARENT = {
    "magi64x8-attn-64k-varlen": (["128x512x8"], []),
    "magi64x8-attn-64k-causal": (["1024x1024x1"], []),
    "mistral7b-train-16k-onemask": (["128x512x8"], ["128x512x8"]),
    "magi64x8-attn-cp4-256k-varlen": (["1024x1024x1"], []),
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
    "smallthinker-train-16k-traces": (
        ["1024x1024x1", "128x512x7"], ["128x512x7", "128x512x7"],
    ),
    # no parent of ISSUE 54 had it (ISSUE 55's cell): the rungs it was
    # handed in with, 32 / 8 heads of 64 on five and on three documents
    "granite4hmicro-train-packed-traces": (["128x512x8"], ["128x512x8"]),
}
# the two plans ISSUE 54 moves: 23.1% and 3.7% of the square, tied within
# 15% (+8% and +13% over the cheapest), decided until now by the lead
MOVED = {
    "smallthinker-train-16k-traces": (
        ["128x512x7", "128x512x7"], ["128x512x7", "128x512x7"],
    ),
    "magi64x8-attn-cp4-256k-varlen": (["256x512x8"], []),
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


def test_the_table_names_every_cell():
    assert list(PARENT) == _cells() and set(MOVED) < set(PARENT)


@pytest.mark.parametrize("cell", list(PARENT))
def test_every_plan_keeps_the_parents_rung_but_the_two_named(
    telemetry_on, cell, monkeypatch
):
    for var in ("BLOCK_Q", "BLOCK_K", "HEAD_BLOCK", "AUTOTUNE"):
        monkeypatch.delenv("MAGI_ATTENTION_" + var, raising=False)
    timed, check = _rungs(cell)
    got = ([d["rung"] for d in timed], [d["rung"] for d in check])
    print(f"| `{cell}` | {PARENT[cell]} | {got} |")  # PERF.md section 6's row
    assert got == MOVED.get(cell, PARENT[cell])
    # the lead breaks the tie of the dense masks at 65,536 rows and more,
    # and of no other plan; the cp=4 dense plan has no tie to break (no rung
    # fits: the escalation order), but its ranking names the lead all the same
    for d in timed + check:
        assert d["tie_order"] == (
            "long_seq" if cell in LONG_SEQ else "measured"
        )
    if cell == "smallthinker-train-16k-traces":
        # the timed and the checked full kernels are one rung again
        assert got[0][0] == got[1][0]


def test_the_counter_says_how_often_the_lead_was_used(telemetry_on):
    """``magi_autotune_decisions_total{tie_order=}`` and the decision's
    event: ``measured`` for SmallThinker's two plans, ``long_seq`` for the
    dense causal plan."""
    reg = telemetry.get_registry()

    def count(order):
        return reg.counter_value(
            "magi_autotune_decisions_total", bound="mxu", tie_order=order
        )

    before = {o: count(o) for o in ("measured", "long_seq")}
    full, sliding = _decisions(
        lambda: _build_cell("smallthinker-train-16k-traces"),
        "autotune_decision",
    )
    assert (full["tie_order"], sliding["tie_order"]) == ("measured",) * 2
    assert (count("measured"), count("long_seq")) == (
        before["measured"] + 2, before["long_seq"],
    )
    (dense,) = _decisions(
        lambda: _build_cell("magi64x8-attn-64k-causal"), "autotune_decision"
    )
    assert (dense["rung"], dense["tie_order"]) == ("1024x1024x1", "long_seq")
    assert count("long_seq") == before["long_seq"] + 1
