"""What ``train_pattern``'s ``correct`` can see, the new configuration's
files, and the command's own path for a pattern-driven cell. Toy size,
CPU (``data/toy_pattern``: a benchmark of new files only)."""

import io
import json
import os
from contextlib import redirect_stdout

import numpy as np
import pytest

from benchmarks import flops, flops_afmoe, harness, masks, reference_afmoe
from benchmarks.kinds import train_pattern

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TOY = os.path.join(HERE, "data", "toy_pattern")
SLIDING, FULL = "sliding_attention", "full_attention"
CELL = "trinitymini-train-32k-packed"


@pytest.fixture(scope="module")
def readings():
    """The check's readings by what the model was handed; the reference
    always gets the configuration and the weights as they are."""
    import jax
    import jax.numpy as jnp

    from magiattention_tpu.models.pattern import init_pattern_params

    cell = harness.load_cell(TOY, "toy.pattern")
    # the toy traffic file says float32 (its rehearsals check a model a
    # few AdamW steps old); the readings are of bf16, as the cell runs
    cfg, tr = cell.config, dict(cell.traffic, dtype="bfloat16")
    dev = jax.devices()[:1]
    found = {}
    with jax.enable_x64(False):
        job = train_pattern.Job(cfg, tr, 2**31 + 7, dev)
        params = init_pattern_params(
            train_pattern.key_from_seed(job.seed), job.pcfg
        )

        def other(**fields):
            return {"model_job": train_pattern.Job(
                cfg, tr, job.seed, dev, fields
            )}

        faults = {
            "bf16, as the cell runs": {},
            "float32 model": {"model_job": train_pattern.Job(
                cfg, dict(tr, dtype="float32"), job.seed, dev
            )},
            "sliding layers given the global mask": other(sliding_window=None),
            "global layers given rotary": other(rope_kinds=(SLIDING, FULL)),
            "the gate left out": other(attn_gate=False),
            "route_norm left out": other(route_norm=False),
            "bf16 router": other(router_dtype="bfloat16"),
            "fp8 weights": {"model_params": jax.tree.map(
                lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype), params
            )},
            "the reference left to its own choices": {"free_routing": True},
        }
        for name, fault in faults.items():
            found[name] = train_pattern.check_errors(job, params, **fault)
    return found


def test_the_cell_as_it_runs_passes(readings):
    for name in ("bf16, as the cell runs", "float32 model"):
        assert train_pattern.passes(*readings[name]), (name, readings[name])
    rel, grad, routing = readings["float32 model"]
    # float32 against float32 agrees far inside what bf16 is allowed,
    # and makes the reference's own choices
    assert max(grad.values()) < 1e-4 and routing["flipped_share"] == 0.0


@pytest.mark.parametrize("fault", [
    "sliding layers given the global mask", "global layers given rotary",
    "the gate left out", "route_norm left out", "bf16 router", "fp8 weights",
])
def test_a_fault_fails_the_check(readings, fault):
    rel, grad, routing = readings[fault]
    assert not train_pattern.passes(rel, grad, routing), (fault, grad, routing)
    # by a gradient, at twice its tolerance or more: not by the routing
    # criteria alone
    assert any(
        e > 2 * (train_pattern.EXPERT_GRAD_REL_L2_TOL
                 if n in train_pattern.EXPERT_PATH
                 else train_pattern.GRAD_REL_L2_TOL)
        for n, e in grad.items()
    ), (fault, grad)


def test_the_loss_alone_would_miss_every_fault_but_one(readings):
    """Why the gradients are compared: at random init the loss is about
    ln(vocabulary) whatever the model does."""
    within = [
        name for name, (rel, _g, _r) in readings.items()
        if rel <= train_pattern.LOSS_REL_TOL
    ]
    assert "global layers given rotary" in within
    assert "bf16 router" in within


def test_why_the_reference_follows_the_models_choices(readings):
    """bf16 activations flip near-ties of the top-k; left to its own
    choices the reference then computes another function, and the
    expert path's gradients move by more than bf16 itself moves them."""
    _rel, forced, routing = readings["bf16, as the cell runs"]
    _rel, free, _r = readings["the reference left to its own choices"]
    assert 0.0 < routing["flipped_share"] <= train_pattern.ROUTE_FLIP_SHARE_TOL
    assert 0.0 < routing["worst_margin"] <= train_pattern.ROUTE_MARGIN_TOL
    assert free["w_router"] > 2 * forced["w_router"]


def test_a_router_that_chooses_otherwise_fails_by_its_choices():
    """With the reference following the model, the gradients cannot see
    a wrong choice; the routing criteria do."""
    ok = {"flipped_share": 0.01, "worst_margin": 0.009}
    grad = {"wq": 0.03, "w_router": 0.09}
    assert train_pattern.passes(1e-5, grad, ok)
    assert not train_pattern.passes(1e-5, grad, dict(ok, worst_margin=0.2))
    assert not train_pattern.passes(1e-5, grad, dict(ok, flipped_share=0.2))
    assert not train_pattern.passes(1e-5, dict(grad, wq=0.09), ok)
    assert not train_pattern.passes(1e-5, dict(grad, w_router=0.2), ok)


def test_the_check_has_a_document_longer_than_the_window():
    """At ``check_tokens`` the quantile rule caps a document at a quarter
    of the sequence, under the published window: the traffic file names
    the check's documents itself."""
    cell = harness.load_cell(REPO, CELL)
    mask = train_pattern.check_mask(cell.traffic)
    window = cell.config["sliding_window"]
    assert mask.total == cell.traffic["check_tokens"]
    assert max(mask.doc_lengths) > window > min(mask.doc_lengths)
    blind = masks.build_mask(cell.traffic["mask"], mask.total, index=0)
    assert max(blind.doc_lengths) <= window


# ---------------------------------------------------------------------------
# the configuration, its masks and its operation counts
# ---------------------------------------------------------------------------


def test_trinity_mini_states_its_widths_as_published():
    cfg = harness.load_cell(REPO, CELL).config
    published = {
        "hidden_size": 2048, "num_attention_heads": 32,
        "num_key_value_heads": 4, "head_dim": 128, "sliding_window": 2048,
        "intermediate_size": 6144, "moe_intermediate_size": 1024,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_shared_experts": 1, "route_scale": 2.826, "route_norm": True,
        "score_func": "sigmoid", "vocab_size": 200192, "rope_theta": 10000,
        "mup_enabled": True, "rms_norm_eps": 1e-05,
        "max_position_embeddings": 131072, "model_type": "afmoe",
    }
    assert {k: cfg[k] for k in published} == published
    assert set(cfg["reduced"]) == {
        "num_hidden_layers", "layer_types", "num_dense_layers",
        "experts_here", "vocab_here",
    }
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "trinity-mini")
    assert set(entry["reduced"]) == set(cfg["reduced"])
    assert entry["source"] == (
        "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json"
    )
    # one whole period of the pattern behind one leading dense layer
    assert cfg["layer_types"] == [SLIDING] * 4 + [FULL]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"]) == (5, 1)
    assert cfg["experts_here"] == [0, 8] and cfg["vocab_here"] == 12512
    assert cfg["deployment"]["chips"] == 16
    assert 16 * 8 == cfg["num_experts"] and 16 * 12512 == cfg["vocab_size"]
    for key in ("router", "rotary", "attention_gate", "norms", "expert_bias"):
        assert key in cfg["assumed"]


def test_the_cells_masks_are_the_issues():
    cell = harness.load_cell(REPO, CELL)
    mask = masks.build_mask(cell.traffic["mask"], 32768, index=0)
    assert (len(mask.doc_lengths), min(mask.doc_lengths),
            max(mask.doc_lengths)) == (29, 31, 7117)
    assert mask.area == 52_178_708
    window = cell.config["sliding_window"]
    assert train_pattern.window_area(mask.doc_lengths, window) == 35_014_957
    assert sum(n > window for n in mask.doc_lengths) == 4
    swa = harness.load_cell(REPO, "magi64x8-attn-64k-swa1024")
    assert swa.traffic["kind"] == "attn_iter"
    assert masks.build_mask(swa.traffic["mask"], 65536).area == 66_585_088


@pytest.mark.parametrize("docs,window", [
    ([5, 1, 9], 4), ([16], 16), ([16], 1), ([3, 3, 3], 8), ([40, 2], 7),
])
def test_window_area_and_window_allowed_agree_with_brute_force(docs, window):
    import jax.numpy as jnp

    total = sum(docs)
    mask = masks.build_mask(
        {"type": "varlen_block_causal", "lengths": docs}, total
    )
    rows = np.arange(total)
    allow = reference_afmoe.window_allowed(
        jnp.asarray(masks.allowed(mask, rows, rows)), window
    )
    brute = np.zeros((total, total), bool)
    start = 0
    for n in docs:
        for q in range(start, start + n):
            brute[q, max(start, q - window + 1): q + 1] = True
        start += n
    assert np.array_equal(np.asarray(allow), brute)
    assert train_pattern.window_area(docs, window) == int(brute.sum())


def test_the_sliding_slices_the_program_plans_cover_the_window_mask():
    """The builder's slices (``infer_attn_mask_from_cu_seqlens`` with a
    window) against the definition the reference uses."""
    from magiattention_tpu.api.functools import infer_attn_mask_from_cu_seqlens

    docs, window = [150, 40, 66], 48
    total = sum(docs)
    cu = [0, *np.cumsum(docs).tolist()]
    q, k, ts = infer_attn_mask_from_cu_seqlens(
        cu, causal=False, window_size=(window - 1, 0)
    )
    sliced = masks.slices_to_dense(masks.Mask(
        "slices", total, tuple(q.to_naive_ranges()),
        tuple(k.to_naive_ranges()), tuple(int(t) for t in ts), 0,
    ))
    assert int(sliced.sum()) == train_pattern.window_area(docs, window)
    assert masks.BICAUSAL in [int(t) for t in ts]


def test_flops_of_a_step():
    cfg = harness.load_cell(REPO, CELL).config
    # ISSUE 26's arithmetic: attention 27.26 M with its gate, an expert
    # 6.29 M, and the share every token meets
    assert flops_afmoe.attn_params(cfg) == 27_262_976
    assert flops_afmoe.expert_params(cfg) == 6_291_456
    per_token = (
        5 * 27_262_976 + 3 * 2048 * 6144 + 4 * (6_291_456 + 2048 * 128)
        + 2048 * 12512
    )
    assert flops_afmoe.per_token_params(cfg) == per_token
    areas = {FULL: 52_178_708, SLIDING: 35_014_957}
    got = flops_afmoe.train_step_flops(cfg, 32768, areas, 60_000.0)
    attn = (
        4 * flops.attn_fwdbwd_flops(areas[SLIDING], 32, 128)
        + flops.attn_fwdbwd_flops(areas[FULL], 32, 128)
    )
    assert got == pytest.approx(
        6.0 * per_token * 32768 + 6.0 * 60_000 * 6_291_456 + attn
    )
    # what the kernels execute under remat: forward twice and the backward
    assert flops_afmoe.attn_executed_flops(cfg, FULL, areas[FULL]) == (
        pytest.approx(4.5 * flops.attn_fwd_flops(areas[FULL], 32, 128))
    )
    assert flops_afmoe.attn_executed_flops(cfg, SLIDING, areas[SLIDING]) == (
        pytest.approx(4 * 4.5 * flops.attn_fwd_flops(areas[SLIDING], 32, 128))
    )


def test_the_new_metric_files_match_the_scopes_the_program_sets():
    """The patterns of the per-kind metrics against operation names and
    scopes as the chip's compiler prints them (compile for a described
    v5e, PR 26)."""
    import re

    cell = harness.load_cell(REPO, CELL)
    spec = {m["name"]: m["source"] for m in cell.per_layer}
    base = "jit(step)/transpose(jvp())/checkpoint/"
    ops = {
        "sliding": "magi_flex_dkv_kernel.8 " + base
        + "magi_attn_sliding/magi_merged_kernel/magi_flex_dkv_kernel/pallas_call",
        "full": "magi_flex_fwd_kernel.15 " + base + "rematted_computation/"
        "magi_attn_full/magi_merged_kernel/magi_flex_fwd_kernel/pallas_call",
        "moe": "fusion.12 " + base + "magi_moe_experts/while/body/cond/mul",
        "grouped": "ragged-dot-none.32 ragged-dot-none",
        # a container: its body's operations are in the trace too
        "loop": "while.437 " + base + "magi_moe_experts/while",
        "branch": "cond.394 " + base + "magi_moe_experts/while/body/cond",
        "other": "fusion.3 " + base + "dot_general",
    }

    def hits(metric):
        rx = re.compile(spec[metric]["pattern"])
        return {k for k, op in ops.items() if rx.search(op)}

    assert hits("train_sliding_flex_share") == {"sliding"}
    assert hits("train_full_flex_share") == {"full"}
    assert hits("train_sliding_flex_roofline") == {"sliding"}
    assert hits("train_full_flex_roofline") == {"full"}
    assert hits("train_moe_share") == {"moe", "grouped"}
    assert hits("train_flex_kernel_share") == {"sliding", "full"}
    assert spec["train_sliding_flex_roofline"]["flops"] == "attn_sliding_executed"
    assert spec["train_full_flex_roofline"]["flops"] == "attn_full_executed"


# ---------------------------------------------------------------------------
# the command's own path
# ---------------------------------------------------------------------------


@pytest.fixture
def _as_the_command_runs():
    import jax

    with jax.enable_x64(False):
        yield


@pytest.mark.parametrize(
    "workload,trace",
    [("toy.pattern", 0), ("toy.pattern", 1), ("toy.pattern-cp4", 0)],
)
def test_rehearsal_prints_the_result_line(workload, trace, _as_the_command_runs):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = harness.main(
            ["--workload", workload, "--seed", str(2**31 + 12345),
             "--seconds", "1.5", "--trace", str(trace), "--root", TOY],
            allow_cpu=True,
        )
    assert rc == 0
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert res["device"]["count"] == (4 if workload.endswith("cp4") else 1)
    if not trace:
        assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
        assert all(m["value"] > 0 for m in res["metrics"].values())
    else:
        # no device trace and no peak on the CPU: those readers find
        # nothing and the line leaves their metrics out
        assert set(res["metrics"]) == {"train_step_steady_ms", "moe_pairs_here"}
        assert res["metrics"]["moe_pairs_here"]["value"] > 0
