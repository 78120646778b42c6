"""What ``train_prerouted``'s ``correct`` can see,
SmallThinker-21BA3B-Instruct's configuration files, mask and operation
counts, and the command's own path for the cell. Toy size, CPU
(``data/toy_prerouted``: a benchmark of new files only)."""

import io
import json
import os
import re
from contextlib import redirect_stdout

import pytest

from benchmarks import flops, flops_smallthinker, harness, masks
from benchmarks.kinds import train_prerouted
from benchmarks.kinds.train_pattern import FULL, SLIDING, window_area

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TOY = os.path.join(HERE, "data", "toy_prerouted")
CELL = "smallthinker-train-16k-traces"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

FAULTS = {
    "the window ignored": {"sliding_window": None},
    "rotary on the full layers": {
        "rope_kinds": ("sliding_attention", "full_attention")
    },
    "the router fed the FFN half's norm": {"router_input": "ffn"},
    "SiLU for ReLU": {"expert_act": "silu"},
    "the softmax not renormalised over the chosen": {"route_norm": False},
}
# what the check cannot tell apart inside a bf16 model, at the toy's widths
# as at Trinity's (``train_pattern``'s note): a bfloat16 router. It is held
# in float32 at toy size (tests/test_models/test_pattern_prerouted.py)


@pytest.fixture(scope="module")
def readings():
    """The check's readings by what the model was handed; the reference
    always gets the configuration and the weights as they are."""
    import jax
    import jax.numpy as jnp

    from magiattention_tpu.models.pattern import init_pattern_params

    cell = harness.load_cell(TOY, "toy.prerouted")
    cfg, tr = cell.config, dict(cell.traffic, dtype="bfloat16")
    dev = jax.devices()[:1]
    found = {}
    with jax.enable_x64(False):
        job = train_prerouted.Job(cfg, tr, 1, dev)
        params = init_pattern_params(
            train_prerouted.key_from_seed(job.seed), job.pcfg
        )
        handed = {
            "bf16, as the cell runs": {},
            "float32 model": {"model_job": train_prerouted.Job(
                cfg, dict(tr, dtype="float32"), job.seed, dev
            )},
            "fp8 weights": {"model_params": jax.tree.map(
                lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype), params
            )},
        }
        for name, overrides in FAULTS.items():
            handed[name] = {"model_job": train_prerouted.Job(
                cfg, tr, job.seed, dev, overrides
            )}
        for name, fault in handed.items():
            found[name] = train_prerouted.check_errors(job, params, **fault)
    return found


def test_the_cell_as_it_runs_passes(readings):
    for name in ("bf16, as the cell runs", "float32 model"):
        assert train_prerouted.passes(*readings[name]), (name, readings[name])
    rel, grad, routing = readings["float32 model"]
    # float32 against float32 agrees far inside what bf16 is allowed
    assert rel < 1e-5 and max(grad.values()) < 1e-4
    assert routing == {"flipped_share": 0.0, "worst_margin": 0.0}
    assert set(grad) == {
        "embed", "final_norm", "lm_head", "attn_norm", "mlp_norm", "wq", "wk",
        "wv", "wo", "w_router", "we_gate", "we_up", "we_down",
    }


@pytest.mark.parametrize("fault", ["fp8 weights", *FAULTS])
def test_a_fault_fails_the_check(readings, fault):
    rel, grad, routing = readings[fault]
    assert not train_prerouted.passes(rel, grad, routing), (
        fault, rel, grad, routing
    )


def test_a_route_made_on_the_wrong_input_is_held_by_its_choices(readings):
    """A router fed the FFN half's norm chooses other experts than the
    reference's router on the attention half's: a large share of the pairs
    flip, by wide margins, whatever the gradients read."""
    _rel, _grad, routing = readings["the router fed the FFN half's norm"]
    assert routing["flipped_share"] > 4 * train_prerouted.ROUTE_FLIP_SHARE_TOL
    assert routing["worst_margin"] > 4 * train_prerouted.ROUTE_MARGIN_TOL


@pytest.fixture(scope="module")
def timed():
    """The toy's compiled step called once on the seed's weights, as
    ``run`` calls the window's before it warms up."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from magiattention_tpu.models.pattern import init_pattern_params

    cell = harness.load_cell(TOY, "toy.prerouted")
    cfg, tr = cell.config, cell.traffic
    with jax.enable_x64(False):
        job = train_prerouted.Job(cfg, tr, 7, jax.devices()[:1])
        mask = masks.build_mask(tr["mask"], tr["total_tokens"], index=0)
        model, meta = job.build(mask)
        params = init_pattern_params(
            train_prerouted.key_from_seed(job.seed), job.pcfg
        )
        tokens_g, *batch = job.batch_for(meta, mask, 0)
        choices = np.asarray(jax.jit(lambda p, *b: model.loss_fn(
            p, *b, model.sharded_tables(), with_stats=True
        )[1])(params, *batch)["expert_idx"])[0]
        opt = optax.adamw(float(tr["learning_rate"]))
        step = model.make_train_step(opt)

        def first_call(batch):
            mine = jax.tree.map(jnp.copy, params)  # the step donates
            after, _opt, loss = step(mine, opt.init(mine), *batch)
            return float(loss), after

        yield job, mask, meta, tokens_g, choices, params, first_call, batch


@pytest.mark.parametrize("fault", [None, "no update", "twice the rate"])
def test_the_timed_step_is_held_at_its_own_size(timed, fault):
    """What ``correct`` holds of the program the window times: its first
    loss against the reference's forward pass on the window's documents,
    and its first update against AdamW's first step."""
    import jax

    job, mask, meta, tokens_g, choices, params, first_call, batch = timed
    lr = float(job.tr["learning_rate"])
    with jax.enable_x64(False):
        loss, after = first_call(batch)
        if fault == "no update":
            after = params
        moved = train_prerouted.update_share(
            params, after, lr / 2 if fault == "twice the rate" else lr
        )
        rel = train_prerouted.timed_loss_error(
            job, params, mask, meta, tokens_g, choices, loss
        )
    assert train_prerouted.timed_step_passes(rel, moved) == (fault is None), (
        fault, rel, moved
    )
    if fault is None:  # float32 toy: the two agree far inside the limit
        assert rel < 1e-5 and 0.9 < moved < 1.001
    else:
        assert moved == pytest.approx(0.0 if fault == "no update" else 2.0, abs=0.2)


# ---------------------------------------------------------------------------
# the configuration, its mask and its operation counts
# ---------------------------------------------------------------------------


def test_smallthinker_states_its_widths_as_published():
    cell = harness.load_cell(REPO, CELL)
    cfg = cell.config
    published = {
        "hidden_size": 2560, "head_dim": 128, "num_attention_heads": 28,
        "num_key_value_heads": 4, "moe_ffn_hidden_size": 768,
        "moe_num_primary_experts": 64, "moe_num_active_primary_experts": 6,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "sliding_window_size": 4096, "rope_theta": 1500000,
        "rms_norm_eps": 1e-06, "max_position_embeddings": 16384,
        "vocab_size": 151936, "tie_word_embeddings": False,
        "rope_scaling": None,
    }
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_layout"] == cfg["sliding_window_layout"] == [0, 1, 1, 1] * 13
    if os.path.exists(CATALOG):  # every key of the catalog row's config
        with open(CATALOG) as f:
            row = next(
                r for r in map(json.loads, f)
                if r["name"] == "SmallThinker-21BA3B-Instruct"
            )
        differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differs == {"num_hidden_layers"}
        assert row["source_url"] in cfg["source"]
    assert list(cfg["reduced"]) == [
        "num_hidden_layers", "experts_here", "vocab_here"
    ]
    # two whole periods, the floors of the experts and the vocabulary
    assert cfg["num_hidden_layers"] == 8 and 8 % 4 == 0
    assert "52 published" in cfg["reduced"]["num_hidden_layers"]
    assert cfg["experts_here"] == [0, 8] and "8 of the 64" in (
        cfg["reduced"]["experts_here"]
    )
    assert cfg["vocab_here"] == 18992 == 151936 // 8
    assert cfg["published"] == {
        "num_hidden_layers": 52, "moe_num_primary_experts": 64,
        "vocab_size": 151936, "deployment": "8 chips share a layer",
    }
    assert cfg["deployment"]["chips"] == 8
    assert "98,304" in cfg["deployment"]["distorts"]
    assert cfg["flat_expert_rows"] is True
    for key in ("router_input", "expert_act", "no_qk_norm_gate_bias", "rotary",
                "window", "no_secondary_experts", "router", "initializer",
                "flat_expert_rows"):
        assert key in cfg["assumed"]
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(
        c for c in bench["configs"] if c["name"] == "smallthinker-21b-a3b"
    )
    assert entry["reduced"] == list(cfg["reduced"])
    assert entry["source"] == (
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/"
        "blob/main/config.json"
    )
    assert (cell.chips, cell.config_name) == (1, "smallthinker-21b-a3b")
    assert cell.traffic["kind"] == "train_prerouted"
    assert cell.end_to_end == ["train_tokens_per_s", "setup_s"]


def test_the_cells_masks_are_the_issues():
    """The full layers' and the window layers' areas by the closed form,
    by the program's slices and by the reference's dense definition; one
    document 2.5 windows long, one exactly a window; the check's mask has
    a document the window binds."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import reference_smallthinker
    from benchmarks.kinds.train_sambay import doc_ids
    from magiattention_tpu.api.functools import infer_attn_mask_from_cu_seqlens
    from magiattention_tpu.tuning.cost_model import exact_mask_area

    cell = harness.load_cell(REPO, CELL)
    tr, window = cell.traffic, cell.config["sliding_window_size"]
    mask = masks.build_mask(tr["mask"], tr["total_tokens"], index=0)
    assert mask.total == 16384 == cell.config["max_position_embeddings"]
    assert mask.doc_lengths == (10240, 4096, 1536, 512)
    assert (10240 / window, 4096 / window) == (2.5, 1.0)
    assert mask.area == 62_136_320
    assert round(mask.describe()["causal_share_pct"], 2) == 46.29
    under = window_area(mask.doc_lengths, window)
    assert under == 43_258_880 and round(100 * under / mask.area, 1) == 69.6
    q, k, t = infer_attn_mask_from_cu_seqlens(
        mask.cu_seqlens, causal=False, window_size=(window - 1, 0)
    )
    assert exact_mask_area(
        q.to_naive_ranges(), k.to_naive_ranges(), [int(x) for x in t]
    ) == under
    check = train_prerouted.check_mask(tr)
    assert (check.total, check.doc_lengths) == (8192, (6144, 1536, 512))
    assert max(check.doc_lengths) > window  # or the check is blind to it
    # the reference's dense definition, counted in row blocks
    doc = jnp.asarray(doc_ids(check))
    place = reference_smallthinker.places(doc)
    assert np.asarray(place)[[0, 6143, 6144, 8191]].tolist() == [0, 6143, 0, 511]
    rows = jnp.arange(check.total)
    for windowed, want in (
        (False, check.area), (True, window_area(check.doc_lengths, window)),
    ):
        got = sum(
            int(reference_smallthinker.allowed(
                doc, place, rows[a:a + 1024], jnp.asarray(windowed), window
            ).sum())
            for a in range(0, check.total, 1024)
        )
        assert got == want


def test_flops_of_a_step_by_hand():
    """At the toy's size, every term written out; then the cell's."""
    cfg = harness.load_cell(TOY, "toy.prerouted").config
    d, hd = 128, 16
    attn = d * hd * (2 * 14 + 2 * 2)
    assert flops_smallthinker.expert_params(cfg) == 3 * d * 64
    per_row = 2 * (attn + d * 16)
    assert flops_smallthinker.per_row_params(cfg) == per_row
    assert flops_smallthinker.head_params(cfg) == d * 512
    assert flops_smallthinker.kind_layers(cfg, FULL) == 1
    assert flops_smallthinker.kind_layers(cfg, SLIDING) == 1
    areas, tokens, pairs = {FULL: 12_345, SLIDING: 6_789}, 256, 700.0
    fwd = {k: 4.0 * a * 14 * hd for k, a in areas.items()}
    assert flops_smallthinker.train_step_flops(cfg, tokens, areas, pairs) == (
        6.0 * (per_row + d * 512) * tokens + 6.0 * pairs * 3 * d * 64
        + 3.5 * (fwd[FULL] + fwd[SLIDING])
    )
    # what a step executes in the flex kernels: one forward and one
    # backward launch a layer (the next test counts them), 3.5 x forward
    assert flops_smallthinker.EXECUTED_OVER_FWD == 3.5
    for kind in (FULL, SLIDING):
        assert flops_smallthinker.attn_executed_flops(
            cfg, kind, areas[kind]
        ) == 3.5 * fwd[kind]
    # ISSUE 53's counts for the cell
    cfg = harness.load_cell(REPO, CELL).config
    assert flops_smallthinker.kind_layers(cfg, FULL) == 2
    assert flops_smallthinker.kind_layers(cfg, SLIDING) == 6
    assert flops.attn_fwd_flops(62_136_320, 28, 128) == pytest.approx(
        0.891e12, rel=1e-3
    )
    assert flops.attn_fwd_flops(43_258_880, 28, 128) == pytest.approx(
        0.620e12, rel=1e-3
    )
    executed = sum(
        flops_smallthinker.attn_executed_flops(cfg, kind, area)
        for kind, area in ((FULL, 62_136_320), (SLIDING, 43_258_880))
    )
    assert executed == pytest.approx(19.3e12, rel=5e-3)
    assert flops_smallthinker.per_row_params(cfg) == 8 * (20_971_520 + 163_840)
    assert flops_smallthinker.expert_params(cfg) == 5_898_240


def test_the_executed_count_is_the_steps_launches():
    """``flops_smallthinker.LAUNCHES`` against the flex kernels of the toy
    step's gradient under remat: a layer's forward once (its out and lse
    are kept across the checkpoint), its backward once. A step that ran
    the forward again would make both rooflines under-read, one that this
    file counted twice makes them over-read."""
    import jax

    from magiattention_tpu.models.pattern import init_pattern_params
    from tests.test_models.pattern_harness import _kernels_by_name

    cell = harness.load_cell(TOY, "toy.prerouted")
    with jax.enable_x64(False):
        job = train_prerouted.Job(
            cell.config, cell.traffic, 0, jax.devices()[:1]
        )
        assert job.pcfg.remat
        mask = masks.build_mask(
            cell.traffic["mask"], cell.traffic["total_tokens"], index=0
        )
        model, meta = job.build(mask)
        params = jax.eval_shape(
            lambda r: init_pattern_params(r, job.pcfg), jax.random.PRNGKey(0)
        )
        _g, *batch = job.batch_for(meta, mask, 0)
        jaxpr = jax.make_jaxpr(jax.value_and_grad(model.loss_fn))(
            params, *batch, model.sharded_tables()
        ).jaxpr
    kernels = _kernels_by_name(jaxpr)
    by_pass = {
        which: sum(n for name, n in kernels.items() if f"_{which}_" in name)
        for which in ("fwd", "bwd")
    }
    assert sum(by_pass.values()) == sum(kernels.values()), kernels
    layers = job.pcfg.n_layers
    assert by_pass == {
        which: layers * n for which, n in flops_smallthinker.LAUNCHES.items()
    }, kernels


def test_the_metric_files_match_the_scopes_the_program_sets():
    """The patterns the cell was appended to against operation names as the
    chip's compiler prints them for this model (the router's under the
    layer's checkpoint, before the attention kernel), and the new metric
    against the series the program sets."""
    from magiattention_tpu.telemetry import collectors

    cell = harness.load_cell(REPO, CELL)
    spec = {m["name"]: m["source"] for m in cell.per_layer}
    base = "jit(step)/jit(main)/jit(shmap_body)/"
    ops = {
        "embed": "fusion.3 " + base + "magi_embed/gather",
        "head": "fusion.30 " + base + "magi_head/dot_general",
        "router": "fusion.201 " + base + "checkpoint/magi_moe_router/"
        "dot_general",
        "router_bwd": "fusion.77 " + base + "transpose(jvp(checkpoint))/"
        "magi_moe_router/dot_general",
        "proj": "fusion.31 " + base + "checkpoint/magi_proj/dot_general",
        "ffn": "fusion.41 " + base + "checkpoint/magi_ffn/mul",
        "experts": "fusion.52 " + base + "checkpoint/magi_moe_experts/"
        "magi_moe_matmul/ragged_dot",
        "flex_full": "magi_flex_fwd_kernel.2 " + base + "checkpoint/"
        "magi_attn_full/magi_merged_kernel/magi_flex_fwd_kernel/pallas_call",
        "flex_sliding": "magi_flex_bwd_kernel.5 " + base + "transpose(jvp("
        "checkpoint))/magi_attn_sliding/magi_merged_kernel/"
        "magi_flex_bwd_kernel/pallas_call",
        "other": "fusion.1 " + base + "add",
    }

    def hits(metric):
        rx = re.compile(spec[metric]["pattern"])
        return {k for k, op in ops.items() if rx.search(op)}

    assert hits("train_router_share") == {"router", "router_bwd"}
    assert hits("train_moe_share") == {"router", "router_bwd", "experts"}
    assert hits("train_moe_matmul_share") == {"experts"}
    assert hits("train_proj_share") == {"proj"}
    assert hits("train_ffn_share") == {"ffn"}
    assert hits("train_full_flex_roofline") == {"flex_full"}
    assert hits("train_sliding_flex_roofline") == {"flex_sliding"}
    assert hits("train_flex_kernel_share") == {"flex_full", "flex_sliding"}
    assert hits("train_unscoped_share") == {"other"}
    assert spec["train_full_flex_roofline"]["flops"] == "attn_full_executed"
    assert spec["train_sliding_flex_roofline"]["flops"] == "attn_sliding_executed"
    assert spec["train_route_ahead_layers"] == {
        "kind": "registry_gauge",
        "series": collectors.M_MOE_ROUTE_AHEAD_LAYERS,
    }
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    listed = {
        m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])
    }
    assert listed == {m["name"] for m in cell.per_layer} >= {
        "train_step_steady_ms", "train_mfu_steady", "train_device_idle_share",
        "train_flex_kernel_share", "train_sliding_flex_share",
        "train_full_flex_share", "train_sliding_flex_roofline",
        "train_full_flex_roofline", "train_moe_share", "train_router_share",
        "train_proj_share", "train_ffn_share", "train_embed_share",
        "train_head_share", "train_optimizer_share", "train_remat_share",
        "train_unscoped_share", "key_build_ms", "train_route_ahead_layers",
    }
    entry = next(
        m for m in bench["per_layer"] if m["name"] == "train_route_ahead_layers"
    )
    assert CELL in entry["workloads"]
    assert entry["moves"] == "train_tokens_per_s" in cell.end_to_end
    cells = [w["name"] for w in bench["workloads"]]
    assert CELL in cells and len(cells) >= 15
    assert next(
        w for w in bench["workloads"] if w["name"] == CELL
    )["chips"] == 1


def test_the_check_plans_the_windows_rungs():
    """The plans of the cell's 16,384 rows and of the check's 8,192 (host
    only): each kind's exact area; the check's window kernels at the rung
    and on the grid the timed step's run at. The two full plans are
    compared with the tuner's own rule, not with a literal: the timed
    mask's four long documents fill 23% of the square, under the ranker's
    ``SPARSE_DENSITY_THRESHOLD``, and take the rung with the fewest steps;
    the check's three fill 30% and take the measured preference order's
    (PERF.md section 7: what holds the timed full kernels is the first
    loss and the first update)."""
    import jax

    from magiattention_tpu import telemetry

    cell = harness.load_cell(REPO, CELL)
    job = train_prerouted.Job(cell.config, cell.traffic, 0, jax.devices()[:1])
    mask = masks.build_mask(
        cell.traffic["mask"], cell.traffic["total_tokens"], index=0
    )
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    try:
        with jax.enable_x64(False):
            model, meta = job.build(mask)
            check, _meta = job.build(train_prerouted.check_mask(job.tr))
        gauges = telemetry.snapshot()["gauges"]
    finally:
        telemetry.set_enabled(was)
    areas = job.areas(mask)
    assert set(model.plans) == {FULL, SLIDING} == set(check.plans)
    from magiattention_tpu.tuning.cost_model import SPARSE_DENSITY_THRESHOLD

    def rung(p):
        return p.block_q, p.block_k, p.head_block, p.grid

    for kind in (FULL, SLIDING):
        assert model.plans[kind].total_area == areas[kind]
        for p in (model.attn_params[kind], check.attn_params[kind]):
            # a head-batched step holds whole groups of 7
            assert p.head_block in (1, 7, 14, 28), (kind, rung(p))
    assert rung(check.attn_params[SLIDING]) == rung(model.attn_params[SLIDING])
    held = train_prerouted.check_mask(job.tr)
    sparse = [
        m.area / m.total ** 2 < SPARSE_DENSITY_THRESHOLD for m in (mask, held)
    ]
    if sparse[0] == sparse[1]:  # one regime: one rung
        assert rung(check.attn_params[FULL]) == rung(model.attn_params[FULL])
    assert meta.total_seqlen == 16384
    assert gauges["magi_moe_route_ahead_layers"] == 8.0


# ---------------------------------------------------------------------------
# the command's own path
# ---------------------------------------------------------------------------


@pytest.fixture
def _as_the_command_runs():
    import jax

    with jax.enable_x64(False):
        yield


@pytest.mark.parametrize(
    "workload,trace", [("toy.prerouted", 1), ("toy.prerouted-cp2", 0)],
)
def test_rehearsal_prints_the_result_line(
    workload, trace, _as_the_command_runs
):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = harness.main(
            ["--workload", workload, "--seed", str(2**31 + 12345),
             "--seconds", "1.5", "--trace", str(trace), "--root", TOY],
            allow_cpu=True,
        )
    assert rc == 0
    lines = out.getvalue().strip().splitlines()
    res = json.loads(lines[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert res["device"]["count"] == (2 if workload.endswith("cp2") else 1)
    for kind in ("full_attention", "sliding_attention"):
        assert [ln for ln in lines if f"] tiles of {kind}" in ln]
        assert [ln for ln in lines if f"] check: tiles of {kind}" in ln]
    for when in ("the seed's weights", "as the window opens",
                 "as the window closes"):
        assert [ln for ln in lines if f"] expert layers, {when}: pairs" in ln]
    if not trace:
        assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
        assert all(m["value"] > 0 for m in res["metrics"].values())
    else:
        # no device trace on the CPU: the share's reader finds nothing and
        # the line leaves it out, as on a parent without the scope; the
        # gauge is plan-time host code
        assert set(res["metrics"]) == {
            "train_step_steady_ms", "train_route_ahead_layers"
        }
        assert res["metrics"]["train_route_ahead_layers"]["value"] == 2.0


def test_the_parent_has_no_such_cell():
    """An unknown workload fails at once, before jax is touched: how the
    parent answers the new cell (with this PR's files laid over it, at the
    kind's import of ``smallthinker_config``)."""
    with pytest.raises(SystemExit, match="no workload"):
        harness.load_cell(TOY, CELL)
