"""What ``train_blockdiff``'s ``correct`` can see, SDAR-30B-A3B-Chat's
configuration files, mask and operation counts, and the command's own
path for the cell. Toy size, CPU (``data/toy_blockdiff``: a benchmark of
new files only)."""

import io
import json
import os
import re
from contextlib import redirect_stdout

import numpy as np
import pytest

from benchmarks import flops, flops_sdar, harness, masks_blockdiff
from benchmarks.kinds import train_blockdiff

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TOY = os.path.join(HERE, "data", "toy_blockdiff")
CELL = "sdar30b-train-16k-blockdiff"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


class _StepOne(train_blockdiff.Job):
    """Every step of the model's mask set to 1: a causal mask (and a
    one-key diagonal) in place of the staircase. The fault of this mask's
    own kind."""

    def build(self, mask):
        from unittest import mock

        from magiattention_tpu.api import functools as api_functools

        real = api_functools.infer_block_diffusion_mask

        def unstepped(*a, **k):
            q, kk, types = real(*a, **k)
            return q, kk, [t.base for t in types]

        with mock.patch.object(
            api_functools, "infer_block_diffusion_mask", unstepped
        ):
            return super().build(mask)


@pytest.fixture(scope="module")
def readings():
    """The check's readings by what the model was handed; the reference
    always gets the configuration and the weights as they are."""
    import jax
    import jax.numpy as jnp

    from magiattention_tpu.models.pattern import init_pattern_params

    cell = harness.load_cell(TOY, "toy.blockdiff")
    cfg, tr = cell.config, dict(cell.traffic, dtype="bfloat16")
    dev = jax.devices()[:1]
    found = {}
    with jax.enable_x64(False):
        job = train_blockdiff.Job(cfg, tr, 1, dev)
        params = init_pattern_params(
            train_blockdiff.key_from_seed(job.seed), job.pcfg
        )
        handed = {
            "bf16, as the cell runs": {},
            "float32 model": {"model_job": train_blockdiff.Job(
                cfg, dict(tr, dtype="float32"), job.seed, dev
            )},
            "every step set to 1": {
                "model_job": _StepOne(cfg, tr, job.seed, dev)
            },
            "blocks of 2 for blocks of 4": {
                "model_job": train_blockdiff.Job(
                    cfg, tr, job.seed, dev, {"diffusion_block": 2}
                )
            },
            "fp8 weights": {"model_params": jax.tree.map(
                lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype), params
            )},
        }
        for name, fault in handed.items():
            found[name] = train_blockdiff.check_errors(job, params, **fault)
    return found


def test_the_cell_as_it_runs_passes(readings):
    for name in ("bf16, as the cell runs", "float32 model"):
        assert train_blockdiff.passes(*readings[name]), (name, readings[name])
    rel, grad, routing = readings["float32 model"]
    # float32 against float32 agrees far inside what bf16 is allowed
    assert rel < 1e-5 and max(grad.values()) < 1e-4
    assert routing == {"flipped_share": 0.0, "worst_margin": 0.0}
    assert set(grad) == {
        "embed", "final_norm", "lm_head", "attn_norm", "mlp_norm", "q_norm",
        "k_norm", "wq", "wk", "wv", "wo", "w_router", "we_gate", "we_up",
        "we_down",
    }


@pytest.mark.parametrize("fault", [
    "every step set to 1", "blocks of 2 for blocks of 4", "fp8 weights",
])
def test_a_fault_fails_the_check(readings, fault):
    rel, grad, routing = readings[fault]
    assert not train_blockdiff.passes(rel, grad, routing), (
        fault, rel, grad, routing
    )


def test_the_staircase_is_held_by_the_attention_gradients(readings):
    """A causal mask in place of the staircase lets a clean row miss its
    own block's later tokens and a noisy row its block's: the projections'
    gradients leave the reference's by most of their norm."""
    _rel, grad, _routing = readings["every step set to 1"]
    assert min(grad[n] for n in ("wq", "wk", "wv", "wo")) > 5 * (
        train_blockdiff.GRAD_REL_L2_TOL
    ), grad


@pytest.fixture(scope="module")
def timed():
    """The toy's compiled step called once on the seed's weights, as
    ``run`` calls the window's before it warms up: (job, mask, meta,
    draw, the model's choices, the weights, the step, its batch)."""
    import jax
    import jax.numpy as jnp
    import optax

    from magiattention_tpu.models.pattern import init_pattern_params

    cell = harness.load_cell(TOY, "toy.blockdiff")
    cfg, tr = cell.config, cell.traffic
    with jax.enable_x64(False):
        job = train_blockdiff.Job(cfg, tr, 7, jax.devices()[:1])
        mask = job.mask(tr["mask"], tr["data_tokens"])
        model, meta = job.build(mask)
        params = init_pattern_params(
            train_blockdiff.key_from_seed(job.seed), job.pcfg
        )
        draw, batch = job.batch_for(meta, mask, 0)
        choices = np.asarray(jax.jit(lambda p, *b: model.loss_fn(
            p, *b[:3], model.sharded_tables(), b[3], with_stats=True
        )[1])(params, *batch)["expert_idx"])[0]
        opt = optax.adamw(float(tr["learning_rate"]))
        step = model.make_train_step(opt)

        def first_call(batch):
            mine = jax.tree.map(jnp.copy, params)  # the step donates
            after, _opt, loss = step(mine, opt.init(mine), *batch)
            return float(loss), after

        yield job, mask, meta, draw, choices, params, first_call, batch


@pytest.mark.parametrize("fault", [
    None, "the weights on the loss dropped", "no update", "twice the rate",
])
def test_the_timed_step_is_held_at_its_own_size(timed, fault):
    """What ``correct`` holds of the program the window times: its first
    loss against the reference's forward pass on the window's documents,
    and its first update against AdamW's first step."""
    import jax
    import jax.numpy as jnp

    job, mask, meta, draw, choices, params, first_call, batch = timed
    lr = float(job.tr["learning_rate"])
    with jax.enable_x64(False):
        if fault == "the weights on the loss dropped":
            batch = (*batch[:3], jnp.ones_like(batch[3]))
        loss, after = first_call(batch)
        if fault == "no update":
            after = params
        moved = train_blockdiff.update_share(
            params, after, lr / 2 if fault == "twice the rate" else lr
        )
        rel = train_blockdiff.timed_loss_error(
            job, params, mask, meta, draw, choices, loss
        )
    assert train_blockdiff.timed_step_passes(rel, moved) == (fault is None), (
        fault, rel, moved
    )
    if fault is None:  # float32 toy: the two agree far inside the limit
        assert rel < 1e-5 and 0.9 < moved < 1.001
    elif fault == "the weights on the loss dropped":
        assert rel > 0.1 and 0.9 < moved < 1.001
    else:
        assert moved == pytest.approx(0.0 if fault == "no update" else 2.0, abs=0.2)


# ---------------------------------------------------------------------------
# the configuration, its mask and its operation counts
# ---------------------------------------------------------------------------


def test_sdar_states_its_widths_as_published():
    cell = harness.load_cell(REPO, CELL)
    cfg = cell.config
    published = {
        "hidden_size": 2048, "head_dim": 128, "num_attention_heads": 32,
        "num_key_value_heads": 4, "moe_intermediate_size": 768,
        "intermediate_size": 6144, "num_experts": 128,
        "num_experts_per_tok": 8, "norm_topk_prob": True,
        "vocab_size": 151936, "rope_theta": 1000000, "rms_norm_eps": 1e-06,
        "max_position_embeddings": 32768, "tie_word_embeddings": False,
        "model_type": "sdar_moe", "hidden_act": "silu",
        "sliding_window": None, "use_sliding_window": False,
        "mlp_only_layers": [], "decoder_sparse_step": 1,
    }
    assert {k: cfg[k] for k in published} == published
    if os.path.exists(CATALOG):  # every key of the catalog row's config
        with open(CATALOG) as f:
            row = next(
                r for r in map(json.loads, f)
                if r["name"] == "SDAR-30B-A3B-Chat"
            )
        differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differs == {"num_hidden_layers"}
        assert row["source_url"] in cfg["source"]
    assert list(cfg["reduced"]) == [
        "num_hidden_layers", "experts_here", "vocab_here"
    ]
    assert 4 <= cfg["num_hidden_layers"] == 6
    assert "48 published" in cfg["reduced"]["num_hidden_layers"]
    assert cfg["experts_here"] == [0, 16] and "16 of the 128" in (
        cfg["reduced"]["experts_here"]
    )
    assert cfg["vocab_here"] == 18992 == 151936 // 8
    assert cfg["mask_token_id"] == cfg["vocab_here"] - 1
    assert cfg["published"] == {
        "num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151936,
        "deployment": "8 chips share a layer",
    }
    assert cfg["deployment"]["chips"] == 8
    assert "1,024 rows" in cfg["deployment"]["distorts"]
    assert cfg["block_length"] == 4
    for key in ("block_length", "noise_schedule", "loss_weight",
                "no_logit_shift", "row_order", "mask_token_id",
                "initializer", "flat_expert_rows"):
        assert key in cfg["assumed"]
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "sdar-30b-a3b-chat")
    assert entry["reduced"] == list(cfg["reduced"])
    assert entry["source"] == (
        "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json"
    )
    assert (cell.chips, cell.config_name) == (1, "sdar-30b-a3b-chat")
    assert cell.traffic["kind"] == "train_blockdiff"
    assert cell.end_to_end == ["train_tokens_per_s", "setup_s"]


def test_the_pattern_and_the_parameters_the_program_builds_from_the_file():
    import jax

    from magiattention_tpu.models.pattern import (
        EXPERTS, FULL, GQA, SOFTMAX, init_pattern_params, sdar_moe_config,
    )

    cfg = harness.load_cell(REPO, CELL).config
    p = sdar_moe_config(
        cfg, expert_range=tuple(cfg["experts_here"]),
        vocab_size=cfg["vocab_here"],
    )
    assert (p.dim, p.n_heads, p.n_kv_heads, p.head_dim) == (2048, 32, 4, 128)
    assert p.layer_types == (FULL,) * 6 and p.plan_kinds == (FULL,)
    assert p.ffn_types == (EXPERTS,) * 6 and p.rope_kinds == (FULL,)
    assert (p.attn_form, p.router_form, p.router_dtype) == (
        GQA, SOFTMAX, "float32"
    )
    assert (p.n_experts, p.top_k, p.expert_hidden, p.held_experts) == (
        128, 8, 768, (0, 16)
    )
    assert (p.n_shared_experts, p.route_norm, p.route_scale) == (0, True, 1.0)
    assert (p.rope_theta, p.rms_eps, p.vocab_size) == (1e6, 1e-6, 18992)
    assert (p.qk_norm, p.attn_gate, p.post_norms) == (True, False, False)
    assert p.diffusion_block == 4 and not p.tie_embeddings
    assert p.flat_expert_rows
    shapes = jax.eval_shape(
        lambda r: init_pattern_params(r, p), jax.random.PRNGKey(0)
    )
    sizes = {
        jax.tree_util.keystr(k): v.size
        for k, v in jax.tree_util.tree_leaves_with_path(shapes)
    }
    # ISSUE 42: 645.6 M parameters = 10.33 GB at 16 bytes
    layer0 = sum(n for k, n in sizes.items() if k.startswith("['layers'][0]"))
    assert layer0 - 128 == 94_638_336 == (  # less the bias buffer
        flops_sdar.attn_params(cfg) + 2048 * 128
        + 16 * flops_sdar.expert_params(cfg) + 2 * 2048 + 2 * 128
    )
    assert sum(sizes.values()) == 645_624_064
    assert "94,638,336" in cfg["reduced"]["num_hidden_layers"]


def test_the_cells_mask_is_the_issues():
    """Area 40,402,944 by the closed form, by the program's
    ``exact_mask_area``, by the dense definition counted in row blocks;
    nine slices where the unstepped types take 6,141, at step 4."""
    from magiattention_tpu.api import infer_block_diffusion_mask
    from magiattention_tpu.common.mask import unstepped_slice_count
    from magiattention_tpu.tuning.cost_model import exact_mask_area

    cell = harness.load_cell(REPO, CELL)
    tr = cell.traffic
    mask = masks_blockdiff.build_mask(
        tr["mask"], tr["data_tokens"], cell.config["block_length"]
    )
    assert (mask.data_tokens, mask.rows, mask.block) == (8192, 16384, 4)
    assert mask.doc_lengths == (6144, 1536, 512)
    assert mask.area == 40_402_944 == (
        6144 ** 2 + 1536 ** 2 + 512 ** 2 + 4 * 8192
    )
    assert round(mask.describe()["causal_share_pct"], 1) == 30.1
    q, k, t = infer_block_diffusion_mask(mask.cu_seqlens, mask.block)
    naive = q.to_naive_ranges(), k.to_naive_ranges(), [int(x) for x in t]
    assert exact_mask_area(*naive) == mask.area
    assert (len(t), unstepped_slice_count(*naive)) == (9, 6141)
    rows = np.arange(mask.rows)
    brute = sum(
        int(masks_blockdiff.allowed(mask, rows[a:a + 2048], rows).sum())
        for a in range(0, mask.rows, 2048)
    )
    assert brute == mask.area
    check = masks_blockdiff.build_mask(tr["check_mask"], tr["check_tokens"], 4)
    # the check: 4,096 rows, three documents, each a whole number of
    # blocks, one boundary off the chunk grid
    assert (check.rows, check.doc_lengths) == (4096, (1280, 584, 184))
    assert tr["chunk_size"] == 512 and 1280 % 512 != 0 == 2048 % 512
    with pytest.raises(ValueError, match="whole number of blocks"):
        masks_blockdiff.build_mask(
            {"type": "varlen_block_causal", "lengths": [6, 10]}, 16, 4
        )


@pytest.mark.parametrize("docs,block", [((24, 8, 32), 4), ((16, 48), 8),
                                        ((12,), 2), ((5, 3), 1)])
def test_the_area_and_the_definition_by_brute_force(docs, block):
    """``flops_sdar``'s area (``blockdiff_area``) == the dense definition
    counted pair by pair == the program's slices' area."""
    from magiattention_tpu.api import infer_block_diffusion_mask
    from magiattention_tpu.common.mask import make_attn_mask_from_ranges

    n = sum(docs)
    mask = masks_blockdiff.build_mask(
        {"type": "varlen_block_causal", "lengths": list(docs)}, n, block
    )
    cu = mask.cu_seqlens
    count = 0
    want = np.zeros((2 * n, 2 * n), bool)
    for q in range(2 * n):
        for k in range(2 * n):
            qt, kt = q % n, k % n
            qd = next(i for i in range(len(docs)) if cu[i] <= qt < cu[i + 1])
            kd = next(i for i in range(len(docs)) if cu[i] <= kt < cu[i + 1])
            qb, kb = (qt - cu[qd]) // block, (kt - cu[kd]) // block
            ok = qd == kd and (
                (q < n and k < n and qb == kb) or (q < n <= k and kb < qb)
                or (q >= n and k >= n and kb <= qb)
            )
            want[q, k] = ok
            count += ok
    rows = np.arange(2 * n)
    assert count == mask.area == masks_blockdiff.blockdiff_area(docs, block)
    assert (masks_blockdiff.allowed(mask, rows, rows) == want).all()
    qr, kr, ts = infer_block_diffusion_mask(cu, block)
    assert (make_attn_mask_from_ranges(qr, kr, ts, 2 * n, 2 * n) == want).all()


def test_flops_of_a_step_by_hand():
    """At the toy's size, every term written out; then the cell's."""
    cfg = harness.load_cell(TOY, "toy.blockdiff").config
    d, hd = 128, 16
    attn = d * hd * (2 * 8 + 2 * 2)
    assert flops_sdar.attn_params(cfg) == attn == 40_960
    assert flops_sdar.expert_params(cfg) == 3 * d * 64 == 24_576
    per_row = 2 * (attn + d * 16)
    assert flops_sdar.per_row_params(cfg) == per_row == 86_016
    assert flops_sdar.head_params(cfg) == d * 512
    area, tokens, pairs = 12_345, 256, 700.0
    attn_fwd = 4.0 * area * 8 * hd
    assert flops_sdar.train_step_flops(cfg, tokens, area, pairs) == (
        6.0 * per_row * 2 * tokens + 6.0 * d * 512 * tokens
        + 6.0 * pairs * 24_576 + 2 * 3.5 * attn_fwd
    )
    assert flops_sdar.attn_executed_flops(cfg, area) == 2 * 4.5 * attn_fwd
    # ISSUE 42's count for the cell: 661.96 GFLOP a layer forward in the
    # flex kernels, 17.9 TFLOP executed a step; 143.1 M parameters a row
    cfg = harness.load_cell(REPO, CELL).config
    area = 40_402_944
    assert flops.attn_fwd_flops(area, 32, 128) == pytest.approx(661.96e9, rel=1e-4)
    assert flops_sdar.attn_executed_flops(cfg, area) == pytest.approx(
        17.87e12, rel=1e-3
    )
    assert flops_sdar.per_row_params(cfg) == 6 * (18_874_368 + 262_144)
    step = flops_sdar.train_step_flops(cfg, 8192, area, 6 * 16384.0)
    assert step == pytest.approx(
        6.0 * 16384 * 114_819_072 + 6.0 * 8192 * 2048 * 18992
        + 6.0 * 6 * 16384 * 4_718_592 + 6 * 3.5 * 661.96e9, rel=1e-4,
    )
    assert 29e12 < step < 31e12


def test_the_metric_files_match_the_scopes_the_program_sets():
    """The new pattern against operation names and scopes as the chip's
    compiler prints them, and the lists the cell was appended to."""
    cell = harness.load_cell(REPO, CELL)
    spec = {m["name"]: m["source"] for m in cell.per_layer}
    base = "jit(step)/jit(main)/jit(shmap_body)/"
    ops = {
        "embed": "fusion.3 " + base + "magi_diffusion_io/magi_embed/gather",
        "embed_bwd": "fusion.7 " + base + "transpose(jvp(magi_diffusion_io))/"
        "magi_embed/scatter-add",
        "gather": "fusion.21 " + base + "magi_diffusion_io/magi_head/gather",
        "weights": "fusion.22 " + base + "magi_head/magi_diffusion_io/mul",
        "head": "fusion.30 " + base + "magi_head/dot_general",
        "router": "fusion.201 " + base + "checkpoint/magi_moe_router/"
        "dot_general",
        "proj": "fusion.31 " + base + "checkpoint/magi_proj/dot_general",
        "flex": "magi_flex_fwd_kernel.2 " + base + "checkpoint/"
        "magi_attn_full/magi_merged_kernel/magi_flex_fwd_kernel/pallas_call",
        "loop": "while.3 " + base + "magi_diffusion_io/while",
        "other": "fusion.1 " + base + "add",
    }

    def hits(metric):
        rx = re.compile(spec[metric]["pattern"])
        return {k for k, op in ops.items() if rx.search(op)}

    io_ops = {"embed", "embed_bwd", "gather", "weights"}
    assert hits("train_diffusion_io_share") == io_ops
    # a cross-cut: each of its operations is some part's too
    assert hits("train_embed_share") == {"embed", "embed_bwd"}
    assert hits("train_head_share") == {"gather", "weights", "head"}
    assert hits("train_router_share") == {"router"} == hits("train_moe_share")
    assert hits("train_full_flex_roofline") == {"flex"}
    assert hits("train_unscoped_share") == {"other"}
    assert spec["flex_stepped_tile_share"] == {
        "kind": "registry_gauge", "series": "magi_flex_stepped_tile_share",
    }
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    listed = {
        m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])
    }
    assert listed == {m["name"] for m in cell.per_layer} >= {
        "train_step_steady_ms", "train_mfu_steady", "train_device_idle_share",
        "train_flex_kernel_share", "train_full_flex_share",
        "train_full_flex_roofline", "train_moe_share", "train_moe_sort_share",
        "train_moe_gather_share", "train_moe_matmul_share",
        "train_moe_scatter_share", "train_router_share", "train_proj_share",
        "train_ffn_share", "train_embed_share", "train_head_share",
        "train_optimizer_share", "train_attn_layout_share",
        "train_remat_share", "train_unscoped_share", "key_build_ms",
        "program_trace_s", "program_lower_s", "program_compile_s",
        "program_cache_load_s", "train_diffusion_io_share",
        "flex_stepped_tile_share",
    }
    for name in ("train_diffusion_io_share", "flex_stepped_tile_share"):
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "train_tokens_per_s"
    names = [w["name"] for w in bench["workloads"]]
    assert CELL in names and sum(w["chips"] == 4 for w in bench["workloads"]) == 2
    assert len(bench["workloads"]) >= 12 and len(bench["configs"]) >= 7


def test_the_cells_plan_reports_what_the_mask_is_made_of():
    """The plan of the cell's 16,384 rows (host only): nine slices, 6,141
    rectangles at step 1, step 4, the exact area; the tuner's rung is
    Trinity's, on the compact grid; a quarter of the live steps cross a
    stepped bound."""
    import jax

    from magiattention_tpu import telemetry

    cell = harness.load_cell(REPO, CELL)
    job = train_blockdiff.Job(
        cell.config, cell.traffic, 0, jax.devices()[:1]
    )
    mask = job.mask(cell.traffic["mask"], cell.traffic["data_tokens"])
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    try:
        seen = len(telemetry.get_event_buffer().events())
        with jax.enable_x64(False):
            model, meta = job.build(mask)
        events = telemetry.get_event_buffer().events()[seen:]
        gauges = telemetry.snapshot()["gauges"]
    finally:
        telemetry.set_enabled(was)
    (span,) = [e["args"] for e in events if e["name"] == "plan_flex_attn"]
    assert (span["slices"], span["rectangles"], span["mask_step"]) == (9, 6141, 4)
    (plan,), (p,) = model.plans.values(), model.attn_params.values()
    assert plan.total_area == mask.area == 40_402_944
    assert (p.block_q, p.block_k, p.head_block, p.grid, p.mask_step) == (
        128, 512, 8, "sparse", 4
    )
    assert meta.total_seqlen == 16384 and model.noisy_rows.rows.shape == (1, 8192)
    assert gauges["magi_mask_step{kind=full}"] == 4.0
    assert 20.0 < gauges["magi_flex_stepped_tile_share"] < 35.0


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
    [("toy.blockdiff", 1), ("toy.blockdiff-cp2", 0)],
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
    tiles = next(ln for ln in lines if "] tiles of full_attention" in ln)
    assert "mask step 4; plan area 33920" in tiles
    for when in ("the seed's weights", "as the window opens",
                 "as the window closes"):
        assert [ln for ln in lines if f"] expert layers, {when}: pairs" in ln]
    assert [ln for ln in lines if "data tokens/s (512 rows a step)" in ln]
    if not trace:
        assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
        assert all(m["value"] > 0 for m in res["metrics"].values())
    else:
        # no device trace on the CPU: the share's reader finds nothing and
        # the line leaves it out, as on a parent without the scope; the
        # gauge is plan-time host code
        assert set(res["metrics"]) == {
            "train_step_steady_ms", "flex_stepped_tile_share"
        }
        assert 0 < res["metrics"]["flex_stepped_tile_share"]["value"] <= 100


def test_the_parent_has_no_such_cell():
    """An unknown workload fails at once, before jax is touched: how the
    parent answers the new cell."""
    with pytest.raises(SystemExit, match="no workload"):
        harness.load_cell(TOY, CELL)
