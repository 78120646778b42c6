"""What ``train_latent``'s ``correct`` can see, GLM-4.7-Flash's
configuration files, and the command's own path for a latent-attention
cell. Toy size, CPU (``data/toy_latent``: a benchmark of new files
only)."""

import io
import json
import os
import re
from contextlib import redirect_stdout

import pytest

from benchmarks import flops, flops_glm4moe, harness, masks
from benchmarks.kinds import train_latent
from tests.test_benchmarks import latent_faults

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TOY = os.path.join(HERE, "data", "toy_latent")
CELL = "glm47flash-train-16k-packed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


class _OneDocument(train_latent.Job):
    """The model planned as if the packed sequence were one document."""

    def build(self, mask):
        return super().build(
            masks.build_mask(
                {"type": "varlen_block_causal", "lengths": [mask.total]},
                mask.total,
            )
        )


@pytest.fixture(scope="module")
def readings():
    """The check's readings by what the model was handed; the reference
    always gets the configuration and the weights as they are."""
    import jax
    import jax.numpy as jnp

    from magiattention_tpu.models.pattern import init_pattern_params

    cell = harness.load_cell(TOY, "toy.latent")
    # the toy traffic file says float32 (its rehearsals check a model a
    # few AdamW steps old); the readings are of bf16, as the cell runs.
    # At 256 tokens and ranks of 48 and 32 bf16's own error swings with
    # the seed (w_router 4e-2 to 2.6e-1 over six seeds, where the chip at
    # the published widths reads 1.0e-1 to 1.4e-1): the seed is one whose
    # reading is typical of the cell's
    cfg, tr = cell.config, dict(cell.traffic, dtype="bfloat16")
    dev = jax.devices()[:1]
    found = {}
    with jax.enable_x64(False):
        job = train_latent.Job(cfg, tr, 77, dev)
        params = init_pattern_params(
            train_latent.key_from_seed(job.seed), job.pcfg
        )
        handed = {
            "bf16, as the cell runs": {},
            "float32 model": {"model_job": train_latent.Job(
                cfg, dict(tr, dtype="float32"), job.seed, dev
            )},
            "attention across documents": {
                "model_job": _OneDocument(cfg, tr, job.seed, dev)
            },
            "route_norm left out": {"model_job": train_latent.Job(
                cfg, tr, job.seed, dev, {"route_norm": False}
            )},
            # the reference reads the file's 0.3
            "the MTP loss weighed 0.6": {"model_job": train_latent.Job(
                cfg, tr, job.seed, dev, {"mtp_loss_weight": 0.6}
            )},
            "the MTP loss weighed 0.305": {"model_job": train_latent.Job(
                cfg, tr, job.seed, dev, {"mtp_loss_weight": 0.305}
            )},
            "fp8 weights": {"model_params": jax.tree.map(
                lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype), params
            )},
            "the reference left to its own choices": {"free_routing": True},
        }
        for name, fault in handed.items():
            found[name] = train_latent.check_errors(job, params, **fault)
        for name in latent_faults.PLANTED:
            with latent_faults.planted(name):
                found[name] = train_latent.check_errors(job, params)
    return found


def test_the_cell_as_it_runs_passes(readings):
    for name in ("bf16, as the cell runs", "float32 model"):
        assert train_latent.passes(*readings[name]), (name, readings[name])
    _rel, grad, routing = readings["float32 model"]
    # float32 against float32 agrees far inside what bf16 is allowed,
    # and makes the reference's own choices
    assert max(grad.values()) < 1e-4 and routing["flipped_share"] == 0.0
    # every parameter is held: the latent attention's seven, the
    # module's four of its own, and the module's layer among the layers
    assert {
        "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wkv_b", "wo",
        "mtp.embed_norm", "mtp.hidden_norm", "mtp.eh_proj", "mtp.final_norm",
        "w_router", "we_gate", "ws_down", "w_gate", "embed", "lm_head",
    } <= set(grad)


@pytest.mark.parametrize("fault", [
    *latent_faults.PLANTED, "attention across documents",
    "route_norm left out", "the MTP loss weighed 0.6", "fp8 weights",
])
def test_a_fault_fails_the_check(readings, fault):
    rel, grad, routing = readings[fault]
    assert not train_latent.passes(rel, grad, routing), (fault, grad, routing)
    # by a gradient, at twice its tolerance or more: not by the routing
    # criteria alone
    assert any(
        e > 2 * train_latent.grad_limit(n) for n, e in grad.items()
    ), (fault, grad)


def test_the_loss_holds_what_no_gradient_shows(readings):
    """A head's weight off by a sixtieth moves every gradient by less
    than bf16 does and the loss by twelve times its limit: the loss
    alone refuses it. The limit is three times the largest the chip
    read (1.02e-4); the one ``train_pattern`` has, 1e-3, would have let
    the MTP target rolled by -1 pass by the loss."""
    rel, grad, routing = readings["the MTP loss weighed 0.305"]
    assert all(e <= train_latent.grad_limit(n) for n, e in grad.items())
    assert routing == readings["bf16, as the cell runs"][2]
    assert rel > 10 * train_latent.LOSS_REL_TOL
    assert not train_latent.passes(rel, grad, routing)
    sound = readings["bf16, as the cell runs"][0]
    assert 3 * sound < train_latent.LOSS_REL_TOL == 3e-4
    for fault in ("the MTP loss weighed 0.6", "mtp target rolled by -1"):
        assert readings[fault][0] > 3 * train_latent.LOSS_REL_TOL, fault
    assert readings["mtp target rolled by -1"][0] < 1e-3


def test_every_parameter_has_its_limit():
    assert train_latent.grad_limit("wq_a") == train_latent.GRAD_REL_L2_TOL
    assert train_latent.grad_limit("mtp.eh_proj") == train_latent.GRAD_REL_L2_TOL
    assert train_latent.grad_limit("we_up") == train_latent.EXPERT_GRAD_REL_L2_TOL
    assert train_latent.grad_limit("w_router") == train_latent.ROUTER_GRAD_REL_L2_TOL
    ok = {"flipped_share": 0.018, "worst_margin": 0.019}
    grad = {"wq_a": 0.048, "we_up": 0.106, "w_router": 0.153}
    assert train_latent.passes(1.02e-4, grad, ok)  # the cell's largest readings
    assert not train_latent.passes(4e-4, grad, ok)
    assert not train_latent.passes(8e-5, dict(grad, wq_a=0.09), ok)
    assert not train_latent.passes(8e-5, dict(grad, we_up=0.2), ok)
    assert not train_latent.passes(8e-5, dict(grad, w_router=0.3), ok)
    assert not train_latent.passes(8e-5, grad, dict(ok, flipped_share=0.07))


def test_why_the_reference_follows_the_models_choices(readings):
    _rel, forced, routing = readings["bf16, as the cell runs"]
    _rel, free, _r = readings["the reference left to its own choices"]
    assert routing["flipped_share"] <= train_latent.ROUTE_FLIP_SHARE_TOL
    assert routing["worst_margin"] <= train_latent.ROUTE_MARGIN_TOL
    if routing["flipped_share"] > 0.0:
        assert free["w_router"] > forced["w_router"]


# ---------------------------------------------------------------------------
# the configuration, its mask and its operation counts
# ---------------------------------------------------------------------------


def test_glm47flash_states_its_widths_as_published():
    cfg = harness.load_cell(REPO, CELL).config
    published = {
        "hidden_size": 2048, "intermediate_size": 10240,
        "moe_intermediate_size": 1536, "num_attention_heads": 20,
        "num_key_value_heads": 20, "q_lora_rank": 768, "kv_lora_rank": 512,
        "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
        "n_routed_experts": 64, "num_experts_per_tok": 4,
        "n_shared_experts": 1, "routed_scaling_factor": 1.8,
        "norm_topk_prob": True, "first_k_dense_replace": 1,
        "num_nextn_predict_layers": 1, "vocab_size": 154880,
        "rope_theta": 1000000, "rms_norm_eps": 1e-05, "n_group": 1,
        "topk_group": 1, "topk_method": "noaux_tc",
        "max_position_embeddings": 202752, "model_type": "glm4_moe_lite",
    }
    assert {k: cfg[k] for k in published} == published
    if os.path.exists(CATALOG):  # every number of the catalog row's config
        with open(CATALOG) as f:
            row = next(
                r for r in map(json.loads, f) if r["name"] == "GLM-4.7-Flash"
            )
        differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differs == {"num_hidden_layers"}
    assert set(cfg["reduced"]) == {
        "num_hidden_layers", "experts_here", "vocab_here",
    }
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "glm-4.7-flash")
    assert set(entry["reduced"]) == set(cfg["reduced"])
    assert entry["source"] == (
        "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json"
    )
    assert cfg["num_hidden_layers"] == 5
    assert cfg["experts_here"] == [0, 8] and cfg["vocab_here"] == 19360
    assert cfg["deployment"]["chips"] == 8
    assert 8 * 8 == cfg["n_routed_experts"] and 8 * 19360 == cfg["vocab_size"]
    for key in ("rotary", "latent_attention", "router", "expert_bias",
                "mtp_input", "mtp_loss_weight", "labels"):
        assert key in cfg["assumed"]


def test_the_pattern_the_program_builds_from_the_file():
    from magiattention_tpu.models.pattern import (
        DENSE, EXPERTS, FULL, LATENT, glm4_moe_lite_config,
    )

    cfg = harness.load_cell(REPO, CELL).config
    p = glm4_moe_lite_config(
        cfg, expert_range=tuple(cfg["experts_here"]),
        vocab_size=cfg["vocab_here"],
    )
    assert (p.dim, p.n_heads, p.n_kv_heads, p.head_dim) == (2048, 20, 20, 256)
    assert (p.attn_form, p.q_lora_rank, p.kv_lora_rank, p.rope_head_dim) == (
        LATENT, 768, 512, 64
    )
    assert p.layer_types == (FULL,) * 5 and p.plan_kinds == (FULL,)
    assert p.ffn_types == (DENSE,) + (EXPERTS,) * 4
    assert (p.ffn_hidden, p.expert_hidden, p.n_experts, p.top_k) == (
        10240, 1536, 64, 4
    )
    assert (p.n_shared_experts, p.route_norm, p.route_scale) == (1, True, 1.8)
    assert (p.qk_norm, p.attn_gate, p.post_norms, p.embed_scale) == (
        False, False, False, 1.0
    )
    assert (p.rope_theta, p.rope_kinds, p.rms_eps) == (1e6, (FULL,), 1e-5)
    assert (p.n_mtp, p.mtp_loss_weight, p.vocab_size) == (1, 0.3, 19360)
    assert p.held_experts == (0, 8)


def test_the_parameter_count_is_the_files():
    import jax

    from magiattention_tpu.models.pattern import (
        glm4_moe_lite_config, init_pattern_params,
    )

    cfg = harness.load_cell(REPO, CELL).config
    p = glm4_moe_lite_config(
        cfg, expert_range=tuple(cfg["experts_here"]),
        vocab_size=cfg["vocab_here"],
    )
    shapes = jax.eval_shape(
        lambda r: init_pattern_params(r, p), jax.random.PRNGKey(0)
    )
    sizes = {
        jax.tree_util.keystr(k): v.size
        for k, v in jax.tree_util.tree_leaves_with_path(shapes)
    }
    matrices = sum(
        n for k, n in sizes.items() if "norm" not in k and "bias" not in k
    )
    assert matrices == 706_478_080  # ISSUE 30's table, with the module
    assert "706,478,080" in cfg["parameters"]["with_mtp_module"]
    assert flops_glm4moe.attn_params(cfg) == 21_757_952
    layer1 = sum(n for k, n in sizes.items() if k.startswith("['layers'][1]")
                 and "norm" not in k and "bias" not in k)
    assert layer1 == 106_823_680


def test_the_cells_masks_are_the_issues():
    cell = harness.load_cell(REPO, CELL)
    assert cell.traffic["kind"] == "train_latent"
    mask = masks.build_mask(cell.traffic["mask"], 16384, index=0)
    assert len(mask.doc_lengths) == 17 and mask.area == 16_361_635
    mistral = harness.load_cell(REPO, "mistral7b-train-16k-onemask")
    assert cell.traffic["mask"] == mistral.traffic["mask"]
    check = train_latent.check_mask(cell.traffic)
    assert check.total == cell.traffic["check_tokens"] == 4096
    chunk = harness.load_cell(REPO, "magi64x8-attn-64k-chunkcausal")
    assert chunk.traffic["kind"] == "attn_iter" and chunk.chips == 1
    m = masks.build_mask(chunk.traffic["mask"], 65536)
    assert (len(m.types), m.area) == (16, 4096**2 * 136) == (16, 2_281_701_376)


def test_the_check_plans_the_windows_rung_and_grid():
    """``correct`` is decided on a plan of the check's own at 4,096
    tokens; it at least walks the rung and the grid the window's 16,384
    do."""
    import jax

    cell = harness.load_cell(REPO, CELL)
    job = train_latent.Job(cell.config, cell.traffic, 1, jax.devices()[:1])
    chosen = []
    for mask in (
        train_latent.check_mask(cell.traffic),
        masks.build_mask(cell.traffic["mask"], 16384, index=0),
    ):
        (p,) = job.build(mask)[0].attn_params.values()
        chosen.append((p.block_q, p.block_k, p.head_block, p.grid))
    assert chosen[0] == chosen[1] == (128, 512, 5, "sparse")


def test_flops_of_a_step():
    cfg = harness.load_cell(REPO, CELL).config
    attn, expert = 21_757_952, 3 * 2048 * 1536
    layer = attn + expert + 2048 * 64  # attention, the shared expert, router
    head = 2048 * 19360
    per_token = (
        head + attn + 3 * 2048 * 10240 + 4 * layer
        + layer + 2 * 2048 * 2048 + head  # the module, through the head again
    )
    assert flops_glm4moe.per_token_params(cfg) == per_token == 328_990_720
    area = 16_361_635
    got = flops_glm4moe.train_step_flops(cfg, 16384, area, 50_000.0)
    assert got == pytest.approx(
        6.0 * per_token * 16384 + 6.0 * 50_000 * expert
        + 6 * flops.attn_fwdbwd_flops(area, 20, 256)
    )
    assert flops_glm4moe.attn_executed_flops(cfg, area) == pytest.approx(
        6 * 4.5 * flops.attn_fwd_flops(area, 20, 256)
    )
    without = dict(cfg, num_nextn_predict_layers=0)
    assert flops_glm4moe.attn_layers(without) == 5
    assert flops_glm4moe.per_token_params(without) == (
        per_token - layer - 2 * 2048 * 2048 - head
    )


def test_the_new_metric_files_match_the_scopes_the_program_sets():
    """The patterns against operation names and scopes as the chip's
    compiler prints them (a compile of the cell's step for a described
    v5e, PR 30)."""
    cell = harness.load_cell(REPO, CELL)
    spec = {m["name"]: m["source"] for m in cell.per_layer}
    base = "jit(step)/jit(main)/jit(shmap_body)/"
    ops = {
        "q": "fusion.41 " + base + "checkpoint/magi_mla_q/dot_general",
        "kv_bwd": "fusion.9 " + base
        + "transpose(jvp(checkpoint))/magi_mla_kv/concatenate",
        "out": "fusion.7 " + base + "checkpoint/magi_mla_out/dot_general",
        "mtp_proj": "fusion.3 " + base + "magi_mtp/dot_general",
        "mtp_q": "fusion.5 " + base + "magi_mtp/checkpoint/magi_mla_q/mul",
        "mtp_flex": "magi_flex_fwd_kernel.6 " + base + "magi_mtp/checkpoint/"
        "magi_attn_full/magi_merged_kernel/magi_flex_fwd_kernel/pallas_call",
        "flex": "magi_flex_dq_kernel.2 " + base + "transpose(jvp(checkpoint))/"
        "magi_attn_full/magi_merged_kernel/magi_flex_dq_kernel/pallas_call",
        "moe": "fusion.12 " + base
        + "checkpoint/magi_moe_experts/while/body/mul",
        # a container: its body's operations are in the trace too
        "mtp_loop": "while.7 " + base
        + "magi_mtp/checkpoint/magi_moe_experts/while",
        "other": "fusion.1 " + base + "checkpoint/dot_general",
    }

    def hits(metric):
        rx = re.compile(spec[metric]["pattern"])
        return {k for k, op in ops.items() if rx.search(op)}

    assert hits("train_mla_proj_share") == {"q", "kv_bwd", "out", "mtp_q"}
    assert hits("train_mtp_share") == {"mtp_proj", "mtp_q", "mtp_flex"}
    assert hits("train_full_flex_share") == {"flex", "mtp_flex"}
    assert hits("train_full_flex_roofline") == {"flex", "mtp_flex"}
    assert hits("train_flex_kernel_share") == {"flex", "mtp_flex"}
    assert hits("train_moe_share") == {"moe"}
    assert spec["train_full_flex_roofline"]["flops"] == "attn_full_executed"
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    listed = {
        m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])
    }
    assert listed == {m["name"] for m in cell.per_layer} >= {
        "train_mla_proj_share", "train_mtp_share", "train_mfu_steady",
        "train_device_idle_share", "key_build_ms", "program_compile_s",
    }


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
    [("toy.latent", 0), ("toy.latent", 1), ("toy.latent-cp4", 0)],
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
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert res["device"]["count"] == (4 if workload.endswith("cp4") else 1)
    if not trace:
        assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
        assert all(m["value"] > 0 for m in res["metrics"].values())
    else:
        # no device trace on the CPU: the two shares' reader finds nothing
        # and the line leaves them out; the gauge is the program's
        assert set(res["metrics"]) == {
            "train_step_steady_ms", "mla_kv_cast_width_latent",
        }
        assert res["metrics"]["mla_kv_cast_width_latent"]["value"] == 32 + 8
