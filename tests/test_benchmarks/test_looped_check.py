"""What ``train_looped``'s ``correct`` can see, Ouro-2.6B's configuration
files and operation counts, and the command's own path for a looped
cell. Toy size, CPU (``data/toy_looped``: a benchmark of new files
only)."""

import io
import json
import os
import re
from contextlib import redirect_stdout

import pytest

from benchmarks import flops, flops_ouro, harness, masks
from benchmarks.kinds import train_looped
from tests.test_benchmarks import looped_faults

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TOY = os.path.join(HERE, "data", "toy_looped")
CELL = "ouro26b-train-16k-looped"
CP4_CELL = "magi64x8-attn-cp4-256k-causal"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


class _OneDocument(train_looped.Job):
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

    cell = harness.load_cell(TOY, "toy.looped")
    # the toy traffic file says float32 (its rehearsals check a model a
    # few AdamW steps old); the readings are of bf16, as the cell runs
    cfg, tr = cell.config, dict(cell.traffic, dtype="bfloat16")
    dev = jax.devices()[:1]
    found = {}
    with jax.enable_x64(False):
        job = train_looped.Job(cfg, tr, 123, dev)
        params = init_pattern_params(
            train_looped.key_from_seed(job.seed), job.pcfg
        )

        def other(**fields):
            return {"model_job": train_looped.Job(cfg, tr, job.seed, dev, fields)}

        no_bias = dict(params, exit_gate=dict(
            params["exit_gate"], b=jnp.zeros_like(params["exit_gate"]["b"])
        ))
        handed = {
            "bf16, as the cell runs": {},
            "float32 model": {"model_job": train_looped.Job(
                cfg, dict(tr, dtype="float32"), job.seed, dev
            )},
            "one pass fewer": other(n_loops=3),
            "the gate's bias dropped": {"model_params": no_bias},
            "beta 0.055 for 0.05": other(exit_entropy_weight=0.055),
            "the entropy's sign flipped": other(exit_entropy_weight=-0.05),
            "attention across documents": {
                "model_job": _OneDocument(cfg, tr, job.seed, dev)
            },
            "fp8 weights": {"model_params": jax.tree.map(
                lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype), params
            )},
        }
        for name, fault in handed.items():
            found[name] = train_looped.check_errors(job, params, **fault)
        for name in looped_faults.PLANTED:
            with looped_faults.planted(name):
                found[name] = train_looped.check_errors(job, params)
    return found


def test_the_cell_as_it_runs_passes(readings):
    for name in ("bf16, as the cell runs", "float32 model"):
        assert train_looped.passes(*readings[name]), (name, readings[name])
    rel, grad = readings["float32 model"]
    # float32 against float32 agrees far inside what bf16 is allowed
    assert rel < 1e-5 and max(grad.values()) < 1e-4
    # every parameter is held, the gate (its weight and bias as one affine
    # map) and the shared head among them
    assert set(grad) == {
        "embed", "lm_head", "final_norm", "exit_gate",
        "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "attn_norm",
        "mlp_norm", "post_attn_norm", "post_mlp_norm",
    }


FAULTS = [
    "one pass fewer", *looped_faults.PLANTED, "the gate's bias dropped",
    "the entropy's sign flipped", "attention across documents", "fp8 weights",
]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_fails_the_check(readings, fault):
    rel, grad = readings[fault]
    assert not train_looped.passes(rel, grad), (fault, rel, grad)
    # by a gradient, at twice its tolerance or more
    assert any(
        e > 2 * train_looped.GRAD_REL_L2_TOL for e in grad.values()
    ), (fault, grad)


def test_what_the_objectives_arithmetic_moves(readings):
    """The loss and the gate hold the objective. The entropy's sign
    flipped moves the loss by far more than ten times its limit and the
    gate's gradient by half its size, and leaves the head's gradient
    where it was. The entropy's weight a tenth off (0.055 for 0.05)
    moves the loss 0.005 x H(p) / loss, which on the seed's weights
    (every exit live) is three times the limit and more, and no gradient
    past its own limit: the loss alone fails it."""
    sound_rel, sound = readings["bf16, as the cell runs"]
    assert sound_rel < train_looped.LOSS_REL_TOL == 1.5e-4
    rel, grad = readings["the entropy's sign flipped"]
    assert rel > 10 * train_looped.LOSS_REL_TOL
    assert grad["exit_gate"] > 0.5 and grad["lm_head"] == sound["lm_head"]
    rel, grad = readings["beta 0.055 for 0.05"]
    assert rel > 3 * train_looped.LOSS_REL_TOL
    assert grad["exit_gate"] > 2 * sound["exit_gate"]
    assert not train_looped.passes(rel, grad)
    assert all(e <= train_looped.GRAD_REL_L2_TOL for e in grad.values())
    assert all(
        abs(e - sound[n]) < 5e-3 for n, e in grad.items() if n != "exit_gate"
    )


# ---------------------------------------------------------------------------
# the configuration, its mask and its operation counts
# ---------------------------------------------------------------------------


def test_ouro_states_its_widths_as_published():
    cell = harness.load_cell(REPO, CELL)
    cfg = cell.config
    published = {
        "hidden_size": 2048, "intermediate_size": 5632, "head_dim": 128,
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "vocab_size": 49152, "rms_norm_eps": 1e-06, "rope_theta": 1000000,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "max_position_embeddings": 65536, "sliding_window": None,
        "tie_word_embeddings": False, "model_type": "ouro",
        "hidden_act": "silu", "max_window_layers": 48,
    }
    assert {k: cfg[k] for k in published} == published
    if os.path.exists(CATALOG):  # every key of the catalog row's config
        with open(CATALOG) as f:
            row = next(
                r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B"
            )
        differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differs == {"num_hidden_layers"}
        assert row["source_url"] in cfg["source"]
    assert set(cfg["reduced"]) == {"num_hidden_layers"}
    assert cfg["num_hidden_layers"] == 6 and cfg["exit_entropy_weight"] == 0.05
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "ouro-2.6b")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == (
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    )
    for key in ("loop", "exit_gate", "exit_distribution", "loss",
                "exit_entropy_weight", "rotary", "layer", "labels"):
        assert key in cfg["assumed"]
    assert (cell.chips, cell.config_name) == (1, "ouro-2.6b")
    assert cell.traffic["kind"] == "train_looped"


def test_the_pattern_the_program_builds_from_the_file():
    from magiattention_tpu.models.pattern import DENSE, FULL, GQA, ouro_config

    cfg = harness.load_cell(REPO, CELL).config
    p = ouro_config(cfg)
    assert (p.dim, p.n_heads, p.n_kv_heads, p.head_dim) == (2048, 16, 16, 128)
    assert p.layer_types == (FULL,) * 6 and p.plan_kinds == (FULL,)
    assert p.ffn_types == (DENSE,) * 6 and p.ffn_hidden == 5632
    assert (p.attn_form, p.n_mtp, p.n_experts) == (GQA, 0, 0)
    assert (p.qk_norm, p.attn_gate, p.post_norms, p.embed_scale) == (
        False, False, True, 1.0
    )
    assert (p.rope_theta, p.rope_kinds, p.rms_eps) == (1e6, (FULL,), 1e-6)
    assert (p.n_loops, p.exit_entropy_weight, p.vocab_size) == (4, 0.05, 49152)


def test_the_parameter_count_is_the_files():
    import jax

    from magiattention_tpu.models.pattern import (
        init_pattern_params, ouro_config,
    )

    cfg = harness.load_cell(REPO, CELL).config
    shapes = jax.eval_shape(
        lambda r: init_pattern_params(r, ouro_config(cfg)),
        jax.random.PRNGKey(0),
    )
    sizes = {
        jax.tree_util.keystr(k): v.size
        for k, v in jax.tree_util.tree_leaves_with_path(shapes)
    }
    assert sum(sizes.values()) == 509_661_185  # ISSUE 32's arithmetic
    assert "509,661,185" in cfg["parameters"]["total"]
    layer0 = sum(n for k, n in sizes.items() if k.startswith("['layers'][0]"))
    assert layer0 == 51_388_416 == flops_ouro.layer_params(cfg) + 4 * 2048
    assert "51,388,416" in cfg["parameters"]["a_layer"]
    assert sizes["['exit_gate']['w']"] + sizes["['exit_gate']['b']"] == 2049


def test_the_cells_masks_are_the_issues():
    cell = harness.load_cell(REPO, CELL)
    mask = masks.build_mask(cell.traffic["mask"], 16384, index=0)
    assert len(mask.doc_lengths) == 17 and mask.area == 16_361_635
    assert (min(mask.doc_lengths), max(mask.doc_lengths)) == (48, 3446)
    for other in ("mistral7b-train-16k-onemask", "glm47flash-train-16k-packed"):
        assert cell.traffic["mask"] == harness.load_cell(REPO, other).traffic["mask"]
    check = train_looped.check_mask(cell.traffic)
    assert check.total == cell.traffic["check_tokens"] == 4096
    cp4 = harness.load_cell(REPO, CP4_CELL)
    dense = harness.load_cell(REPO, "magi64x8-attn-64k-causal")
    assert (cp4.chips, cp4.config_name) == (4, "magi-cp-bench-64x8")
    assert cp4.traffic["kind"] == "attn_iter"
    assert cp4.traffic["mask"] == dense.traffic["mask"] == {"type": "causal"}
    assert cp4.traffic["check"] == dense.traffic["check"] == {"tail_rows": 256}
    m = masks.build_mask(cp4.traffic["mask"], cp4.traffic["total_tokens"])
    assert m.area == 262144 * 262145 // 2
    varlen = harness.load_cell(REPO, "magi64x8-attn-cp4-256k-varlen")
    # its plan casts through one all-to-all, which the device trace names
    # ``all_to_all.N`` and ``trace_reduce.COLLECTIVE`` does not match:
    # ``comm_exposed_ms`` finds nothing to read there (PERF.md section 7)
    assert [m["name"] for m in cp4.per_layer] == [
        m["name"] for m in varlen.per_layer if m["name"] != "comm_exposed_ms"
    ]
    assert cp4.end_to_end == varlen.end_to_end


def test_the_check_plans_the_windows_rung_and_grid():
    """``correct`` is decided on a plan of the check's own at 4,096
    tokens; it at least walks the rung and the grid the window's 16,384
    do, at 16 query = 16 key-value heads of 128 (GQA group 1)."""
    import jax

    cell = harness.load_cell(REPO, CELL)
    job = train_looped.Job(cell.config, cell.traffic, 1, jax.devices()[:1])
    chosen = []
    for mask in (
        train_looped.check_mask(cell.traffic),
        masks.build_mask(cell.traffic["mask"], 16384, index=0),
    ):
        (p,) = job.build(mask)[0].attn_params.values()
        chosen.append((p.block_q, p.block_k, p.head_block, p.grid))
    assert chosen[0] == chosen[1] == (128, 512, 8, "sparse")


def test_flops_of_a_step():
    cfg = harness.load_cell(REPO, CELL).config
    layer, head = 4 * 2048**2 + 3 * 2048 * 5632, 2048 * 49152
    assert flops_ouro.layer_params(cfg) == layer == 51_380_224
    assert flops_ouro.layer_applications(cfg) == 24
    per_token = 24 * layer + 4 * (head + 2048)
    assert flops_ouro.per_token_params(cfg) == per_token
    area = 16_361_635
    got = flops_ouro.train_step_flops(cfg, 16384, area)
    assert got == pytest.approx(
        6.0 * per_token * 16384 + 24 * flops.attn_fwdbwd_flops(area, 16, 128)
    )
    # ISSUE 32: 160.8 + 11.3 = 172 TFLOP at the published widths
    assert 6.0 * per_token * 16384 == pytest.approx(160.8e12, rel=1e-3)
    assert 24 * flops.attn_fwdbwd_flops(area, 16, 128) == pytest.approx(
        3.5 * 24 * 4 * area * 2048
    ) == pytest.approx(11.26e12, rel=1e-3)
    assert got == pytest.approx(172.06e12, rel=1e-4)
    assert flops_ouro.attn_executed_flops(cfg, area) == pytest.approx(
        24 * 4.5 * flops.attn_fwd_flops(area, 16, 128)
    )
    # the whole model: 48 layers x 4 passes
    assert flops_ouro.layer_applications(dict(cfg, num_hidden_layers=48)) == 192


def test_the_metric_files_match_the_scopes_the_program_sets():
    """The patterns against operation names and scopes as the chip's
    compiler prints them (a compile of the cell's step for a described
    v5e, PR 32)."""
    cell = harness.load_cell(REPO, CELL)
    spec = {m["name"]: m["source"] for m in cell.per_layer}
    base = "jit(step)/jit(main)/jit(shmap_body)/"
    ops = {
        "flex": "magi_flex_fwd_kernel.2 " + base + "magi_loop/while/body/"
        "checkpoint/magi_attn_full/magi_merged_kernel/magi_flex_fwd_kernel/"
        "pallas_call",
        "flex_bwd": "magi_flex_dkv_kernel.1 " + base + "transpose(jvp("
        "magi_loop))/while/body/checkpoint/magi_attn_full/magi_merged_kernel/"
        "magi_flex_dkv_kernel/pallas_call",
        "layer": "fusion.31 " + base + "magi_loop/while/body/checkpoint/"
        "dot_general",
        "exit": "fusion.12 " + base + "magi_exit_head/while/body/checkpoint/"
        "dot_general",
        "exit_bwd": "fusion.4 " + base + "transpose(jvp(magi_exit_head))/"
        "while/body/checkpoint/reduce_sum",
        "objective": "fusion.9 " + base + "magi_exit_head/exp",
        # containers: their bodies' operations are in the trace too
        "exit_loop": "while.3 " + base + "magi_exit_head/while",
        "pass_loop": "while.1 " + base + "magi_loop/while",
        "other": "fusion.1 " + base + "add",
    }

    def hits(metric):
        rx = re.compile(spec[metric]["pattern"])
        return {k for k, op in ops.items() if rx.search(op)}

    assert hits("train_exit_head_share") == {"exit", "exit_bwd", "objective"}
    for metric in ("train_flex_kernel_share", "train_full_flex_share",
                   "train_full_flex_roofline"):
        assert hits(metric) == {"flex", "flex_bwd"}, metric
    assert spec["train_full_flex_roofline"]["flops"] == "attn_full_executed"
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    listed = {
        m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])
    }
    assert listed == {m["name"] for m in cell.per_layer} == {
        "train_step_steady_ms", "train_mfu_steady", "train_device_idle_share",
        "train_flex_kernel_share", "train_full_flex_share",
        "train_full_flex_roofline", "key_build_ms", "program_trace_s",
        "program_lower_s", "program_compile_s", "program_cache_load_s",
        "train_exit_head_share",
    }
    assert cell.end_to_end == ["train_tokens_per_s", "setup_s"]
    assert bench["workloads"][-2]["name"] == CP4_CELL
    assert bench["workloads"][-1]["name"] == CELL
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 2
    assert len(bench["workloads"]) == 10 and len(bench["configs"]) == 5


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
    [("toy.looped", 0), ("toy.looped", 1), ("toy.looped-cp4", 0)],
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
        # no device trace on the CPU: the share's reader finds nothing and
        # the line leaves it out; the gauge is the program's
        assert set(res["metrics"]) == {
            "train_step_steady_ms", "model_layer_applications",
        }
        assert res["metrics"]["model_layer_applications"]["value"] == 2 * 4


def test_the_check_reads_the_seeds_weights(monkeypatch, _as_the_command_runs):
    """``correct`` is decided on the weights ``--seed`` makes, not on
    what the window trained: a few AdamW steps on the window's batches
    saturate the exit gate at the published widths, and the later passes
    then weigh nothing in the loss (one pass fewer passed there, PR 32)."""
    import jax
    import numpy as np

    from magiattention_tpu.models.pattern import init_pattern_params

    seen = []
    real = train_looped._check

    def spy(job, params):
        seen.append((job, jax.device_get(params)))
        return real(job, params)

    monkeypatch.setattr(train_looped, "_check", spy)
    seed = 2**31 + 77
    with redirect_stdout(io.StringIO()):
        rc = harness.main(
            ["--workload", "toy.looped", "--seed", str(seed), "--seconds",
             "0.5", "--trace", "0", "--root", TOY], allow_cpu=True,
        )
    assert rc == 0
    ((job, got),) = seen
    want = jax.device_get(
        init_pattern_params(train_looped.key_from_seed(seed), job.pcfg)
    )
    assert jax.tree.structure(got) == jax.tree.structure(want)
    # to the last bit or two (the run makes them under jit); one AdamW
    # step moves every weight 3e-4
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-8)
