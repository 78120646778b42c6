"""What ``train_ssd``'s ``correct`` can see, granite-4.0-h-micro's
configuration files and operation counts, and the command's own path for
the cell. Toy size, CPU (``data/toy_ssd``: a benchmark of new files
only). The faults' readings share one reference (``check_reference``,
made once) and run the kernels' ``jax.numpy`` backends: what is tested
here is the check, and the kernels are ``tests/test_ops``' and
``tests/test_models``'. The traced rehearsal is
``test_ssd_traced.py``'s (a second worker's)."""

import io
import json
import os
import re
from contextlib import redirect_stdout

import pytest

from benchmarks import flops, flops_granite, harness, masks
from benchmarks.kinds import train_ssd
from tests.test_benchmarks.test_sambay_check import (
    _planted, _shift_without_documents,
)

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TOY = os.path.join(HERE, "data", "toy_ssd")
CELL = "granite4hmicro-train-packed-traces"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def planted():
    """name -> (module, attribute, what takes its place given the real):
    wrong models no configuration field and no weight expresses."""
    from magiattention_tpu.models import pattern

    return {
        "the state carried across a document's start": (
            pattern, "shift_valid", lambda real: lambda tabs: real(tabs) | True,
        ),
        "the convolution reading across a document's start": (
            pattern, "shift_local", lambda real: _shift_without_documents,
        ),
    }


@pytest.fixture(scope="module")
def readings():
    """The check's readings by what the model was handed; the reference
    always gets the configuration and the weights as they are, once."""
    import jax
    import jax.numpy as jnp

    from magiattention_tpu.models.pattern import init_pattern_params

    cell = harness.load_cell(TOY, "toy.ssd")
    cfg, tr = cell.config, cell.traffic  # float32: the faults are structure
    low = dict(tr, dtype="bfloat16")
    dev = jax.devices()[:1]
    found = {}
    with jax.enable_x64(False), pytest.MonkeyPatch.context() as mp:
        mp.setenv("MAGI_ATTENTION_KERNEL_BACKEND", "jnp")
        job = train_ssd.Job(cfg, tr, 1, dev)
        params = init_pattern_params(
            train_ssd.key_from_seed(job.seed), job.pcfg
        )
        reference = train_ssd.check_reference(job, params)

        def other(**fields):
            return {"model_job": train_ssd.Job(cfg, tr, job.seed, dev, fields)}

        handed = {
            "float32 model": {},
            "the residual multiplier left out": other(residual_scale=1.0),
            "the softmax scale 1 / sqrt(d) for the published 1 / d": other(
                softmax_scale=None
            ),
            "fp8 weights": {
                "model_job": train_ssd.Job(cfg, low, job.seed, dev),
                "model_params": jax.tree.map(
                    lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype),
                    params,
                ),
            },
            "a bfloat16 scan state": other(scan_state_dtype="bfloat16"),
        }
        for name, fault in handed.items():
            found[name] = train_ssd.check_errors(
                job, params, reference=reference, **fault
            )
        for name, fault in planted().items():
            with _planted(fault):
                found[name] = train_ssd.check_errors(
                    job, params, reference=reference
                )
    return found


def test_the_float32_model_agrees_far_inside_the_limits(readings):
    rel, grad, scan = readings["float32 model"]
    assert train_ssd.passes(rel, grad, scan)
    assert rel < 1e-5 and max(grad.values()) < 1e-4, grad
    assert scan < train_ssd.SCAN_REL_TOL / 10
    # every parameter is held: the mixer's, the attention's, the tied
    # embedding; no lm_head
    assert set(grad) == {
        "embed", "final_norm", "attn_norm", "mlp_norm", "w_gate", "w_up",
        "w_down", "ssd_in", "ssd_conv_w", "ssd_conv_b", "ssd_dt_b",
        "ssd_a_log", "ssd_d", "ssd_norm", "ssd_out", "wq", "wk", "wv", "wo",
    }


# the embedding's and the logits' scalars and attention across documents
# are ``tests/test_models/test_pattern_ssd.py``'s, on the same comparison
FAULTS = [
    "the residual multiplier left out",
    "the softmax scale 1 / sqrt(d) for the published 1 / d",
    "the state carried across a document's start",
    "the convolution reading across a document's start",
    "fp8 weights", "a bfloat16 scan state",
]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_fails_the_check(readings, fault):
    rel, grad, scan = readings[fault]
    assert not train_ssd.passes(rel, grad, scan), (fault, rel, grad, scan)


def test_a_bfloat16_state_fails_on_the_scans_own_reading(readings):
    """Inside the model a bfloat16 state at the chunks' ends hides in what
    bf16 activations already do; what holds ``scan_state_dtype`` is the
    scan run alone on float32 operands, where it leaves the float32
    reading by orders."""
    rel, grad, scan = readings["a bfloat16 scan state"]
    assert rel <= train_ssd.LOSS_REL_TOL
    assert scan > 1.5 * train_ssd.SCAN_REL_TOL
    assert scan > 100 * readings["float32 model"][2]


def test_the_timed_step_is_held_by_loss_and_update():
    assert train_ssd.timed_step_passes(1e-5, 0.99)
    assert not train_ssd.timed_step_passes(1e-2, 0.99)
    assert not train_ssd.timed_step_passes(1e-5, 0.0)  # a state unchanged
    assert not train_ssd.timed_step_passes(1e-5, 2.0)  # twice the rate


# ---------------------------------------------------------------------------
# the configuration, its mask and its operation counts
# ---------------------------------------------------------------------------


def test_granite_states_its_widths_as_published():
    cell = harness.load_cell(REPO, CELL)
    cfg = cell.config
    published = {
        "hidden_size": 2048, "num_attention_heads": 32,
        "num_key_value_heads": 8, "intermediate_size": 8192,
        "shared_intermediate_size": 8192, "mamba_n_heads": 64,
        "mamba_d_head": 64, "mamba_d_state": 128, "mamba_n_groups": 1,
        "mamba_d_conv": 4, "mamba_expand": 2, "mamba_chunk_size": 256,
        "embedding_multiplier": 12, "residual_multiplier": 0.22,
        "attention_multiplier": 0.015625, "logits_scaling": 8,
        "position_embedding_type": "nope", "vocab_size": 100352,
        "tie_word_embeddings": True, "model_type": "granitemoehybrid",
        "num_local_experts": 0, "rms_norm_eps": 1e-05,
    }
    assert {k: cfg[k] for k in published} == published
    assert len(cfg["layer_types"]) == 40  # the published list, whole
    assert [i for i, k in enumerate(cfg["layer_types"]) if k == "attention"] == [
        5, 15, 25, 35
    ]
    if os.path.exists(CATALOG):  # every key of the catalog row's config
        with open(CATALOG) as f:
            row = next(
                r for r in map(json.loads, f)
                if r["name"] == "granite-4.0-h-micro"
            )
        differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differs == {"num_hidden_layers"}
        assert row["source_url"] in cfg["source"]
    assert list(cfg["reduced"]) == ["num_hidden_layers", "vocab_here"]
    assert cfg["num_hidden_layers"] == 10
    assert cfg["num_hidden_layers_published"] == 40
    assert "40 published" in cfg["reduced"]["num_hidden_layers"]
    assert cfg["vocab_here"] == 25088 == 100352 // 4
    assert cfg["deployment"]["chips"] == 4
    assert cfg["assumed"]["sizes"] == {"head_dim": 64}
    for key in ("sizes_origin", "layer_kinds", "multipliers", "fused_matrices",
                "gated_norm", "delta", "documents", "position",
                "initialisation", "labels", "head"):
        assert key in cfg["assumed"]
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == cell.config_name)
    assert entry["reduced"] == list(cfg["reduced"])
    assert entry["source"] == (
        "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/"
        "config.json"
    )
    assert (cell.chips, cell.config_name) == (1, "granite-4.0-h-micro")
    assert cell.traffic["kind"] == "train_ssd"
    assert cell.traffic_name == "train-16k-packed-ssd"


def test_the_pattern_and_the_parameter_count_are_the_files():
    import jax

    from magiattention_tpu.models.pattern import (
        FULL, GQA, SSD, init_pattern_params,
    )

    cell = harness.load_cell(REPO, CELL)
    cfg = cell.config
    p = train_ssd.Job(cfg, cell.traffic, 0, jax.devices()[:1]).pcfg
    assert p.layer_types == (SSD,) * 5 + (FULL,) + (SSD,) * 4
    assert (p.dim, p.n_heads, p.n_kv_heads, p.head_dim) == (2048, 32, 8, 64)
    assert (p.ssm_inner, p.ssm_heads, p.ssm_state, p.ssm_conv, p.ssm_chunk) == (
        4096, 64, 128, 4, 256
    )
    assert (p.attn_form, p.vocab_size, p.rope_kinds) == (GQA, 25088, ())
    assert (p.embed_scale, p.residual_scale, p.softmax_scale,
            p.logits_scaling) == (12.0, 0.22, 0.015625, 8.0)
    assert p.kernel_heads is p  # the width as it is: no padded lanes
    assert p.remat and p.dtype == "bfloat16" and p.scan_state_dtype == "float32"
    shapes = jax.eval_shape(
        lambda r: init_pattern_params(r, p), jax.random.PRNGKey(0)
    )
    by_layer = [
        sum(v.size for v in jax.tree.leaves(layer)) for layer in shapes["layers"]
    ]
    assert by_layer == [76_182_976] * 5 + [60_821_504] + [76_182_976] * 4
    total = sum(v.size for v in jax.tree.leaves(shapes))
    assert total == 797_850_560  # ISSUE 55's ladder: a quarter of the rows
    assert total - 12_544 * 2048 == 772_160_448  # its first rung, an eighth
    assert "797850560" in cfg["parameters"]["ten_layers_plus_vocabulary"]
    assert "772160448" in cfg["parameters"]["ten_layers_plus_vocabulary"]
    assert cfg["parameters"]["mamba_layer"].endswith(str(by_layer[0]))
    assert cfg["parameters"]["attention_layer"].endswith(str(by_layer[5]))
    assert cfg["parameters"]["vocabulary_slice"].endswith(str(25088 * 2048))
    assert "lm_head" not in shapes


def test_the_cells_masks_are_the_issues():
    cell = harness.load_cell(REPO, CELL)
    mask = masks.build_mask(cell.traffic["mask"], 16384, index=0)
    assert mask.doc_lengths == (8000, 4200, 2560, 1100, 524)
    starts = [8000, 12200, 14760, 15860]
    assert all(s % 128 for s in starts)  # none on a multiple of 128 or 256
    assert train_ssd.reset_chunks(mask.doc_lengths, 256) == 4
    assert 33.0 < 100 * mask.area / (16384 * 16385 / 2) < 34.0
    check = train_ssd.check_mask(cell.traffic)
    assert check.total == cell.traffic["check_tokens"] == 4096
    assert check.doc_lengths == (2900, 812, 384)
    assert train_ssd.reset_chunks(check.doc_lengths, 256) == 2
    assert 2900 > 11 * 256  # one document longer than eleven chunks
    assert cell.traffic["chunk_size"] == 512
    # a reset on a chunk's edge is no reset inside one
    assert train_ssd.reset_chunks((256, 100, 156), 256) == 1


def test_flops_and_bytes_by_hand():
    """At the toy's size, every term written out."""
    cfg = harness.load_cell(TOY, "toy.ssd").config
    d, e, n, h, taps, ffn, vocab, q = 64, 128, 16, 4, 4, 96, 512, 32
    mamba = d * (2 * e + 2 * n + h) + taps * (e + 2 * n) + e * d
    attn = d * 2 * (4 + 2) * 16
    kinds = flops_granite.layer_kinds(cfg)
    assert kinds == ["mamba", "attention", "mamba", "mamba"]
    assert [flops_granite.mixer_params(cfg, k) for k in kinds] == [
        mamba, attn, mamba, mamba
    ]
    per_token = 3 * mamba + attn + 4 * 3 * d * ffn + d * vocab
    assert flops_granite.per_token_params(cfg) == per_token
    area, tokens = 12_345, 512
    assert flops_granite.attn_fwd_flops(cfg, area) == 4 * area * 4 * 16
    a_pass = tokens * (2 * q * n + 2 * q * e + 4 * e * n)
    assert flops_granite.ssd_scan_fwd_flops(cfg, tokens) == a_pass
    assert flops_granite.train_step_flops(cfg, tokens, area) == (
        6.0 * per_token * tokens
        + 3.5 * flops_granite.attn_fwd_flops(cfg, area) + 3 * 3.0 * a_pass
    )
    # as launched: the attention's forward once and backward once, the
    # scan's forward twice and backward (2 x forward) once
    assert flops_granite.attn_executed_flops(cfg, area) == (
        3.5 * flops_granite.attn_fwd_flops(cfg, area)
    )
    assert flops_granite.ssd_scan_flops(cfg, tokens) == 3 * 4.0 * a_pass
    fwd = tokens * (e * 2 + h * 4 + 2 * n * 2 + e * 2)
    bwd = tokens * (
        e * 2 + h * 4 + 2 * n * 2 + e * 2 + e * 2 + h * 4 + 2 * n * 2
    )
    assert flops_granite.ssd_scan_bytes(cfg, tokens) == 3 * (2 * fwd + bwd)


def test_flops_of_the_cells_step():
    """ISSUE 55's count: a scan's forward pass 69.8 GFLOP a layer at
    16,384 rows; the attention layer's forward on the exact area at 32 x
    64."""
    cfg = harness.load_cell(REPO, CELL).config
    t, area = 16384, 44_847_280
    assert flops_granite.ssd_scan_fwd_flops(cfg, t) == pytest.approx(
        69.8e9, rel=1e-3
    )
    assert flops_granite.ssd_scan_flops(cfg, t) == 9 * 4 * (
        flops_granite.ssd_scan_fwd_flops(cfg, t)
    )
    assert flops_granite.attn_fwd_flops(cfg, area) == flops.attn_fwd_flops(
        area, 32, 64
    )
    assert flops_granite.per_token_params(cfg) == (
        9 * (2048 * 8512 + 4 * 4352 + 4096 * 2048) + 2 * 2048 * 2048
        + 2 * 2048 * 512 + 10 * 3 * 2048 * 8192 + 2048 * 25088
    )
    a_pass = t * (4096 * 4 + 64 * 4 + 256 * 2)
    assert flops_granite.ssd_scan_bytes(cfg, t) == 9 * (
        2 * a_pass + t * (4096 * 6 + 64 * 8 + 512 * 2)
    )
    step = flops_granite.train_step_flops(cfg, t, area)
    assert 80e12 < step < 83e12


def test_the_executed_counts_are_the_steps_launches():
    """``flops_granite.LAUNCHES`` against the kernels of the toy step's
    gradient under remat: the attention layer's forward once (its out and
    lse are kept across the checkpoint) and its backward once; a Mamba-2
    layer's scan forward twice (remat keeps nothing of it) and backward
    once. A count that differed would make a roofline over- or
    under-read."""
    import jax

    from magiattention_tpu.models.pattern import init_pattern_params
    from tests.test_models.pattern_harness import _kernels_by_name

    cell = harness.load_cell(TOY, "toy.ssd")
    with jax.enable_x64(False):
        job = train_ssd.Job(cell.config, cell.traffic, 0, jax.devices()[:1])
        assert job.pcfg.remat
        mask = masks.build_mask(
            cell.traffic["mask"], cell.traffic["total_tokens"], index=0
        )
        model, meta = job.build(mask)
        params = jax.eval_shape(
            lambda r: init_pattern_params(r, job.pcfg), jax.random.PRNGKey(0)
        )
        _g, *batch = job.batch_for(meta, mask.total, 0)
        jaxpr = jax.make_jaxpr(jax.value_and_grad(model.loss_fn))(
            params, *batch, model.sharded_tables()
        ).jaxpr
    kinds = flops_granite.layer_kinds(cell.config)
    layers = {"flex": kinds.count("attention"), "ssd_scan": kinds.count("mamba")}
    assert dict(_kernels_by_name(jaxpr)) == {
        name: n * layers["flex" if "flex" in name else "ssd_scan"]
        for name, n in flops_granite.LAUNCHES.items()
    }
    assert flops_granite.ATTN_EXECUTED_OVER_FWD == 3.5


def test_the_metric_files_match_the_scopes_the_program_sets():
    """The three new patterns and the lists the cell was appended to,
    against operation names and scopes as the chip's compiler prints them
    (a compile of the cell's step for a described v5e, PR 55)."""
    cell = harness.load_cell(REPO, CELL)
    spec = {m["name"]: m["source"] for m in cell.per_layer}
    base = "jit(step)/jit(main)/jit(shmap_body)/"
    ops = {
        "scan": "magi_ssd_scan_fwd_kernel.2 " + base + "checkpoint/"
        "magi_ssd_scan/magi_ssd_scan_fwd_kernel/pallas_call",
        "scan_remat": "magi_ssd_scan_fwd_kernel.11 " + base + "transpose(jvp("
        "checkpoint))/rematted_computation/magi_ssd_scan/"
        "magi_ssd_scan_fwd_kernel/pallas_call",
        "scan_bwd": "magi_ssd_scan_bwd_kernel " + base + "transpose(jvp("
        "checkpoint))/magi_ssd_scan/magi_ssd_scan_bwd_kernel/pallas_call",
        "scan_glue": "fusion.91 " + base + "checkpoint/magi_ssd_scan/cumsum",
        "mix": "fusion.321 " + base + "checkpoint/magi_ssm_mix/logistic",
        "proj": "fusion.31 " + base + "checkpoint/magi_proj/dot_general",
        "flex_full": "magi_flex_fwd_kernel.2 " + base + "checkpoint/"
        "magi_attn_full/magi_merged_kernel/magi_flex_fwd_kernel/pallas_call",
        "phi4s_scan": "magi_ssm_scan_fwd_kernel.2 " + base + "checkpoint/"
        "magi_ssm_scan/magi_ssm_scan_fwd_kernel/pallas_call",
        "other": "fusion.1 " + base + "add",
    }

    def hits(metric):
        rx = re.compile(spec[metric]["pattern"])
        return {k for k, op in ops.items() if rx.search(op)}

    kernels = {"scan", "scan_remat", "scan_bwd"}
    assert hits("train_ssd_scan_share") == kernels
    assert hits("train_ssd_scan_roofline") == kernels
    assert hits("train_ssd_scan_hbm_share") == kernels
    assert hits("train_ssm_mix_share") == {"mix"}
    assert hits("train_proj_share") == {"proj"}
    assert hits("train_flex_kernel_share") == {"flex_full"}
    assert hits("train_full_flex_roofline") == {"flex_full"}
    assert (spec["train_ssd_scan_roofline"]["kind"],
            spec["train_ssd_scan_roofline"]["flops"]) == (
        "trace_kernel", "ssd_scan_executed"
    )
    assert (spec["train_ssd_scan_hbm_share"]["kind"],
            spec["train_ssd_scan_hbm_share"]["bytes"]) == (
        "trace_kernel_bytes", "ssd_scan_bytes"
    )
    # the new scope is a sibling of magi_proj, which the remainder's
    # pattern (the benchmark's file) does not know: it would read it too,
    # so the cell is not on that metric's list (PERF.md section 7)
    unscoped = json.load(open(os.path.join(
        REPO, "benchmarks", "metrics", "train_unscoped_share.json"
    )))["source"]["pattern"]
    assert kernels | {"scan_glue"} <= {
        k for k, op in ops.items() if re.search(unscoped, op)
    }
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    listed = {
        m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])
    }
    new = {"train_ssd_scan_share", "train_ssd_scan_roofline",
           "train_ssd_scan_hbm_share"}
    assert listed == {m["name"] for m in cell.per_layer} == new | {
        "train_step_steady_ms", "train_mfu_steady", "train_device_idle_share",
        "train_flex_kernel_share", "train_full_flex_share",
        "train_full_flex_roofline", "train_ssm_mix_share", "train_proj_share",
        "train_ffn_share", "train_embed_share", "train_head_share",
        "train_optimizer_share", "train_attn_layout_share",
        "train_remat_share", "key_build_ms", "program_trace_s",
        "program_lower_s", "program_compile_s", "program_cache_load_s",
        "setup_boot_s", "setup_package_import_s", "program_trace_attn_s",
        "program_trace_unscoped_s", "setup_unspanned_s",
    }
    assert cell.end_to_end == ["train_tokens_per_s", "setup_s"]
    for name in new:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert (entry["moves"], entry["source"], entry["unit"], entry["layer"]) == (
            "train_tokens_per_s", "device_trace", "%", "kernels"
        )
    ours = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (ours["config"], ours["traffic"], ours["chips"]) == (
        "granite-4.0-h-micro", "train-16k-packed-ssd", 1
    )
    assert len(ours["why"]) <= 200
    # one cell in four may hold four chips; this one adds none
    assert 4 * sum(w["chips"] == 4 for w in bench["workloads"]) <= len(
        bench["workloads"]
    )


def test_the_program_sets_the_series_the_documents_name():
    from magiattention_tpu.telemetry import collectors

    assert collectors.M_SSD_SCAN_CALLS == "magi_ssd_scan_calls_total"
    assert (collectors.M_SSD_HEADS, collectors.M_SSD_CHUNKS,
            collectors.M_SSD_RESET_CHUNKS, collectors.M_SSD_STATE_BYTES) == (
        "magi_ssd_heads", "magi_ssd_chunks", "magi_ssd_reset_chunks",
        "magi_ssd_state_bytes",
    )
    assert collectors.M_MODEL_MULTIPLIERS == "magi_model_multipliers"
    with open(os.path.join(REPO, "docs", "observability.md")) as f:
        text = f.read()
    for name in ("magi_ssd_scan", "magi_ssd_scan_calls_total", "magi_ssd_heads",
                 "magi_ssd_chunks", "magi_ssd_reset_chunks",
                 "magi_ssd_state_bytes", "magi_model_multipliers",
                 "train_ssd_scan_share", "train_ssd_scan_roofline",
                 "train_ssd_scan_hbm_share"):
        assert name in text, name


# ---------------------------------------------------------------------------
# the command's own path
# ---------------------------------------------------------------------------


def test_rehearsal_prints_the_result_line():
    import jax

    out = io.StringIO()
    with jax.enable_x64(False), redirect_stdout(out):
        rc = harness.main(
            ["--workload", "toy.ssd", "--seed", str(2**31 + 12345),
             "--seconds", "1.5", "--trace", "0", "--root", TOY],
            allow_cpu=True,
        )
    assert rc == 0
    lines = out.getvalue().strip().splitlines()
    res = json.loads(lines[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1 and res["device"]["count"] == 1
    assert any("] shift: taps (1, 2, 3) over 3" in ln for ln in lines)
    assert any("2 of 16 scan chunks hold a reset" in ln for ln in lines)
    assert any("correct=True of the timed step" in ln for ln in lines)
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())


def test_the_parent_has_no_such_cell():
    """An unknown workload fails at once, before jax is touched: how the
    parent's own benchmark answers the new cell."""
    with pytest.raises(SystemExit, match="no workload"):
        harness.load_cell(TOY, CELL)
