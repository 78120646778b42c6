"""What ``train_sambay``'s ``correct`` can see, Phi-4-mini-flash-reasoning's
configuration files and operation counts, and the command's own path for
the cell. Toy size, CPU (``data/toy_sambay``: a benchmark of new files
only). The faults' readings share one reference (``check_reference``,
made once) and run the kernels' ``jax.numpy`` backends: what is tested
here is the check, and the kernels are ``tests/test_ops``' and
``tests/test_models``'. The traced rehearsal is
``test_sambay_traced.py``'s (a second worker's: a run of the command is
45 s here)."""

import contextlib
import io
import json
import os
import re
from contextlib import redirect_stdout

import pytest

from benchmarks import flops, flops_phi4flash, harness, masks
from benchmarks.kinds import train_sambay

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TOY = os.path.join(HERE, "data", "toy_sambay")
CELL = "phi4flash-train-16k-traces"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _shift_without_documents(x, tables, plan, axis_name):
    """``shift_local`` that forgets the documents: a document's first
    tokens read the last rows of the one before (cp = 1: a roll)."""
    import jax.numpy as jnp

    return tuple(
        jnp.roll(x, j, axis=0).at[:j].set(0) for j in plan.taps
    )


def _combine(negate_a2=False, normed=True):
    """``pattern._diff_combine`` with a fault in it."""
    import jax
    import jax.numpy as jnp

    from magiattention_tpu.models import pattern

    def combine(out, layer, cfg, index):
        t, d = out.shape[0], cfg.head_dim
        pairs, g = cfg.n_kv_heads // 2, cfg.n_heads // cfg.n_kv_heads
        out = out.reshape(t, pairs, 2, g, 2 * d).astype(jnp.float32)
        lam0 = pattern.diff_lambda_init(index)
        lam = (
            jnp.exp(jnp.sum(layer["lambda_q1"] * layer["lambda_k1"]))
            - jnp.exp(jnp.sum(layer["lambda_q2"] * layer["lambda_k2"])) + lam0
        )
        x = out[:, :, 0] - (-lam if negate_a2 else lam) * out[:, :, 1]
        if normed:
            x = x * jax.lax.rsqrt(
                jnp.mean(x * x, axis=-1, keepdims=True) + cfg.rms_eps
            )
        x = x * layer["diff_norm"] * (1.0 - lam0)
        return x.reshape(t, -1).astype(cfg.jnp_dtype)

    return combine


def _memory_after_the_gate(real):
    import jax

    def mixer(h, layer, cfg, shift, start, **kw):
        out, y = real(h, layer, cfg, shift, start, **kw)
        z = (h @ layer["ssm_in"].astype(h.dtype))[:, cfg.ssm_inner :]
        return out, y * jax.nn.silu(z)

    return mixer


def _cross_on_its_own_input(real, params):
    """A cross layer's keys and values made from ITS input (through the
    full layer's weights), not handed on."""
    from magiattention_tpu.models import pattern

    def half(x, pos, layer, carry, *, cfg, layer_type, **kw):
        if layer_type == pattern.CROSS:
            maker = params["layers"][cfg.kv_layer]
            h = pattern._norm(x, layer, "attn_norm", cfg)
            carry = dict(carry, kv=pattern._diff_kv(
                h @ maker["wk"] + maker["bk"], h @ maker["wv"] + maker["bv"],
                cfg,
            ))
        return real(x, pos, layer, carry, cfg=cfg, layer_type=layer_type, **kw)

    return half


def planted(params):
    """name -> (module, attribute, what takes its place given the real):
    wrong models no configuration field and no weight expresses."""
    from magiattention_tpu.models import pattern, ssm

    return {
        "the state carried across a document's start": (
            pattern, "shift_valid", lambda real: lambda tabs: real(tabs) | True,
        ),
        "the convolution reading across a document's start": (
            pattern, "shift_local", lambda real: _shift_without_documents,
        ),
        "lambda's sign": (
            pattern, "_diff_combine", lambda real: _combine(negate_a2=True),
        ),
        "the sub-norm left out": (
            pattern, "_diff_combine", lambda real: _combine(normed=False),
        ),
        "the memory taken after the gate": (
            ssm, "mamba_mixer", _memory_after_the_gate,
        ),
        "a cross layer on its own input's keys and values": (
            pattern, "_attention_half",
            lambda real: _cross_on_its_own_input(real, params),
        ),
    }


@contextlib.contextmanager
def _planted(fault):
    module, attr, make = fault
    real = getattr(module, attr)
    setattr(module, attr, make(real))
    try:
        yield
    finally:
        setattr(module, attr, real)


@pytest.fixture(scope="module")
def readings():
    """The check's readings by what the model was handed; the reference
    always gets the configuration and the weights as they are, once."""
    import jax
    import jax.numpy as jnp

    from magiattention_tpu.models.pattern import init_pattern_params

    cell = harness.load_cell(TOY, "toy.sambay")
    cfg, tr = cell.config, cell.traffic  # float32: the faults are structure
    low = dict(tr, dtype="bfloat16")
    dev = jax.devices()[:1]
    found = {}
    with jax.enable_x64(False), pytest.MonkeyPatch.context() as mp:
        mp.setenv("MAGI_ATTENTION_KERNEL_BACKEND", "jnp")
        job = train_sambay.Job(cfg, tr, 1, dev)
        params = init_pattern_params(
            train_sambay.key_from_seed(job.seed), job.pcfg
        )
        reference = train_sambay.check_reference(job, params)

        def other(**fields):
            return {"model_job": train_sambay.Job(cfg, tr, job.seed, dev, fields)}

        handed = {
            "float32 model": {},
            "a window one key short (511 for 512)": other(sliding_window=cfg["sliding_window"] - 1),
            "a window one key long (513 for 512)": other(sliding_window=cfg["sliding_window"] + 1),
            "fp8 weights": {
                "model_job": train_sambay.Job(cfg, low, job.seed, dev),
                "model_params": jax.tree.map(
                    lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype),
                    params,
                ),
            },
            "a bfloat16 scan state": other(scan_state_dtype="bfloat16"),
        }
        for name, fault in handed.items():
            found[name] = train_sambay.check_errors(
                job, params, reference=reference, **fault
            )
        for name, fault in planted(params).items():
            with _planted(fault):
                found[name] = train_sambay.check_errors(
                    job, params, reference=reference
                )
    return found


def test_the_float32_model_agrees_far_inside_the_limits(readings):
    rel, grad, scan = readings["float32 model"]
    assert train_sambay.passes(rel, grad, scan)
    assert rel < 1e-5 and max(grad.values()) < 1e-4, grad
    assert scan < train_sambay.SCAN_REL_TOL / 10
    # every parameter is held: the scan's, lambda's, both norms' biases,
    # the tied embedding; no lm_head
    assert set(grad) == {
        "embed", "final_norm", "final_norm_b", "attn_norm", "attn_norm_b",
        "mlp_norm", "mlp_norm_b", "w_gate", "w_up", "w_down",
        "ssm_in", "ssm_conv_w", "ssm_conv_b", "ssm_x", "ssm_dt_w", "ssm_dt_b",
        "ssm_a_log", "ssm_d", "ssm_out", "gmu_in", "gmu_out",
        "wq", "wk", "wv", "wo", "bq", "bk", "bv", "bo", "diff_norm",
        "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2",
    }
    assert set(train_sambay.CANCELLING) <= set(grad)


FAULTS = [
    "the state carried across a document's start",
    "the convolution reading across a document's start",
    # the toy's window is 8 keys, so that one key is an eighth of a row's
    # mass: at 512 it is a five-hundredth, under what bf16 moves, and the
    # chip's check cannot see it (PERF.md section 7)
    "a window one key short (511 for 512)",
    "a window one key long (513 for 512)",
    "lambda's sign", "the sub-norm left out",
    "the memory taken after the gate",
    "a cross layer on its own input's keys and values",
    "fp8 weights", "a bfloat16 scan state",
]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_fails_the_check(readings, fault):
    rel, grad, scan = readings[fault]
    assert not train_sambay.passes(rel, grad, scan), (fault, rel, grad, scan)


def test_a_bfloat16_state_fails_on_the_scans_own_reading(readings):
    """Inside the model a bfloat16 state moves the gradients by a tenth of
    their limit; what holds ``scan_state_dtype`` is the scan run alone on
    float32 operands, where it leaves the float32 reading by orders."""
    rel, grad, scan = readings["a bfloat16 scan state"]
    assert rel <= train_sambay.LOSS_REL_TOL
    assert all(e <= train_sambay.grad_limit(n) for n, e in grad.items())
    assert scan > 5 * train_sambay.SCAN_REL_TOL
    assert scan > 100 * readings["float32 model"][2]


def test_a_dead_bias_is_held_to_the_live_ones_norm(readings):
    """The key's bias moves no score; against its own (zero) norm its
    rounding would read thousands."""
    _rel, grad, _scan = readings["float32 model"]
    assert grad["bk"] < 1e-5
    assert train_sambay.DEAD == {"bk": "bq"}
    assert train_sambay.grad_limit("lambda_q1") == (
        train_sambay.CANCELLING_GRAD_REL_L2_TOL
    ) > train_sambay.grad_limit("ssm_in") == train_sambay.GRAD_REL_L2_TOL


def test_the_timed_step_is_held_by_loss_and_update():
    assert train_sambay.timed_step_passes(1e-5, 0.99)
    assert not train_sambay.timed_step_passes(1e-2, 0.99)
    assert not train_sambay.timed_step_passes(1e-5, 0.0)  # a state unchanged
    assert not train_sambay.timed_step_passes(1e-5, 2.0)  # twice the rate


# ---------------------------------------------------------------------------
# the configuration, its mask and its operation counts
# ---------------------------------------------------------------------------


def test_phi4flash_states_its_widths_as_published():
    cell = harness.load_cell(REPO, CELL)
    cfg = cell.config
    published = {
        "hidden_size": 2560, "num_attention_heads": 40,
        "num_key_value_heads": 20, "intermediate_size": 10240,
        "sliding_window": 512, "mb_per_layer": 2, "layer_norm_eps": 1e-05,
        "vocab_size": 200064, "max_position_embeddings": 262144,
        "tie_word_embeddings": True, "model_type": "phi4flash",
        "hidden_act": "silu", "mlp_bias": False, "lm_head_bias": False,
    }
    assert {k: cfg[k] for k in published} == published
    if os.path.exists(CATALOG):  # every key of the catalog row's config
        with open(CATALOG) as f:
            row = next(
                r for r in map(json.loads, f)
                if r["name"] == "Phi-4-mini-flash-reasoning"
            )
        differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differs == {"num_hidden_layers"}
        assert row["source_url"] in cfg["source"]
    assert list(cfg["reduced"]) == ["num_hidden_layers", "vocab_here"]
    assert cfg["num_hidden_layers"] == 6 == len(cfg["layers_kept"])
    assert cfg["layers_kept"] == [14, 15, 16, 17, 18, 19]
    assert cfg["num_hidden_layers_published"] == 32
    assert "32 published" in cfg["reduced"]["num_hidden_layers"]
    assert cfg["vocab_here"] == 25008 == 200064 // 8
    assert cfg["deployment"]["chips"] == 8
    assert cfg["assumed"]["sizes"] == {
        "d_state": 16, "d_conv": 4, "expand": 2, "dt_rank": 160, "head_dim": 64,
    }
    for key in ("sizes_origin", "layer_kinds", "differential_attention",
                "window", "biases", "position", "initialisation", "labels"):
        assert key in cfg["assumed"]
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(
        c for c in bench["configs"] if c["name"] == "phi-4-mini-flash-reasoning"
    )
    assert entry["reduced"] == list(cfg["reduced"])
    assert entry["source"] == (
        "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/"
        "main/config.json"
    )
    assert (cell.chips, cell.config_name) == (1, "phi-4-mini-flash-reasoning")
    assert cell.traffic["kind"] == "train_sambay"
    assert cell.traffic_name == "train-16k-packed-sambay"


def test_the_pattern_and_the_parameter_count_are_the_files():
    import jax

    from magiattention_tpu.models.pattern import (
        CROSS, DIFF, FULL, GMU, SLIDING, SSM, init_pattern_params,
    )

    cell = harness.load_cell(REPO, CELL)
    cfg = cell.config
    p = train_sambay.Job(cfg, cell.traffic, 0, jax.devices()[:1]).pcfg
    assert p.layer_types == (SSM, SLIDING, SSM, FULL, GMU, CROSS)
    assert p.layer_index == (14, 15, 16, 17, 18, 19)
    assert (p.dim, p.n_heads, p.n_kv_heads, p.head_dim) == (2560, 40, 20, 64)
    assert (p.ssm_inner, p.ssm_state, p.ssm_conv, p.ssm_dt_rank) == (
        5120, 16, 4, 160
    )
    assert (p.attn_form, p.sliding_window, p.vocab_size) == (DIFF, 512, 25008)
    heads = p.kernel_heads
    assert (heads.n_heads, heads.n_kv_heads, heads.head_dim) == (40, 20, 128)
    assert heads.softmax_scale == 0.125 and p.remat and p.dtype == "bfloat16"
    shapes = jax.eval_shape(
        lambda r: init_pattern_params(r, p), jax.random.PRNGKey(0)
    )
    by_layer = [
        sum(v.size for v in jax.tree.leaves(layer)) for layer in shapes["layers"]
    ]
    assert by_layer == [
        119_895_040, 98_322_304, 119_895_040, 98_322_304, 104_867_840,
        91_766_144,
    ]
    total = sum(v.size for v in jax.tree.leaves(shapes))
    assert total == 697_094_272  # ISSUE 46: 697.09 M = 11.15 GB
    assert "697094272" in cfg["parameters"]["six_layers_plus_vocabulary"]
    for i, key in enumerate(
        ("mamba_layer", "attention_layer", None, None, "gmu_layer",
         "cross_layer")
    ):
        if key:
            assert cfg["parameters"][key].endswith(str(by_layer[i]))
    assert "lm_head" not in shapes


def test_the_cells_masks_are_the_issues():
    cell = harness.load_cell(REPO, CELL)
    mask = masks.build_mask(cell.traffic["mask"], 16384, index=0)
    assert mask.doc_lengths == (8192, 4096, 2560, 1024, 512)
    assert mask.area == 45_883_392
    assert train_sambay.window_area(mask.doc_lengths, 512) == 7_734_528
    check = train_sambay.check_mask(cell.traffic)
    assert check.total == cell.traffic["check_tokens"] == 4096
    # two documents longer than the window; a start off the chunk grid
    assert check.doc_lengths == (3072, 640, 384)
    assert cell.traffic["chunk_size"] == 512 and 3712 % 512 != 0
    ids = train_sambay.doc_ids(check)
    assert ids.shape == (4096,) and ids[3071] == 0 and ids[3072] == 1
    assert ids[3711] == 1 and ids[3712] == 2


def test_flops_and_bytes_by_hand():
    """At the toy's size, every term written out."""
    cfg = harness.load_cell(TOY, "toy.sambay").config
    d, e, n, r, taps, ffn, vocab = 64, 128, 4, 4, 4, 96, 512
    q, kv = 4 * 16, 2 * 16
    mamba = d * 2 * e + taps * e + e * (r + 2 * n) + r * e + e * d
    attn = d * (2 * q + 2 * kv)
    kinds = flops_phi4flash.layer_kinds(cfg)
    assert [flops_phi4flash.mixer_params(cfg, k) for k in kinds] == [
        mamba, attn, mamba, attn, 2 * d * e, d * 2 * q
    ]
    per_token = (
        2 * mamba + 2 * attn + 2 * d * e + 2 * d * q + 6 * 3 * d * ffn
        + d * vocab
    )
    assert flops_phi4flash.per_token_params(cfg) == per_token
    area = 12_345
    # a query pair: two score matrices at 16, two value products at 32
    assert flops_phi4flash.attn_fwd_flops(cfg, area) == (
        area * (2 * 4 * 16 + 2 * 4 * 32)
    )
    assert flops_phi4flash.attn_layers(cfg) == {
        "sliding_attention": 1, "full_attention": 2
    }
    tokens = 512
    areas = {"full_attention": 20_000, "sliding_attention": area}
    scans = 2 * 3.0 * 7 * tokens * e * n
    assert flops_phi4flash.train_step_flops(cfg, tokens, areas) == (
        6.0 * per_token * tokens
        + 3.5 * flops_phi4flash.attn_fwd_flops(cfg, area)
        + 2 * 3.5 * flops_phi4flash.attn_fwd_flops(cfg, 20_000) + scans
    )
    assert flops_phi4flash.attn_executed_flops(cfg, "full_attention", 20_000) == (
        2 * 4.5 * flops_phi4flash.attn_fwd_flops(cfg, 20_000)
    )
    # the scan's operands: u and y bf16, delta float32, B and C bf16, a
    # pass; the backward's cotangents beside them
    fwd = tokens * (e * 2 + e * 4 + 2 * n * 2 + e * 2)
    bwd = tokens * (e * 2 + e * 4 + 2 * n * 2 + e * 2 + e * 2 + e * 4 + 2 * n * 2)
    assert flops_phi4flash.ssm_scan_bytes(cfg, tokens) == 2 * (2 * fwd + bwd)


def test_flops_of_the_cells_step():
    """ISSUE 46's count: the matmuls 20.7 TFLOP forward in the layers and
    2.1 in the head; a full-context layer 0.705 TFLOP forward, the window
    layer 0.119; a scan pass 0.67 GB."""
    cfg = harness.load_cell(REPO, CELL).config
    t = 16384
    head = 2560 * 25008
    layers = flops_phi4flash.per_token_params(cfg) - head
    assert 2.0 * t * layers == pytest.approx(20.7e12, rel=5e-3)
    assert 2.0 * t * head == pytest.approx(2.1e12, rel=5e-3)
    assert flops_phi4flash.attn_fwd_flops(cfg, 45_883_392) == pytest.approx(
        0.705e12, rel=1e-3
    )
    assert flops_phi4flash.attn_fwd_flops(cfg, 7_734_528) == pytest.approx(
        0.119e12, rel=5e-3
    )
    assert flops.attn_fwd_flops(45_883_392, 40, 128) == pytest.approx(
        4 / 3 * 0.705e12, rel=1e-3  # what the padded kernels execute
    )
    a_pass = t * (5120 * 8 + 64)
    assert a_pass == pytest.approx(0.67e9, rel=5e-3)
    assert flops_phi4flash.ssm_scan_bytes(cfg, t) == 2 * t * (
        2 * (5120 * 8 + 64) + 5120 * 14 + 128
    )
    step = flops_phi4flash.train_step_flops(
        cfg, t, {"full_attention": 45_883_392, "sliding_attention": 7_734_528}
    )
    assert 73e12 < step < 75e12


def test_the_metric_files_match_the_scopes_the_program_sets():
    """The four new patterns against operation names and scopes as the
    chip's compiler prints them (a compile of the cell's step for a
    described v5e, PR 46), and the lists the cell was appended to."""
    cell = harness.load_cell(REPO, CELL)
    spec = {m["name"]: m["source"] for m in cell.per_layer}
    base = "jit(step)/jit(main)/jit(shmap_body)/"
    ops = {
        "scan": "magi_ssm_scan_fwd_kernel.2 " + base + "checkpoint/"
        "magi_ssm_scan/magi_ssm_scan_fwd_kernel/pallas_call",
        "scan_bwd": "magi_ssm_scan_bwd_kernel " + base + "transpose(jvp("
        "checkpoint))/magi_ssm_scan/magi_ssm_scan_bwd_kernel/pallas_call",
        "scan_glue": "fusion.91 " + base + "checkpoint/magi_ssm_scan/"
        "broadcast_in_dim",
        "mix": "fusion.321 " + base + "checkpoint/magi_ssm_mix/dot_general",
        "mix_remat": "fusion.12 " + base + "transpose(jvp(checkpoint))/"
        "rematted_computation/magi_ssm_mix/logistic",
        "gmu": "fusion.44 " + base + "checkpoint/magi_gmu/dot_general",
        "combine": "fusion.7 " + base + "checkpoint/magi_diff_combine/rsqrt",
        "proj": "fusion.31 " + base + "checkpoint/magi_proj/dot_general",
        "flex_full": "magi_flex_fwd_kernel.2 " + base + "checkpoint/"
        "magi_attn_full/magi_merged_kernel/magi_flex_fwd_kernel/pallas_call",
        "flex_window": "magi_flex_bwd_kernel.1 " + base + "transpose(jvp("
        "checkpoint))/magi_attn_sliding/magi_merged_kernel/"
        "magi_flex_bwd_kernel/pallas_call",
        "loop": "while.3 " + base + "checkpoint/magi_ssm_mix/while",
        "other": "fusion.1 " + base + "add",
    }

    def hits(metric):
        rx = re.compile(spec[metric]["pattern"])
        return {k for k, op in ops.items() if rx.search(op)}

    assert hits("train_ssm_scan_share") == {"scan", "scan_bwd"}
    assert hits("train_ssm_scan_roofline") == {"scan", "scan_bwd"}
    assert hits("train_ssm_mix_share") == {"mix", "mix_remat", "gmu"}
    assert hits("train_diff_combine_share") == {"combine"}
    assert hits("train_proj_share") == {"proj"}
    assert hits("train_flex_kernel_share") == {"flex_full", "flex_window"}
    assert hits("train_full_flex_roofline") == {"flex_full"}
    assert hits("train_sliding_flex_share") == {"flex_window"}
    assert spec["train_ssm_scan_roofline"]["kind"] == "trace_kernel_bytes"
    assert spec["train_ssm_scan_roofline"]["bytes"] == "ssm_scan_bytes"
    # the new scopes are siblings of magi_proj, which the remainder's
    # pattern (the benchmark's file) does not know: it would read them
    # too, so the cell is not on that metric's list (PERF.md section 7)
    unscoped = json.load(open(os.path.join(
        REPO, "benchmarks", "metrics", "train_unscoped_share.json"
    )))["source"]["pattern"]
    assert {k for k, op in ops.items() if re.search(unscoped, op)} == {
        "scan", "scan_bwd", "scan_glue", "mix", "mix_remat", "gmu", "combine",
        "other",
    }
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    listed = {
        m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])
    }
    assert listed == {m["name"] for m in cell.per_layer} == {
        "train_step_steady_ms", "train_mfu_steady", "train_device_idle_share",
        "train_flex_kernel_share", "train_sliding_flex_share",
        "train_sliding_flex_roofline", "train_full_flex_share",
        "train_full_flex_roofline", "train_proj_share", "train_ffn_share",
        "train_embed_share", "train_head_share", "train_optimizer_share",
        "train_attn_layout_share", "train_remat_share", "key_build_ms",
        "program_trace_s", "program_lower_s", "program_compile_s",
        "program_cache_load_s", "train_ssm_scan_share",
        "train_ssm_scan_roofline", "train_ssm_mix_share",
        "train_diff_combine_share",
    }
    assert "train_unscoped_share" not in listed
    assert cell.end_to_end == ["train_tokens_per_s", "setup_s"]
    for name in ("train_ssm_scan_share", "train_ssm_scan_roofline",
                 "train_ssm_mix_share", "train_diff_combine_share"):
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert (entry["moves"], entry["source"], entry["unit"]) == (
            "train_tokens_per_s", "device_trace", "%"
        )
    assert bench["workloads"][-1] == {
        "name": CELL, "config": "phi-4-mini-flash-reasoning",
        "traffic": "train-16k-packed-sambay", "chips": 1,
        "why": bench["workloads"][-1]["why"],
    }
    assert len(bench["workloads"]) == 13
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 2


def test_the_bytes_source_reads_the_hbm_roofline():
    """``trace_kernel_bytes`` on a trace of one kernel event: bytes over
    the HBM's pace over the kernel's time; nothing to read, nothing
    reported."""
    from benchmarks import trace_reduce
    from benchmarks.sources import trace_kernel_bytes

    spec = {"pattern": "magi_ssm_scan_\\w+_kernel", "phase": "window",
            "bytes": "ssm_scan_bytes"}

    class Trace:
        def phase(self, name):
            return (0, 10**9) if name == "window" else None

    obs = harness.Observations(
        end_to_end={}, attempted=1, failed=0, correct=True,
        flops={"ssm_scan_bytes": 8.19e9}, iters={"window": 2},
    )
    obs.trace, obs.peaks = Trace(), {"hbm_gbps": 819.0}
    seen = {}

    def kernel_seconds(trace, pattern, lo, hi):
        seen["pattern"] = pattern
        return seen.get("seconds", 0.0)

    real, trace_reduce.kernel_seconds = (
        trace_reduce.kernel_seconds, kernel_seconds
    )
    try:
        assert trace_kernel_bytes.read(spec, obs) is None  # no such kernel
        seen["seconds"] = 0.1
        # 2 x 8.19 GB at 819 GB/s are 20 ms of the kernels' 100
        assert trace_kernel_bytes.read(spec, obs) == pytest.approx(20.0)
        obs.flops = {}
        assert trace_kernel_bytes.read(spec, obs) is None  # a parent's run
    finally:
        trace_reduce.kernel_seconds = real
    assert seen["pattern"] == spec["pattern"]


# ---------------------------------------------------------------------------
# the command's own path
# ---------------------------------------------------------------------------


def test_rehearsal_prints_the_result_line():
    import jax

    out = io.StringIO()
    with jax.enable_x64(False), redirect_stdout(out):
        rc = harness.main(
            ["--workload", "toy.sambay", "--seed", str(2**31 + 12345),
             "--seconds", "1.5", "--trace", "0", "--root", TOY],
            allow_cpu=True,
        )
    assert rc == 0
    lines = out.getvalue().strip().splitlines()
    res = json.loads(lines[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1 and res["device"]["count"] == 1
    assert any("] shift: taps (1, 2, 3) over 3" in ln for ln in lines)
    assert any("correct=True of the timed step" in ln for ln in lines)
    assert any("tiles of sliding_attention" in ln for ln in lines)
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())


def test_the_parent_has_no_such_cell():
    """An unknown workload fails at once, before jax is touched: how the
    parent's own benchmark answers the new cell."""
    with pytest.raises(SystemExit, match="no workload"):
        harness.load_cell(TOY, CELL)
