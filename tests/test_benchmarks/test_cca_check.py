"""What ``train_cca``'s ``correct`` can see, ZAYA1-8B's configuration
files and operation counts, and the command's own path for the cell. Toy
size, CPU (``data/toy_cca``: a benchmark of new files only)."""

import contextlib
import io
import json
import os
import re
from contextlib import redirect_stdout

import pytest

from benchmarks import flops, flops_zaya, harness, masks
from benchmarks.kinds import train_cca

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TOY = os.path.join(HERE, "data", "toy_cca")
CELL = "zaya1-train-16k-traces"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


class _OneDocument(train_cca.Job):
    """The model planned as if the packed sequence were one document:
    attention, the convolutions and the value's shift all cross."""

    def build(self, mask):
        return super().build(
            masks.build_mask(
                {"type": "varlen_block_causal", "lengths": [mask.total]},
                mask.total,
            )
        )


class _ShiftAcrossDocuments(train_cca.Job):
    """The attention's mask sound, the shift's documents forgotten: a
    document's first tokens read the last rows of the one before."""

    def build(self, mask):
        import dataclasses

        from magiattention_tpu.parallel.dispatch import make_shift_plan

        model, meta = super().build(mask)
        plan = make_shift_plan(meta, [0, mask.total], self.pcfg.shift_taps)
        return dataclasses.replace(model, shift_plan=plan), meta


PLANTED = {
    # (the attribute of models/pattern.py, what takes its place)
    "the router's state dropped between layers": (
        "_router_scores",
        lambda real: lambda h, r, layer, cfg: real(h, 0.0 * r, layer, cfg),
    ),
    "the q-k mean left out": (
        "_qk_mean",
        lambda real: lambda q, k: tuple(0.0 * m for m in real(q, k)),
    ),
}


@contextlib.contextmanager
def _planted(name):
    """A wrong model no configuration field and no weight expresses, for
    the length of a ``with``; the model is traced inside it."""
    from magiattention_tpu.models import pattern

    attr, make = PLANTED[name]
    real = getattr(pattern, attr)
    setattr(pattern, attr, make(real))
    try:
        yield
    finally:
        setattr(pattern, attr, real)


@pytest.fixture(scope="module")
def readings():
    """The check's readings by what the model was handed; the reference
    always gets the configuration and the weights as they are."""
    import jax
    import jax.numpy as jnp

    from magiattention_tpu.models.pattern import init_pattern_params

    cell = harness.load_cell(TOY, "toy.cca")
    # the toy traffic file says float32; the readings are of bf16, as the
    # cell runs. At this size (256 tokens, a seeded share of them with a
    # held expert) the expert half's readings swing 10x with the seed
    # (0.02 to 0.10 over eight seeds: one token's weight moves them, where
    # 4,096 tokens at the published widths read 0.02 to 0.05); seed 1
    # reads a quarter of the limits
    cfg, tr = cell.config, dict(cell.traffic, dtype="bfloat16")
    dev = jax.devices()[:1]
    found = {}
    with jax.enable_x64(False):
        job = train_cca.Job(cfg, tr, 1, dev)
        params = init_pattern_params(
            train_cca.key_from_seed(job.seed), job.pcfg
        )

        def other(**fields):
            return {"model_job": train_cca.Job(cfg, tr, job.seed, dev, fields)}

        handed = {
            "bf16, as the cell runs": {},
            "float32 model": {"model_job": train_cca.Job(
                cfg, dict(tr, dtype="float32"), job.seed, dev
            )},
            "a bfloat16 router": other(router_dtype="bfloat16"),
            "rotary on the whole head": other(rope_head_dim=16),
            "the depthwise convolution one tap short": {
                "model_params": dict(params, layers=[
                    dict(layer, cca_conv1_w=layer["cca_conv1_w"].at[1].set(0))
                    for layer in params["layers"]
                ]),
            },
            "the temperature left out": {
                "model_params": dict(params, layers=[
                    dict(layer, cca_temp=jnp.ones_like(layer["cca_temp"]))
                    for layer in params["layers"]
                ]),
            },
            "attention and shifts across documents": {
                "model_job": _OneDocument(cfg, tr, job.seed, dev)
            },
            "a shift that leaks a row across a document": {
                "model_job": _ShiftAcrossDocuments(cfg, tr, job.seed, dev)
            },
            "fp8 weights": {"model_params": jax.tree.map(
                lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype), params
            )},
        }
        for name, fault in handed.items():
            found[name] = train_cca.check_errors(job, params, **fault)
        for name in PLANTED:
            with _planted(name):
                found[name] = train_cca.check_errors(job, params)
    return found


def test_the_cell_as_it_runs_passes(readings):
    for name in ("bf16, as the cell runs", "float32 model"):
        assert train_cca.passes(*readings[name]), (name, readings[name])
    rel, grad, routing = readings["float32 model"]
    # float32 against float32 agrees far inside what bf16 is allowed
    assert rel < 1e-5 and max(grad.values()) < 1e-4
    assert routing["flipped_share"] == 0.0 == routing["worst_margin"]
    # the router alone on the reference's inputs: float32 on both sides
    assert routing["router_score_rel"] < 1e-5 > routing["router_state_rel"]
    # every parameter is held: the convolutions', the temperatures, the
    # router's six and the tied embedding among them; no lm_head
    assert set(grad) == {
        "embed", "final_norm", "attn_norm", "mlp_norm", "wq", "wk", "wv",
        "wo", "cca_conv1_w", "cca_conv1_b", "cca_conv2_w", "cca_conv2_b",
        "cca_temp", *train_cca.ROUTER, "we_gate", "we_up", "we_down",
    }


FAULTS = [
    "rotary on the whole head",
    "the depthwise convolution one tap short", "the temperature left out",
    "attention and shifts across documents",
    "a shift that leaks a row across a document",
    "the router's state dropped between layers", "the q-k mean left out",
    "fp8 weights", "a bfloat16 router",
]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_fails_the_check(readings, fault):
    rel, grad, routing = readings[fault]
    assert not train_cca.passes(rel, grad, routing), (fault, rel, grad, routing)


def test_a_bfloat16_router_fails_on_the_routers_own_reading(readings):
    """Inside the model a bfloat16 router reads the bf16 hidden state
    either way and moves the router's six gradients by no more than the
    seed moves them: what holds ``router_dtype`` is the router run alone
    on the reference's float32 inputs, where the state it hands on and
    its chosen score leave the float32 reading by three orders."""
    sound = readings["bf16, as the cell runs"][2]
    _rel, _grad, routing = readings["a bfloat16 router"]
    for reading in ("router_state_rel", "router_score_rel"):
        assert sound[reading] < train_cca.ROUTER_REL_TOL / 10
        assert routing[reading] > 100 * sound[reading]
    assert routing["router_state_rel"] > 10 * train_cca.ROUTER_REL_TOL


def test_the_leak_is_one_row_a_document_and_a_gradient_holds_it(readings):
    """A shift that forgets the documents moves two documents' first two
    tokens and nothing else forward: it is the convolutions' and the
    value's gradients that see it, at twice their limit or more."""
    _rel, grad, _routing = readings["a shift that leaks a row across a document"]
    assert max(grad.values()) > 2 * train_cca.GRAD_REL_L2_TOL, grad


# ---------------------------------------------------------------------------
# the configuration, its mask and its operation counts
# ---------------------------------------------------------------------------


def test_zaya_states_its_widths_as_published():
    cell = harness.load_cell(REPO, CELL)
    cfg = cell.config
    published = {
        "hidden_size": 2048, "head_dim": 128, "num_attention_heads": 8,
        "num_key_value_heads": 2, "cca_time0": 2, "cca_time1": 2,
        "moe_intermediate_size": 2048, "num_experts": 16,
        "num_experts_per_tok": 1, "router_hidden_size": 256,
        "partial_rotary_factor": 0.5, "vocab_size": 262272,
        "rms_norm_eps": 1e-05, "max_position_embeddings": 131072,
        "tie_word_embeddings": True, "model_type": "zaya",
        "hidden_act": "silu", "sliding_window": None,
    }
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_parameters"]["hybrid"] == {
        "partial_rotary_factor": 0.5, "rope_theta": 5000000,
        "rope_type": "default",
    }
    if os.path.exists(CATALOG):  # every key of the catalog row's config
        with open(CATALOG) as f:
            row = next(
                r for r in map(json.loads, f) if r["name"] == "ZAYA1-8B"
            )
        differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differs == {"num_hidden_layers"}
        assert row["source_url"] in cfg["source"]
    assert list(cfg["reduced"]) == [
        "num_hidden_layers", "experts_here", "vocab_here"
    ]
    assert cfg["num_hidden_layers"] == 5 and "40 published" in (
        cfg["reduced"]["num_hidden_layers"]
    )
    assert cfg["experts_here"] == [0, 8] and "8 of the 16" in (
        cfg["reduced"]["experts_here"]
    )
    assert cfg["vocab_here"] == 32784 == 262272 // 8
    assert cfg["deployment"]["chips"] == 8
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "zaya1-8b")
    assert entry["reduced"] == list(cfg["reduced"])
    assert entry["source"] == (
        "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json"
    )
    for key in ("convolutions", "qk_mean", "qk_norm", "rotary", "value_shift",
                "router", "expert_bias", "head", "labels", "left_out"):
        assert "from memory" in cfg["assumed"]["from_memory"]
        assert key in cfg["assumed"]
    assert (cell.chips, cell.config_name) == (1, "zaya1-8b")
    assert cell.traffic["kind"] == "train_cca"


def test_the_pattern_the_program_builds_from_the_file():
    from magiattention_tpu.models.pattern import (
        CCA, EXPERTS, FULL, MLP, zaya_config,
    )

    cfg = harness.load_cell(REPO, CELL).config
    p = zaya_config(
        cfg, expert_range=tuple(cfg["experts_here"]),
        vocab_size=cfg["vocab_here"],
    )
    assert (p.dim, p.n_heads, p.n_kv_heads, p.head_dim) == (2048, 8, 2, 128)
    assert p.layer_types == (FULL,) * 5 and p.plan_kinds == (FULL,)
    assert p.ffn_types == (EXPERTS,) * 5
    assert (p.attn_form, p.conv_taps, p.rope_head_dim, p.shift_taps) == (
        CCA, (2, 2), 64, (1, 2)
    )
    assert (p.router_form, p.router_hidden, p.router_dtype) == (
        MLP, 256, "float32"
    )
    assert "balancing" not in cfg  # expert_bias: a buffer nothing moves
    assert (p.n_experts, p.top_k, p.expert_hidden, p.held_experts) == (
        16, 1, 2048, (0, 8)
    )
    assert (p.n_shared_experts, p.route_norm, p.route_scale) == (0, False, 1.0)
    assert (p.rope_theta, p.rms_eps, p.vocab_size) == (5e6, 1e-5, 32784)
    assert p.tie_embeddings and (p.n_mtp, p.n_loops) == (0, 1)


def test_the_parameter_count_is_the_files():
    import jax

    from magiattention_tpu.models.pattern import (
        init_pattern_params, zaya_config,
    )

    cfg = harness.load_cell(REPO, CELL).config
    shapes = jax.eval_shape(
        lambda r: init_pattern_params(r, zaya_config(
            cfg, expert_range=tuple(cfg["experts_here"]),
            vocab_size=cfg["vocab_here"],
        )),
        jax.random.PRNGKey(0),
    )
    sizes = {
        jax.tree_util.keystr(k): v.size
        for k, v in jax.tree_util.tree_leaves_with_path(shapes)
    }
    assert sum(sizes.values()) == 601_658_970  # ISSUE 39: 601.6 M = 9.63 GB
    assert "601658970" in cfg["parameters"]["five_layers_plus_vocabulary"]
    layer0 = sum(n for k, n in sizes.items() if k.startswith("['layers'][0]"))
    assert layer0 == 106_903_058 == int(cfg["parameters"]["layer_share"])
    vectors = 2 * 1280 + 2 + 2 * 2048 + 2 * 256 + 16  # biases, norms, bias
    assert layer0 == (
        flops_zaya.attn_params(cfg) + flops_zaya.router_params(cfg)
        + 8 * flops_zaya.expert_params(cfg) + vectors
    )
    assert "['lm_head']" not in sizes


def test_the_cells_masks_are_the_issues():
    cell = harness.load_cell(REPO, CELL)
    mask = masks.build_mask(cell.traffic["mask"], 16384, index=0)
    assert mask.doc_lengths == (8192, 4096, 2560, 1024, 512)
    assert mask.area == 45_883_392
    assert round(100 * mask.causal_share, 2) == 34.18
    check = train_cca.check_mask(cell.traffic)
    assert check.total == cell.traffic["check_tokens"] == 4096
    # a document's start off the chunk grid and on it, and chunk edges
    # inside a document: a shift crosses both
    assert check.doc_lengths == (3072, 640, 384)
    assert cell.traffic["chunk_size"] == 512 and 3072 % 512 == 0 != 3712 % 512


def test_flops_of_a_step_by_hand():
    """At the toy's size, every term written out."""
    cfg = harness.load_cell(TOY, "toy.cca").config
    d, hd, rh = 128, 16, 32
    q, kv = 8 * hd, 2 * hd
    attn = d * q + 2 * d * kv + q * d + 2 * (q + kv) + 2 * 10 * hd * hd
    router = d * rh + 2 * rh * rh + rh * 16
    assert flops_zaya.latent_widths(cfg) == (q, kv) == (128, 32)
    assert flops_zaya.attn_params(cfg) == attn == 46_400
    assert flops_zaya.router_params(cfg) == router == 6_656
    assert flops_zaya.expert_params(cfg) == 3 * d * 128 == 49_152
    per_token = 3 * (attn + router) + d * 512
    assert flops_zaya.per_token_params(cfg) == per_token == 224_704
    area, tokens, pairs = 12_345, 512, 700.0
    attn_fwd = 4.0 * area * 8 * hd
    assert flops.attn_fwd_flops(area, 8, hd) == attn_fwd
    assert flops_zaya.train_step_flops(cfg, tokens, area, pairs) == (
        6.0 * per_token * tokens + 6.0 * pairs * 49_152 + 3 * 3.5 * attn_fwd
    )
    assert flops_zaya.attn_executed_flops(cfg, area) == 3 * 4.5 * attn_fwd
    # bytes: forward twice (q, k, v in, out out), dq, dkv; bf16
    fwd, dq, dkv = 2 * q + 2 * kv, 3 * q + 2 * kv, 2 * q + 4 * kv
    assert flops_zaya.attn_executed_bytes(cfg, tokens) == (
        3 * tokens * 2 * (2 * fwd + dq + dkv)
    ) == 3 * 512 * 2 * 1_472


def test_flops_of_the_cells_step():
    """ISSUE 39's count: a layer's forward on the cell's mask is about
    188 GFLOP in the flex kernels, 182 in the projections and the mixing,
    206 in the held experts at half the tokens, 22 in the router."""
    cfg = harness.load_cell(REPO, CELL).config
    t, area = 16384, 45_883_392
    assert flops.attn_fwd_flops(area, 8, 128) == pytest.approx(187.9e9, rel=1e-3)
    assert 2.0 * t * flops_zaya.attn_params(cfg) == pytest.approx(182.6e9, rel=1e-3)
    assert 2.0 * (t / 2) * flops_zaya.expert_params(cfg) == pytest.approx(
        206.2e9, rel=1e-3
    )
    assert 2.0 * t * flops_zaya.router_params(cfg) == pytest.approx(21.6e9, rel=1e-3)
    # the head's weight as published: 41.6% here, 41.7% in the model
    layer = flops_zaya.attn_params(cfg) + flops_zaya.router_params(cfg) + (
        flops_zaya.expert_params(cfg)
    )
    head = 2048 * cfg["vocab_here"]
    assert head / (5 * layer + head) == pytest.approx(0.416, abs=1e-3)
    assert 2048 * 262272 / (40 * layer + 2048 * 262272) == pytest.approx(
        0.417, abs=1e-3
    )
    step = flops_zaya.train_step_flops(cfg, t, area, 5 * t / 2)
    assert step == pytest.approx(
        6.0 * t * (flops_zaya.per_token_params(cfg)
                   + 2.5 * flops_zaya.expert_params(cfg))
        + 5 * 3.5 * 187.9e9, rel=1e-3,
    )
    assert 15e12 < step < 17e12
    # 8 query heads on 2 key-value heads: a quarter of the key bytes
    assert flops_zaya.attn_executed_bytes(cfg, t) == 5 * t * 2 * (
        2 * 2560 + 3584 + 3072
    )


def test_the_metric_files_match_the_scopes_the_program_sets():
    """The two new patterns against operation names and scopes as the
    chip's compiler prints them (a compile of the cell's step for a
    described v5e, PR 39), and the lists the cell was appended to."""
    cell = harness.load_cell(REPO, CELL)
    spec = {m["name"]: m["source"] for m in cell.per_layer}
    base = "jit(step)/jit(main)/jit(shmap_body)/"
    ops = {
        "mix": "fusion.321 " + base + "checkpoint/magi_cca_mix/dot_general",
        "mix_bwd": "fusion.77 " + base + "transpose(jvp(checkpoint))/"
        "magi_cca_mix/mul",
        "mix_remat": "fusion.12 " + base + "transpose(jvp(checkpoint))/"
        "rematted_computation/magi_cca_mix/rsqrt",
        "router": "fusion.201 " + base + "checkpoint/magi_moe_router/"
        "dot_general",
        "router_bwd": "fusion.9 " + base + "transpose(jvp(checkpoint))/"
        "magi_moe_router/erf",
        "experts": "fusion.55 " + base + "checkpoint/magi_moe_experts/"
        "magi_moe_gather/gather",
        "proj": "fusion.31 " + base + "checkpoint/magi_proj/dot_general",
        "flex": "magi_flex_fwd_kernel.2 " + base + "checkpoint/"
        "magi_attn_full/magi_merged_kernel/magi_flex_fwd_kernel/pallas_call",
        "loop": "while.3 " + base + "checkpoint/magi_moe_router/while",
        "other": "fusion.1 " + base + "add",
    }

    def hits(metric):
        rx = re.compile(spec[metric]["pattern"])
        return {k for k, op in ops.items() if rx.search(op)}

    assert hits("train_cca_mix_share") == {"mix", "mix_bwd", "mix_remat"}
    assert hits("train_router_share") == {"router", "router_bwd"}
    assert hits("train_moe_share") == {"router", "router_bwd", "experts"}
    assert hits("train_proj_share") == {"proj"}
    assert hits("train_full_flex_roofline") == {"flex"}
    # the mix is a sibling of magi_proj, which the remainder's pattern
    # (the benchmark's file) does not know: it would read the mix too, so
    # the cell is not on that metric's list until a benchmark PR adds the
    # scope to the pattern (PERF.md section 7)
    unscoped = json.load(open(os.path.join(
        REPO, "benchmarks", "metrics", "train_unscoped_share.json"
    )))["source"]["pattern"]
    assert {k for k, op in ops.items() if re.search(unscoped, op)} == {
        "mix", "mix_bwd", "mix_remat", "other"
    }
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    listed = {
        m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])
    }
    assert listed == {m["name"] for m in cell.per_layer} == {
        "train_step_steady_ms", "train_mfu_steady", "train_device_idle_share",
        "train_flex_kernel_share", "train_full_flex_share",
        "train_full_flex_roofline", "train_moe_share", "train_moe_sort_share",
        "train_moe_gather_share", "train_moe_matmul_share",
        "train_moe_scatter_share", "train_proj_share", "train_ffn_share",
        "train_embed_share", "train_head_share", "train_optimizer_share",
        "train_attn_layout_share", "train_remat_share", "key_build_ms", "program_trace_s", "program_lower_s",
        "program_compile_s", "program_cache_load_s", "train_cca_mix_share",
        "train_router_share",
    }
    assert cell.end_to_end == ["train_tokens_per_s", "setup_s"]
    for name in ("train_cca_mix_share", "train_router_share"):
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert (entry["layer"], entry["moves"], entry["source"]) == (
            "model step", "train_tokens_per_s", "device_trace"
        )
    assert bench["workloads"][-1] == {
        "name": CELL, "config": "zaya1-8b", "traffic": "train-16k-packed-cca",
        "chips": 1, "why": bench["workloads"][-1]["why"],
    }
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 2


# ---------------------------------------------------------------------------
# the command's own path
# ---------------------------------------------------------------------------


@pytest.fixture
def _as_the_command_runs():
    import jax

    with jax.enable_x64(False):
        yield


@pytest.mark.parametrize(
    "workload,trace", [("toy.cca", 0), ("toy.cca", 1), ("toy.cca-cp4", 0)],
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
    cp = 4 if workload.endswith("cp4") else 1
    assert res["device"]["count"] == cp
    shift = next(ln for ln in lines if "] shift: taps (1, 2) over 3" in ln)
    assert (" 0 rows from another rank" in shift) == (cp == 1)
    # the held experts' load on the seed's weights and at both ends of
    # the window, whose mean the step's FLOPs are counted at
    for when in ("the seed's weights", "as the window opens",
                 "as the window closes"):
        stats = next(
            ln for ln in lines if f"] expert layers, {when}: tokens" in ln
        )
        assert "no held expert" in stats and "busiest held expert" in stats
    if not trace:
        assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
        assert all(m["value"] > 0 for m in res["metrics"].values())
    else:
        # no device trace on the CPU: the two shares' reader finds nothing
        # and the line leaves them out, as on a parent without the scopes
        assert set(res["metrics"]) == {"train_step_steady_ms"}


def test_the_parent_has_no_such_cell():
    """An unknown workload fails at once, before jax is touched: how the
    parent answers the new cell."""
    with pytest.raises(SystemExit, match="no workload"):
        harness.load_cell(TOY, CELL)
