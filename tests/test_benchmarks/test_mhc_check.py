"""What ``train_mhc``'s ``correct`` can see, Xing4.0-29B-A4B's
configuration files and operation counts, and the command's own path for
the cell. Toy size, CPU (``data/toy_mhc``: a benchmark of new files only).
The faults' readings run the kernels' ``jax.numpy`` backends: what is
tested here is the check, and the kernels are ``tests/test_ops``' and
``tests/test_models``'."""

import io
import json
import os
from contextlib import redirect_stdout

import pytest

from benchmarks import flops_xing, harness, masks
from benchmarks.kinds import train_mhc

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TOY = os.path.join(HERE, "data", "toy_mhc")
CELL = "xing4-train-8k-traces"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def readings():
    """The check's readings by what the model was handed; the reference
    always gets the configuration and the weights as they are."""
    import jax
    import jax.numpy as jnp

    from magiattention_tpu.models.pattern import init_pattern_params

    cell = harness.load_cell(TOY, "toy.mhc")
    cfg, tr = cell.config, cell.traffic  # float32: the faults are structure
    low = dict(tr, dtype="bfloat16")
    dev = jax.devices()[:1]
    found = {}
    with jax.enable_x64(False), pytest.MonkeyPatch.context() as mp:
        mp.setenv("MAGI_ATTENTION_KERNEL_BACKEND", "jnp")
        job = train_mhc.Job(cfg, tr, 1, dev)
        params = init_pattern_params(
            train_mhc.key_from_seed(job.seed), job.pcfg
        )

        def other(**fields):
            return {"model_job": train_mhc.Job(cfg, tr, job.seed, dev, fields)}

        handed = {
            "float32 model": {},
            "bf16 model": {"model_job": train_mhc.Job(cfg, low, job.seed, dev)},
            "bf16 coefficients": other(hc_dtype="bfloat16"),
            "a value padded into the softmax's scale": other(
                softmax_scale=64 ** -0.5
            ),
            "two Sinkhorn rounds for twenty": other(hc_sinkhorn_iters=2),
            "fp8 weights": {
                "model_job": train_mhc.Job(cfg, low, job.seed, dev),
                "model_params": jax.tree.map(
                    lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype),
                    params,
                ),
            },
        }
        for name, fault in handed.items():
            found[name] = train_mhc.check_errors(job, params, **fault)
    return found


def test_the_float32_model_agrees_far_inside_the_limits(readings):
    rel, grad, routing = readings["float32 model"]
    assert train_mhc.passes(rel, grad, routing)
    assert rel < 1e-5 and max(grad.values()) < 1e-4, grad
    assert (routing["flipped_share"], routing["worst_margin"]) == (0.0, 0.0)
    assert routing["coef_alone"] < train_mhc.COEF_REL_TOL / 10
    # every parameter is read: both mixers' three, latent attention's
    # seven, the experts', the MTP module's own four
    assert set(grad) == {
        "embed", "final_norm", "lm_head", "attn_norm", "mlp_norm",
        "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wkv_b", "wo",
        "w_gate", "w_up", "w_down", "w_router", "we_gate", "we_up", "we_down",
        "ws_gate", "ws_up", "ws_down",
        "hc_attn.phi", "hc_attn.b", "hc_attn.alpha",
        "hc_ffn.phi", "hc_ffn.b", "hc_ffn.alpha",
        "mtp.embed_norm", "mtp.hidden_norm", "mtp.eh_proj", "mtp.final_norm",
    }


def test_the_bf16_model_passes_and_reads_roundings_size(readings):
    """At toy size (hidden 128, 256 tokens) bf16 moves a gradient 3e-2 to
    7e-2, the loss 1e-4, a hundredth of the expert choices; a mixer's
    ``alpha``, three numbers that each sum terms of both signs over every
    token, 0.1 to 0.35 (on the chip up to 2: decided by none). Every
    structural fault reads several times a matrix's."""
    rel, grad, routing = readings["bf16 model"]
    assert train_mhc.passes(rel, grad, routing), (rel, grad, routing)
    rest = {n: e for n, e in grad.items() if not n.endswith(".alpha")}
    assert max(rest.values()) < 0.08, rest
    for fault in ("a value padded into the softmax's scale",
                  "two Sinkhorn rounds for twenty"):
        worst = max(e for n, e in readings[fault][1].items()
                    if not n.endswith(".alpha"))
        assert worst > 2 * max(rest.values()), (fault, worst)


@pytest.mark.parametrize("fault", [
    "bf16 coefficients", "a value padded into the softmax's scale",
    "two Sinkhorn rounds for twenty", "fp8 weights",
])
def test_a_fault_fails_the_check(readings, fault):
    assert not train_mhc.passes(*readings[fault]), (fault, readings[fault])


def test_bf16_coefficients_fail_on_the_coefficients_own_reading(readings):
    """Inside a bf16 model a bfloat16 coefficient path moves no matrix's
    gradient past what bf16 activations do (the chip's readings,
    ``train_mhc``'s comment); what holds ``hc_dtype`` is ``_mhc_coef`` run
    alone on a float32 state, where it leaves the float32 reading by
    orders."""
    _rel, _grad, routing = readings["bf16 coefficients"]
    assert routing["coef_alone"] > 10 * train_mhc.COEF_REL_TOL
    assert routing["coef_alone"] > 1000 * readings["float32 model"][2]["coef_alone"]


def test_each_parameter_has_its_limit():
    assert train_mhc.grad_limit("wq_a") == train_mhc.GRAD_REL_L2_TOL
    assert train_mhc.grad_limit("we_up") == train_mhc.GRAD_REL_L2_TOL
    assert train_mhc.grad_limit("hc_ffn.phi") == train_mhc.GRAD_REL_L2_TOL
    assert train_mhc.grad_limit("hc_attn.b") == (
        train_mhc.CANCELLING_GRAD_REL_L2_TOL
    ) > train_mhc.GRAD_REL_L2_TOL
    assert train_mhc.grad_limit("hc_attn.alpha") == float("inf")
    assert train_mhc.grad_limit("mtp.eh_proj") == train_mhc.GRAD_REL_L2_TOL


def test_the_timed_step_is_held_by_loss_and_update():
    assert train_mhc.timed_step_passes(1e-5, 0.99)
    assert not train_mhc.timed_step_passes(1e-2, 0.99)
    assert not train_mhc.timed_step_passes(1e-5, 0.0)  # a state unchanged
    assert not train_mhc.timed_step_passes(1e-5, 2.0)  # twice the rate


# ---------------------------------------------------------------------------
# the configuration, its mask and its operation counts
# ---------------------------------------------------------------------------


def test_xing_states_its_widths_as_published():
    cell = harness.load_cell(REPO, CELL)
    cfg = cell.config
    if os.path.exists(CATALOG):  # every key of the catalog row's config
        with open(CATALOG) as f:
            row = next(
                r for r in map(json.loads, f) if r["name"] == "Xing4.0-29B-A4B"
            )
        differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differs == {"num_hidden_layers", "num_nextn_predict_layers"}
        assert row["source_url"] in cfg["source"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"]) == (3584, 32)
    assert (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"]) == (128, 64, 128)
    assert (cfg["hc_mult"], cfg["hc_sinkhorn_iters"], cfg["hc_eps"]) == (
        4, 20, 1e-6
    )
    assert list(cfg["reduced"]) == [
        "num_hidden_layers", "experts_here", "vocab_here",
        "num_nextn_predict_layers",
    ]
    assert cfg["num_hidden_layers"] == 5 == len(cfg["layers_kept"])
    assert cfg["layers_kept"] == [0, 2, 3, 4, 5]
    assert cfg["first_k_dense_replace"] == 2  # as published
    assert train_mhc.model_keys(cfg)["first_k_dense_replace"] == 1
    assert "40 published" in cfg["reduced"]["num_hidden_layers"]
    assert cfg["experts_here"] == [0, 8] and cfg["n_routed_experts"] == 64
    assert cfg["vocab_here"] == 16384 == cfg["vocab_size"] // 8
    assert cfg["deployment"]["chips"] == 8
    for key in ("origin", "coefficients", "sinkhorn", "streams", "mtp_streams",
                "seed", "rotary", "expert_bias", "mtp_loss_weight"):
        assert key in cfg["assumed"]
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "xing4.0-29b-a4b")
    assert entry["reduced"] == list(cfg["reduced"])
    assert entry["source"] == (
        "https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/"
        "config.json"
    )
    assert (cell.chips, cell.config_name) == (1, "xing4.0-29b-a4b")
    assert cell.traffic["kind"] == "train_mhc"
    assert cell.traffic_name == "train-8k-packed-mhc"
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert len(bench["workloads"]) == 14 and len(four) == 2


def test_the_pattern_and_the_parameter_count_are_the_files():
    import jax

    from magiattention_tpu.models.pattern import (
        DENSE, EXPERTS, LATENT, init_pattern_params,
    )

    cell = harness.load_cell(REPO, CELL)
    cfg = cell.config
    p = train_mhc.Job(cfg, cell.traffic, 0, jax.devices()[:1]).pcfg
    assert p.ffn_types == (DENSE,) + (EXPERTS,) * 4
    assert (p.dim, p.n_heads, p.head_dim, p.v_head_dim) == (3584, 32, 192, 128)
    assert (p.attn_form, p.hc_mult, p.hc_sinkhorn_iters) == (LATENT, 4, 20)
    assert (p.n_experts, p.top_k, p.held_experts) == (64, 4, (0, 8))
    assert p.n_mtp == 0 and p.vocab_size == 16384 and p.remat
    assert p.flat_expert_rows  # every seed does the same work
    assert p.rope_yarn == (64.0, 32.0, 1.0, 4096)
    heads = p.kernel_heads
    assert (heads.head_dim, heads.v_head_dim) == (192, 128)  # no padding
    assert heads.softmax_scale == pytest.approx(0.14468, rel=1e-4)
    shapes = jax.eval_shape(
        lambda r: init_pattern_params(r, p), jax.random.PRNGKey(0)
    )

    def matrices(tree):
        return sum(v.size for v in jax.tree.leaves(tree) if len(v.shape) > 1)

    by_layer = [matrices(layer) for layer in shapes["layers"]]
    assert by_layer == [128188416] + [128417792] * 4  # the file's parameters
    assert matrices(shapes) == 759300096
    assert flops_xing.dense_layers(cfg) == 1
    assert flops_xing.per_token_params(cfg) == (
        128188416 + 4 * (128417792 - 8 * 3 * 3584 * 1024) + 3584 * 16384
    )


def test_the_mask_and_the_counts():
    cell = harness.load_cell(REPO, CELL)
    cfg, tr = cell.config, cell.traffic
    mask = masks.build_mask(tr["mask"], tr["total_tokens"], index=0)
    assert list(mask.doc_lengths) == [4096, 2048, 1280, 512, 256]
    assert mask.area == 11472896 and round(100 * mask.causal_share, 2) == 34.19
    check = train_mhc.check_mask(tr)
    assert check.total == 2048 and list(check.doc_lengths) == [1280, 512, 256]
    fwd = flops_xing.attn_fwd_flops(cfg, mask.area)
    assert fwd == 2.0 * 11472896 * 32 * (192 + 128)
    # the forward kernel once, the backward once: 3 products at the keys'
    # width and 2 at the values' beside the forward's one of each
    assert flops_xing.attn_executed_flops(cfg, mask.area) == pytest.approx(
        5 * fwd * (1 + (3 * 192 + 2 * 128) / 320)
    )
    state = 8192 * 4 * 3584 * 2
    assert flops_xing.mhc_stream_bytes(cfg, 8192) == (
        10 * (3 * state + state // 2) * 3
    )
    step = flops_xing.train_step_flops(cfg, 8192, mask.area, 4 * 4096.0)
    assert 21e12 < step < 24e12


def test_the_new_metrics_read_the_scopes_the_program_sets():
    import re

    import jax
    import jax.numpy as jnp

    from magiattention_tpu.models.pattern import init_pattern_params

    cell = harness.load_cell(TOY, "toy.mhc")
    with jax.enable_x64(False):
        job = train_mhc.Job(cell.config, cell.traffic, 0, jax.devices()[:1])
        mask = masks.build_mask(cell.traffic["mask"], 512, index=0)
        model, _meta = job.build(mask)
        params = init_pattern_params(jax.random.PRNGKey(0), job.pcfg)
        batch = jnp.zeros((1, 512), jnp.int32)
        text = jax.jit(jax.grad(model.loss_fn)).lower(
            params, batch, batch, batch, model.sharded_tables()
        ).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    specs = {m["name"]: m["source"] for m in cell.per_layer}
    for metric, scopes in (
        ("train_mhc_share", {"magi_mhc_coef", "magi_mhc_read", "magi_mhc_write"}),
        ("train_mhc_roofline", {"magi_mhc_coef", "magi_mhc_read", "magi_mhc_write"}),
        ("train_mhc_coef_share", {"magi_mhc_coef"}),
    ):
        pattern = re.compile(specs[metric]["pattern"])
        hit = {n for n in names if pattern.search("fusion.1 " + n)}
        assert hit, metric
        found = {s for s in ("magi_mhc_coef", "magi_mhc_read", "magi_mhc_write")
                 if any(s in n for n in hit)}
        assert found == scopes, (metric, found)
    assert specs["train_mhc_roofline"]["bytes"] == "mhc_stream_bytes"


def test_rehearsal_prints_the_result_line():
    import jax

    out = io.StringIO()
    with jax.enable_x64(False), redirect_stdout(out):
        rc = harness.main(
            ["--workload", "toy.mhc", "--seed", "2147483655", "--seconds",
             "1.5", "--trace", "1", "--root", TOY],
            allow_cpu=True,
        )
    assert rc == 0
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    # no device trace on the CPU: the three new metrics' readers find
    # nothing and the line leaves them out, as on a parent without the scopes
    assert set(res["metrics"]) == {"train_step_steady_ms"}
    assert res["device"]["busy_s"] == 0.0
