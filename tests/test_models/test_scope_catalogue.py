"""Every operation the device runs lies under exactly one *part* scope
that a per-layer metric reads (``docs/observability.md``, "Device
scopes"): the six toy models' train steps and the bare attention call
(seven with the decoder-hybrid-decoder's, eight with the residual
streams', nine with the Mamba-2 hybrid's) are compiled here, on the CPU, their ``op_name``s read as the benchmark
reads them (``trace_reduce.hlo_scopes``), and held against the patterns
of the metric files themselves, so a scope that is renamed, dropped or
wrapped round another part fails here and not as a silent zero on the
chip."""

import collections
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmarks import trace_reduce
from magiattention_tpu import api
from magiattention_tpu.models import LlamaConfig, build_magi_llama, init_params
from magiattention_tpu.models.pattern import (
    build_magi_pattern, init_pattern_params,
)
from tests.test_models import pattern_harness as toy
from tests.test_models.test_pattern import CFG as AFMOE
from tests.test_models import test_pattern_blockdiff as blockdiff
from tests.test_models.test_pattern_cca import _zaya
from tests.test_models.test_pattern_latent import _glm
from tests.test_models.test_pattern_looped import _ouro
from tests.test_models.test_pattern_mhc import _xing
from tests.test_models.test_pattern_sambay import _sambay
from tests.test_models.test_pattern_ssd import _granite

METRICS = os.path.join(
    os.path.dirname(__file__), "..", "..", "benchmarks", "metrics"
)


def _pattern(metric: str) -> re.Pattern:
    with open(os.path.join(METRICS, metric + ".json")) as f:
        return re.compile(json.load(f)["source"]["pattern"])


# a part, by the metric that reads it
PARTS = {
    part: _pattern(metric)
    for part, metric in {
        "embed": "train_embed_share",
        "proj": "train_proj_share",
        "layout": "train_attn_layout_share",
        "flex": "train_flex_kernel_share",
        "cast": "attn_cast_fwdbwd_ms",
        "ffn": "train_ffn_share",
        "moe": "train_moe_share",
        "head": "train_head_share",
        "exit_head": "train_exit_head_share",
        "cca_mix": "train_cca_mix_share",
        "ssm_scan": "train_ssm_scan_share",
        "ssm_mix": "train_ssm_mix_share",
        "ssd_scan": "train_ssd_scan_share",
        "diff_combine": "train_diff_combine_share",
        "mhc": "train_mhc_share",
        "optimizer": "train_optimizer_share",
    }.items()
}
EXPERT_PARTS = {
    part: _pattern(f"train_moe_{part}_share")
    for part in ("sort", "gather", "matmul", "scatter")
}
# parts newer than the remainder's pattern, which is the benchmark's and
# would read them too: their cells are not on its list (PERF.md section 7)
NEWER_THAN_THE_REMAINDER = {
    "cca_mix", "ssm_scan", "ssm_mix", "diff_combine", "mhc", "ssd_scan",
}
REMAINDERS = {
    "step": _pattern("train_unscoped_share"),
    "attn": _pattern("attn_unscoped_fwdbwd_ms"),
}
# the operations that cost: jax's primitive is the last word of op_name
HEAVY = re.compile(
    r"/(dot_general|ragged_dot\w*|sort|gather|scatter(-add)?|pallas_call"
    r"|custom_call)$"
)

ATTN_CALL = ["magi_layout", "magi_flex_fwd_kernel"]
ATTN_BWD = ["magi_flex_bwd_kernel"]
# magi_bwd_delta: delta, made before the one backward kernel (ISSUE 43: on
# the k-major walk a q block has no first step to make it in), the lse
# cotangent folded in, and the sink's gradient
ATTN_DLSE = ["magi_bwd_delta"]
STEP = [
    "magi_embed", "magi_proj", "magi_ffn", "magi_optimizer",
    "rematted_computation", *ATTN_CALL, *ATTN_BWD,
]
EXPERTS = [
    "magi_moe_router", "magi_moe_experts", "magi_moe_shared", "magi_moe_sort",
    "magi_moe_gather", "magi_moe_matmul", "magi_moe_scatter",
]
CASES = {
    "llama": STEP + ["magi_head"],
    "afmoe": STEP + EXPERTS + [
        "magi_head", "magi_attn_sliding", "magi_attn_full",
    ],
    "latent+mtp": STEP + EXPERTS + [
        "magi_head", "magi_attn_full", "magi_mla_q", "magi_mla_kv",
        "magi_mla_out", "magi_mtp",
        # the module's own operations take the part they are an instance of
        r"magi_mtp\S*magi_embed", r"magi_mtp\S*magi_head",
        r"magi_proj\S*magi_mla_q", r"magi_proj\S*magi_mla_out",
    ],
    "looped": STEP + [
        "magi_loop", "magi_exit_head", "magi_attn_full",
        r"magi_loop\S*magi_head\b",  # the final norm inside the loop
    ],
    "cca": STEP + [s for s in EXPERTS if s != "magi_moe_shared"] + [
        "magi_head", "magi_attn_full", "magi_cca_mix",
        r"checkpoint/magi_cca_mix",  # a sibling of magi_proj, not inside it
    ],
    "sambay": STEP + [
        "magi_head", "magi_attn_sliding", "magi_attn_full",
        "magi_ssm_scan_fwd_kernel", "magi_ssm_scan_bwd_kernel",
        # siblings of magi_proj, as the attention call is, not inside it
        r"checkpoint/magi_ssm_scan", r"checkpoint/magi_ssm_mix",
        r"checkpoint/magi_gmu", r"checkpoint/magi_diff_combine",
    ],
    "ssd": STEP + [
        "magi_head", "magi_attn_full",
        "magi_ssd_scan_fwd_kernel", "magi_ssd_scan_bwd_kernel",
        # siblings of magi_proj, as the attention call is, not inside it
        r"checkpoint/magi_ssd_scan", r"checkpoint/magi_ssm_mix",
    ],
    "mhc": STEP + EXPERTS + [
        "magi_head", "magi_attn_full", "magi_mla_q", "magi_mtp",
        # siblings of magi_proj and magi_ffn, not inside them
        r"checkpoint/magi_mhc_coef", r"checkpoint/magi_mhc_read",
        r"checkpoint/magi_mhc_write",
        r"magi_mtp\S*magi_mhc_write",  # the module's layer has its streams
    ],
    "blockdiff": STEP + [s for s in EXPERTS if s != "magi_moe_shared"] + [
        "magi_head", "magi_attn_full",
        # a cross-cut: its operations carry their part too
        r"magi_diffusion_io\S*magi_embed", r"magi_diffusion_io\S*magi_head",
    ],
    "attn-fwd-cp1": ATTN_CALL,
    "attn-fwdbwd-cp1": ATTN_CALL + ATTN_BWD + ATTN_DLSE,
    "attn-fwd-cp2": ATTN_CALL + [r"magi_merged_cast\S*magi_group_cast"],
    "attn-fwdbwd-cp2": ATTN_CALL + ATTN_BWD + ATTN_DLSE + [
        r"transpose\(jvp\S*magi_merged_cast\S*magi_group_cast",
    ],
}


def toy_model(name: str):
    """(a toy model on one device, its parameters): the dense decoder or
    a pattern form (``tests/test_models/test_train_step.py`` takes its
    models here too)."""
    mesh = toy._mesh(1)
    if name == "llama":
        cfg = LlamaConfig(
            vocab_size=64, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
            head_dim=16, ffn_hidden=96, dtype="float32", remat=True,
        )
        qr, kr, ts = api.infer_varlen_mask_from_batch(toy.DOCS)
        model, _ = build_magi_llama(
            cfg, mesh, toy.TOTAL, qr, kr, ts, chunk_size=toy.CHUNK
        )
        return model, init_params(jax.random.PRNGKey(0), cfg)
    cfg = {
        "afmoe": AFMOE, "latent+mtp": _glm(1)[1],
        "looped": _ouro()[1], "cca": _zaya()[1], "sambay": _sambay()[1],
        "mhc": _xing()[1], "ssd": _granite()[1],
    }[name]
    model, _ = build_magi_pattern(cfg, mesh, toy.CU, chunk_size=toy.CHUNK)
    return model, init_pattern_params(jax.random.PRNGKey(0), cfg)


def _step_text(name: str) -> str:
    model, params = toy_model(name)
    opt = optax.adamw(1e-3)
    batch = jnp.zeros((1, toy.TOTAL), jnp.int32)
    return (
        model.make_train_step(opt)
        .lower(params, opt.init(params), batch, batch, batch)
        .compile()
        .as_text()
    )


def _blockdiff_step_text() -> str:
    """The doubled sequence's step: its batch is twice the tokens' rows
    and takes the rows' weights."""
    cfg = blockdiff._sdar()[1]
    model, _ = build_magi_pattern(
        cfg, toy._mesh(1), list(blockdiff._mask().cu_seqlens),
        chunk_size=blockdiff.CHUNK,
    )
    params = init_pattern_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adamw(1e-3)
    batch = jnp.zeros((1, 2 * blockdiff.TOKENS), jnp.int32)
    return (
        model.make_train_step(opt)
        .lower(params, opt.init(params), batch, batch, batch,
               batch.astype(jnp.float32))
        .compile()
        .as_text()
    )


def _attn_text(name: str) -> str:
    _attn, phase, cp = name.split("-")
    cp = int(cp[2:])
    total, hq, hk, d = 1024, 4, 2, 64
    mesh = Mesh(np.array(jax.devices()[:cp]), ("cp",))
    key = api.magi_attn_varlen_key(
        [0, 300, 700, total], total, mesh, num_heads=(hq, hk), head_dim=d,
        chunk_size=128, out_dtype="float32",
    )
    sharded = NamedSharding(mesh, P("cp"))
    q = jax.device_put(jnp.ones((total, hq, d), jnp.float32), sharded)
    k = jax.device_put(jnp.ones((total, hk, d), jnp.float32), sharded)
    d_lse = jax.device_put(jnp.ones((total, hq), jnp.float32), sharded)

    def fwd(q, k, v):
        out, meta = api.calc_attn(q, k, v, key)
        return out, meta.lse

    def fwdbwd(q, k, v, d_out, d_lse):
        _res, vjp = jax.vjp(fwd, q, k, v)
        return vjp((d_out, d_lse))

    if phase == "fwd":
        return jax.jit(fwd).lower(q, k, k).compile().as_text()
    return jax.jit(fwdbwd).lower(q, k, k, q, d_lse).compile().as_text()


@pytest.mark.parametrize("case", sorted(CASES))
def test_every_heavy_operation_lies_under_exactly_one_part(case):
    with jax.enable_x64(False):
        is_attn = case.startswith("attn")
        if case == "blockdiff":
            text = _blockdiff_step_text()
        else:
            text = _attn_text(case) if is_attn else _step_text(case)
    scopes = trace_reduce.hlo_scopes(text)
    # as the metrics see an operation: "<instruction name> <scope>"
    lines = {f"{name} {scope}" for name, scope in scopes.items()}
    heavy = sorted(s for s in lines if HEAVY.search(s))
    assert len(heavy) >= 4, heavy
    seen = collections.Counter()
    for line in heavy:
        parts = [p for p, rx in PARTS.items() if rx.search(line)]
        assert len(parts) == 1, (line, parts)
        seen[parts[0]] += 1
        # and the remainder does not count it (but for the parts newer
        # than the remainder's pattern)
        assert bool(
            REMAINDERS["attn" if is_attn else "step"].search(line)
        ) == (parts[0] in NEWER_THAN_THE_REMAINDER)
        if "magi_moe_experts" in line:
            inside = [p for p, rx in EXPERT_PARTS.items() if rx.search(line)]
            assert len(inside) == 1, (line, inside)
    # a flex kernel is a sibling of the projections, never inside them
    assert not [
        s for s in lines
        if PARTS["flex"].search(s) and PARTS["proj"].search(s)
    ]
    # each scope of the vocabulary occurs where the model has the part
    for scope in CASES[case]:
        rx = re.compile(scope)
        assert any(rx.search(s) for s in lines), scope
    absent = {
        "llama": ["magi_moe_", "magi_mla_", "magi_mtp", "magi_exit_head"],
        "afmoe": ["magi_mla_", "magi_mtp", "magi_exit_head"],
        "latent+mtp": ["magi_exit_head", "magi_attn_sliding"],
        "looped": ["magi_moe_", "magi_mtp"],
        "cca": ["magi_mla_", "magi_mtp", "magi_exit_head", "magi_moe_shared",
                "magi_proj/magi_cca_mix", "magi_cca_mix/magi_proj"],
        "blockdiff": ["magi_mla_", "magi_mtp", "magi_exit_head",
                      "magi_moe_shared", "magi_cca_mix"],
        "mhc": ["magi_exit_head", "magi_attn_sliding", "magi_proj/magi_mhc",
                "magi_ffn/magi_mhc", "magi_mhc_read/magi_proj"],
        "sambay": ["magi_moe_", "magi_mla_", "magi_mtp", "magi_exit_head",
                   "magi_cca_mix", "magi_proj/magi_ssm", "magi_proj/magi_gmu",
                   "magi_proj/magi_diff_combine", "magi_ssm_mix/magi_proj"],
        "ssd": ["magi_moe_", "magi_mla_", "magi_mtp", "magi_exit_head",
                "magi_cca_mix", "magi_attn_sliding", "magi_gmu",
                "magi_ssm_scan", "magi_diff_combine", "magi_proj/magi_ssd",
                "magi_proj/magi_ssm", "magi_ssm_mix/magi_proj",
                "magi_ssm_mix/magi_ssd"],
    }.get(case, ["magi_proj", "magi_ffn", "magi_head", "magi_optimizer"])
    for scope in absent:
        assert not any(scope in s for s in lines), scope
    if not is_attn:
        # every cca layer is an expert layer: magi_ffn holds its norm and
        # its residual add, nothing heavy
        own = {
            "cca": {"cca_mix", "moe"}, "blockdiff": {"moe"},
            "sambay": {"ffn", "ssm_scan", "ssm_mix"},
            # Mamba-2's mix holds no matmul (its B, C and step come out of
            # the one input projection): nothing heavy under magi_ssm_mix
            "ssd": {"ffn", "ssd_scan"},
            "mhc": {"ffn", "moe", "mhc"},
        }.get(case, {"ffn"})
        assert {"proj", "flex", "layout", "embed"} | own <= set(seen), seen
        assert ("exit_head" if case == "looped" else "head") in seen, seen
