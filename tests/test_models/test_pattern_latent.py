"""Latent attention and the MTP module (GLM-4.7-Flash's layer) of the
pattern-driven decoder (models/pattern.py) against the plain float32
reference of ``benchmarks/reference_glm4moe.py`` on seeded random weights,
at toy size on the CPU, through the normal path at cp = 1, 2 and 4. (Split
from ``test_pattern.py`` in ISSUE 45: a form, a file.)"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_glm4moe
from magiattention_tpu import telemetry
from magiattention_tpu.models import pattern
from magiattention_tpu.models.pattern import (
    build_magi_pattern, glm4_moe_lite_config, init_pattern_params,
)
from magiattention_tpu.parallel import dispatch, roll
from tests.test_benchmarks import latent_faults
from tests.test_models.pattern_harness import (
    CHUNK, CU, TOTAL, _allow_full, _mesh, _model_loss_and_grads, _worst,
    computed_once, unfaulted_loss_and_grads,
)

# the published widths in ratio: 4 heads of 24 + 8 / 32, ranks 24 and 16
# (768 and 512 against a 256-wide head), 8 experts top-2, one dense layer
GLM_HF = dict(
    hidden_size=64, intermediate_size=160, num_attention_heads=4,
    num_key_value_heads=4, qk_nope_head_dim=24, qk_rope_head_dim=8,
    v_head_dim=32, q_lora_rank=24, kv_lora_rank=16, num_hidden_layers=3,
    first_k_dense_replace=1, rope_theta=1e6, rms_norm_eps=1e-5,
    n_routed_experts=8, num_experts_per_tok=2, moe_intermediate_size=48,
    n_shared_experts=1, norm_topk_prob=True, routed_scaling_factor=1.8,
    n_group=1, topk_group=1, num_nextn_predict_layers=1, vocab_size=256,
    experts_here=[2, 6], vocab_here=64, mtp_loss_weight=0.3,
)


def _glm(mtp=1, dtype="float32", **share):
    hf = dict(GLM_HF, num_nextn_predict_layers=mtp, **share)
    return hf, glm4_moe_lite_config(
        hf, dtype=dtype, remat=True, expert_range=tuple(hf["experts_here"]),
        vocab_size=hf["vocab_here"],
    )


@computed_once
def _glm_reference(hf, params, tokens_g):
    toks = jnp.asarray(tokens_g, jnp.int32)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: reference_glm4moe.glm4moe_loss(
                p, toks, jnp.roll(toks, -1), jnp.roll(toks, -2),
                _allow_full(), hf,
            )
        )(params)


@pytest.fixture(scope="module")
def glm_params():
    with jax.enable_x64(False):
        return {
            mtp: init_pattern_params(jax.random.PRNGKey(7), _glm(mtp)[1])
            for mtp in (0, 1)
        }


@pytest.mark.parametrize("mtp", [1, 0], ids=["mtp", "no-mtp"])
@pytest.mark.parametrize("cp", [1, 2, 4])
def test_latent_loss_and_every_gradient_match_the_reference(glm_params, cp, mtp):
    """Float32 on both sides (tolerances as the AFMoE comparison's): the
    latent projections, the shared rotary key, the experts, and with the
    module the distributed roll by -2 across 1, 2 and 4 ranks."""
    hf, cfg = _glm(mtp)
    params = glm_params[mtp]
    assert ("mtp" in params) == bool(mtp)
    with jax.enable_x64(False):
        loss, grads, tokens_g, _model, _meta = unfaulted_loss_and_grads(
            cfg, cp, params
        )
        want, want_grads = _glm_reference(hf, params, tokens_g)
    assert abs(loss - float(want)) <= 2e-5 * abs(float(want))
    assert _worst(grads, want_grads) <= 2e-4


def _across_documents(cfg, params):
    """The model planned for one document where the tokens are three."""
    mesh = _mesh(1)
    model, meta = build_magi_pattern(cfg, mesh, [0, TOTAL], chunk_size=CHUNK)
    tokens_g = np.random.default_rng(3).integers(0, 64, (1, TOTAL))
    tokens = jax.vmap(lambda x: dispatch(x, meta))(
        jnp.asarray(tokens_g, jnp.int32)
    )
    labels = roll(tokens, meta, -1, axis=1, mesh=mesh, cp_axis="cp")
    pos = jnp.asarray(meta.perm_idx)[None]
    _loss, grads = jax.jit(jax.value_and_grad(model.loss_fn))(
        params, tokens, labels, pos, model.sharded_tables()
    )
    return grads, tokens_g[0]


LATENT_FAULTS = (
    *latent_faults.PLANTED, "attention across documents",
    "bf16 against a float32 statement", "fp8 weights",
)


@pytest.mark.parametrize("fault", LATENT_FAULTS)
def test_a_latent_fault_moves_a_gradient(glm_params, fault):
    """Each wrong model fails the float32 comparison's 2e-4 by orders."""
    hf, cfg = _glm(1)
    params = model_params = glm_params[1]
    with jax.enable_x64(False):
        if fault == "attention across documents":
            grads, tokens_g = _across_documents(cfg, params)
        elif fault in latent_faults.PLANTED:
            with latent_faults.planted(fault):
                _l, grads, tokens_g, _m, _meta = _model_loss_and_grads(
                    cfg, 1, params
                )
        else:
            if fault == "fp8 weights":
                model_params = jax.tree.map(
                    lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype),
                    params,
                )
            else:
                cfg = dataclasses.replace(cfg, dtype="bfloat16")
            _l, grads, tokens_g, _m, _meta = _model_loss_and_grads(
                cfg, 1, model_params
            )
        _want, want_grads = _glm_reference(hf, params, tokens_g)
    floor = 5e-3 if fault.startswith("bf16") else 0.05
    assert _worst(grads, want_grads) > floor, fault


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The guide's share test: an expert layer's routed part as each of
    the ranks that split the experts computes it (the model, two experts
    a rank), added up, with the shared expert counted once, is the uncut
    reference's layer output."""
    hf, cfg = _glm(0, experts_here=[0, 8])
    t = 96
    rng = np.random.default_rng(5)
    with jax.enable_x64(False):
        whole = init_pattern_params(
            jax.random.PRNGKey(2), dataclasses.replace(cfg, expert_range=(0, 8))
        )["layers"][1]
        h = jnp.asarray(rng.standard_normal((t, cfg.dim)), jnp.float32)
        with jax.default_matmul_precision("highest"):
            want, _routed = reference_glm4moe.expert_ffn(h, whole, hf)
        total = jnp.zeros_like(want)
        for first in range(0, 8, 2):
            share_cfg = dataclasses.replace(
                cfg, expert_range=(first, first + 2), n_shared_experts=0
            )
            share = {
                k: v[first:first + 2] if k.startswith("we_") else v
                for k, v in whole.items()
            }
            y, stats = pattern._expert_ffn(
                h, share, share_cfg, pattern._route(h, share, share_cfg)
            )
            assert int(stats["expert_counts"].sum()) > 0
            total = total + y
        shared = pattern._swiglu(
            h, whole["ws_gate"], whole["ws_up"], whole["ws_down"], jnp.float32
        )
    np.testing.assert_allclose(total + shared, want, rtol=2e-4, atol=2e-5)


def test_the_latent_scopes_and_the_cast_width_gauge(glm_params):
    hf, cfg = _glm(1)
    telemetry.set_enabled(True)
    reg = telemetry.get_registry()
    try:
        with jax.enable_x64(False):
            model, _meta = build_magi_pattern(cfg, _mesh(1), CU, chunk_size=CHUNK)
            batch = jnp.zeros((1, TOTAL), jnp.int32)
            text = jax.jit(model.loss_fn).lower(
                glm_params[1], batch, batch, batch, model.sharded_tables()
            ).as_text(debug_info=True)
        widths = {
            form: reg.gauge_value("magi_mla_kv_cast_width", form=form)
            for form in ("expanded", "latent")
        }
        span = [
            e["args"] for e in telemetry.get_event_buffer().events()
            if e["name"] == "plan_flex_attn"
        ][-1]
    finally:
        reg.clear_metric("magi_mla_kv_cast_width")
        telemetry.set_enabled(None)
    for scope in ("magi_mla_q", "magi_mla_kv", "magi_mla_out", "magi_mtp",
                  "magi_attn_full", "magi_moe_experts"):
        assert scope in text, scope
    # every head's k and v as the kernels take them; the latent and the key
    assert widths == {"expanded": 2 * 4 * 32.0, "latent": 16 + 8.0}
    assert (span["heads_q"], span["heads_kv"], span["head_dim"]) == (4, 4, 32)


def test_latent_config_rejects_what_is_not_built():
    # a value width of its own is built since ISSUE 49 (the kernels take
    # it; tests/test_models/test_pattern_mhc.py runs one against its
    # reference); the key heads' width is no value width of its own
    assert glm4_moe_lite_config(dict(GLM_HF, v_head_dim=16)).v_head_dim == 16
    cfg = _glm()[1]
    assert cfg.v_head_dim == 0 and cfg.kernel_heads is cfg
    with pytest.raises(ValueError, match="n_group"):
        glm4_moe_lite_config(dict(GLM_HF, n_group=2))
    with pytest.raises(ValueError, match="latent attention needs"):
        dataclasses.replace(_glm()[1], rope_head_dim=0)
    with pytest.raises(ValueError, match="more than one MTP module"):
        glm4_moe_lite_config(dict(GLM_HF, num_nextn_predict_layers=2))
    with pytest.raises(ValueError, match="dispatch_meta"):
        model, _meta = build_magi_pattern(_glm()[1], _mesh(1), CU, chunk_size=CHUNK)
        batch = jnp.zeros((1, TOTAL), jnp.int32)
        dataclasses.replace(model, dispatch_meta=None).loss_fn(
            None, batch, batch, batch, model.sharded_tables()
        )
