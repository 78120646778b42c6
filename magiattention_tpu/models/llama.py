"""Llama-style decoder trained with context-parallel flex attention.

Role of reference ``examples/torch_native/main.py`` (Llama-3 1B FSDP+CP
trainer), re-designed TPU-first: the whole transformer runs inside one
``shard_map`` over a (dp, cp) mesh — parameters replicated, tokens sharded on
cp, batch on dp — with the attention layers calling the framework's
``dist_attn_local`` hot path. RoPE uses the dispatch position ids, so the
chunk-permuted token layout is transparent to the model.

Pure-jax (params = pytree), so the train step is a single jit: autodiff
through shard_map inserts the parameter-gradient psums and the dKV
group-reduce automatically.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.dist_attn import DistAttnPlan, dist_attn_local
from ..utils.compat import shard_map
from ..utils.instrument import named_scope
from ..ops.flex_attn import FlexAttnParams
from ._common import layer_under_remat, masked_ce_sums


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 2048
    n_layers: int = 16
    n_heads: int = 16
    n_kv_heads: int = 8
    head_dim: int = 128
    ffn_hidden: int = 5632
    rope_theta: float = 500000.0
    dtype: str = "bfloat16"
    # rematerialize each decoder layer in backward (jax.checkpoint):
    # activation memory drops from O(layers x t_loc x dim) to a layer's
    # input and its attention output (O(t_loc x dim) each a layer) at ~1/3
    # extra matmul FLOPs; the attention kernel is not run again
    # (_common.layer_under_remat) — the standard long-context
    # memory/compute trade on TPU (HBM is the usual bottleneck)
    remat: bool = False

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)


def init_params(rng: jax.Array, cfg: LlamaConfig) -> dict:
    """Parameter pytree (fp32 master weights)."""
    keys = jax.random.split(rng, cfg.n_layers + 2)

    def dense(key, shape, scale=None):
        scale = scale or (1.0 / np.sqrt(shape[0]))
        return (jax.random.normal(key, shape, jnp.float32) * scale)

    layers = []
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[i], 7)
        layers.append(
            {
                "wq": dense(k[0], (cfg.dim, cfg.n_heads * cfg.head_dim)),
                "wk": dense(k[1], (cfg.dim, cfg.n_kv_heads * cfg.head_dim)),
                "wv": dense(k[2], (cfg.dim, cfg.n_kv_heads * cfg.head_dim)),
                "wo": dense(k[3], (cfg.n_heads * cfg.head_dim, cfg.dim)),
                "w_gate": dense(k[4], (cfg.dim, cfg.ffn_hidden)),
                "w_up": dense(k[5], (cfg.dim, cfg.ffn_hidden)),
                "w_down": dense(k[6], (cfg.ffn_hidden, cfg.dim)),
                "attn_norm": jnp.ones((cfg.dim,), jnp.float32),
                "mlp_norm": jnp.ones((cfg.dim,), jnp.float32),
            }
        )
    return {
        "embed": dense(keys[-2], (cfg.vocab_size, cfg.dim), scale=0.02),
        "layers": layers,
        "final_norm": jnp.ones((cfg.dim,), jnp.float32),
        "lm_head": dense(keys[-1], (cfg.dim, cfg.vocab_size)),
    }


def _rms_norm(x, w, eps=1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w.astype(x.dtype)


def _rope(x, pos_ids, theta, head_dim):
    """x [t, h, hd]; pos_ids [t] global positions (dispatch-aware)."""
    half = head_dim // 2
    freqs = 1.0 / (
        theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    )
    angles = pos_ids.astype(jnp.float32)[:, None] * freqs[None, :]  # [t, half]
    cos = jnp.cos(angles)[:, None, :]
    sin = jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    rot = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    )
    return rot.astype(x.dtype)


def _layer_local(
    x,  # [t_loc, dim]
    pos,  # [t_loc] global position ids
    layer: dict,
    cfg: LlamaConfig,
    tables,
    plan: DistAttnPlan,
    attn_params: FlexAttnParams,
    axis_name: str,
    tp_axis: str | None = None,
):
    """One decoder layer on this rank's dispatched tokens.

    With ``tp_axis``, the layer params arrive column-sharded (wq/wk/wv,
    w_gate/w_up) / row-sharded (wo, w_down) over that mesh axis —
    Megatron-style tensor parallelism (reference ships TP only as a
    README patch, examples/megatron): each tp rank owns a head group and
    an FFN slice, and the two row-parallel matmuls end in a psum.
    Head counts are inferred from the (possibly sharded) weight shapes.
    """
    dt = cfg.jnp_dtype
    # one part scope a half (docs/observability.md, "Device scopes"); the
    # attention call is a sibling: a flex kernel never lies under magi_proj
    with named_scope("magi_proj"):
        h = _rms_norm(x, layer["attn_norm"])
        t = h.shape[0]
        q = (h @ layer["wq"].astype(dt)).reshape(t, -1, cfg.head_dim)
        k = (h @ layer["wk"].astype(dt)).reshape(t, -1, cfg.head_dim)
        v = (h @ layer["wv"].astype(dt)).reshape(t, -1, cfg.head_dim)
        q = _rope(q, pos, cfg.rope_theta, cfg.head_dim)
        k = _rope(k, pos, cfg.rope_theta, cfg.head_dim)
    out, _, _ = dist_attn_local(
        q, k, v, tables, plan, attn_params, axis_name=axis_name
    )
    with named_scope("magi_proj"):
        attn_out = out.reshape(t, -1) @ layer["wo"].astype(dt)
        if tp_axis is not None:
            with named_scope("magi_llama_attn_tp_psum"):
                attn_out = jax.lax.psum(attn_out, tp_axis)
        x = x + attn_out

    with named_scope("magi_ffn"):
        h = _rms_norm(x, layer["mlp_norm"])
        gate = jax.nn.silu(h @ layer["w_gate"].astype(dt))
        up = h @ layer["w_up"].astype(dt)
        mlp_out = (gate * up) @ layer["w_down"].astype(dt)
        if tp_axis is not None:
            with named_scope("magi_llama_mlp_tp_psum"):
                mlp_out = jax.lax.psum(mlp_out, tp_axis)
        x = x + mlp_out
    return x


def forward_local(
    params: dict,
    tokens,  # [t_loc] int32 dispatched tokens
    pos,  # [t_loc] global position ids
    cfg: LlamaConfig,
    tables,
    plan: DistAttnPlan,
    attn_params: FlexAttnParams,
    axis_name: str = "cp",
    tp_axis: str | None = None,
):
    """Per-cp-rank forward over dispatched tokens -> logits [t_loc, vocab]."""
    dt = cfg.jnp_dtype
    with named_scope("magi_embed"):
        x = params["embed"].astype(dt)[tokens]

    # under cfg.remat a layer saves its input and its attention call's out
    # and lse; the rest (projections, rotary, FFN) recomputes in backward
    one_layer = layer_under_remat(
        lambda attn_params: lambda x, pos, layer: _layer_local(
            x, pos, layer, cfg, tables, plan, attn_params, axis_name, tp_axis
        ),
        attn_params, "full", remat=cfg.remat,
    )
    for layer in params["layers"]:
        x = one_layer(x, pos, layer)
    with named_scope("magi_head"):
        x = _rms_norm(x, params["final_norm"])
        return (x @ params["lm_head"].astype(dt)).astype(jnp.float32)


@dataclasses.dataclass(frozen=True, eq=False)
class MagiLlama:
    """The flagship model bundle: config + plan + mesh + jitted step makers.

    ``tokens`` / ``labels`` / ``pos`` are in DISPATCH order, shaped
    [batch, total_padded] with batch sharded on 'dp' and tokens on 'cp'.
    """

    cfg: LlamaConfig
    mesh: Mesh
    plan: DistAttnPlan
    attn_params: FlexAttnParams
    cp_axis: str | tuple[str, str] = "cp"
    dp_axis: str = "dp"
    tp_axis: str | None = None

    def param_specs(self):
        """PartitionSpec pytree for the parameter pytree.

        Without tp: everything replicated. With tp: Megatron column/row
        sharding on the per-layer weights; embed / lm_head / norms stay
        replicated (vocab is small relative to the layer stack).
        """
        if self.tp_axis is None:
            return P()
        tp = self.tp_axis
        layer_spec = {
            "wq": P(None, tp),
            "wk": P(None, tp),
            "wv": P(None, tp),
            "wo": P(tp, None),
            "w_gate": P(None, tp),
            "w_up": P(None, tp),
            "w_down": P(tp, None),
            "attn_norm": P(),
            "mlp_norm": P(),
        }
        return {
            "embed": P(),
            "layers": [layer_spec] * self.cfg.n_layers,
            "final_norm": P(),
            "lm_head": P(),
        }

    def loss_fn(self, params, tokens, labels, pos, tables):
        """Mean next-token CE over valid (label >= 0) positions."""
        cfg = self.cfg
        tables = tuple(tables)

        @functools.partial(
            shard_map,
            mesh=self.mesh,
            in_specs=(
                self.param_specs(),
                P(self.dp_axis, self.cp_axis),
                P(self.dp_axis, self.cp_axis),
                P(self.dp_axis, self.cp_axis),
            )
            + (P(self.cp_axis),) * len(tables),
            out_specs=P(),
            check_vma=False,
        )
        def _local(params, tok, lab, pos, *tabs):
            def one(tok1, lab1, pos1):
                logits = forward_local(
                    params,
                    tok1,
                    pos1,
                    cfg,
                    tabs,
                    self.plan,
                    self.attn_params,
                    self.cp_axis,
                    self.tp_axis,
                )
                with named_scope("magi_head"):
                    return masked_ce_sums(logits, lab1)

            loss_sum, count = jax.vmap(one)(tok, lab, pos)
            with named_scope("magi_head"), named_scope("magi_llama_loss_psum"):
                loss_sum = jax.lax.psum(
                    jax.lax.psum(loss_sum.sum(), self.cp_axis), self.dp_axis
                )
                count = jax.lax.psum(
                    jax.lax.psum(count.sum(), self.cp_axis), self.dp_axis
                )
            with named_scope("magi_head"):
                return loss_sum / jnp.maximum(count, 1.0)

        return _local(params, tokens, labels, pos, *tables)

    def sharded_tables(self):
        from ._common import sharded_plan_tables

        return sharded_plan_tables(self.plan, self.mesh, self.cp_axis)

    def make_train_step(self, optimizer):
        """optax-style optimizer -> jitted (params, opt_state, batch) step."""
        from ._common import make_model_train_step

        return make_model_train_step(self, optimizer)

    def make_forward(self):
        tables = self.sharded_tables()
        cfg = self.cfg

        @functools.partial(
            shard_map,
            mesh=self.mesh,
            in_specs=(
                self.param_specs(),
                P(self.dp_axis, self.cp_axis),
                P(self.dp_axis, self.cp_axis),
            )
            + (P(self.cp_axis),) * len(tables),
            out_specs=P(self.dp_axis, self.cp_axis),
            check_vma=False,
        )
        def _fwd(params, tok, pos, *tabs):
            return jax.vmap(
                lambda t1, p1: forward_local(
                    params,
                    t1,
                    p1,
                    cfg,
                    tabs,
                    self.plan,
                    self.attn_params,
                    self.cp_axis,
                    self.tp_axis,
                )
            )(tok, pos)

        def fwd(params, tokens, pos):
            return _fwd(params, tokens, pos, *tables)

        return fwd


def build_magi_llama(
    cfg: LlamaConfig,
    mesh: Mesh,
    total_seqlen: int,
    q_ranges,
    k_ranges,
    attn_type_map,
    *,
    chunk_size: int,
    cp_axis: str | tuple[str, str] = "cp",
    dp_axis: str = "dp",
    tp_axis: str | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    overlap_config=None,
) -> tuple[MagiLlama, Any]:
    """Plan the CP attention for one mask and bundle the model.

    Returns (model, dispatch_meta) — dispatch tokens/labels with
    parallel.dispatch using the meta before feeding the step.

    ``tp_axis`` turns on Megatron-style tensor parallelism over that mesh
    axis (head groups + FFN slices; see ``_layer_local``). Requires the
    head counts to divide by the axis size.

    ``cp_axis`` may be an ``(inter, intra)`` mesh-axis pair for
    hierarchical 2-level cp comm; ``overlap_config`` forces the overlap
    degree (None = the plan builder's default: degree-0 merged path).
    """
    from ._common import plan_flex_attn

    if isinstance(cp_axis, list):
        cp_axis = tuple(cp_axis)
    plan, attn_params, mq = plan_flex_attn(
        cfg,
        mesh,
        total_seqlen,
        q_ranges,
        k_ranges,
        attn_type_map,
        chunk_size=chunk_size,
        cp_axis=cp_axis,
        tp_axis=tp_axis,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
        overlap_config=overlap_config,
    )
    model = MagiLlama(
        cfg=cfg,
        mesh=mesh,
        plan=plan,
        attn_params=attn_params,
        cp_axis=cp_axis,
        dp_axis=dp_axis,
        tp_axis=tp_axis,
    )
    return model, mq
