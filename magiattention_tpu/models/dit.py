"""Magi-1-style video-diffusion transformer (DiT) on CP flex attention.

The reference framework exists to train SandAI's Magi-1: an
*autoregressive chunked* video diffusion model — the video latent stream
is split into fixed-size time chunks; each chunk denoises while attending
to itself fully and to all PREVIOUS chunks (which are cleaner in the
denoising schedule), never to future chunks. That attention pattern is
exactly the ``varlen_block_causal`` mask family of the reference's
benchmark suite (cp_benchmark.md:78-86), expressed here as FULL slices
per chunk covering ``[0, chunk_end)`` — and it is why heterogeneous-mask
CP attention is the product: at 1M-token context the mask is the model.

Block anatomy (DiT / Magi-1 shape):
- adaLN-zero conditioning: the diffusion-timestep embedding produces
  per-block (shift, scale, gate) for both attention and MLP branches.
- self-attention over the video stream through the distributed flex
  kernel (chunked block-causal mask, CP-sharded, GQA, RoPE on flat
  positions).
- cross-attention to text tokens: text is a few hundred tokens and every
  video token attends all of them, so K/V are computed from a REPLICATED
  text stream and the cross-attention is rank-local — zero communication.
  (The framework's cross-attn dispatch machinery exists for the case
  where the kv stream is itself long/sharded; conditioning text is not
  that case, and burning a group_cast on it would be a translation
  artifact, not a design.)
- MLP with GELU.

Training objective: rectified-flow / velocity matching — noise the clean
latents per-chunk with independently sampled t in [0, 1], predict the
velocity (x1 - x0), MSE over valid tokens. Per-chunk independent t is
what makes chunked AR denoising trainable (later chunks see earlier,
less-noised chunks — the Magi-1 pipeline schedule).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.flex_attn import FlexAttnParams
from ..utils.compat import shard_map
from ..utils.instrument import named_scope
from ..parallel.dist_attn import (
    DistAttnPlan,
    dist_attn_local,
    make_attn_params,
)


@dataclasses.dataclass(frozen=True)
class DiTConfig:
    in_dim: int = 16  # latent channels per token (VAE patch)
    dim: int = 256
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 4
    head_dim: int = 32
    ffn_hidden: int = 512
    text_dim: int = 64
    text_len: int = 64
    rope_theta: float = 10000.0
    dtype: str = "float32"
    # rematerialize each DiT block in backward (jax.checkpoint), same
    # memory/compute trade as LlamaConfig.remat
    remat: bool = False

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)


def chunk_causal_mask(total: int, chunk_tokens: int):
    """The Magi-1 attention pattern: chunk i attends [0, end_of_chunk_i)
    fully. Returns (q_ranges, k_ranges, attn_type_map) naive lists."""
    qr, kr, ts = [], [], []
    c = 0
    while c < total:
        e = min(c + chunk_tokens, total)
        qr.append((c, e))
        kr.append((0, e))
        ts.append(0)  # FULL
        c = e
    return qr, kr, ts


def init_dit_params(rng: jax.Array, cfg: DiTConfig) -> dict:
    ks = iter(jax.random.split(rng, 10 + cfg.n_layers * 16))

    def dense(shape, scale=None):
        fan_in = shape[0]
        s = scale if scale is not None else fan_in ** -0.5
        return (jax.random.normal(next(ks), shape, jnp.float32) * s)

    d, hd = cfg.dim, cfg.head_dim
    hq, hk = cfg.n_heads, cfg.n_kv_heads
    layers = []
    for _ in range(cfg.n_layers):
        layers.append(
            {
                # adaLN-zero: 6 modulation vectors; final proj init 0 so
                # each block starts as identity (DiT recipe)
                "ada_w1": dense((d, d)),
                "ada_w2": jnp.zeros((d, 6 * d), jnp.float32),
                "wq": dense((d, hq * hd)),
                "wk": dense((d, hk * hd)),
                "wv": dense((d, hk * hd)),
                "wo": jnp.zeros((hq * hd, d), jnp.float32),
                "xwq": dense((d, hq * hd)),
                "xwk": dense((cfg.text_dim, hq * hd)),
                "xwv": dense((cfg.text_dim, hq * hd)),
                "xwo": jnp.zeros((hq * hd, d), jnp.float32),
                "w_up": dense((d, cfg.ffn_hidden)),
                "w_down": jnp.zeros((cfg.ffn_hidden, d), jnp.float32),
            }
        )
    return {
        "patch_in": dense((cfg.in_dim, d)),
        "t_embed_w1": dense((256, d)),
        "t_embed_w2": dense((d, d)),
        "final_ada": jnp.zeros((d, 2 * d), jnp.float32),
        "patch_out": jnp.zeros((d, cfg.in_dim), jnp.float32),
        "layers": layers,
    }


def _timestep_embedding(t, dim=256):
    """Sinusoidal embedding of diffusion time t in [0, 1] ([...,] -> [..., dim])."""
    half = dim // 2
    freqs = jnp.exp(
        -jnp.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half
    )
    ang = t[..., None] * 1000.0 * freqs
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)


def _ln(x):  # parameter-free LayerNorm (adaLN supplies scale/shift)
    m = x.mean(axis=-1, keepdims=True)
    v = ((x - m) ** 2).mean(axis=-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + 1e-6)


def _rope(x, pos, theta, hd):
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs  # [t, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos[:, None] - x2 * sin[:, None],
         x1 * sin[:, None] + x2 * cos[:, None]],
        axis=-1,
    ).astype(x.dtype)


def _cross_attn_local(xq_in, text_k, text_v, hq, hd):
    """Rank-local dense cross-attention to the replicated text stream.
    xq_in [t_loc, hq*hd]; text_k/v [t_text, hq*hd]."""
    t_loc = xq_in.shape[0]
    t_text = text_k.shape[0]
    q = xq_in.reshape(t_loc, hq, hd)
    k = text_k.reshape(t_text, hq, hd)
    v = text_v.reshape(t_text, hq, hd)
    z = jnp.einsum("qhd,khd->hqk", q, k) * (hd ** -0.5)
    p = jax.nn.softmax(z.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("hqk,khd->qhd", p, v).reshape(t_loc, hq * hd)


def dit_forward_local(
    params: dict,
    lat,  # [t_loc, in_dim] dispatched noised latents
    pos,  # [t_loc] global positions
    t_chunk,  # [t_loc] per-token diffusion time (constant within a chunk)
    text,  # [text_len, text_dim] replicated conditioning
    cfg: DiTConfig,
    tables,
    plan: DistAttnPlan,
    attn_params: FlexAttnParams,
    axis_name: str = "cp",
):
    """Per-cp-rank forward: noised latents -> predicted velocity."""
    dt = cfg.jnp_dtype
    hq, hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    x = (lat.astype(dt) @ params["patch_in"].astype(dt))
    # per-TOKEN conditioning: chunks carry independent t (AR denoising)
    temb = _timestep_embedding(t_chunk)  # [t_loc, 256]
    c = jax.nn.silu(temb.astype(dt) @ params["t_embed_w1"].astype(dt))
    c = c @ params["t_embed_w2"].astype(dt)  # [t_loc, d]

    def one_block(x, c, layer):
        mod = jax.nn.silu(c @ layer["ada_w1"].astype(dt))
        mod = mod @ layer["ada_w2"].astype(dt)  # [t_loc, 6d]
        sh1, sc1, g1, sh2, sc2, g2 = jnp.split(mod, 6, axis=-1)

        h = _ln(x) * (1 + sc1) + sh1
        q = (h @ layer["wq"].astype(dt)).reshape(-1, hq, hd)
        k = (h @ layer["wk"].astype(dt)).reshape(-1, hk, hd)
        v = (h @ layer["wv"].astype(dt)).reshape(-1, hk, hd)
        q = _rope(q, pos, cfg.rope_theta, hd)
        k = _rope(k, pos, cfg.rope_theta, hd)
        out, _lse, _mx = dist_attn_local(
            q, k, v, tables, plan, attn_params, axis_name=axis_name
        )
        x = x + g1 * (
            out.astype(dt).reshape(-1, hq * hd) @ layer["wo"].astype(dt)
        )

        # cross-attention to text (replicated, rank-local, zero comm)
        hx = _ln(x)
        xq = hx @ layer["xwq"].astype(dt)
        tk = text.astype(dt) @ layer["xwk"].astype(dt)
        tv = text.astype(dt) @ layer["xwv"].astype(dt)
        xo = _cross_attn_local(xq, tk, tv, hq, hd)
        x = x + xo @ layer["xwo"].astype(dt)

        h2 = _ln(x) * (1 + sc2) + sh2
        x = x + g2 * (
            jax.nn.gelu(h2 @ layer["w_up"].astype(dt))
            @ layer["w_down"].astype(dt)
        )
        return x

    if cfg.remat:
        one_block = jax.checkpoint(one_block)
    for layer in params["layers"]:
        x = one_block(x, c, layer)

    fmod = c @ params["final_ada"].astype(dt)
    fsh, fsc = jnp.split(fmod, 2, axis=-1)
    x = _ln(x) * (1 + fsc) + fsh
    return (x @ params["patch_out"].astype(dt)).astype(jnp.float32)


@dataclasses.dataclass(frozen=True, eq=False)
class MagiDiT:
    """Bundled Magi-1-style model: config + CP plan + jitted step makers.

    Batch layout: ``lat``/``t_chunk``/``pos`` are [batch, total_padded]
    (+ trailing feature dims) in DISPATCH order, batch on 'dp', tokens on
    'cp'; ``text`` is [batch, text_len, text_dim] replicated over cp.
    """

    cfg: DiTConfig
    mesh: Mesh
    plan: DistAttnPlan
    attn_params: FlexAttnParams
    cp_axis: str = "cp"
    dp_axis: str = "dp"

    def sharded_tables(self):
        from ._common import sharded_plan_tables

        return sharded_plan_tables(self.plan, self.mesh, self.cp_axis)

    def loss_fn(self, params, noised, target_v, t_chunk, pos, text, tables):
        """Velocity-matching MSE over valid tokens.

        Valid = ``t_chunk >= 0``. Uneven-shard pad slots MUST carry a
        negative t: dispatch ``t_chunk`` with ``pad_value=-1.0`` (the
        default pad_value=0 would pass the test and leak garbage pad rows
        into the loss)."""
        cfg = self.cfg
        tables = tuple(tables)

        @functools.partial(
            shard_map,
            mesh=self.mesh,
            in_specs=(
                P(),
                P(self.dp_axis, self.cp_axis),
                P(self.dp_axis, self.cp_axis),
                P(self.dp_axis, self.cp_axis),
                P(self.dp_axis, self.cp_axis),
                P(self.dp_axis),
            )
            + (P(self.cp_axis),) * len(tables),
            out_specs=P(),
            check_vma=False,
        )
        def _local(params, lat, tv, tc, pos, text, *tabs):
            def one(lat1, tv1, tc1, pos1, text1):
                pred = dit_forward_local(
                    params, lat1, pos1, tc1, text1, cfg, tabs,
                    self.plan, self.attn_params, self.cp_axis,
                )
                valid = (tc1 >= 0.0)[:, None]
                err = jnp.where(valid, pred - tv1, 0.0)
                return (err.astype(jnp.float32) ** 2).sum(), valid.sum()

            s, n = jax.vmap(one)(lat, tv, tc, pos, text)
            with named_scope("magi_dit_loss_psum"):
                s = jax.lax.psum(
                    jax.lax.psum(s.sum(), self.cp_axis), self.dp_axis
                )
                n = jax.lax.psum(
                    jax.lax.psum(n.sum(), self.cp_axis), self.dp_axis
                )
            return s / jnp.maximum(n.astype(jnp.float32) * cfg.in_dim, 1.0)

        return _local(params, noised, target_v, t_chunk, pos, text, *tables)

    def make_train_step(self, optimizer):
        tables = self.sharded_tables()

        def step(params, opt_state, noised, target_v, t_chunk, pos, text):
            loss, grads = jax.value_and_grad(self.loss_fn)(
                params, noised, target_v, t_chunk, pos, text, tables
            )
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = jax.tree.map(lambda p, u: p + u, params, updates)
            return params, opt_state, loss

        from ._common import tpu_compiler_options

        return jax.jit(
            step,
            donate_argnums=(0, 1),
            compiler_options=tpu_compiler_options(self.mesh),
        )

    def make_forward(self):
        """Jitted velocity prediction over dispatched [b, total, ...]."""
        tables = self.sharded_tables()
        cfg = self.cfg

        @functools.partial(
            shard_map,
            mesh=self.mesh,
            in_specs=(
                P(),
                P(self.dp_axis, self.cp_axis),
                P(self.dp_axis, self.cp_axis),
                P(self.dp_axis, self.cp_axis),
                P(self.dp_axis),
            )
            + (P(self.cp_axis),) * len(tables),
            out_specs=P(self.dp_axis, self.cp_axis),
            check_vma=False,
        )
        def _fwd(params, lat, tc, pos, text, *tabs):
            return jax.vmap(
                lambda l1, t1, p1, x1: dit_forward_local(
                    params, l1, p1, t1, x1, cfg, tabs,
                    self.plan, self.attn_params, self.cp_axis,
                )
            )(lat, tc, pos, text)

        def fwd(params, lat, t_chunk, pos, text):
            return _fwd(params, lat, t_chunk, pos, text, *tables)

        return jax.jit(fwd)


def build_magi_dit(
    cfg: DiTConfig,
    mesh: Mesh,
    total_tokens: int,
    chunk_tokens: int,
    *,
    dispatch_chunk: int | None = None,
    cp_axis: str = "cp",
    dp_axis: str = "dp",
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
) -> tuple[MagiDiT, Any]:
    """Plan the chunked block-causal CP attention and bundle the model.

    ``chunk_tokens`` = tokens per AR video chunk (frames x patches);
    ``dispatch_chunk`` = CP load-balancing chunk (defaults to a divisor-
    friendly fraction of the video chunk). Returns (model, dispatch_meta).
    """
    from .. import env
    from ..common.enum import AttnMaskType
    from ..common.ranges import AttnRanges
    from ..meta.dispatch_meta import make_dispatch_meta_from_qk_ranges
    from ..parallel.dist_attn import build_dist_attn_plan

    qr, kr, ts = chunk_causal_mask(total_tokens, chunk_tokens)
    cp_size = mesh.shape[cp_axis]
    if dispatch_chunk is None:
        dispatch_chunk = max(
            total_tokens // (env.min_chunks_per_rank() * cp_size), 1
        )
    mq, _, bucket = make_dispatch_meta_from_qk_ranges(
        AttnRanges.from_ranges(qr),
        AttnRanges.from_ranges(kr),
        [AttnMaskType(t) for t in ts],
        total_tokens,
        total_tokens,
        chunk_size=dispatch_chunk,
        cp_size=cp_size,
    )
    # plan-aware blocking (ISSUE 2): caller args -> autotuner -> env
    # default — the one harness policy, shared with plan_flex_attn
    from ._common import resolve_harness_blocking

    bq, bk, hb = resolve_harness_blocking(
        cfg, mesh, None, qr, kr, ts,
        total_tokens, cp_size, block_q, block_k,
    )
    plan = build_dist_attn_plan(mq, bucket, block_q=bq, block_k=bk)
    attn_params = make_attn_params(
        plan,
        cfg.head_dim,
        out_dtype=cfg.dtype,
        interpret=interpret,
        head_block=hb,
    )
    model = MagiDiT(
        cfg=cfg,
        mesh=mesh,
        plan=plan,
        attn_params=attn_params,
        cp_axis=cp_axis,
        dp_axis=dp_axis,
    )
    return model, mq
