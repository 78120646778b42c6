"""Shared model-bundle helpers (one copy for every model family)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import telemetry
from ..common.axes import cp_axis_names
from ..ops.flex_attn import KEPT_NAMES
from ..utils.instrument import named_scope


def masked_ce_tokens(logits, labels):
    """(CE a position, 0 where its label < 0; which positions are valid).

    The single definition of the next-token loss — MagiLlama and
    MagiLlamaPP must stay numerically identical through it.
    """
    logp = jax.nn.log_softmax(logits, axis=-1)
    valid = labels >= 0
    safe = jnp.where(valid, labels, 0)
    tok_loss = -jnp.take_along_axis(logp, safe[:, None], axis=1)[:, 0]
    return jnp.where(valid, tok_loss, 0.0), valid


def masked_ce_sums(logits, labels):
    """(sum of CE over positions with label >= 0, count of them)."""
    tok_loss, valid = masked_ce_tokens(logits, labels)
    return tok_loss.sum(), valid.sum().astype(jnp.float32)


def layer_under_remat(
    make_layer, attn_params, kinds, *, remat: bool, applied_once: bool = True
):
    """``make_layer(attn_params)``, the layer function of a trunk, as
    ``cfg.remat`` has it. ``attn_params``: the layer's ``FlexAttnParams``,
    one or a dict of them; ``kinds``: the attention kind of each, in the
    same form, as the counters name it.

    Without ``remat`` the layer as it is. With it the layer under
    ``jax.checkpoint``, which keeps the layer's inputs and, of a layer the
    trunk applies once a step, its attention calls' out and compact lse:
    the calls are told so (``FlexAttnParams.kept``) here where the policy
    that saves their names is set, so the two cannot disagree. The
    backward's recomputation then remakes the norm, the projections, rotary
    and the layouts, which the backward kernel needs anyway, and launches
    no forward kernel: a step runs it once a layer, not twice.

    ``applied_once=False`` is a layer inside a scan over passes (the looped
    trunk): what it kept would be held once a pass on shared weights, the
    bytes times the passes, so it keeps its inputs alone and recomputes the
    forward kernel (24 applications of 68 MB against 1.06 GB of room in the
    looped cell; PERF.md section 6, PR 48)."""
    if not remat:
        return make_layer(attn_params)
    if not applied_once:
        return jax.checkpoint(make_layer(attn_params))
    kept = jax.tree.map(
        lambda p, kind: dataclasses.replace(p, kept=kind), attn_params, kinds
    )
    return jax.checkpoint(
        make_layer(kept),
        policy=jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES),
    )


def _can_place(mesh) -> bool:
    """Whether this process can put arrays on every device of the mesh:
    they belong to a live backend (a described AOT topology's client
    only compiles) and to this process."""
    devs = list(mesh.devices.flat)
    try:
        client = jax.devices(devs[0].platform)[0].client
    except RuntimeError:  # no live backend of that platform
        return False
    me = jax.process_index()
    return all(d.client is client and d.process_index == me for d in devs)


def sharded_plan_tables(plan, mesh, cp_axis):
    """The plan's device tables placed P(cp_axis) — or left as host
    constants where placement is impossible (AOT-compilation
    topologies, other processes' devices) and jit embeds them."""
    tables = plan.device_tables()
    if _can_place(mesh):
        spec = NamedSharding(mesh, P(cp_axis_names(cp_axis)))
        return tuple(jax.device_put(t, spec) for t in tables)
    return tuple(tables)


def _cp_geometry(mesh, cp_axis):
    """(cp_size, cp_mesh_shape) of ``cp_axis``: one mesh axis, or an
    ``(inter, intra)`` pair (hierarchical 2-level comm)."""
    names = cp_axis_names(cp_axis)
    assert len(names) in (1, 2), (
        f"cp_axis must be one mesh axis or an (inter, intra) pair, got "
        f"{cp_axis!r}"
    )
    cp_size = 1
    for a in names:
        cp_size *= mesh.shape[a]
    cp_mesh_shape = (
        (mesh.shape[names[0]], mesh.shape[names[1]])
        if len(names) == 2
        else None
    )
    return cp_size, cp_mesh_shape


def _check_tp(cfg, mesh, tp_axis) -> None:
    if tp_axis is not None:
        tp = mesh.shape[tp_axis]
        if cfg.n_heads % tp or cfg.n_kv_heads % tp:
            raise ValueError(
                f"tp={tp} must divide n_heads={cfg.n_heads} and "
                f"n_kv_heads={cfg.n_kv_heads}"
            )


def _plan_on_dispatch(
    cfg, mesh, mq, bucket, total_seqlen, q_ranges, k_ranges, attn_type_map,
    *, cp_axis, tp_axis, block_q, block_k, interpret, overlap_config, kind,
):
    """Tile choice, CP plan and kernel parameters of ONE mask on a solved
    dispatch (``mq``, and the mask's ``bucket`` on its chunks)."""
    from ..parallel.dist_attn import build_dist_attn_plan, make_attn_params

    cp_size, cp_mesh_shape = _cp_geometry(mesh, cp_axis)
    # the value heads' width where the kernels' is not ``head_dim``
    # (pattern.KernelHeads under latent attention); None: one width
    v_head_dim = getattr(cfg, "v_head_dim", None) or None
    telemetry.annotate_span(  # what the kernels are handed, every model's
        heads_q=cfg.n_heads, heads_kv=cfg.n_kv_heads, head_dim=cfg.head_dim,
        v_head_dim=v_head_dim or cfg.head_dim,
    )
    if telemetry.enabled():  # what the mask is made of
        from ..common.enum import AttnMaskType
        from ..common.mask import unstepped_slice_count

        q_naive, k_naive = q_ranges.to_naive_ranges(), k_ranges.to_naive_ranges()
        mask_step = max(
            (AttnMaskType(int(t)).step for t in attn_type_map), default=1
        )
        telemetry.annotate_span(
            mask_step=mask_step,
            slices=len(attn_type_map),
            # the slices the same mask takes at step 1
            rectangles=unstepped_slice_count(q_naive, k_naive, attn_type_map),
        )
        if kind is not None:
            telemetry.record_mask_step(kind, mask_step)
    if kind is not None:
        telemetry.annotate_span(kind=kind)
        telemetry.record_model_attn_plan(kind)
    bq, bk, hb = resolve_harness_blocking(
        cfg, mesh, tp_axis,
        q_ranges.to_naive_ranges(),
        k_ranges.to_naive_ranges(),
        attn_type_map,
        total_seqlen, cp_size, block_q, block_k, v_head_dim,
    )
    plan = build_dist_attn_plan(
        mq,
        bucket,
        block_q=bq,
        block_k=bk,
        overlap_config=overlap_config,
        cp_mesh_shape=cp_mesh_shape,
    )
    with telemetry.span("attn_fn_build"):
        attn_params = make_attn_params(
            plan,
            cfg.head_dim,
            # a model whose kernel heads are wider than its own
            # (pattern.KernelHeads) keeps its own softmax scale
            scale=getattr(cfg, "softmax_scale", None),
            out_dtype=cfg.dtype,
            interpret=interpret,
            head_block=hb,
            v_head_dim=v_head_dim,
        )
    return plan, attn_params


@telemetry.span("plan_flex_attn")  # one span a call, as a key's key_build
def plan_flex_attn(
    cfg,
    mesh,
    total_seqlen,
    q_ranges,
    k_ranges,
    attn_type_map,
    *,
    chunk_size: int,
    cp_axis,
    tp_axis: str | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    overlap_config=None,
    kind: str | None = None,
):
    """Shared builder tail for every Llama-family bundle: validate tp
    divisibility, build the dispatch meta + CP plan for one mask, and
    derive the kernel params. Returns (plan, attn_params, dispatch_meta).

    ``cp_axis`` may be an ``(inter, intra)`` mesh-axis pair: the plan is
    then built with hierarchical 2-level comm (``cp_mesh_shape``) and the
    runtime routes casts through the two-hop dedup path (comm/hier.py).
    ``overlap_config`` forces the overlap degree/algorithm (default:
    OverlapConfig(), i.e. the degree-0 merged no-overlap path; pass
    degree=None for the auto-tuned degree). ``kind`` names the attention
    kind the mask belongs to in a model with several
    (``models/pattern.py``): it lands on the span and in
    ``magi_model_attn_plans_total{kind=}``."""
    from ..common.enum import AttnMaskType
    from ..meta.dispatch_meta import make_dispatch_meta_from_qk_ranges

    _check_tp(cfg, mesh, tp_axis)
    cp_size, _ = _cp_geometry(mesh, cp_axis)
    mq, _, bucket = make_dispatch_meta_from_qk_ranges(
        q_ranges,
        k_ranges,
        [AttnMaskType(int(t)) for t in attn_type_map],
        total_seqlen,
        total_seqlen,
        chunk_size=chunk_size,
        cp_size=cp_size,
    )
    plan, attn_params = _plan_on_dispatch(
        cfg, mesh, mq, bucket, total_seqlen, q_ranges, k_ranges,
        attn_type_map,
        cp_axis=cp_axis, tp_axis=tp_axis, block_q=block_q, block_k=block_k,
        interpret=interpret, overlap_config=overlap_config, kind=kind,
    )
    return plan, attn_params, mq


@telemetry.span("plan_flex_attn")
def plan_flex_attn_on_dispatch(
    cfg,
    mesh,
    dispatch_meta,
    q_ranges,
    k_ranges,
    attn_type_map,
    *,
    cp_axis,
    tp_axis: str | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    overlap_config=None,
    kind: str | None = None,
):
    """Another mask on a dispatch that :func:`plan_flex_attn` solved (the
    ``*_for_new_mask_after_dispatch`` rule of ``api/interface.py``: same
    ``chunk_size``, same partitions, so dispatched activations are shared
    and only the plan, the tiles and the tables differ). Returns
    (plan, attn_params)."""
    from ..common.enum import AttnMaskType
    from ..meta.dispatch_meta import make_global_bucket_from_qk_ranges

    _check_tp(cfg, mesh, tp_axis)
    bucket = make_global_bucket_from_qk_ranges(
        q_ranges,
        k_ranges,
        [AttnMaskType(int(t)) for t in attn_type_map],
        dispatch_meta.total_seqlen,
        dispatch_meta.chunk_size,
    )
    return _plan_on_dispatch(
        cfg, mesh, dispatch_meta, bucket, dispatch_meta.total_seqlen,
        q_ranges, k_ranges, attn_type_map,
        cp_axis=cp_axis, tp_axis=tp_axis, block_q=block_q, block_k=block_k,
        interpret=interpret, overlap_config=overlap_config, kind=kind,
    )


def resolve_harness_blocking(
    cfg, mesh, tp_axis, q_naive, k_naive, attn_type_map,
    total_seqlen, cp_size, block_q, block_k, v_head_dim=None,
) -> tuple[int, int, int]:
    """(block_q, block_k, head_block) for a model-harness plan — ONE
    policy shared by every bundle builder (ISSUE 2): caller args win;
    else the plan-aware autotuner (which itself steps aside for env pins /
    autotune=off / tiny shards); else the legacy env defaults. Heads are
    the PER-RANK counts the kernels actually run under tp. When the tuner
    steps aside, an explicit MAGI_ATTENTION_HEAD_BLOCK is honored (snapped
    to the per-tp-rank GQA geometry); unset keeps the harness's legacy
    head_block of 1."""
    from .. import env

    tp = mesh.shape[tp_axis] if tp_axis is not None else 1
    hq = max(cfg.n_heads // tp, 1)
    hkv = max(cfg.n_kv_heads // tp, 1)
    if block_q is None and block_k is None:
        from ..tuning.autotuner import resolve_block_config

        with telemetry.span("tile_choice"):
            tuned = resolve_block_config(
                q_naive,
                k_naive,
                tuple(int(t) for t in attn_type_map),
                total_seqlen,
                total_seqlen,
                cp_size,
                hq,
                hkv,
                cfg.head_dim,
                str(cfg.dtype),
                v_head_dim,
            )
        if tuned is not None:
            return tuned
    hb_env = env.head_block_override()
    if hb_env is None:
        hb = 1
    else:
        from ..ops.flex_attn import _auto_head_block

        hb = _auto_head_block(hb_env, hq, max(hq // hkv, 1))
    return (block_q or env.block_q(), block_k or env.block_k(), hb)


def make_model_train_step(model, optimizer):
    """optax-style optimizer -> jitted (params, opt_state, batch) step.

    Works for any bundle exposing ``loss_fn`` + ``sharded_tables``.

    A weight matrix's update is not computed inside the matmul that makes
    its gradient: every 2-D gradient leaf goes through
    ``jax.lax.optimization_barrier`` by itself before ``optimizer.update``.
    On one chip nothing else stands between the two (in a data-parallel
    or sharded job a collective does), and the TPU compiler then fuses
    AdamW into the weight-gradient matmul as three float32 outputs (the
    new weight, ``mu``, ``nu``); those shrink the tile the matmul can
    keep, and it ran at 73-96 TFLOP/s where the same shapes' forward and
    input-gradient matmuls ran at 158-170: 185 ms of Mistral-7B's 589 ms
    step on a v5e, 115 ms with the update apart (PERF.md, PR 47). A
    barrier a leaf and not one round the tree: each gradient is retired
    as it comes, where one barrier would hold every float32 gradient at
    once (2.8 GB there). Matrices only: a matrix's gradient is one
    matmul's output, while a stacked leaf's (held experts,
    ``[experts, in, out]``) is a sum over row chunks that its update
    reads in the same pass, and a barrier there costs a float32 write
    and read of the whole leaf for nothing (5 ms of ZAYA1's 314). So the
    ``magi_optimizer`` scope reads the whole update, about 28 bytes a
    parameter at the HBM's pace."""
    tables = model.sharded_tables()

    def step(params, opt_state, tokens, labels, pos, weights=None):
        # ``weights``: a row's weight on the loss, where the bundle's
        # loss takes one (models/pattern.py under diffusion over blocks)
        loss, grads = jax.value_and_grad(model.loss_fn)(
            params, tokens, labels, pos, tables,
            *(() if weights is None else (weights,)),
        )
        grads = jax.tree.map(
            lambda g: jax.lax.optimization_barrier(g) if g.ndim == 2 else g,
            grads,
        )
        with named_scope("magi_optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = jax.tree.map(lambda p, u: p + u, params, updates)
        return params, opt_state, loss

    return jax.jit(
        step,
        donate_argnums=(0, 1),
        compiler_options=tpu_compiler_options(model.mesh),
    )


def tpu_compiler_options(mesh):
    """jit compiler options for the train step: async-a2a overlap where
    the mesh is of TPU devices (docs/overlap.md), None elsewhere (the
    options are TPU-only)."""
    if mesh.devices.flat[0].platform == "tpu":
        from ..env import recommended_compiler_options

        return recommended_compiler_options()
    return None
