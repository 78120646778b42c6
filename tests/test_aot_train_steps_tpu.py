"""Whole steps, asked of the TPU compiler without a chip (see
``tests/test_aot_compile_tpu.py``, which compiles the kernels alone): paged
decode, the serve prefill continuation, train steps over the four
described chips, and the keyed path's program as the benchmark's attention
cells compile it."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from .aot import (  # noqa: F401
    _GROUP_ONE, _as_on_the_chip, _compile, _on, topo, update_fusions,
)


def _serve_cache(chip, hk, d):
    """The smoke's serve-phase pool: 128k tokens, default page size."""
    from magiattention_tpu import env
    from magiattention_tpu.serving import make_paged_kv_cache

    shapes = jax.eval_shape(
        functools.partial(
            make_paged_kv_cache, 131072 // env.page_size(), env.page_size(),
            hk, d, max_seqs=8, max_pages_per_seq=128,
        )
    )
    return jax.tree.map(lambda s: _on(chip, s.shape, s.dtype), shapes)


@pytest.mark.parametrize("splits", [1, 4])
def test_paged_decode(topo, splits):
    from magiattention_tpu.serving import decode_attn_paged

    hq, hk, d, b = 32, 4, 64, 4
    chip = SingleDeviceSharding(topo.devices[0])
    text = _compile(
        functools.partial(
            decode_attn_paged, num_splits=splits, interpret=False
        ),
        _on(chip, (b, hq, d)), _serve_cache(chip, hk, d),
        _on(chip, (b,), jnp.int32),
    )
    assert "tpu_custom_call" in text


def test_serve_prefill_continuation(topo):
    """The serve phase's last prefill chunk: 2048 queries against the
    8192 keys written so far (``continue_prefill_into_cache``)."""
    from magiattention_tpu.serving.engine import continue_prefill_into_cache

    hq, hk, d, t, start = 32, 4, 64, 2048, 6144
    chip = SingleDeviceSharding(topo.devices[0])
    text = _compile(
        functools.partial(
            continue_prefill_into_cache, slot=0, start=start, interpret=False
        ),
        _on(chip, (t, hq, d)), _on(chip, (t, hk, d)), _on(chip, (t, hk, d)),
        _serve_cache(chip, hk, d),
    )
    assert "tpu_custom_call" in text


def _benchmark_json(*parts):
    import json

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", *parts)) as f:
        return json.load(f)


def _compile_train_step(model, init, opt, rows: int):
    """The model's optimizer step compiled for its described mesh: the
    parameters of ``init`` and the optimizer's state replicated, one
    ``(1, rows)`` row of token ids, labels and positions."""
    rep = NamedSharding(model.mesh, P())
    params = jax.eval_shape(init)
    state = jax.eval_shape(opt.init, params)
    params, state = jax.tree.map(
        lambda s: _on(rep, s.shape, s.dtype), (params, state)
    )
    batch = _on(NamedSharding(model.mesh, P("dp", "cp")), (1, rows), jnp.int32)
    return (
        model.make_train_step(opt)
        .lower(params, state, batch, batch, batch)
        .compile()
    )


def test_two_layer_train_step_cp4(topo):
    """A whole optimizer step over the four described chips: the plan
    tables cannot be placed there (``sharded_plan_tables`` leaves them
    to jit), ``recommended_compiler_options`` is accepted, and the
    plan's collectives are in the program."""
    import optax

    from magiattention_tpu.api import infer_varlen_mask_from_batch
    from magiattention_tpu.models import (
        LlamaConfig,
        build_magi_llama,
        init_params,
    )

    total = 2048
    cfg = LlamaConfig(
        vocab_size=1024, dim=256, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=64, ffn_hidden=512,
    )
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("dp", "cp"))
    qr, kr, ts = infer_varlen_mask_from_batch([700, 300, 1048])
    model, _ = build_magi_llama(
        cfg, mesh, total, qr, kr, ts, chunk_size=128, interpret=False
    )
    text = _compile_train_step(
        model, lambda: init_params(jax.random.PRNGKey(0), cfg),
        optax.adamw(1e-4), total,
    ).as_text()
    assert "tpu_custom_call" in text
    assert "all-to-all" in text or "collective-permute" in text


def _dense_step(mesh):
    """Mistral-7B's step as its cell runs it: the published widths, 2
    layers, remat, the cell's 16,384-token packed mask."""
    from benchmarks import masks
    from benchmarks.kinds.train_stream import _llama_config
    from magiattention_tpu.common import AttnRanges
    from magiattention_tpu.models import build_magi_llama, init_params

    tr = _benchmark_json("traffic", "train-16k-onemask.json")
    cfg = _llama_config(_benchmark_json("configs", "mistral-7b-v0.3.json"), tr)
    total = int(tr["total_tokens"])
    mask = masks.build_mask(tr["mask"], total, index=0)
    model, _ = build_magi_llama(
        cfg, mesh, total,
        AttnRanges.from_ranges(list(mask.q_ranges)),
        AttnRanges.from_ranges(list(mask.k_ranges)),
        list(mask.types), chunk_size=int(tr["chunk_size"]), interpret=False,
    )
    return model, (lambda: init_params(jax.random.PRNGKey(0), cfg)), total


def _experts_step(mesh):
    """Trinity-Mini's step at the published widths, its rank's share of
    the experts and the vocabulary, 2 of its layers (a dense one, one
    with held experts and the shared expert), at the check's 4,096
    tokens."""
    from benchmarks import masks
    from magiattention_tpu.models.pattern import (
        afmoe_config, build_magi_pattern, init_pattern_params,
    )

    hf = _benchmark_json("configs", "trinity-mini.json")
    tr = _benchmark_json("traffic", "train-32k-packed-swa-global.json")
    cfg = afmoe_config(
        dict(hf, num_hidden_layers=2,
             layer_types=["sliding_attention", "full_attention"]),
        remat=True, expert_range=tuple(hf["experts_here"]),
        vocab_size=hf["vocab_here"],
    )
    total = int(tr["check_tokens"])
    mask = masks.build_mask(tr["check_mask"], total, index=0)
    model, _ = build_magi_pattern(
        cfg, mesh, mask.cu_seqlens, chunk_size=int(tr["chunk_size"]),
        interpret=False,
    )
    return (
        model, (lambda: init_pattern_params(jax.random.PRNGKey(0), cfg)),
        total,
    )


def _prerouted_step(mesh):
    """SmallThinker's step as its cell runs it: the published widths, its
    rank's share of the experts and the vocabulary, all 8 kept layers
    (2 full, 6 window-4,096), remat, the cell's 16,384-token mask."""
    from benchmarks import masks
    from magiattention_tpu.models.pattern import (
        build_magi_pattern, init_pattern_params, smallthinker_config,
    )

    hf = _benchmark_json("configs", "smallthinker-21b-a3b.json")
    tr = _benchmark_json("traffic", "train-16k-packed-prerouted.json")
    cfg = smallthinker_config(
        hf, dtype=tr["dtype"], remat=bool(tr["remat"]),
        expert_range=tuple(hf["experts_here"]), vocab_size=hf["vocab_here"],
    )
    total = int(tr["total_tokens"])
    mask = masks.build_mask(tr["mask"], total, index=0)
    model, _ = build_magi_pattern(
        cfg, mesh, mask.cu_seqlens, chunk_size=int(tr["chunk_size"]),
        interpret=False,
    )
    return (
        model, (lambda: init_pattern_params(jax.random.PRNGKey(0), cfg)),
        total,
    )


def _ssd_step(mesh):
    """granite-4.0-h-micro's step as its cell runs it: the published
    widths, its rank's share of the vocabulary, all 10 kept layers (nine
    Mamba-2, one attention), remat, the cell's 16,384-token mask."""
    from benchmarks import masks
    from benchmarks.kinds.train_ssd import model_keys
    from magiattention_tpu.models.pattern import (
        build_magi_pattern, granitemoehybrid_config, init_pattern_params,
    )

    hf = _benchmark_json("configs", "granite-4.0-h-micro.json")
    tr = _benchmark_json("traffic", "train-16k-packed-ssd.json")
    cfg = granitemoehybrid_config(
        model_keys(hf), dtype=tr["dtype"], remat=bool(tr["remat"]),
        vocab_size=hf["vocab_here"],
    )
    total = int(tr["total_tokens"])
    mask = masks.build_mask(tr["mask"], total, index=0)
    model, _ = build_magi_pattern(
        cfg, mesh, mask.cu_seqlens, chunk_size=int(tr["chunk_size"]),
        interpret=False,
    )
    return (
        model, (lambda: init_pattern_params(jax.random.PRNGKey(0), cfg)),
        total,
    )


_STEPS = {"dense": _dense_step, "experts": _experts_step}


@functools.cache
def _update_in_step(topo, which: str, barrier: bool):
    """(``update_fusions`` of the compiled AdamW step's text, its
    temporaries' bytes) on one described chip; ``barrier`` False builds
    the same step with ``make_model_train_step``'s barrier taken out."""
    import optax

    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("dp", "cp"))
    model, init, total = _STEPS[which](mesh)
    with pytest.MonkeyPatch.context() as patch:
        if not barrier:
            patch.setattr(jax.lax, "optimization_barrier", lambda x: x)
        exe = _compile_train_step(model, init, optax.adamw(3e-4), total)
    return update_fusions(exe.as_text()), exe.memory_analysis().temp_size_in_bytes


def test_the_prerouted_cells_step_fits_the_chip(topo):
    """The SmallThinker cell's whole AdamW step compiled for one described
    v5e at its size (ISSUE 53): 28 / 4 heads of 128 on the full plan's and
    the window plan's rungs, the route made before each layer's attention
    kernel, the ReLU-gated held experts on whole chunks. Its arguments and
    temporaries fit the chip with room (15.5 of its 16 GB are the
    program's), no weight's update is fused into its gradient's matmul,
    and a layer's forward kernel is in the program once (kept across
    remat): 2 flex kernels a layer."""
    import optax

    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("dp", "cp"))
    model, init, total = _prerouted_step(mesh)
    exe = _compile_train_step(model, init, optax.adamw(3e-4), total)
    mem = exe.memory_analysis()
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert held < 15.5e9, (mem.argument_size_in_bytes, mem.temp_size_in_bytes)
    text = exe.as_text()
    fused, under_scope = update_fusions(text)
    assert fused == [] and under_scope > 0
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    for kernel in ("magi_flex_fwd_kernel", "magi_flex_bwd_kernel"):
        launched = sum(f"/{kernel}/pallas_call" in ln for ln in calls)
        assert launched == model.cfg.n_layers, (kernel, launched)
    assert "magi_moe_router" in text
    print(f"arguments {mem.argument_size_in_bytes} temporaries "
          f"{mem.temp_size_in_bytes}")


def test_the_ssd_cells_step_fits_the_chip(topo):
    """The granite-4.0-h-micro cell's whole AdamW step compiled for one
    described v5e at its size (ISSUE 55): nine Mamba-2 layers on the
    state-space-dual scan's kernel pair at 64 heads of 64 x 128 states,
    one attention layer at 32 / 8 heads of 64 (the width as it is: no
    padded lanes). Its arguments and temporaries fit the chip, no
    weight's update is fused into its gradient's matmul, the attention
    layer's forward kernel is in the program once (kept across remat), and
    a Mamba-2 layer's scan runs forward twice (remat keeps nothing of it)
    and backward once."""
    import optax

    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("dp", "cp"))
    model, init, total = _ssd_step(mesh)
    exe = _compile_train_step(model, init, optax.adamw(3e-4), total)
    mem = exe.memory_analysis()
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert held < 15.8e9, (mem.argument_size_in_bytes, mem.temp_size_in_bytes)
    text = exe.as_text()
    fused, under_scope = update_fusions(text)
    assert fused == [] and under_scope > 0
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    scans = model.cfg.layer_types.count("state_space_dual")
    for kernel, launches in (
        ("magi_flex_fwd_kernel", 1), ("magi_flex_bwd_kernel", 1),
        ("magi_ssd_scan_fwd_kernel", 2 * scans),
        ("magi_ssd_scan_bwd_kernel", scans),
    ):
        launched = sum(f"/{kernel}/pallas_call" in ln for ln in calls)
        assert launched == launches, (kernel, launched)
    print(f"arguments {mem.argument_size_in_bytes} temporaries "
          f"{mem.temp_size_in_bytes}")


@pytest.mark.parametrize("which", list(_STEPS))
def test_no_update_inside_a_gradients_matmul(topo, which):
    """A weight matrix's AdamW update is not computed inside the matmul
    that makes its gradient (ISSUE 47: three float32 outputs on such a
    matmul halved its pace on the chip): no fused computation of the step
    holds a ``convolution`` and an instruction under ``magi_optimizer``
    (the held experts' stacked leaves, which take no barrier, never were
    in one: their gradient is a sum over row chunks), and the update is
    still there under its scope."""
    (fused, under_scope), _temp = _update_in_step(topo, which, True)
    assert fused == []
    assert under_scope > 0


def test_the_gradients_barrier_is_what_keeps_the_update_out(topo):
    """The dense step without the barrier is the program the check above
    refuses (were it not, that check would hold nothing: 15 such fused
    computations at ISSUE 47), and a barrier a matrix keeps no gradient
    alive longer than it has to: the temporaries are within 1% (one
    barrier round the whole tree would hold every float32 gradient at
    once, 2.8 GB). The sparse-expert step is not compiled a second time
    for this (45 s; it too held such fusions without the barrier when
    this was written, as the Trinity cell's step does: PERF.md, PR 47)."""
    _fusions, temp = _update_in_step(topo, "dense", True)
    (fused_without, _n), temp_without = _update_in_step(topo, "dense", False)
    assert fused_without
    assert abs(temp - temp_without) <= 0.01 * temp_without, (temp, temp_without)


def test_looped_train_step_holds_a_layers_kernels_once(topo):
    """Ouro-2.6B's step at the published widths (16 query = 16 key-value
    heads of 128, the tuner's rung for the cell's mask), 2 of its layers
    and 4 passes, at the check's 4,096 tokens: the pass is one scan, so
    the compiled step holds 3 x layers flex kernels (the forward in the
    scanned pass; remat's forward and the backward in its transpose) and
    not 3 x layers x passes, and it traces, differentiates and rematerialises
    ``dist_attn_local`` inside ``scan`` + ``checkpoint`` + ``shard_map``
    for the chip's compiler as it stands."""
    import optax

    from benchmarks import masks
    from magiattention_tpu.models.pattern import (
        build_magi_pattern, init_pattern_params, ouro_config,
    )

    cfg = ouro_config(
        dict(_benchmark_json("configs", "ouro-2.6b.json"), num_hidden_layers=2),
        remat=True,
    )
    mask = masks.build_mask(
        _benchmark_json("traffic", "train-16k-packed-looped.json")["mask"],
        4096, index=0,
    )
    assert (cfg.n_loops, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads) == (
        4, 2, 16, 16
    )
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("dp", "cp"))
    model, _ = build_magi_pattern(
        cfg, mesh, mask.cu_seqlens, chunk_size=512, interpret=False
    )
    (p,) = model.attn_params.values()
    assert (p.block_q, p.block_k, p.head_block, p.grid) == (
        *_GROUP_ONE["looped"][2], "sparse"
    )
    text = _compile_train_step(
        model, lambda: init_pattern_params(jax.random.PRNGKey(0), cfg),
        optax.adamw(3e-4), 4096,
    ).as_text()
    assert text.count("tpu_custom_call") == 3 * cfg.n_layers


def test_cca_train_step_at_two_key_value_heads(topo):
    """ZAYA1-8B's step at the published widths (8 query / 2 key-value
    heads of 128, both convolutions, top-1 of 16 experts behind the MLP
    router, the tied embedding's slice), 2 of its layers, at the check's
    4,096 tokens: the flex kernels compile at two key-value heads on the
    rung the cell's 16,384-token mask gets too, the shift at cp = 1 is a
    slice (no gather under ``magi_cca_mix``), and the router's state
    crosses ``checkpoint`` inside ``shard_map``."""
    import optax

    from benchmarks import masks, trace_reduce
    from magiattention_tpu.models.pattern import (
        build_magi_pattern, init_pattern_params, zaya_config,
    )

    hf = _benchmark_json("configs", "zaya1-8b.json")
    cfg = zaya_config(
        dict(hf, num_hidden_layers=2), remat=True,
        expert_range=tuple(hf["experts_here"]), vocab_size=hf["vocab_here"],
    )
    tr = _benchmark_json("traffic", "train-16k-packed-cca.json")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("dp", "cp"))
    rungs = []
    for spec, total in ((tr["check_mask"], 4096), (tr["mask"], 16384)):
        mask = masks.build_mask(spec, total, index=0)
        model, _ = build_magi_pattern(
            cfg, mesh, mask.cu_seqlens, chunk_size=512, interpret=False
        )
        (p,) = model.attn_params.values()
        rungs.append((p.block_q, p.block_k, p.head_block, p.grid))
        if total == 4096:
            check = model
    # (128, 512, 8) until ISSUE 56: 256 is the cheaper of the pair on both
    assert rungs[0] == rungs[1] == (256, 512, 8, "sparse")
    assert check.shift_plan.fwd.offsets == (1, 2)
    assert check.shift_plan.bwd.offsets == (-1, -2)
    text = _compile_train_step(
        check, lambda: init_pattern_params(jax.random.PRNGKey(0), cfg),
        optax.adamw(3e-4), 4096,
    ).as_text()
    scopes = trace_reduce.hlo_scopes(text)
    # a layer's forward and its backward: the checkpoint keeps the call's
    # out and lse, so remat launches no forward of its own (ISSUE 48; the
    # grouped matmuls are tpu_custom_calls too: count the flex kernels by
    # name)
    flex = [n for n in scopes if n.startswith("magi_flex_")]
    assert len(flex) == 2 * cfg.n_layers, flex
    mix = [s for s in scopes.values() if "magi_cca_mix" in s]
    assert mix and not [s for s in mix if s.endswith("/gather")]
    assert any("magi_moe_router" in s for s in scopes.values())


@pytest.mark.parametrize("cp", [1, 4])
def test_keyed_kernels_carry_role_names(topo, cp, monkeypatch):
    """The keyed path's forward+backward program, as the benchmark's
    attention cells compile it: every flex kernel is an HLO instruction
    named by its role, the two roofline metrics' patterns still match
    the scopes they matched, each per-kernel metric's pattern matches
    one role, and the collectives carry the group cast's scope."""
    import json
    import re

    from benchmarks import trace_reduce
    from magiattention_tpu import api

    def pattern(metric):
        path = os.path.join(
            os.path.dirname(__file__), "..", "benchmarks", "metrics",
            metric + ".json",
        )
        with open(path) as f:
            return re.compile(json.load(f)["source"]["pattern"])

    # a described device takes no arrays: leave the plan's tables to jit
    monkeypatch.setattr(jax, "device_put", lambda x, *_a, **_k: x)
    api.clear_cache()
    mesh = Mesh(np.array(topo.devices[:cp]), ("cp",))
    sharded = NamedSharding(mesh, P("cp"))
    t, hq, hk, d = 2048 * cp, 8, 2, 128
    key = api.magi_attn_varlen_key(
        [0, 500 * cp, 1300 * cp, t], t, mesh, num_heads=(hq, hk),
        head_dim=d, out_dtype="bfloat16", interpret=False,
    )

    def fwd(q, k, v):
        out, meta = api.calc_attn(q, k, v, key)
        return out, meta.lse

    def fwdbwd(q, k, v, d_out, d_lse):
        _res, vjp = jax.vjp(fwd, q, k, v)
        return vjp((d_out, d_lse))

    text = _compile(
        fwdbwd, _on(sharded, (t, hq, d)), _on(sharded, (t, hk, d)),
        _on(sharded, (t, hk, d)), _on(sharded, (t, hq, d)),
        _on(sharded, (t, hq), jnp.float32),
    )
    api.clear_cache()
    scopes = trace_reduce.hlo_scopes(text)
    kernels = {  # the custom calls themselves, by instruction name
        name: scope for name, scope in scopes.items()
        if name.startswith("magi_flex_")
    }
    roles = sorted(name.split(".")[0] for name in kernels)
    assert roles == ["magi_flex_bwd_kernel", "magi_flex_fwd_kernel"]
    fwd_rx, bwd_rx = pattern("flex_fwd_roofline"), pattern("flex_bwd_roofline")
    new = {
        role: pattern(f"flex_{role}_kernel_ms")
        for role in ("fwd", "bwd", "dq", "dkv")  # the last two: silent now
    }
    for name, scope in kernels.items():
        line = f"{name} {scope}"  # what trace_reduce.kernel_seconds matches
        assert scope.endswith("/pallas_call")
        backward = "_fwd_" not in name
        assert bool(bwd_rx.search(line)) == backward, line
        assert bool(fwd_rx.search(line)) == (not backward), line
        hit = [role for role, rx in new.items() if rx.search(line)]
        assert hit == [name.split("_")[2]], line
    assert pattern("train_flex_kernel_share").search("magi_flex_bwd_kernel.1 ")
    # the plan's own choice of group-collective implementation (a2a at
    # this size, hops in the benchmark's cp=4 cell)
    collectives = [
        scope for name, scope in scopes.items()
        if name.startswith(("collective-permute", "all_to_all", "all-to-all"))
    ]
    assert bool(collectives) == (cp > 1)
    assert all("magi_group_cast" in scope for scope in collectives)
