"""The main path's kernels, asked of the TPU compiler without a chip.

The TPU compiler is installed with jax and compiles for a chip that is
described and not attached (``v5e:2x2``): a slice not aligned to the
tiling, too much fast memory, a kernel that cannot be partitioned are
refused here exactly as on the chip — which interpret mode never shows.
Nothing runs, so this says nothing about results or times. Skipped where
the topology cannot be described.
"""

import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs land in /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from magiattention_tpu.ops import flex_flash_attn_func
from magiattention_tpu.testing.workloads import ranges_of, varlen_block_causal


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e!r}")


@pytest.fixture(scope="module", autouse=True)
def _as_on_the_chip():
    """Compile cache off: such a compile is written to the persistent
    cache but cannot be read back without a chip, so the next run would
    warn and compile again (guide on-chip-measurement §2.3). 64-bit mode
    off: the suite's conftest turns it on for its fp64 oracles, the chip
    runs without it, and Mosaic refuses the int64 index maps it makes."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with jax.enable_x64(False):
        yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


# the build counter's labels at the cells' blockings (block_q a multiple of
# 128): the statistics cross the forward's boundary with rows along lanes
# (ISSUE 40), delta is made before the one backward kernel (ISSUE 43), and
# every q block of these masks has a key, so dq is written by its blocks'
# last visits and nothing is zero-filled (ISSUE 44)
_FORMS = {"fwd": {"stats": "compact"}, "bwd": {"delta": "xla", "dq": "visits"}}


def _compile_fwd_bwd(chip, mask, t, hq, hk, d, rung, grid, softcap=0.0) -> str:
    """The compiled text of forward + backward (a loss that reads out and
    lse) on ``mask`` = (q_ranges, k_ranges, types) at the pinned rung."""
    qr, kr, ts = mask

    def loss(q, k, v):
        out, lse = flex_flash_attn_func(
            q, k, v, qr, kr, ts, grid=grid, block_q=rung[0],
            block_k=rung[1], head_block=rung[2], softcap=softcap,
            interpret=False,
        )
        return out.astype(jnp.float32).sum() + lse.sum()

    return _compile(
        jax.value_and_grad(loss, argnums=(0, 1, 2)),
        _on(chip, (t, hq, d)), _on(chip, (t, hk, d)), _on(chip, (t, hk, d)),
    )


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("hq,hk,d", [(32, 4, 64), (32, 8, 128)])
def test_flex_fwd_bwd_16k_varlen(topo, grid, hq, hk, d):
    """Forward and both backward kernels, autotuner's own tiles."""
    t = 16384
    qr, kr, ts = ranges_of(varlen_block_causal(t))
    chip = SingleDeviceSharding(topo.devices[0])

    def loss(q, k, v):
        out, lse = flex_flash_attn_func(
            q, k, v, qr, kr, ts, grid=grid, interpret=False
        )
        return out.astype(jnp.float32).sum() + lse.sum()

    text = _compile(
        jax.value_and_grad(loss, argnums=(0, 1, 2)),
        _on(chip, (t, hq, d)), _on(chip, (t, hk, d)), _on(chip, (t, hk, d)),
    )
    assert text.count("tpu_custom_call") >= 2  # fwd, bwd


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("rung", [(128, 512, 8), (128, 128, 1)],
                         ids=["heads-batched", "per-head"])
def test_stepped_bound_at_the_block_diffusion_cells_shapes(topo, grid, rung):
    """ISSUE 42: the interval mask's block index (one ``and`` of the row
    column with a scalar ``-step``, the step read from the slice's type
    word) compiles in the forward and the backward on both grids, at the
    SDAR cell's head geometry (32 query / 4 key-value heads of 128) on a
    4,096-row ``[noisy ; clean]`` mask of three documents."""
    from magiattention_tpu.api import infer_block_diffusion_mask

    qr, kr, ts = infer_block_diffusion_mask([0, 1280, 1864, 2048], 4)
    mask = (qr.to_naive_ranges(), kr.to_naive_ranges(), [int(x) for x in ts])
    chip = SingleDeviceSharding(topo.devices[0])
    text = _compile_fwd_bwd(chip, mask, 4096, 32, 4, 128, rung, grid)
    assert text.count("tpu_custom_call") >= 2  # fwd, bwd


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize(
    "hq,hk,d,rung",
    [(64, 8, 128, (128, 512, 8)), (64, 8, 128, (1024, 1024, 1)),
     (20, 20, 256, (256, 512, 5))],
    ids=["heads-batched", "per-head", "latent-20x256"],
)
def test_zero_filled_dq_where_the_table_leaves_q_blocks_out(
    topo, hq, hk, d, rung, grid
):
    """ISSUE 44: the other form of the backward's dq output. On a mask
    whose second half of the rows has no key the k-major table names half
    the q blocks, the kernel's dq output is aliased to a zero fill in the
    inputs' dtype (one more operand in ``memory_space=ANY``), and the
    build counter says ``dq=zero_filled``: it compiles at the cells' rungs
    and head geometries as the ``visits`` form does in the tests beside
    this one."""
    from magiattention_tpu import telemetry

    t = 16384
    mask = ([(0, t // 2)], [(0, t // 2)], [1])
    chip = SingleDeviceSharding(topo.devices[0])
    reg = telemetry.get_registry()
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    reg.clear_metric("magi_flex_kernel_build_total")
    try:
        text = _compile_fwd_bwd(chip, mask, t, hq, hk, d, rung, grid)
        assert reg.counter_value(
            "magi_flex_kernel_build_total", kernel="bwd", grid=grid,
            heads_per_step=rung[2], delta="xla", dq="zero_filled",
        ) == 1
    finally:
        reg.clear_metric("magi_flex_kernel_build_total")
        telemetry.set_enabled(was)
    assert text.count("tpu_custom_call") == 2  # fwd, bwd


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize(
    "t,hq,hk,rung",
    [
        (65536, 64, 8, (128, 512, 8)),
        (16384, 32, 8, (128, 512, 8)),
        (16384, 64, 8, (256, 1024, 8)),
    ],
    ids=["varlen-cell-64x8", "train-cell-32x8", "largest-tuner-step"],
)
def test_head_batched_bwd_at_the_cells_shapes(topo, t, hq, hk, rung, grid):
    """The head-batched forward and backward, on the row-major and on the
    compact grid, at the blocking the tuner gives the benchmark's packed
    cells, (128, 512, 8) at head_dim 128: group 8 is one kv head a step,
    group 4 two (the batched transposed contraction). And at the largest
    step a row-major rung of the tuner asks for: (256, 1024, 2), whose
    head_block snaps to 8 at group 8. They fit the VMEM the kernels ask
    for, and it is the batched programs that were built, not the per-head
    fallback."""
    from magiattention_tpu import telemetry

    chip = SingleDeviceSharding(topo.devices[0])
    reg = telemetry.get_registry()
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    reg.clear_metric("magi_flex_kernel_build_total")
    try:
        text = _compile_fwd_bwd(
            chip, ranges_of(varlen_block_causal(t)), t, hq, hk, 128, rung, grid
        )
        for kernel, form in _FORMS.items():
            assert reg.counter_value(
                "magi_flex_kernel_build_total", kernel=kernel,
                heads_per_step=8, grid=grid, **form,
            ) >= 1, kernel
    finally:
        reg.clear_metric("magi_flex_kernel_build_total")
        telemetry.set_enabled(was)
    assert text.count("tpu_custom_call") == 2  # fwd, bwd


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize(
    "rung,sink,softcap",
    [((128, 512, 8), False, 0.0), ((1024, 1024, 1), False, 0.0),
     ((128, 512, 8), True, 30.0), ((1024, 1024, 1), True, 30.0)],
    ids=["packed-rung", "dense-rung", "packed-rung-sink-softcap",
         "dense-rung-sink-softcap"],
)
def test_forward_state_at_the_cells_rungs(topo, rung, sink, softcap, grid):
    """The forward alone at the two rungs the benchmark's cells run,
    (128, 512, 8) head-batched and (1024, 1024, 1) per head, 64 q / 8 kv
    heads, head_dim 128, 64k tokens. Since ISSUE 29 both bodies share one
    softmax-state update: the row-sum scratch holds per-lane partial sums
    in all its 128 lanes (static 128-lane slices of the probability tile)
    and is reduced across lanes when a q block is written, and the mask
    select writes a finite value. The scratch shapes are what they were
    ((rows, 128), (rows, 128), (rows, head_dim), float32); this asks the
    chip's compiler whether it still takes the new use of them, with and
    without the sink and softcap paths of the finalize."""
    t, hq, hk, d = 65536, 64, 8, 128
    qr, kr, ts = ranges_of(varlen_block_causal(t))
    chip = SingleDeviceSharding(topo.devices[0])

    def fwd(q, k, v, s):
        return flex_flash_attn_func(
            q, k, v, qr, kr, ts, grid=grid, block_q=rung[0],
            block_k=rung[1], head_block=rung[2], softcap=softcap,
            sink=s if sink else None, return_max_logits=True,
            interpret=False,
        )

    text = _compile(
        fwd, _on(chip, (t, hq, d)), _on(chip, (t, hk, d)),
        _on(chip, (t, hk, d)), _on(chip, (hq,), jnp.float32),
    )
    assert text.count("tpu_custom_call") == 1


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("softcap", [0.0, 30.0], ids=["nocap", "softcap"])
@pytest.mark.parametrize(
    "t,hq,hk,d,rung",
    [(65536, 64, 8, 128, (128, 512, 8)), (65536, 64, 8, 128, (1024, 1024, 1)),
     (16384, 20, 20, 256, (128, 512, 5)), (16384, 20, 20, 256, (256, 512, 5)),
     (16384, 16, 16, 128, (256, 512, 8))],
    ids=["packed-rung", "dense-rung", "latent-rung-pr30", "latent-rung",
         "looped-rung"],
)
def test_backward_block_at_the_cells_rungs(topo, t, hq, hk, d, rung, softcap, grid):
    """The backward at the rungs the benchmark's cells run: head-batched
    (128, 512, 8) and per head (1024, 1024, 1) at 64 q / 8 kv heads of
    width 128, and at GQA group 1 head-batched (256, 512, 5) at 20 / 20
    heads of width 256 and (256, 512, 8) at 16 / 16 of 128 (ISSUE 35; the
    GLM cell's rung before it, (128, 512, 5), beside them).
    Since ISSUE 31 the one block all four backward bodies share takes lse
    and delta at the (rows, 128) shape their blocks arrive in and the
    (rows, block_k) tiles s and dP in static 128-lane slices, with the
    softcap derivative on the whole tile after: this asks the chip's
    compiler whether it takes those slices and the concatenations at
    every body's row stacking, with and without softcap, in the VMEM the
    kernels ask for."""
    text = _compile_fwd_bwd(
        SingleDeviceSharding(topo.devices[0]),
        ranges_of(varlen_block_causal(t)), t, hq, hk, d, rung, grid, softcap,
    )
    assert text.count("tpu_custom_call") == 2  # fwd, bwd


def _glm_cell_mask(t):
    """The GLM-4.7-Flash cell's packed mask at ``t`` tokens (16,384 in
    the window, 4,096 in the check), as the benchmark builds it."""
    import json

    from benchmarks import masks

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
        here, "benchmarks", "traffic", "train-16k-packed-mla.json"
    )) as f:
        return masks.build_mask(json.load(f)["mask"], t, index=0)


def _tuners_rung(mask, hq, hk, d):
    from magiattention_tpu.tuning.autotuner import resolve_block_config

    return resolve_block_config(
        [list(r) for r in mask.q_ranges], [list(r) for r in mask.k_ranges],
        tuple(mask.types), mask.total, mask.total, 1, hq, hk, d, "bfloat16",
    )


# GQA group 1 at the kernels: (query = key-value heads, head_dim, the rung
# the tuner gives the 16k packed mask since ISSUE 35)
_GROUP_ONE = {
    "latent": (20, 256, (256, 512, 5)),  # GLM-4.7-Flash after up-projection
    "looped": (16, 128, (256, 512, 8)),  # Ouro-2.6B
}


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize(
    "geometry,t,rung",
    [("latent", 16384, None), ("latent", 4096, None),
     ("latent", 16384, (128, 512, 5)), ("latent", 16384, (256, 512, 4)),
     ("latent", 16384, (256, 1024, 2)), ("latent", 16384, (512, 768, 4)),
     ("latent", 16384, (1024, 1024, 1)), ("latent", 16384, (512, 2048, 1)),
     ("looped", 16384, None), ("looped", 4096, None),
     ("looped", 16384, (128, 512, 8)), ("looped", 16384, (256, 512, 4)),
     ("looped", 16384, (256, 1024, 2)), ("looped", 16384, (1024, 1024, 1))],
    ids=["tuner-16k", "tuner-4k-check", "pr30-rung", "four-heads-a-step",
         "widest-row-major-step", "widest-compact-step", "per-head-long-rung",
         "escalation-rung", "looped-tuner-16k", "looped-tuner-4k-check",
         "looped-pr32-rung", "looped-four-heads-a-step",
         "looped-widest-row-major-step", "looped-per-head-long-rung"],
)
def test_group_one_geometries(topo, geometry, t, rung, grid):
    """Forward and backward at GQA group 1, where a step brings as many
    key-value heads' tiles as it has query heads, on both grids: what
    latent attention hands the kernels after its up-projection
    (GLM-4.7-Flash: 20 query = 20 key-value heads of 256; no power of two,
    so eight heads a step snap to five) and a plain MHA decoder (Ouro-2.6B:
    16 = 16 of 128). The rung the tuner gives the cells' packed mask at the
    window's 16k and at the check's 4k, (256, 512, 8) snapped to the
    geometry since ISSUE 35 priced the bytes a step streams; the rung it
    gave before; and every other step its table holds for the geometry, so
    that whatever a mask makes it return compiles. At head_dim 256 K, V, dO
    and the accumulators are twice head_dim 128's; they fit the VMEM the
    kernels ask for, and the batched programs are the ones built."""
    from magiattention_tpu import telemetry

    hq, d, tuned = _GROUP_ONE[geometry]
    hk = hq
    mask = _glm_cell_mask(t)  # the Ouro cell's mask too
    if rung is None:
        rung = _tuners_rung(mask, hq, hk, d)
        assert rung == tuned
    qr, kr = [list(r) for r in mask.q_ranges], [list(r) for r in mask.k_ranges]
    chip = SingleDeviceSharding(topo.devices[0])
    reg = telemetry.get_registry()
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    reg.clear_metric("magi_flex_kernel_build_total")
    try:
        text = _compile_fwd_bwd(
            chip, (qr, kr, list(mask.types)), t, hq, hk, d, rung, grid
        )
        for kernel, form in _FORMS.items():
            assert reg.counter_value(
                "magi_flex_kernel_build_total", kernel=kernel,
                heads_per_step=rung[2], grid=grid, **form,
            ) >= 1, kernel
    finally:
        reg.clear_metric("magi_flex_kernel_build_total")
        telemetry.set_enabled(was)
    assert text.count("tpu_custom_call") == 2  # fwd, bwd


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
    opt = optax.adamw(1e-4)
    rep = NamedSharding(mesh, P())
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    state = jax.eval_shape(opt.init, params)
    params, state = jax.tree.map(
        lambda s: _on(rep, s.shape, s.dtype), (params, state)
    )
    batch = _on(NamedSharding(mesh, P("dp", "cp")), (1, total), jnp.int32)
    text = (
        model.make_train_step(opt)
        .lower(params, state, batch, batch, batch)
        .compile()
        .as_text()
    )
    assert "tpu_custom_call" in text
    assert "all-to-all" in text or "collective-permute" in text


def test_looped_train_step_holds_a_layers_kernels_once(topo):
    """Ouro-2.6B's step at the published widths (16 query = 16 key-value
    heads of 128, the tuner's rung for the cell's mask), 2 of its layers
    and 4 passes, at the check's 4,096 tokens: the pass is one scan, so
    the compiled step holds 3 x layers flex kernels (the forward in the
    scanned pass; remat's forward and the backward in its transpose) and
    not 3 x layers x passes, and it traces, differentiates and rematerialises
    ``dist_attn_local`` inside ``scan`` + ``checkpoint`` + ``shard_map``
    for the chip's compiler as it stands."""
    import json

    import optax

    from benchmarks import masks
    from magiattention_tpu.models.pattern import (
        build_magi_pattern, init_pattern_params, ouro_config,
    )

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs", "ouro-2.6b.json")) as f:
        cfg = ouro_config(
            dict(json.load(f), num_hidden_layers=2), remat=True
        )
    with open(os.path.join(
        here, "benchmarks", "traffic", "train-16k-packed-looped.json"
    )) as f:
        mask = masks.build_mask(json.load(f)["mask"], 4096, index=0)
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
    opt = optax.adamw(3e-4)
    rep = NamedSharding(mesh, P())
    params = jax.eval_shape(
        lambda: init_pattern_params(jax.random.PRNGKey(0), cfg)
    )
    state = jax.eval_shape(opt.init, params)
    params, state = jax.tree.map(
        lambda s: _on(rep, s.shape, s.dtype), (params, state)
    )
    batch = _on(NamedSharding(mesh, P("dp", "cp")), (1, 4096), jnp.int32)
    text = (
        model.make_train_step(opt)
        .lower(params, state, batch, batch, batch)
        .compile()
        .as_text()
    )
    assert text.count("tpu_custom_call") == 3 * cfg.n_layers


def test_cca_train_step_at_two_key_value_heads(topo):
    """ZAYA1-8B's step at the published widths (8 query / 2 key-value
    heads of 128, both convolutions, top-1 of 16 experts behind the MLP
    router, the tied embedding's slice), 2 of its layers, at the check's
    4,096 tokens: the flex kernels compile at two key-value heads on the
    rung the cell's 16,384-token mask gets too, the shift at cp = 1 is a
    slice (no gather under ``magi_cca_mix``), and the router's state
    crosses ``checkpoint`` inside ``shard_map``."""
    import json

    import optax

    from benchmarks import masks, trace_reduce
    from magiattention_tpu.models.pattern import (
        build_magi_pattern, init_pattern_params, zaya_config,
    )

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs", "zaya1-8b.json")) as f:
        hf = json.load(f)
    cfg = zaya_config(
        dict(hf, num_hidden_layers=2), remat=True,
        expert_range=tuple(hf["experts_here"]), vocab_size=hf["vocab_here"],
    )
    with open(os.path.join(
        here, "benchmarks", "traffic", "train-16k-packed-cca.json"
    )) as f:
        tr = json.load(f)
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
    assert rungs[0] == rungs[1] == (128, 512, 8, "sparse")
    assert check.shift_plan.fwd.offsets == (1, 2)
    assert check.shift_plan.bwd.offsets == (-1, -2)
    opt = optax.adamw(3e-4)
    rep = NamedSharding(mesh, P())
    params = jax.eval_shape(
        lambda: init_pattern_params(jax.random.PRNGKey(0), cfg)
    )
    state = jax.eval_shape(opt.init, params)
    params, state = jax.tree.map(
        lambda s: _on(rep, s.shape, s.dtype), (params, state)
    )
    batch = _on(NamedSharding(mesh, P("dp", "cp")), (1, 4096), jnp.int32)
    text = (
        check.make_train_step(opt)
        .lower(params, state, batch, batch, batch)
        .compile()
        .as_text()
    )
    scopes = trace_reduce.hlo_scopes(text)
    # a layer's forward, remat's forward and the backward (the grouped
    # matmuls are tpu_custom_calls too: count the flex kernels by name)
    flex = [n for n in scopes if n.startswith("magi_flex_")]
    assert len(flex) == 3 * cfg.n_layers, flex
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
