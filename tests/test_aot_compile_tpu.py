"""The main path's kernels, asked of the TPU compiler without a chip.

The TPU compiler is installed with jax and compiles for a chip that is
described and not attached (``v5e:2x2``): a slice not aligned to the
tiling, too much fast memory, a kernel that cannot be partitioned are
refused here exactly as on the chip — which interpret mode never shows.
Nothing runs, so this says nothing about results or times. Skipped where
the topology cannot be described. This file: the flex kernels at the
cells' shapes and rungs; whole serve and train steps:
``tests/test_aot_train_steps_tpu.py``.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from magiattention_tpu.ops import flex_flash_attn_func
from magiattention_tpu.testing.workloads import ranges_of, varlen_block_causal

from .aot import _GROUP_ONE, _as_on_the_chip, _compile, _on, topo  # noqa: F401


# the build counter's labels at the cells' blockings (block_q a multiple of
# 128): the statistics cross both kernels' boundaries with rows along lanes
# (ISSUE 40 the forward's, ISSUE 58 the backward's), delta is made before
# the one backward kernel (ISSUE 43), and every q block of these masks has a
# key, so dq is written by its blocks' last visits and nothing is
# zero-filled (ISSUE 44)
_FORMS = {
    "fwd": {"stats": "compact"},
    "bwd": {"stats": "compact", "delta": "xla", "dq": "visits"},
}


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
            heads_per_step=rung[2], stats="compact", delta="xla",
            dq="zero_filled",
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
        (65536, 64, 8, (256, 512, 8)),
        (16384, 32, 8, (256, 512, 8)),
    ],
    ids=["varlen-cell-64x8", "train-cell-32x8", "largest-tuner-step",
         "varlen-cell-64x8-at-256", "train-cell-32x8-at-256"],
)
def test_head_batched_bwd_at_the_cells_shapes(topo, t, hq, hk, rung, grid):
    """The head-batched forward and backward, on the row-major and on the
    compact grid, at the blocking the tuner gives the benchmark's packed
    cells, (128, 512, 8) at head_dim 128 and, where the pair's price puts
    it ahead (ISSUE 56: the packed 64k cell, six training cells),
    (256, 512, 8): group 8 is one kv head a step,
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


def _smallthinker_mask(which: str, kind: str):
    """(q_ranges, k_ranges, types, rows) of the SmallThinker cell's mask
    (``which`` "mask": the window's 16,384 rows; "check_mask": the check's
    8,192) as ``build_magi_pattern`` makes it for a layer ``kind``: the
    documents' causal mask, or the same under the published window."""
    import json

    from magiattention_tpu.api.functools import infer_attn_mask_from_cu_seqlens

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
        here, "benchmarks", "traffic", "train-16k-packed-prerouted.json"
    )) as f:
        lengths = json.load(f)[which]["lengths"]
    with open(os.path.join(
        here, "benchmarks", "configs", "smallthinker-21b-a3b.json"
    )) as f:
        window = json.load(f)["sliding_window_size"]
    cu = [0]
    for n in lengths:
        cu.append(cu[-1] + n)
    if kind == "full":
        q, k, t = infer_attn_mask_from_cu_seqlens(cu, causal=True)
    else:
        q, k, t = infer_attn_mask_from_cu_seqlens(
            cu, causal=False, window_size=(window - 1, 0)
        )
    return q.to_naive_ranges(), k.to_naive_ranges(), [int(x) for x in t], cu[-1]


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize(
    "which,kind,rung",
    [("mask", "full", None), ("mask", "window", None),
     ("check_mask", "full", None), ("check_mask", "window", None),
     ("mask", "full", (128, 512, 8)), ("mask", "full", (256, 512, 8)),
     ("mask", "window", (256, 1024, 2)), ("mask", "full", (512, 768, 4)),
     ("mask", "window", (1024, 1024, 1)), ("mask", "full", (512, 2048, 1))],
    ids=["tuner-16k-full", "tuner-16k-window", "tuner-8k-check-full",
         "tuner-8k-check-window", "seven-heads-a-step", "block-q-256",
         "widest-row-major-step", "widest-compact-step",
         "per-head-long-rung", "escalation-rung"],
)
def test_group_seven_geometry(topo, which, kind, rung, grid):
    """Forward and backward at 28 query / 4 key-value heads of 128
    (SmallThinker, ISSUE 53): the first GQA group that is no power of two,
    so every head-batched rung snaps to 7 heads a step and a key-value
    head's step stacks 7 x ``block_q`` query rows (896, 1,792, 3,584)
    where every shape compiled before stacked 1, 2, 4 or 8. On both grids,
    on the cell's own masks (documents 10,240 / 4,096 / 1,536 / 512 and
    the check's 6,144 / 1,536 / 512, each as the full layers and as the
    window-4,096 layers see them): at the rung the tuner returns for each
    of the four plans, and at every other step its table holds for the
    geometry, so that whatever a mask makes it return compiles. The
    forward builds at the rung's heads a step; the backward at what
    ``_bwd_head_block`` allows it (one head a step past 32 MiB of live
    float32 tiles: 7 x 512 rows)."""
    from magiattention_tpu import telemetry
    from magiattention_tpu.ops.flex_attn import (
        _BWD_HB_LIVE_BYTES, _auto_head_block,
    )
    from magiattention_tpu.tuning.autotuner import resolve_block_config

    hq, hk, d = 28, 4, 128
    qr, kr, ts, t = _smallthinker_mask(which, kind)
    if rung is None:
        rung = resolve_block_config(
            qr, kr, tuple(ts), t, t, 1, hq, hk, d, "bfloat16"
        )
    rung = (*rung[:2], _auto_head_block(rung[2], hq, hq // hk))
    assert rung[2] in (1, 7)
    fits = 4 * 4 * rung[2] * rung[0] * rung[1] <= _BWD_HB_LIVE_BYTES
    heads = {"fwd": rung[2], "bwd": rung[2] if fits else 1}
    chip = SingleDeviceSharding(topo.devices[0])
    reg = telemetry.get_registry()
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    reg.clear_metric("magi_flex_kernel_build_total")
    try:
        text = _compile_fwd_bwd(chip, (qr, kr, ts), t, hq, hk, d, rung, grid)
        for kernel, form in _FORMS.items():
            assert reg.counter_value(
                "magi_flex_kernel_build_total", kernel=kernel,
                heads_per_step=heads[kernel], grid=grid, **form,
            ) >= 1, (kernel, rung)
    finally:
        reg.clear_metric("magi_flex_kernel_build_total")
        telemetry.set_enabled(was)
    assert text.count("tpu_custom_call") == 2  # fwd, bwd


@pytest.mark.parametrize("chunk,block", [(128, 1024), (256, 512)])
def test_selective_scan_at_the_sambay_cells_size(topo, chunk, block):
    """The selective scan's kernel pair (ISSUE 46) at the Phi-4-mini-flash
    cell's 16,384 rows of 5,120 channels and 16 states, operands in the
    cell's dtypes: the token loop's 8-row reads (a dynamic row index the
    chip's compiler takes only as a multiple of 8), the chunk's states in
    VMEM and the lane sums on the MXU compile; interpret mode shows none
    of the three."""
    from magiattention_tpu.ops.selective_scan import selective_scan

    chip = SingleDeviceSharding(topo.devices[0])
    t, ch, n = 16384, 5120, 16
    args = (
        _on(chip, (t, ch)), _on(chip, (t, ch), jnp.float32),
        _on(chip, (ch, n), jnp.float32), _on(chip, (t, n)), _on(chip, (t, n)),
        _on(chip, (ch,), jnp.float32), _on(chip, (t,), jnp.bool_),
    )

    def loss(u, delta, a, b, c, d, start):
        y = selective_scan(
            u, delta, a, b, c, d, start, chunk=chunk, channel_block=block,
            interpret=False,
        )
        return y.astype(jnp.float32).sum()

    text = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5)), *args)
    for kernel in ("magi_ssm_scan_fwd_kernel", "magi_ssm_scan_bwd_kernel"):
        assert kernel in text, kernel


def _granite_mask(which: str):
    """(q_ranges, k_ranges, types, rows) of the granite-4.0-h-micro cell's
    documents (``which`` "mask": the window's 16,384 rows; "check_mask":
    the check's 4,096), causal inside each, as ``build_magi_pattern``
    makes it for the attention layer."""
    import json

    from magiattention_tpu.api.functools import infer_attn_mask_from_cu_seqlens

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
        here, "benchmarks", "traffic", "train-16k-packed-ssd.json"
    )) as f:
        lengths = json.load(f)[which]["lengths"]
    cu = [0]
    for n in lengths:
        cu.append(cu[-1] + n)
    q, k, t = infer_attn_mask_from_cu_seqlens(cu, causal=True)
    return q.to_naive_ranges(), k.to_naive_ranges(), [int(x) for x in t], cu[-1]


def _every_rung():
    """(id, mask, rung, grid): every step of the tuner's table on the
    cell's mask, the row-major rungs on both grids and the sparse-only
    ones on theirs; the tuner's own choice on the cell's mask and the
    check's (rung None)."""
    from magiattention_tpu.ops.flex_attn import _AUTO_BLOCK_CONFIGS
    from magiattention_tpu.tuning.cost_model import SPARSE_ONLY_CONFIGS

    cases = [
        (f"tuner-{which}-{grid}", which, None, grid)
        for which in ("mask", "check_mask") for grid in ("row_major", "sparse")
    ]
    cases += [
        ("x".join(map(str, rung)) + "-" + grid, "mask", rung, grid)
        for rung in _AUTO_BLOCK_CONFIGS for grid in ("row_major", "sparse")
    ]
    cases += [
        ("x".join(map(str, rung)) + "-sparse-only", "mask", rung, "sparse")
        for rung in SPARSE_ONLY_CONFIGS if rung not in _AUTO_BLOCK_CONFIGS
    ]
    return cases


@pytest.mark.parametrize(
    "which,rung,grid", [c[1:] for c in _every_rung()],
    ids=[c[0] for c in _every_rung()],
)
def test_group_four_at_heads_of_64(topo, which, rung, grid):
    """Forward and backward at 32 query / 8 key-value heads of 64
    (granite-4.0-h-micro, ISSUE 55): the first plan whose q, k AND v are
    64 wide (phi4's 64-wide q and k ride 128-wide kernel heads beside a
    128-wide value pair), under the published softmax scale 1/64. Every
    rung of the tuner's table compiles for a v5e at that width on both
    grids, on the cell's own masks, so the model takes the width as it is:
    no zero lanes, ``magi_flex_pad_lane_share`` is not set."""
    from magiattention_tpu.ops.flex_attn import _auto_head_block
    from magiattention_tpu.tuning.autotuner import resolve_block_config

    hq, hk, d = 32, 8, 64
    qr, kr, ts, t = _granite_mask(which)
    if rung is None:
        rung = resolve_block_config(
            qr, kr, tuple(ts), t, t, 1, hq, hk, d, "bfloat16"
        )
    rung = (*rung[:2], _auto_head_block(rung[2], hq, hq // hk))
    chip = SingleDeviceSharding(topo.devices[0])

    def loss(q, k, v):
        out, lse = flex_flash_attn_func(
            q, k, v, qr, kr, ts, grid=grid, block_q=rung[0],
            block_k=rung[1], head_block=rung[2], scale=0.015625,
            interpret=False,
        )
        return out.astype(jnp.float32).sum() + lse.sum()

    text = _compile(
        jax.value_and_grad(loss, argnums=(0, 1, 2)),
        _on(chip, (t, hq, d)), _on(chip, (t, hk, d)), _on(chip, (t, hk, d)),
    )
    assert text.count("tpu_custom_call") == 2  # fwd, bwd


@pytest.mark.parametrize("rows,dtype", [
    (16384, jnp.bfloat16), (4096, jnp.bfloat16), (4096, jnp.float32),
], ids=["cell", "check", "scan-alone-float32"])
def test_ssd_scan_at_the_granite_cells_size(topo, rows, dtype):
    """The state-space-dual scan's kernel pair (ISSUE 55) at 64 heads of
    64 channels and 128 states in chunks of 256: the cell's 16,384 rows
    and the check's 4,096 in the cell's dtypes, and the check's scan
    alone on float32 operands (every product at the highest precision).
    What interpret mode shows none of: two 64-wide heads sharing a
    128-lane tile, a head's column of the [chunk, heads] vectors taken by
    a masked lane sum, its row by a traced sublane index, the products
    with a transposed left operand in the backward, the carried state of
    all head blocks in VMEM scratch."""
    from magiattention_tpu.ops.ssd_scan import ssd_scan

    chip = SingleDeviceSharding(topo.devices[0])
    h, p, n = 64, 64, 128
    f32 = jnp.float32
    args = (
        _on(chip, (rows, h, p), dtype), _on(chip, (rows, h), f32),
        _on(chip, (h,), f32), _on(chip, (rows, n), dtype),
        _on(chip, (rows, n), dtype), _on(chip, (h,), f32),
        _on(chip, (rows,), jnp.bool_),
    )

    def loss(x, delta, a, b, c, d, start):
        y = ssd_scan(x, delta, a, b, c, d, start, chunk=256, interpret=False)
        return y.astype(f32).sum()

    text = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5)), *args)
    for kernel in ("magi_ssd_scan_fwd_kernel", "magi_ssd_scan_bwd_kernel"):
        assert kernel in text, kernel


def _xing_cell():
    """(configuration, traffic) of ``xing4-train-8k-traces``."""
    import json

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def load(*parts):
        with open(os.path.join(here, "benchmarks", *parts)) as f:
            return json.load(f)

    return (
        load("configs", "xing4.0-29b-a4b.json"),
        load("traffic", "train-8k-packed-mhc.json"),
    )


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("d", [256, 192], ids=["keys-on-256", "keys-at-192"])
def test_a_value_width_of_its_own_at_the_mhc_cells_size(topo, d, grid):
    """Forward and backward with keys wider than values (ISSUE 49): 32
    query = 32 key-value heads, q and k at 192 as they are (what the cell
    runs: a block of one and a half vregs, 0.8% faster on the chip) and on
    256 lanes (zeros in the last 64), v of 128, on the cell's 8,192-token mask at
    the rung the tuner gives it. The build counter says which widths the
    kernels were built at."""
    from benchmarks import masks
    from magiattention_tpu import telemetry

    _cfg, tr = _xing_cell()
    t = int(tr["total_tokens"])
    mask = masks.build_mask(tr["mask"], t, index=0)
    qr, kr = [list(r) for r in mask.q_ranges], [list(r) for r in mask.k_ranges]
    rung = (256, 512, 8)
    chip = SingleDeviceSharding(topo.devices[0])

    def loss(q, k, v):
        out, lse = flex_flash_attn_func(
            q, k, v, qr, kr, list(mask.types), grid=grid, block_q=rung[0],
            block_k=rung[1], head_block=rung[2], interpret=False,
        )
        assert out.shape == (t, 32, 128)
        return out.astype(jnp.float32).sum() + lse.sum()

    reg = telemetry.get_registry()
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    reg.clear_metric("magi_flex_kernel_build_total")
    try:
        text = _compile(
            jax.value_and_grad(loss, argnums=(0, 1, 2)),
            _on(chip, (t, 32, d)), _on(chip, (t, 32, d)),
            _on(chip, (t, 32, 128)),
        )
        for kernel, form in _FORMS.items():
            assert reg.counter_value(
                "magi_flex_kernel_build_total", kernel=kernel,
                heads_per_step=rung[2], grid=grid, v_head_dim="128", **form,
            ) == 1, kernel
    finally:
        reg.clear_metric("magi_flex_kernel_build_total")
        telemetry.set_enabled(was)
    assert text.count("tpu_custom_call") == 2  # fwd, bwd


def test_the_coefficients_product_of_a_bf16_state_at_the_mhc_cells_size(topo):
    """ISSUE 52: ``_mhc_normed`` and its backward rule on the cell's
    8,192 x 14,336 bfloat16 state: six convolutions, the forward's alone at
    ``highest``, ``d
    phi``'s a stream each and ``dx``'s one a bf16 x bf16 pass, the state's
    cotangent an output in bfloat16, and no temporary of the size of ONE
    float32 copy of the state: ``dx``'s float32 sum with the norm's term
    lives inside its fusion."""
    from magiattention_tpu.models.pattern import _mhc_normed

    t, width, k = 8192, 4 * 3584, 24
    chip = SingleDeviceSharding(topo.devices[0])

    def both(x, phi, dm):
        m, vjp = jax.vjp(
            lambda x, phi: _mhc_normed(x, phi, 1e-6, jnp.dtype("float32"), 4),
            x, phi,
        )
        return m, vjp(dm)

    exe = jax.jit(both).lower(
        _on(chip, (t, width)), _on(chip, (width, k), jnp.float32),
        _on(chip, (k, t), jnp.float32),
    ).compile()
    text = exe.as_text()
    products = [line for line in text.splitlines() if " convolution(" in line]
    assert len(products) == 6, products
    at_highest = [line for line in products if "highest" in line]
    assert len(at_highest) == 1 and f"f32[{k},{t}]" in at_highest[0]
    results = text.split("ENTRY")[1].split("\n")[0].split("->")[1]
    assert f"bf16[{t},{width}]" in results and f"f32[{t},{width}]" not in results
    assert exe.memory_analysis().temp_size_in_bytes < t * width * 4


def test_the_mhc_cells_step_fits_one_chip(topo):
    """``xing4-train-8k-traces``'s whole AdamW step at the published
    widths, the cell's five layers, 8 of 64 experts and an eighth of the
    vocabulary, on its 8,192-token mask: arguments and temporaries stay
    under the 17.18 x 10^9 bytes one v5e holds (14.16 x 10^9 when this was
    written: 9.11 of float32 weights and AdamW's moments, 5.05 of
    gradients, the four-stream states kept at the layers' boundaries and
    one layer's recomputation), and the tuner's rung is the pinned one."""
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks import masks
    from benchmarks.kinds import train_mhc
    from magiattention_tpu.models.pattern import init_pattern_params

    cfg, tr = _xing_cell()
    total = int(tr["total_tokens"])
    with pytest.MonkeyPatch.context() as patch:
        # the kind's Job passes no interpret=: the kernels ask the backend
        patch.setattr(jax, "default_backend", lambda: "tpu")
        job = train_mhc.Job(cfg, tr, 0, topo.devices[:1])
        model, _meta = job.build(masks.build_mask(tr["mask"], total, index=0))
        (params,) = model.attn_params.values()
        assert (params.block_q, params.block_k, params.head_block) == (256, 512, 8)
        assert not params.interpret
        opt = optax.adamw(float(tr["learning_rate"]))
        held = NamedSharding(job.mesh, P())
        weights = jax.eval_shape(
            lambda r: init_pattern_params(r, job.pcfg), jax.random.PRNGKey(0)
        )

        def on_chip(tree):
            return jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=held),
                tree,
            )

        batch = jax.ShapeDtypeStruct(
            (1, total), jnp.int32, sharding=NamedSharding(job.mesh, P("dp", "cp"))
        )
        exe = model.make_train_step(opt).lower(
            on_chip(weights), on_chip(jax.eval_shape(opt.init, weights)),
            batch, batch, batch,
        ).compile()
    mem = exe.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 17.18e9
    # a layer's forward kernel once (its out and lse are kept), its
    # backward once: 5 layers (the grouped matmuls are custom calls too)
    calls = [
        line for line in exe.as_text().splitlines()
        if "custom-call(" in line and "tpu_custom_call" in line
    ]
    for kernel in ("magi_flex_fwd_kernel", "magi_flex_bwd_kernel"):
        assert sum(kernel in line for line in calls) == 5, kernel
