"""Every flex kernel carries its role's name (ISSUE 24): the lowered
text of a forward+backward holds the two names (three before ISSUE 43
made dq and dkv one kernel), whatever the grid kind and the head block. (The scopes and instruction names of the
compiled TPU program: tests/test_aot_compile_tpu.py.)"""

import re

import jax
import jax.numpy as jnp
import pytest

from magiattention_tpu.ops import flex_flash_attn_func
from magiattention_tpu.testing.workloads import ranges_of, varlen_block_causal

NAMES = {"magi_flex_fwd_kernel", "magi_flex_bwd_kernel"}


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize("head_block", [1, 2])
def test_lowered_fwd_bwd_holds_the_kernel_names(grid, head_block):
    t, hq, hk, d = 512, 4, 2, 64
    qr, kr, ts = ranges_of(varlen_block_causal(t))

    def loss(q, k, v):
        out, lse = flex_flash_attn_func(
            q, k, v, qr, kr, ts, grid=grid, head_block=head_block,
            block_q=128, block_k=128, interpret=True,
        )
        return out.astype(jnp.float32).sum() + lse.sum()

    q = jnp.ones((t, hq, d), jnp.float32)
    kv = jnp.ones((t, hk, d), jnp.float32)
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv)
    text = lowered.as_text(debug_info=True)
    assert set(re.findall(r"magi_flex_\w+_kernel", text)) == NAMES
    # all of them match the roofline metrics' kernel pattern
    assert all(re.fullmatch(r"magi_\w*kernel", name) for name in NAMES)


def _pallas_calls(jaxpr):
    """(name, grid) of every pallas_call in a jaxpr, nested ones too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grid = tuple(eqn.params["grid_mapping"].grid)
            found.append((eqn.params["name"], grid))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_calls(sub)
    return found


def _grad_fn(t, head_block, block_q, block_k, grid="row_major"):
    qr, kr, ts = ranges_of(varlen_block_causal(t))

    def loss(q, k, v):
        out, lse = flex_flash_attn_func(
            q, k, v, qr, kr, ts, grid=grid, head_block=head_block,
            block_q=block_q, block_k=block_k, interpret=True,
        )
        return out.astype(jnp.float32).sum() + lse.sum()

    return jax.grad(loss, argnums=(0, 1, 2))


@pytest.mark.parametrize("hq,hk,head_block", [(8, 2, 4), (8, 2, 8), (4, 4, 2)])
def test_head_block_is_the_leading_grid_dimension_of_both(
    hq, hk, head_block
):
    """At head_block > 1 the row-major backward takes head_block q heads a
    grid step, as the forward does: one call of each name, and
    hq // head_block resp. hk // (head_block // group) leading."""
    t, d = 512, 64
    q = jnp.ones((t, hq, d), jnp.float32)
    kv = jnp.ones((t, hk, d), jnp.float32)
    jaxpr = jax.make_jaxpr(_grad_fn(t, head_block, 128, 128))(q, kv, kv)
    grids = dict(_pallas_calls(jaxpr.jaxpr))
    assert set(grids) == NAMES
    assert len(_pallas_calls(jaxpr.jaxpr)) == 2
    group = hq // hk
    nq = nk = t // 128
    assert grids["magi_flex_fwd_kernel"][:2] == (hq // head_block, nq)
    assert len(grids["magi_flex_fwd_kernel"]) == 3
    assert grids["magi_flex_bwd_kernel"][:2] == (
        hk // (head_block // group), nk,
    )
    assert len(grids["magi_flex_bwd_kernel"]) == 3  # the group is in the step


@pytest.mark.parametrize("hq,hk,head_block", [(8, 2, 4), (8, 2, 8), (4, 4, 2)])
def test_compact_grid_is_head_groups_by_entries_for_both(
    hq, hk, head_block
):
    """On the compact grid the two head-batched kernels launch one step
    an entry of their table and nothing else: (hq // head_block, E) for
    the forward, (hk // (head_block // group), E2) for the backward."""
    from magiattention_tpu.ops import build_block_meta

    t, d = 512, 64
    qr, kr, ts = ranges_of(varlen_block_causal(t))
    meta = build_block_meta(qr, kr, ts, t, t, block_q=128, block_k=128)
    q = jnp.ones((t, hq, d), jnp.float32)
    kv = jnp.ones((t, hk, d), jnp.float32)
    jaxpr = jax.make_jaxpr(_grad_fn(t, head_block, 128, 128, "sparse"))(
        q, kv, kv
    )
    assert len(_pallas_calls(jaxpr.jaxpr)) == 2
    group = hq // hk
    assert dict(_pallas_calls(jaxpr.jaxpr)) == {
        "magi_flex_fwd_kernel": (hq // head_block, meta.num_fwd_entries),
        "magi_flex_bwd_kernel": (
            hk // (head_block // group), meta.num_bwd_entries,
        ),
    }


def _builds():
    from magiattention_tpu import telemetry

    return {
        key: int(val)
        for key, val in telemetry.get_registry().snapshot()["counters"].items()
        if key.startswith("magi_flex_kernel_build_total")
    }


@pytest.fixture
def telemetry_on():
    from magiattention_tpu import telemetry

    was = telemetry.enabled()
    telemetry.set_enabled(True)
    telemetry.get_registry().clear_metric("magi_flex_kernel_build_total")
    yield
    telemetry.get_registry().clear_metric("magi_flex_kernel_build_total")
    telemetry.set_enabled(was)


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
@pytest.mark.parametrize(
    "head_block,block,bwd_heads",
    [
        (1, (128, 128), 1),
        (4, (128, 128), 4),
        # four f32 (4*2048, 2048) intermediates are 256 MiB: past what the
        # kernels ask of VMEM, so the backward stays per head (by shapes)
        (4, (2048, 2048), 1),
    ],
)
def test_build_counter_carries_heads_per_step_and_grid(
    telemetry_on, head_block, block, bwd_heads, grid
):
    t, hq, hk, d = 4096, 8, 2, 64
    q = jnp.ones((t, hq, d), jnp.float32)
    kv = jnp.ones((t, hk, d), jnp.float32)
    jax.make_jaxpr(_grad_fn(t, head_block, *block, grid))(q, kv, kv)
    name = "magi_flex_kernel_build_total"
    assert _builds() == {
        f"{name}{{grid={grid},heads_per_step={head_block},kernel=fwd,"
        "stats=compact}": 1,
        # (ISSUE 44: the dense causal table names every q block)
        f"{name}{{delta=xla,dq=visits,grid={grid},"
        f"heads_per_step={bwd_heads},kernel=bwd,stats=compact}}": 1,
    }


@pytest.mark.parametrize("grid", ["row_major", "sparse"])
def test_build_counter_says_how_the_statistics_cross_the_backward(
    telemetry_on, grid
):
    """ISSUE 58: the backward's build carries the label the forward's has.
    On the rung the tuner itself returns for the mask (block_q a multiple
    of 128, as every rung it can return) lse and delta enter the kernel
    with rows along lanes, ``stats=compact``, and so they do at a 64-row
    test block, where the forward still writes its own replicated over
    lanes, ``stats=lanes``: the backward has the one body."""
    from magiattention_tpu import telemetry

    t, hq, hk, d = 2048, 8, 2, 64
    q = jnp.ones((t, hq, d), jnp.float32)
    kv = jnp.ones((t, hk, d), jnp.float32)
    name = "magi_flex_kernel_build_total"

    def stats_by_kernel():
        got = {}
        for key in _builds():
            labels = dict(
                kv.split("=") for kv in key[len(name) + 1 : -1].split(",")
            )
            got[labels["kernel"]] = labels["stats"]
        return got

    jax.make_jaxpr(_grad_fn(t, None, None, None, grid))(q, kv, kv)
    (said,) = [
        ev["args"] for ev in telemetry.get_event_buffer().events()
        if ev["name"] == "autotune_decision"
    ][-1:]
    assert int(said["rung"].split("x")[0]) % 128 == 0  # the tuner's own
    assert stats_by_kernel() == {"fwd": "compact", "bwd": "compact"}
    telemetry.get_registry().clear_metric(name)
    jax.make_jaxpr(_grad_fn(t, 1, 64, 64, grid))(q, kv, kv)
    assert stats_by_kernel() == {"fwd": "lanes", "bwd": "compact"}
