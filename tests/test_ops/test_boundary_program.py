"""What a keyed attention call's program holds round its kernels (ISSUE
40): no XLA pass that only reformats a kernel's side operand. The forward
makes no lane-replicated statistic at all. Forward+backward (ISSUE 43: one
backward kernel over the k-major table) makes delta before the kernel,
once, on 4 bytes a row, and nothing else: since ISSUE 44 dq's float32
buffer is neither zero-filled before the kernel nor rounded after it, and
since ISSUE 58 neither delta nor lse is replicated over lanes for it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from magiattention_tpu import api
from magiattention_tpu.ops.flex_attn import LANES

TOTAL, HQ, HK, D = 1024, 8, 2, 64


def _outside_kernels(jaxpr):
    """Every equation of a jaxpr and of the jaxprs it calls, but for the
    kernels' own bodies."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _outside_kernels(sub)


@pytest.fixture(scope="module")
def programs():
    mesh = Mesh(np.array(jax.devices()[:1]), ("cp",))
    with jax.enable_x64(False):
        key = api.magi_attn_varlen_key(
            [0, 300, 700, TOTAL], TOTAL, mesh, num_heads=(HQ, HK),
            head_dim=D, chunk_size=128, out_dtype="bfloat16",
        )
        sharded = NamedSharding(mesh, P("cp"))
        q = jax.device_put(jnp.ones((TOTAL, HQ, D), jnp.bfloat16), sharded)
        k = jax.device_put(jnp.ones((TOTAL, HK, D), jnp.bfloat16), sharded)
        d_lse = jax.device_put(jnp.ones((TOTAL, HQ), jnp.float32), sharded)

        def fwd(q, k, v):
            out, meta = api.calc_attn(q, k, v, key)
            return out, meta.lse

        def fwdbwd(q, k, v, d_out, d_lse):
            _res, vjp = jax.vjp(fwd, q, k, v)
            return vjp((d_out, d_lse))

        return (
            jax.make_jaxpr(fwd)(q, k, k).jaxpr,
            jax.make_jaxpr(fwdbwd)(q, k, k, q, d_lse).jaxpr,
        )


def _avals(eqn):
    return [v.aval for v in (*eqn.invars, *eqn.outvars) if hasattr(v, "aval")]


def _kernels(jaxpr):
    return [
        e for e in _outside_kernels(jaxpr) if e.primitive.name == "pallas_call"
    ]


def test_forward_holds_no_lane_replicated_statistic(programs):
    fwd, _ = programs
    (kernel,) = _kernels(fwd)
    assert kernel.params["name"] == "magi_flex_fwd_kernel"
    block_q = kernel.outvars[1].aval.shape[-1]  # (hq / HBG, nq, 2, HBG, bq)
    assert len(kernel.outvars) == 2  # out, and both statistics as one
    assert block_q % LANES == 0
    for eqn in _outside_kernels(fwd):
        for aval in _avals(eqn):
            shape = getattr(aval, "shape", ())
            assert not (len(shape) == 3 and shape[-1] == LANES), eqn


def test_forward_backward_makes_delta_once_and_nothing_of_dq_in_xla(programs):
    """ISSUE 43: the backward is one k-major kernel. A q block has no first
    step on that walk, so delta is made before the kernel, once; ISSUE 58:
    [hq, tqp], and it enters the kernel with lse as one ``(hq / HBG, nq,
    2, HBG, bq)`` operand, rows along lanes: no [hq, tqp, 128] float32
    array is made anywhere in the program. ISSUE 44: dq leaves the kernel
    in the inputs' dtype, written by each q block's last visit; the
    float32 buffer it is summed in is an output nobody reads, aliased to
    an operand nobody has written (``lax.empty``: no fill), the result has
    no fill behind it (this mask names every q block), and no tensor-sized
    rounding is left in XLA. dk and dv leave in the
    inputs' dtype, the lse cotangent folds into delta on 4 bytes a row."""
    _, fwdbwd = programs
    names = [e.params["name"] for e in _kernels(fwdbwd)]
    assert sorted(names) == ["magi_flex_bwd_kernel", "magi_flex_fwd_kernel"]
    (bwd,) = [e for e in _kernels(fwdbwd) if e.params["name"].endswith("bwd_kernel")]
    dk, dv, dq, acc = (v.aval for v in bwd.outvars)
    # (whole vregs of lanes a tile: head_dim 64 is padded to 128 and cut)
    assert dq.shape == acc.shape and dq.shape[0] == HQ
    assert dq.shape[2] == -(-D // LANES) * LANES
    assert dk.dtype == dv.dtype == dq.dtype == jnp.bfloat16
    assert acc.dtype == jnp.float32
    # the float32 sums' buffer comes in as an operand nobody has written
    # (12, aliased to the fourth output; PERF.md section 6, PR 44, says why
    # it is an operand at all); the result has no fill behind it: this
    # mask names every q block
    assert dict(bwd.params["input_output_aliases"]) == {12: 3}
    assert len(bwd.invars) == 7 + 5 + 1  # tables; q, k, v, dO, lse | delta
    stats = bwd.invars[11].aval
    block_q = stats.shape[-1]
    assert block_q % LANES == 0 and stats.dtype == jnp.float32
    assert stats.shape == (
        HQ // stats.shape[3], dq.shape[1] // block_q, 2, stats.shape[3], block_q
    )
    (made,) = [
        e for e in _outside_kernels(fwdbwd) if bwd.invars[12] in e.outvars
    ]
    assert made.primitive.name == "empty"  # no pass on the chip
    used = {id(v) for e in _outside_kernels(fwdbwd) for v in e.invars}
    assert id(bwd.outvars[3]) not in used  # scratch: dropped where it leaves
    grad_sized = HK * TOTAL * D  # dk and dv; dq is larger
    to_lanes, rounded = [], []
    for eqn in _outside_kernels(fwdbwd):
        if eqn.primitive.name == "broadcast_in_dim":
            # (a scalar's fill is no operand's reformat: the zeros jax
            # makes for the residuals' places; the kernel takes none)
            (out,) = eqn.outvars
            if (
                eqn.invars[0].aval.ndim
                and out.aval.shape[-1:] == (LANES,)
                and out.aval.dtype == jnp.float32
                and out.aval.ndim >= 3
            ):
                to_lanes.append(out.aval.shape)
        if eqn.primitive.name == "convert_element_type":
            (x,), (out,) = eqn.invars, eqn.outvars
            if (
                x.aval.dtype == jnp.float32
                and out.aval.dtype == jnp.bfloat16
                and out.aval.size >= grad_sized
            ):
                rounded.append(out.aval.shape)
    assert to_lanes == []
    assert rounded == []

    # nothing anywhere is a statistic replicated over lanes
    lanes = [
        e.primitive.name
        for e in _outside_kernels(fwdbwd)
        for v in e.outvars
        if v.aval.shape[-1:] == (LANES,) and v.aval.ndim == 3
        # dq's padded lanes are no statistic, nor is its sums' buffer
        and v not in (*bwd.outvars[2:], bwd.invars[12])
        and not list(jax.core.jaxprs_in_params(e.params))  # hands on
    ]
    assert lanes == []
