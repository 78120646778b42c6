"""What a keyed attention call's program holds round its kernels (ISSUE
40): no XLA pass that only reformats a kernel's side operand. The forward
makes no lane-replicated statistic at all; forward+backward makes none in
XLA (delta is dq's, the lse cotangent reaches it with rows along lanes),
and no float32 gradient is rounded outside the kernel that wrote it."""

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


def test_forward_backward_reformats_no_side_operand_in_xla(programs):
    _, fwdbwd = programs
    names = [e.params["name"] for e in _kernels(fwdbwd)]
    assert sorted(names) == [
        "magi_flex_dkv_kernel", "magi_flex_dq_kernel", "magi_flex_fwd_kernel",
    ]
    grad_sized = HK * TOTAL * D  # dk and dv; dq is larger
    for eqn in _outside_kernels(fwdbwd):
        if eqn.primitive.name == "broadcast_in_dim":
            # (a scalar's fill is no operand's reformat: the zeros jax
            # makes for the residuals' places, which nothing reads)
            (out,) = eqn.outvars
            assert not (
                eqn.invars[0].aval.ndim
                and out.aval.shape[-1:] == (LANES,)
                and out.aval.dtype == jnp.float32
                and out.aval.ndim >= 3
            ), eqn
        if eqn.primitive.name == "convert_element_type":
            (x,), (out,) = eqn.invars, eqn.outvars
            assert not (
                x.aval.dtype == jnp.float32
                and out.aval.dtype == jnp.bfloat16
                and out.aval.size >= grad_sized
            ), eqn
    # the lane-replicated arrays that are left are the kernels' own: lse,
    # the backward's residual, and delta, dq's second output
    def makes(eqn):  # not hands on: a shard_map, the custom_vjp's call
        if eqn.primitive.name == "broadcast_in_dim":
            return bool(eqn.invars[0].aval.ndim)
        return eqn.primitive.name == "pallas_call" or not list(
            jax.core.jaxprs_in_params(eqn.params)
        )

    lanes = [
        e.primitive.name
        for e in _outside_kernels(fwdbwd)
        for v in e.outvars
        if v.aval.shape[-1:] == (LANES,) and v.aval.ndim == 3 and makes(e)
    ]
    assert lanes == ["pallas_call"] * 2
