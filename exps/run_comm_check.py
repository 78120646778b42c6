"""Group-collective drift guard (``make comm-check``) — ISSUE 5.

Three assertions on the hop-scheduled collectives, all CPU-safe:

1. **Parity** on a canonical skewed varlen plan (4k varlen-block-causal,
   cp=4): the hops impl must produce a BIT-IDENTICAL cast recv buffer and
   a matching sum-reduce against the legacy globally-padded a2a, on a
   real 4-device virtual mesh — and its traced program must contain no
   ``all_to_all`` at all.
2. **Volume** on the 16k varlen-block-causal plan at cp=4: hop
   scheduling must cut scheduled comm volume by >= 30% vs the legacy
   padded volume (the ISSUE 5 acceptance floor), and auto mode must pick
   hops there.
3. **Auto-mode choice sanity**: a perfectly uniform nonlocal send map
   stays on a2a (hop scheduling saves nothing), an empty map resolves to
   hops with zero hops (no collective traced).

Exit codes: 0 = pass, 1 = drift/violation.
"""

import functools
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def _setup_cpu_mesh_env() -> None:
    """Force the 8-virtual-device CPU platform. Must run before jax
    initializes (every jax import below is function-local, so calling
    this at the top of ``main`` is early enough)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()


VOLUME_REDUCTION_FLOOR = 0.30  # ISSUE 5 acceptance criterion


def _headline_plan_meta(total: int, cp: int, impl: str):
    """Build the varlen-block-causal distributed plan host-side with the
    group-collective impl pinned; returns its merged comm meta."""
    from magiattention_tpu import env
    from magiattention_tpu.common.enum import AttnMaskType
    from magiattention_tpu.common.ranges import AttnRanges
    from magiattention_tpu.meta.dispatch_meta import (
        make_dispatch_meta_from_qk_ranges,
    )
    from magiattention_tpu.parallel.dist_attn import build_dist_attn_plan
    from magiattention_tpu.testing.workloads import varlen_block_causal

    slices = varlen_block_causal(total)
    qr = AttnRanges.from_ranges([(a, b) for a, b, _, _, _ in slices])
    kr = AttnRanges.from_ranges([(c, e) for _, _, c, e, _ in slices])
    mts = [AttnMaskType(t) for *_, t in slices]
    chunk = total // (env.min_chunks_per_rank() * cp)
    mq, _, bucket = make_dispatch_meta_from_qk_ranges(
        qr, kr, mts, total, total, chunk_size=chunk, cp_size=cp
    )
    prev = os.environ.get("MAGI_ATTENTION_GROUP_COLL_IMPL")
    os.environ["MAGI_ATTENTION_GROUP_COLL_IMPL"] = impl
    try:
        plan = build_dist_attn_plan(mq, bucket)
    finally:
        if prev is None:
            os.environ.pop("MAGI_ATTENTION_GROUP_COLL_IMPL", None)
        else:
            os.environ["MAGI_ATTENTION_GROUP_COLL_IMPL"] = prev
    return plan.merged_comm


def check_parity(total: int = 4096, cp: int = 4) -> list[str]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from magiattention_tpu.comm.group_collective import (
        group_cast_m,
        group_reduce_sum_m,
    )
    from magiattention_tpu.utils.compat import shard_map

    errors: list[str] = []
    a2a = _headline_plan_meta(total, cp, "a2a")
    hops = _headline_plan_meta(total, cp, "hops")
    if hops.impl != "hops" or not hops.hops:
        return [f"hops plan did not build a hop schedule: {hops.impl}"]
    if (hops.max_recv, hops.recv_total) != (a2a.max_recv, a2a.recv_total):
        return ["recv geometry diverged between impls"]

    mesh = Mesh(np.array(jax.devices()[:cp]), ("cp",))

    def shard(a):
        a = np.asarray(a)
        return jax.device_put(
            jnp.asarray(a),
            NamedSharding(mesh, P("cp", *([None] * (a.ndim - 1)))),
        )

    shard_len = total // cp
    rng = np.random.default_rng(0)
    x = shard(rng.standard_normal((cp, shard_len, 4)).astype(np.float32))
    y = shard(
        rng.standard_normal((cp, a2a.max_recv, 4)).astype(np.float32)
    )
    acc = shard(rng.standard_normal((cp, shard_len, 4)).astype(np.float32))

    outs, reds, jaxprs = {}, {}, {}
    for meta in (a2a, hops):
        arrays = [shard(a) for a in meta.reduce_device_arrays()]
        n = len(arrays)

        @functools.partial(
            shard_map,
            mesh=mesh,
            in_specs=(P("cp"),) * (1 + n),
            out_specs=P("cp"),
            check_vma=False,
        )
        def cast(x_, *arrs, _m=meta):
            return group_cast_m(x_[0], _m, arrs, axis_name="cp")[None]

        @functools.partial(
            shard_map,
            mesh=mesh,
            in_specs=(P("cp"),) * (2 + n),
            out_specs=P("cp"),
            check_vma=False,
        )
        def red(y_, acc_, *arrs, _m=meta):
            return group_reduce_sum_m(
                y_[0], acc_[0], _m, arrs, axis_name="cp"
            )[None]

        outs[meta.impl] = np.asarray(jax.jit(cast)(x, *arrays))
        reds[meta.impl] = np.asarray(jax.jit(red)(y, acc, *arrays))
        jaxprs[meta.impl] = str(jax.make_jaxpr(cast)(x, *arrays))

    if not np.array_equal(outs["a2a"], outs["hops"]):
        errors.append("cast recv buffers are NOT bit-identical")
    if not np.allclose(reds["a2a"], reds["hops"], rtol=1e-6, atol=1e-6):
        errors.append("sum-reduce results diverged")
    if "all_to_all" in jaxprs["hops"]:
        errors.append("hops cast still traces an all_to_all")
    if "ppermute" not in jaxprs["hops"]:
        errors.append("hops cast traces no ppermute (nothing moved?)")
    return errors


def check_volume() -> tuple[list[str], dict]:
    """True / scheduled / legacy-padded rows and the auto-mode impl
    choice for the 16k varlen plan. Host-side planning only."""
    comm = _headline_plan_meta(16384, 4, "auto")
    padded = comm.padded_rows_per_rank
    scheduled = comm.scheduled_rows_per_rank
    probe = {
        "impl": comm.impl,
        "impl_reason": comm.impl_reason,
        "true_rows_total": comm.true_rows_total,
        "scheduled_rows_per_rank": scheduled,
        "padded_rows_per_rank": padded,
        "volume_reduction": 1.0 - scheduled / padded if padded else 0.0,
    }
    errors: list[str] = []
    if probe["impl"] != "hops":
        errors.append(
            f"auto mode picked {probe['impl']} ({probe['impl_reason']}) on "
            "the headline skewed varlen plan — expected hops"
        )
    if probe["volume_reduction"] < VOLUME_REDUCTION_FLOOR:
        errors.append(
            f"scheduled volume reduction {probe['volume_reduction']:.1%} "
            f"< required {VOLUME_REDUCTION_FLOOR:.0%} "
            f"(scheduled {probe['scheduled_rows_per_rank']} vs padded "
            f"{probe['padded_rows_per_rank']} rows/rank)"
        )
    return errors, probe


def check_auto_choice() -> list[str]:
    import numpy as np

    from magiattention_tpu.comm.group_collective import GroupCollectiveMeta

    errors: list[str] = []
    cp = 4
    uniform = [
        [
            np.arange(8, dtype=np.int64) if d != s else np.empty(0, np.int64)
            for d in range(cp)
        ]
        for s in range(cp)
    ]
    m = GroupCollectiveMeta.build(uniform, [16] * cp, impl="auto")
    if m.impl != "a2a":
        errors.append(f"uniform map resolved to {m.impl}, expected a2a")
    empty = [[np.empty(0, np.int64)] * cp for _ in range(cp)]
    m = GroupCollectiveMeta.build(empty, [16] * cp, impl="auto")
    if m.impl != "hops" or m.hops:
        errors.append(
            f"empty map resolved to {m.impl} with {len(m.hops)} hops, "
            "expected hops with none"
        )
    return errors


def main() -> int:
    _setup_cpu_mesh_env()
    failures: list[str] = []

    print("comm-check 1/3: hops vs a2a parity on the 4k skewed varlen plan")
    errs = check_parity()
    failures += errs
    print("  " + ("OK" if not errs else "; ".join(errs)))

    print("comm-check 2/3: scheduled-volume reduction on the 16k headline plan")
    errs, probe = check_volume()
    failures += errs
    print(
        f"  impl {probe['impl']} ({probe['impl_reason']}): true "
        f"{probe['true_rows_total']} rows, scheduled "
        f"{probe['scheduled_rows_per_rank']}/rank vs legacy padded "
        f"{probe['padded_rows_per_rank']}/rank "
        f"(-{probe['volume_reduction']:.1%})"
    )
    print("  " + ("OK" if not errs else "; ".join(errs)))

    print("comm-check 3/3: auto-mode choice sanity")
    errs = check_auto_choice()
    failures += errs
    print("  " + ("OK" if not errs else "; ".join(errs)))

    if failures:
        print(f"\ncomm-check FAILED: {len(failures)} violation(s)")
        return 1
    print("\ncomm-check OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
