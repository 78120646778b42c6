"""Example: train a Llama-style decoder with context-parallel flex attention.

Role of reference ``examples/torch_native/main.py`` (Llama FSDP+CP trainer),
TPU-native: a (dp, cp) mesh, varlen packed batches, the key-cached dispatch
workflow, and a jitted train step where the whole model runs inside one
shard_map. Every ``--masks`` draws a new packed-document mask (document
lengths from ``testing/data/doc_length_distribution.csv``) and trains
``--steps`` steps on it: a new mask means a new plan and a new compiled
step, and the script prints what each cost.

Runs on whatever ``jax.devices()`` offers and needs cp*dp*tp*pp of them.
One TPU chip at the published widths of TinyLlama-1.1B, depth cut to fit
16 GB (what ``chip_smoke.py`` runs):

    python examples/train_llama.py --cp 1 --dp 1 --dim 2048 --heads 32 \\
        --kv-heads 4 --head-dim 64 --ffn 5632 --vocab 32000 \\
        --rope-theta 10000 --layers 8 --total 16384 --chunk 512 --remat \\
        --masks 2 --steps 3

A CPU simulation is the caller's choice, at a size the interpreter bears:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python examples/train_llama.py --dtype float32

Optionally composes tensor parallelism (--tp, Megatron-style head/FFN
sharding) and pipeline parallelism (--pp, GPipe over ppermute) with the
CP attention — the reference covers these only via a Megatron README
patch (examples/megatron):

    ... python examples/train_llama.py --pp 2 --dp 1 --cp 2 --tp 2
"""

import argparse
import collections
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_COLLECTIVE_RE = re.compile(
    r"\s(all-to-all|all-gather|all-reduce|collective-permute|"
    r"reduce-scatter)(?:-start)?\("
)


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=5, help="steps per mask")
    p.add_argument(
        "--masks", type=int, default=1,
        help="packed-document masks to draw, each trained --steps steps",
    )
    p.add_argument("--seed", type=int, default=0, help="weights, masks, data")
    p.add_argument("--total", type=int, default=2048, help="tokens per stream")
    p.add_argument("--cp", type=int, default=4)
    p.add_argument("--dp", type=int, default=2)
    p.add_argument("--tp", type=int, default=1, help="tensor parallel size")
    p.add_argument("--pp", type=int, default=1, help="pipeline parallel size")
    p.add_argument("--dim", type=int, default=256)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--kv-heads", type=int, default=4)
    p.add_argument("--head-dim", type=int, default=64)
    p.add_argument("--ffn", type=int, default=None, help="default 2*dim")
    p.add_argument("--vocab", type=int, default=1024)
    p.add_argument("--rope-theta", type=float, default=500000.0)
    p.add_argument("--dtype", default="bfloat16", help="compute dtype")
    p.add_argument("--chunk", type=int, default=128)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument(
        "--remat", action="store_true",
        help="rematerialize decoder layers in backward (jax.checkpoint): "
        "~1/3 extra FLOPs for O(layers)x less activation memory",
    )
    p.add_argument(
        "--label-shift", type=int, default=1,
        help="predict the token this many positions ahead (MTP-style "
        "shifting via the distributed roll)",
    )
    p.add_argument(
        "--ckpt", default="", help="checkpoint dir (resume if it has state)"
    )
    p.add_argument("--ckpt-every", type=int, default=5)
    return p.parse_args(argv)


def llama_config(args):
    from magiattention_tpu.models import LlamaConfig

    return LlamaConfig(
        vocab_size=args.vocab,
        dim=args.dim,
        n_layers=args.layers,
        n_heads=args.heads,
        n_kv_heads=args.kv_heads,
        head_dim=args.head_dim,
        ffn_hidden=args.ffn if args.ffn is not None else args.dim * 2,
        rope_theta=args.rope_theta,
        dtype=args.dtype,
        remat=args.remat,
    )


def make_mesh(args, devices):
    """The (pp, dp, cp, tp) mesh over the first cp*dp*tp*pp ``devices``
    (size-1 pp/tp axes are left out)."""
    import numpy as np
    from jax.sharding import Mesh

    n_dev = args.cp * args.dp * args.tp * args.pp
    if len(devices) < n_dev:
        raise RuntimeError(
            f"--cp {args.cp} --dp {args.dp} --tp {args.tp} --pp {args.pp} "
            f"needs {n_dev} devices; jax offers {len(devices)} "
            f"({devices[0].platform}). For a CPU simulation set "
            "JAX_PLATFORMS=cpu XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_dev}"
        )
    devs = np.array(devices[:n_dev])
    if args.pp > 1:
        return Mesh(
            devs.reshape(args.pp, args.dp, args.cp, args.tp),
            ("pp", "dp", "cp", "tp"),
        )
    if args.tp > 1:
        return Mesh(
            devs.reshape(args.dp, args.cp, args.tp), ("dp", "cp", "tp")
        )
    return Mesh(devs.reshape(args.dp, args.cp), ("dp", "cp"))


def packed_mask(args, mask_idx: int):
    """The ``mask_idx``-th packed-document block-causal mask of a run:
    a pure function of (--seed, mask_idx), so a resumed run and a run at
    another cp draw the same documents."""
    import numpy as np

    from magiattention_tpu.api import infer_varlen_mask_from_batch
    from magiattention_tpu.testing.workloads import sample_doc_cuts

    cuts = sample_doc_cuts(
        args.total, np.random.default_rng([args.seed, mask_idx])
    )
    return infer_varlen_mask_from_batch(np.diff(cuts).tolist())


def train(args, devices=None) -> list[dict]:
    """Run the trainer; returns one record per step (what was printed).

    ``devices`` defaults to ``jax.devices()``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from magiattention_tpu.benchmarking import enable_compile_cache
    from magiattention_tpu.models import (
        build_magi_llama,
        build_magi_llama_pp,
        init_params,
        init_pp_params,
    )
    from magiattention_tpu.parallel import dispatch, roll
    from magiattention_tpu.telemetry import get_compile_tracker
    from magiattention_tpu.utils import (
        latest_step,
        restore_train_state,
        save_train_state,
    )

    enable_compile_cache()
    tracker = get_compile_tracker()
    cfg = llama_config(args)
    mesh = make_mesh(args, devices if devices is not None else jax.devices())
    mesh_devices = list(mesh.devices.flat)
    print(f"mesh: {mesh}", flush=True)
    tp_axis = "tp" if args.tp > 1 else None
    build = build_magi_llama_pp if args.pp > 1 else build_magi_llama
    init = init_pp_params if args.pp > 1 else init_params
    # two GPipe microbatches per dp rank under pp
    batch_rows = args.dp * 2 if args.pp > 1 else args.dp

    # born on the mesh (replicated; the step re-shards under tp/pp), not
    # on the default device with a copy to every other chip afterwards
    replicated = NamedSharding(mesh, P())
    params = jax.jit(
        lambda key: init(key, cfg), out_shardings=replicated
    )(jax.random.PRNGKey(args.seed))
    opt = optax.adamw(args.lr)
    opt_state = jax.jit(opt.init, out_shardings=replicated)(params)
    start_step = 0
    if args.ckpt and latest_step(args.ckpt) is not None:
        start_step, st = restore_train_state(
            args.ckpt,
            template={"params": params, "opt_state": opt_state},
        )
        # back to uncommitted host arrays: orbax restores committed to
        # one device, which conflicts with the mesh-wide train step —
        # as host arrays jit places them exactly like fresh init
        st = jax.tree.map(np.asarray, st)
        params, opt_state = st["params"], st["opt_state"]
        print(f"resumed from step {start_step}", flush=True)

    records: list[dict] = []
    for mask_idx in range(start_step // args.steps, args.masks):
        qr, kr, ts = packed_mask(args, mask_idx)
        t0 = time.perf_counter()
        model, meta = build(
            cfg, mesh, args.total, qr, kr, ts,
            chunk_size=args.chunk, tp_axis=tp_axis,
        )
        plan_ms = (time.perf_counter() - t0) * 1e3
        ap = model.attn_params
        print(
            f"mask {mask_idx}: {len(ts)} docs, plan {plan_ms:.0f} ms, "
            f"cp={model.plan.cp_size}, shard={model.plan.shard_q_len}, "
            f"remote rows/rank={model.plan.comm.recv_total} "
            f"({model.plan.comm.impl}), "
            f"tiles (block_q, block_k, head_block)="
            f"({ap.block_q}, {ap.block_k}, {ap.head_block}), "
            f"interpret={ap.interpret}",
            flush=True,
        )
        step_fn = model.make_train_step(opt)
        pos = jnp.broadcast_to(
            jnp.asarray(meta.perm_idx), (batch_rows, args.total)
        )
        compiled = False
        first = max(start_step - mask_idx * args.steps, 0)
        for i in range(first, args.steps):
            step = mask_idx * args.steps + i
            # per-step RNG: a resumed run samples the same data an
            # uninterrupted run would see at this step
            rng = np.random.default_rng([args.seed, 1000 + step])
            tokens_g = jnp.asarray(
                rng.integers(0, cfg.vocab_size, (batch_rows, args.total)),
                jnp.int32,
            )
            tokens = jax.vmap(lambda x: dispatch(x, meta))(tokens_g)
            # next-token labels via the DISTRIBUTED roll (reference
            # roll_p2p's MTP use case): shift in dispatch space over the
            # batched array — the mesh-aware P2P path keeps it O(N/P)
            # (exps/run_roll_proof.py); --label-shift K trains a
            # K-token-ahead predictor
            labels = roll(
                tokens, meta, -args.label_shift, axis=1, mesh=mesh,
                cp_axis="cp",
            )
            rec = {
                "step": step, "mask": mask_idx, "docs": len(ts),
                "plan_ms": 0.0, "compile_s": 0.0,
                "interpret": ap.interpret,
                "tiles": (ap.block_q, ap.block_k, ap.head_block),
                "comm_impl": model.plan.comm.impl,
                "remote_rows": tuple(model.plan.comm.recv_total),
            }
            if not compiled:
                # a new mask is a new program: compiled ahead of the
                # first call (which then finds it in jit's own cache),
                # so its cost is not read as a slow first step
                t0 = time.perf_counter()
                exe = step_fn.lower(
                    params, opt_state, tokens, labels, pos
                ).compile()
                compiled = True
                rec["plan_ms"] = plan_ms
                rec["compile_s"] = time.perf_counter() - t0
                text = exe.as_text()
                rec["collectives"] = dict(
                    collections.Counter(_COLLECTIVE_RE.findall(text))
                )
                rec["kernel_calls"] = text.count("tpu_custom_call")
                mem = exe.memory_analysis()  # per device
                rec["program_bytes"] = {
                    "arguments": mem.argument_size_in_bytes,
                    "temp": mem.temp_size_in_bytes,
                }
                print(
                    f"  compiled in {rec['compile_s']:.1f} s: collectives "
                    f"{rec['collectives']}, "
                    f"{rec['kernel_calls']} tpu_custom_call, per-device "
                    f"bytes {rec['program_bytes']}",
                    flush=True,
                )
            mark = tracker.mark()
            t0 = time.perf_counter()
            params, opt_state, loss = jax.block_until_ready(
                step_fn(params, opt_state, tokens, labels, pos)
            )
            rec["step_s"] = time.perf_counter() - t0
            # compiles inside the timed call: none, unless the step's
            # inputs changed under it
            rec["recompiles"], rec["recompile_s"] = tracker.since(mark)
            rec["loss"] = float(loss)
            rec["peak_bytes"] = [
                (d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in mesh_devices
            ]
            records.append(rec)
            print(
                f"step {step}: loss={rec['loss']:.4f}  "
                f"({rec['step_s']:.2f}s, {rec['recompiles']} recompiles "
                f"{rec['recompile_s']:.1f}s, peak bytes/device "
                f"{rec['peak_bytes']})",
                flush=True,
            )
            if (
                args.ckpt
                and args.ckpt_every > 0
                and (step + 1) % args.ckpt_every == 0
            ):
                save_train_state(
                    args.ckpt,
                    step + 1,
                    {"params": params, "opt_state": opt_state},
                )
                print(f"saved checkpoint at step {step + 1}", flush=True)
    return records


if __name__ == "__main__":
    train(parse_args())
