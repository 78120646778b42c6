"""Train a Magi-1-style chunked video-diffusion DiT on a (dp, cp) mesh.

The video latent stream attends chunk-causally (each AR chunk sees itself
+ all earlier chunks — the varlen_block_causal mask family), conditioned
on text via rank-local cross-attention and on per-chunk diffusion time
via adaLN. Objective: rectified-flow velocity matching with independent
per-chunk t — the Magi-1 pipeline-denoising training shape (BASELINE
config 5, scaled down).

Runs on whatever ``jax.devices()`` offers (dp*cp of them):
    python examples/train_dit.py --dp 1 --cp 1
CPU simulation, the caller's choice:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_dit.py
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--total", type=int, default=2048)
    p.add_argument("--chunk", type=int, default=512, help="AR video chunk tokens")
    p.add_argument("--dp", type=int, default=2)
    p.add_argument("--cp", type=int, default=4)
    p.add_argument("--steps", type=int, default=5)
    p.add_argument("--lr", type=float, default=1e-3)
    args = p.parse_args()
    assert args.total % args.chunk == 0, (
        "--total must be a multiple of --chunk (the per-chunk diffusion "
        "time below is built by repeat; chunk_causal_mask itself tolerates "
        "a ragged last chunk)"
    )
    n_dev = args.dp * args.cp

    import jax

    if len(jax.devices()) < n_dev:
        raise RuntimeError(
            f"--dp {args.dp} --cp {args.cp} needs {n_dev} devices; jax "
            f"offers {len(jax.devices())} ({jax.devices()[0].platform}). "
            "For a CPU simulation set JAX_PLATFORMS=cpu XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_dev}"
        )

    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh

    from magiattention_tpu.models import (
        DiTConfig,
        build_magi_dit,
        init_dit_params,
    )
    from magiattention_tpu.parallel.dispatch import dispatch

    cfg = DiTConfig(
        dtype="float32" if jax.default_backend() == "cpu" else "bfloat16"
    )
    mesh = Mesh(
        np.array(jax.devices()[:n_dev]).reshape(args.dp, args.cp),
        ("dp", "cp"),
    )
    model, mq = build_magi_dit(cfg, mesh, args.total, args.chunk)
    print(
        f"mesh {mesh} | chunks {args.total // args.chunk} x {args.chunk} "
        f"tokens | remote rows/rank {model.plan.comm.recv_total}",
        flush=True,
    )

    params = init_dit_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adamw(args.lr)
    opt_state = opt.init(params)
    step_fn = model.make_train_step(opt)

    rng = np.random.default_rng(0)
    disp = lambda x: jax.vmap(lambda a: dispatch(a, mq))(x)
    # pad slots (uneven shard) must read t < 0 so the loss excludes them
    disp_t = lambda x: jax.vmap(
        lambda a: dispatch(a, mq, pad_value=-1.0)
    )(x)
    pos = disp(
        jnp.broadcast_to(
            jnp.arange(args.total, dtype=jnp.int32), (args.dp, args.total)
        )
    )
    for step in range(args.steps):
        lat = jnp.asarray(
            rng.standard_normal((args.dp, args.total, cfg.in_dim)),
            jnp.float32,
        )
        text = jnp.asarray(
            rng.standard_normal((args.dp, cfg.text_len, cfg.text_dim)),
            jnp.float32,
        )
        tc = jnp.repeat(
            jnp.asarray(
                rng.uniform(0.02, 0.98, (args.dp, args.total // args.chunk))
            ),
            args.chunk,
            axis=1,
        ).astype(jnp.float32)
        noise = jnp.asarray(rng.standard_normal(lat.shape), jnp.float32)
        noised = (1 - tc[..., None]) * lat + tc[..., None] * noise
        target_v = noise - lat
        t0 = time.time()
        params, opt_state, loss = step_fn(
            params, opt_state, disp(noised), disp(target_v), disp_t(tc),
            pos, text,
        )
        print(
            f"step {step}: loss={float(loss):.4f} ({time.time()-t0:.2f}s)",
            flush=True,
        )


if __name__ == "__main__":
    main()
