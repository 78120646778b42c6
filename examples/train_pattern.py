"""Example: train a window/global sparse-expert decoder (Trinity / AFMoE
layer pattern) with context-parallel flex attention.

``examples/train_llama.py``'s sibling for ``models/pattern.py``: one
dispatch, a plan per attention kind. ``--config`` is a published AFMoE
``config.json`` (or ``benchmarks/configs/trinity-mini.json``, the one-rank
share the benchmark runs); ``--layers N`` keeps its first N layers,
``--experts-here`` and ``--vocab-here`` give one rank's share. The loss of
the same packed sequence at ``--cp 4`` against ``--cp 1`` is the
comparison ``chip_smoke.py`` makes for the dense decoder:

    python examples/train_pattern.py --cp 1 --layers 5 --total 8192 --remat
    python examples/train_pattern.py --cp 4 --layers 5 --total 8192 --remat

A CPU simulation is the caller's choice, at a size the interpreter bears:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python examples/train_pattern.py --toy --cp 4 --dtype float32
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOY = dict(
    hidden_size=128, intermediate_size=256, num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, num_hidden_layers=4,
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    num_dense_layers=1, sliding_window=64, rope_theta=10000.0,
    rms_norm_eps=1e-5, mup_enabled=True, num_experts=8,
    num_experts_per_tok=2, moe_intermediate_size=64, num_shared_experts=1,
    route_norm=True, route_scale=2.826, vocab_size=512,
)


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument(
        "--config", default=os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "benchmarks", "configs", "trinity-mini.json",
        ),
    )
    p.add_argument("--toy", action="store_true", help="a toy config, not --config")
    p.add_argument("--layers", type=int, default=None)
    p.add_argument("--experts-here", type=int, nargs=2, default=None)
    p.add_argument("--vocab-here", type=int, default=None)
    p.add_argument("--cp", type=int, default=1)
    p.add_argument("--dp", type=int, default=1)
    p.add_argument("--total", type=int, default=None)
    p.add_argument("--docs", type=int, nargs="*", default=None,
                   help="document lengths (default: thirds of --total)")
    p.add_argument("--chunk", type=int, default=None)
    p.add_argument("--steps", type=int, default=3)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--remat", action="store_true")
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args(argv)


def main(argv=None) -> list[float]:
    args = parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh

    from magiattention_tpu.models.pattern import (
        afmoe_config, build_magi_pattern, init_pattern_params,
    )
    from magiattention_tpu.parallel import dispatch, roll

    hf = dict(TOY) if args.toy else json.load(open(args.config))
    if args.layers is not None:
        hf["num_hidden_layers"] = args.layers
        hf["layer_types"] = hf["layer_types"][: args.layers]
    held = args.experts_here or hf.get("experts_here")
    vocab = args.vocab_here or hf.get("vocab_here") or hf["vocab_size"]
    cfg = afmoe_config(
        hf, dtype=args.dtype, remat=args.remat,
        expert_range=tuple(held) if held else None, vocab_size=vocab,
    )
    total = args.total or (512 if args.toy else 8192)
    chunk = args.chunk or (64 if args.toy else 512)
    docs = args.docs or [total // 2, total // 8, total - total // 2 - total // 8]
    assert sum(docs) == total, (docs, total)
    cu = [0, *np.cumsum(docs).tolist()]
    n = args.dp * args.cp
    mesh = Mesh(np.array(jax.devices()[:n]).reshape(args.dp, args.cp), ("dp", "cp"))

    t0 = time.perf_counter()
    model, meta = build_magi_pattern(cfg, mesh, cu, chunk_size=chunk)
    print(
        f"{cfg.n_layers} layers {list(zip(cfg.layer_types, cfg.ffn_types))}; "
        f"documents {docs}; one dispatch, plans {sorted(model.plans)} in "
        f"{time.perf_counter() - t0:.2f} s; tiles "
        + str({k: (p.block_q, p.block_k, p.head_block)
               for k, p in model.attn_params.items()})
    )
    opt = optax.adamw(args.lr)
    params = init_pattern_params(jax.random.PRNGKey(args.seed), cfg)
    opt_state = opt.init(params)
    step = model.make_train_step(opt)
    rng = np.random.default_rng(args.seed)
    losses = []
    for k in range(args.steps):
        tokens_g = rng.integers(0, cfg.vocab_size, (args.dp, total))
        tokens = jax.vmap(lambda x: dispatch(x, meta))(
            jnp.asarray(tokens_g, jnp.int32)
        )
        labels = roll(tokens, meta, -1, axis=1, mesh=mesh, cp_axis="cp")
        pos = jnp.broadcast_to(jnp.asarray(meta.perm_idx)[None], tokens.shape)
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, tokens, labels, pos)
        losses.append(float(loss))
        print(f"step {k}: loss {losses[-1]:.6f} ({time.perf_counter() - t0:.2f} s)")
    return losses


if __name__ == "__main__":
    main()
