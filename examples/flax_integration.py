"""Integrating CP flex attention into an existing flax model.

Role of reference ``examples/transformers`` (registering
``magi_attention_forward`` as a custom HF attention backend via
``ALL_ATTENTION_FUNCTIONS`` + fetching the key with ``get_most_recent_key``):
the same drop-in pattern for flax/linen models on TPU — an attention
function with the standard (q, k, v) -> out signature that internally
routes through the framework, fetching the runtime key out-of-band so the
module graph does not need to thread it.

Runs on whatever ``jax.devices()`` offers and needs four of them; as a
CPU mesh simulation:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python examples/flax_integration.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def magi_attention_forward(q, k, v):
    """Drop-in attention: [tokens, heads, head_dim] in dispatch order.

    The runtime key is fetched via get_most_recent_key() — the hook for
    module code that cannot thread framework objects (reference
    examples/transformers/magi_attention_func.py:26-53).
    """
    from magiattention_tpu.api import calc_attn, get_most_recent_key

    key = get_most_recent_key()
    out, _meta = calc_attn(q, k, v, key)
    return out


def main() -> None:
    import jax

    if len(jax.devices()) < 4:
        raise RuntimeError(
            f"needs 4 devices; jax offers {len(jax.devices())} (see the "
            "module docstring for the CPU simulation)"
        )

    import flax.linen as nn
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from magiattention_tpu.api import (
        dispatch,
        get_position_ids,
        magi_attn_varlen_key,
        undispatch,
    )

    total, dim, hq, hkv, hd = 1024, 256, 8, 4, 32
    mesh = Mesh(np.array(jax.devices()[:4]), ("cp",))

    class Block(nn.Module):
        """An ordinary flax block whose attention is the framework's —
        note the module knows nothing about meshes, keys or dispatch."""

        @nn.compact
        def __call__(self, x):
            h = nn.LayerNorm()(x)
            q = nn.DenseGeneral((hq, hd), name="wq")(h)
            k = nn.DenseGeneral((hkv, hd), name="wk")(h)
            v = nn.DenseGeneral((hkv, hd), name="wv")(h)
            attn = magi_attention_forward(q, k, v)
            return x + nn.DenseGeneral(
                dim, axis=(-2, -1), name="wo"
            )(attn)

    # 1. plan once per packed batch shape (three documents, per-doc causal)
    key = magi_attn_varlen_key(
        [0, 384, 768, total],
        total,
        mesh,
        num_heads=(hq, hkv),
        head_dim=hd,
        chunk_size=64,
        out_dtype="float32",
    )

    # 2. dispatch activations into CP layout; the model runs unchanged
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((total, dim)), jnp.float32)
    xd = dispatch(x, key)
    pos = get_position_ids(key)  # for RoPE etc. (unused by this tiny block)

    model = Block()
    params = model.init(jax.random.PRNGKey(0), xd)
    y_d = jax.jit(lambda p, x: model.apply(p, x))(params, xd)
    y = undispatch(y_d, key)
    print(f"flax block through CP flex attention: out {y.shape}", flush=True)

    # 3. correctness: same model on the undispatched input with a cp=1 key
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("cp",))
    key1 = magi_attn_varlen_key(
        [0, 384, 768, total],
        total,
        mesh1,
        num_heads=(hq, hkv),
        head_dim=hd,
        chunk_size=64,
        out_dtype="float32",
    )
    y1 = model.apply(params, dispatch(x, key1))
    y1 = undispatch(y1, key1)
    err = float(np.max(np.abs(np.asarray(y) - np.asarray(y1))))
    assert err < 1e-4, err
    print(f"cp=4 vs cp=1 max err: {err:.2e} — identical model, sharded attention")


if __name__ == "__main__":
    main()
