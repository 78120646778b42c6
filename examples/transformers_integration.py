"""HF transformers integration: register magiattention-tpu as an attention
implementation.

Role of reference ``examples/transformers`` (magi_attention_func.py:26-53 +
run_magi_clm.py:514): the reference registers a custom attention backend
under ``ALL_ATTENTION_FUNCTIONS`` so any HF model runs MagiAttention by
setting ``config._attn_implementation`` — model code untouched. This module
does the same for this framework:

    import examples.transformers_integration as mi
    mi.register()                       # once per process
    key = mi.prepare(total, mesh, num_heads, head_dim)   # per mask shape
    model.set_attn_implementation("magi_attention_tpu")

The registered forward bridges the model's torch tensors to jax, runs the
key-cached distributed flex attention (``calc_attn``), and returns a torch
tensor — the ``get_most_recent_key`` convention of the reference
(magi_attention_func.py:35: the key created most recently for the process
group is fetched inside the attention call, so the model never sees it).

The bridge is DIFFERENTIABLE: when any input requires grad, the forward
runs under ``jax.vjp`` inside a ``torch.autograd.Function``, so HF
training through this backend gets exact dq/dk/dv (parameter-gradient
parity vs eager attention is tested); ``examples/hf_trainer.py`` builds
a ``transformers.Trainer`` subclass on top.

Scope note, stated honestly: HF's torch models execute on the torch
device; each attention call crosses host<->device once in each direction
(twice when training). That is the right shape for parity demos and CPU
validation (this file's ``main()``), not for TPU production — there, use
the jax-native model family (``magiattention_tpu/models``) or an HF Flax
model. The reference has the same split: its transformers example is the
integration story, Megatron the performance story (SURVEY.md §2.9
examples)."""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_REGISTERED = False
_BRIDGE_CLS = None


def _bridge_cls():
    """Module-level torch<->jax autograd Function (built once — a fresh
    class per attention call would run per-layer-per-step on the hot
    path). ``apply(q, k, v, pipeline, to_jax, to_torch)``: the non-tensor
    helpers ride as constants (grad None)."""
    global _BRIDGE_CLS
    if _BRIDGE_CLS is not None:
        return _BRIDGE_CLS
    import jax
    import jax.numpy as jnp
    import torch

    class _Bridge(torch.autograd.Function):
        """Forward runs the jax pipeline under jax.vjp; backward feeds
        the torch cotangent through the stored vjp — so HF training
        through this backend gets EXACT dq/dk/dv (the reference's
        MagiAttention autograd role; without this the bridge would
        silently train with detached attention)."""

        @staticmethod
        def forward(ctx, q_t, k_t, v_t, pipeline, to_jax, to_torch):
            out, vjp = jax.vjp(
                pipeline, to_jax(q_t), to_jax(k_t), to_jax(v_t)
            )
            ctx._vjp = vjp
            ctx._to_torch = to_torch
            return to_torch(out, q_t)  # [s, hq, d]

        @staticmethod
        @torch.autograd.function.once_differentiable
        def backward(ctx, dout):
            # once_differentiable: the grads are numpy-built (no torch
            # graph), so second-order autodiff through attention would be
            # silently zero — fail loudly instead. ctx._vjp stays on ctx
            # (freed with the graph), so retain_graph / repeated
            # first-order backward keeps working.
            dq, dk, dv = ctx._vjp(
                jnp.asarray(
                    dout.detach().cpu().to(torch.float32).numpy()
                )
            )
            to_torch = ctx._to_torch

            def back(a):  # [s, h, d] jax -> [1, h, s, d] torch
                return to_torch(a, dout).permute(1, 0, 2).unsqueeze(0)

            return back(dq), back(dk), back(dv), None, None, None

    _BRIDGE_CLS = _Bridge
    return _Bridge


def magi_attention_forward(
    module,
    query,  # torch [b, hq, s, d] (post-RoPE)
    key,  # torch [b, hk, s, d]
    value,
    attention_mask,
    scaling=None,
    dropout: float = 0.0,
    **kwargs,
):
    """HF attention-interface conformant forward (same contract as
    transformers.integrations.sdpa_attention.sdpa_attention_forward:
    returns (attn_output [b, s, hq, d], attn_weights=None))."""
    import jax.numpy as jnp
    import torch

    from magiattention_tpu.api import calc_attn, dispatch, undispatch
    from magiattention_tpu.api import get_most_recent_key

    assert dropout == 0.0, "attention dropout is not supported"
    b, hq, s, d = query.shape
    assert scaling is None or abs(scaling - d ** -0.5) < 1e-9, (
        f"model uses a non-default attention scale {scaling} (default "
        f"{d ** -0.5:.6f}); the bridged key is planned with 1/sqrt(d) — "
        "unsupported, would silently mis-scale logits"
    )
    assert b == 1, (
        "the magi attention backend follows the reference's packed-varlen "
        "convention: squash the batch into one stream (reference "
        "squash_batch_dim, api/functools.py) and express samples as a "
        "varlen mask"
    )
    k = get_most_recent_key()
    assert k.total_seqlen_q - k.pad_size == s, (
        f"most-recent key plans {k.total_seqlen_q - k.pad_size} tokens, "
        f"attention got {s}: create the key for this sequence length first"
    )

    import numpy as np

    def _pipeline(qj, kj, vj):
        qd, kd, vd = dispatch(qj, k), dispatch(kj, k), dispatch(vj, k)
        out_d, _ = calc_attn(qd, kd, vd, k)
        return undispatch(out_d, k)  # [s, hq, d]

    def to_jax(t):  # [1, h, s, d] torch -> [s, h, d] jax fp32
        return jnp.asarray(
            t[0].permute(1, 0, 2).detach().cpu().to(torch.float32).numpy()
        )

    def to_torch(a, like):
        return (
            torch.from_numpy(np.asarray(a).copy())
            .to(like.dtype)
            .to(like.device)
        )

    if query.requires_grad or key.requires_grad or value.requires_grad:
        out = _bridge_cls().apply(
            query, key, value, _pipeline, to_jax, to_torch
        )
    else:  # inference fast path: no vjp residuals kept
        out = to_torch(_pipeline(to_jax(query), to_jax(key), to_jax(value)),
                       query)
    return out.unsqueeze(0), None


def register() -> None:
    """Register 'magi_attention_tpu' with transformers (idempotent)."""
    global _REGISTERED
    if _REGISTERED:
        return
    from transformers.modeling_utils import ALL_ATTENTION_FUNCTIONS

    ALL_ATTENTION_FUNCTIONS.register(
        "magi_attention_tpu", magi_attention_forward
    )
    _REGISTERED = True


def prepare(
    total: int,
    mesh,
    num_heads: tuple[int, int],
    head_dim: int,
    *,
    cu_seqlens=None,
    chunk_size: int | None = None,
    causal: bool = True,
):
    """Create (and make most-recent) the runtime key the registered
    forward will fetch — causal over the full stream by default, or
    per-document when ``cu_seqlens`` is given (the reference example's
    per-step varlen key, examples/torch_native/main.py:242)."""
    from magiattention_tpu.api import magi_attn_flex_key

    if cu_seqlens is not None:
        from magiattention_tpu.api import infer_attn_mask_from_cu_seqlens

        qr, kr, ts = infer_attn_mask_from_cu_seqlens(
            cu_seqlens, causal=causal
        )
        qr, kr = qr.to_naive_ranges(), kr.to_naive_ranges()
        ts = [int(t) for t in ts]
    else:
        qr, kr, ts = [(0, total)], [(0, total)], [1 if causal else 0]
    return prepare_slices(
        qr, kr, ts, total, mesh, num_heads, head_dim,
        chunk_size=chunk_size,
    )


def prepare_slices(
    qr, kr, ts, total, mesh, num_heads, head_dim, *, chunk_size=None
):
    """Slice-level prepare: key an arbitrary (q_range, k_range, type)
    list (e.g. from the padded-attention-mask adapter,
    infer_varlen_mask_from_padded_batch) for the registered backend."""
    from magiattention_tpu.api import magi_attn_flex_key

    return magi_attn_flex_key(
        qr, kr, ts, total, total, mesh,
        num_heads=num_heads, head_dim=head_dim,
        chunk_size=chunk_size, out_dtype="float32",
    )


def main() -> None:
    """Demo on the first device jax offers (JAX_PLATFORMS=cpu for the
    CPU): tiny HF Llama, magi backend vs eager attention."""
    import jax

    import numpy as np
    import torch
    from jax.sharding import Mesh
    from transformers import LlamaConfig, LlamaForCausalLM

    register()
    cfg = LlamaConfig(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=512,
    )
    torch.manual_seed(0)
    model = LlamaForCausalLM(cfg).eval()

    total = 256
    mesh = Mesh(np.array(jax.devices()[:1]), ("cp",))
    prepare(total, mesh, (4, 2), cfg.hidden_size // 4, chunk_size=64)

    ids = torch.randint(0, cfg.vocab_size, (1, total))
    with torch.no_grad():
        model.set_attn_implementation("eager")
        ref = model(ids).logits
        model.set_attn_implementation("magi_attention_tpu")
        out = model(ids).logits
    err = (out - ref).abs().max().item()
    print(f"max |logits diff| vs eager: {err:.2e}")
    assert err < 1e-3, "magi attention diverges from eager"
    print("transformers integration OK")


if __name__ == "__main__":
    main()
