"""MagiTrainer: HF ``transformers.Trainer`` wired to magiattention-tpu.

Role of reference ``examples/transformers/magi_trainer.py`` (a Trainer
subclass whose ``_prepare_inputs`` builds the varlen key for each batch
and routes attention through MagiAttention): here the registered
DIFFERENTIABLE jax attention backend
(``examples/transformers_integration.py``) does the compute, and this
subclass automates the per-batch key plumbing — derive the batch's mask
structure, create (or fetch from the LRU cache) the runtime key *before*
the forward, so every attention layer picks it up via
``get_most_recent_key``.

Mask-structure priority per [1, total] batch row:

1. explicit ``cu_seqlens`` in the batch (packed collators),
2. ``position_ids`` resets (packed samples restart at 0),
3. ``attention_mask`` with pad zeros (right-padded HF convention —
   routed through ``infer_varlen_mask_from_padded_batch``, so pad rows
   attend nothing instead of being treated as real tokens),
4. one full-stream causal document.

Scope matches the integration module's honest note: torch model + jax
attention bridge — the parity/integration story (CPU-validatable), not
the TPU performance story (use ``magiattention_tpu/models`` for that).

Use ``get_magi_trainer_cls()`` to subclass/override Trainer hooks;
``MagiTrainer(...)`` is a convenience constructor of that class.

Run a 2-step smoke train on the two first devices jax offers; as a CPU
simulation:  JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=2 python
examples/hf_trainer.py
"""

from __future__ import annotations

import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@functools.cache
def get_magi_trainer_cls():
    """The MagiTrainer class (built lazily so importing this module never
    hard-requires torch/transformers; cached so there is exactly one)."""
    import torch
    import transformers

    import examples.transformers_integration as mi

    class MagiTrainer(transformers.Trainer):
        """transformers.Trainer + automatic magi key management."""

        def __init__(
            self,
            *args,
            mesh=None,
            num_heads: tuple[int, int] | None = None,
            head_dim: int | None = None,
            chunk_size: int | None = None,
            causal: bool = True,
            **kwargs,
        ):
            assert mesh is not None, "MagiTrainer requires mesh="
            mi.register()
            self._mesh = mesh
            self._chunk_size = chunk_size
            self._causal = causal
            super().__init__(*args, **kwargs)
            # head geometry from the model config (overridable; a typo'd
            # override would plan the key with wrong head counts, so
            # cross-check when both are available)
            cfg = getattr(self.model, "config", None)
            cfg_heads = (
                (
                    int(cfg.num_attention_heads),
                    int(
                        getattr(
                            cfg, "num_key_value_heads",
                            cfg.num_attention_heads,
                        )
                    ),
                )
                if cfg is not None and hasattr(cfg, "num_attention_heads")
                else None
            )
            cfg_head_dim = (
                int(
                    getattr(
                        cfg, "head_dim",
                        cfg.hidden_size // cfg.num_attention_heads,
                    )
                )
                if cfg is not None and hasattr(cfg, "num_attention_heads")
                else None
            )
            self._num_heads = tuple(num_heads) if num_heads else cfg_heads
            self._head_dim = (
                int(head_dim) if head_dim is not None else cfg_head_dim
            )
            assert self._num_heads and self._head_dim, (
                "could not derive num_heads/head_dim from the model "
                "config; pass num_heads=(hq, hkv), head_dim= explicitly"
            )
            if num_heads and cfg_heads and tuple(num_heads) != cfg_heads:
                raise ValueError(
                    f"num_heads={tuple(num_heads)} contradicts the model "
                    f"config {cfg_heads}"
                )
            if (
                head_dim is not None
                and cfg_head_dim is not None
                and int(head_dim) != cfg_head_dim
            ):
                raise ValueError(
                    f"head_dim={head_dim} contradicts the model config "
                    f"{cfg_head_dim}"
                )
            self.model.set_attn_implementation("magi_attention_tpu")

        def _magi_prepare_key(self, inputs, total: int) -> None:
            cu = None
            if inputs.get("cu_seqlens") is not None:
                raw = inputs["cu_seqlens"]
                raw = (
                    raw.reshape(-1).tolist()
                    if isinstance(raw, torch.Tensor)
                    else list(raw)
                )
                cu = [int(c) for c in raw]
            elif inputs.get("position_ids") is not None:
                p = inputs["position_ids"].reshape(-1).tolist()
                cu = [0] + [
                    i for i in range(1, len(p)) if p[i] == 0
                ] + [len(p)]
            else:
                am = inputs.get("attention_mask")
                if am is not None and not bool(am.bool().all()):
                    # right-padded batch: pad rows must attend nothing
                    from magiattention_tpu.api import (
                        infer_varlen_mask_from_padded_batch,
                    )

                    qr, kr, ts = infer_varlen_mask_from_padded_batch(
                        am.detach().cpu().numpy(), causal=self._causal
                    )
                    mi.prepare_slices(
                        qr.to_naive_ranges(), kr.to_naive_ranges(),
                        [int(t) for t in ts], total, self._mesh,
                        self._num_heads, self._head_dim,
                        chunk_size=self._chunk_size,
                    )
                    return
            mi.prepare(
                total, self._mesh, self._num_heads, self._head_dim,
                cu_seqlens=cu, chunk_size=self._chunk_size,
                causal=self._causal,
            )

        def _prepare_inputs(self, inputs):
            inputs = super()._prepare_inputs(inputs)
            ids = inputs.get("input_ids")
            if ids is None:
                return inputs
            if ids.shape[0] > 1:
                inputs = self._squash_batch(inputs)
            else:
                self._magi_prepare_key(inputs, int(ids.shape[1]))
            return inputs

        def _squash_batch(self, inputs):
            """[b, s] -> [1, b*s] packed stream (reference magi_trainer's
            squash_batch_dim role — e.g. the default eval batch of 8):
            the key is built from the per-sample structure (padded-mask
            adapter when pads exist, else uniform cu_seqlens) so
            attention stays sample-local, and explicit position_ids
            restart RoPE at every sample."""
            from magiattention_tpu.api import (
                infer_varlen_mask_from_padded_batch,
            )

            am2d = inputs.get("attention_mask")
            b, s = inputs["input_ids"].shape
            if am2d is not None and not bool(am2d.bool().all()):
                qr, kr, ts = infer_varlen_mask_from_padded_batch(
                    am2d.detach().cpu().numpy(), causal=self._causal
                )
                mi.prepare_slices(
                    qr.to_naive_ranges(), kr.to_naive_ranges(),
                    [int(t) for t in ts], b * s, self._mesh,
                    self._num_heads, self._head_dim,
                    chunk_size=self._chunk_size,
                )
            else:
                mi.prepare(
                    b * s, self._mesh, self._num_heads, self._head_dim,
                    cu_seqlens=list(range(0, b * s + 1, s)),
                    chunk_size=self._chunk_size, causal=self._causal,
                )
            out = dict(inputs)
            for name in ("input_ids", "labels", "attention_mask"):
                if out.get(name) is not None:
                    out[name] = out[name].reshape(1, b * s)
            out["position_ids"] = (
                torch.arange(s).repeat(b).reshape(1, b * s)
                .to(inputs["input_ids"].device)
            )
            return out

    return MagiTrainer


def MagiTrainer(*args, **kwargs):
    """Convenience constructor: ``get_magi_trainer_cls()(*args, **kwargs)``."""
    return get_magi_trainer_cls()(*args, **kwargs)


def main() -> None:  # pragma: no cover - exercised by tests at small size
    import jax

    if len(jax.devices()) < 2:
        raise RuntimeError(
            f"needs 2 devices; jax offers {len(jax.devices())} (CPU "
            "simulation: JAX_PLATFORMS=cpu "
            "XLA_FLAGS=--xla_force_host_platform_device_count=2)"
        )
    import numpy as np
    import torch
    from jax.sharding import Mesh
    from transformers import (
        LlamaConfig,
        LlamaForCausalLM,
        TrainingArguments,
    )

    total, vocab = 128, 128
    cfg = LlamaConfig(
        vocab_size=vocab, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
        max_position_embeddings=total,
    )
    torch.manual_seed(0)
    model = LlamaForCausalLM(cfg)

    class Packed(torch.utils.data.Dataset):
        def __len__(self):
            return 8

        def __getitem__(self, i):
            g = torch.Generator().manual_seed(i)
            ids = torch.randint(0, vocab, (total,), generator=g)
            return {"input_ids": ids, "labels": ids.clone()}

    mesh = Mesh(np.array(jax.devices()[:2]), ("cp",))
    trainer = MagiTrainer(
        model=model,
        args=TrainingArguments(
            output_dir="/tmp/magi_hf_trainer", max_steps=2,
            per_device_train_batch_size=1, report_to=[], logging_steps=1,
            use_cpu=True,
        ),
        train_dataset=Packed(),
        mesh=mesh,
        num_heads=(2, 2),
        head_dim=cfg.hidden_size // 2,
        chunk_size=16,
    )
    out = trainer.train()
    print(f"MagiTrainer smoke: loss={out.training_loss:.4f}")


if __name__ == "__main__":
    main()
