"""A decoder driven by a layer pattern: attention kind x FFN kind a layer.

Hybrid models alternate attention kinds over one token stream (Trinity /
AFMoE: three ``sliding_attention`` layers, window 2048, then one
``full_attention``) and FFN kinds (leading dense layers, then sparse
experts). Tokens do not move between layers, so the builder solves ONE
dispatch and makes a plan, kernel parameters and tables PER ATTENTION
KIND on it (``_common.plan_flex_attn`` for the kind that decides the
balance, ``plan_flex_attn_on_dispatch`` for the others — the
``*_for_new_mask_after_dispatch`` rule of ``api/interface.py``). Which
kind a layer is comes from ``PatternConfig.layer_types`` alone.

The layer is AFMoE's (``afmoe_config`` reads a published ``config.json``):
per-head RMSNorm on q and k, rotary on the kinds in ``rope_kinds`` only,
a sigmoid output gate on the attention, a norm before and after each
half, the embedding scaled under muP, and a sparse-expert FFN whose
router is float32 sigmoid, top-k on score + bias, weights from the
scores. With every extra off — ``llama_pattern`` — a layer is
``models/llama.py``'s, parameter names included.

A layer's attention has one of two forms on that skeleton
(``PatternConfig.attn_form``): ``gqa``, q / k / v straight from the
hidden state, or ``latent`` (GLM-4.7-Flash / ``glm4_moe_lite_config``,
the DeepSeek family's multi-head latent attention): a low-rank q with a
norm in the middle, one down-projection of the hidden state to a latent
(norm, then an up-projection to every head's ``k_nope`` and ``v``) and
to ONE rotary key head every query head shares, rotary on the last
``rope_head_dim`` of a head only. After the up-projection the kernels
see ``n_heads`` query and ``n_heads`` key-value heads of ``head_dim``.
``n_mtp`` multi-token-prediction modules follow the trunk
(:func:`_mtp_local`): one more layer each, fed the next token's
embedding beside the trunk's hidden state, sharing embedding and head,
with a loss on the token after the next.

``n_loops > 1`` makes the decoder a looped one (Ouro / ``ouro_config``):
the layer stack runs ``n_loops`` times on the SAME weights, the final
norm inside the loop, as the body of one ``jax.lax.scan``
(:func:`_looped_trunk_local`); every pass ends in the shared head and an
exit gate, and the loss is the expectation of the exits' per-token
losses under the gate's distribution, less ``exit_entropy_weight`` x its
entropy (:func:`_exit_losses`, :func:`exit_log_probs`).

The third attention form, ``cca`` (ZAYA1 / ``zaya_config``: compressed
convolutional attention), keeps the attention inside the projections'
latent: q and k are mixed along the sequence by two short causal
convolutions (depthwise, then one block a head), take the mean of each
other's heads and an L2 norm with a learned key temperature, half the
value heads read the token BEFORE, and the output projection follows the
kernels with no up-projection between. A token reads its predecessors
inside its document only, wherever dispatch put them
(``parallel/dispatch.shift_local`` on the plan ``build_magi_pattern``
makes beside the attention's). ZAYA1's expert half routes top-1 through
an MLP (``router_form``) whose 256-wide input carries over from layer to
layer: the first state beside ``x`` on the residual path (:func:`route`).

``diffusion_block > 0`` trains the same layers by diffusion over blocks
(SDAR / ``sdar_moe_config``; the mask of BD3-LM, arXiv:2503.09573): the
sequence is fed twice, ``[noisy ; clean]``, 2L rows that both carry the
token's own position, under the three-slice stepped mask of
``api.infer_block_diffusion_mask``; the head runs on the noisy half's
rows alone and the loss is the cross-entropy of the masked ones under
the caller's per-row weights (1/t), over the sequence's data tokens
(:meth:`MagiPattern.loss_fn`). SDAR's router is the third
``router_form``: one matrix, a softmax over all experts, top-k,
renormalised.

A decoder-hybrid-decoder (SambaY; Phi-4-mini-flash-reasoning /
``phi4flash_config``) brings three layer kinds that are not attention
over their own q, k, v, beside ``sliding_attention`` and
``full_attention``: ``state_space`` (the Mamba-1 mixer of
``models/ssm.py``), ``gated_memory`` (a unit that gates the LAST mixer's
scan output, the memory ``m``) and ``cross_attention`` (a query alone, on
the keys and values of the last ``full_attention`` layer before it). What
a layer hands on, ``m``, ``(k, v)`` and the MLP router's state, is one
carry beside ``x`` (:func:`_layer_local`): a layer's ``checkpoint`` takes
it as an input and gives it as an output, so it is kept, never
recomputed, and its cotangent is the sum over its readers. Its attention
form is ``diff`` (the Differential Transformer's, arXiv:2410.05258):
adjacent heads pair, two softmaxes on one value pair, their difference
under a learned ``lambda`` through a norm of its own
(:func:`_diff_heads`, :func:`_diff_combine`); LayerNorm with a bias in
place of the RMS norm (``norm_form``), no position encoding.

``hc_mult > 0`` widens the residual path to that many streams under
manifold-constrained hyper-connections (Xing4.0 / ``xing4_config``;
arXiv:2512.24880 over arXiv:2409.19606): the state between half-layers is
``[t, hc_mult x dim]``, stream ``i`` in lanes ``[i dim, (i + 1) dim)``;
each half-layer reads ONE hidden state as a learned per-token mix of the
streams and writes back through a per-token ``hc_mult x hc_mult`` matrix
that 20 Sinkhorn rounds make doubly stochastic (:func:`_mhc_coef`,
:func:`_mhc_read`, :func:`_mhc_write`; docs/hyper_connections.md). The
same configuration's latent attention has value heads narrower than its
key heads (``v_head_dim`` 128 beside 128 + 64): the kernels take both
widths (``KernelHeads.v_head_dim``), and YaRN stretches the rotary lanes
(``rope_yarn``).

A layer's router reads the FFN half's normed input, or
(``router_input = "attn"``; SmallThinker / ``smallthinker_config``) the
ATTENTION half's: the route is then made before the attention call, on
rows no collective has touched, and the FFN half consumes it
(:func:`_route`, a key of the layer's own ``carry``). The same
configuration's experts gate through ReLU (``expert_act``), its full
layers carry no position and its window layers rotary.

A Mamba-2 hybrid (Granite 4.0-H / ``granitemoehybrid_config``) is the
plain GQA skeleton with one more layer kind, ``state_space_dual``: the
Mamba-2 mixer of ``models/ssm.py`` on the chunked scan of
``ops/ssd_scan.py`` (nine such layers to one NoPE ``full_attention``
layer), and the family's four scalars: ``embed_scale`` on the embedding,
``residual_scale`` on both residual adds (:func:`_residual`),
``softmax_scale`` (1/64 at 64-wide heads, not 1/8) and
``logits_scaling`` under the logits. At their defaults (1, 1,
``head_dim ** -0.5``, 1) no operation is added to another
configuration's program.

Like ``llama.py`` the whole decoder runs inside one ``shard_map`` over a
(dp, cp) mesh with parameters replicated, so the train step is a single
jit (``_common.make_model_train_step``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .. import telemetry
from ..ops.flex_attn import FlexAttnParams
from ..parallel.dispatch import (
    make_shift_plan, roll, shift_local, shift_valid,
)
from ..parallel.dist_attn import DistAttnPlan, dist_attn_local
from ..utils.compat import shard_map
from ..utils.instrument import named_scope
from ._common import layer_under_remat, masked_ce_sums, masked_ce_tokens
from . import ssm
from .llama import _rms_norm, _rope

SLIDING, FULL = "sliding_attention", "full_attention"
# the kinds that are not attention over their own q, k, v
SSM, GMU, CROSS = "state_space", "gated_memory", "cross_attention"
SSD = "state_space_dual"  # the Mamba-2 mixer, under GQA
DENSE, EXPERTS = "dense", "experts"
GQA, LATENT, CCA, DIFF = "gqa", "latent", "cca", "diff"
RMS, LAYER = "rms", "layer"  # the norm's forms
SIGMOID, MLP, SOFTMAX = "sigmoid", "mlp", "softmax"  # the router's forms
_EXPERT_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}  # on the gate
_SHORT = {SLIDING: "sliding", FULL: "full"}  # spans, counters, scopes


@dataclasses.dataclass(frozen=True)
class PatternConfig:
    vocab_size: int
    dim: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    # SLIDING | FULL, a layer; under DIFF also SSM | GMU | CROSS; under GQA
    # also SSD
    layer_types: tuple[str, ...]
    ffn_types: tuple[str, ...]  # DENSE | EXPERTS, a layer
    ffn_hidden: int  # the dense FFN's width
    # keys a SLIDING layer's query sees inside its document, itself
    # included; None: the whole document, and one plan serves every layer
    sliding_window: int | None = None
    rope_theta: float = 10000.0
    rope_kinds: tuple[str, ...] = (SLIDING,)  # the others carry no position
    qk_norm: bool = True
    attn_gate: bool = True
    post_norms: bool = True
    embed_scale: float = 1.0
    # Granite's scalars beside ``embed_scale`` and ``softmax_scale``: what a
    # half-layer's output is multiplied by before the residual add, and
    # what the logits are divided by
    residual_scale: float = 1.0
    logits_scaling: float = 1.0
    rms_eps: float = 1e-5
    # the expert FFN
    n_experts: int = 0  # the router's width
    top_k: int = 0
    expert_hidden: int = 0
    n_shared_experts: int = 0
    route_norm: bool = True
    route_scale: float = 1.0
    router_dtype: str = "float32"
    # SIGMOID: sigmoid scores of one matrix. MLP (ZAYA1): the hidden state
    # down to ``router_hidden``, plus a learned per-channel weight times
    # the layer before's (the state a layer hands the next), a norm, two
    # GELU layers, softmax scores. SOFTMAX (Qwen3-MoE, SDAR): the softmax
    # of one matrix over all experts
    router_form: str = SIGMOID
    router_hidden: int = 0
    # the experts THIS rank holds, [first, last): the router stays
    # ``n_experts`` wide and top-k; a pair whose expert is held elsewhere
    # contributes nothing here (in a deployment it arrives with the
    # all-to-all's combine). None: all of them.
    expert_range: tuple[int, int] | None = None
    # the held experts' grouped matmuls take a chunk's rows whatever it
    # holds, as the gather and the scatter-add round them do: the rows past
    # the held pairs are zeros in the last group. A step then costs the
    # same wherever the router sends the tokens (top-1 of 16 swings a
    # layer's held share from 2% to 100% with the seed: PERF.md section 6,
    # PR 39), at the price of the matmuls a full chunk takes; past top-2
    # every chunk runs, where the pairs' form skips one nothing reaches
    flat_expert_rows: bool = False
    # what the router reads: "ffn", the FFN half's own normed input, or
    # "attn" (SmallThinker): the ATTENTION half's normed input, so a
    # layer's route is made before its attention call and the FFN half
    # consumes it (:func:`_attention_out`, :func:`_ffn_out`)
    router_input: str = "ffn"
    # the activation on an expert's gate: "silu" (SwiGLU) or "relu"
    # (ReGLU: a gate that is exactly zero for the rows it turns off); the
    # dense FFN and the shared expert are SwiGLU either way
    expert_act: str = "silu"
    dtype: str = "bfloat16"
    remat: bool = False
    # the attention's form, every layer's: GQA (q, k, v from the hidden
    # state), LATENT or CCA. Under CCA q and k are mixed by two causal
    # convolutions of ``conv_taps`` taps along the document and the second
    # half of the value heads reads the token before; rotary on the FIRST
    # ``rope_head_dim`` of a head. Under LATENT ``head_dim`` is a query's
    # and a key's width (a value's too unless ``v_head_dim`` says another):
    # ``head_dim - rope_head_dim`` without position and ``rope_head_dim``
    # rotary, in every layer whatever ``rope_kinds`` says;
    # ``n_kv_heads == n_heads``
    attn_form: str = GQA
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    rope_head_dim: int = 0
    conv_taps: tuple[int, int] = (0, 0)
    # logits on the embedding's rows: no ``lm_head`` parameter
    tie_embeddings: bool = False
    # multi-token-prediction modules after the trunk (0 or 1 until a
    # reference states a chain of them), each one more layer of the last
    # trunk layer's kinds; their losses weigh ``mtp_loss_weight``
    n_mtp: int = 0
    mtp_loss_weight: float = 0.3
    # passes through the layer stack on shared weights; above 1 every
    # pass ends in the head and an exit gate, and the loss is the exits'
    # expected loss less ``exit_entropy_weight`` x the exit distribution's
    # entropy (a uniform prior over exits)
    n_loops: int = 1
    exit_entropy_weight: float = 0.05
    # diffusion over blocks of this many tokens (a power of two; 0: next-
    # token training): the model is fed [noisy ; clean], twice the
    # documents' rows, under ``api.infer_block_diffusion_mask``
    diffusion_block: int = 0
    # RMS, or LAYER: LayerNorm with a weight and a bias (``<name>_b``)
    norm_form: str = RMS
    attn_bias: bool = False  # a bias on q, k, v and the output projection
    # the Mamba-1 mixer of an SSM layer and the width of the memory a GMU
    # gates: channels, states a channel, the convolution's taps, the rank
    # of the step's projection
    ssm_inner: int = 0
    ssm_state: int = 0
    ssm_conv: int = 0
    ssm_dt_rank: int = 0
    # the Mamba-2 mixer of an SSD layer: ``ssm_heads`` heads of ``ssm_inner
    # / ssm_heads`` channels, one group of ``ssm_state`` states, the scan's
    # chunk (0: the kernel's own)
    ssm_heads: int = 0
    ssm_chunk: int = 0
    scan_state_dtype: str = "float32"  # bfloat16: the benchmark's control
    # a layer's index in the published model where the layers are a cut
    # of it (DIFF's lambda_init reads it); (): the layer's own place
    layer_index: tuple[int, ...] = ()
    # LATENT: the value heads' width where it is not ``head_dim`` (the key
    # heads': DeepSeek-V3's 128 beside 128 + 64); 0: ``head_dim``
    v_head_dim: int = 0
    # YaRN on the rotary lanes (arXiv:2309.00071): (factor, beta_fast,
    # beta_slow, original_max_position_embeddings); None: plain rotary
    rope_yarn: tuple[float, float, float, int] | None = None
    # the attention's softmax scale where it is not ``head_dim ** -0.5``
    # (YaRN's ``mscale`` squared rides on it)
    softmax_scale: float | None = None
    # residual streams under manifold-constrained hyper-connections; 0: one
    # stream, the plain residual path. The Sinkhorn rounds that make a
    # half-layer's stream-to-stream matrix doubly stochastic, the epsilon
    # under their sums, and the clamp on the matrix's logits
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: tuple[float, float] = (-30.0, 30.0)
    hc_dtype: str = "float32"  # bfloat16: the tests' and the check's control

    def __post_init__(self):
        if len(self.layer_types) != len(self.ffn_types):
            raise ValueError("layer_types and ffn_types differ in length")
        bad = set(self.layer_types) - {SLIDING, FULL, SSM, GMU, CROSS, SSD}
        bad |= set(self.ffn_types) - {DENSE, EXPERTS}
        bad |= {self.attn_form} - {GQA, LATENT, CCA, DIFF}
        bad |= {self.router_form} - {SIGMOID, MLP, SOFTMAX}
        bad |= {self.norm_form} - {RMS, LAYER}
        bad |= {self.router_input} - {"ffn", "attn"}
        bad |= {self.expert_act} - set(_EXPERT_ACTS)
        if bad:
            raise ValueError(f"unknown layer kinds {sorted(bad)}")
        self._check_handed_on()
        if self.attn_form == LATENT and not (
            self.n_kv_heads == self.n_heads
            and 0 < self.rope_head_dim < self.head_dim
            and self.q_lora_rank > 0 and self.kv_lora_rank > 0
        ):
            raise ValueError(
                "latent attention needs n_kv_heads == n_heads, both ranks "
                "and 0 < rope_head_dim < head_dim"
            )
        if self.attn_form == CCA and not (
            self.n_heads % self.n_kv_heads == 0
            and self.n_kv_heads % 2 == 0
            and min(self.conv_taps) >= 1
            and 0 < self.rope_head_dim <= self.head_dim
        ):
            raise ValueError(
                "cca needs whole query groups, an even number of key-value "
                "heads (half read the token before), both convolutions' "
                "taps and 0 < rope_head_dim <= head_dim"
            )
        if self.attn_form == CCA and (
            self.sliding_window is not None or self.n_mtp or self.n_loops > 1
        ):
            raise ValueError(
                "cca under a window, an MTP module or a loop: no reference "
                "states one"
            )
        if self.router_form == MLP and (self.router_hidden < 1 or self.n_mtp):
            raise ValueError(
                "the MLP router needs router_hidden, and hands its state "
                "from layer to layer: an MTP module has no layer before"
            )
        if self.router_input == "attn" and (
            self.router_form == MLP or self.hc_mult
        ):
            raise ValueError(
                "router_input 'attn' with an MLP router's state or under "
                "residual streams (hc_mult): no reference states one"
            )
        if self.n_mtp > 1:
            raise ValueError(
                "more than one MTP module: no reference states the chain"
            )
        if self.diffusion_block and (
            self.sliding_window is not None or self.n_mtp
            or self.n_loops > 1 or self.attn_form == CCA
        ):
            raise ValueError(
                "diffusion over blocks under a window, an MTP module, a "
                "loop or cca: no reference states one"
            )
        if self.v_head_dim and self.attn_form != LATENT:
            raise ValueError(
                "v_head_dim goes with latent attention: the other forms "
                "cut q, k and v out of one width"
            )
        if self.hc_mult and (
            self.attn_form != LATENT or self.n_loops > 1
            or self.diffusion_block or self.post_norms
        ):
            raise ValueError(
                "residual streams (hc_mult) are stated for the latent form "
                "without loops, diffusion or post-norms"
            )
        if self.n_loops < 1:
            raise ValueError(f"n_loops {self.n_loops}: at least one pass")
        if self.n_loops > 1 and (EXPERTS in self.ffn_types or self.n_mtp):
            raise ValueError(
                "a looped decoder with experts or an MTP module: no "
                "reference states one"
            )

    def _check_handed_on(self):
        kinds = self.layer_types
        if SSD in kinds:
            if (
                self.attn_form != GQA or self.hc_mult or self.n_loops > 1
                or self.diffusion_block or self.n_mtp
                or EXPERTS in self.ffn_types
            ):
                raise ValueError(
                    "state-space-dual layers are stated beside plain GQA "
                    "layers and dense FFNs: no reference states another"
                )
            if min(
                self.ssm_inner, self.ssm_state, self.ssm_conv, self.ssm_heads
            ) < 1 or self.ssm_inner % self.ssm_heads:
                raise ValueError(
                    "a state-space-dual layer needs the mixer's sizes: "
                    "ssm_inner in whole heads, ssm_state, ssm_conv"
                )
        if self.residual_scale != 1.0 and self.hc_mult:
            raise ValueError(
                "a residual multiplier under residual streams (hc_mult): "
                "no reference states one"
            )
        if self.attn_form != DIFF:
            if set(kinds) & {SSM, GMU, CROSS}:
                raise ValueError(
                    "state-space, gated-memory and cross layers go with "
                    "attn_form 'diff': no reference states another"
                )
            return
        if self.layer_index and len(self.layer_index) != len(kinds):
            raise ValueError("layer_index and layer_types differ in length")
        if (
            self.n_heads % self.n_kv_heads or self.n_kv_heads % 2
            or self.qk_norm or self.attn_gate or self.post_norms
            or self.rope_kinds or self.n_mtp or self.n_loops > 1
            or self.diffusion_block or EXPERTS in self.ffn_types
        ):
            raise ValueError(
                "differential attention pairs adjacent heads (an even "
                "number of key-value heads, whole query groups) and is "
                "stated without qk-norm, gate, post-norms, rotary, MTP, "
                "loops, diffusion or experts"
            )
        for i, kind in enumerate(kinds):
            if kind == CROSS and FULL not in kinds[:i]:
                raise ValueError(
                    f"layer {i} is cross attention with no full_attention "
                    "layer before it to make its keys and values"
                )
            if kind == GMU and SSM not in kinds[:i]:
                raise ValueError(
                    f"layer {i} is a gated memory unit with no state-space "
                    "layer before it to make its memory"
                )
        if SSM in kinds and min(
            self.ssm_inner, self.ssm_state, self.ssm_conv, self.ssm_dt_rank
        ) < 1:
            raise ValueError("a state-space layer needs the mixer's four sizes")
        if GMU in kinds and self.ssm_inner < 1:
            raise ValueError("a gated memory unit needs ssm_inner")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def memory_layer(self) -> int | None:
        """The layer whose scan output the gated memory units read: the
        last state-space layer before the first of them."""
        if GMU not in self.layer_types:
            return None
        first = self.layer_types.index(GMU)
        return max(i for i in range(first) if self.layer_types[i] == SSM)

    @property
    def kv_layer(self) -> int | None:
        """The layer whose keys and values the cross layers read: the
        last full-attention layer before the first of them."""
        if CROSS not in self.layer_types:
            return None
        first = self.layer_types.index(CROSS)
        return max(i for i in range(first) if self.layer_types[i] == FULL)

    @property
    def kernel_heads(self):
        """What the flex kernels are planned and tuned for: this
        configuration, or under DIFF its two head widths made one
        (:class:`KernelHeads`)."""
        if self.attn_form == LATENT and self.v_head_dim:
            return KernelHeads(
                n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
                head_dim=self.kernel_qk_lanes, dtype=self.dtype,
                softmax_scale=self.softmax_scale or self.head_dim ** -0.5,
                v_head_dim=self.v_head_dim,
            )
        if self.attn_form != DIFF:
            return self
        return KernelHeads(
            n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            head_dim=2 * self.head_dim, dtype=self.dtype,
            softmax_scale=self.head_dim ** -0.5,
        )

    @property
    def kernel_qk_lanes(self) -> int:
        """The lanes q and k ride into the kernels on under LATENT with a
        value width of its own: ``head_dim`` up to a multiple of
        :data:`LATENT_QK_LANES`, zeros past the head's own (the logits are
        the head's; the published 192 is not padded)."""
        return -(-self.head_dim // LATENT_QK_LANES) * LATENT_QK_LANES

    @property
    def value_dim(self) -> int:
        return self.v_head_dim or self.head_dim

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def shift_taps(self) -> tuple[int, ...]:
        """How far back a layer reads outside the attention call: the two
        convolutions one after the other (and the value's one token)."""
        if {SSM, SSD} & set(self.layer_types):  # the mixer's convolution
            return tuple(range(1, self.ssm_conv))
        if self.attn_form != CCA:
            return ()
        return tuple(range(1, max(sum(self.conv_taps) - 2, 1) + 1))

    @property
    def held_experts(self) -> tuple[int, int]:
        return self.expert_range or (0, self.n_experts)

    def plan_kind(self, layer_type: str) -> str | None:
        """The plan a layer's attention runs on; None: no attention."""
        if layer_type in (SSM, GMU, SSD):
            return None
        if layer_type == SLIDING and self.sliding_window is not None:
            return SLIDING
        return FULL

    @property
    def plan_kinds(self) -> tuple[str, ...]:
        """The plans the model needs, the one that decides the dispatch
        first: FULL where a layer has it (the larger area decides the
        balance)."""
        kinds = {self.plan_kind(t) for t in self.layer_types}
        return tuple(k for k in (FULL, SLIDING) if k in kinds)


@dataclasses.dataclass(frozen=True)
class KernelHeads:
    """The heads the flex kernels see where they are not the model's own
    (``_common.plan_flex_attn`` reads these six). Under DIFF a query
    and a key head of ``head_dim`` lanes ride a kernel head of twice
    that, zeros in the other half, beside a value pair that fills it;
    the softmax scale stays the published head's. Under LATENT with a
    value width of its own q and k are ``head_dim`` lanes (the published
    192), v and out ``v_head_dim``."""

    n_heads: int
    n_kv_heads: int
    head_dim: int
    dtype: str
    softmax_scale: float
    v_head_dim: int = 0  # 0: head_dim


# What q and k of a latent head with a value width of its own are padded
# to a multiple of before the kernels: half a vreg's lanes, so the published
# 192 rides as it is, a block of one and a half vregs. On the chip that read
# 0.78% more tokens a second than 192 on 256 lanes, zeros in the last 64
# (12,496 against 12,399 on one seed, 655.9 against 660.9 ms a step; my chip
# runs, PR 49, PERF.md section 6); 128 here is the padded form.
LATENT_QK_LANES = 64


def afmoe_config(
    hf: dict,
    *,
    dtype: str = "bfloat16",
    remat: bool = False,
    expert_range: tuple[int, int] | None = None,
    vocab_size: int | None = None,
) -> PatternConfig:
    """A published AFMoE ``config.json`` (Trinity) as a pattern.
    ``expert_range`` and ``vocab_size`` give one rank's share of an
    expert-parallel, vocabulary-parallel deployment."""
    n = int(hf["num_hidden_layers"])
    types = tuple(hf["layer_types"])
    if len(types) != n:
        raise ValueError(f"{len(types)} layer_types for {n} layers")
    n_dense = int(hf["num_dense_layers"])
    dim = int(hf["hidden_size"])
    return PatternConfig(
        vocab_size=int(vocab_size or hf["vocab_size"]),
        dim=dim,
        n_heads=int(hf["num_attention_heads"]),
        n_kv_heads=int(hf["num_key_value_heads"]),
        head_dim=int(hf["head_dim"]),
        layer_types=types,
        ffn_types=tuple(DENSE if i < n_dense else EXPERTS for i in range(n)),
        ffn_hidden=int(hf["intermediate_size"]),
        sliding_window=int(hf["sliding_window"]),
        rope_theta=float(hf["rope_theta"]),
        embed_scale=float(np.sqrt(dim)) if hf.get("mup_enabled") else 1.0,
        rms_eps=float(hf["rms_norm_eps"]),
        n_experts=int(hf["num_experts"]),
        top_k=int(hf["num_experts_per_tok"]),
        expert_hidden=int(hf["moe_intermediate_size"]),
        n_shared_experts=int(hf["num_shared_experts"]),
        route_norm=bool(hf["route_norm"]),
        route_scale=float(hf["route_scale"]),
        expert_range=expert_range,
        dtype=dtype,
        remat=remat,
    )


def glm4_moe_lite_config(
    hf: dict,
    *,
    dtype: str = "bfloat16",
    remat: bool = False,
    expert_range: tuple[int, int] | None = None,
    vocab_size: int | None = None,
    **more,
) -> PatternConfig:
    """A published ``glm4_moe_lite`` ``config.json`` (GLM-4.7-Flash) as a
    pattern: latent attention in every layer, all of them
    ``full_attention`` (one plan), ``first_k_dense_replace`` dense layers
    and then sparse experts under the ``noaux_tc`` router at one group
    (``route``), ``num_nextn_predict_layers`` MTP modules. A
    ``v_head_dim`` that is not the key heads' ``qk_nope_head_dim +
    qk_rope_head_dim`` is the value heads' own width (DeepSeek-V3's 128
    beside 192). ``expert_range`` and ``vocab_size`` give one rank's
    share, as in :func:`afmoe_config`. ``mtp_loss_weight`` is no published
    key: a configuration file may state it. ``more``: fields another
    configuration of the family sets on top (:func:`xing4_config`)."""
    n = int(hf["num_hidden_layers"])
    n_dense = int(hf["first_k_dense_replace"])
    heads = int(hf["num_attention_heads"])
    nope, rope = int(hf["qk_nope_head_dim"]), int(hf["qk_rope_head_dim"])
    v_dim = int(hf["v_head_dim"])
    if int(hf["num_key_value_heads"]) != heads:
        raise ValueError(
            "latent attention up-projects one key-value head a query head"
        )
    if int(hf.get("n_group", 1)) != 1 or int(hf.get("topk_group", 1)) != 1:
        raise ValueError("group-limited routing (n_group > 1) is not built")
    return PatternConfig(**{**dict(
        vocab_size=int(vocab_size or hf["vocab_size"]),
        dim=int(hf["hidden_size"]),
        n_heads=heads,
        n_kv_heads=heads,
        head_dim=nope + rope,
        layer_types=(FULL,) * n,
        ffn_types=tuple(DENSE if i < n_dense else EXPERTS for i in range(n)),
        ffn_hidden=int(hf["intermediate_size"]),
        rope_theta=float(hf["rope_theta"]),
        rope_kinds=(FULL,),
        qk_norm=False,
        attn_gate=False,
        post_norms=False,
        rms_eps=float(hf["rms_norm_eps"]),
        n_experts=int(hf["n_routed_experts"]),
        top_k=int(hf["num_experts_per_tok"]),
        expert_hidden=int(hf["moe_intermediate_size"]),
        n_shared_experts=int(hf["n_shared_experts"]),
        route_norm=bool(hf["norm_topk_prob"]),
        route_scale=float(hf["routed_scaling_factor"]),
        expert_range=expert_range,
        dtype=dtype,
        remat=remat,
        attn_form=LATENT,
        q_lora_rank=int(hf["q_lora_rank"]),
        kv_lora_rank=int(hf["kv_lora_rank"]),
        rope_head_dim=rope,
        v_head_dim=0 if v_dim == nope + rope else v_dim,
        n_mtp=int(hf.get("num_nextn_predict_layers", 0)),
        mtp_loss_weight=float(hf.get("mtp_loss_weight", 0.3)),
    ), **more})


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor ``0.1 mscale ln(factor) + 1`` (1 at a
    factor of 1 or less)."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * float(np.log(factor)) + 1.0


def xing4_config(
    hf: dict,
    *,
    dtype: str = "bfloat16",
    remat: bool = False,
    expert_range: tuple[int, int] | None = None,
    vocab_size: int | None = None,
) -> PatternConfig:
    """A published ``xing4_0`` ``config.json`` (Xing4.0-29B-A4B) as a
    pattern: :func:`glm4_moe_lite_config`'s decoder (latent attention, the
    ``noaux_tc`` router, MTP modules) with value heads of ``v_head_dim``,
    ``hc_mult`` residual streams under manifold-constrained
    hyper-connections (``hc_sinkhorn_iters``, ``hc_eps``,
    ``mhc_h_res_clamp_min`` / ``_max``) and YaRN ``rope_scaling`` on the
    rotary lanes. As DeepSeek-V3 has it, ``mscale`` over ``mscale_all_dim``
    scales cos and sin (1 here, and anything else raises: no reference
    states it) and ``mscale_all_dim``'s factor squared goes on the softmax
    scale.

    ``flat_expert_rows`` is set, as ISSUE 49 asks of a cell whose rate
    spreads between seeds: under AdamW at 3e-4 the seeded router leaves the
    held eighth of the experts inside a window (4,400 pairs a layer on the
    seed's weights, 130 to 465 as the window closes), so a step's grouped
    matmuls would follow the seed and the step count; with every chunk run
    whole they take the ``top_k t`` pair rows a rank of the deployment
    computes (PERF.md section 6, PR 49)."""
    more = dict(
        flat_expert_rows=True,
        hc_mult=int(hf["hc_mult"]),
        hc_sinkhorn_iters=int(hf["hc_sinkhorn_iters"]),
        hc_eps=float(hf["hc_eps"]),
        hc_clamp=(
            float(hf["mhc_h_res_clamp_min"]), float(hf["mhc_h_res_clamp_max"])
        ),
    )
    scaling = hf.get("rope_scaling")
    if scaling:
        if scaling["type"] != "yarn":
            raise ValueError(f"rope_scaling {scaling['type']!r} is not built")
        factor = float(scaling["factor"])
        all_dim = float(scaling.get("mscale_all_dim", 0.0))
        if yarn_mscale(factor, float(scaling.get("mscale", 1.0))) != (
            yarn_mscale(factor, all_dim)
        ):
            raise ValueError(
                "YaRN with mscale != mscale_all_dim scales cos and sin: no "
                "reference states it"
            )
        head_dim = int(hf["qk_nope_head_dim"]) + int(hf["qk_rope_head_dim"])
        more.update(
            rope_yarn=(
                factor, float(scaling["beta_fast"]),
                float(scaling["beta_slow"]),
                int(scaling["original_max_position_embeddings"]),
            ),
            softmax_scale=head_dim ** -0.5 * yarn_mscale(factor, all_dim) ** 2,
        )
    return glm4_moe_lite_config(
        hf, dtype=dtype, remat=remat, expert_range=expert_range,
        vocab_size=vocab_size, **more,
    )


def zaya_config(
    hf: dict,
    *,
    dtype: str = "bfloat16",
    remat: bool = False,
    expert_range: tuple[int, int] | None = None,
    vocab_size: int | None = None,
) -> PatternConfig:
    """A published ``zaya`` ``config.json`` (ZAYA1-8B) as a pattern: every
    layer ``hybrid``, compressed convolutional attention (``cca_time0``
    and ``cca_time1`` the two kernels; rotary on ``partial_rotary_factor``
    of a head) and then top-``num_experts_per_tok`` experts behind the
    MLP router of ``router_hidden_size``; no window, one plan; the
    embedding tied. ``expert_range`` and ``vocab_size`` give one rank's
    share, as in :func:`afmoe_config`."""
    n = int(hf["num_hidden_layers"])
    # a cut in depth keeps the published list and runs its first n
    if set(hf["layer_types"][:n]) != {"hybrid"} or len(hf["layer_types"]) < n:
        raise ValueError(
            f"layer_types {sorted(set(hf['layer_types']))} for {n} layers: "
            "every layer of the model written down is 'hybrid'"
        )
    if hf.get("sliding_window") is not None:
        raise ValueError("a zaya configuration with a window is not built")
    head_dim = int(hf["head_dim"])
    rope = hf["rope_parameters"]["hybrid"]
    return PatternConfig(
        vocab_size=int(vocab_size or hf["vocab_size"]),
        dim=int(hf["hidden_size"]),
        n_heads=int(hf["num_attention_heads"]),
        n_kv_heads=int(hf["num_key_value_heads"]),
        head_dim=head_dim,
        layer_types=(FULL,) * n,
        ffn_types=(EXPERTS,) * n,
        ffn_hidden=0,
        rope_theta=float(rope["rope_theta"]),
        rope_kinds=(FULL,),
        qk_norm=False,
        attn_gate=False,
        post_norms=False,
        rms_eps=float(hf["rms_norm_eps"]),
        n_experts=int(hf["num_experts"]),
        top_k=int(hf["num_experts_per_tok"]),
        expert_hidden=int(hf["moe_intermediate_size"]),
        route_norm=False,
        router_form=MLP,
        router_hidden=int(hf["router_hidden_size"]),
        expert_range=expert_range,
        flat_expert_rows=True,
        dtype=dtype,
        remat=remat,
        attn_form=CCA,
        rope_head_dim=int(head_dim * float(rope["partial_rotary_factor"])),
        conv_taps=(int(hf["cca_time0"]), int(hf["cca_time1"])),
        tie_embeddings=bool(hf["tie_word_embeddings"]),
    )


def sdar_moe_config(
    hf: dict,
    *,
    dtype: str = "bfloat16",
    remat: bool = False,
    expert_range: tuple[int, int] | None = None,
    vocab_size: int | None = None,
) -> PatternConfig:
    """A published ``sdar_moe`` ``config.json`` (SDAR-30B-A3B) as a
    pattern: a qk-normed GQA decoder, rotary in every layer, no window,
    every layer ``num_experts`` experts behind the softmax router
    (``norm_topk_prob``), no shared expert, untied head; trained by
    diffusion over blocks of ``hf["block_length"]`` tokens (the published
    file does not state it: the caller's configuration does).
    ``expert_range`` and ``vocab_size`` give one rank's share, as in
    :func:`afmoe_config`.

    ``flat_expert_rows`` is set, as ISSUE 42 asks of a cell whose rate
    spreads between seeds: every chunk of the held experts' pairs runs,
    so a step's work does not follow the seed's router (PERF.md section
    6, PR 42)."""
    n = int(hf["num_hidden_layers"])
    if hf.get("mlp_only_layers") or int(hf.get("decoder_sparse_step", 1)) != 1:
        raise ValueError(
            "an sdar_moe configuration with dense layers is not built"
        )
    if hf.get("use_sliding_window") or hf.get("sliding_window") is not None:
        raise ValueError("an sdar_moe configuration with a window is not built")
    return PatternConfig(
        vocab_size=int(vocab_size or hf["vocab_size"]),
        dim=int(hf["hidden_size"]),
        n_heads=int(hf["num_attention_heads"]),
        n_kv_heads=int(hf["num_key_value_heads"]),
        head_dim=int(hf["head_dim"]),
        layer_types=(FULL,) * n,
        ffn_types=(EXPERTS,) * n,
        ffn_hidden=int(hf["intermediate_size"]),
        rope_theta=float(hf["rope_theta"]),
        rope_kinds=(FULL,),
        qk_norm=True,
        attn_gate=False,
        post_norms=False,
        rms_eps=float(hf["rms_norm_eps"]),
        n_experts=int(hf["num_experts"]),
        top_k=int(hf["num_experts_per_tok"]),
        expert_hidden=int(hf["moe_intermediate_size"]),
        route_norm=bool(hf["norm_topk_prob"]),
        router_form=SOFTMAX,
        expert_range=expert_range,
        flat_expert_rows=True,
        dtype=dtype,
        remat=remat,
        tie_embeddings=bool(hf["tie_word_embeddings"]),
        diffusion_block=int(hf["block_length"]),
    )


def smallthinker_config(
    hf: dict,
    *,
    dtype: str = "bfloat16",
    remat: bool = False,
    expert_range: tuple[int, int] | None = None,
    vocab_size: int | None = None,
) -> PatternConfig:
    """A published ``smallthinker`` ``config.json`` (SmallThinker-21BA3B,
    arXiv:2507.20984) as a pattern: a GQA decoder with no qk-norm, gate,
    bias or post-norm; a layer whose ``sliding_window_layout`` entry is 1
    sees ``sliding_window_size`` keys (itself included) and carries
    rotary, a layer at 0 sees its whole document and carries no position
    (``rope_layout``: the two lists have to agree, since a
    ``PatternConfig`` says which kinds carry rotary, not which layers);
    every layer ``moe_num_primary_experts`` ReLU-gated experts, top
    ``moe_num_active_primary_experts`` of a softmax router that reads the
    ATTENTION half's normed input (``router_input``), renormalised over
    the chosen (``norm_topk_prob``); no shared expert, untied head. The
    layouts are read up to ``num_hidden_layers`` (a cut keeps the
    published lists whole). ``expert_range`` and ``vocab_size`` give one
    rank's share, as in :func:`afmoe_config`; ``flat_expert_rows`` is the
    configuration file's own key (absent: off)."""
    n = int(hf["num_hidden_layers"])
    rope = [int(v) for v in hf["rope_layout"][:n]]
    window = [int(v) for v in hf["sliding_window_layout"][:n]]
    if len(rope) != n or len(window) != n:
        raise ValueError(
            f"rope_layout / sliding_window_layout are shorter than the "
            f"{n} layers"
        )
    if rope != window or set(rope) - {0, 1}:
        raise ValueError(
            "a smallthinker configuration whose rope_layout and "
            "sliding_window_layout differ (a layer windowed without "
            "rotary, or rotary without a window) is not built: "
            f"{rope} / {window}"
        )
    if not hf["moe_primary_router_apply_softmax"]:
        raise ValueError(
            "moe_primary_router_apply_softmax false (sigmoid scores, then "
            "renormalised) is not built"
        )
    if hf.get("rope_scaling"):
        raise ValueError("a smallthinker configuration with rope_scaling")
    return PatternConfig(
        vocab_size=int(vocab_size or hf["vocab_size"]),
        dim=int(hf["hidden_size"]),
        n_heads=int(hf["num_attention_heads"]),
        n_kv_heads=int(hf["num_key_value_heads"]),
        head_dim=int(hf["head_dim"]),
        layer_types=tuple(SLIDING if w else FULL for w in window),
        ffn_types=(EXPERTS,) * n,
        ffn_hidden=0,  # no dense layer
        sliding_window=int(hf["sliding_window_size"]),
        rope_theta=float(hf["rope_theta"]),
        rope_kinds=(SLIDING,),
        qk_norm=False,
        attn_gate=False,
        post_norms=False,
        rms_eps=float(hf["rms_norm_eps"]),
        n_experts=int(hf["moe_num_primary_experts"]),
        top_k=int(hf["moe_num_active_primary_experts"]),
        expert_hidden=int(hf["moe_ffn_hidden_size"]),
        route_norm=bool(hf["norm_topk_prob"]),
        router_form=SOFTMAX,
        router_input="attn",
        expert_act="relu",
        expert_range=expert_range,
        flat_expert_rows=bool(hf.get("flat_expert_rows", False)),
        dtype=dtype,
        remat=remat,
        tie_embeddings=bool(hf["tie_word_embeddings"]),
    )


def ouro_config(
    hf: dict, *, dtype: str = "bfloat16", remat: bool = False
) -> PatternConfig:
    """A published ``ouro`` ``config.json`` (Ouro-2.6B, a looped decoder)
    as a pattern: every layer ``full_attention`` with rotary and a dense
    SwiGLU, a norm before and after each half, and ``total_ut_steps``
    passes through the stack. ``exit_entropy_weight`` is no published
    key: a configuration file may state it."""
    n = int(hf["num_hidden_layers"])
    if hf.get("sliding_window") is not None or hf.get("use_sliding_window"):
        raise ValueError("an ouro configuration with a window is not built")
    return PatternConfig(
        vocab_size=int(hf["vocab_size"]),
        dim=int(hf["hidden_size"]),
        n_heads=int(hf["num_attention_heads"]),
        n_kv_heads=int(hf["num_key_value_heads"]),
        head_dim=int(hf["head_dim"]),
        layer_types=(FULL,) * n,
        ffn_types=(DENSE,) * n,
        ffn_hidden=int(hf["intermediate_size"]),
        rope_theta=float(hf["rope_theta"]),
        rope_kinds=(FULL,),
        qk_norm=False,
        attn_gate=False,
        post_norms=True,
        rms_eps=float(hf["rms_norm_eps"]),
        dtype=dtype,
        remat=remat,
        n_loops=int(hf["total_ut_steps"]),
        exit_entropy_weight=float(hf.get("exit_entropy_weight", 0.05)),
    )


def phi4flash_kinds(n_layers: int, mb_per_layer: int) -> tuple[str, ...]:
    """A published ``phi4flash`` depth as layer kinds: the self-decoder's
    first half alternates a Mamba mixer (every ``mb_per_layer``-th layer,
    from 0) with window attention; the mixer at the half hands on its
    scan, the layer after it is the one full attention and hands on its
    keys and values; from there gated memory units alternate with cross
    attention."""
    half = n_layers // 2

    def kind(i):
        if i % mb_per_layer == 0:
            return SSM if i <= half else GMU
        return SLIDING if i < half else FULL if i == half + 1 else CROSS

    return tuple(kind(i) for i in range(n_layers))


def phi4flash_config(
    hf: dict,
    *,
    dtype: str = "bfloat16",
    remat: bool = False,
    vocab_size: int | None = None,
    layers: Sequence[int] | None = None,
) -> PatternConfig:
    """A published ``phi4flash`` ``config.json``
    (Phi-4-mini-flash-reasoning) as a pattern: :func:`phi4flash_kinds`
    from ``num_hidden_layers`` and ``mb_per_layer``, differential
    attention at ``hidden_size / num_attention_heads`` a head, LayerNorm
    with a bias, no position encoding, a dense SwiGLU in every layer, the
    embedding tied. ``layers`` keeps those published layers alone, each
    with its published index (the depth read is then
    ``num_hidden_layers_published``, where the file states it);
    ``vocab_size`` gives one rank's share of the rows. The mixer's sizes
    are no published keys: ``hf`` may state ``d_state``, ``d_conv``,
    ``expand`` and ``dt_rank`` (Mamba-1's defaults otherwise)."""
    depth = int(hf.get("num_hidden_layers_published", hf["num_hidden_layers"]))
    kinds = phi4flash_kinds(depth, int(hf["mb_per_layer"]))
    keep = tuple(range(depth)) if layers is None else tuple(layers)
    dim, heads = int(hf["hidden_size"]), int(hf["num_attention_heads"])
    return PatternConfig(
        vocab_size=int(vocab_size or hf["vocab_size"]),
        dim=dim,
        n_heads=heads,
        n_kv_heads=int(hf["num_key_value_heads"]),
        head_dim=dim // heads,
        layer_types=tuple(kinds[i] for i in keep),
        ffn_types=(DENSE,) * len(keep),
        ffn_hidden=int(hf["intermediate_size"]),
        sliding_window=int(hf["sliding_window"]),
        rope_kinds=(),
        qk_norm=False,
        attn_gate=False,
        post_norms=False,
        rms_eps=float(hf["layer_norm_eps"]),
        dtype=dtype,
        remat=remat,
        attn_form=DIFF,
        tie_embeddings=bool(hf["tie_word_embeddings"]),
        norm_form=LAYER,
        attn_bias=True,
        ssm_inner=int(hf.get("expand", 2)) * dim,
        ssm_state=int(hf.get("d_state", 16)),
        ssm_conv=int(hf.get("d_conv", 4)),
        ssm_dt_rank=int(hf.get("dt_rank", -(-dim // 16))),
        layer_index=keep,
    )


def granitemoehybrid_config(
    hf: dict,
    *,
    dtype: str = "bfloat16",
    remat: bool = False,
    vocab_size: int | None = None,
) -> PatternConfig:
    """A published ``granitemoehybrid`` ``config.json`` (Granite 4.0-H) as
    a pattern: ``layer_types`` read up to ``num_hidden_layers`` (a cut
    keeps the published list whole), ``mamba`` the Mamba-2 mixer (SSD)
    and ``attention`` GQA over the whole document with no position
    (``position_embedding_type`` nope) at ``hidden_size /
    num_attention_heads`` a head; every layer the shared dense SwiGLU
    (``num_local_experts`` 0 is the only form built); RMSNorm, no bias but
    the convolution's, the embedding tied; and the family's four scalars:
    ``embedding_multiplier`` on the embedding, ``residual_multiplier`` on
    both residual adds, ``attention_multiplier`` as the softmax scale,
    ``logits_scaling`` under the logits. ``vocab_size`` gives one rank's
    share of the rows."""
    n = int(hf["num_hidden_layers"])
    kinds = list(hf["layer_types"][:n])
    if len(kinds) != n or set(kinds) - {"mamba", "attention"}:
        raise ValueError(f"layer_types of {n} layers: {kinds}")
    if hf.get("num_local_experts") or hf.get("num_experts_per_tok"):
        raise ValueError("a granitemoehybrid configuration with experts is not built")
    if hf["position_embedding_type"] != "nope" or hf.get("rope_scaling"):
        raise ValueError("a granitemoehybrid configuration with rotary is not built")
    if (
        hf["mamba_n_groups"] != 1 or not hf["mamba_conv_bias"]
        or hf["mamba_proj_bias"] or hf["attention_bias"]
        or hf.get("normalization_function", "rmsnorm") != "rmsnorm"
    ):
        raise ValueError(
            "built: one group, a bias on the convolution alone, RMSNorm"
        )
    dim, heads = int(hf["hidden_size"]), int(hf["num_attention_heads"])
    inner = int(hf["mamba_expand"]) * dim
    if inner != int(hf["mamba_n_heads"]) * int(hf["mamba_d_head"]):
        raise ValueError("mamba_expand x hidden_size is not the heads' width")
    return PatternConfig(
        vocab_size=int(vocab_size or hf["vocab_size"]),
        dim=dim,
        n_heads=heads,
        n_kv_heads=int(hf["num_key_value_heads"]),
        head_dim=int(hf.get("head_dim") or dim // heads),
        layer_types=tuple(SSD if k == "mamba" else FULL for k in kinds),
        ffn_types=(DENSE,) * n,
        ffn_hidden=int(hf["shared_intermediate_size"]),
        rope_kinds=(),
        qk_norm=False,
        attn_gate=False,
        post_norms=False,
        embed_scale=float(hf["embedding_multiplier"]),
        residual_scale=float(hf["residual_multiplier"]),
        logits_scaling=float(hf["logits_scaling"]),
        softmax_scale=float(hf["attention_multiplier"]),
        rms_eps=float(hf["rms_norm_eps"]),
        dtype=dtype,
        remat=remat,
        tie_embeddings=bool(hf["tie_word_embeddings"]),
        ssm_inner=inner,
        ssm_state=int(hf["mamba_d_state"]),
        ssm_conv=int(hf["mamba_d_conv"]),
        ssm_heads=int(hf["mamba_n_heads"]),
        ssm_chunk=int(hf["mamba_chunk_size"]),
    )


def llama_pattern(cfg) -> PatternConfig:
    """``models/llama.py``'s decoder as a pattern: every layer (full,
    dense), rotary everywhere, the AFMoE extras off. ``init_params`` of
    ``llama.py`` makes its parameters."""
    n = cfg.n_layers
    return PatternConfig(
        vocab_size=cfg.vocab_size, dim=cfg.dim, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        layer_types=(FULL,) * n, ffn_types=(DENSE,) * n,
        ffn_hidden=cfg.ffn_hidden, rope_theta=cfg.rope_theta,
        rope_kinds=(FULL,), qk_norm=False, attn_gate=False,
        post_norms=False, dtype=cfg.dtype, remat=cfg.remat,
    )


def _dense_init(key, shape):
    return jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[-2])


def _ones(*shape):
    return jnp.ones(shape, jnp.float32)


def _init_layer(key: jax.Array, cfg: PatternConfig, ffn: str,
                layer_type: str = FULL) -> dict:
    dense, ones = _dense_init, _ones
    hq, hk = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    e0, e1 = cfg.held_experts
    k = jax.random.split(key, 12)
    if layer_type == SSM:
        layer = ssm.init_mamba(jax.random.fold_in(key, 4), cfg)
    elif layer_type == SSD:
        layer = ssm.init_mamba2(jax.random.fold_in(key, 4), cfg)
    elif layer_type == GMU:
        layer = ssm.init_gmu(jax.random.fold_in(key, 4), cfg)
    elif layer_type == CROSS:  # a query alone
        layer = {
            "wq": dense(k[0], (cfg.dim, hq)),
            "wo": dense(k[3], (hq, cfg.dim)),
        }
    elif cfg.attn_form == LATENT:
        ka = jax.random.split(jax.random.fold_in(key, 1), 5)
        nope = cfg.head_dim - cfg.rope_head_dim
        layer = {
            "wq_a": dense(ka[0], (cfg.dim, cfg.q_lora_rank)),
            "q_a_norm": ones(cfg.q_lora_rank),
            "wq_b": dense(ka[1], (cfg.q_lora_rank, hq)),
            # the latent's columns, then the shared rotary key's
            "wkv_a": dense(
                ka[2], (cfg.dim, cfg.kv_lora_rank + cfg.rope_head_dim)
            ),
            "kv_a_norm": ones(cfg.kv_lora_rank),
            # a head's k_nope columns, then its v columns
            "wkv_b": dense(
                ka[3],
                (cfg.kv_lora_rank, cfg.n_heads * (nope + cfg.value_dim)),
            ),
            "wo": dense(ka[4], (cfg.n_heads * cfg.value_dim, cfg.dim)),
        }
    else:
        layer = {
            "wq": dense(k[0], (cfg.dim, hq)),
            "wk": dense(k[1], (cfg.dim, hk)),
            "wv": dense(k[2], (cfg.dim, hk)),
            "wo": dense(k[3], (hq, cfg.dim)),
        }
    if cfg.attn_form == CCA:
        kc = jax.random.split(jax.random.fold_in(key, 2), 5)
        t0, t1 = cfg.conv_taps
        groups = cfg.n_heads + cfg.n_kv_heads
        layer.update({
            # q's channels, then k's: a weight a channel a tap (tap j
            # reads the token j before), then a block a head a tap
            "cca_conv1_w": dense(kc[0], (t0, hq + hk)),
            "cca_conv1_b": 0.02 * jax.random.normal(kc[1], (hq + hk,)),
            "cca_conv2_w": dense(
                kc[2], (t1, groups, cfg.head_dim, cfg.head_dim)
            ),
            "cca_conv2_b": 0.02 * jax.random.normal(kc[3], (hq + hk,)),
            # a key head's temperature
            "cca_temp": 1.0 + 0.1 * jax.random.normal(
                kc[4], (cfg.n_kv_heads,)
            ),
        })
    if cfg.attn_form == DIFF and layer_type not in (SSM, GMU):
        kd = jax.random.split(jax.random.fold_in(key, 5), 4 + 4)
        # lambda's four vectors N(0, DIFF_LAMBDA_STD): lambda leaves
        # lambda_init and carries gradient; the norm of a pair's
        # difference, 2 heads wide
        for name, kk in zip(
            ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"), kd
        ):
            layer[name] = DIFF_LAMBDA_STD * jax.random.normal(
                kk, (cfg.head_dim,), jnp.float32
            )
        layer["diff_norm"] = ones(2 * cfg.head_dim)
        if cfg.attn_bias:
            for name, kk in zip(("bq", "bk", "bv", "bo"), kd[4:]):
                w = layer.get("w" + name[1])
                if w is not None:
                    layer[name] = 0.02 * jax.random.normal(
                        kk, (w.shape[1],), jnp.float32
                    )
    layer["attn_norm"] = ones(cfg.dim)
    layer["mlp_norm"] = ones(cfg.dim)
    if cfg.norm_form == LAYER:
        layer["attn_norm_b"] = jnp.zeros((cfg.dim,), jnp.float32)
        layer["mlp_norm_b"] = jnp.zeros((cfg.dim,), jnp.float32)
    if cfg.attn_gate:
        layer["w_attn_gate"] = dense(k[4], (cfg.dim, hq))
    if cfg.qk_norm:
        layer["q_norm"] = ones(cfg.head_dim)
        layer["k_norm"] = ones(cfg.head_dim)
    if cfg.post_norms:
        layer["post_attn_norm"] = ones(cfg.dim)
        layer["post_mlp_norm"] = ones(cfg.dim)
    if ffn == DENSE:
        layer["w_gate"] = dense(k[5], (cfg.dim, cfg.ffn_hidden))
        layer["w_up"] = dense(k[6], (cfg.dim, cfg.ffn_hidden))
        layer["w_down"] = dense(k[7], (cfg.ffn_hidden, cfg.dim))
    else:
        eh, held = cfg.expert_hidden, e1 - e0
        if cfg.router_form == MLP:
            kr = jax.random.split(jax.random.fold_in(key, 3), 5)
            rh = cfg.router_hidden
            layer["w_router_down"] = dense(kr[0], (cfg.dim, rh))
            # the layer before's state, a weight a channel
            layer["router_gamma"] = 0.5 + 0.1 * jax.random.normal(kr[1], (rh,))
            layer["router_norm"] = ones(rh)
            layer["w_router_mlp1"] = dense(kr[2], (rh, rh))
            layer["w_router_mlp2"] = dense(kr[3], (rh, rh))
            layer["w_router"] = dense(kr[4], (rh, cfg.n_experts))
        else:
            layer["w_router"] = dense(k[5], (cfg.dim, cfg.n_experts))
        layer["expert_bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
        layer["we_gate"] = dense(k[6], (held, cfg.dim, eh))
        layer["we_up"] = dense(k[7], (held, cfg.dim, eh))
        layer["we_down"] = dense(k[8], (held, eh, cfg.dim))
        if cfg.n_shared_experts:
            sh = eh * cfg.n_shared_experts
            layer["ws_gate"] = dense(k[9], (cfg.dim, sh))
            layer["ws_up"] = dense(k[10], (cfg.dim, sh))
            layer["ws_down"] = dense(k[11], (sh, cfg.dim))
    if cfg.hc_mult:
        for j, half in enumerate(("hc_attn", "hc_ffn")):
            layer[half] = _init_mhc(jax.random.fold_in(key, 6 + j), cfg)
    return layer


# The seed's draws of a half-layer's stream mixer (no published key states
# them; "assumed" in the configuration's file). ``phi`` is drawn so that
# ``m = x_hat phi / rms(x_hat)`` spreads by HC_M_STD whatever the width:
# N(0, HC_M_STD / sqrt(n dim)), which is N(0, 0.02) at the published 4 x
# 3,584. With ``alpha`` 0.5 and 4 on the diagonal of ``b``'s matrix part the
# mixing matrix of the seed's weights is neither the identity nor uniform
# (it leaves both by more than 0.05 in the mean) and differs from token to
# token, so a program that dropped the matrix, or made one for all tokens,
# would not pass the check.
HC_M_STD = 2.4
HC_ALPHA = 0.5
HC_B_RES_DIAG = 4.0


def _init_mhc(key: jax.Array, cfg: PatternConfig) -> dict:
    """One half-layer's mixer, float32: ``phi`` [n dim, n^2 + 2 n] (columns:
    ``H_pre``'s n, ``H_post``'s n, then ``H_res``'s n x n row by row),
    ``b`` of that length, ``alpha`` [3] (one gate a group of columns)."""
    n = cfg.hc_mult
    width = n * cfg.dim
    k_phi, k_b = jax.random.split(key)
    b = 0.02 * jax.random.normal(k_b, (n * n + 2 * n,), jnp.float32)
    b = b.at[2 * n :].add(HC_B_RES_DIAG * jnp.eye(n).reshape(-1))
    return {
        "phi": (HC_M_STD / np.sqrt(width)) * jax.random.normal(
            k_phi, (width, n * n + 2 * n), jnp.float32
        ),
        "b": b,
        "alpha": jnp.full((3,), HC_ALPHA, jnp.float32),
    }


def init_pattern_params(rng: jax.Array, cfg: PatternConfig) -> dict:
    """Parameter pytree (fp32 master weights). ``expert_bias`` is the
    router's selection bias: a buffer, zero, no gradient reaches it.
    ``mtp`` (where ``cfg.n_mtp``): a module's two input norms, its
    projection of [embedding; hidden] back to ``dim``, its layer and the
    norm before the shared head. ``exit_gate`` (where ``cfg.n_loops >
    1``): the ``Linear(dim, 1)`` every pass's normed state goes through,
    seeded like any dense weight (and its bias), so the exit distribution
    is not uniform. No ``lm_head`` where ``cfg.tie_embeddings``."""
    keys = jax.random.split(rng, cfg.n_layers + 2)
    params = {
        "embed": jax.random.normal(
            keys[-2], (cfg.vocab_size, cfg.dim), jnp.float32
        ) * 0.02,
        "layers": [
            _init_layer(keys[i], cfg, ffn, kind)
            for i, (ffn, kind) in enumerate(
                zip(cfg.ffn_types, cfg.layer_types)
            )
        ],
        "final_norm": _ones(cfg.dim),
    }
    if cfg.norm_form == LAYER:
        params["final_norm_b"] = jnp.zeros((cfg.dim,), jnp.float32)
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense_init(keys[-1], (cfg.dim, cfg.vocab_size))
    if cfg.n_mtp:
        params["mtp"] = []
        for j in range(cfg.n_mtp):
            k_proj, k_layer = jax.random.split(jax.random.fold_in(rng, 1 + j))
            params["mtp"].append({
                "embed_norm": _ones(cfg.dim),
                "hidden_norm": _ones(cfg.dim),
                "eh_proj": _dense_init(k_proj, (2 * cfg.dim, cfg.dim)),
                "layer": _init_layer(k_layer, cfg, cfg.ffn_types[-1]),
                "final_norm": _ones(cfg.dim),
            })
    if cfg.n_loops > 1:
        k_w, k_b = jax.random.split(jax.random.fold_in(rng, 0x6A7E))
        params["exit_gate"] = {
            "w": _dense_init(k_w, (cfg.dim, 1)),
            # half a standard normal: every exit stays live in every seed
            # (at a bias of 2 the first exit alone would take 0.88)
            "b": 0.5 * jax.random.normal(k_b, (1,), jnp.float32),
        }
    return params


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------


def _swiglu(h, w_gate, w_up, w_down, dt):
    return (
        jax.nn.silu(h @ w_gate.astype(dt)) * (h @ w_up.astype(dt))
    ) @ w_down.astype(dt)


def _router_scores(h, r, layer: dict, cfg: PatternConfig):
    """(scores [t, n_experts] float32, the state for the next layer) in
    ``router_dtype``. SIGMOID: the sigmoid of one matrix, no state;
    SOFTMAX: its softmax over all experts. MLP:
    ``r_l = h W_down + gamma r_{l-1}`` (``r`` is the layer before's, zero
    before the first), and the softmax of two GELU layers and an output
    matrix on its norm."""
    rdt = jnp.dtype(cfg.router_dtype)

    def dot(a, name):
        return jnp.dot(
            a.astype(rdt), layer[name].astype(rdt),
            precision=jax.lax.Precision.HIGHEST,  # float32 means float32
            preferred_element_type=rdt,
        )

    if cfg.router_form == SIGMOID:
        return jax.nn.sigmoid(dot(h, "w_router")).astype(jnp.float32), r
    if cfg.router_form == SOFTMAX:
        logits = dot(h, "w_router").astype(jnp.float32)
        return jax.nn.softmax(logits, axis=-1), r
    r = dot(h, "w_router_down") + layer["router_gamma"].astype(rdt) * r
    z = _rms_norm(r, layer["router_norm"], cfg.rms_eps)
    for name in ("w_router_mlp1", "w_router_mlp2"):
        z = jax.nn.gelu(dot(z, name), approximate=False)
    return jax.nn.softmax(dot(z, "w_router").astype(jnp.float32), axis=-1), r


def route(h, layer: dict, cfg: PatternConfig, r=None):
    """(expert ids [t, top_k], weights [t, top_k] float32, the router's
    state for the next layer): the scores (:func:`_router_scores`), the
    top k of score + bias, the scores at the chosen experts over their
    sum (``route_norm``), times ``route_scale``."""
    scores, r = _router_scores(h, r, layer, cfg)
    bias = jax.lax.stop_gradient(layer["expert_bias"])
    _, idx = jax.lax.top_k(scores + bias, cfg.top_k)
    w = jnp.take_along_axis(scores, idx, axis=1)
    if cfg.route_norm:
        w = w / w.sum(axis=1, keepdims=True)
    return idx, w * cfg.route_scale, r


def _token_rows(h, tok, n: int):
    """``h[tok]`` for the first ``n`` of ``tok``'s entries, zeros past
    them: a token's row (or number) at each of its pairs, in ``tok``'s
    order."""
    pad = ((0, tok.shape[0] - n),) + ((0, 0),) * (h.ndim - 1)
    return jnp.pad(h[tok[:n]], pad)


def _slot_sums(rows, inv, t: int):
    """``rows[inv]``, the pairs' rows back in pair order, and a token's
    slots summed in float32: ``[t, ...]``."""
    slots = rows[inv].reshape(t, -1, *rows.shape[1:])
    return slots.astype(jnp.float32).sum(1)


def _chunk_groups(lo, rows: int, starts, ends, n_here):
    """Of pair rows ``[lo, lo + rows)`` in the sort's order: how many each
    held expert has, and which rows hold a held pair."""
    sizes = jnp.clip(ends, lo, lo + rows) - jnp.clip(starts, lo, lo + rows)
    valid = (lo + jnp.arange(rows, dtype=jnp.int32) < n_here)[:, None]
    return sizes, valid


def _grouped_ffn(act: str, xs, sizes, valid, we_gate, we_up, we_down):
    """The held experts' gated FFN (``act`` on the gate:
    ``PatternConfig.expert_act``) on a chunk of pair rows, a group an
    expert."""

    def grouped(x, w):
        # rows past the held pairs belong to no group: what the
        # kernel leaves there, and what its transpose leaves in
        # their cotangent, is not data (the chip leaves NaNs)
        x = jnp.where(valid, x, 0)
        return jnp.where(valid, jax.lax.ragged_dot(x, w, sizes), 0)

    a, b = grouped(xs, we_gate), grouped(xs, we_up)
    return grouped(_EXPERT_ACTS[act](a) * b, we_down)


def _whole_chunk(act, xs, ws, lo, bounds, weights):
    """One chunk where every chunk runs: the grouped matmuls take all its
    rows (the zero rows are the last group's), and it weighs its own
    output rows in float32, so its backward, which has them again, makes
    the weights' gradient and no row of them outlives the chunk."""
    rows = xs.shape[0]
    with named_scope("magi_moe_sort"):
        sizes, valid = _chunk_groups(lo, rows, *bounds)
        sizes = sizes.at[-1].add(rows - sizes.sum())
    with named_scope("magi_moe_matmul"):
        o = _grouped_ffn(act, xs, sizes, valid, *weights)
    with named_scope("magi_moe_scatter"):
        return o.astype(jnp.float32) * ws[:, None]


def _chunk_of(stack, lo, rows: int):
    return jax.lax.dynamic_slice_in_dim(stack, lo, rows, 0)


def _each_chunk(n_chunks: int, rows: int, body, init):
    """``body(lo, carry)`` a chunk of ``rows`` in turn (at top-1 the one
    chunk is the whole stack, and there is no loop)."""
    if n_chunks == 1:
        return body(0, init)
    return jax.lax.fori_loop(
        0, n_chunks, lambda c, carry: body(c * rows, carry), init
    )


def _sorted_rows_forward(rows, act, h, w, order, bounds, weights):
    t, k = w.shape
    n, n_rows = t * k, order.shape[0]
    with named_scope("magi_moe_sort"):
        pairs = order[:n]
        inv = jnp.zeros_like(pairs).at[pairs].set(
            jnp.arange(n, dtype=jnp.int32), unique_indices=True
        )
        tok = order // k
    with named_scope("magi_moe_gather"):
        xs = _token_rows(h, tok, n)
        # a pair's weight, in the sort's order: each pair its own
        ws = _token_rows(w.reshape(-1), order, n)

    def chunk(lo, out):
        with named_scope("magi_moe_gather"):
            x, wc = _chunk_of(xs, lo, rows), _chunk_of(ws, lo, rows)
        o = _whole_chunk(act, x, wc, lo, bounds, weights)
        with named_scope("magi_moe_scatter"):
            return jax.lax.dynamic_update_slice_in_dim(out, o, lo, 0)

    with named_scope("magi_moe_scatter"):
        out = jnp.zeros((n_rows, h.shape[1]), jnp.float32)
    out = _each_chunk(n_rows // rows, rows, chunk, out)
    with named_scope("magi_moe_scatter"):
        y = _slot_sums(out, inv, t)
    return y, (xs, ws, tok, inv, bounds, weights)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _experts_on_sorted_rows(rows: int, act: str, h, w, order, bounds,
                            weights):
    """sum_k w_k expert_k(h) (the gate under ``act``) where every chunk of
    ``rows`` pair rows runs:
    ``order`` ``[n_rows]`` is the pairs sorted by expert (whole chunks:
    entries past ``n = t top_k`` are padding), ``bounds`` the held
    experts' (starts, ends, pairs here) in it, ``weights`` their three
    stacked matrices. The chunks' rows end to end are all pairs in the
    sort's order, so the rows move by that permutation and its inverse:
    :func:`_token_rows` lays the token rows (and the pairs' weights) out
    by one gather a layer, a chunk takes its slice, writes its weighed
    float32 output rows into the layer's stack, and :func:`_slot_sums`
    gathers the stack back by the inverse permutation and sums a token's
    ``top_k`` slots. Each of the two is the other's transpose, and the
    backward below is written with them: a chunk gathers its rows of the
    cotangent (``dy[tok]``, never a stack to slice), is differentiated
    with its forward made again, and writes its rows' gradient into a
    stack that one gather by the inverse permutation sums back a token.
    No pass scatter-adds rows. The loops are written out, and not a
    ``lax.scan`` over stacked inputs and outputs, so that a chunk's slice
    and write carry the scope of the end they belong to: the dispatch's
    under ``magi_moe_gather``, the combine's under ``magi_moe_scatter``
    (a scan's own copies carry the scan's name alone)."""
    return _sorted_rows_forward(rows, act, h, w, order, bounds, weights)[0]


def _sorted_rows_fwd(rows, act, h, w, order, bounds, weights):
    for end in ("dispatch", "combine"):
        telemetry.record_moe_rows_permuted(end)
    return _sorted_rows_forward(rows, act, h, w, order, bounds, weights)


def _sorted_rows_bwd(rows, act, res, g):
    xs, ws, tok, inv, bounds, weights = res
    t, n_rows = g.shape[0], xs.shape[0]

    def chunk(lo, carry):
        dxs, dws, dweights = carry
        with named_scope("magi_moe_gather"):
            x, wc = _chunk_of(xs, lo, rows), _chunk_of(ws, lo, rows)
        with named_scope("magi_moe_scatter"):
            # padding's rows read token 0's: no held pair, so no gradient
            g_rows = g[_chunk_of(tok, lo, rows)]
        _, vjp = jax.vjp(
            lambda x, wc, weights: _whole_chunk(
                act, x, wc, lo, bounds, weights
            ),
            x, wc, weights,
        )
        dx, dwc, dchunk = vjp(g_rows)
        with named_scope("magi_moe_gather"):
            dxs = jax.lax.dynamic_update_slice_in_dim(dxs, dx, lo, 0)
            dws = jax.lax.dynamic_update_slice_in_dim(dws, dwc, lo, 0)
        with named_scope("magi_moe_matmul"):
            dweights = jax.tree.map(jnp.add, dweights, dchunk)
        return dxs, dws, dweights

    with named_scope("magi_moe_gather"):
        dxs, dws = jnp.zeros_like(xs), jnp.zeros_like(ws)
    with named_scope("magi_moe_matmul"):
        dweights = jax.tree.map(jnp.zeros_like, weights)
    dxs, dws, dweights = _each_chunk(
        n_rows // rows, rows, chunk, (dxs, dws, dweights)
    )
    with named_scope("magi_moe_gather"):
        dh = _slot_sums(dxs, inv, t).astype(xs.dtype)
        dw = dws[inv].reshape(t, -1)
    return dh, dw, None, None, dweights


_experts_on_sorted_rows.defvjp(_sorted_rows_fwd, _sorted_rows_bwd)


def held_expert_ffn(h, idx, w, layer: dict, cfg: PatternConfig):
    """sum_k w_k expert_k(h) over the token-expert pairs whose expert
    this rank holds; returns (y [t, dim] float32, pairs a held expert
    [held] int32).

    The pairs are sorted by expert, the held ones first, and go through a
    grouped matmul (``jax.lax.ragged_dot``: the TPU compiler turns it
    into a tiled kernel that visits the groups' rows only) a chunk of
    ``2 t`` rows at a time: a token may choose up to ``top_k`` held
    experts, so there are up to ``top_k / 2`` chunks (at top-1 the one
    chunk is the ``t`` pairs there are). The first always
    runs; a later one that no held pair reaches is skipped
    (``lax.cond``). No pair is dropped. The matmuls follow the pairs
    that are here; the row gather and the scatter-add round them take a
    chunk's rows whatever it holds, so a step's time is flat in the load
    up to ``2 t`` pairs, four times an even share (PERF.md section 6, PR
    26: the form whose every pass follows the pairs is faster at an even
    load and follows a drifting router by 9% inside 40 steps). Under
    ``flat_expert_rows`` the matmuls too take a chunk whole and no chunk
    is skipped: a step does the work of ``top_k t`` pairs whatever the
    router sends here (PERF.md section 6, PR 42: a chunk costs a layer
    21 ms of a step, and ran for one seed's router and not for
    another's).

    The rows move in one of two forms, chosen by whether every chunk runs
    (``flat_expert_rows``; PERF.md section 6, PR 50). Where it does, the
    chunks' rows laid end to end are all ``top_k t`` pairs in the sort's
    order, a permutation, and :func:`_experts_on_sorted_rows` moves them
    by it and its inverse: one gather a layer lays the token rows (and
    the pairs' weights) out, a chunk weighs its bf16 output rows in
    float32, and one gather by the inverse permutation with a float32 sum
    over a token's ``top_k`` slots brings them back; each is the other's
    transpose, so no pass scatter-adds rows. The weights are applied
    inside the chunk, whose backward has its output again: applied after
    the gather, their gradient would keep every chunk's output alive
    across the layer's remat, and the layer's recomputed forward would
    run all the grouped matmuls a third time (measured: SDAR's step
    1,028 ms that way, 978 this way).
    Where chunks are skipped (Trinity, GLM), a chunk gathers and
    scatter-adds its own ``2 t`` rows; the TPU compiler re-sorts a
    scatter-add's float32 update rows by token first. They keep that form
    for its memory: the other stacks every slot's float32 output row, at
    Trinity's shapes (32,768 tokens, top-8, 2,048 wide) 2.1 GB a layer
    where a chunk's are 0.5, on a compiled step that holds 13.76 of the
    15.5 GB there are. Whether the gathers would also be slower there is
    not measured: by bytes the two are even at top-8 with one chunk
    running (3.2 GB a pass), but a row gather runs at a third of the
    scatters' pace on the chip, so the count in bytes decides nothing.
    When the experts' exchange brings real rows to every slot (ROADMAP
    R7) the scatter form goes."""
    dt = cfg.jnp_dtype
    t, k = idx.shape
    rows = min(2, k) * t
    e0, e1 = cfg.held_experts
    held = e1 - e0
    with named_scope("magi_moe_sort"):
        local = idx.reshape(-1) - e0
        key = jnp.where((local >= 0) & (local < held), local, held)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)  # pair ids
        order = jnp.pad(order, (0, -(t * k) % rows))  # whole chunks to slice
        counts = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
        ends = jnp.cumsum(counts)
        starts = ends - counts
        n_here = ends[-1]
    with named_scope("magi_moe_matmul"):
        we_gate, we_up, we_down = (
            layer[n].astype(dt) for n in ("we_gate", "we_up", "we_down")
        )

    bounds = (starts, ends, n_here)
    weights = (we_gate, we_up, we_down)
    if cfg.flat_expert_rows:
        y = _experts_on_sorted_rows(
            rows, cfg.expert_act, h, w, order, bounds, weights
        )
        return y, counts

    w_flat = w.reshape(-1)

    @jax.checkpoint  # a chunk keeps its inputs only
    def chunk(h, w_flat, pairs, lo):
        with named_scope("magi_moe_sort"):
            sizes, valid = _chunk_groups(lo, rows, *bounds)
            tok = pairs // k
        with named_scope("magi_moe_gather"):
            xs = h[tok]
        with named_scope("magi_moe_matmul"):
            o = _grouped_ffn(cfg.expert_act, xs, sizes, valid, *weights)
        with named_scope("magi_moe_scatter"):
            o = o.astype(jnp.float32) * w_flat[pairs][:, None]
            return jnp.zeros((t, cfg.dim), jnp.float32).at[tok].add(o)

    def step(y, j):
        with named_scope("magi_moe_sort"):
            lo = j * rows
            pairs = jax.lax.dynamic_slice(order, (lo,), (rows,))
            reached = lo < n_here

        def add_chunk(y):
            o = chunk(h, w_flat, pairs, lo)
            with named_scope("magi_moe_scatter"):
                return y + o

        return jax.lax.cond(reached, add_chunk, lambda y: y, y), None

    with named_scope("magi_moe_sort"):
        first = order[:rows]
    y = chunk(h, w_flat, first, 0)
    n_chunks = order.shape[0] // rows
    if n_chunks > 1:
        y, _ = jax.lax.scan(
            step, y, jnp.arange(1, n_chunks, dtype=jnp.int32)
        )
    return y, counts


def _route(h, layer: dict, cfg: PatternConfig, r=None):
    """A layer's :func:`route` under its scope: made once a layer, on the
    FFN half's normed input (:func:`_ffn_out`) or, under
    ``cfg.router_input == "attn"``, on the attention half's
    (:func:`_attention_out`)."""
    with named_scope("magi_moe_router"):
        return route(h, layer, cfg, r)


def _expert_ffn(h, layer: dict, cfg: PatternConfig, routed):
    """The expert half on its normed input ``h`` by the route ``routed``
    (:func:`_route`'s, wherever it was made)."""
    dt = cfg.jnp_dtype
    idx, w, r = routed
    with named_scope("magi_moe_experts"):
        y, counts = held_expert_ffn(h, idx, w, layer, cfg)
        with named_scope("magi_moe_scatter"):
            y = y.astype(dt)
    if cfg.n_shared_experts:
        with named_scope("magi_moe_shared"):
            y = y + _swiglu(
                h, layer["ws_gate"], layer["ws_up"], layer["ws_down"], dt
            )
    stats = {"expert_idx": idx, "expert_counts": counts}
    if r is not None:
        stats["router_state"] = r  # the next layer's: the caller takes it
    return y, stats


def _latent_qkv(h, pos, layer: dict, cfg: PatternConfig):
    """Latent attention's q, k [t, n_heads, head_dim] and v [t, n_heads,
    value_dim] from the normed hidden state: what the kernels are handed
    is expanded, every head its own ``k_nope`` and ``v`` and a copy of the
    one rotary key. With a value width of its own q and k come on
    ``cfg.kernel_qk_lanes`` lanes, zeros past ``head_dim`` where that is
    more."""
    dt = cfg.jnp_dtype
    t, heads, eps = h.shape[0], cfg.n_heads, cfg.rms_eps
    rope, nope = cfg.rope_head_dim, cfg.head_dim - cfg.rope_head_dim
    pad = cfg.kernel_qk_lanes - cfg.head_dim if cfg.v_head_dim else 0
    zeros = [jnp.zeros((t, heads, pad), dt)] if pad else []

    if cfg.rope_yarn is None:
        def rot(x):
            return _rope(x, pos, cfg.rope_theta, rope)
    else:
        freqs = jnp.asarray(yarn_freqs(cfg.rope_theta, rope, *cfg.rope_yarn))

        def rot(x):
            return _rope_at(x, pos, freqs)

    with named_scope("magi_mla_q"):
        c_q = _rms_norm(h @ layer["wq_a"].astype(dt), layer["q_a_norm"], eps)
        q = (c_q @ layer["wq_b"].astype(dt)).reshape(t, heads, cfg.head_dim)
        q = jnp.concatenate(
            [q[..., :nope], rot(q[..., nope:]), *zeros], axis=-1
        )
    with named_scope("magi_mla_kv"):
        c = h @ layer["wkv_a"].astype(dt)
        c_kv, k_rope = c[:, : cfg.kv_lora_rank], c[:, cfg.kv_lora_rank :]
        kv = (
            _rms_norm(c_kv, layer["kv_a_norm"], eps)
            @ layer["wkv_b"].astype(dt)
        ).reshape(t, heads, nope + cfg.value_dim)
        k_rope = jnp.broadcast_to(rot(k_rope[:, None, :]), (t, heads, rope))
        k = jnp.concatenate([kv[..., :nope], k_rope, *zeros], axis=-1)
        v = kv[..., nope:]
    return q, k, v


def yarn_freqs(theta: float, rope_dim: int, factor: float, beta_fast: float,
               beta_slow: float, original_max: int) -> np.ndarray:
    """The rotary lanes' angular frequencies under YaRN (arXiv:2309.00071,
    as DeepSeek-V3's ``DeepseekV3YarnRotaryEmbedding`` has them), float32
    [rope_dim / 2]: ``f_j = theta^(-2j / rope_dim)``; a pair that turns more
    than ``beta_fast`` times inside the original context keeps ``f_j``, one
    that turns fewer than ``beta_slow`` times takes ``f_j / factor``, and
    between the two pair indices (the first rounded down, the second up,
    inside [0, rope_dim - 1]) a linear ramp blends them."""
    half = rope_dim // 2
    j = np.arange(half, dtype=np.float64)
    f = theta ** (-j / half)

    def pair_turning(beta):  # the pair index that turns beta times
        return rope_dim * np.log(original_max / (2 * np.pi * beta)) / (
            2 * np.log(theta)
        )

    lo = max(int(np.floor(pair_turning(beta_fast))), 0)
    hi = min(int(np.ceil(pair_turning(beta_slow))), rope_dim - 1)
    ramp = np.clip((j - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return (f * (1.0 - ramp) + f / factor * ramp).astype(np.float32)


def _rope_at(x, pos, freqs):
    """``llama._rope``'s half-split rotation of x [t, h, 2 len(freqs)] at
    given angular frequencies."""
    half = freqs.shape[0]
    angles = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    ).astype(x.dtype)


def _qk_mean(q, k):
    """What q [t, kv heads, group, head_dim] and k [t, kv heads,
    head_dim] take of each other before the norm: a query head half of
    itself and half of its key head, a key head half of itself and half
    of the mean of its group's query heads."""
    return 0.5 * (q + k[:, :, None]), 0.5 * (q.mean(axis=2) + k)


def _cca_mix(q, k, v, layer: dict, cfg: PatternConfig, shift):
    """Compressed convolutional attention's mixing on the projections
    ``q`` [t, n_heads x head_dim], ``k`` and ``v`` [t, n_kv_heads x
    head_dim] -> q, k, v a head, before rotary: the depthwise causal
    convolution of ``[q | k]`` along the document, the grouped one (a
    block a head), the mean of each other's heads, the L2 norm a head
    (``sqrt(head_dim) x / |x|``, the key's times its head's
    temperature), and the second half of the value heads read from the
    token before. ``shift(z)`` -> (z shifted by 1, 2, ... along the
    document, whether a slot has that predecessor): the two convolutions
    one after the other read ``conv_taps[0] + conv_taps[1] - 2`` back, so
    one shift of ``[q | k | v's second half]`` serves everything."""
    f32 = jnp.float32
    t, hd = q.shape[0], cfg.head_dim
    t0, t1 = cfg.conv_taps
    hq, hk = cfg.n_heads, cfg.n_kv_heads
    group = hq // hk
    half = hk // 2 * hd  # the value heads that stay on their token
    c = jnp.concatenate([q, k], axis=-1)
    shifted, has = shift(jnp.concatenate([c, v[:, half:]], axis=-1))
    back = [c.astype(f32)] + [s[:, : c.shape[1]].astype(f32) for s in shifted]
    a, b1 = layer["cca_conv1_w"], layer["cca_conv1_b"]

    def conv1_at(i):  # the first convolution's output i tokens back
        y = b1 + sum(a[j] * back[i + j] for j in range(t0))
        return jnp.where(has[i - 1][:, None], y, 0.0) if i else y

    # the second: one matmul a head over its taps' channels
    c1 = jnp.stack(
        [conv1_at(i).reshape(t, hq + hk, hd) for i in range(t1)], axis=2
    ).astype(q.dtype)  # [t, heads, taps, head_dim]
    c2 = jnp.einsum(
        "thjd,jhde->the", c1, layer["cca_conv2_w"].astype(q.dtype),
        preferred_element_type=f32,
    ) + layer["cca_conv2_b"].reshape(hq + hk, hd)
    mean_q, mean_k = _qk_mean(
        back[0][:, : hq * hd].reshape(t, hk, group, hd),
        back[0][:, hq * hd :].reshape(t, hk, hd),
    )
    q = c2[:, :hq] + mean_q.reshape(t, hq, hd)
    k = c2[:, hq:] + mean_k

    def unit(x):
        return x * jax.lax.rsqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + cfg.rms_eps
        )

    q = unit(q).astype(v.dtype)
    k = (unit(k) * layer["cca_temp"][:, None]).astype(v.dtype)
    v = jnp.concatenate([v[:, :half], shifted[0][:, c.shape[1] :]], axis=-1)
    return q, k, v.reshape(t, hk, hd)


def _norm(x, w: dict, name: str, cfg: PatternConfig):
    """The norm ``name`` of ``w``: RMS, or LayerNorm with its bias
    ``<name>_b`` (statistics in float32 either way)."""
    if cfg.norm_form == RMS:
        return _rms_norm(x, w[name], cfg.rms_eps)
    x32 = x.astype(jnp.float32)
    x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = (x32 * jax.lax.rsqrt(var + cfg.rms_eps)).astype(x.dtype)
    return y * w[name].astype(x.dtype) + w[name + "_b"].astype(x.dtype)


def _diff_q(q, cfg: PatternConfig):
    """Differential attention's query [t, n_heads x head_dim] as the
    kernels take it, [t, n_heads, 2 head_dim]: a head's lanes, then as
    many zeros, the heads ordered so that a kernel GQA group reads its
    own key: published head ``2 (g i + jj) + s`` (pair ``g i + jj``, half
    ``s``, ``g`` query heads a key head) is kernel head ``(2 i + s) g +
    jj``, in the group of kernel key head ``2 i + s``."""
    t, d = q.shape[0], cfg.head_dim
    pairs, g = cfg.n_kv_heads // 2, cfg.n_heads // cfg.n_kv_heads
    q = q.reshape(t, pairs, g, 2, d).transpose(0, 1, 3, 2, 4)
    return jnp.pad(q.reshape(t, cfg.n_heads, d), ((0, 0), (0, 0), (0, d)))


def _diff_kv(k, v, cfg: PatternConfig):
    """Its keys and values [t, n_kv_heads x head_dim] as the kernels take
    them, [t, n_kv_heads, 2 head_dim]: kernel key head ``m`` is published
    key ``m`` and zeros; its value is the pair ``[v_{2i} | v_{2i+1}]``,
    ``i = m // 2``, which both of the pair's keys weigh."""
    t, d = k.shape[0], cfg.head_dim
    k = jnp.pad(k.reshape(t, cfg.n_kv_heads, d), ((0, 0), (0, 0), (0, d)))
    v = jnp.repeat(v.reshape(t, cfg.n_kv_heads // 2, 2 * d), 2, axis=1)
    return k, v


# The seed's spread of lambda's four vectors. lambda = exp(l_q1 . l_k1) -
# exp(l_q2 . l_k2) + lambda_init leaves lambda_init (0.79 to 0.80 past
# layer 14) by a draw of spread 11.3 x the square of this at 64 lanes:
# 0.028 here, so 1 - lambda stays above 0.08 at four spreads. The
# Differential Transformer's own 0.1 puts lambda within 0.02 of 1 in a
# layer on one seed in twenty, and there a document's first tokens, whose
# two maps hardly differ yet (a1 = a2 on its first), leave a1 - lambda a2
# under bf16's step of a1 and a2: one token's sub-norm then carries more
# of wq's gradient than the 4,095 others and no bf16 program reads it
# (PERF.md section 6, PR 46).
DIFF_LAMBDA_STD = 0.05


def diff_lambda_init(index: int) -> float:
    """``lambda_init`` of the Differential Transformer at a layer's
    (published) index, counted from 0."""
    return 0.8 - 0.6 * float(np.exp(-0.3 * index))


def _diff_combine(out, layer: dict, cfg: PatternConfig, index: int):
    """The kernels' output [t, n_heads, 2 head_dim], in :func:`_diff_q`'s
    order, -> [t, n_heads x head_dim]: ``(1 - lambda_init) x
    RMSNorm(a1 - lambda a2)`` a query pair, ``lambda = exp(l_q1 . l_k1)
    - exp(l_q2 . l_k2) + lambda_init``, in float32."""
    f32 = jnp.float32
    t, d = out.shape[0], cfg.head_dim
    pairs, g = cfg.n_kv_heads // 2, cfg.n_heads // cfg.n_kv_heads
    out = out.reshape(t, pairs, 2, g, 2 * d).astype(f32)
    lam_init = diff_lambda_init(index)
    lam = (
        jnp.exp(jnp.sum(layer["lambda_q1"] * layer["lambda_k1"]))
        - jnp.exp(jnp.sum(layer["lambda_q2"] * layer["lambda_k2"]))
        + lam_init
    )
    x = out[:, :, 0] - lam * out[:, :, 1]  # [t, pairs, g, 2 d]
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + cfg.rms_eps)
    x = x * layer["diff_norm"] * (1.0 - lam_init)
    return x.reshape(t, -1).astype(cfg.jnp_dtype)


def _layer_local(x, pos, layer, carry=None, *, cfg, layer_type, ffn_type,
                 tables, plans, attn_params, axis_name, shift_plan=None,
                 index=None):
    """One layer on this rank's dispatched tokens -> (x, stats).
    ``carry``: what the layers before handed on, a dict: ``r`` an MLP
    router's state, ``m`` the memory (the scan output of
    ``cfg.memory_layer``), ``kv`` the keys and values of
    ``cfg.kv_layer`` as the kernels take them. The carry this layer
    hands on, what it was handed and what it made, leaves under
    ``carry`` of the stats for the caller to pass to the next. One key
    lives inside the layer alone: ``route``, the expert half's route where
    the attention half makes it (``cfg.router_input``), which
    :func:`_ffn_out` pops.
    ``index``: the layer's place in ``cfg.layer_types``. Under
    ``cfg.hc_mult`` ``x`` is the streams' state [t, hc_mult x dim] and each
    half reads and writes it through its mixer (:func:`_mhc_half`)."""
    carry = dict(carry or {})
    attn = dict(
        cfg=cfg, layer_type=layer_type, tables=tables, plans=plans,
        attn_params=attn_params, axis_name=axis_name, shift_plan=shift_plan,
        index=index,
        route_ahead=ffn_type == EXPERTS and cfg.router_input == "attn",
    )
    if cfg.hc_mult:
        x = _mhc_half(
            x, layer["hc_attn"], cfg,
            lambda u: _attention_out(u, pos, layer, carry, **attn),
        )
        stats = {}

        def ffn(u):
            out, more = _ffn_out(u, layer, carry, cfg=cfg, ffn_type=ffn_type)
            stats.update(more)
            return out

        x = _mhc_half(x, layer["hc_ffn"], cfg, ffn)
        if carry:
            stats["carry"] = carry
        return x, stats
    if layer_type == SSM:
        with named_scope("magi_proj"):
            h = _norm(x, layer, "attn_norm", cfg)
        shift_tabs = tables["shift"]
        out, m = ssm.mamba_mixer(
            h, layer, cfg,
            lambda u: shift_local(u, shift_tabs, shift_plan, axis_name),
            ~shift_valid(shift_tabs)[0],
            # the scan's kernels run where the flex kernels do
            interpret=next(iter(attn_params.values())).interpret,
        )
        if index == cfg.memory_layer:
            carry["m"] = m
        with named_scope("magi_proj"):
            x = x + out
    elif layer_type == SSD:
        with named_scope("magi_proj"):
            h = _norm(x, layer, "attn_norm", cfg)
        shift_tabs = tables["shift"]
        out = ssm.mamba2_mixer(
            h, layer, cfg,
            lambda u: shift_local(u, shift_tabs, shift_plan, axis_name),
            ~shift_valid(shift_tabs)[0],
            interpret=next(iter(attn_params.values())).interpret,
        )
        with named_scope("magi_proj"):
            x = _residual(x, out, cfg)
    elif layer_type == GMU:
        with named_scope("magi_gmu"):
            h = _norm(x, layer, "attn_norm", cfg)
            x = x + ssm.gmu(h, carry["m"], layer, cfg)
    else:
        x = _attention_half(x, pos, layer, carry, **attn)

    out, stats = _ffn_out(x, layer, carry, cfg=cfg, ffn_type=ffn_type)
    if carry:
        stats["carry"] = carry
    with named_scope("magi_ffn"):
        return _residual(x, out, cfg), stats


def _residual(x, out, cfg: PatternConfig):
    """``x`` + a half-layer's output under ``cfg.residual_scale``."""
    if cfg.residual_scale != 1.0:
        out = out * jnp.asarray(cfg.residual_scale, out.dtype)
    return x + out


def _ffn_out(x, layer, carry, *, cfg, ffn_type):
    """(the FFN half's output on ``x``, norm first, before the residual
    sum; the expert layer's routing stats). An MLP router's state goes
    into ``carry``; a route the attention half made comes out of it. An
    expert layer's magi_moe_* scopes are siblings
    between its two magi_ffn blocks, so magi_ffn holds no expert."""
    dt = cfg.jnp_dtype
    with named_scope("magi_ffn"):
        h = _norm(x, layer, "mlp_norm", cfg)
    stats = {}
    if ffn_type == DENSE:
        with named_scope("magi_ffn"):
            out = _swiglu(
                h, layer["w_gate"], layer["w_up"], layer["w_down"], dt
            )
    else:
        # made before the attention call (``router_input``), or here
        routed = carry.pop("route", None) or _route(
            h, layer, cfg, carry.get("r")
        )
        out, stats = _expert_ffn(h, layer, cfg, routed)
        if "router_state" in stats:
            carry["r"] = stats.pop("router_state")
    with named_scope("magi_ffn"):
        if cfg.post_norms:
            out = _rms_norm(out, layer["post_mlp_norm"], cfg.rms_eps)
    return out, stats


# ---------------------------------------------------------------------------
# manifold-constrained hyper-connections: the residual path as streams
# ---------------------------------------------------------------------------


def _bf16_pieces(a):
    """``a`` as bfloat16 arrays whose float32 sum is ``a`` to the bit: a
    bfloat16 array is its own one piece, a float32 array three (8 + 8 + 8
    of its 24 significant bits). The residues are taken in float32 after
    ``reduce_precision``, not after a cast to bfloat16 and back, which a
    compiler that is allowed excess precision may take out."""
    if a.dtype == jnp.bfloat16:
        return [a]
    a = a.astype(jnp.float32)
    hi = jax.lax.reduce_precision(a, 8, 7)
    mid = jax.lax.reduce_precision(a - hi, 8, 7)
    return [p.astype(jnp.bfloat16) for p in (hi, mid, a - hi - mid)]


def _bf16_dot(a, b):
    """``a^T b^T``: one MXU pass of bfloat16 operands into float32."""
    return jax.lax.dot_general(
        a, b, (((0,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _pieces_dot(xs, ds):
    """The float32 sum over ``i + j <= 2`` of ``xs[i]^T ds[j]^T``: the six
    products of bf16 pieces that ``Precision.HIGHEST`` keeps of a float32
    product's nine, each exact in float32, where the MXU sums them. One
    pass a piece of ``xs``, ``ds``'s pieces side by side along their rows."""
    total = 0.0
    for i, x in enumerate(xs):
        side = ds[: 3 - i]
        out = _bf16_dot(x, jnp.concatenate(side))  # [dim, pieces k]
        total = total + out.reshape(out.shape[0], len(side), -1).sum(axis=1)
    return total


def _mhc_normed_fwd(x, phi, eps, cdt, n):
    with named_scope("magi_mhc_coef"):
        xc = x.astype(cdt)
        p = jax.lax.dot_general(  # [k, t]
            phi, xc, (((0,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,  # float32 means float32
            preferred_element_type=cdt,
        )
        r = jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1) + eps)
        return p * r[None], (x, phi, p, r)


def _mhc_normed_bwd(eps, cdt, n, res, dm):
    x, phi, p, r = res
    with named_scope("magi_mhc_coef"):
        phis, dps = _bf16_pieces(phi), _bf16_pieces(dm * r[None])
        # as it is kept where that is bfloat16: one piece, one pass
        xs = _bf16_pieces(x if x.dtype == jnp.bfloat16 else x.astype(cdt))
        dphi = jnp.concatenate([  # [n dim, k], a stream at a time
            _pieces_dot(stream, dps)
            for stream in zip(*(_streams(piece, n) for piece in xs))
        ])
        # the state's cotangent: the same six products in one contraction,
        # and the norm's term (d rsqrt(mean(x^2) + eps) = -r^3 x / width)
        # added before the one rounding to the state's dtype
        pairs = [(d, q) for i, d in enumerate(dps) for q in phis[: 3 - i]]
        dx = _bf16_dot(  # [t, n dim]
            jnp.concatenate([d for d, _ in pairs], axis=0),
            jnp.concatenate([q for _, q in pairs], axis=1),
        ).astype(cdt)
        dr = (dm * p).sum(axis=0)
        dx = dx - (dr * r**3 / x.shape[1])[:, None] * x.astype(cdt)
        return dx.astype(x.dtype), dphi.astype(phi.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _mhc_normed(x, phi, eps, cdt, n):
    """``(phi^T x) rsqrt(mean(x^2) + eps)`` [k, t] in ``cdt`` of a state
    ``x`` [t, n dim] of ``n`` streams and ``phi`` [n dim, k] in ``cdt``,
    float32 in every number where ``cdt`` is, forward and backward.

    Forward, a ``Precision.HIGHEST`` product on the state's copy in
    ``cdt``: the TPU compiler hands that convolution a bfloat16 state as
    it is kept and spends nothing on its copy's zero pieces (PERF.md
    section 6, PR 52), so there is nothing to write out. Backward, where
    both operands of either product are float32 and ``HIGHEST`` makes six
    real passes, the rule is written out in bf16 pieces
    (:func:`_bf16_pieces`, :func:`_pieces_dot`): ``d phi = x^T [dm_hi |
    dm_mid | dm_lo]`` in ONE pass over a bfloat16 state (one piece; three
    over a float32 one), a stream at a time as the state is held, and
    ``dx`` the six products of ``dm``'s and ``phi``'s pieces in one
    contraction, each bf16 x bf16 product exact in float32, where the MXU
    sums them. The norm's factor is inside the rule so that its term joins
    ``dx`` before the state's dtype rounds it, once, as autodiff of the
    float32 copy did. Residuals: ``x``, ``phi``, the product and the
    factor ([k, t] and [t]): nothing of the state's size that a layer's
    ``jax.checkpoint`` does not hold already."""
    return _mhc_normed_fwd(x, phi, eps, cdt, n)[0]


_mhc_normed.defvjp(_mhc_normed_fwd, _mhc_normed_bwd)


def _mhc_coef(x, w: dict, cfg: PatternConfig):
    """A half-layer's per-token coefficients from the streams' state ``x``
    [t, n dim]: (``h_pre`` [n, t], ``h_post`` [n, t], ``h_res`` [n, n, t]),
    in ``cfg.hc_dtype`` (float32; tokens along the last axis, so that the
    n x n matrices of a sequence are whole vregs and not one padded tile a
    token).

    ``m = (x phi) rsqrt(mean(x^2) + eps)``: the projection first, the
    norm's factor after (one pass over ``x`` gives both; the norm's weight
    is folded into ``phi``), float32 whatever the state is kept in
    (:func:`_mhc_normed`: a ``HIGHEST`` product forward, a backward rule
    written out in bf16 pieces, one pass over a bfloat16 state).
    ``h_pre = sigmoid(alpha_1 m[:n] + b[:n])``,
    ``h_post = 2 sigmoid(alpha_2 m[n:2n] + b[n:2n])``; the rest, an n x n
    matrix row by row, times ``alpha_3`` plus ``b``'s, clamped, through
    ``exp`` and ``hc_sinkhorn_iters`` rounds of (rows over their sum +
    ``hc_eps``, then columns over theirs): doubly stochastic, so the write
    neither grows nor shrinks what the streams carry."""
    n, cdt = cfg.hc_mult, jnp.dtype(cfg.hc_dtype)
    t = x.shape[0]
    telemetry.record_mhc_coef(
        "bf16_one_pass" if jnp.bfloat16 in (x.dtype, cdt)
        else "float32_three_pass"
    )
    with named_scope("magi_mhc_coef"):
        m = _mhc_normed(x, w["phi"].astype(cdt), cfg.rms_eps, cdt, n)
        a, b = w["alpha"].astype(cdt), w["b"].astype(cdt)[:, None]
        h_pre = jax.nn.sigmoid(a[0] * m[:n] + b[:n])
        h_post = 2.0 * jax.nn.sigmoid(a[1] * m[n : 2 * n] + b[n : 2 * n])
        lo, hi = cfg.hc_clamp
        mat = jnp.exp(jnp.clip(a[2] * m[2 * n :] + b[2 * n :], lo, hi))
        mat = mat.reshape(n, n, t)
        eps = jnp.asarray(cfg.hc_eps, cdt)
        for _ in range(cfg.hc_sinkhorn_iters):
            mat = mat / (mat.sum(axis=1, keepdims=True) + eps)  # rows
            mat = mat / (mat.sum(axis=0, keepdims=True) + eps)  # columns
    return h_pre, h_post, mat


def _streams(x, n: int):
    """The n streams [t, dim] of a state [t, n dim]."""
    dim = x.shape[1] // n
    return [x[:, i * dim : (i + 1) * dim] for i in range(n)]


def _mhc_read(x, h_pre, cfg: PatternConfig):
    """``u = sum_i h_pre[i] x_i`` [t, dim]: what a half-layer's function
    sees of the streams, summed in float32, in the model's dtype."""
    with named_scope("magi_mhc_read"):
        u = sum(
            h[:, None].astype(jnp.float32) * xi.astype(jnp.float32)
            for h, xi in zip(h_pre, _streams(x, cfg.hc_mult))
        )
        return u.astype(cfg.jnp_dtype)


def _mhc_write(x, y, h_post, h_res, cfg: PatternConfig):
    """``x'_i = sum_j h_res[i, j] x_j + h_post[i] y``: the streams mixed by
    the doubly stochastic matrix, plus the half-layer's output spread over
    them; summed in float32, kept in the model's dtype."""
    f32 = jnp.float32
    with named_scope("magi_mhc_write"):
        xs = [xi.astype(f32) for xi in _streams(x, cfg.hc_mult)]
        y = y.astype(f32)
        return jnp.concatenate(
            [
                (
                    sum(h_res[i, j][:, None].astype(f32) * xj
                        for j, xj in enumerate(xs))
                    + h_post[i][:, None].astype(f32) * y
                ).astype(cfg.jnp_dtype)
                for i in range(cfg.hc_mult)
            ],
            axis=-1,
        )


def _mhc_half(x, w: dict, cfg: PatternConfig, fn):
    """One half-layer on the streams: the coefficients, the read, ``fn``
    (which norms its input itself and returns the half's output before any
    residual sum) and the write."""
    h_pre, h_post, h_res = _mhc_coef(x, w, cfg)
    return _mhc_write(x, fn(_mhc_read(x, h_pre, cfg)), h_post, h_res, cfg)


def _mhc_widen(x, cfg: PatternConfig):
    """A hidden state [t, dim] as the streams' first state: every stream
    a copy (arXiv:2409.19606 section 3)."""
    with named_scope("magi_mhc_write"):
        return jnp.tile(x, (1, cfg.hc_mult))


def _mhc_sum(x, cfg: PatternConfig):
    """The streams' state read out as one hidden state: their sum."""
    with named_scope("magi_mhc_read"):
        return sum(
            xi.astype(jnp.float32) for xi in _streams(x, cfg.hc_mult)
        ).astype(cfg.jnp_dtype)


def _attention_half(x, pos, layer, carry, **how):
    """``x`` + the attention half of a SLIDING, FULL or CROSS layer
    (:func:`_attention_out`)."""
    out = _attention_out(x, pos, layer, carry, **how)
    with named_scope("magi_proj"):
        return _residual(x, out, how["cfg"])


def _attention_out(x, pos, layer, carry, *, cfg, layer_type, tables, plans,
                   attn_params, axis_name, shift_plan, index,
                   route_ahead=False):
    """The attention half of a SLIDING, FULL or CROSS layer on ``x``, norm
    first, before the residual sum; the keys and values ``cfg.kv_layer``
    makes go into ``carry``, and with ``route_ahead`` the expert half's
    route, made on this half's normed input before the attention call."""
    dt = cfg.jnp_dtype
    t = x.shape[0]
    eps = cfg.rms_eps
    kind = cfg.plan_kind(layer_type)

    def proj(h, name):
        y = h @ layer["w" + name].astype(dt)
        if cfg.attn_bias:
            y = y + layer["b" + name].astype(dt)
        return y

    # the attention half but for the attention call, which is a sibling:
    # a flex kernel must never lie under magi_proj
    with named_scope("magi_proj"):
        h = _norm(x, layer, "attn_norm", cfg)
        if cfg.attn_form == LATENT:
            q, k, v = _latent_qkv(h, pos, layer, cfg)
        elif cfg.attn_form == DIFF:
            q = _diff_q(proj(h, "q"), cfg)
            if layer_type == CROSS:
                k, v = carry["kv"]
            else:
                k, v = _diff_kv(proj(h, "k"), proj(h, "v"), cfg)
                if index == cfg.kv_layer:
                    carry["kv"] = (k, v)
        else:
            q = (h @ layer["wq"].astype(dt)).reshape(t, -1, cfg.head_dim)
            k = (h @ layer["wk"].astype(dt)).reshape(t, -1, cfg.head_dim)
            v = (h @ layer["wv"].astype(dt)).reshape(t, -1, cfg.head_dim)
    if route_ahead:
        # a sibling of magi_proj, as the attention call is: token-local,
        # on the dispatched rows, so it depends on nothing the call casts
        carry["route"] = _route(h, layer, cfg, carry.get("r"))
    if cfg.attn_form == CCA:
        # a sibling of magi_proj, as the attention call is
        with named_scope("magi_cca_mix"):
            shift_tabs = tables["shift"]
            q, k, v = _cca_mix(
                q.reshape(t, -1), k.reshape(t, -1), v.reshape(t, -1),
                layer, cfg,
                lambda z: (
                    shift_local(z, shift_tabs, shift_plan, axis_name),
                    shift_valid(shift_tabs),
                ),
            )
    with named_scope("magi_proj"):
        if cfg.attn_form == CCA:
            rope = cfg.rope_head_dim
            q, k = (
                jnp.concatenate(
                    [_rope(a[..., :rope], pos, cfg.rope_theta, rope),
                     a[..., rope:]], axis=-1,
                ) for a in (q, k)
            )
        if cfg.qk_norm:
            q = _rms_norm(q, layer["q_norm"], eps)
            k = _rms_norm(k, layer["k_norm"], eps)
        if cfg.attn_form == GQA and layer_type in cfg.rope_kinds:
            q = _rope(q, pos, cfg.rope_theta, cfg.head_dim)
            k = _rope(k, pos, cfg.rope_theta, cfg.head_dim)
    with named_scope("magi_attn_" + _SHORT[kind]):
        out, _, _ = dist_attn_local(
            q, k, v, tables[kind], plans[kind], attn_params[kind],
            axis_name=axis_name,
        )
    if cfg.attn_form == DIFF:
        # a sibling of magi_proj, as the attention call is
        with named_scope("magi_diff_combine"):
            place = cfg.layer_index[index] if cfg.layer_index else index
            out = _diff_combine(out, layer, cfg, place)
    with named_scope("magi_proj"):
        out = out.reshape(t, -1)
        if cfg.attn_gate:
            out = out * jax.nn.sigmoid(h @ layer["w_attn_gate"].astype(dt))
        with (named_scope("magi_mla_out") if cfg.attn_form == LATENT
              else contextlib.nullcontext()):
            out = proj(out, "o")
        if cfg.post_norms:
            out = _rms_norm(out, layer["post_attn_norm"], eps)
    return out


def _one_layer(cfg, layer_type, ffn_type, tables, plans, attn_params,
               axis_name, shift_plan=None, index=None, applied_once=True):
    """``(x, pos, layer, carry=None) -> (x, stats)`` of one layer
    (:func:`_layer_local`). Under ``cfg.remat`` a layer keeps its inputs,
    the carry among them, and (``applied_once``: every trunk but the
    looped one) its attention call's out and lse, and recomputes the rest
    (``_common.layer_under_remat``): what an earlier layer handed on is
    never made again, and the forward kernel runs once."""
    return layer_under_remat(
        lambda attn_params: functools.partial(
            _layer_local, cfg=cfg, layer_type=layer_type, ffn_type=ffn_type,
            tables=tables, plans=plans, attn_params=attn_params,
            axis_name=axis_name, shift_plan=shift_plan, index=index,
        ),
        attn_params, {kind: _SHORT[kind] for kind in attn_params},
        remat=cfg.remat, applied_once=applied_once,
    )


def _diffusion_io(cfg: PatternConfig):
    """The scope of what diffusion over blocks adds around the layers: a
    cross-cut over ``magi_embed`` / ``magi_head`` (the doubled rows'
    embedding, the noisy half's gather before the head, the weights on
    the loss), as ``magi_mtp`` is."""
    if cfg.diffusion_block:
        return named_scope("magi_diffusion_io")
    return contextlib.nullcontext()


def _embed(params, tokens, cfg: PatternConfig):
    dt = cfg.jnp_dtype
    with _diffusion_io(cfg), named_scope("magi_embed"):
        x = params["embed"].astype(dt)[tokens]
        if cfg.embed_scale != 1.0:
            x = x * jnp.asarray(cfg.embed_scale, dt)
    return x


def _logits(x, params, cfg: PatternConfig):
    if cfg.tie_embeddings:  # this rank's rows of the embedding
        head = params["embed"].astype(cfg.jnp_dtype).T
    else:
        head = params["lm_head"].astype(cfg.jnp_dtype)
    logits = (x @ head).astype(jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def _head(x, w: dict, params, cfg: PatternConfig):
    """Logits of ``x`` through ``w``'s ``final_norm`` (the trunk's: the
    parameters; an MTP module's: the module)."""
    with named_scope("magi_head"):
        return _logits(_norm(x, w, "final_norm", cfg), params, cfg)


def _trunk_local(params, tokens, pos, cfg: PatternConfig, tables, plans,
                 attn_params, axis_name, shift_plan=None):
    """The layers over this rank's dispatched tokens -> (the last layer's
    output before the final norm, the expert layers' routing stats).
    What a layer hands on goes from layer to layer beside ``x`` as one
    carry (:func:`_layer_local`): an MLP router's state, zero before the
    first layer, the memory and the shared keys and values."""
    x = _embed(params, tokens, cfg)
    if cfg.hc_mult:
        x = _mhc_widen(x, cfg)
    carry = {}
    if cfg.router_form == MLP:
        carry["r"] = jnp.zeros(
            (x.shape[0], cfg.router_hidden), cfg.router_dtype
        )
    stats = []
    for i, (layer, layer_type, ffn_type) in enumerate(zip(
        params["layers"], cfg.layer_types, cfg.ffn_types
    )):
        x, s = _one_layer(
            cfg, layer_type, ffn_type, tables, plans, attn_params, axis_name,
            shift_plan, i,
        )(x, pos, layer, carry)
        carry = s.pop("carry", {})
        if s:
            stats.append(s)
    if cfg.hc_mult:
        x = _mhc_sum(x, cfg)
    return x, stats


def _looped_trunk_local(params, tokens, pos, cfg: PatternConfig, tables,
                        plans, attn_params, axis_name):
    """The looped trunk over this rank's dispatched tokens -> the state
    after every pass [n_loops, t, dim]: ``x_t = norm(layers(x_{t-1}))``,
    the one final norm inside the loop, so a pass starts from the normed
    state. The pass is the body of ONE ``lax.scan`` with the layers'
    parameters closed over: the program holds a layer's kernels once a
    direction whatever ``n_loops`` is, and the weights' gradient is the
    scan's sum over passes."""
    one_layer = [
        _one_layer(  # applied n_loops times: a pass keeps its inputs alone
            cfg, layer_type, ffn_type, tables, plans, attn_params, axis_name,
            applied_once=False,
        )
        for layer_type, ffn_type in zip(cfg.layer_types, cfg.ffn_types)
    ]

    def one_pass(x, _):
        for fn, layer in zip(one_layer, params["layers"]):
            x, _stats = fn(x, pos, layer)
        with named_scope("magi_head"):  # the head's norm, inside the loop
            x = _rms_norm(x, params["final_norm"], cfg.rms_eps)
        return x, x

    with named_scope("magi_loop"):
        _, states = jax.lax.scan(
            one_pass, _embed(params, tokens, cfg), None, length=cfg.n_loops
        )
    return states


def exit_log_probs(gate_logits):
    """log of the exit distribution a token, [exits, t] float32, from the
    gates' logits [exits, t]: ``p_t = lambda_t prod_{j<t} (1 -
    lambda_j)`` with ``lambda = sigmoid(logit)``, and the last exit takes
    what is left, ``prod_{j<T} (1 - lambda_j)`` (its own gate is not
    read). One exit: ``p_1 = 1``."""
    early = gate_logits[:-1]
    stayed = jnp.cumsum(jax.nn.log_sigmoid(-early), axis=0)  # log prod_{j<=t}
    zero = jnp.zeros_like(gate_logits[:1])
    return (
        jnp.concatenate([zero, stayed])  # all the earlier exits passed by
        + jnp.concatenate([jax.nn.log_sigmoid(early), zero])
    )


# rows of one exit that go through the head at a time: a block's float32
# logits are ``rows x vocab_size`` (0.8 GB at 4,096 x 49,152), and the
# backward holds two or three such blocks
EXIT_BLOCK_ROWS = 4096


def _exit_losses(states, labels, params, cfg: PatternConfig):
    """(sum over valid positions of ``sum_t p_t CE_t - beta H(p)``, the
    count of them) from the passes' normed states [exits, t, dim]: every
    exit through the shared head, a per-token cross-entropy and the gate
    (float32: a ``Linear(dim, 1)`` with bias), ``EXIT_BLOCK_ROWS`` rows
    of one exit at a time (a whole exit where ``t`` is no multiple of
    it) and recomputed in the backward (``cfg.remat``), so one block's
    logits are alive; the distribution and the objective on [exits, t]
    values."""
    gate = params["exit_gate"]
    n_exits, t, dim = states.shape
    blocks = t // EXIT_BLOCK_ROWS if t % EXIT_BLOCK_ROWS == 0 else 1

    def one_block(block):
        x, lab = block
        ce, _valid = masked_ce_tokens(_logits(x, params, cfg), lab)
        g = jnp.dot(
            x.astype(jnp.float32), gate["w"][:, 0],
            precision=jax.lax.Precision.HIGHEST,
        ) + gate["b"][0]
        return ce, g

    if cfg.remat:
        one_block = jax.checkpoint(one_block)
    ce, gate_logits = jax.lax.map(
        one_block,
        (
            states.reshape(n_exits * blocks, t // blocks, dim),
            jnp.tile(labels.reshape(blocks, t // blocks), (n_exits, 1)),
        ),
    )
    ce, gate_logits = ce.reshape(n_exits, t), gate_logits.reshape(n_exits, t)
    logp = exit_log_probs(gate_logits)
    # H(p) = -sum_t p_t log p_t: the entropy's term goes in with the CE
    per_token = (
        jnp.exp(logp) * (ce + cfg.exit_entropy_weight * logp)
    ).sum(axis=0)
    valid = labels >= 0
    return (
        jnp.where(valid, per_token, 0.0).sum(),
        valid.sum().astype(jnp.float32),
    )


def _mtp_local(params, x, next_tokens, pos, cfg: PatternConfig, tables,
               plans, attn_params, axis_name):
    """The multi-token-prediction modules after the trunk (DeepSeek-V3's
    form, GLM-4.7-Flash's ``num_nextn_predict_layers``). Module ``j`` at
    position ``i`` takes the hidden state before it (the trunk's last
    layer for the first, before the final norm) and the embedding of
    token ``i + j + 1``, ``next_tokens[j]``, both normed, concatenated
    embedding first and projected back to ``dim``; runs one more layer of
    the last trunk layer's kinds on the same mask and positions; and
    predicts token ``i + j + 2`` through its own norm and the shared
    head. -> (logits a module, routing stats a module)."""
    dt = cfg.jnp_dtype
    logits, stats = [], []
    for mod, nxt in zip(params["mtp"], next_tokens):
        e = _embed(params, nxt, cfg)
        with named_scope("magi_embed"):  # the rest of the module's input
            e = _rms_norm(e, mod["embed_norm"], cfg.rms_eps)
            h = _rms_norm(x, mod["hidden_norm"], cfg.rms_eps)
            x = jnp.concatenate([e, h], axis=-1) @ mod["eh_proj"].astype(dt)
        if cfg.hc_mult:  # the module's layer has streams of its own
            x = _mhc_widen(x, cfg)
        x, s = _one_layer(
            cfg, cfg.layer_types[-1], cfg.ffn_types[-1], tables, plans,
            attn_params, axis_name,
        )(x, pos, mod["layer"])
        if cfg.hc_mult:
            x = _mhc_sum(x, cfg)
        if s:
            stats.append(s)
        logits.append(_head(x, mod, params, cfg))
    return logits, stats


def _stacked(stats):
    return jax.tree.map(lambda *a: jnp.stack(a), *stats) if stats else {}


@dataclasses.dataclass(frozen=True, eq=False)
class NoisyRows:
    """Which of a rank's dispatched rows belong to the noisy half of a
    doubled ``[noisy ; clean]`` sequence: ``rows`` [cp, n] int32, a
    rank's local slots in dispatch order, -1 past its own count (the
    dispatch balances the mask's area, not the halves). The head and the
    loss of diffusion over blocks run on these rows alone."""

    rows: np.ndarray

    def device_tables(self):
        return (self.rows,)


def make_noisy_rows(meta, data_tokens: int) -> NoisyRows:
    """The noisy half's rows (global positions below ``data_tokens``) of
    every rank of the dispatch ``meta``."""
    here = [
        np.flatnonzero(np.asarray(meta.position_ids(r)) < data_tokens)
        for r in range(meta.cp_size)
    ]
    rows = np.full((meta.cp_size, max(map(len, here))), -1, np.int32)
    for r, idx in enumerate(here):
        rows[r, : len(idx)] = idx
    return NoisyRows(rows)


@dataclasses.dataclass(frozen=True, eq=False)
class MagiPattern:
    """Config + one dispatch's plans by attention kind + mesh + step
    makers. ``tokens`` / ``labels`` / ``pos`` are in DISPATCH order,
    [batch, total_padded], batch on 'dp' and tokens on 'cp'."""

    cfg: PatternConfig
    mesh: Mesh
    plans: dict[str, DistAttnPlan]
    attn_params: dict[str, FlexAttnParams]
    cp_axis: str | tuple[str, str] = "cp"
    dp_axis: str = "dp"
    dispatch_meta: Any = None  # the plans' dispatch: MTP targets roll on it
    # the documents' forward shift on that dispatch (``cfg.shift_taps``)
    shift_plan: Any = None
    # under ``cfg.diffusion_block``: the noisy half's rows a rank
    noisy_rows: NoisyRows | None = None

    def loss_fn(self, params, tokens, labels, pos, tables, weights=None, *,
                with_stats: bool = False):
        """Mean next-token CE over valid (label >= 0) positions, plus
        ``cfg.mtp_loss_weight`` x each MTP module's mean CE on its own
        target; for a looped decoder (``cfg.n_loops > 1``) the mean of
        the exits' expected CE less ``cfg.exit_entropy_weight`` x the
        exit distribution's entropy; with ``with_stats`` also the expert
        layers' routing (the trunk's, then the modules'): ``expert_idx``
        [batch, layers,
        total_padded, top_k] in dispatch order and ``expert_counts``
        [layers, held] summed over the mesh.

        Under ``cfg.diffusion_block`` the batch is the doubled sequence
        in dispatch order: ``tokens`` the noised ids then the clean ids,
        ``pos`` the tokens' positions twice, ``labels`` a masked noisy
        row's own clean id and -1 everywhere else (no shift: a masked row
        predicts its own token), ``weights`` [batch, total_padded]
        float32 a row's weight (1/t of its block). The head runs on the
        noisy half's rows alone, and the loss is the sum of weight x CE
        over the labelled rows, over the number of noisy rows: the
        sequence's tokens, masked or not."""
        cfg = self.cfg
        if bool(cfg.diffusion_block) != (weights is not None):
            raise ValueError(
                "per-row weights go with diffusion over blocks, and only "
                f"with it (diffusion_block {cfg.diffusion_block})"
            )
        weights = () if weights is None else (weights,)
        tables = {k: tuple(v) for k, v in tables.items()}
        batch = P(self.dp_axis, self.cp_axis)
        stats_specs = (
            {"expert_idx": P(self.dp_axis, None, self.cp_axis),
             "expert_counts": P()}
            if with_stats and EXPERTS in cfg.ffn_types else {}
        )
        mtp_labels = self._mtp_labels(tokens, labels)
        # the part the loss's own sums belong to (docs/observability.md)
        head_part = "magi_exit_head" if cfg.n_loops > 1 else "magi_head"

        @functools.partial(
            shard_map,
            mesh=self.mesh,
            in_specs=(
                P(), batch, batch, batch,
                (batch,) * (cfg.n_mtp + len(weights)),
                {k: (P(self.cp_axis),) * len(v) for k, v in tables.items()},
            ),
            out_specs=(P(), stats_specs),
            check_vma=False,
        )
        def _local(params, tok, lab, pos, mtp_lab, tabs):
            def one_diffusion(tok1, lab1, pos1, w1):
                x, stats = _trunk_local(
                    params, tok1, pos1, cfg, tabs, self.plans,
                    self.attn_params, self.cp_axis,
                )
                with named_scope("magi_diffusion_io"), named_scope("magi_head"):
                    rows = tabs["noisy_rows"][0][0]
                    here = rows >= 0
                    rows = jnp.maximum(rows, 0)
                    x = x[rows]
                    lab1 = jnp.where(here, lab1[rows], -1)
                    w1 = jnp.where(here, w1[rows], 0.0)
                logits = _head(x, params, params, cfg)
                with named_scope("magi_head"):
                    ce, _ = masked_ce_tokens(logits, lab1)
                    with named_scope("magi_diffusion_io"):
                        ce = ce * w1
                    sums = (ce.sum(), here.sum().astype(jnp.float32))
                return (sums,), _stacked(stats)

            def one(tok1, lab1, pos1, *mtp_lab1):
                if cfg.diffusion_block:
                    return one_diffusion(tok1, lab1, pos1, *mtp_lab1)
                run = (cfg, tabs, self.plans, self.attn_params, self.cp_axis)
                if cfg.n_loops > 1:
                    states = _looped_trunk_local(params, tok1, pos1, *run)
                    with named_scope("magi_exit_head"):
                        return (_exit_losses(states, lab1, params, cfg),), {}
                x, stats = _trunk_local(
                    params, tok1, pos1, *run, self.shift_plan
                )
                logits = [_head(x, params, params, cfg)]
                if cfg.n_mtp:
                    with named_scope("magi_mtp"):
                        # module j is fed token i + j + 1: the label, then
                        # the module before's target
                        with named_scope("magi_embed"):
                            fed = [
                                jnp.maximum(t, 0)
                                for t in (lab1, *mtp_lab1[:-1])
                            ]
                        more, routed = _mtp_local(params, x, fed, pos1, *run)
                    logits, stats = logits + more, stats + routed
                stats = _stacked(stats)
                with named_scope("magi_head"):
                    sums = [masked_ce_sums(logits[0], lab1)]
                if cfg.n_mtp:
                    with named_scope("magi_mtp"), named_scope("magi_head"):
                        sums += [
                            masked_ce_sums(lg, t)
                            for lg, t in zip(logits[1:], mtp_lab1)
                        ]
                return tuple(sums), stats

            # a loop, not vmap: a batched lax.cond would run both branches
            outs = [one(*b) for b in zip(tok, lab, pos, *mtp_lab)]
            with named_scope(head_part):
                sums, stats = jax.tree.map(lambda *a: jnp.stack(a), *outs)
                with named_scope("magi_pattern_loss_psum"):
                    sums = [
                        tuple(
                            jax.lax.psum(
                                jax.lax.psum(v.sum(), self.cp_axis),
                                self.dp_axis,
                            )
                            for v in pair
                        )
                        for pair in sums
                    ]
            if stats_specs:
                with named_scope("magi_pattern_stats_psum"):
                    counts = jax.lax.psum(
                        jax.lax.psum(
                            stats["expert_counts"].sum(0), self.cp_axis
                        ),
                        self.dp_axis,
                    )
                stats = {
                    "expert_idx": stats["expert_idx"],
                    "expert_counts": counts,
                }
            else:
                stats = {}
            with named_scope(head_part):
                (loss_sum, count), *mtp_sums = sums
                loss = loss_sum / jnp.maximum(count, 1.0)
                for loss_sum, count in mtp_sums:
                    loss = loss + cfg.mtp_loss_weight * (
                        loss_sum / jnp.maximum(count, 1.0)
                    )
            return loss, stats

        loss, stats = _local(
            params, tokens, labels, pos, mtp_labels + weights, tables
        )
        return (loss, stats) if with_stats else loss

    def _mtp_labels(self, tokens, labels):
        """MTP module ``j``'s target a position, in dispatch order: token
        ``i + j + 2``, the distributed roll of the token ids by
        ``-(j + 2)`` along the global sequence, wrapping at its end as
        the caller's ``labels`` (a roll by -1) do; -1 where ``labels``
        is."""
        if not self.cfg.n_mtp:
            return ()
        if self.dispatch_meta is None:
            raise ValueError("MTP modules roll the tokens: pass dispatch_meta")
        with named_scope("magi_mtp"), named_scope("magi_embed"):
            return tuple(
                jnp.where(
                    labels >= 0,
                    roll(
                        tokens, self.dispatch_meta, -(j + 2), axis=1,
                        mesh=self.mesh, cp_axis=self.cp_axis,
                    ),
                    -1,
                )
                for j in range(self.cfg.n_mtp)
            )

    def sharded_tables(self):
        from ._common import sharded_plan_tables

        tables = {
            k: sharded_plan_tables(p, self.mesh, self.cp_axis)
            for k, p in self.plans.items()
        }
        for name, plan in (
            ("shift", self.shift_plan), ("noisy_rows", self.noisy_rows)
        ):
            if plan is not None:
                tables[name] = sharded_plan_tables(
                    plan, self.mesh, self.cp_axis
                )
        return tables

    def make_train_step(self, optimizer):
        """optax-style optimizer -> jitted (params, opt_state, batch) step."""
        from ._common import make_model_train_step

        return make_model_train_step(self, optimizer)

    def record_expert_load(self, expert_counts) -> None:
        """``expert_counts`` [layers, held] of one step, read on the
        host: the gauges ``magi_moe_pairs_here`` /
        ``magi_moe_load_max_over_mean`` a layer."""
        for i, counts in enumerate(np.asarray(expert_counts)):
            telemetry.record_moe_load(i, counts)


def _cast_rows(plan) -> int:
    """Rows all ranks' key-value casts of one attention call carry."""
    if plan is None or plan.cp_size == 1:
        return 0
    return int(plan.comm.scheduled_rows_total)


def build_magi_pattern(
    cfg: PatternConfig,
    mesh: Mesh,
    cu_seqlens: Sequence[int],
    *,
    chunk_size: int,
    cp_axis: str | tuple[str, str] = "cp",
    dp_axis: str = "dp",
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    overlap_config=None,
) -> tuple[MagiPattern, Any]:
    """Plan the CP attention of one packed sequence (documents
    ``cu_seqlens``, causal inside each) for every attention kind of the
    pattern, and bundle the model. Returns (model, dispatch_meta). Under
    ``cfg.diffusion_block`` the plan and the dispatch are of the doubled
    sequence ``[noisy ; clean]``, twice ``cu_seqlens[-1]`` rows, under
    ``api.infer_block_diffusion_mask``.

    One dispatch solve, on the first of ``cfg.plan_kinds`` (the
    documents' whole mask where a layer has it); every other kind's
    plan, tiles and tables are built for its own slices on that dispatch.
    A pattern of one kind builds one plan."""
    from ..api.functools import (
        infer_attn_mask_from_cu_seqlens, infer_block_diffusion_mask,
    )
    from ._common import (
        _cp_geometry, plan_flex_attn, plan_flex_attn_on_dispatch,
    )

    if isinstance(cp_axis, list):
        cp_axis = tuple(cp_axis)
    cu = [int(c) for c in cu_seqlens]
    data_tokens = cu[-1]
    cp_size, _ = _cp_geometry(mesh, cp_axis)
    scans = {SSM, SSD} & set(cfg.layer_types)
    if scans and cp_size > 1:
        raise NotImplementedError(
            f"a state-space layer at cp = {cp_size}: the scan runs on one "
            "rank's rows in sequence order; the hand-over of its state "
            "between ranks and a dispatch that keeps a document's chunks "
            "in order are ROADMAP R8"
        )
    # under diffusion over blocks the plan is the doubled sequence's
    total = (2 if cfg.diffusion_block else 1) * data_tokens
    if cfg.diffusion_block and data_tokens % chunk_size:
        raise ValueError(
            f"{data_tokens} tokens are no whole number of chunks of "
            f"{chunk_size}: a chunk of [noisy ; clean] is of one half"
        )

    def mask(kind):
        if cfg.diffusion_block:
            return infer_block_diffusion_mask(cu, cfg.diffusion_block)
        if kind == FULL:
            return infer_attn_mask_from_cu_seqlens(cu, causal=True)
        return infer_attn_mask_from_cu_seqlens(
            cu, causal=False, window_size=(cfg.sliding_window - 1, 0)
        )

    common = dict(
        cp_axis=cp_axis, block_q=block_q, block_k=block_k,
        interpret=interpret, overlap_config=overlap_config,
    )
    lead, *rest = cfg.plan_kinds
    plans, attn_params = {}, {}
    heads = cfg.kernel_heads
    plans[lead], attn_params[lead], meta = plan_flex_attn(
        heads, mesh, total, *mask(lead), chunk_size=chunk_size,
        kind=_SHORT[lead], **common,
    )
    for kind in rest:
        plans[kind], attn_params[kind] = plan_flex_attn_on_dispatch(
            heads, mesh, meta, *mask(kind), kind=_SHORT[kind], **common,
        )
    if scans and not np.array_equal(
        np.asarray(meta.perm_idx), np.arange(total)
    ):
        raise NotImplementedError(
            "a state-space layer on a dispatch that moves rows: the scan "
            "runs on rows in sequence order (ROADMAP R8)"
        )
    model = MagiPattern(
        cfg=cfg, mesh=mesh, plans=plans, attn_params=attn_params,
        cp_axis=cp_axis, dp_axis=dp_axis, dispatch_meta=meta,
        shift_plan=(
            make_shift_plan(meta, cu, cfg.shift_taps)
            if cfg.shift_taps else None
        ),
        noisy_rows=(
            make_noisy_rows(meta, data_tokens) if cfg.diffusion_block else None
        ),
    )
    telemetry.record_model_loop(cfg.n_loops, cfg.n_layers)
    ffn_kinds = cfg.ffn_types + cfg.ffn_types[-1:] * cfg.n_mtp  # modules'
    telemetry.record_moe_route_ahead(
        ffn_kinds.count(EXPERTS) if cfg.router_input == "attn" else 0
    )
    if SSD in cfg.layer_types:
        from ..ops.ssd_scan import CHUNK

        chunk = cfg.ssm_chunk or CHUNK
        telemetry.record_ssd_model(
            documents=len(cu) - 1,
            # the chunks with a document's start strictly inside
            reset_chunks=len({c // chunk for c in cu[1:-1] if c % chunk}),
            multipliers={
                "embed": cfg.embed_scale, "residual": cfg.residual_scale,
                "softmax": cfg.softmax_scale or cfg.head_dim ** -0.5,
                "logits": cfg.logits_scaling,
            },
        )
    if cfg.attn_form == DIFF:
        readers = cfg.layer_types.count(CROSS)
        telemetry.record_handed_on(
            documents=len(cu) - 1 if SSM in cfg.layer_types else None,
            kv_readers=readers,
            # every reader's attention call casts the handed-on pair
            # again: the rows the FULL plan's casts bring a rank, once a
            # reader (none at cp = 1)
            recast_rows=readers * _cast_rows(plans.get(FULL)),
            pad_lane_share=0.5,
        )
    if cfg.attn_form == LATENT:
        telemetry.record_mla_kv_cast_width(
            expanded=cfg.n_heads * (cfg.head_dim + cfg.value_dim),
            latent=cfg.kv_lora_rank + cfg.rope_head_dim,
        )
    if cfg.hc_mult:
        telemetry.record_mhc(
            streams=cfg.hc_mult, sinkhorn_iters=cfg.hc_sinkhorn_iters,
            stream_bytes=mhc_stream_bytes(cfg, data_tokens),
            pad_lane_share=(
                1.0 - cfg.head_dim / cfg.kernel_qk_lanes
                if cfg.v_head_dim else 0.0
            ),
        )
    return model, meta


def mhc_stream_bytes(cfg: PatternConfig, tokens: int) -> int:
    """The bytes the stream mix of one training step moves at the least,
    all half-layers (the MTP modules' too). A state is ``tokens x hc_mult x
    dim`` in the model's dtype, a hidden state 1 / ``hc_mult`` of it.
    Forward, a half-layer reads the state twice (its coefficients need a
    whole row before the read can weigh it; the write reads it again),
    writes it once, writes ``u`` and reads ``y``; backward it reads the
    state and the new state's cotangent, writes the state's, reads
    ``u``'s and writes ``y``'s; under ``cfg.remat`` the forward runs
    twice."""
    state = tokens * cfg.hc_mult * cfg.dim * cfg.jnp_dtype.itemsize
    one = 3 * state + 2 * state // cfg.hc_mult
    halves = 2 * (cfg.n_layers + cfg.n_mtp)
    return halves * one * (3 if cfg.remat else 2)
