"""Enums shared across the framework.

Behavioral parity with reference ``magi_attention/common/enum.py`` (int codes
for mask types are part of the kernel ABI: 0=FULL, 1=CAUSAL, 2=INVCAUSAL,
3=BICAUSAL — chosen so that bit0 = "causal lower bound", bit1 = "inv-causal
upper bound", which the Pallas kernel exploits directly).
"""

from __future__ import annotations

import enum
from typing import Literal, TypeAlias

import numpy as np

GroupReduceOp: TypeAlias = Literal["sum", "avg", "lse"]


class AttnType(enum.Enum):
    """Type of attention calculation."""

    SELF_ATTN = "self_attn"
    CROSS_ATTN = "cross_attn"


class AttnRole(enum.Enum):
    """Tensor role in attention."""

    QUERY = "query"
    KEY = "key"
    VALUE = "value"


# A slice's type word: the two bound bits below, log2 of the step above
# them (0 for the unstepped types, so their words are 0..3 as before).
MASK_TYPE_BITS = 2
MAX_MASK_STEP_LOG2 = 15


class AttnMaskType(enum.IntEnum):
    """Unit mask types applied per (q_range, k_range) attention slice.

    The int values are a stable ABI shared with the Pallas kernels:
    bit 0 set -> causal constraint (bottom-right aligned lower triangle),
    bit 1 set -> inv-causal constraint (top-left aligned upper triangle).

    Semantics (see reference flex_flash_attn.py:1247-1341):
      FULL      : every q in q_range attends every k in k_range.
      CAUSAL    : bottom-right aligned — allow iff (k - k_end) <= (q - q_end),
                  i.e. the *last* q row sees the whole k_range.
      INVCAUSAL : top-left aligned — allow iff (k - k_start) >= (q - q_start),
                  i.e. the *first* q row sees the whole k_range.
      BICAUSAL  : intersection of CAUSAL and INVCAUSAL.

    A bound may move in steps of ``s`` keys every ``s`` rows instead of
    one key a row (:meth:`with_step`, s a power of two): the two bounds
    then compare block indices counted from the slice's aligned corner,
      CAUSAL    : (k_end - 1 - k) // s >= (q_end - 1 - q) // s,
      INVCAUSAL : (k - k_start) // s >= (q - q_start) // s,
    and s = 1 is the predicate above. The stepped types are members made
    on demand (``AttnMaskType(word)`` with ``word = type | log2(s) << 2``):
    they pass wherever a type passes, ``int()`` of one is the kernels'
    type word, and none of them equals one of the four named members, so
    code that branches on a named member reads :attr:`base` and
    :attr:`step`.
    """

    FULL = 0
    CAUSAL = 1
    INVCAUSAL = 2
    BICAUSAL = 3

    @classmethod
    def _missing_(cls, value):
        if (
            isinstance(value, (int, np.integer))
            and 4 <= int(value) < (MAX_MASK_STEP_LOG2 + 1) << MASK_TYPE_BITS
        ):
            value = int(value)
            member = int.__new__(cls, value)
            base = cls(value & 3)
            member._name_ = f"{base.name}_STEP{1 << (value >> MASK_TYPE_BITS)}"
            member._value_ = value
            # later lookups of the same word find this member
            return cls._value2member_map_.setdefault(value, member)
        return None

    @classmethod
    def from_int_type(cls, int_type: int) -> "AttnMaskType":
        return cls(int_type)

    def to_int_type(self) -> int:
        return int(self.value)

    @property
    def is_causal_bound(self) -> bool:
        return bool(self.value & 1)

    @property
    def is_inv_causal_bound(self) -> bool:
        return bool(self.value & 2)

    @property
    def base(self) -> "AttnMaskType":
        """The named type whose bounds this one has (itself at step 1)."""
        return AttnMaskType(self.value & 3)

    @property
    def step(self) -> int:
        """Keys a bound moves at a time, every that many rows (1: a key
        a row)."""
        return 1 << (self.value >> MASK_TYPE_BITS)

    def with_step(self, step: int) -> "AttnMaskType":
        """This type's bounds in steps of ``step`` (a power of two).
        FULL has no bound to step and stays FULL."""
        step = int(step)
        if step < 1 or step & (step - 1) or step >> MAX_MASK_STEP_LOG2 > 1:
            raise ValueError(
                f"a mask step is a power of two up to "
                f"{1 << MAX_MASK_STEP_LOG2}, got {step}"
            )
        if self.base is AttnMaskType.FULL:
            return AttnMaskType.FULL
        return AttnMaskType(
            (self.value & 3) | (step.bit_length() - 1) << MASK_TYPE_BITS
        )


class AttnOverlapMode(enum.Enum):
    """Multi-stage-overlap scheduling mode."""

    STATIC = "static"
    DYNAMIC = "dynamic"


class DispatchAlgType(enum.Enum):
    """Load-balance bin-packing algorithms for the dispatch solver."""

    LOWER_BOUND = "lower_bound"
    DYNAMIC_PROGRAMMING = "dynamic_programming"
    BINARY_SEARCH = "binary_search"
    MIN_HEAP = "min_heap"
    BACKTRACK_PRUNING = "backtrack_pruning"
    TOPP_HEAP = "topp_heap"
    RANDOM_SELECT = "random_select"
    SEQUENTIAL_SELECT = "sequential_select"
    BATCH_TOPP_HEAP = "batch_topp_heap"
    SORTED_SEQUENTIAL_SELECT = "sorted_sequential_select"


class OverlapAlgType(enum.Enum):
    """Multi-stage overlap partitioning algorithms."""

    UNIFORM = "uniform"
    GREEDY = "greedy"


class DynamicAttnAlgType(enum.Enum):
    """Dynamic (qo-comm) attention partitioning algorithms."""

    BINARY_GREEDY_PARALLEL = "binary_greedy_parallel"
    BINARY_GREEDY = "binary_greedy"
    FAST_SIMPLEX_NETWORK_FLOW = "fast_simplex_network_flow"
    SIMPLEX_NETWORK_FLOW = "simplex_network_flow"
    GREEDY_RANDOM_GRID = "greedy_random_grid"
    NON_COMMUNICATION_QO = "non_communication_qo"


class AttnKernelBackend(enum.Enum):
    """Which attention kernel executes the per-stage AttnArgs.

    PALLAS : the TPU Pallas flex-flash-attention kernel (production path).
    JNP    : pure-jnp dense reference (any platform; testing/precision).
    JNP_ONLINE : block-wise online-softmax jnp variant (lower memory).
    """

    PALLAS = "pallas"
    JNP = "jnp"
    JNP_ONLINE = "jnp_online"


class AttnPrecision(enum.Enum):
    """Compute precision for the attention kernels."""

    BF16 = "bf16"
    FP32 = "fp32"
    FP64 = "fp64"

    def to_jnp_dtype(self):
        import jax.numpy as jnp

        return {
            AttnPrecision.BF16: jnp.bfloat16,
            AttnPrecision.FP32: jnp.float32,
            AttnPrecision.FP64: jnp.float64,
        }[self]
