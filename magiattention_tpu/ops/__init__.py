"""Pallas TPU kernels + host-side kernel planning."""

from .block_meta import FlexAttnBlockMeta, build_block_meta
from .block_sparse import (
    BlockEnumeration,
    TickEnumeration,
    TickSegment,
    block_sparse_attn_func,
    build_block_meta_from_block_mask,
)
from .correction import (
    correct_attn_lse,
    correct_attn_lse_with_sink,
    correct_attn_out,
    correct_attn_out_lse,
    correct_attn_out_lse_with_sink,
    correct_attn_out_with_sink,
    safe_lse_merge,
)
from .flex_attn import flex_attn_with_meta, flex_flash_attn_func
from .index_attn import index_attn_func, sparse_load_attn_func
from .range_merge import merge_ranges

__all__ = [
    "BlockEnumeration",
    "FlexAttnBlockMeta",
    "TickEnumeration",
    "TickSegment",
    "block_sparse_attn_func",
    "correct_attn_lse",
    "correct_attn_lse_with_sink",
    "correct_attn_out",
    "correct_attn_out_lse",
    "correct_attn_out_lse_with_sink",
    "correct_attn_out_with_sink",
    "safe_lse_merge",
    "build_block_meta_from_block_mask",
    "build_block_meta",
    "flex_attn_with_meta",
    "flex_flash_attn_func",
    "index_attn_func",
    "merge_ranges",
    "sparse_load_attn_func",
]
