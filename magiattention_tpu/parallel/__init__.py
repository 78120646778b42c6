"""Distributed execution runtime: CP attention plan + hot path + dispatch.

This package is the analogue of reference ``magi_attention/functional/``;
its ``*_func`` export spellings are aliased below for porters
(``dist_attn_func`` maps to the SPMD hot path ``dist_attn_local`` — the
reference's autograd Function role is plain jax autodiff here, so there
is no separate Function object; ``ffa_fa4_func`` has no analogue,
Blackwell-only).

Only the SPELLINGS are ported, not the call signatures: there is no
torch process-group argument anywhere, and the meta comes before the
shift in :func:`roll` (``roll(x, meta, shift)`` vs the reference's
``roll(x, shift, ...)``) — check each docstring when porting a call
site."""

from .dispatch import (
    ShiftPlan, dispatch, make_shift_plan, position_ids, roll, shift_local,
    shift_valid, undispatch,
)
from .dist_attn import (
    DistAttnPlan,
    build_dist_attn_plan,
    dist_attn_local,
    make_attn_params,
    make_dist_attn_fn,
)
from .qo_comm import (
    QoCommPlan,
    build_qo_comm_plan,
    make_qo_comm_attn_fn,
    qo_comm_attn_local,
)

# reference functional/__init__.py export spellings
dispatch_func = dispatch
undispatch_func = undispatch
roll_func = roll
roll_simple_func = roll
dist_attn_func = dist_attn_local

__all__ = [
    "DistAttnPlan",
    "QoCommPlan",
    "build_qo_comm_plan",
    "make_qo_comm_attn_fn",
    "qo_comm_attn_local",
    "build_dist_attn_plan",
    "dispatch",
    "dispatch_func",
    "dist_attn_func",
    "dist_attn_local",
    "make_attn_params",
    "make_dist_attn_fn",
    "make_shift_plan",
    "position_ids",
    "roll",
    "roll_func",
    "roll_simple_func",
    "ShiftPlan",
    "shift_local",
    "shift_valid",
    "undispatch",
    "undispatch_func",
]
