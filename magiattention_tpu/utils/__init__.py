"""Utility subpackage: instrumentation, cost factors, packing, checkpointing, plan visualization."""

from .checkpoint import latest_step, restore_train_state, save_train_state
from .cost import (
    TPU_PEAK_SPECS,
    get_calc_cost_factor,
    get_comm_cost_factor,
)
from .instrument import switch_profile
from .vis import plot_dynamic_solution, plot_mask
from .packing import (
    bin_cu_seqlens,
    pack_corpus,
    pack_documents,
    packing_efficiency,
)

__all__ = [
    "TPU_PEAK_SPECS",
    "bin_cu_seqlens",
    "get_calc_cost_factor",
    "get_comm_cost_factor",
    "latest_step",
    "pack_corpus",
    "pack_documents",
    "packing_efficiency",
    "plot_dynamic_solution",
    "plot_mask",
    "restore_train_state",
    "save_train_state",
    "switch_profile",
]
