"""Hardware cost factors for the overlap/dispatch solvers.

Role of reference ``utils/_utils.py`` get_calc_cost_factor /
get_comm_cost_factor (which read H100/NVLink peak specs,
testing/precision.py:40-51): seconds-per-unit conversion factors from
hardware peaks, used to weigh comm vs calc when scheduling overlap stages.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class TpuPeakSpec:
    bf16_tflops: float  # peak matmul TFLOPs/s per chip
    hbm_gbps: float  # HBM bandwidth GB/s
    ici_gbps: float  # per-link ICI bandwidth GB/s (one direction)
    mfu: float = 0.5  # achievable fraction for attention workloads
    dcn_gbps: float = 25.0  # inter-slice data-center network GB/s per host


# public-spec numbers for common TPU generations
TPU_PEAK_SPECS = {
    "v4": TpuPeakSpec(bf16_tflops=275.0, hbm_gbps=1228.0, ici_gbps=50.0),
    "v5e": TpuPeakSpec(bf16_tflops=197.0, hbm_gbps=819.0, ici_gbps=50.0),
    "v5p": TpuPeakSpec(bf16_tflops=459.0, hbm_gbps=2765.0, ici_gbps=100.0),
    "v6e": TpuPeakSpec(bf16_tflops=918.0, hbm_gbps=1640.0, ici_gbps=100.0),
}


# ``jax.Device.device_kind`` of each generation in the table
DEVICE_KIND_GENERATION = {
    "TPU v4": "v4",
    "TPU v5 lite": "v5e",
    "TPU v5": "v5p",
    "TPU v6 lite": "v6e",
}


def generation_of_device_kind(device_kind: str) -> str:
    """The peak-table key of a ``device_kind`` as jax reports it; a kind
    the table does not know is an error, not a default."""
    gen = DEVICE_KIND_GENERATION.get(device_kind)
    if gen is None:
        raise ValueError(
            f"unknown TPU device_kind {device_kind!r}; known: "
            f"{sorted(DEVICE_KIND_GENERATION)}"
        )
    return gen


def peak_spec(generation: str) -> TpuPeakSpec:
    """The generation's row of the peak table (unknown = error)."""
    spec = TPU_PEAK_SPECS.get(generation)
    if spec is None:
        raise ValueError(
            f"unknown TPU generation {generation!r}; known: "
            f"{sorted(TPU_PEAK_SPECS)} "
            "(set MAGI_ATTENTION_TPU_GENERATION accordingly)"
        )
    return spec


def get_calc_cost_factor(
    num_heads_q: int,
    head_dim: int,
    generation: str = "v5p",
    mfu: float | None = None,
) -> float:
    """Seconds per unit mask *area* of attention (fwd), from peak specs.

    FLOPs per area unit = 4 * nh_q * hd (2 matmuls); seconds = flops /
    (peak * mfu). Relative magnitudes are what the solvers consume.
    """
    spec = peak_spec(generation)
    eff = spec.bf16_tflops * 1e12 * (mfu if mfu is not None else spec.mfu)
    return 4.0 * num_heads_q * head_dim / eff


def get_comm_cost_factor(
    num_heads_kv: int,
    head_dim: int,
    generation: str = "v5p",
    bytes_per_elt: int = 2,
    bwu: float = 0.6,
    link: str = "ici",
) -> float:
    """Seconds per KV *token row* moved over the given link (K and V).

    bytes per row = 2 (K+V) * nh_kv * hd * dtype bytes; seconds = bytes /
    (link bandwidth * utilization) — the reference's A2A_BWU analogue.
    ``link``: 'ici' (intra-slice) or 'dcn' (inter-slice hop of the
    hierarchical cast).
    """
    spec = peak_spec(generation)
    bw = spec.ici_gbps if link == "ici" else spec.dcn_gbps
    return (2.0 * num_heads_kv * head_dim * bytes_per_elt) / (
        bw * 1e9 * bwu
    )
