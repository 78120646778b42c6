"""A kernel's share of the memory roofline, from the device trace.

``{"kind": "trace_kernel_bytes", "pattern": <regex on an operation's
name or jax scope>, "phase": <phase span>, "bytes": <key of the kind's
work per iteration>}``: the bytes the phase's iterations cannot avoid
moving over the HBM's bandwidth (``peaks.json``: ``hbm_gbps``), over the
summed device time of the matching operations in that phase. For a kernel
with no matmul (the selective scan): its roofline is the HBM's. The
bytes sit beside the FLOPs in what the kind hands back
(``Observations.flops``: a step's work by name).

Nothing matches in a program that has no such kernel: the metric is then
left out of the line.
"""

from .. import trace_reduce


def read(spec: dict, obs):
    phase = obs.trace.phase(spec["phase"])
    iters = obs.iters.get(spec["phase"])
    work = obs.flops.get(spec["bytes"])
    if phase is None or not iters or work is None or obs.peaks is None:
        return None
    seconds = trace_reduce.kernel_seconds(obs.trace, spec["pattern"], *phase)
    if seconds <= 0.0:
        return None
    # per chip: kernel time is averaged over the devices, the work shared
    least = work * iters / obs.chips / (obs.peaks["hbm_gbps"] * 1e9)
    return 100.0 * least / seconds
