"""A kernel's share of the compute roofline, from the device trace.

``{"kind": "trace_kernel", "pattern": <regex on an operation's name or
jax scope>, "phase": <phase span>, "flops": <key of the kind's FLOPs per
iteration>}``: the FLOPs of the phase's iterations over the peak, over
the summed device time of the matching operations in that phase.
"""

from .. import flops, trace_reduce


def read(spec: dict, obs):
    phase = obs.trace.phase(spec["phase"])
    iters = obs.iters.get(spec["phase"])
    work = obs.flops.get(spec["flops"])
    if phase is None or not iters or work is None or obs.peaks is None:
        return None
    seconds = trace_reduce.kernel_seconds(obs.trace, spec["pattern"], *phase)
    if seconds <= 0.0:
        return None
    # per chip: kernel time is averaged over the devices, the work shared
    return flops.roofline_pct(
        work * iters / obs.chips, seconds, obs.peaks["bf16_tflops"]
    )
