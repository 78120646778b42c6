"""A kernel's device time, in ms per iteration of a phase: the summed
time of the operations whose name or jax scope matches ``pattern``,
averaged over the devices that ran one, over the phase's iterations.

``{"kind": "trace_kernel_ms", "pattern": <regex>, "phase": <phase span>}``

Nothing matches in a program whose kernels carry no such name: the
metric is then left out of the line.
"""

from .. import trace_reduce


def read(spec: dict, obs):
    phase = obs.trace.phase(spec["phase"])
    iters = obs.iters.get(spec["phase"])
    if phase is None or not iters:
        return None
    seconds = trace_reduce.kernel_seconds(obs.trace, spec["pattern"], *phase)
    if seconds <= 0.0:
        return None
    return 1e3 * seconds / iters
