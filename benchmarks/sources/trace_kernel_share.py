"""A kernel's share of the device's busy time in a phase, in %: the
summed time of the operations whose name or jax scope matches
``pattern`` over the union of all operations' intervals, both averaged
over devices.

``{"kind": "trace_kernel_share", "pattern": <regex>, "phase": <phase span>}``
"""

from .. import trace_reduce


def read(spec: dict, obs):
    phase = obs.trace.phase(spec["phase"])
    if phase is None:
        return None
    seconds = trace_reduce.kernel_seconds(obs.trace, spec["pattern"], *phase)
    busy = trace_reduce.busy_seconds(obs.trace, *phase)
    if seconds <= 0.0 or busy <= 0.0:
        return None
    return 100.0 * seconds / busy
