"""The device's idle share of a phase: 1 - union of the device
operations' intervals over the phase's length, in %.

``{"kind": "trace_idle", "phase": "window"}``
"""

from .. import trace_reduce


def read(spec: dict, obs):
    phase = obs.trace.phase(spec["phase"])
    if phase is None or not obs.trace.ops:
        return None
    return trace_reduce.idle_share_pct(obs.trace, *phase)
