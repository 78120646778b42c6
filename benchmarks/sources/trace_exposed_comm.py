"""Collective time on a device while no compute runs there, in ms per
iteration of a phase.

``{"kind": "trace_exposed_comm", "phase": "fwdbwd"}``
"""

from .. import trace_reduce


def read(spec: dict, obs):
    phase = obs.trace.phase(spec["phase"])
    iters = obs.iters.get(spec["phase"])
    if phase is None or not iters or obs.chips < 2:
        return None
    total, exposed = trace_reduce.exposed_comm_seconds(obs.trace, *phase)
    if total <= 0.0:
        return None
    return 1e3 * exposed / iters
