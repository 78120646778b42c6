"""A value the traffic kind measured around the program's calls: its own
host-clock timers, and the program's ``CompileTracker`` read at the
window's edges.

``{"kind": "observation", "key": "compiles_in_window"}``
"""


def read(spec: dict, obs):
    value = obs.values.get(spec["key"])
    return None if value is None else value * spec.get("scale", 1.0)
