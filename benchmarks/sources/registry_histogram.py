"""A histogram of the program's metrics registry (``telemetry.snapshot``).

``{"kind": "registry_histogram", "name": ..., "stat": "mean", "scale": 1000}``
"""


def read(spec: dict, obs):
    hist = obs.registry.get("histograms", {}).get(spec["name"])
    if not hist or not hist.get("count"):
        return None
    return hist[spec.get("stat", "mean")] * spec.get("scale", 1.0)
