"""A gauge of the program's metrics registry, by its full series key
(``name`` or ``name{label=value}``).

``{"kind": "registry_gauge", "series": "magi_x{kind=cast}"}``
"""


def read(spec: dict, obs):
    return obs.registry.get("gauges", {}).get(spec["series"])
