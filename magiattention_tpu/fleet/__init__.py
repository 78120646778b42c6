"""Serving traffic as an artifact (ISSUE 19): seeded, serializable
request traces.

:mod:`~magiattention_tpu.fleet.workload` holds the generators (Poisson /
bursty-MMPP / diurnal arrivals, zipf-shared prefixes, long-tail output
lengths) and the ``FleetTrace`` JSON format. A trace is replayed by
handing its requests to a ``serving.Scheduler``; nothing here runs one.
"""

from .workload import (  # noqa: F401
    FLEET_TRACE_FORMAT,
    FleetTrace,
    TraceRequest,
    generate_trace,
)

__all__ = [
    "FLEET_TRACE_FORMAT",
    "FleetTrace",
    "TraceRequest",
    "generate_trace",
]
