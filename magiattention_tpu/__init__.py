"""MagiAttention-TPU: a TPU-native distributed flex-attention framework.

A from-scratch JAX/XLA/Pallas rebuild of the capabilities of
SandAI-org/MagiAttention (reference: /root/reference): context-parallel
attention for ultra-long-context, heterogeneous-mask training.

Layering (mirrors reference SURVEY.md layer map, re-designed TPU-first):

- ``common/``   : range/mask data structures & enums (host-side planning types)
- ``ops/``      : Pallas flex-flash-attention kernels + jnp fallbacks
- ``meta/``     : host-side planning — dispatch/overlap/dist-attn solvers
- ``comm/``     : group_cast/group_reduce collectives over jax.lax + shard_map
- ``parallel/`` : distributed attention runtime (the hot path)
- ``serving/``  : inference path — paged KV cache + split-KV decode
- ``resilience/``: fault injection + numerical guards + degradation
- ``api/``      : user-facing key-cached interface
- ``models/``   : flagship model families built on the framework
- ``testing/``  : reference oracles + precision harness
"""

import sys as _sys
import time as _time

# the package's first statement, as a clock read: where ``process_boot``
# ends and ``package_import`` begins (telemetry/events.post_boot_spans
# turns these marks into spans the first time telemetry is on; nothing
# is recorded here, telemetry on or off)
_BOOT = {
    "began": _time.perf_counter(),
    "jax_before": "jax" in _sys.modules,
    "backend_before": False,
    "jax_import_s": 0.0,
}
if _BOOT["jax_before"]:
    from .utils.compat import backends_are_initialized as _ready  # noqa: E402

    _BOOT["backend_before"] = _ready()
else:
    # the package imports jax below whoever asks for it (telemetry does):
    # first here, so that its share of the import is known
    import jax as _jax  # noqa: E402,F401

    _BOOT["jax_import_s"] = _time.perf_counter() - _BOOT["began"]

__version__ = "0.4.0"

# reference magi_attention/__init__.py:61-83 — an explicitly-set
# MAGI_ATTENTION_LOG_LEVEL (env.log_level()) sets the package logger's
# level and attaches a formatted stderr handler (unknown values degrade
# to WARNING instead of crashing the import); unset leaves the logger
# untouched so embedders' own logging config stays in control
from .telemetry.logger import configure_logging as _configure_logging

logger = _configure_logging()

from . import common  # noqa: F401,E402
from .env import recommended_compiler_options  # noqa: F401,E402


def __getattr__(name):
    # lazy subpackage access (reference magi_attention/__init__.py exports
    # its subpackages; loading ops/models eagerly would import jax at
    # package-import time, which some host-only consumers avoid)
    import importlib

    if name in (
        "analysis", "api", "benchmarking", "comm", "config", "env",
        "meta", "models", "ops", "parallel", "resilience", "serving",
        "telemetry", "testing", "utils",
    ):
        return importlib.import_module(f".{name}", __name__)
    if name in ("init_dist_attn_runtime_key", "init_dist_attn_runtime_mgr"):
        from .api import interface

        return getattr(interface, name)
    raise AttributeError(name)


__all__ = [
    "api",
    "benchmarking",
    "comm",
    "common",
    "config",
    "env",
    "init_dist_attn_runtime_key",
    "init_dist_attn_runtime_mgr",
    "meta",
    "models",
    "ops",
    "parallel",
    "recommended_compiler_options",
    "resilience",
    "serving",
    "telemetry",
    "testing",
    "utils",
    "__version__",
]

_BOOT["ended"] = _time.perf_counter()  # the package's last statement
