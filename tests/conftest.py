"""Test configuration: force an 8-device virtual CPU platform.

Distributed behavior is tested by simulating N devices on host CPU
(xla_force_host_platform_device_count), matching how the reference simulates
multi-rank with spawned local processes (testing/dist_common.py).

The platform is forced through jax.config before any backend init, so the
suite runs on the CPU whatever JAX_PLATFORMS says.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

# Persistent compilation cache: the suite's cost is dominated by XLA
# compiles of many distinct jit programs (tiny shapes, big graphs), so a
# warm cache cuts wall time several-fold. Safe across processes (content
# keyed); MAGI_TEST_JAX_CACHE=0 disables. Placement is
# enable_compile_cache's rule: JAX_COMPILATION_CACHE_DIR, else
# <checkout>/.jax_cache.
if os.environ.get("MAGI_TEST_JAX_CACHE") != "0":
    from magiattention_tpu.benchmarking import enable_compile_cache

    enable_compile_cache()


def pytest_addoption(parser):
    parser.addoption(
        "--run-slow",
        action="store_true",
        default=False,
        help="run slow-marked full-size scenarios (reference --skip-slow"
        " inverted: the CPU-sim suite skips them by default)",
    )


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: full-size (10k-15k token) oracle scenarios"
    )


def pytest_collection_modifyitems(config, items):
    import pytest as _pytest

    run_slow = os.environ.get("MAGI_RUN_SLOW", "").lower() in (
        "1", "true", "yes",
    )
    if config.getoption("--run-slow") or run_slow:
        return
    skip = _pytest.mark.skip(reason="slow; use --run-slow or MAGI_RUN_SLOW=1")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)
