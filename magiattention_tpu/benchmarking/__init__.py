"""Benchmark harness (reference ``magi_attention/benchmarking/``)."""

from .bench import (
    BenchResult,
    MemoryRecorder,
    chained_ms,
    do_bench,
    enable_compile_cache,
)

__all__ = [
    "BenchResult",
    "MemoryRecorder",
    "chained_ms",
    "do_bench",
    "enable_compile_cache",
]
