"""Benchmark harness: do_bench and memory recorder sanity."""

import jax.numpy as jnp

from magiattention_tpu.benchmarking import do_bench


def test_do_bench_times_and_memory():
    f = lambda x: jnp.sum(x * x)
    x = jnp.ones((256, 256), jnp.float32)
    r = do_bench(f, x, warmup=1, rep=3, inner=2, record_memory=True)
    assert r.min_ms <= r.median_ms <= r.max_ms
    assert r.tflops(1e9) > 0


def test_memory_recorder_graceful_on_cpu():
    """CPU backend may not expose memory_stats; the recorder must stay
    usable and report whatever the backend gives (possibly nothing)."""
    import jax.numpy as jnp

    from magiattention_tpu.benchmarking import MemoryRecorder, do_bench

    with MemoryRecorder(interval_s=0.001) as rec:
        _ = jnp.ones((256, 256)) @ jnp.ones((256, 256))
    assert isinstance(rec.peak_bytes, dict)  # may be empty on CPU

    res = do_bench(
        lambda: jnp.ones((64, 64)) @ jnp.ones((64, 64)),
        warmup=1, rep=2, inner=1, record_memory=True,
    )
    if res.peak_bytes is None:
        assert res.peak_bytes_per_device == ()
    else:
        assert res.peak_bytes_per_device
        assert res.peak_bytes == max(res.peak_bytes_per_device)
