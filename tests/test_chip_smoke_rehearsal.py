"""Rehearsal of ``chip_smoke.py`` without the chip: every phase function
at a tiny size on the CPU with interpret kernels (``compiled=False``),
the cp phase on 4 of the 8 virtual devices — wrong paths, arguments and
control flow are found here, at no chip time.
"""

import json

import jax
import pytest

import chip_smoke
from magiattention_tpu import telemetry

TINY = {
    "dim": 32, "heads": 4, "kv-heads": 2, "head-dim": 32, "ffn": 64,
    "vocab": 64, "rope-theta": 10000.0,
}
HEADS = dict(hq=4, hk=2, d=32)
TRAIN = dict(
    widths=TINY, layers=1, total=128, chunk=16, steps=1, dtype="float32",
    compiled=False, seed=0,
)

PHASES = {
    "device": lambda: chip_smoke.phase_device("(rehearsal)"),
    "kernel": lambda: chip_smoke.phase_kernel(
        total=256, parity_total=256, compiled=False, seed=0, **HEADS
    ),
    "api": lambda: chip_smoke.phase_api(
        total=256, chunk=32, n_docs=3, compiled=False, seed=0,
        devices=jax.devices()[:1], **HEADS
    ),
    "train": lambda: chip_smoke.phase_train(
        **TRAIN, masks=2, parity_total=128
    ),
    "serve": lambda: chip_smoke.phase_serve(
        prompts=(32, 32), gen=2, pool_tokens=2048, chunk=32,
        compiled=False, seed=0, **HEADS
    ),
    "cp": lambda: chip_smoke.phase_cp(
        **TRAIN, masks=1, devices=jax.devices()[:4]
    ),
}


@pytest.fixture(autouse=True)
def _telemetry_on():
    """As in ``chip_smoke.run``: the fallback counters record only then."""
    telemetry.set_enabled(True)
    telemetry.reset()
    yield
    telemetry.reset()
    telemetry.set_enabled(None)


@pytest.mark.parametrize("phase", list(PHASES))
def test_phase(phase):
    PHASES[phase]()
    assert chip_smoke.fallbacks_fired() == []


def test_fallbacks_fail_the_smoke():
    """A degraded plan build or a crashed autotune candidate stays
    library behaviour, but the smoke sees that it fired."""
    telemetry.record_degraded_path("plan_build_error")
    telemetry.record_autotune_measure_failure("128x128x1:sparse", "boom")
    assert len(chip_smoke.fallbacks_fired()) == 2


def test_main_refuses_cpu(capsys):
    """An uncaught exception is the non-zero exit; the last stdout line
    still says the run is not a pass."""
    with pytest.raises(RuntimeError, match="needs a TPU"):
        chip_smoke.main([])
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
