"""examples/train_pattern.py at toy size: the comparison chip_smoke.py
makes for the dense decoder, for a two-kind model, by hand."""

import importlib.util
import os

import jax
import pytest

PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "examples", "train_pattern.py",
)


@pytest.fixture(scope="module")
def example():
    spec = importlib.util.spec_from_file_location("train_pattern_example", PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_cp4_trains_the_losses_cp1_trains(example):
    with jax.enable_x64(False):
        runs = [
            example.main(["--toy", "--cp", cp, "--dtype", "float32",
                          "--steps", "2"])
            for cp in ("1", "4")
        ]
    assert runs[0] == pytest.approx(runs[1], rel=1e-5)
    assert runs[0][1] < runs[0][0]  # and it learns


def test_layers_cuts_the_pattern(example):
    with jax.enable_x64(False):
        losses = example.main(
            ["--toy", "--layers", "2", "--dtype", "float32", "--steps", "1"]
        )
    assert len(losses) == 1 and losses[0] > 0
