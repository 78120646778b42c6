"""What the training cell's ``correct`` can see. At random init the loss
is about ln(vocab) whatever the model does, so the loss alone holds
little; the gradients of the layers' parameters hold the mask, the
backward and the precision. Toy size, CPU."""

import json
import os

import pytest

from benchmarks import masks
from benchmarks.kinds import train_stream

TOY = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "data", "toy", "benchmarks"
)


def _load(*parts):
    with open(os.path.join(TOY, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def errors():
    """(loss error, worst gradient error) of the check, by what the
    model was handed."""
    import jax
    import jax.numpy as jnp

    from magiattention_tpu.models import init_params

    cfg = _load("configs", "toy-decoder.json")
    tr = dict(_load("traffic", "toy-onemask.json"), dtype="bfloat16")
    found = {}
    with jax.enable_x64(False):
        job = train_stream.Job(cfg, tr, 2**31 + 7, jax.devices()[:1])
        params = init_params(train_stream.key_from_seed(job.seed), job.lcfg)
        t = int(tr["check_tokens"])
        faults = {
            "bf16, as the cell runs": {},
            "float32 model": {"job": train_stream.Job(
                cfg, dict(tr, dtype="float32"), job.seed, jax.devices()[:1]
            )},
            "fp8 weights": {"model_params": jax.tree.map(
                lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype), params
            )},
            "plain causal mask": {"model_mask": masks.build_mask(
                {"type": "causal"}, t
            )},
        }
        for name, fault in faults.items():
            rel, grad = train_stream.check_errors(
                fault.pop("job", job), params, **fault
            )
            found[name] = (rel, max(grad.values()), grad)
    return found


def test_the_cell_as_it_runs_passes(errors):
    for name in ("bf16, as the cell runs", "float32 model"):
        rel, _worst, grad = errors[name]
        assert train_stream.passes(rel, grad), (name, rel, grad)
    # float32 against float32 agrees far inside what bf16 is allowed
    assert errors["float32 model"][1] < 1e-4


@pytest.mark.parametrize("fault", ["fp8 weights", "plain causal mask"])
def test_a_fault_fails_the_check_by_its_gradients(errors, fault):
    rel, worst, grad = errors[fault]
    assert not train_stream.passes(rel, grad), (fault, rel, grad)
    assert worst > train_stream.GRAD_REL_L2_TOL


def test_the_loss_alone_would_miss_a_wrong_mask(errors):
    """Why the gradients are compared: at random init the loss of a
    model that attends across documents is within the loss tolerance."""
    rel, _worst, _grad = errors["plain causal mask"]
    assert rel <= train_stream.LOSS_REL_TOL
