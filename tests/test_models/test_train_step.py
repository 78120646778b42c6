"""``make_model_train_step`` is ``value_and_grad`` + ``optimizer.update`` +
apply and nothing else: one step equals, leaf for leaf and bit for bit on
the CPU, the same three written out here (the loss, the parameters, both
of AdamW's moments), for the dense decoder and for a pattern model with
held experts, and the parameters and the optimizer's state are donated.
What stands between the gradients and the update (ISSUE 47: each matrix
through ``jax.lax.optimization_barrier`` by itself, so that the chip's
compiler does not compute a weight's update inside the matmul of its
gradient; ``tests/test_aot_train_steps_tpu.py`` holds the compiled
program to that) changes no value."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tests.test_models import pattern_harness as toy
from tests.test_models.test_pattern import CFG as AFMOE
from tests.test_models.test_scope_catalogue import toy_model


@pytest.mark.parametrize("name", ["llama", "afmoe"])
def test_a_step_is_grad_update_apply_bit_for_bit(name):
    with jax.enable_x64(False):
        assert AFMOE.expert_range == (2, 6)  # four of eight experts held
        model, params = toy_model(name)
        opt = optax.adamw(1e-3)
        state = opt.init(params)
        tokens = jnp.asarray(
            np.random.default_rng(11).integers(0, 64, (1, toy.TOTAL)),
            jnp.int32,
        )
        labels = jnp.roll(tokens, -1, axis=1)
        pos = jnp.arange(toy.TOTAL, dtype=jnp.int32)[None]
        tables = model.sharded_tables()

        @jax.jit
        def written_out(params, state):
            loss, grads = jax.value_and_grad(model.loss_fn)(
                params, tokens, labels, pos, tables
            )
            updates, state = opt.update(grads, state, params)
            return optax.apply_updates(params, updates), state, loss

        want = written_out(params, state)
        step = model.make_train_step(opt)
        donated = jax.tree.map(
            lambda a: a.donated,
            step.lower(params, state, tokens, labels, pos).args_info[0],
        )
        assert all(jax.tree.leaves(donated[:2]))
        assert not any(jax.tree.leaves(donated[2:]))
        got = step(params, state, tokens, labels, pos)
        assert all(x.is_deleted() for x in jax.tree.leaves((params, state)))

    moments = [
        s for s in got[1] if isinstance(s, optax.ScaleByAdamState)
    ]
    assert len(moments) == 1 and int(moments[0].count) == 1
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)
    ):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=jax.tree_util.keystr(path)
        )
    assert np.isfinite(float(got[2]))
