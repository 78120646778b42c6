"""The state-space-dual scan (``ops/ssd_scan.py``) against a recurrence a
token at a time in float32: the forward and the three operand-gradient
groups, on both backends (the kernels in interpret mode), over documents
whose starts fall on a chunk's edge, inside a chunk, twice in one chunk
and on the last row, a document longer than several chunks and a
sequence that is no whole number of chunks. Each case is computed once a
backend (``_run``'s memo) and read by four tests; the cases are a table
of their own, as ``test_selective_scan.py``'s are."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from magiattention_tpu.ops import ssd_scan as ssd

CHUNK, STATES, WIDTH = 16, 8, 16
# name -> (rows, heads, heads a grid step, the rows at which a document starts)
CASES = {
    "a_reset_on_a_chunk_edge": (64, 4, 4, (0, 32)),
    "a_reset_inside_a_chunk": (64, 4, 4, (0, 39)),
    "two_resets_in_one_chunk": (64, 4, 4, (0, 18, 27)),
    "a_reset_at_the_last_row": (48, 4, 4, (0, 47)),
    "a_document_over_five_chunks": (80, 4, 4, (0,)),
    # two head blocks (b's and c's gradients are sums over both), a
    # one-token document and a last chunk the sequence does not fill
    "two_blocks_and_a_short_chunk": (70, 8, 4, (0, 15, 16, 17, 40)),
}
BACKENDS = ("pallas", "jnp")
GROUPS = {"x_delta": (0, 1), "a_d": (2, 5), "b_c": (3, 4)}


def _operands(name):
    rows, heads, _block, starts = CASES[name]
    k = jax.random.split(jax.random.PRNGKey(len(name)), 7)
    x = jax.random.normal(k[0], (rows, heads, WIDTH), jnp.float32)
    delta = jax.nn.softplus(jax.random.normal(k[1], (rows, heads)) - 1.0)
    a = -jnp.exp(0.5 * jax.random.normal(k[2], (heads,)))
    b = jax.random.normal(k[3], (rows, STATES), jnp.float32)
    c = jax.random.normal(k[4], (rows, STATES), jnp.float32)
    d = jax.random.normal(k[5], (heads,), jnp.float32)
    start = np.zeros(rows, bool)
    start[list(starts)] = True
    weight = jax.random.normal(k[6], (rows, heads, WIDTH), jnp.float32)
    return (x, delta, a, b, c, d), jnp.asarray(start), weight


def token_by_token(x, delta, a, b, c, d, start):
    """The oracle: one token a step on a [heads, width, states] state,
    the reset written out."""

    def token(s, row):
        xt, dt, bt, ct, first = row
        s = jnp.where(first, 0.0, s)
        s = (
            jnp.exp(dt * a)[:, None, None] * s
            + (dt[:, None] * xt)[:, :, None] * bt[None, None, :]
        )
        return s, s @ ct + d[:, None] * xt

    heads, width = x.shape[1:]
    _, y = jax.lax.scan(
        token, jnp.zeros((heads, width, b.shape[1])), (x, delta, b, c, start)
    )
    return y


def _value_and_grads(fn, xs, start, weight):
    def loss(*xs):
        y = fn(*xs, start)
        return (y * weight).sum(), y

    (_, y), grads = jax.value_and_grad(
        loss, argnums=tuple(range(6)), has_aux=True
    )(*xs)
    return y, grads


@functools.lru_cache(maxsize=None)
def _run(name, backend, start_left_out=None, state_dtype="float32"):
    """(y, the six gradients) of a case: ``backend`` ``oracle``, or the
    scan on one of :data:`BACKENDS`; computed once a process."""
    xs, start, weight = _operands(name)
    with jax.enable_x64(False):
        if backend == "oracle":
            return _value_and_grads(token_by_token, xs, start, weight)
        if start_left_out is not None:
            start = start.at[start_left_out].set(False)
        block = CASES[name][2]
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("MAGI_ATTENTION_KERNEL_BACKEND", backend)
            return _value_and_grads(
                lambda *a: ssd.ssd_scan(
                    *a, chunk=CHUNK, head_block=block, state_dtype=state_dtype
                ),
                xs, start, weight,
            )


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_forward_matches_the_token_by_token_recurrence(case, backend):
    y, _ = _run(case, backend)
    want, _ = _run(case, "oracle")
    assert y.shape == want.shape and y.dtype == jnp.float32
    assert _rel(y, want) < 1e-5


@pytest.mark.parametrize("group", sorted(GROUPS))
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_gradients_match_the_token_by_token_recurrence(case, backend, group):
    _, grads = _run(case, backend)
    _, want = _run(case, "oracle")
    for i in GROUPS[group]:
        assert grads[i].shape == want[i].shape
        assert _rel(grads[i], want[i]) < 2e-5, (i, _rel(grads[i], want[i]))


@pytest.mark.parametrize("backend", BACKENDS)
def test_a_start_row_left_out_reads_wrong(backend):
    """The state carried into the second document, whose start lies
    inside a chunk: every row of it differs, and so do the gradients that
    reach the first."""
    case = "a_reset_inside_a_chunk"
    y, grads = _run(case, backend, start_left_out=39)
    want, want_grads = _run(case, "oracle")
    assert _rel(y[:39], want[:39]) < 1e-5  # the rows before it are sound
    assert _rel(y[39:], want[39:]) > 1e-2
    assert _rel(grads[0], want_grads[0]) > 1e-2


@pytest.mark.parametrize("backend", BACKENDS)
def test_a_bfloat16_state_is_told_apart_at_the_checks_limit(backend):
    """The control the benchmark's check is held against
    (``train_ssd.SCAN_REL_TOL`` 4.5e-5 on float32 operands): the carried
    state rounded to bfloat16 at every chunk's end leaves the float32
    reading by three orders and lies six times over the limit."""
    case = "a_document_over_five_chunks"
    y, grads = _run(case, backend, state_dtype="bfloat16")
    want, want_grads = _run(case, "oracle")
    sound = _rel(_run(case, backend)[0], want)
    assert _rel(y, want) > 3e-4 and 4.5e-5 > 100 * sound
    assert _rel(grads[1], want_grads[1]) > 3e-4


def test_operands_in_bfloat16_keep_a_float32_state():
    """The cell's dtypes: x, b, c in bfloat16, the step in float32; y
    comes back in x's dtype, computed on a float32 state and float32
    accumulators: against the oracle on the same (rounded) operands it
    reads bfloat16's last places (the MXU's operands are rounded once
    more: ``delta x`` and the decayed ``C B^T``), not the state's drift."""
    case = "a_document_over_five_chunks"
    (x, delta, a, b, c, d), start, _w = _operands(case)
    low = [v.astype(jnp.bfloat16) for v in (x, b, c)]
    with jax.enable_x64(False):
        y = ssd.ssd_scan(low[0], delta, a, low[1], low[2], d, start, chunk=CHUNK)
        want = token_by_token(
            low[0].astype(jnp.float32), delta, a,
            low[1].astype(jnp.float32), low[2].astype(jnp.float32), d, start,
        )
    assert y.dtype == jnp.bfloat16
    assert _rel(y.astype(jnp.float32), want) < 1e-2


def test_blocking_is_checked():
    with pytest.raises(ValueError, match="multiple of 8"):
        ssd.make_ssd_params(64, 4, 16, chunk=12)
    with pytest.raises(ValueError, match="whole blocks"):
        ssd.make_ssd_params(64, 8, 64, head_block=3)
    p = ssd.make_ssd_params(16384, 64, 64)  # the cell's
    assert (p.chunk, p.head_block, p.tile_heads) == (256, 8, 2)
    assert ssd.make_ssd_params(4096, 8, 128).tile_heads == 1
    assert ssd.make_ssd_params(40, 4, 16).chunk == 40  # one short chunk


def test_the_scan_is_counted():
    from magiattention_tpu import telemetry

    telemetry.set_enabled(True)
    reg = telemetry.get_registry()
    try:
        telemetry.reset()
        xs, start, weight = _operands("two_blocks_and_a_short_chunk")
        with jax.enable_x64(False):
            _value_and_grads(
                lambda *a: ssd.ssd_scan(*a, chunk=CHUNK, head_block=4),
                xs, start, weight,
            )
        calls = {
            phase: reg.counter_value("magi_ssd_scan_calls_total", phase=phase)
            for phase in ("fwd", "bwd")
        }
        gauges = {
            name: reg.gauge_value(name)
            for name in ("magi_ssd_heads", "magi_ssd_chunks",
                         "magi_ssd_state_bytes")
        }
    finally:
        telemetry.set_enabled(False)
    assert calls == {"fwd": 1, "bwd": 1}
    # 70 rows in 5 chunks of 16; a boundary state is 8 states x 8 x 16 float32
    assert gauges == {
        "magi_ssd_heads": 8.0, "magi_ssd_chunks": 5.0,
        "magi_ssd_state_bytes": 5.0 * 8 * 128 * 4,
    }
