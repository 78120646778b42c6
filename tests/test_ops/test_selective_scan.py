"""The selective scan (``ops/selective_scan.py``) against a token-by-token
float32 scan: the forward and the three operand-gradient groups, on both
backends (the kernels in interpret mode), over documents whose starts
fall on, before and after a chunk's edge, a one-token document, a
document longer than several chunks and a sequence that is no whole
number of chunks. Each case is computed once a backend (``_run``'s memo)
and read by four tests. ``kernel_cases.py``'s table is of masks and flex
tiles: a scan has neither, so its cases are a table of their own here."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from magiattention_tpu.ops import selective_scan as ss

CHUNK, STATES = 16, 4
# name -> (rows, channels, channel block, the rows at which a document starts)
CASES = {
    "start_on_a_chunk_edge": (64, 256, 256, (0, 32)),
    "start_one_before_an_edge": (64, 256, 256, (0, 31)),
    "start_one_after_an_edge": (64, 256, 256, (0, 33)),
    "a_one_token_document": (48, 256, 256, (0, 20, 21)),
    "a_document_over_five_chunks": (80, 256, 256, (0,)),
    # two channel blocks (b's and c's gradients are sums over both) and a
    # last chunk the sequence does not fill
    "two_blocks_and_a_short_chunk": (70, 256, 128, (0, 15, 16, 17, 40)),
}
BACKENDS = ("pallas", "jnp")
GROUPS = {"u_delta": (0, 1), "a_d": (2, 5), "b_c": (3, 4)}


def _operands(name):
    rows, channels, _block, starts = CASES[name]
    k = jax.random.split(jax.random.PRNGKey(len(name)), 7)
    u = jax.random.normal(k[0], (rows, channels), jnp.float32)
    delta = jax.nn.softplus(jax.random.normal(k[1], (rows, channels)) - 1.0)
    a = -jnp.exp(0.5 * jax.random.normal(k[2], (channels, STATES)))
    b = jax.random.normal(k[3], (rows, STATES), jnp.float32)
    c = jax.random.normal(k[4], (rows, STATES), jnp.float32)
    d = jax.random.normal(k[5], (channels,), jnp.float32)
    start = np.zeros(rows, bool)
    start[list(starts)] = True
    weight = jax.random.normal(k[6], (rows, channels), jnp.float32)
    return (u, delta, a, b, c, d), jnp.asarray(start), weight


def token_by_token(u, delta, a, b, c, d, start):
    """The oracle: one token a step, the reset written out."""

    def token(s, row):
        ut, dt, bt, ct, first = row
        s = jnp.where(first, 0.0, s)
        s = jnp.exp(dt[:, None] * a) * s + (dt * ut)[:, None] * bt[None, :]
        return s, s @ ct + d * ut

    _, y = jax.lax.scan(token, jnp.zeros(a.shape), (u, delta, b, c, start))
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
                lambda *a: ss.selective_scan(
                    *a, chunk=CHUNK, channel_block=block,
                    state_dtype=state_dtype,
                ),
                xs, start, weight,
            )


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_forward_matches_the_token_by_token_scan(case, backend):
    y, _ = _run(case, backend)
    want, _ = _run(case, "oracle")
    assert y.shape == want.shape and y.dtype == jnp.float32
    assert _rel(y, want) < 1e-5


@pytest.mark.parametrize("group", sorted(GROUPS))
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_gradients_match_the_token_by_token_scan(case, backend, group):
    _, grads = _run(case, backend)
    _, want = _run(case, "oracle")
    for i in GROUPS[group]:
        assert grads[i].shape == want[i].shape
        assert _rel(grads[i], want[i]) < 2e-5, (i, _rel(grads[i], want[i]))


@pytest.mark.parametrize("backend", BACKENDS)
def test_a_start_row_left_out_reads_wrong(backend):
    """The state carried into the second document: every row of it
    differs, and so do the gradients that reach the first."""
    case = "start_one_after_an_edge"
    y, grads = _run(case, backend, start_left_out=33)
    want, want_grads = _run(case, "oracle")
    assert _rel(y[:33], want[:33]) < 1e-5  # the rows before it are sound
    assert _rel(y[33:], want[33:]) > 1e-2
    assert _rel(grads[0], want_grads[0]) > 1e-2


@pytest.mark.parametrize("backend", BACKENDS)
def test_a_bfloat16_state_reads_wrong(backend):
    """The control the benchmark's check is held against: the state
    rounded to bfloat16 after every token leaves the float32 reading by
    three orders."""
    case = "a_document_over_five_chunks"
    y, grads = _run(case, backend, state_dtype="bfloat16")
    want, want_grads = _run(case, "oracle")
    sound = _rel(_run(case, backend)[0], want)
    assert _rel(y, want) > 1e-3 > 100 * sound
    assert _rel(grads[1], want_grads[1]) > 1e-3


def test_operands_in_bfloat16_keep_a_float32_state():
    """The cell's dtypes: u, b, c in bfloat16, the step in float32; y
    comes back in u's dtype, computed on a float32 state: against the
    oracle on the same (rounded) operands it reads bfloat16's last
    place, not the state's drift."""
    case = "a_document_over_five_chunks"
    (u, delta, a, b, c, d), start, _w = _operands(case)
    low = [x.astype(jnp.bfloat16) for x in (u, b, c)]
    with jax.enable_x64(False):
        y = ss.selective_scan(
            low[0], delta, a, low[1], low[2], d, start, chunk=CHUNK
        )
        want = token_by_token(
            low[0].astype(jnp.float32), delta, a,
            low[1].astype(jnp.float32), low[2].astype(jnp.float32), d, start,
        )
    assert y.dtype == jnp.bfloat16
    assert _rel(y.astype(jnp.float32), want) < 4e-3


def test_blocking_is_checked():
    with pytest.raises(ValueError, match="multiple of 8"):
        ss.make_scan_params(64, 256, chunk=12)
    with pytest.raises(ValueError, match="whole blocks"):
        ss.make_scan_params(64, 384, channel_block=256)
    p = ss.make_scan_params(16384, 5120)
    assert (p.chunk, p.channel_block, p.lane, p.tiles) == (128, 1024, 128, 8)
    assert ss.make_scan_params(40, 96).lane == 96  # narrower than a register


def test_the_scan_is_counted(monkeypatch):
    from magiattention_tpu import telemetry

    telemetry.set_enabled(True)
    try:
        telemetry.reset()
        xs, start, weight = _operands("start_on_a_chunk_edge")
        with jax.enable_x64(False):
            _value_and_grads(
                lambda *a: ss.selective_scan(*a, chunk=CHUNK), xs, start, weight
            )
        snap = telemetry.snapshot()
    finally:
        telemetry.set_enabled(False)
    text = str(snap)
    for name in ("magi_ssm_scan_calls_total", "magi_ssm_chunks",
                 "magi_ssm_state_bytes"):
        assert name in text, name
