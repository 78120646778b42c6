"""In-graph numerical guards (``MAGI_ATTENTION_GUARD=off|check|repair``).

The runtime's whole output is LSE-corrected merges of partial (out, lse)
pairs — one non-finite partial silently poisons everything downstream.
These sentinels sit at every merge boundary (dist_attn stage merges,
decode split merges, ``ops/correction``) and detect/contain that, fully
inside the traced program:

- detection is *error-code accumulation*: each guarded site contributes
  one bit to an int32 code carried alongside the outputs — no
  ``.item()``, no host sync, nothing value-dependent at trace time (the
  MAGI003 lint stays green);
- ``check`` leaves the data bit-identical to ``off`` and decodes the
  accumulated code at the jit boundary (:func:`consume_error_code`),
  raising a typed :class:`NumericalGuardError` naming the failing
  site(s);
- ``repair`` additionally *quarantines* bad rows in-graph — lse -> -inf,
  out -> 0, i.e. weight 0 through the all-neg-inf-hardened correction
  path (ISSUE 4) — so one poisoned partial merges as a no-op instead of
  poisoning the result. The quarantine is where-based and therefore
  differentiable: cotangents to quarantined rows are exactly zero.

A partial's legitimate "no coverage" value is lse = -inf with out = 0;
the guards treat that as healthy (only nan / +inf lse and non-finite out
trip them). Every guard contains at least one ``jnp.isfinite`` — the
``is_finite`` primitive is the guards' census marker, and the trace
audit proves the ``off`` path traces ZERO of them (the off path is
provably free).

Mode is read from the env at trace time and folded into
``flags_fingerprint``; counters: ``magi_guard_checks{site=}`` (one per
guard traced), ``magi_guard_violations{site=}`` /
``magi_guard_repairs{site=}`` (decoded at the jit boundary).
"""

from __future__ import annotations

import functools

import numpy as np

NEG_INF = float("-inf")

# one bit per site in the int32 error code; deep split counts wrap
# (site names may alias past 31 sites — decode reports every aliased
# candidate rather than dropping the violation)
_CODE_BITS = 31


class NumericalGuardError(RuntimeError):
    """A guarded merge saw a non-finite partial (``check`` mode).

    ``sites`` names the tripped guard site(s), e.g. ``("stage1",)``."""

    def __init__(self, sites):
        self.sites = tuple(sites)
        super().__init__(
            "non-finite partial detected at guard site(s) "
            f"{list(self.sites)} (MAGI_ATTENTION_GUARD=check; use "
            "'repair' to quarantine instead of raising)"
        )


def guard_mode() -> str:
    from .. import env

    return env.guard_mode()


def guards_active() -> bool:
    return guard_mode() != "off"


def new_error_code():
    import jax.numpy as jnp

    return jnp.zeros((), jnp.int32)


def _bad_rows(out, lse):
    """[..., h] bool: rows whose partial is poisoned. lse = -inf is the
    legitimate zero-coverage value and stays healthy; nan / +inf lse or
    any non-finite out element is bad."""
    import jax.numpy as jnp

    out_ok = jnp.all(jnp.isfinite(out), axis=-1)
    lse_bad = jnp.isnan(lse) | (lse == jnp.inf)
    return lse_bad | ~out_ok


def guard_partial(out, lse, code, site_index: int, site: str):
    """Guard one partial (out [..., h, d], lse [..., h]) at ``site``.

    Returns ``(out, lse, code)``: in ``check`` mode the data passes
    through bit-identically and the site bit accumulates into ``code``;
    in ``repair`` mode bad rows are quarantined to (0, -inf). ``code``
    may be None (caller not threading a code — repair still applies).
    Caller gates on :func:`guards_active`; ``off`` mode must not call
    this (the off path traces no guard ops at all).
    """
    import jax.numpy as jnp

    from .. import telemetry

    mode = guard_mode()
    assert mode != "off", "guard_partial called with guards off"
    telemetry.record_guard_check(site)
    bad = _bad_rows(out, lse)
    if code is not None:
        bit = jnp.int32(1 << (site_index % _CODE_BITS))
        code = code | jnp.where(jnp.any(bad), bit, jnp.int32(0))
    if mode == "repair":
        lse = jnp.where(bad, jnp.asarray(NEG_INF, lse.dtype), lse)
        out = jnp.where(bad[..., None], jnp.zeros((), out.dtype), out)
    return out, lse, code


def quarantine_if_repair(out, lse, site: str):
    """Repair-only guard for merge helpers that cannot thread a code
    (``ops/correction``, group LSE reduces): quarantine bad rows when
    mode is ``repair``, identity (zero traced ops) otherwise."""
    if guard_mode() != "repair":
        return out, lse
    out, lse, _ = guard_partial(out, lse, None, 0, site)
    return out, lse


def plan_guard_sites(plan) -> tuple[str, ...]:
    """Guard-site names of a DistAttnPlan, in error-code bit order —
    must match the site order ``dist_attn_local`` guards in."""
    if plan.overlap_degree == 0:
        return ("merged",)
    return ("host",) + tuple(f"stage{i}" for i in range(len(plan.stages)))


# ---------------------------------------------------------------------------
# jit-boundary consumption
# ---------------------------------------------------------------------------


def _decode_bits(value: int, sites) -> list[str]:
    sites = tuple(sites)
    out = []
    for i, s in enumerate(sites):
        if (value >> (i % _CODE_BITS)) & 1:
            out.append(s)
    return out


def _report(code, *, sites, mode: str, under_jit: bool):
    """Host side of the consume: decode the accumulated bits, tick
    counters, raise in eager check mode."""
    from .. import telemetry
    from ..telemetry.logger import get_logger

    arr = np.asarray(code).reshape(-1).astype(np.int64)
    value = 0
    for v in arr:
        value |= int(v)
    if not value:
        return
    bad = _decode_bits(value, sites)
    for s in bad:
        if mode == "repair":
            telemetry.record_guard_repair(s)
        else:
            telemetry.record_guard_violation(s)
    if mode == "check":
        # a tripped guard is a post-mortem moment (ISSUE 11): dump the
        # serving flight recorder's recent ticks, if any were recorded
        from ..telemetry.trace import get_flight_recorder

        get_flight_recorder().trigger("numerical_guard", sites=list(bad))
        if under_jit:
            # inside someone else's jit the callback cannot unwind the
            # python stack cleanly — surface loudly instead of raising
            # through the XLA runtime
            get_logger("resilience").error(
                "NumericalGuardError (under jit): non-finite partial at "
                "guard site(s) %s", bad,
            )
        else:
            raise NumericalGuardError(bad)


def consume_error_code(code, sites, *, mode: str | None = None) -> None:
    """The jit boundary of the guard design: decode an accumulated error
    code where outputs become concrete.

    Eager callers (shard_map / op entry points called outside jit) get a
    concrete code: violations/repairs are recorded and ``check`` mode
    raises :class:`NumericalGuardError` with the failing sites. Under an
    outer ``jax.jit`` the code is a tracer: the same decode runs as a
    ``jax.debug.callback`` at execution time (counters + error log — an
    exception cannot cleanly cross the XLA runtime, documented in
    docs/resilience.md).
    """
    if code is None:
        return
    if mode is None:
        mode = guard_mode()
    if mode == "off":
        return
    import jax

    if isinstance(code, jax.core.Tracer):
        try:
            jax.debug.callback(
                functools.partial(
                    _report, sites=tuple(sites), mode=mode, under_jit=True
                ),
                code,
            )
        except Exception:  # noqa: BLE001 — reporting must never take
            # the traced program down (e.g. callbacks unsupported in
            # this tracing context); detection still
            # happened, repair still applied — only the report is lost
            from ..telemetry.logger import get_logger

            get_logger("resilience").debug(
                "guard error-code report could not attach to this "
                "tracing context"
            )
        return
    _report(code, sites=tuple(sites), mode=mode, under_jit=False)
