"""Request-shape plan resolution riding the scheduler tick (ISSUE 20).

The serving loop's attention shapes change every tick — prefill chunks
advance, decode contexts grow, requests join and leave the batch. Without
plan reuse every distinct shape costs a full dispatch solve; with the
fingerprint-bucketed second-level cache (``meta/plan_fingerprint.py`` +
``api/interface.py``) near-identical shapes collapse onto one canonical
plan. This probe is the bridge: it threads the REAL request shapes of a
:class:`~magiattention_tpu.serving.scheduler.Scheduler`'s ticks through
the REAL keyed-runtime planner (``magi_attn_flex_key`` /
``magi_attn_varlen_key``), so a plan-cache hit rate
(``magi_plan_cache_hits`` / ``_misses``) can be read against a
scheduler's own traffic, not synthetic key sequences.

Shape policy (the serving layer's half of the reuse bargain):

- **Prefill**: a chunk ``[lo, hi)`` of a prompt attends causally over
  ``[0, hi)`` — resolved as a flex key with ``q=[lo, hi)``,
  ``k=[0, hi)``, CAUSAL, ``total=hi``. ``lo`` lands on the scheduler's
  chunk grid and stays exact (it is interior to the k-range); only the
  ``hi`` tail is bucketed, so prompts of near-equal length share a plan.
- **Decode**: the tick's batch becomes one packed varlen-causal mask.
  Contexts are capped at a rolling window ``decode_window`` (the
  attention window a decode step actually serves — long generations pin
  at the cap, so steady-state ticks repeat the same mask exactly), sorted
  descending (batch membership order does not change the attention
  semantics of a packed batch), and the BATCH is padded to the bucket
  grid with window-length dummy sequences — shape-class canonicalization
  so batch sizes 5, 6, 7 resolve the same key. Residual per-context
  variation is what the fingerprint bucket cache absorbs.

The probe deliberately does NOT touch the scheduler's launch ledger
(``_tick_programs``): plan resolution is host solver work, not a device
launch, and the launch-census invariants of ISSUE 16 must keep holding
with a probe attached.
"""

from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["PlanProbeStats", "PlanReuseProbe"]


@dataclasses.dataclass
class PlanProbeStats:
    """Host-side tally of what the probe resolved (the authoritative
    hit/miss accounting lives in telemetry — ``magi_plan_cache_*`` — this
    is the probe's own sanity ledger)."""

    prefill_resolutions: int = 0
    decode_resolutions: int = 0
    ticks: int = 0

    @property
    def total_resolutions(self) -> int:
        return self.prefill_resolutions + self.decode_resolutions


class PlanReuseProbe:
    """Resolve real runtime keys for each scheduler tick's shapes.

    Attach via ``Scheduler(engine, plan_probe=PlanReuseProbe())``.
    Planning runs on
    a private 1-device CPU mesh — it exercises the full solver + cache
    stack without touching the serving engine's device state, and works
    under the stubbed device layer the serving tests use (the stub patches
    engine surfaces, not the planner).
    """

    def __init__(
        self,
        *,
        decode_window: int = 32,
        chunk_size: int = 16,
        num_heads: tuple[int, int] = (2, 2),
        head_dim: int = 32,
    ):
        if decode_window < 1:
            raise ValueError(
                f"decode_window={decode_window} must be >= 1"
            )
        self.decode_window = int(decode_window)
        self.chunk_size = int(chunk_size)
        self.num_heads = tuple(num_heads)
        self.head_dim = int(head_dim)
        self.stats = PlanProbeStats()
        self._mesh = None

    # -- planning surface --------------------------------------------------

    def _mesh_or_build(self):
        if self._mesh is None:
            import jax
            from jax.sharding import Mesh

            self._mesh = Mesh(
                np.array(jax.devices("cpu")[:1]), ("cp",)
            )
        return self._mesh

    def _flex_kwargs(self) -> dict:
        return dict(
            num_heads=self.num_heads,
            head_dim=self.head_dim,
            chunk_size=self.chunk_size,
            out_dtype="float32",
        )

    # -- scheduler hooks ---------------------------------------------------

    def note_prefill(self, rid: int, lo: int, hi: int) -> None:
        """A prefill chunk [lo, hi) of request ``rid`` ran this tick."""
        if hi <= lo:
            return
        from ..api.interface import magi_attn_flex_key

        magi_attn_flex_key(
            [(lo, hi)],
            [(0, hi)],
            "causal",
            hi,
            hi,
            self._mesh_or_build(),
            **self._flex_kwargs(),
        )
        self.stats.prefill_resolutions += 1

    def note_decode(self, states) -> None:
        """A batched decode step over ``states`` ran this tick. Each
        state's context is its prompt plus the tokens decoded so far,
        capped at the rolling window."""
        if not states:
            return
        from ..api.interface import magi_attn_varlen_key

        contexts = sorted(
            (
                min(
                    st.request.prompt_len + st.tokens_done + 1,
                    self.decode_window,
                )
                for st in states
            ),
            reverse=True,
        )
        # batch padded UP to a power of two with window-length dummies:
        # batch sizes within one octave resolve the SAME packed mask
        # (coarser than bucket_len's 4-steps-per-octave grid on purpose —
        # a dummy window-length row is cheap, a distinct plan is not)
        target = 1 << (len(contexts) - 1).bit_length()
        contexts = [self.decode_window] * (
            target - len(contexts)
        ) + contexts
        cu = np.cumsum([0] + contexts)
        magi_attn_varlen_key(
            [int(v) for v in cu],
            int(cu[-1]),
            self._mesh_or_build(),
            causal=True,
            **self._flex_kwargs(),
        )
        self.stats.decode_resolutions += 1

    def on_step_end(self, report) -> None:
        """End-of-tick hook (kept for symmetry/extension; the per-shape
        resolution already happened inline)."""
        self.stats.ticks += 1
