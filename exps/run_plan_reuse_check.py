"""Plan-reuse gate (``make plan-reuse-check``) — CPU.

The ISSUE 20 acceptance surface for fingerprint-bucketed plan reuse
(``meta/plan_fingerprint.py`` + the second-level cache in
``api/interface.py``):

1. **Parity**: for a family of masks covering FULL / CAUSAL / INVCAUSAL
   / BICAUSAL slices and packed varlen-causal batches, the bucketed
   adapter path (``MAGI_ATTENTION_PLAN_REUSE=bucket``) must match the
   exact reuse-off plan — forward output AND q/k/v gradients — on BOTH
   kernel backends (``jnp`` dense reference, ``pallas`` in interpret
   mode). Both reuse flavors are exercised per mask: the fingerprint-miss
   path (canonical cold solve + adapter) and the bucket-hit path (a
   second, slightly different mask served off the live canonical plan).
2. **Exact-hit identity**: with reuse ON, re-requesting the same mask
   must return the SAME key and the SAME mgr object (the exact-key LRU
   stays in front of the fingerprint cache — byte-for-byte identical to
   the reuse-off path), and a mask already on bucket boundaries must not
   grow the fingerprint cache.
3. ``--self-test``: a PLANTED mis-padded dispatch — one REAL row of the
   bucketed adapter's dispatch table stolen (swapped with another real
   row) — must trip the parity gate, proving the gate catches real
   layout corruption. (Corrupting a pad slot would NOT change real
   outputs; the plant must touch a real row.)

Exits non-zero on any violation.
"""

import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

PASS = "\x1b[32mPASS\x1b[0m"
FAIL = "\x1b[31mFAIL\x1b[0m"

# fp32 allclose: the canonical plan partitions blocks differently, so
# reduction order (and pallas block boundaries) may differ
TOL = dict(rtol=2e-4, atol=2e-4)

# parity mask family: (name, q_ranges, k_ranges, types, total) — every
# mask type, each with at least one bucketed (off-grid) segment; the
# "+1" variant for the bucket-hit flavor is derived by extending total
PARITY_MASKS = [
    ("causal", [(0, 51)], [(0, 51)], ["causal"], 51),
    ("varlen_causal", [(0, 21), (21, 51)], [(0, 21), (21, 51)],
     ["causal", "causal"], 51),
    # tail segment len 21 -> bucket 24: the +1 extend (len 22) stays in
    # the same bucket, so BOTH flavors engage (a len-11 tail would land
    # its extend exactly on the 12-grid and degrade to the exact path)
    ("full_offset", [(32, 53)], [(0, 32)], ["full"], 53),
    ("invcausal_offset", [(32, 53)], [(0, 32)], ["inv_causal"], 53),
    ("bicausal_tail", [(0, 30)], [(0, 30)], ["bi_causal"], 51),
    ("mixed", [(0, 10), (10, 51)], [(0, 10), (0, 51)],
     ["full", "causal"], 51),
]


def _mesh():
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices("cpu")[:1]), ("cp",))


def _extend_mask(q_ranges, k_ranges, total, delta):
    """Grow every range ending at ``total`` by ``delta`` (the roll/extend
    shape class: same structure, one more token)."""
    ntot = total + delta

    def grow(rs):
        return [
            (s, ntot if e == total else e) for (s, e) in rs
        ]

    return grow(q_ranges), grow(k_ranges), ntot


def _run_mask(mesh, q_ranges, k_ranges, types, total, interpret, corrupt=False):
    """Build the key under the CURRENT env, run fwd+grad, and return
    (outputs..., mgr). Deterministic inputs per (total,) so reuse-on and
    reuse-off runs see identical tensors."""
    import jax
    import jax.numpy as jnp

    from magiattention_tpu.api import interface as api

    key = api.magi_attn_flex_key(
        q_ranges, k_ranges, types, total, total, mesh,
        num_heads=(2, 2), head_dim=32, chunk_size=16,
        out_dtype="float32", interpret=interpret,
    )
    mgr = api.get_runtime_mgr(key)
    if corrupt:
        # --self-test plant: steal one REAL dispatch row (swap the first
        # two distinct real entries). A pad-slot plant would be invisible
        # in real outputs — the theft must land on served tokens.
        idx = np.array(mgr._bucket_dispatch_idx)
        real_total = key.total_seqlen_q - key.pad_size
        real_pos = np.flatnonzero(idx < real_total)
        a, b = real_pos[0], real_pos[1]
        idx[a], idx[b] = idx[b], idx[a]
        mgr._bucket_dispatch_idx = idx
    rng = np.random.default_rng(total)
    x = jnp.asarray(rng.standard_normal((total, 2, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((total, 2, 32)), jnp.float32)

    def loss(q, k, v):
        qd, kd, vd = mgr.dispatch(q), mgr.dispatch(k), mgr.dispatch(v)
        out, _meta = mgr.calc_attn(qd, kd, vd)
        return jnp.sum(mgr.undispatch(out) * w)

    lval, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(x, x, x)
    return float(lval), [np.asarray(g) for g in grads], mgr, key


def _clear_all():
    from magiattention_tpu.api import interface as api

    api.clear_cache()


def parity_check(self_test: bool = False) -> list[str]:
    """Reuse-on (both flavors) vs reuse-off parity over the mask family,
    on both backends. Returns a list of violation strings."""
    from magiattention_tpu.api.interface import BucketedDistAttnRuntimeMgr

    mesh = _mesh()
    errors: list[str] = []
    engaged = 0
    for backend, interpret in (("jnp", None), ("pallas", True)):
        os.environ["MAGI_ATTENTION_KERNEL_BACKEND"] = backend
        for name, qr, kr, ts, total in PARITY_MASKS:
            qr2, kr2, total2 = _extend_mask(qr, kr, total, 1)
            # references: exact plans, reuse off
            os.environ["MAGI_ATTENTION_PLAN_REUSE"] = "off"
            _clear_all()
            l_ref, g_ref, m_ref, _ = _run_mask(
                mesh, qr, kr, ts, total, interpret
            )
            _clear_all()
            l_ref2, g_ref2, _, _ = _run_mask(
                mesh, qr2, kr2, ts, total2, interpret
            )
            # reuse on: first request = fingerprint-miss flavor
            os.environ["MAGI_ATTENTION_PLAN_REUSE"] = "bucket"
            _clear_all()
            corrupt = self_test and name == "causal" and backend == "jnp"
            l_on, g_on, m_on, _ = _run_mask(
                mesh, qr, kr, ts, total, interpret, corrupt=corrupt
            )
            bucketed = isinstance(m_on, BucketedDistAttnRuntimeMgr)
            if bucketed:
                engaged += 1
                # second request, same bucket = bucket-hit flavor
                l_hit, g_hit, m_hit, _ = _run_mask(
                    mesh, qr2, kr2, ts, total2, interpret
                )
                if not isinstance(m_hit, BucketedDistAttnRuntimeMgr):
                    errors.append(
                        f"[{backend}/{name}] +1-token extend did not "
                        "take the bucketed path"
                    )
                elif m_hit.canonical_key != m_on.canonical_key:
                    errors.append(
                        f"[{backend}/{name}] extend resolved a different "
                        "canonical plan (bucket-hit path not engaged)"
                    )
                else:
                    if not np.allclose(l_hit, l_ref2, **TOL):
                        errors.append(
                            f"[{backend}/{name}] bucket-hit loss parity: "
                            f"{l_hit} vs {l_ref2}"
                        )
                    for gi, (a, b) in enumerate(zip(g_hit, g_ref2)):
                        if not np.allclose(a, b, **TOL):
                            errors.append(
                                f"[{backend}/{name}] bucket-hit grad[{gi}] "
                                f"parity: max diff "
                                f"{np.abs(a - b).max():.3e}"
                            )
            if not np.allclose(l_on, l_ref, **TOL):
                errors.append(
                    f"[{backend}/{name}] fwd loss parity: "
                    f"{l_on} vs {l_ref} (bucketed={bucketed})"
                )
            for gi, (a, b) in enumerate(zip(g_on, g_ref)):
                if not np.allclose(a, b, **TOL):
                    errors.append(
                        f"[{backend}/{name}] grad[{gi}] parity: max diff "
                        f"{np.abs(a - b).max():.3e} (bucketed={bucketed})"
                    )
    # the family must actually exercise the adapter, or parity is vacuous
    if engaged < 8:
        errors.append(
            f"only {engaged} mask runs took the bucketed path "
            "(expected >= 8 of 12) — the parity family has gone vacuous"
        )
    return errors


def exact_hit_check() -> list[str]:
    """Exact-key requests stay in front of the fingerprint cache."""
    from magiattention_tpu.api import interface as api

    mesh = _mesh()
    errors: list[str] = []
    os.environ["MAGI_ATTENTION_PLAN_REUSE"] = "bucket"
    os.environ["MAGI_ATTENTION_KERNEL_BACKEND"] = "jnp"
    _clear_all()
    qr, kr, ts, total = [(0, 51)], [(0, 51)], ["causal"], 51
    k1 = api.magi_attn_flex_key(
        qr, kr, ts, total, total, mesh,
        num_heads=(2, 2), head_dim=32, chunk_size=16,
        out_dtype="float32",
    )
    m1 = api.get_runtime_mgr(k1)
    k2 = api.magi_attn_flex_key(
        qr, kr, ts, total, total, mesh,
        num_heads=(2, 2), head_dim=32, chunk_size=16,
        out_dtype="float32",
    )
    if k2 != k1:
        errors.append("repeat request resolved a different key")
    if api.get_runtime_mgr(k2) is not m1:
        errors.append(
            "repeat request resolved a different mgr object — the exact "
            "LRU is no longer in front of the fingerprint cache"
        )
    # a mask already on bucket boundaries must not touch the fingerprint
    # cache (identity canonicalization short-circuits)
    before = len(api._plan_reuse_cache)
    api.magi_attn_flex_key(
        [(0, 64)], [(0, 64)], ["causal"], 64, 64, mesh,
        num_heads=(2, 2), head_dim=32, chunk_size=16,
        out_dtype="float32",
    )
    if len(api._plan_reuse_cache) != before:
        errors.append(
            "an on-grid mask grew the fingerprint cache (identity masks "
            "must short-circuit to the exact LRU)"
        )
    return errors


def self_test() -> int:
    """The planted mis-padded dispatch MUST trip the parity gate."""
    errors = parity_check(self_test=True)
    planted = [e for e in errors if "[jnp/causal]" in e]
    if not planted:
        print(f"{FAIL} self-test: stolen dispatch row NOT caught")
        return 1
    print(
        f"{PASS} self-test: stolen real dispatch row caught by parity "
        f"gate ({len(planted)} violations, e.g. {planted[0]!r})"
    )
    return 0


def main() -> int:
    if "--self-test" in sys.argv[1:]:
        return self_test()
    failures = 0
    for title, fn in (
        ("parity (both backends, fwd+grad)", parity_check),
        ("exact-hit identity", exact_hit_check),
    ):
        errors = fn()
        if errors:
            failures += 1
            print(f"{FAIL} {title}")
            for e in errors:
                print(f"  - {e}")
        else:
            print(f"{PASS} {title}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
