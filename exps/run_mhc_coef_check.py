"""The stream mix's coefficient rule (``models/pattern._mhc_normed``, ISSUE
52: a backward written out in bf16 pieces) against the same
``Precision.HIGHEST`` product under autodiff, which it replaced: its
numbers, its time, and the toy check's reading seed by seed.

``--what numbers`` (default; on whatever ``jax.devices()`` offers, the
chip through ``chiprun``): at ``--size t n dim`` (the cell's 8192 4 3584)
a bfloat16 and a float32 state through both forms, forward, ``d phi`` and
``dx`` against float64 on the host.
``--what chain``: ms a half-layer of a loss and gradient over three
checkpointed layers chained through ``_mhc_write``, as the step chains
them, with the rule, with the form under autodiff, and with the rule's
``d phi`` over the whole state (``n = 1``) instead of a stream at a time.
``--what toy --seeds 1 2 3`` (CPU): the worst gradient but a mixer's
``alpha`` of the toy bfloat16 model that
``tests/test_benchmarks/test_mhc_check.py`` bounds at 0.08 on seed 1, with
either form: the bound is one seed's rounding noise (any change to the
forward's sums moves it), and this is how to read it again.

One JSON line a reading; ``--out`` appends them to a file as well.
"""

import argparse
import json
import os
import sys
import time
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from magiattention_tpu.models import pattern  # noqa: E402

K, EPS, F32 = 24, 1e-6, jnp.dtype("float32")


def highest_normed(x, phi, eps, cdt, n):
    """The form before ISSUE 52: the same forward, autodiff's backward."""
    xc = x.astype(cdt)
    m = jax.lax.dot_general(
        phi, xc, (((0,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=cdt,
    )
    return m * jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1) + eps)[None]


FORMS = {"rule": pattern._mhc_normed, "highest": highest_normed}


def rel(a, b):
    a, b = (
        v if isinstance(v, np.ndarray)
        else np.asarray(v.astype(jnp.float32), np.float64) for v in (a, b)
    )
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def numbers(t, n, dim, seed):
    rng = np.random.default_rng(seed)
    phi = jnp.asarray(rng.standard_normal((n * dim, K)) * 0.01, jnp.float32)
    dm = jnp.asarray(rng.standard_normal((K, t)), jnp.float32)
    for dtype in ("bfloat16", "float32"):
        x = jnp.asarray(rng.standard_normal((t, n * dim)), dtype)
        x64, p64, d64 = (
            np.asarray(a.astype(jnp.float32), np.float64) for a in (x, phi, dm)
        )
        r = (np.mean(x64 * x64, axis=-1) + EPS) ** -0.5
        prod = (x64 @ p64).T
        want = {
            "fwd": prod * r, "dphi": x64.T @ (d64 * r).T,
            "dx": (d64 * r).T @ p64.T
            - ((d64 * prod).sum(0) * r**3 / x64.shape[1])[:, None] * x64,
        }
        got = {}
        for name, form in FORMS.items():
            m, vjp = jax.jit(lambda x, phi, f=form: jax.vjp(
                lambda x, phi: f(x, phi, EPS, F32, n), x, phi
            ))(x, phi)
            dx, dphi = jax.jit(vjp)(dm)
            got[name] = {"fwd": m, "dphi": dphi, "dx": dx}
        yield {
            "what": "numbers", "state": dtype, "size": [t, n, dim],
            "seed": seed, "dx_dtype": str(got["rule"]["dx"].dtype),
            "rel_l2_to_float64": {
                name: {k: rel(v, want[k]) for k, v in outs.items()}
                for name, outs in got.items()
            },
            "dx_bit_equal_share": float(
                (got["rule"]["dx"] == got["highest"]["dx"]).mean()
            ),
        }


def chain(t, n, dim, seed, layers=3, reps=10):
    rng = np.random.default_rng(seed)
    cfg = mock.Mock(
        hc_mult=n, hc_dtype="float32", rms_eps=EPS, hc_clamp=(-30.0, 30.0),
        hc_eps=1e-6, hc_sinkhorn_iters=20, jnp_dtype=jnp.dtype("bfloat16"),
    )
    x0 = jnp.asarray(rng.standard_normal((t, dim)), jnp.bfloat16)
    mixer = lambda: {  # noqa: E731
        "phi": jnp.asarray(rng.standard_normal((n * dim, K)) * 0.01, jnp.float32),
        "alpha": jnp.asarray([0.5, 0.7, 0.9], jnp.float32),
        "b": jnp.asarray(rng.standard_normal(K) * 0.3, jnp.float32),
    }
    ws = [[mixer(), mixer()] for _ in range(layers)]

    def half(x, w):
        h_pre, h_post, h_res = pattern._mhc_coef(x, w, cfg)
        u = pattern._mhc_read(x, h_pre, cfg)
        y = (u.astype(jnp.float32) * 0.5 + 0.1).astype(jnp.bfloat16)
        return pattern._mhc_write(x, y, h_post, h_res, cfg)

    def loss(x0, ws):
        x = pattern._mhc_widen(x0, cfg)
        for w2 in ws:
            x = jax.checkpoint(lambda x, w2: half(half(x, w2[0]), w2[1]))(x, w2)
        return jnp.mean(jnp.square(pattern._mhc_sum(x, cfg).astype(jnp.float32)))

    forms = dict(FORMS, rule_whole_state=lambda x, phi, eps, cdt, n: (
        FORMS["rule"](x, phi, eps, cdt, 1)
    ))
    first = None
    for name, form in forms.items():
        with mock.patch.object(pattern, "_mhc_normed", form):
            f = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
            out = jax.block_until_ready(f(x0, ws))
        jax.block_until_ready(f(x0, ws))
        start = time.perf_counter()
        for _ in range(reps):
            done = f(x0, ws)
        jax.block_until_ready(done)
        ms = (time.perf_counter() - start) / reps * 1e3
        flat = jnp.concatenate([
            g.astype(jnp.float32).ravel() for g in jax.tree.leaves(out[1][1])
        ])
        first = flat if first is None else first
        yield {
            "what": "chain", "form": name, "size": [t, n, dim], "seed": seed,
            "ms_a_half_layer": ms / (2 * layers), "loss": float(out[0]),
            "mixer_grads_rel_to_rule": rel(flat, first),
        }


def toy(seeds):
    os.environ.setdefault("MAGI_ATTENTION_KERNEL_BACKEND", "jnp")
    from benchmarks import harness
    from benchmarks.kinds import train_mhc

    cell = harness.load_cell(
        os.path.join(ROOT, "tests/test_benchmarks/data/toy_mhc"), "toy.mhc"
    )
    low = dict(cell.traffic, dtype="bfloat16")
    dev = jax.devices()[:1]
    for name, form in FORMS.items():
        for seed in seeds:
            job = train_mhc.Job(cell.config, cell.traffic, seed, dev)
            params = pattern.init_pattern_params(
                train_mhc.key_from_seed(job.seed), job.pcfg
            )
            with mock.patch.object(pattern, "_mhc_normed", form):
                _rel, grad, routing = train_mhc.check_errors(
                    job, params,
                    model_job=train_mhc.Job(cell.config, low, seed, dev),
                )
            rest = {k: e for k, e in grad.items() if not k.endswith(".alpha")}
            worst = max(rest, key=rest.get)
            yield {
                "what": "toy", "form": name, "seed": seed, "worst": worst,
                "worst_gradient": rest[worst],
                "flipped_share": routing["flipped_share"],
                "coef_alone": routing["coef_alone"],
            }


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--what", choices=["numbers", "chain", "toy"],
                    nargs="+", default=["numbers"])
    ap.add_argument("--size", type=int, nargs=3, default=[8192, 4, 3584],
                    metavar=("T", "N", "DIM"))
    ap.add_argument("--seed", type=int, default=52000003)
    ap.add_argument("--seeds", type=int, nargs="+", default=[1, 2, 3])
    ap.add_argument("--out")
    args = ap.parse_args()
    runs = {
        "numbers": lambda: numbers(*args.size, args.seed),
        "chain": lambda: chain(*args.size, args.seed),
        "toy": lambda: toy(args.seeds),
    }
    for what in args.what:
        for reading in runs[what]():
            reading["device"] = jax.devices()[0].device_kind
            line = json.dumps(reading)
            print(line, flush=True)
            if args.out:
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(line + "\n")


if __name__ == "__main__":
    main()
