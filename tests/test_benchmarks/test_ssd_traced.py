"""The command's own path for the ``train_ssd`` cell with the tracer on,
at toy size on the CPU (``data/toy_ssd``); apart from
``test_ssd_check.py`` so that the two runs of the command go to two
workers."""

import io
import json
import os
from contextlib import redirect_stdout

TOY = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "data", "toy_ssd"
)


def test_traced_rehearsal_prints_the_result_line():
    import jax

    from benchmarks import harness

    out = io.StringIO()
    with jax.enable_x64(False), redirect_stdout(out):
        rc = harness.main(
            ["--workload", "toy.ssd", "--seed", "7", "--seconds", "1.5",
             "--trace", "1", "--root", TOY],
            allow_cpu=True,
        )
    assert rc == 0
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    # no device trace on the CPU: the three new metrics' readers find
    # nothing and the line leaves them out, as on a parent without the
    # scope and the kernels
    assert set(res["metrics"]) == {"train_step_steady_ms"}
    assert res["device"]["busy_s"] == 0.0
