"""Model FLOP/s utilisation: the kind's FLOPs for one step (no
recomputation counted) over a measured step time and the chips' peak.

``{"kind": "mfu", "flops": "train_step", "seconds_key": "steady_step_s"}``
"""

from .. import flops


def read(spec: dict, obs):
    work = obs.flops.get(spec["flops"])
    seconds = obs.values.get(spec["seconds_key"])
    if work is None or not seconds or obs.peaks is None:
        return None
    return flops.roofline_pct(
        work / obs.chips, seconds, obs.peaks["bf16_tflops"]
    )
