"""Share of the decode step's roofline: the least time the chip could take
for the step's work (the larger of its operations over peak FLOP/s and
its bytes over peak bandwidth; weights, the live keys and values of each
active slot, and the new ones, counted from shapes by
``chipbench/costs.py``) over the step's device time, the mean over the
traced window."""
import numpy as np

from chipbench import costs, readers

MODULE = r"^_decode_impl$"


def read(run):
    dev = readers.module_mean_s(run, MODULE)
    d = readers.decodes(run)
    if dev is None or not d:
        return None
    arch = run.config["arch"]
    least = np.mean([costs.roofline_seconds(
        *costs.decode_step_cost(arch, ctx), run.peak) for _, ctx in d])
    return 100.0 * least / dev
