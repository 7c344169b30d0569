"""Model operations of every prefill and decode token sent in the window
(``chipbench/costs.py``) over the window's seconds times the chip's peak
bf16 rate: the whole serving step's share of the peak."""
from chipbench import readers


def read(run):
    f = readers.serve_flops(run)
    return 100.0 * f / (run.window_s * run.peak["bf16_flops_per_s"]) \
        if f else None
