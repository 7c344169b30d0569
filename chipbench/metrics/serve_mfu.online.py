"""The whole serving step's share of the chip's peak bf16 rate in the
online cells: model operations of every prefill and decode token sent in
the window over the window's seconds times the peak."""
from chipbench import readers


def read(run):
    f = readers.serve_flops(run)
    return 100.0 * f / (run.window_s * run.peak["bf16_flops_per_s"]) \
        if f else None
