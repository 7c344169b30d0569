"""Device milliseconds of the jitted prefill per 1000 prompt tokens, over
the traced window."""
from chipbench import readers

MODULE = r"^_prefill_impl$"     # SlotServer's jitted batch-1 prefill


def read(run):
    if run.reduced is None:
        return None
    times = run.reduced.module_times(MODULE)
    toks = sum(n for _, n in readers.prefills(run))
    if not times or not toks:
        return None
    return sum(times) * 1e3 / (toks / 1e3)
