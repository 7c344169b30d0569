"""Device milliseconds of one execution of the jitted decode step, the
mean over the traced window."""
from chipbench import readers

MODULE = r"^_decode_impl$"      # SlotServer's jitted decode


def read(run):
    t = readers.module_mean_s(run, MODULE)
    return None if t is None else t * 1e3
