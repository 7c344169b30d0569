"""Per cent of the traced window in which no operation ran on the device:
1 less the union of the device's operation intervals over the window."""
from chipbench import readers


def read(run):
    return readers.idle_share(run)
