"""Host milliseconds of the serving engine's own work per step: the mean,
over the engine steps that began in the window, of the program's
``engine.step`` span less the ``*.sync`` spans inside it (the waits for
the device's tokens).  Read from the program's tracer (``repro.obs``)."""
from chipbench import scopes


def read(run):
    return scopes.host_ms_per_step(run)
