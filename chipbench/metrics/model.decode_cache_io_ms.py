"""Device milliseconds per decode step of the operations in the scope
``layers`` and outside any ``block``: the layer scan's own work, which is
slicing each layer's keys and values out of the stacked cache and writing
them back into it.  Operations are named by their scope in the compiled
decode step (``chipbench/scopes.py``)."""
from chipbench import scopes


def read(run):
    return scopes.decode_ms(run, lambda p: "layers" in p and "block" not in p,
                            scopes.decode_scopes(run))
