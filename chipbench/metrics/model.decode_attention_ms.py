"""Device milliseconds per decode step of the operations in the scope
``attention``: the q, k and v projections with their rotary embedding,
the scores over every cached position, the mask, the softmax, the sum over
values and the output projection.  Operations are named by their scope in
the compiled decode step (``chipbench/scopes.py``)."""
from chipbench import scopes


def read(run):
    return scopes.decode_ms(run, lambda p: "attention" in p,
                            scopes.decode_scopes(run))
