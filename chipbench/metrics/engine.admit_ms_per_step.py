"""Host milliseconds the serving engine spends in ``SlotServer._admit``
(batch-1 prefills, the host sync on each first token, slot bookkeeping)
per decode step, over the window.  Span: ``engine.admit``, recorded by the
harness around the call."""
from chipbench import readers


def read(run):
    n = len(readers.decodes(run))
    if not n:
        return None
    return run.spans.total("engine.admit", *run.window) * 1e3 / n
