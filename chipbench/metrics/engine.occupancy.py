"""Mean share of the slots active in a decode step, over the window's
decode steps (the engine's ``active`` mask as each decode is sent)."""
from chipbench import readers


def read(run):
    d = readers.decodes(run)
    if not d:
        return None
    slots = run.traffic["engine"]["max_slots"]
    return 100.0 * sum(len(c) for _, c in d) / (len(d) * slots)
