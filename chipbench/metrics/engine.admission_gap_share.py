"""Per cent of the token gaps that span an admission: slots decoded in
engine steps that also ran a prefill, over all slots decoded, in the
window.  Read from the counters on the program's ``engine.step`` spans
(``slots``, ``prefills``)."""
from chipbench import scopes


def read(run):
    steps = [s.counts or {} for s, _ in scopes.engine_steps(run)]
    slots = sum(c.get("slots", 0) for c in steps)
    if not slots:
        return None
    return 100.0 * sum(c.get("slots", 0) for c in steps
                       if c.get("prefills", 0)) / slots
