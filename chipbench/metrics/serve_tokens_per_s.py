"""Output tokens made in the window over the window's seconds."""


def read(run):
    t0, t1 = run.window
    n = sum(1 for r in run.stats.get("recs", []) for t in r.stamps
            if t0 <= t <= t1)
    return n / (t1 - t0) if n else None
