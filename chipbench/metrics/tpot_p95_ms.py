"""95th percentile of every gap between consecutive output tokens of every
request, each token stamped on the host clock after the step that made
it; gaps that end inside the window."""
from chipbench import readers


def read(run):
    t0, t1 = run.window
    gaps = [(b - a) * 1e3 for r in run.stats.get("recs", [])
            for a, b in zip(r.stamps, r.stamps[1:]) if t0 <= b <= t1]
    return readers.p95(gaps)
