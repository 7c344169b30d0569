#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: the highest offered rate at
which the backlog does not grow over the window.

    python3 chipbench/sweep.py --workload olmo-1b.serve.chat \
        --rates 1.5 2 2.5 3 --seconds 40 --seed 5

Each rate is one run of the cell, as the benchmark makes it, with only the
arrival rate changed, all in one process.  The backlog at a moment is the
number of requests that were due and have no first token yet; it is
averaged over each quarter of the window.  A rate holds when the last
quarter's mean is at most ``GROWTH`` times the second quarter's: the
window starts empty, so the first quarter is left out, and a backlog that
still grows by half from the second quarter to the last has not settled.
Prints one JSON line per rate and, last, the knee and 0.8 of it.
"""
import argparse
import copy
import json
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent / "src")]

from chipbench import bench  # noqa: E402
from chipbench import run as run_mod  # noqa: E402

GROWTH = 1.5


def backlog_quarters(run) -> list[float]:
    """Mean backlog in each quarter of the window, sampled every 50 ms."""
    t0, t1 = run.window
    recs = run.stats["recs"]
    due = np.array([r.due for r in recs])
    first = np.array([r.stamps[0] if r.stamps else np.inf for r in recs])
    ts = np.linspace(t0, t1, max(8, int((t1 - t0) / 0.05)))
    waiting = np.array([np.sum((due <= t) & (first > t)) for t in ts])
    return [float(q.mean()) for q in np.array_split(waiting, 4)]


def holds(q: list[float]) -> bool:
    """Whether a rate's backlog by quarter has stopped growing."""
    return q[3] <= GROWTH * q[1]


def sweep(args, **claim):
    spec, devices, device, peak = run_mod.claim(args.workload, HERE,
                                                **claim)
    rows = []
    for rate in args.rates:
        run = bench.prepare(HERE, spec, args.workload, args.seed,
                            args.seconds, False, device, peak,
                            time.perf_counter(), devices)
        run.traffic = copy.deepcopy(run.traffic)
        run.traffic["arrivals"]["rate_per_s"] = rate
        out = bench.execute(run)
        q = backlog_quarters(run)
        row = {"rate_per_s": rate, "due": out["attempted"],
               "no_first_token": out["failed"], "backlog_quarters": q,
               "holds": holds(q),
               "metrics": {k: m["value"] for k, m in out["metrics"].items()},
               "correct": out["correct"]}
        print(json.dumps(row), flush=True)
        rows.append(row)
    held = [r["rate_per_s"] for r in rows if r["holds"]]
    knee = max(held) if held else None
    print(json.dumps({"knee_per_s": knee,
                      "cell_rate_per_s": None if knee is None
                      else 0.8 * knee}), flush=True)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=2**31 + 77)
    args = ap.parse_args(argv)
    try:
        sweep(args)
    except run_mod.NoChip as e:
        print(f"chipbench: {e}. Nothing was run.", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
