#!/usr/bin/env python3
"""The readings the limits of ``correct`` are set from, on the chip.

    python3 chipbench/control.py --workload olmo-1b.serve.chat \
        --seeds 12 --control-seeds 3 --seconds 20

Runs the cell as the benchmark does, once per seed, in one process (a
short window at the cell's own load), and prints each number ``correct``
compares.  On the first ``--control-seeds`` seeds it also reads the
control (the float32 reference computed with float8 e4m3 linear layers, in
the program's place) and the faults the cell can have (serving: a served
token altered; training: half of the batch left out, the state left
unchanged), and judges each against the cell's limits as ``correct``
judges the program: ``judged`` holds what ``correct`` would read with that
path in the program's place, which has to be false.  The last line is a
summary: per number, the largest sound reading (the lower end of its
limit) and the smallest control and fault readings (the upper end), and
whether every control and fault came out not correct.  The benchmark's own
runs never compute these extra readings.
"""
import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent / "src")]

from chipbench import bench  # noqa: E402
from chipbench import run as run_mod  # noqa: E402


def readings(args, **claim):
    spec, devices, device, peak = run_mod.claim(args.workload, HERE,
                                                **claim)
    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + i
        run = bench.prepare(HERE, spec, args.workload, seed, args.seconds,
                            False, device, peak, time.perf_counter(),
                            devices)
        run.stats["readings"] = i < args.control_seeds
        out = bench.execute(run)
        row = {"seed": seed, "correct": out["correct"],
               "attempted": out["attempted"], "failed": out["failed"],
               "checks": {k: c["value"] for k, c in out["checks"].items()},
               "metrics": {k: m["value"] for k, m in out["metrics"].items()},
               "memory_peak_bytes": out["device"]["memory_peak_bytes"]}
        extra = run.stats.get("readings")
        if isinstance(extra, dict):
            row["readings"] = extra
            row["judged"] = {path: bench.passes(run, v)
                             for path, v in extra.items() if wrong(path)}
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def wrong(path: str) -> bool:
    """Whether a reading's path stands for a run that has to fail."""
    return path == "control" or path.startswith("fault.")


def summary(rows) -> dict:
    """Per number: the largest sound reading, and the smallest reading of
    the control and of each fault; and whether each run of the program
    came out correct and each control and fault not."""
    out = {}
    for k in rows[0]["checks"]:
        out[k] = {"lower": max(r["checks"][k] for r in rows)}
        for r in rows:
            for path, v in r.get("readings", {}).items():
                if wrong(path) and k in v:
                    out[k][path] = min(out[k].get(path, v[k]), v[k])
    judged = [ok for r in rows for ok in r.get("judged", {}).values()]
    return {"numbers": out,
            "program_correct": all(r["correct"] for r in rows),
            "controls_and_faults_fail": bool(judged) and not any(judged)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    try:
        rows = readings(args)
    except run_mod.NoChip as e:
        print(f"chipbench: {e}. Nothing was run.", file=sys.stderr)
        return 2
    print(json.dumps({"summary": summary(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
