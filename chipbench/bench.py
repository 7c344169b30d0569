"""The harness: one run of one cell, driven by the files that
``BENCHMARK.json`` names.

A cell names a configuration and a traffic mix.  Everything that belongs
to one of them is found by name:

* ``configs/<config>.json``: sizes, source and the program's ``ArchConfig``;
  its ``reference`` names ``references/<reference>.py``;
* ``traffic/<traffic>.json``: the mix's parameters; its ``kind`` names the
  module ``kinds/<kind>.py`` that feeds the program;
* ``limits/<workload>.json``: the limit of each number ``correct`` compares;
* ``metrics/<metric>.py``: one reader per metric, end-to-end or per layer.

A kind has ``setup(run)``, ``window(run, state)``, ``drain(run, state)``
and ``check(run, state)``; a reader has ``read(run)``, which returns a
number or None when the run holds nothing for it to read.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path


def load_module(path: Path):
    """Import one file of the benchmark by its path."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def find_cell(spec: dict, workload: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == workload:
            return cell
    names = [c["name"] for c in spec["workloads"]]
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json: {names}")


def cell_metrics(spec: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics this cell reports: end-to-end without the trace, per
    layer with it; a metric with ``workloads`` only in those cells."""
    group = spec["per_layer" if trace else "end_to_end"]
    return [m for m in group
            if workload in m.get("workloads", [workload])]


@dataclass
class Run:
    """Everything one run knows; the kind fills ``stats`` and ``checks``,
    readers read them."""
    root: Path                       # the benchmark's directory
    spec: dict
    cell: dict
    config: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    device: dict                     # platform, kind, count
    peak: dict                       # the chip's row of peaks.json
    t_process: float                 # perf_counter at process start
    spans: object = None
    compiles: object = None
    arch: object = None
    window: tuple = (0.0, 0.0)       # perf_counter at window start, end
    stats: dict = field(default_factory=dict)
    checks: dict = field(default_factory=dict)   # name -> (value, limit)
    attempted: int = 0
    failed: int = 0
    reduced: object = None           # trace.Reduced of a traced run
    devices: list = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def memory_peak(self) -> int:
        """Peak bytes in use on the fullest chip, where JAX reports it."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        return int(max(peaks, default=0))


def prepare(root: Path, spec: dict, workload: str, seed: int,
            seconds: float, trace: bool, device: dict, peak: dict,
            t_process: float, devices: list) -> Run:
    from chipbench.weights import load_config
    cell = find_cell(spec, workload)
    config = load_config(cell["config"], root)
    traffic = json.loads((root / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    limits = json.loads((root / "limits" / f"{workload}.json").read_text())
    return Run(root, spec, cell, config, traffic, limits, seed, seconds,
               trace, device, peak, t_process, devices=devices)


def _start_trace(log_dir: str):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # harness spans only
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def execute(run: Run) -> dict:
    """Set up, measure, check and read the metrics of one run; returns
    the result's JSON object."""
    import jax
    from chipbench import spans as spans_mod
    from chipbench import trace as trace_mod
    from chipbench.weights import arch_config

    run.spans = spans_mod.Spans(annotate=run.trace)
    run.compiles = spans_mod.CompileClock().__enter__()
    try:
        run.arch = arch_config(run.config)
        kind = load_module(run.root / "kinds" / f"{run.traffic['kind']}.py")
        t_setup = time.perf_counter()
        state = kind.setup(run)
        run.stats["setup_phases"] = phases(run, t_setup)
        log_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
            if run.trace else None
        if log_dir:
            _start_trace(log_dir)
        try:
            with run.spans.span(trace_mod.WINDOW_SPAN):
                kind.window(run, state)
            kind.drain(run, state)
        finally:
            if log_dir:
                jax.profiler.stop_trace()
        if log_dir:
            names = {n for n, _, _ in run.spans.items}
            run.reduced = trace_mod.read(trace_mod.find_xplane(log_dir),
                                         names)
            shutil.rmtree(log_dir, ignore_errors=True)
        run.stats["memory_peak_bytes"] = run.memory_peak()
        kind.check(run, state)
        del state
        gc.collect()
    finally:
        run.compiles.__exit__(None, None, None)
    return result(run)


def result(run: Run) -> dict:
    metrics = {}
    for m in cell_metrics(run.spec, run.cell["name"], run.trace):
        reader = load_module(run.root / "metrics" / f"{m['name']}.py")
        value = reader.read(run)
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    correct = passes(run)
    device = dict(run.device,
                  memory_peak_bytes=run.stats.get("memory_peak_bytes", 0))
    out = {"correct": correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": device}
    if run.reduced is not None:
        device["busy_s"] = run.reduced.busy_s()
        device["window_s"] = run.reduced.window_s
        out["breakdown"] = {"device_ops": run.reduced.top_ops(10),
                            "idle_gaps": run.reduced.idle_gaps(10)}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in run.checks.items()}
    return out


def passes(run: Run, readings: dict | None = None) -> bool:
    """``correct``: requests were attempted, none failed, and every number
    compared is within its limit.  ``readings`` puts another path's
    numbers (the control's, a fault's) in the program's place."""
    readings = readings or {}
    return (run.attempted > 0 and run.failed == 0 and bool(run.checks)
            and all(readings.get(k, v) <= lim
                    for k, (v, lim) in run.checks.items()))


def phases(run: Run, t_setup: float) -> dict:
    """Where set-up went: before the kind's set-up (interpreter, imports,
    the chip), each of its ``setup.*`` spans, and compilation."""
    out = {"before_kind": t_setup - run.t_process}
    for name, s, e in run.spans.items:
        if name.startswith("setup."):
            out[name[6:]] = out.get(name[6:], 0.0) + e - s
    out["compile"] = run.compiles.seconds
    return out


def limit(run: Run, name: str) -> float:
    return float(run.limits[name]["limit"])


def emit(out: dict, setup_phases: dict) -> None:
    """Set-up's phases and the checks as the last lines of standard error,
    then the result as the last line of standard output."""
    print("setup " + " ".join(f"{k} {v:.3f}s" for k, v in
                              setup_phases.items()),
          file=sys.stderr, flush=True)
    for k, c in out["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


def process_age() -> float:
    """Seconds since this process started (Linux), else 0."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(0.0, up - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0
