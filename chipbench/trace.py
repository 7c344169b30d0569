"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

Read through ``jax.profiler.ProfileData``:

* each ``/device:TPU:<n>`` plane's ``XLA Ops`` line gives the intervals in
  which an operation ran; their union is the device's busy time;
* its ``XLA Modules`` line gives each executed program (``jit_<name>(id)``)
  with its device time;
* the host plane holds the harness's spans (``TraceAnnotation``) on the
  same clock, so each idle gap on the device is put down to the span the
  host was in.

All times here are nanoseconds on the trace's clock.
"""
from __future__ import annotations

import bisect
import glob
import re
from collections import defaultdict
from dataclasses import dataclass

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# ops that hold other ops (a layer scan is a while loop): their time is
# their body's, so they are left out of the op breakdown
CONTAINER_OP = re.compile(r"^%(while|conditional|call)[.\s]")
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, t0: float, t1: float):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def module_name(event_name: str) -> str:
    """``jit__decode_impl(1502...)`` -> ``_decode_impl``."""
    name = event_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def op_name(event_name: str) -> str:
    """``%fusion.140 = s32[16]{0:T(128)} fusion(...)`` -> ``%fusion.140 =
    s32[16]``: the op and its result's shape."""
    return event_name.split("{", 1)[0].split(" fusion(", 1)[0][:80]


@dataclass
class Device:
    busy: list            # merged (start, end) of operations
    modules: list         # (module name, start, end)
    ops: list             # (op name, start, end)


@dataclass
class Reduced:
    devices: list[Device]
    spans: list           # (name, start, end) harness spans on the host
    window: tuple[float, float]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Busy seconds inside the window, averaged over the devices."""
        t0, t1 = self.window
        per = [sum(e - s for s, e in clip(d.busy, t0, t1))
               for d in self.devices]
        return sum(per) / len(per) * 1e-9 if per else 0.0

    def module_times(self, pattern: str) -> list[float]:
        """Device seconds of each execution, in the window, of the
        programs whose name matches ``pattern`` (a regex), all devices."""
        rx = re.compile(pattern)
        t0, t1 = self.window
        return [(e - s) * 1e-9 for d in self.devices
                for n, s, e in d.modules
                if rx.search(n) and s >= t0 and s < t1]

    def top_ops(self, n: int = 10) -> list[list]:
        """The ``n`` operations that took most device time in the window,
        named ``<program>/<op>``, in seconds averaged over devices; loops
        and calls, whose time is that of the ops inside them, are left
        out."""
        t0, t1 = self.window
        tot: dict = defaultdict(float)
        for d in self.devices:
            starts = [s for _, s, _ in d.modules]
            for name, s, e in d.ops:
                if e <= t0 or s >= t1 or CONTAINER_OP.match(name):
                    continue
                i = bisect.bisect_right(starts, s) - 1
                mod = d.modules[i][0] if i >= 0 and s < d.modules[i][2] \
                    else "?"
                tot[f"{mod}/{name}"] += (min(e, t1) - max(s, t0)) * 1e-9
        k = max(1, len(self.devices))
        return [[name, v / k] for name, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """Idle seconds in the window put down to what the host was doing:
        each gap of the first device goes to the harness span that
        overlaps it most (the shortest such span on a tie), summed by
        span name; the ``n`` largest sums."""
        t0, t1 = self.window
        busy = clip(self.devices[0].busy, t0, t1) if self.devices else []
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        spans = sorted((s, e, name) for name, s, e in self.spans
                       if name != WINDOW_SPAN)
        starts = [s for s, _, _ in spans]
        tot: dict = defaultdict(float)
        for gs, ge in gaps:
            best, key = "host outside any span", (0.0, 0.0)
            # spans are short and do not reach far back; scan those that
            # start before the gap ends
            j = bisect.bisect_right(starts, ge)
            for s, e, name in spans[max(0, j - 64):j]:
                ov = min(e, ge) - max(s, gs)
                if ov > 0 and (ov, -(e - s)) > key:
                    best, key = name, (ov, -(e - s))
            tot[best] += (ge - gs) * 1e-9
        return [[name, v] for name, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def read(path: str, span_names: set[str]) -> Reduced:
    """Reduce one ``.xplane.pb`` file.  ``span_names``: the harness's span
    names to take from the host plane.  The window is the ``bench.window``
    span if the trace has one, else the extent of the device's events."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(op_name(e.name), e.start_ns, e.end_ns)
                           for e in line.events]
                elif line.name == "XLA Modules":
                    mods = [(module_name(e.name), e.start_ns, e.end_ns)
                            for e in line.events]
            devices.append(Device(union((s, e) for _, s, e in ops),
                                  sorted(mods, key=lambda m: m[1]),
                                  ops))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name in span_names or e.name == WINDOW_SPAN:
                        spans.append((e.name, e.start_ns, e.end_ns))
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if win:
        window = max(win, key=lambda w: w[1] - w[0])
    else:
        ev = [x for d in devices for iv in d.busy for x in iv]
        window = (min(ev), max(ev)) if ev else (0.0, 0.0)
    return Reduced(devices, spans, window)


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]
