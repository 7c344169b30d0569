"""Small helpers the metric readers share: what a run recorded inside its
window."""
from __future__ import annotations

import numpy as np


def in_window(run, t: float) -> bool:
    return run.window[0] <= t <= run.window[1]


def decodes(run) -> list:
    """(time, [ctx of each active slot]) of each decode sent in the
    window."""
    return [d for d in run.stats.get("decodes", []) if in_window(run, d[0])]


def prefills(run) -> list:
    """(time, prompt length) of each prefill sent in the window."""
    return [p for p in run.stats.get("prefills", []) if in_window(run, p[0])]


def serve_flops(run) -> int:
    """Model operations of every prefill and decode token sent in the
    window."""
    from chipbench import costs
    arch = run.config["arch"]
    f = sum(costs.prefill_flops(arch, n) for _, n in prefills(run))
    f += sum(costs.decode_flops(arch, c) for _, ctx in decodes(run)
             for c in ctx)
    return f


def p95(values) -> float | None:
    values = list(values)
    return float(np.percentile(values, 95)) if values else None


def module_mean_s(run, pattern: str) -> float | None:
    """Mean device seconds of one execution of the programs matching
    ``pattern`` in the traced window; None without a trace or a match."""
    if run.reduced is None:
        return None
    t = run.reduced.module_times(pattern)
    return float(np.mean(t)) if t else None


def idle_share(run) -> float | None:
    """Per cent of the traced window in which no operation ran."""
    r = run.reduced
    if r is None or r.window_s <= 0 or not r.devices:
        return None
    return 100.0 * (1.0 - r.busy_s() / r.window_s)
