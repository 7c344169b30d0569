"""Production serving driver: SlotServer under LithOS multi-tenancy.

Runs the continuous-batching engine (serve/engine.py) over a synthetic
request stream and reports latency/throughput; with ``--collocated`` it
additionally runs the LithOS simulator to show the same workload stacked
with a best-effort tenant under each scheduling system.

With ``--ctl-state-dir`` the driver does not serve locally at all: it is
the first client of the online control plane (:mod:`repro.ctl`), and the
invocation becomes a *job submission* — the serve deployment turns into a
tenant (SLO class + slice quota) that the daemon admits onto a device and
runs under multi-tenancy, survivable across daemon crashes.

Usage:
    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --reduced \
        --requests 32 --max-new 8
    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b \
        --ctl-state-dir /tmp/ctl --rps 40 --duration 5 --quota 8 --slo 0.25
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.configs.registry import ARCH_IDS, get_config


def serve(cfg, *, n_requests: int = 16, max_slots: int = 4,
          max_len: int = 128, max_new: int = 16, seed: int = 0,
          verbose: bool = True):
    """Serve ``n_requests`` random prompts, all submitted at once; returns
    the finished requests and ``latency_stats`` of them.  Every prompt
    length is served once first, so that no compilation is timed."""
    # deferred: the --ctl-state-dir submit path must not pay (or require)
    # the jax import just to drop a spec file in the daemon's inbox
    from repro.serve.engine import ServeConfig, SlotServer

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(2, cfg.vocab_size, int(rng.integers(
        4, max_len // 2))).astype(np.int32) for _ in range(n_requests)]
    srv = SlotServer(cfg, serve_cfg=ServeConfig(
        max_slots=max_slots, max_len=max_len, max_new_tokens=max_new),
        seed=seed)
    for n in sorted({len(p) for p in prompts}):
        srv.submit(np.full(n, 2, np.int32), max_new_tokens=2)
    srv.run_until_drained()
    srv.done.clear()
    t0 = time.perf_counter()
    for p in prompts:
        srv.submit(p, max_new_tokens=max_new)
    done = srv.run_until_drained()
    wall = time.perf_counter() - t0
    stats = latency_stats(done)
    if verbose:
        toks = sum(len(r.output) for r in done)
        print(f"[serve] {len(done)} requests, {toks} tokens in {wall:.2f}s "
              f"({toks/wall:.1f} tok/s); p50/p99 ms: " + ", ".join(
                  f"{k} {v[0]*1e3:.1f}/{v[1]*1e3:.1f}"
                  for k, v in stats.items()))
    return done, stats


def latency_stats(done) -> dict:
    """p50 and p99 in seconds, from the engine's own stamps, of the wait
    for a slot (``queue``), the time to first token (``ttft``) and the
    gaps between a request's tokens (``tpot``); a name with no values is
    left out."""
    values = {
        "queue": [r.t_admit - r.t_submit for r in done],
        "ttft": [r.t_first_token - r.t_submit for r in done],
        "tpot": [b - a for r in done
                 for a, b in zip(r.token_times, r.token_times[1:])]}
    return {k: (float(np.percentile(v, 50)), float(np.percentile(v, 99)))
            for k, v in values.items() if v}


def submit_to_ctl(args) -> str:
    """Express this serve deployment as a control-plane job: an open-loop
    ``serve`` tenant with the CLI's SLO class and slice quota.  Returns the
    job id; the daemon owning ``--ctl-state-dir`` admits and runs it."""
    from repro.ctl import store

    spec = {"kind": "serve", "arch": args.arch, "reduced": args.reduced,
            "name": args.name or f"serve-{args.arch}",
            "priority": args.priority, "quota_slices": args.quota,
            "rps": args.rps, "duration": args.duration,
            "slo_latency": args.slo, "batch": args.max_slots,
            "decode_tokens": args.max_new, "seed": args.seed}
    return store.request_submit(args.ctl_state_dir, spec)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="olmo-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ctl = ap.add_argument_group("control plane (submit instead of serving)")
    ctl.add_argument("--ctl-state-dir", default=None,
                     help="submit this deployment as a ctl job instead of "
                          "serving locally")
    ctl.add_argument("--name", default=None)
    ctl.add_argument("--priority", default="hp", choices=["hp", "be"])
    ctl.add_argument("--quota", type=int, default=0,
                     help="pinned TPC slices for the tenant")
    ctl.add_argument("--rps", type=float, default=20.0)
    ctl.add_argument("--duration", type=float, default=5.0,
                     help="serve window, simulated seconds")
    ctl.add_argument("--slo", type=float, default=0.25,
                     help="SLO latency target, seconds")
    args = ap.parse_args(argv)
    if args.ctl_state_dir is not None:
        print(submit_to_ctl(args))
        return
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.is_encoder_decoder:
        raise SystemExit("SlotServer serves decoder-only configs; "
                         "whisper uses examples/whisper_decode.py")
    serve(cfg, n_requests=args.requests, max_slots=args.max_slots,
          max_len=args.max_len, max_new=args.max_new, seed=args.seed)


if __name__ == "__main__":
    main()
