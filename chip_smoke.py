#!/usr/bin/env python3
"""Bring-up check on the TPU: the atom kernels, olmo-1b serving and olmo-1b
training at full width, through the entry points a user calls.

    python chip_smoke.py              # one chip: kernels, serving, training
    python chip_smoke.py --chips 4    # sharded training on a (data=2,
                                      # model=2) mesh against one chip

Weights are random, drawn from ``--seed``; nothing is downloaded.  Each phase
checks its results against a float32 reference and raises on a mismatch, so
any failure exits non-zero.  Without a TPU the script exits non-zero before
running anything.  The last line of standard output is one JSON object naming
the device, e.g. ``{"ok": true, "device": {"platform": "tpu", "kind":
"TPU v5 lite", "count": 1}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import obs  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402
from repro.kernels.atom_matmul.ops import atom_matmul  # noqa: E402
from repro.kernels.atom_matmul.ref import matmul_ref  # noqa: E402
from repro.kernels.decode_attention.ops import decode_attention  # noqa: E402
from repro.kernels.decode_attention.ref import decode_attention_ref  # noqa: E402
from repro.kernels.flash_attention.ops import flash_attention  # noqa: E402
from repro.kernels.flash_attention.ref import attention_ref  # noqa: E402
from repro.launch.cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.train import train  # noqa: E402
from repro.models import transformer  # noqa: E402
from repro.serve.engine import ServeConfig, SlotServer  # noqa: E402
from repro.train.step import TrainConfig  # noqa: E402

ARCH = "olmo-1b"
# Each kernel output element must lie within KERNEL_TOL * sum|terms| of the
# f32 reference, the usual bound for a rounded sum of products; sum|terms|
# is the reference evaluated on absolute operands.  The chip's matrix unit
# rounds its inputs (the attention probabilities among them) and the bf16
# output to 2^-9; KERNEL_TOL allows five such roundings.
KERNEL_TOL = 1e-2
# Served bf16 logits against the f32 forward: max |served - ref| over
# rms(ref).  bf16 rounding over the layers gives 0.03-0.04 on the CPU (olmo
# width at 2 layers; width 256 at 16 layers); a wrong token, position or
# slot gives errors of order 1.
SERVE_TOL = 0.15
# Sharded against one-chip training: max |loss difference| per step.
LOSS_TOL = 2e-2


def _check_close(name, out, ref, abs_ref):
    """bf16 kernel output against its f32 reference; prints the error and
    raises on a miss."""
    out, ref, abs_ref = (np.asarray(x, np.float32) for x in (out, ref, abs_ref))
    if out.shape != ref.shape or not np.isfinite(out).all():
        raise AssertionError(f"{name}: shape {out.shape} vs {ref.shape}, "
                             f"finite={bool(np.isfinite(out).all())}")
    err = np.abs(out - ref)
    ratio = float(np.max(err / (KERNEL_TOL * abs_ref + 1e-30)))
    print(f"[kernels] {name}: max|err| {float(err.max()):.3e} max|ref| "
          f"{float(np.abs(ref).max()):.3e} worst err/bound {ratio:.3f} "
          f"(pass <= 1)", flush=True)
    if ratio > 1.0:
        raise AssertionError(f"{name} misses its f32 reference")


def run_kernels(cfg, *, tokens: int = 2048, batch: int = 4,
                kv_len: int = 2048, seed: int = 0, interpret: bool = False):
    """The three atom kernels at ``cfg``'s widths, each split into atoms,
    against their float32 references."""
    D, F, H, Hk, Dh = (cfg.d_model, cfg.d_ff, cfg.n_heads, cfg.n_kv_heads,
                       cfg.head_dim)
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)

    def bf16(k, shape):
        return jax.random.normal(k, shape, jnp.float32).astype(jnp.bfloat16)

    def f32_ref(ref_fn, *xs, **kw):
        xs = [x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x
              for x in xs]
        with jax.default_matmul_precision("highest"):
            return ref_fn(*xs, **kw)

    a, w = bf16(ks[0], (tokens, D)), bf16(ks[1], (D, F))
    out = atom_matmul(a, w, n_atoms=4, interpret=interpret)
    _check_close(f"atom_matmul {tokens}x{D} @ {D}x{F}, 4 atoms", out,
                 f32_ref(matmul_ref, a, w),
                 f32_ref(matmul_ref, jnp.abs(a), jnp.abs(w)))

    q = bf16(ks[2], (1, tokens, H, Dh))
    k, v = bf16(ks[3], (1, tokens, Hk, Dh)), bf16(ks[4], (1, tokens, Hk, Dh))
    out = flash_attention(q, k, v, causal=True, n_atoms=2, interpret=interpret)
    _check_close(f"flash_attention 1x{tokens}x{H}x{Dh} causal, 2 atoms", out,
                 f32_ref(attention_ref, q, k, v, causal=True),
                 f32_ref(attention_ref, q, k, jnp.abs(v), causal=True))

    q = bf16(ks[5], (batch, H, Dh))
    kc = bf16(ks[6], (batch, kv_len, Hk, Dh))
    vc = bf16(ks[7], (batch, kv_len, Hk, Dh))
    lens = jnp.asarray(np.random.default_rng(seed).integers(
        1, kv_len + 1, batch), jnp.int32)
    out = decode_attention(q, kc, vc, lens, n_atoms=2, interpret=interpret)
    _check_close(f"decode_attention B={batch} H={H} S={kv_len} D={Dh}, "
                 f"2 atoms", out,
                 f32_ref(decode_attention_ref, q, kc, vc, lens),
                 f32_ref(decode_attention_ref, q, kc, jnp.abs(vc), lens))


def _serve(cfg, prompts, sc, seed, check_steps):
    """Serve ``prompts`` through a SlotServer; keep the logits it served for
    each request's prefill and first ``check_steps`` decode steps."""
    t0 = time.perf_counter()
    srv = SlotServer(cfg, serve_cfg=sc, seed=seed,
                     clock=lambda: time.perf_counter() - t0)
    served = [[] for _ in prompts]
    prefill, decode = srv._prefill, srv._decode
    admitted = iter(range(len(prompts)))      # admission is FIFO

    def prefill_rec(params, toks, caches, slot):
        logits, caches = prefill(params, toks, caches, slot)
        served[next(admitted)].append(np.asarray(logits, np.float32))
        return logits, caches

    def decode_rec(params, last, pos, caches, active):
        nxt, logits, caches = decode(params, last, pos, caches, active)
        want = [(s, r.rid) for s, r in enumerate(srv.slot_req)
                if r is not None and len(served[r.rid]) <= check_steps]
        if want:
            host = np.asarray(logits, np.float32)
            for s, rid in want:
                served[rid].append(host[s])
        return nxt, logits, caches

    srv._prefill, srv._decode = prefill_rec, decode_rec
    for p in prompts:
        srv.submit(p)
    done = srv.run_until_drained()
    wall = time.perf_counter() - t0
    return srv.params, done, served, wall


def run_serve(cfg, *, slots: int = 8, max_len: int = 512,
              prompt_lens=(64, 192), n_requests: int = 12, max_new: int = 32,
              check_steps: int = 4, seed: int = 0):
    """Serve ``n_requests`` random prompts and check every request's token
    count and the served logits against ``transformer.forward`` in f32."""
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(2, cfg.vocab_size, prompt_lens[i % len(prompt_lens)]
                            ).astype(np.int32) for i in range(n_requests)]
    # eos_id=-1: no token ends a request early, so each yields max_new
    sc = ServeConfig(max_slots=slots, max_len=max_len,
                     max_new_tokens=max_new, eos_id=-1)
    compiled = obs.tracer().compile_seconds
    params, done, served, wall = _serve(cfg, prompts, sc, seed, check_steps)
    compiled = obs.tracer().compile_seconds - compiled
    gc.collect()            # the recorders and the server form a cycle
    n_tok = sum(len(r.output) for r in done)
    print(f"[serve] {ARCH}: {len(done)}/{n_requests} requests, {n_tok} "
          f"tokens, {slots} slots, max_len {max_len}, prompt lengths "
          f"{sorted(set(prompt_lens))}, wall {wall:.2f}s, of it compile "
          f"{compiled:.2f}s", flush=True)
    if len(done) != n_requests or any(len(r.output) != max_new for r in done):
        raise AssertionError("a request did not finish with "
                             f"{max_new} tokens")

    cfg32 = dataclasses.replace(cfg, dtype="float32")

    @jax.jit
    def ref_logits(params, tokens):
        p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
        with jax.default_matmul_precision("highest"):
            h, _ = transformer.forward(p32, cfg32, tokens)
            return transformer.lm_logits(p32, cfg32, h)

    outputs = {r.rid: r.output for r in done}
    worst, agree, total = 0.0, 0, 0
    for plen in sorted(set(prompt_lens)):
        ids = [i for i, p in enumerate(prompts) if len(p) == plen]
        toks = np.stack([np.concatenate([prompts[i],
                                         outputs[i][:check_steps]])
                         for i in ids]).astype(np.int32)
        ref = np.asarray(ref_logits(params, jnp.asarray(toks)))
        ref = ref[:, plen - 1:plen + check_steps]       # [n, steps+1, V]
        got = np.stack([np.stack(served[i]) for i in ids])
        scale = float(np.sqrt(np.mean(ref ** 2)))
        worst = max(worst, float(np.abs(got - ref).max()) / scale)
        agree += int((got.argmax(-1) == ref.argmax(-1)).sum())
        total += got.shape[0] * got.shape[1]
    print(f"[serve] logits vs f32 forward over prefill + {check_steps} "
          f"decode steps: max|err|/rms(ref) {worst:.4f} (tol {SERVE_TOL}), "
          f"argmax agrees {agree}/{total}", flush=True)
    if not worst <= SERVE_TOL:
        raise AssertionError("served logits miss the f32 forward")


def run_train(cfg, *, steps: int = 4, batch: int = 4, seq: int = 1024,
              mesh=None, seed: int = 0) -> list[float]:
    """``steps`` train steps through ``launch.train.train``; returns the
    losses, all of which must be finite.  ``remat="full"``: at batch 8 x
    seq 1024, olmo-1b's step with "dots" needs 21.88G of v5e's 15.75G."""
    tc = TrainConfig(remat="full", n_micro=1, moment_dtype=cfg.moment_dtype,
                     total_steps=steps, warmup_steps=1)
    where = "one chip" if mesh is None else f"mesh {dict(mesh.shape)}"
    print(f"[train] {where}: batch {batch} x seq {seq}, remat={tc.remat} "
          f"moment_dtype={tc.moment_dtype} n_micro={tc.n_micro}", flush=True)
    t0 = time.perf_counter()
    compiled = obs.tracer().compile_seconds
    state, losses = train(cfg, steps=steps, batch=batch, seq=seq, tc=tc,
                          mesh=mesh, seed=seed, log_every=1)
    compiled = obs.tracer().compile_seconds - compiled
    del state
    gc.collect()
    print(f"[train] losses {losses}; wall {time.perf_counter() - t0:.2f}s, "
          f"of it compile {compiled:.2f}s", flush=True)
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError("non-finite train loss")
    return losses


def _print_peak(devices):
    for d in devices:
        stats = d.memory_stats() or {}
        print(f"[memory] {d}: peak_bytes_in_use "
              f"{stats.get('peak_bytes_in_use', 'not reported')}", flush=True)


def run_sharded(cfg, **train_kw):
    """The same train steps on one chip and on a (data=2, model=2) mesh;
    their per-step losses must agree within LOSS_TOL."""
    one = run_train(cfg, **train_kw)
    mesh = make_mesh((2, 2), ("data", "model"))
    four = run_train(cfg, mesh=mesh, **train_kw)
    diff = max(abs(a - b) for a, b in zip(one, four))
    print(f"[sharded] max |loss(1 chip) - loss(2x2 mesh)| {diff:.3e} "
          f"(tol {LOSS_TOL})", flush=True)
    if not diff <= LOSS_TOL:
        raise AssertionError("sharded losses differ from one chip")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded training path and its "
                         "one-chip comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
              f"{len(devices)} {dev.platform} device(s). Nothing was run.",
              file=sys.stderr)
        return 1
    cache_dir = use_compile_cache()       # before the first compile
    print(f"[device] {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache_dir}", flush=True)

    cfg = get_config(ARCH)
    if args.chips == 4:
        run_sharded(cfg, seed=args.seed)
        _print_peak(devices[:4])
    else:
        run_kernels(cfg, seed=args.seed)
        run_serve(cfg, seed=args.seed)
        run_train(cfg, seed=args.seed)
        _print_peak(devices[:1])
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
