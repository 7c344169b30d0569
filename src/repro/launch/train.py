"""Production training driver.

Wires every substrate together: synthetic data pipeline -> sharded
train_step (pjit) -> checkpointing (async, keep-last-k) -> fault-tolerance
coordinator (heartbeats, straggler log, elastic restart hook).

On this CPU container it runs reduced configs end-to-end (the quickstart
and examples call into it); on a pod the same driver runs the full configs —
the only difference is the mesh passed in.

Usage:
    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --reduced \
        --steps 100 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import numpy as np

from repro.checkpoint.sharded import CheckpointManager, latest_step
from repro.configs.registry import ARCH_IDS, get_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.distributed.coordinator import Coordinator, CoordinatorConfig
from repro.launch import shardings as shlib
from repro.launch.cache import use_compile_cache
from repro.models.sharding import use_mesh
from repro.train.step import TrainConfig, TrainState, make_train_step


def train(cfg, *, steps: int = 50, batch: int = 8, seq: int = 128,
          tc: Optional[TrainConfig] = None, mesh=None, seed: int = 0,
          ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
          log_every: int = 10, coordinator: Optional[Coordinator] = None,
          frontend_batch=None, verbose: bool = True):
    """Train ``cfg`` on the synthetic corpus; returns (state, loss_history)."""
    tc = tc or TrainConfig(total_steps=steps, warmup_steps=max(1, steps // 10))
    init_state, train_step = make_train_step(cfg, tc)

    key = jax.random.PRNGKey(seed)
    template = jax.eval_shape(init_state, key)
    state_sh = (shlib.train_state_shardings(template, cfg, mesh)
                if mesh is not None else None)
    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    start_step = 0
    if mgr and latest_step(ckpt_dir) is not None:
        state = mgr.restore(template)
        if mesh is not None:
            state = jax.device_put(state, state_sh)
        start_step = int(np.asarray(state.opt.step))
        if verbose:
            print(f"[train] restored checkpoint at step {start_step}")
    else:
        # one program builds the state in place: on a mesh, each device
        # makes only its shard and no device ever holds the whole state
        state = jax.jit(init_state, out_shardings=state_sh)(key)

    # the step donates the state: the caller never holds two copies of it
    jstep = jax.jit(train_step, in_shardings=(state_sh, None),
                    out_shardings=(state_sh, None), donate_argnums=0)

    if cfg.frontend == "none":
        data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                                      global_batch=batch, seed=seed)).batches()
    else:
        assert frontend_batch is not None, \
            "stub-frontend archs need a frontend_batch factory"
        data = iter(frontend_batch, None)

    coord = coordinator
    losses = []
    t_start = time.time()
    ctx = use_mesh(mesh) if mesh is not None else _nullcontext()
    with ctx:
        for step in range(start_step, steps):
            t0 = time.time()
            batch_np = next(data)
            state, metrics = jstep(state, {k: jax.numpy.asarray(v)
                                           for k, v in batch_np.items()})
            loss = float(metrics["loss"])
            losses.append(loss)
            if coord is not None:
                coord.report_step(0, time.time() - t0)
                coord.check()
            if mgr and (step + 1) % ckpt_every == 0:
                mgr.save(state, step + 1)
            if verbose and (step + 1) % log_every == 0:
                dt = (time.time() - t_start) / (step + 1 - start_step)
                print(f"[train] step {step+1:5d} loss {loss:.4f} "
                      f"({dt*1e3:.0f} ms/step)")
    if mgr:
        mgr.save(state, steps)
        mgr.wait_all()
    return state, losses


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="olmo-1b")
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced (CPU-sized) config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    tc = TrainConfig(remat=args.remat, n_micro=args.n_micro,
                     grad_compress=args.grad_compress,
                     moment_dtype=cfg.moment_dtype,
                     total_steps=args.steps,
                     warmup_steps=max(1, args.steps // 10))
    coord = Coordinator(1, CoordinatorConfig())
    _, losses = train(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                      tc=tc, ckpt_dir=args.ckpt_dir, seed=args.seed,
                      coordinator=coord)
    print(f"[train] done: first loss {losses[0]:.4f} -> last {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
