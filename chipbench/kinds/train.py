"""Training: ``make_train_step``'s ``train_step``, jitted with its
state donated as ``launch/train.py`` runs it.

Set-up builds the one state and compiled step that the window then runs,
and drives them through the first ``check_steps`` steps with the window's
own call and feed, reading what the comparison needs as it goes: each
step's loss, each leaf's gradient as the optimizer got it (from the first
moment after step 1) and each leaf's change after the last of them.
Batches are drawn on the device from the seed, every row its own.

Besides norms, the first gradient is compared entry by entry at
``GRAD_SAMPLE`` flat positions of each leaf drawn from the seed: the norm
of a gradient barely moves under rounding noise, which averages out, while
its entries show it.
"""
from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import bench, loadgen, weights

GRAD_SAMPLE = 4096          # entries of each leaf's first gradient compared


@dataclass
class State:
    state: object
    step: object                    # the jitted train step
    feed: object                    # i -> batch
    shapes: dict
    losses: list = field(default_factory=list)
    grad_norms: dict = field(default_factory=dict)
    grad_sample: dict = field(default_factory=dict)
    change: dict = field(default_factory=dict)
    sample_at: dict = field(default_factory=dict)
    steps: int = 0
    tokens_per_step: int = 0


@jax.jit
def _norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in weights.flat(tree).items()}


@jax.jit
def _diff_norms(a, b):
    fb = weights.flat(b)
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)
                                           - fb[k].astype(jnp.float32))))
            for k, v in weights.flat(a).items()}


def sample_positions(shapes: dict, seed: int) -> dict:
    """The flat positions of each leaf whose first gradient is compared."""
    return {k: jnp.asarray(loadgen.rng_for(seed, "grad/" + k).integers(
                0, int(np.prod(s.shape)), GRAD_SAMPLE))
            for k, s in shapes.items() if s is not None}


@jax.jit
def _take(tree, at):
    return {k: v.reshape(-1)[at[k]] for k, v in weights.flat(tree).items()}


def make_feed(run):
    mix = run.traffic
    b, s = mix["batch"], mix["seq"]
    key = weights.jax_key(run.seed, "batches")
    lo, hi = int(mix.get("token_min", 2)), int(run.config["vocab_size"])

    @jax.jit
    def rows(key, i):
        r = jax.random.randint(jax.random.fold_in(key, i), (b, s + 1),
                               lo, hi, jnp.int32)
        return {"tokens": r[:, :-1], "labels": r[:, 1:]}
    return lambda i: rows(key, i)     # the key stays an argument


def train_config(run):
    from repro.train.step import TrainConfig
    o = run.traffic["optimizer"]
    return TrainConfig(remat=run.traffic["remat"],
                       moment_dtype=run.arch.moment_dtype,
                       lr=o["lr"], warmup_steps=o["warmup_steps"],
                       total_steps=o["total_steps"],
                       weight_decay=o["weight_decay"],
                       grad_clip=o["grad_clip"])


def setup(run) -> State:
    from repro.train.step import make_train_step
    init_state, train_step = make_train_step(run.arch, train_config(run))
    with run.spans.span("setup.state"):
        template = jax.eval_shape(init_state, jax.random.PRNGKey(0))
        shapes = weights.layout(run.arch)
        params = weights.make_weights(shapes, run.seed)
        zeros = jax.jit(lambda: jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), template.opt))()
        state = template._replace(params=params, opt=zeros)
        del params, zeros
    st = State(state, jax.jit(train_step, donate_argnums=0),
               make_feed(run), shapes)
    st.tokens_per_step = run.traffic["batch"] * run.traffic["seq"]
    st.sample_at = sample_positions(shapes, run.seed)
    b1 = run.traffic["optimizer"]["b1"]
    with run.spans.span("setup.check_steps"):
        for i in range(run.traffic["check_steps"]):
            st.state, m = st.step(st.state, st.feed(i))
            st.losses.append(float(m["loss"]))
            if i == 0:       # mu = (1 - b1) * g after one step from zero
                st.grad_norms = {k: float(v) / (1 - b1) for k, v in
                                 _norms(st.state.opt.mu).items()}
                st.grad_sample = {k: np.asarray(v) / (1 - b1) for k, v in
                                  _take(st.state.opt.mu, st.sample_at)
                                  .items()}
        p0 = weights.make_weights(shapes, run.seed)
        st.change = {k: float(v) for k, v in
                     _diff_norms(st.state.params, p0).items()}
        del p0
    st.steps = run.traffic["check_steps"]
    return st


def window(run, st: State):
    """Steps until the window's time is up; each step is sent before the
    previous one is waited for, and the window ends when the last is
    done, so it holds all the work it timed."""
    t0 = time.perf_counter()
    t_end = t0 + run.seconds
    losses, n, prev = [], 0, None
    while time.perf_counter() < t_end:
        with run.spans.span("train.feed"):
            batch = st.feed(st.steps + n)
        with run.spans.span("train.step"):
            st.state, m = st.step(st.state, batch)
        if prev is not None:
            with run.spans.span("train.sync"):
                losses.append(float(prev))
        prev, n = m["loss"], n + 1
    with run.spans.span("train.sync"):
        losses.append(float(prev))
    run.window = (t0, time.perf_counter())
    run.stats.update(window_steps=n, window_tokens=n * st.tokens_per_step,
                     window_losses=losses)


def drain(run, st: State):
    pass


def _worst(prog: dict, ref: dict, keys) -> float:
    """Worst leaf: |program norm - reference norm| over the larger of the
    reference's norm of that leaf and of the median leaf."""
    med = float(np.median([ref[k] for k in keys]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys)


def _worst_entries(prog: dict, ref: dict) -> float:
    """Worst leaf: norm of the difference of the sampled entries over the
    larger of the reference's sample norm of that leaf and of the median
    leaf."""
    norms = {k: float(np.linalg.norm(v)) for k, v in ref.items()}
    med = float(np.median(list(norms.values())))
    return max(float(np.linalg.norm(prog[k] - ref[k])) / max(norms[k], med)
               for k in ref)


def compare(losses, grads, change, sample, ref_out) -> dict:
    """The numbers ``correct`` compares, from the program's readings (or
    another path's) and the reference's."""
    r_losses, r_grads, r_change, r_sample = ref_out
    med = float(np.median(list(r_grads.values())))
    moved = [k for k in r_grads if r_grads[k] >= 1e-3 * med]
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(losses, r_losses)),
        "grad_norm_gap": _worst(grads, r_grads, sorted(r_grads)),
        "grad_sample_gap": _worst_entries(sample, r_sample),
        "update_norm_gap": _worst(change, r_change, moved),
    }


def reference_batches(st: State, n: int):
    return [(b["tokens"], b["labels"]) for b in map(st.feed, range(n))]


def check(run, st: State):
    losses = run.stats["window_losses"]
    run.attempted = len(losses)
    run.failed = sum(1 for x in losses if not np.isfinite(x))
    st.state = None
    gc.collect()
    ref = bench.load_module(run.root / "references" /
                            f"{run.config['reference']}.py")

    def make():
        return weights.make_weights(st.shapes, run.seed)
    batches = reference_batches(st, len(st.losses))
    theta, opt = float(run.arch.rope_theta), run.traffic["optimizer"]
    out = ref.train(make, batches, theta, opt, sample_at=st.sample_at)
    for k, v in compare(st.losses, st.grad_norms, st.change,
                        st.grad_sample, out).items():
        run.checks[k] = (v, bench.limit(run, k))
    if run.stats.get("readings"):     # chipbench/control.py only
        run.stats["readings"] = readings(run, st, ref, make, batches, out)


def readings(run, st: State, ref, make, batches, out) -> dict:
    """The control's and the faults' readings of each compared number,
    and each leaf's norms (program and reference) for the record."""
    theta, opt = float(run.arch.rope_theta), run.traffic["optimizer"]
    half = [(t[:len(t) // 2], lab[:len(t) // 2]) for t, lab in batches]
    p0 = make()
    still = [float(ref.loss(p0, t, lab, theta)) for t, lab in batches]
    del p0
    zero = {k: np.zeros_like(v) for k, v in st.grad_sample.items()}
    return {
        "control": compare(*ref.train(make, batches, theta, opt, lowp=True,
                                      sample_at=st.sample_at), out),
        "fault.half_batch": compare(*ref.train(make, half, theta, opt,
                                               sample_at=st.sample_at), out),
        # the state left unchanged: every step sees the initial weights,
        # the optimizer's moments stay zero and nothing moves
        "fault.state_unchanged": compare(
            still, {k: 0.0 for k in st.grad_norms},
            {k: 0.0 for k in st.change}, zero, out),
        "leaves": {k: [st.grad_norms[k], out[1][k], st.change[k], out[2][k]]
                   for k in sorted(st.grad_norms)},
    }
