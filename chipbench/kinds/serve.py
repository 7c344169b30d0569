"""Serving: feeds ``SlotServer`` through ``submit`` and ``step``.

``arrivals.process`` in the traffic file is ``open`` (requests are due on
a schedule and timed from when they were due, whether or not the server
has caught up) or ``backlog`` (the queue is topped up to ``depth`` before
every step, for throughput).  Every token is stamped on the host clock
after the step that made it; a step syncs on its tokens, so a stamp is
the time a user would see the token.

The harness records spans around the engine's admission, prefill and
decode calls and, per decode, the active slots and their positions.
"""
from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import bench, loadgen, weights

DRAIN_S = 60.0          # how long a due request may wait past the window


@dataclass
class Rec:
    """One request as the harness saw it."""
    req: object                     # the engine's Request
    due: float | None               # perf_counter when it was due
    max_new: int
    plen: int
    stamps: list = field(default_factory=list)   # perf_counter per token


@dataclass
class State:
    srv: object
    traffic: loadgen.Traffic
    recs: list = field(default_factory=list)
    inflight: list = field(default_factory=list)
    decodes: list = field(default_factory=list)  # (t, ctx per active slot)
    prefills: list = field(default_factory=list)  # (t, prompt length)


def _instrument(run, st: State):
    """Spans around the engine's admission, prefill and decode calls;
    each decode's active slots and positions."""
    srv, spans = st.srv, run.spans
    admit, prefill, decode = srv._admit, srv._prefill, srv._decode

    def prefill_rec(params, toks, caches, slot):
        st.prefills.append((time.perf_counter(), int(toks.shape[1])))
        with spans.span("engine.prefill"):
            return prefill(params, toks, caches, slot)

    def decode_rec(*a):
        ctx = (srv.pos[srv.active] + 1).tolist()
        st.decodes.append((time.perf_counter(), ctx))
        with spans.span("engine.decode"):
            return decode(*a)

    srv._admit = spans.wrap("engine.admit", admit)
    srv._prefill, srv._decode = prefill_rec, decode_rec


def setup(run) -> State:
    from repro.serve.engine import ServeConfig, SlotServer

    mix, eng = run.traffic, run.traffic["engine"]
    with run.spans.span("setup.weights"):
        params = weights.make_weights(weights.layout(run.arch), run.seed)
        jax.block_until_ready(params)
    sc = ServeConfig(max_slots=eng["max_slots"], max_len=eng["max_len"],
                     max_new_tokens=int(mix["output_len"].get(
                         "clip", [0, eng["max_len"]])[1]),
                     eos_id=-1)           # every request runs to max_new
    with run.spans.span("setup.server"):
        srv = SlotServer(run.arch, params, serve_cfg=sc)
        jax.block_until_ready(srv.caches)
    st = State(srv, loadgen.Traffic(mix, run.seed, run.seconds,
                                    run.config["vocab_size"]))
    with run.spans.span("setup.warmup"):
        # every prompt length the mix can send, and the decode step
        for n in loadgen.length_set(mix["prompt_len"]):
            srv.submit(np.full(n, 2, np.int32), max_new_tokens=2)
        srv.run_until_drained()
        srv.done.clear()
        jax.block_until_ready(srv.caches)
    _instrument(run, st)
    return st


def _submit(st: State, due):
    it = st.traffic.next()
    req = st.srv.submit(it.prompt, max_new_tokens=it.max_new)
    rec = Rec(req, due, it.max_new, len(it.prompt))
    st.recs.append(rec)
    st.inflight.append(rec)


def _step(run, st: State):
    with run.spans.span("engine.step"):
        st.srv.step()
    t = time.perf_counter()
    keep = []
    for rec in st.inflight:
        new = len(rec.req.output) - len(rec.stamps)
        rec.stamps.extend([t] * new)
        if rec.req.t_finish is None:
            keep.append(rec)
    st.inflight = keep


def window(run, st: State):
    srv, tr = st.srv, st.traffic
    open_loop = tr.open_loop
    depth = run.traffic["arrivals"].get("depth", 0)
    t0 = time.perf_counter()
    t_end = t0 + run.seconds
    run.window = (t0, t_end)
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        if open_loop:
            while tr.issued < tr.n_due and t0 + tr.due[tr.issued] <= now:
                _submit(st, t0 + tr.due[tr.issued])
        else:
            while len(srv.queue) < depth:
                _submit(st, None)
        if srv.queue or srv.active.any():
            _step(run, st)
        else:
            nxt = (t0 + tr.due[tr.issued] if tr.issued < tr.n_due
                   else t_end)
            with run.spans.span("loadgen.wait"):
                time.sleep(max(0.0, min(nxt, t_end) - time.perf_counter()))
    run.window = (t0, time.perf_counter())


def drain(run, st: State):
    """After the window: send what fell due in its last step, then step on
    until every request that was due in it has its first token, for at
    most DRAIN_S."""
    tr = st.traffic
    if not tr.open_loop:
        return
    t0 = run.window[0]
    while tr.issued < tr.n_due and tr.due[tr.issued] < run.seconds:
        _submit(st, t0 + tr.due[tr.issued])
    deadline = time.perf_counter() + DRAIN_S
    while (any(not r.stamps for r in st.recs)
           and time.perf_counter() < deadline
           and (st.srv.queue or st.srv.active.any())):
        _step(run, st)


def check(run, st: State):
    """Free the server, then compare a sample of the finished requests
    with the float32 reference, in blocks of one request."""
    srv = st.srv
    t0, t1 = run.window
    if st.traffic.open_loop:
        run.attempted = len(st.recs)
        run.failed = sum(1 for r in st.recs if not r.stamps)
    else:
        run.attempted = sum(1 for r in st.recs if r.stamps and
                            r.stamps[0] <= t1)
        run.failed = 0
    done = [r for r in st.recs if r.req.t_finish is not None]
    wrong = sum(1 for r in done if len(r.req.output) != r.max_new)
    run.stats.update(recs=st.recs, decodes=st.decodes,
                     prefills=st.prefills)
    # the program's state goes before the reference runs
    srv.caches = srv.params = None
    st.srv = None
    del srv
    gc.collect()
    ref = bench.load_module(run.root / "references" /
                            f"{run.config['reference']}.py")
    sample = pick_sample(done, run.seed, run.traffic["check"]["requests"])
    gaps = served_gaps(run, ref, sample)
    run.stats["checked_tokens"] = sum(len(r.req.output) for r in sample)
    run.checks["max_logit_gap"] = (max(gaps) if gaps else float("inf"),
                                   bench.limit(run, "max_logit_gap"))
    run.checks["wrong_lengths"] = (float(wrong),
                                   bench.limit(run, "wrong_lengths"))
    if run.stats.get("readings"):     # chipbench/control.py only
        run.stats["readings"] = {
            "control": {"max_logit_gap": max(
                served_gaps(run, ref, sample, control=True))},
            "fault.token_altered": {"max_logit_gap": max(
                served_gaps(run, ref, sample, alter=True))}}


def pick_sample(done: list, seed: int, n: int) -> list:
    """The longest finished request and others drawn from the seed."""
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: done[i].plen + len(done[i].req.output))
    rest = [i for i in range(len(done)) if i != longest]
    pick = loadgen.rng_for(seed, "check").permutation(rest)[:n - 1]
    return [done[longest]] + [done[i] for i in sorted(pick)]


def padded(run, rec: Rec):
    """The prompt and served tokens padded to ``max_len``, the token
    served after each position, and the positions that served one."""
    L = run.traffic["engine"]["max_len"]
    out = np.asarray(rec.req.output, np.int32)
    seq = np.concatenate([rec.req.tokens, out[:-1]])
    toks = np.zeros(L, np.int32)
    toks[:len(seq)] = seq
    served = np.zeros(L, np.int32)
    at = np.arange(rec.plen - 1, rec.plen - 1 + len(out))
    served[at] = out
    return jnp.asarray(toks), jnp.asarray(served), at


def served_gaps(run, ref, sample: list, control: bool = False,
                alter: bool = False) -> list:
    """Per sampled request, the widest gap between the reference's best
    logit and that of the token served (or, with ``control``, the token
    the lower-precision control puts first; with ``alter``, the served
    tokens with the middle one changed to the next token id)."""
    params = weights.make_weights(weights.layout(run.arch), run.seed)
    theta = float(run.arch.rope_theta)
    gaps = []
    for rec in sample:
        toks, served, at = padded(run, rec)
        if alter:
            mid = at[len(at) // 2]
            served = served.at[mid].set((served[mid] + 1)
                                        % run.arch.vocab_size)
        g = (ref.control_gaps(params, toks, theta) if control
             else ref.serve_gaps(params, toks, served, theta))
        gaps.append(float(np.asarray(g)[at].max()))
    return gaps
