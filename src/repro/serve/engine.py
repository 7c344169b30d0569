"""Slot-based continuous-batching serving engine.

A fixed pool of ``max_slots`` decode slots shares one KV-cache allocation
(static shapes — pjit-able).  Requests prefill at batch 1 and their caches
are scattered into a free slot; every engine iteration decodes *all* active
slots in one batched ``serve_decode`` call; finished slots (EOS or
max-tokens) free immediately and admit queued requests — the standard
continuous-batching discipline (Orca/vLLM style) expressed in pure JAX.

Each request is stamped on the engine's clock when it is submitted, when
it is admitted to a slot, and for every token right after the host sync
that made the token visible.  Each iteration records spans in a
``repro.obs.Tracer`` (the process's default unless one is given):

    engine.step                  one iteration; counters ``slots`` (slots
                                 decoded), ``queued`` (requests waiting as
                                 it began) and ``prefills``
      engine.admit               admission of queued requests
        engine.prefill           one request's prefill call (its ``rid``)
        engine.prefill.sync      the wait for its first token
      engine.decode              the decode call and its inputs' transfer
      engine.decode.sync         the wait for the step's tokens
      engine.emit                the per-slot bookkeeping
"""
from __future__ import annotations

import itertools
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ArchConfig
from repro.models import transformer
from repro.models.common import scoped
from repro.models.registry import init_model

PyTree = Any


@dataclass
class ServeConfig:
    max_slots: int = 4
    max_len: int = 256
    max_new_tokens: int = 32
    eos_id: int = 1
    greedy: bool = True


@dataclass
class Request:
    rid: int
    tokens: np.ndarray                 # [S] prompt
    t_submit: float = 0.0
    max_new_tokens: Optional[int] = None
    # filled by the engine, on its clock
    output: list[int] = field(default_factory=list)
    t_admit: Optional[float] = None
    t_first_token: Optional[float] = None
    t_finish: Optional[float] = None
    token_times: list[float] = field(default_factory=list)  # per output


class SlotServer:
    """Continuous-batching server for decoder-only configs."""

    def __init__(self, cfg: ArchConfig, params: Optional[PyTree] = None, *,
                 serve_cfg: Optional[ServeConfig] = None, seed: int = 0,
                 clock: Optional[Callable[[], float]] = None,
                 tracer: Optional[obs.Tracer] = None):
        assert not cfg.is_encoder_decoder, "SlotServer serves decoder LMs"
        self.cfg = cfg
        # a ServeConfig() default argument would be evaluated once and
        # shared by every server — mutating one server's sc (e.g. tuning
        # max_new_tokens) would silently retune all of them
        self.sc = serve_cfg if serve_cfg is not None else ServeConfig()
        self.params = (params if params is not None
                       else init_model(cfg, jax.random.PRNGKey(seed)))
        self.clock = clock or time.perf_counter
        self.tracer = tracer or obs.tracer()
        B, L = self.sc.max_slots, self.sc.max_len
        self.caches = transformer.init_caches(cfg, B, L)
        self.pos = np.zeros(B, np.int64)            # next position per slot
        self.budget = np.zeros(B, np.int64)         # tokens left per slot
        self.active = np.zeros(B, bool)
        self.slot_req: list[Optional[Request]] = [None] * B
        self.queue: list[Request] = []
        self.done: list[Request] = []
        self._rid = itertools.count()
        self._last = jnp.zeros(B, jnp.int32)        # last sampled token

        # the caches (argument 2 of prefill, 3 of decode) are donated: each
        # step updates them in place, and the engine keeps only the caches
        # a step returns
        self._prefill = jax.jit(self._prefill_impl, donate_argnums=2)
        self._decode = jax.jit(self._decode_impl, donate_argnums=3)

    # -- jitted compute ----------------------------------------------------------

    @scoped("prefill")
    def _prefill_impl(self, params, tokens, caches, slot):
        """Batch-1 prefill; scatter the new caches into ``slot``."""
        logits, new1 = transformer.prefill(params, self.cfg, tokens,
                                           max_len=self.sc.max_len)

        def scatter(full, one):
            # full: [B, ...] or [G, B, ...] (scanned layers); one: B=1.
            # The slot axis is the first axis where shapes differ.
            axis = next(i for i in range(one.ndim)
                        if one.shape[i] != full.shape[i])
            return jax.lax.dynamic_update_slice_in_dim(
                full, one.astype(full.dtype), slot, axis=axis)

        merged = jax.tree.map(scatter, caches, new1)
        return logits[0], merged

    @scoped("decode")
    def _decode_impl(self, params, tokens, pos, caches, active):
        """One decode step over all slots (per-slot positions); inactive
        slots still compute (static shapes) but their outputs are ignored.
        Returns (next tokens [B], logits [B,V], new caches)."""
        logits, new_caches = transformer.decode_step(
            params, self.cfg, tokens, pos, caches)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, logits, new_caches

    # -- public API -----------------------------------------------------------------

    def submit(self, tokens: np.ndarray,
               max_new_tokens: Optional[int] = None) -> Request:
        req = Request(next(self._rid), np.asarray(tokens, np.int32),
                      t_submit=self.clock(),
                      max_new_tokens=max_new_tokens)
        self.queue.append(req)
        return req

    def _admit(self) -> int:
        """Prefill queued requests into free slots; returns how many."""
        tr, n = self.tracer, 0
        with tr.span("engine.admit"):
            for slot in range(self.sc.max_slots):
                if self.active[slot] or not self.queue:
                    continue
                req = self.queue.pop(0)
                req.t_admit = self.clock()
                n += 1
                toks = req.tokens[-(self.sc.max_len - 1):][None, :]
                with tr.span("engine.prefill", req.rid):
                    logits, self.caches = self._prefill(
                        self.params, jnp.asarray(toks), self.caches, slot)
                with tr.span("engine.prefill.sync", req.rid):
                    first = int(jnp.argmax(logits, -1))
                req.t_first_token = self.clock()
                req.token_times.append(req.t_first_token)
                req.output.append(first)
                self.slot_req[slot] = req
                self.pos[slot] = toks.shape[1]
                self.budget[slot] = (req.max_new_tokens or
                                     self.sc.max_new_tokens) - 1
                self.active[slot] = True
                self._last = self._last.at[slot].set(first)
                if first == self.sc.eos_id or self.budget[slot] <= 0:
                    self._finish(slot)
        return n

    def _finish(self, slot: int):
        req = self.slot_req[slot]
        req.t_finish = self.clock()
        self.done.append(req)
        self.slot_req[slot] = None
        self.active[slot] = False

    def step(self) -> int:
        """One engine iteration: admit then decode all active slots.
        Returns number of active slots decoded."""
        tr = self.tracer
        with tr.span("engine.step") as span:
            queued = len(self.queue)
            prefills = self._admit()
            n = self._decode_active() if self.active.any() else 0
            span.count(slots=n, queued=queued, prefills=prefills)
        return n

    def _decode_active(self) -> int:
        """Decode every active slot once; returns how many."""
        tr = self.tracer
        with tr.span("engine.decode"):
            nxt, _, self.caches = self._decode(
                self.params, self._last, jnp.asarray(self.pos),
                self.caches, jnp.asarray(self.active))
        with tr.span("engine.decode.sync"):
            nxt_np = np.asarray(nxt)
        t = self.clock()
        n = 0
        with tr.span("engine.emit"):
            for slot in range(self.sc.max_slots):
                if not self.active[slot]:
                    continue
                n += 1
                tok = int(nxt_np[slot])
                req = self.slot_req[slot]
                req.output.append(tok)
                req.token_times.append(t)
                self.pos[slot] += 1
                self.budget[slot] -= 1
                if (tok == self.sc.eos_id or self.budget[slot] <= 0
                        or self.pos[slot] >= self.sc.max_len - 1):
                    self._finish(slot)
        self._last = nxt
        return n

    def run_until_drained(self, max_iters: int = 10_000) -> list[Request]:
        for _ in range(max_iters):
            if not self.queue and not self.active.any():
                break
            self.step()
        return self.done
