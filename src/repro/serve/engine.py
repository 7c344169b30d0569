"""Slot-based continuous-batching serving engine.

A fixed pool of ``max_slots`` decode slots shares one KV-cache allocation
(static shapes — pjit-able).  Requests prefill at batch 1 and their caches
are scattered into a free slot; every engine iteration decodes *all* active
slots in one batched ``serve_decode`` call; finished slots (EOS or
max-tokens) free immediately and admit queued requests — the standard
continuous-batching discipline (Orca/vLLM style) expressed in pure JAX.

SLO accounting mirrors the paper's measurement: per-request end-to-end
latency (arrival -> last token) and time-to-first-token.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import transformer
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
    arrival: float = 0.0
    max_new_tokens: Optional[int] = None
    # filled by the engine
    output: list[int] = field(default_factory=list)
    t_first_token: Optional[float] = None
    t_finish: Optional[float] = None


class SlotServer:
    """Continuous-batching server for decoder-only configs."""

    def __init__(self, cfg: ArchConfig, params: Optional[PyTree] = None, *,
                 serve_cfg: Optional[ServeConfig] = None, seed: int = 0,
                 clock: Optional[Callable[[], float]] = None):
        assert not cfg.is_encoder_decoder, "SlotServer serves decoder LMs"
        self.cfg = cfg
        # a ServeConfig() default argument would be evaluated once and
        # shared by every server — mutating one server's sc (e.g. tuning
        # max_new_tokens) would silently retune all of them
        self.sc = serve_cfg if serve_cfg is not None else ServeConfig()
        self.params = (params if params is not None
                       else init_model(cfg, jax.random.PRNGKey(seed)))
        self.clock = clock or (lambda: 0.0)
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

        self._prefill = jax.jit(self._prefill_impl)
        self._decode = jax.jit(self._decode_impl)

    # -- jitted compute ----------------------------------------------------------

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
                      arrival=self.clock(),
                      max_new_tokens=max_new_tokens)
        self.queue.append(req)
        return req

    def _admit(self):
        for slot in range(self.sc.max_slots):
            if self.active[slot] or not self.queue:
                continue
            req = self.queue.pop(0)
            toks = req.tokens[-(self.sc.max_len - 1):][None, :]
            logits, self.caches = self._prefill(
                self.params, jnp.asarray(toks), self.caches, slot)
            first = int(jnp.argmax(logits, -1))
            req.output.append(first)
            req.t_first_token = self.clock()
            self.slot_req[slot] = req
            self.pos[slot] = toks.shape[1]
            self.budget[slot] = (req.max_new_tokens or
                                 self.sc.max_new_tokens) - 1
            self.active[slot] = True
            self._last = self._last.at[slot].set(first)
            if first == self.sc.eos_id or self.budget[slot] <= 0:
                self._finish(slot)

    def _finish(self, slot: int):
        req = self.slot_req[slot]
        req.t_finish = self.clock()
        self.done.append(req)
        self.slot_req[slot] = None
        self.active[slot] = False

    def step(self) -> int:
        """One engine iteration: admit then decode all active slots.
        Returns number of active slots decoded."""
        self._admit()
        if not self.active.any():
            return 0
        nxt, _, self.caches = self._decode(
            self.params, self._last, jnp.asarray(self.pos),
            self.caches, jnp.asarray(self.active))
        nxt_np = np.asarray(nxt)
        n = 0
        for slot in range(self.sc.max_slots):
            if not self.active[slot]:
                continue
            n += 1
            tok = int(nxt_np[slot])
            req = self.slot_req[slot]
            req.output.append(tok)
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

    # -- metrics ------------------------------------------------------------------------

    def latencies(self) -> list[float]:
        return [r.t_finish - r.arrival for r in self.done
                if r.t_finish is not None]
