"""Traffic from a mix's parameters and a seed.

One general generator reads every traffic file.  Every seed gets the same
schedule, so that runs of different seeds measure the same load; the
seed draws the prompts' tokens (and, elsewhere, the weights):

* sizes (prompt and output lengths) are a fixed stratified set, the
  distribution's quantiles at (i + 0.5) / n, in an order drawn from the
  mix's ``schedule_seed``, prompt and output lengths paired independently;
* an open loop sends exactly ``rate * seconds`` requests in the window.
  Their gaps are Gamma(shape) draws, from ``schedule_seed``, scaled to
  fill the window: a Gamma renewal process conditioned on its count
  (shape 1 is Poisson).

The schedule is the mix's and not the seed's because at 0.8 of the knee
the tail of time to first token turns on which long requests arrive
together: with the order drawn from the seed, the 95th percentile of a
window of about a hundred requests spread by 20% to several times its
median from seed to seed.

Nothing here touches JAX: it runs before the chip is claimed.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator for each named use of one seed."""
    tag = [ord(c) for c in stream]
    return np.random.default_rng([int(seed) & (2**64 - 1), *tag])


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified sizes of a length distribution, as int64.

    ``{"dist": "lognormal", "median": m, "sigma": s}`` with optional
    ``"clip": [lo, hi]`` and ``"snap": [v, ...]`` (nearest value in log
    space), or ``{"dist": "choice", "values": [v, ...]}`` (equal shares).
    """
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "choice":
        vals = np.asarray(dist["values"], np.int64)
        return vals[np.minimum((u * len(vals)).astype(int), len(vals) - 1)]
    if kind != "lognormal":
        raise ValueError(f"unknown length distribution {kind!r}")
    z = np.array([NormalDist().inv_cdf(x) for x in u])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    if "clip" in dist:
        x = np.clip(x, *dist["clip"])
    if "snap" in dist:
        snap = np.asarray(sorted(dist["snap"]), np.float64)
        x = snap[np.abs(np.log(x)[:, None] - np.log(snap)[None]).argmin(1)]
    return np.rint(x).astype(np.int64)


def length_set(dist: dict) -> list[int]:
    """Every prompt length a distribution can give (what set-up warms)."""
    if dist["dist"] == "choice":
        return sorted(set(int(v) for v in dist["values"]))
    if "snap" in dist:
        return sorted(set(int(v) for v in dist["snap"]))
    raise ValueError("a prompt distribution must have a finite set of "
                     "lengths: give 'snap' or use 'choice'")


@dataclass
class Item:
    """One request: when it is due (seconds into the window, or None for
    a backlog), its prompt and how many tokens it asks for."""
    due: float | None
    prompt: np.ndarray
    max_new: int


def arrival_times(arrivals: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times in [0, seconds): ``round(rate * seconds)`` arrivals whose
    gaps are Gamma(``shape``) and sum to the window."""
    n = max(1, round(arrivals["rate_per_s"] * seconds))
    shape = float(arrivals.get("gamma_shape", 1.0))
    gaps = rng_for(seed, "arrivals").gamma(shape, 1.0, n + 1)
    t = np.cumsum(gaps)[:n] / gaps.sum() * seconds
    return t


class Traffic:
    """The requests of one run, in order."""

    def __init__(self, mix: dict, seed: int, seconds: float, vocab: int):
        self.mix, self.vocab = mix, vocab
        arr = mix["arrivals"]
        self.open_loop = arr["process"] == "open"
        sched = int(mix["schedule_seed"])
        if self.open_loop:
            self.due = arrival_times(arr, seconds, sched)
            n = len(self.due)
        else:
            self.due, n = None, int(mix["round"])
        self.n_due = len(self.due) if self.open_loop else None
        self.round = n
        self._prompt_rng = rng_for(seed, "prompts")
        self._order_rng = rng_for(sched, "order")
        self._plens = quantiles(mix["prompt_len"], n)
        self._olens = quantiles(mix["output_len"], n)
        self._buf: list[tuple[int, int]] = []
        self.issued = 0

    def _sizes(self) -> tuple[int, int]:
        if not self._buf:
            # one round: every stratified size once, in the seed's order;
            # prompt and output lengths are paired independently
            p = self._order_rng.permutation(self.round)
            o = self._order_rng.permutation(self.round)
            self._buf = list(zip(self._plens[p].tolist(),
                                 self._olens[o].tolist()))[::-1]
        return self._buf.pop()

    def next(self) -> Item:
        plen, olen = self._sizes()
        due = float(self.due[self.issued]) if self.open_loop else None
        self.issued += 1
        lo = int(self.mix.get("token_min", 2))
        prompt = self._prompt_rng.integers(lo, self.vocab, plen,
                                           dtype=np.int64).astype(np.int32)
        return Item(due, prompt, olen)
