"""The one traffic generator: reads a mix's parameters, draws requests.

A mix (`bench/traffic/<name>.json`) gives

  loop          "open" (arrivals on a schedule, `rate_rps`, Poisson) or
                "closed" (`clients` that each send their next request
                when the last one finished, no think time)
  prompt_len    {"choice": [...]} or {"loguniform": [lo, hi]}, with an
  output_len    optional "round_up": m
  block         requests per block (default 48)
  warm_s        open loop: seconds of arrivals served before the window
  warm_steps    closed loop: engine steps served before the window
  schedule_seed the seed of the schedule's shuffles

Requests come in blocks. Every block holds the same multiset of prompt
lengths, output lengths and (open loop) arrival gaps, taken at the
quantiles (i + 0.5) / block of their distributions, shuffled by the
mix's own `schedule_seed`. The run's --seed draws the prompts' token ids
(and, elsewhere, the weights). So every seed offers the same work at the
same times: on the chip, seeds that also reshuffled the schedule moved
an open loop's time-to-first-token tail by half its median, since the
tail is set by which arrivals bunch up (PERF.md).
"""
from __future__ import annotations

import math

import numpy as np

DEFAULT_BLOCK = 48


def _quantiles(k: int) -> np.ndarray:
    return (np.arange(k) + 0.5) / k


def block_values(dist: dict, k: int) -> np.ndarray:
    """The k values one block draws from `dist`, in ascending order."""
    if "choice" in dist:
        vals = list(dist["choice"])
        if k % len(vals):
            raise ValueError(f"block {k} is not a multiple of the "
                             f"{len(vals)} choices {vals}")
        out = np.repeat(np.asarray(vals, np.int64), k // len(vals))
    elif "loguniform" in dist:
        lo, hi = dist["loguniform"]
        x = np.exp(math.log(lo) + _quantiles(k) * (math.log(hi)
                                                   - math.log(lo)))
        out = np.clip(np.rint(x), lo, hi).astype(np.int64)
    else:
        raise ValueError(f"unknown length distribution {dist}")
    m = dist.get("round_up")
    if m:
        out = -(-out // m) * m
    return np.sort(out)


def prompt_lengths(traffic: dict) -> list[int]:
    k = traffic.get("block", DEFAULT_BLOCK)
    return sorted(set(int(n) for n in block_values(traffic["prompt_len"],
                                                   k)))


def _gaps(rate: float, k: int) -> np.ndarray:
    """k exponential gaps at their quantiles, rescaled to mean 1/rate."""
    g = -np.log1p(-_quantiles(k))
    return g / g.mean() / rate


class RequestStream:
    """Requests in the order the load generator issues them.

    `next()` gives (prompt ids, max_new_tokens, due seconds). Due times
    are relative to the window's start and begin at -warm_s; closed
    loops give None (a request is due when its client sends it)."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.t = traffic
        self.k = traffic.get("block", DEFAULT_BLOCK)
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        self.sched = np.random.default_rng(traffic["schedule_seed"])
        self.prompts = block_values(traffic["prompt_len"], self.k)
        self.outputs = block_values(traffic["output_len"], self.k)
        self.open = traffic["loop"] == "open"
        self.gaps = _gaps(traffic["rate_rps"], self.k) if self.open else None
        self.due = -float(traffic.get("warm_s", 0.0))
        self._block: list = []

    def _refill(self):
        r = self.sched
        p = r.permutation(self.prompts)
        o = r.permutation(self.outputs)
        g = r.permutation(self.gaps) if self.open else [None] * self.k
        self._block = list(zip(p, o, g))[::-1]

    def next(self) -> tuple[np.ndarray, int, float | None]:
        if not self._block:
            self._refill()
        plen, olen, gap = self._block.pop()
        ids = self.rng.integers(1, self.vocab, size=int(plen),
                                dtype=np.int32)
        due = None
        if self.open:
            due = self.due
            self.due += float(gap)
        return ids, int(olen), due
