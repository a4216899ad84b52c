"""Closed-loop single-turn requests: ``clients`` callers, each sending
its next request as soon as the previous one completes (bulk and
offline API jobs that hold a fixed number of requests in flight).

Prompt lengths are lognormal, output lengths uniform; the population of
sizes comes from the mix's ``shape_seed`` and is the same for every run
seed, which only reorders it inside each block of ``clients`` requests
(so every seed sends the same sizes at about the same point of the run)
and draws the token ids.  The first
request of client ``i`` asks for ``(i + 1) / clients`` of its output
length, so the clients finish at spread times and the window starts
with their progress spread.
"""
from __future__ import annotations

import random
from typing import Dict, List

import numpy as np

from bench.traffic.base import (Submit, fresh_prefills, lognormal_len,
                                permuted, token_ids)


def shapes(mix: Dict):
    rng = random.Random(mix["shape_seed"])
    return [(lognormal_len(rng, mix["prompt_mu"], mix["prompt_sigma"],
                           mix["prompt_cap"]),
             rng.randint(mix["output_min"], mix["output_max"]))
            for _ in range(mix["population"])]


class ClosedTraffic:
    def __init__(self, mix: Dict, seed: int, vocab: int):
        self.vocab = vocab
        order = np.random.default_rng([seed, 1])
        pop, n = shapes(mix), mix["clients"]
        self._shapes = [s for i in range(0, len(pop), n)
                        for s in permuted(pop[i:i + n], order)]
        self._ids = np.random.default_rng([seed, 2])
        self._next = 0
        self._ready: List[Submit] = []
        n = mix["clients"]
        for i in range(n):
            self._ready.append(self._take(0.0, (i + 1) / n))

    def _take(self, now: float, frac: float = 1.0) -> Submit:
        p, r = self._shapes[self._next % len(self._shapes)]
        sid = self._next
        self._next += 1
        return Submit(sid=sid, turn=0,
                      prompt=token_ids(self._ids, p, self.vocab),
                      max_tokens=max(1, int(round(r * frac))),
                      retain=False, due=now)

    def next_due(self) -> float:
        return min((s.due for s in self._ready), default=float("inf"))

    def pop_due(self, now: float) -> List[Submit]:
        out = [s for s in self._ready if s.due <= now]
        self._ready = [s for s in self._ready if s.due > now]
        return out

    def finished(self, sid: int, turn: int, now: float) -> None:
        self._ready.append(self._take(now))

    def is_first(self, sid: int, turn: int) -> bool:
        return True


def prefill_shapes(mix: Dict, block_size: int):
    """(chunk, prefix, seeded) prefills the mix can reach: fresh prompts
    only."""
    return fresh_prefills(mix["prompt_cap"], block_size)


def swaps(mix: Dict) -> bool:
    """A finished turn's KV is copied to the host even when not retained,
    so the swap-out programs run here too."""
    return True


def build(mix: Dict, seed: int, vocab: int) -> ClosedTraffic:
    return ClosedTraffic(mix, seed, vocab)
