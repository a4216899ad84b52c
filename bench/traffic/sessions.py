"""Open-loop multi-turn chat sessions with Multi-Round ShareGPT
statistics (paper section 4): mean 5.5 turns, 78% multi-turn, lognormal
prompt and response lengths, a think time between a turn's last token
and the next turn, every follow-up on the session's retained KV.

New sessions arrive as a Poisson stream of ``rate_per_s``, drawn for as
long as the run lasts.  The sizes, think times and arrival times come
from the mix's ``shape_seed`` alone; ``--seed`` reorders the sessions in
progress at the start (all due at once) and draws the token ids.  So
every seed offers the same work at the same times.

Sessions already in progress when the traffic starts stand for the
steady state of the stream: by Little's law ``rate_per_s`` times
``session_s``, the mean seconds a session lasts from its first send to
its last token (measured on the chip at that rate).  Each begins at a
turn drawn uniformly from its turns, its history (the earlier turns'
prompts and responses) sent as that turn's prompt.
"""
from __future__ import annotations

import heapq
import random
from typing import Dict, List, Tuple

import numpy as np

from bench.traffic.base import (Submit, fresh_prefills, geometric,
                                lognormal_len, permuted, reused_prefills,
                                token_ids)


def _session_shapes(rng: random.Random, mix: Dict) -> Tuple[List, float]:
    """One session's [(prompt, response)] turns and think time."""
    mt, frac = mix["mean_turns"], mix["multi_turn_frac"]
    if rng.random() < frac:
        k = 2 + geometric(rng, 1.0 / (mt - (1 - frac)))
    else:
        k = 1
    cap, ctx_cap = mix["max_tokens"], mix["max_context"]
    turns, ctx = [], 0
    for _ in range(k):
        p = lognormal_len(rng, mix["prompt_mu"], mix["prompt_sigma"], cap)
        r = lognormal_len(rng, mix["response_mu"], mix["response_sigma"],
                          cap)
        if turns and ctx + p + r > ctx_cap:
            break
        p = min(p, max(4, ctx_cap - ctx - 8))
        r = min(r, max(4, ctx_cap - ctx - p))
        ctx += p + r
        turns.append((p, r))
    think = max(mix["think_min_s"],
                rng.gauss(mix["think_mean_s"], mix["think_sd_s"]))
    return turns, think


def in_progress(mix: Dict) -> int:
    return int(round(mix["rate_per_s"] * mix["session_s"]))


def live_shapes(mix: Dict) -> List[Tuple]:
    """The sessions in progress at the start: (turns, think, start turn)."""
    rng = random.Random(mix["shape_seed"])
    live = []
    for _ in range(in_progress(mix)):
        turns, think = _session_shapes(rng, mix)
        live.append((turns, think, rng.randrange(len(turns))))
    return live


def arrivals(mix: Dict):
    """The seed-independent stream of new sessions: (due_s, turns, think)."""
    rng = random.Random(f"{mix['shape_seed']}/arrivals")
    t = 0.0
    while True:
        t += rng.expovariate(mix["rate_per_s"])
        turns, think = _session_shapes(rng, mix)
        yield t, turns, think


class SessionTraffic:
    """The generator's state for one run."""

    def __init__(self, mix: Dict, seed: int, vocab: int):
        self._order = np.random.default_rng([seed, 1])
        self._ids = np.random.default_rng([seed, 2])
        self._arrivals = arrivals(mix)
        self.vocab = vocab
        self.sessions: Dict[int, Dict] = {}
        self._queue: List[Tuple[float, int, int]] = []   # (due, sid, turn)
        self._arrived_until = 0.0    # due of the last new session drawn
        for turns, think, start in permuted(live_shapes(mix), self._order):
            self._add(turns, think, start, 0.0)
        self._draw()

    def _add(self, turns, think, start, due):
        sid = len(self.sessions)
        self.sessions[sid] = {"turns": turns, "think": think,
                              "start": start}
        heapq.heappush(self._queue, (due, sid, start))

    def _draw(self):
        t, turns, think = next(self._arrivals)
        self._add(turns, think, 0, t)
        self._arrived_until = t

    def next_due(self) -> float:
        return self._queue[0][0]

    def pop_due(self, now: float) -> List[Submit]:
        while self._arrived_until <= now:
            self._draw()
        out = []
        while self._queue[0][0] <= now:
            due, sid, turn = heapq.heappop(self._queue)
            s = self.sessions[sid]
            turns = s["turns"]
            if turn == s["start"]:
                # first turn sent: the history so far travels as prompt
                n = sum(p + r for p, r in turns[:turn]) + turns[turn][0]
            else:
                n = turns[turn][0]
            out.append(Submit(sid=sid, turn=turn,
                              prompt=token_ids(self._ids, n, self.vocab),
                              max_tokens=turns[turn][1],
                              retain=turn + 1 < len(turns), due=due))
        return out

    def finished(self, sid: int, turn: int, now: float) -> None:
        s = self.sessions[sid]
        if turn + 1 < len(s["turns"]):
            heapq.heappush(self._queue, (now + s["think"], sid, turn + 1))

    def is_first(self, sid: int, turn: int) -> bool:
        return turn == self.sessions[sid]["start"]


def prefill_shapes(mix: Dict, block_size: int):
    """(chunk, prefix, seeded) prefills the mix can reach: a session's
    first send holds up to a whole context; a follow-up reuses its
    session's cached prefix."""
    return (fresh_prefills(mix["max_context"], block_size)
            + reused_prefills(mix["max_context"], block_size))


def swaps(mix: Dict) -> bool:
    return True


def build(mix: Dict, seed: int, vocab: int) -> SessionTraffic:
    return SessionTraffic(mix, seed, vocab)
