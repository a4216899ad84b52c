"""Metric arithmetic over the client's per-turn records (host clock).

A turn record holds ``due`` (when the client should have sent it),
``sent`` and ``times`` (the host time at which each of its tokens came
back from ``step``).  The window is ``[w0, w1)``.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence

import numpy as np


def percentile(xs: Sequence[float], p: float) -> Optional[float]:
    """Linear-interpolated percentile (numpy's default); None if empty."""
    if len(xs) == 0:
        return None
    return float(np.percentile(np.asarray(xs, np.float64), p))


def ttft_samples(turns: Iterable[Dict], w0: float, w1: float) -> List[float]:
    """Seconds from due to first token, for every turn due in the window.
    A turn still unanswered at ``w1`` counts with its wait so far."""
    out = []
    for t in turns:
        if not (w0 <= t["due"] < w1):
            continue
        first = t["times"][0] if t["times"] else None
        out.append((first if first is not None and first < w1 else w1)
                   - t["due"])
    return out


def tbt_samples(turns: Iterable[Dict], w0: float, w1: float) -> List[float]:
    """Gaps between consecutive tokens of a turn that start in the window:
    complete gaps ending before ``w1``, and for a token in the window
    whose successor had not come by ``w1`` (a preempted or stalled
    turn), the wait so far.  A turn's last token opens no gap."""
    out = []
    for t in turns:
        ts = t["times"]
        n = len(ts)
        for i, a in enumerate(ts):
            if not (w0 <= a < w1):
                continue
            if i + 1 < n:
                b = ts[i + 1]
                out.append((b if b < w1 else w1) - a)
            elif not t["done"] or t["end"] >= w1:
                out.append(w1 - a)
    return out


def tokens_in(turns: Iterable[Dict], w0: float, w1: float) -> int:
    return sum(1 for t in turns for a in t["times"] if w0 <= a < w1)


def union_length(intervals: Iterable[Sequence[float]]) -> float:
    """Total length covered by ``[(start, end)]`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals: Iterable[Sequence[float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(busy: List[List[float]], lo: float, hi: float
         ) -> List[List[float]]:
    """The idle intervals of ``[lo, hi)`` between merged busy ones."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append([t, min(s, hi)])
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append([t, hi])
    return [g for g in out if g[1] > g[0]]


def slope(ts: Sequence[float], ys: Sequence[float]) -> float:
    """Least-squares slope of ``ys`` over ``ts`` (0 with < 2 points)."""
    if len(ts) < 2:
        return 0.0
    return float(np.polyfit(np.asarray(ts), np.asarray(ys, float), 1)[0])
