"""Reduction of a profiler trace (``jax.profiler`` xplane) to what the
per-layer metrics read: the device's op and program intervals, and the
benchmark's own host spans (``bench:<name>`` annotations), on the
trace's one clock.

Device planes are ``/device:TPU:<n>``.  Within one, the ``XLA Ops``
line holds one event per executed HLO op (a Pallas kernel is one op)
and ``XLA Modules`` one per executed program.  A plane without those
lines contributes all its events as ops.
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from bench.stats import gaps, merged

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "bench:"

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)


@dataclass
class Reduced:
    ops: Dict[int, List[Event]] = field(default_factory=dict)
    modules: Dict[int, List[Event]] = field(default_factory=dict)
    spans: List[Event] = field(default_factory=list)

    def window(self) -> Optional[Tuple[float, float]]:
        """The traced window: the ``bench:window`` span."""
        w = [(s, e) for n, s, e in self.spans if n == "window"]
        return w[0] if w else None

    def busy_ns(self, dev: int, lo: float, hi: float) -> float:
        return sum(min(e, hi) - max(s, lo)
                   for s, e in self.busy(dev) if e > lo and s < hi)

    def busy(self, dev: int) -> List[List[float]]:
        return merged((s, e) for _, s, e in self.ops.get(dev, []))

    def idle_gaps(self, dev: int, lo: float, hi: float
                  ) -> List[List[float]]:
        return gaps(self.busy(dev), lo, hi)

    def span_at(self, t: float) -> str:
        """The innermost benchmark span open at ``t`` (else "client")."""
        best, best_len = "client", None
        for n, s, e in self.spans:
            if n != "window" and s <= t < e and (best_len is None
                                                 or e - s < best_len):
                best, best_len = n, e - s
        return best


def _events(line) -> List[Event]:
    return [(ev.name, float(ev.start_ns),
             float(ev.start_ns) + float(ev.duration_ns))
            for ev in line.events]


def reduce_planes(planes) -> Reduced:
    """``planes``: iterable of objects with ``name`` and ``lines`` (each
    with ``name`` and ``events`` of ``name``/``start_ns``/
    ``duration_ns``), as ``jax.profiler.ProfileData`` gives them."""
    r = Reduced()
    for pl in planes:
        m = _DEVICE.match(pl.name)
        if m:
            dev = int(m.group(1))
            lines = {ln.name: ln for ln in pl.lines}
            if "XLA Ops" in lines:
                r.ops[dev] = _events(lines["XLA Ops"])
                if "XLA Modules" in lines:
                    r.modules[dev] = _events(lines["XLA Modules"])
            else:
                r.ops[dev] = [ev for ln in pl.lines for ev in _events(ln)]
        elif pl.name.startswith("/host:"):
            for ln in pl.lines:
                r.spans += [(n[len(SPAN_PREFIX):], s, e)
                            for n, s, e in _events(ln)
                            if n.startswith(SPAN_PREFIX)]
    return r


def load(trace_dir: Path) -> Reduced:
    from jax.profiler import ProfileData
    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no xplane trace under {trace_dir}")
    return reduce_planes(ProfileData.from_file(files[-1]).planes)


def describe(trace_dir: Path, top: int = 25) -> Dict:
    """Every plane and line with its event count and busiest names, and
    the stats of a few events: for reading a trace by hand."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    out = {}
    for pl in ProfileData.from_file(files[-1]).planes:
        lines = {}
        for ln in pl.lines:
            tot: Dict[str, float] = {}
            first = {}
            n = 0
            for ev in ln.events:
                n += 1
                tot[ev.name] = tot.get(ev.name, 0.0) + ev.duration_ns
                if ev.name not in first and len(first) < 8:
                    first[ev.name] = {k: str(v)[:160] for k, v in ev.stats}
            lines[ln.name] = {
                "events": n,
                "top": sorted(tot.items(), key=lambda kv: -kv[1])[:top],
                "stats": first}
        out[pl.name] = lines
    return out
