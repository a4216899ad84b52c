"""Which layer of the program a device event belongs to.

The program names no scopes yet, so events are matched by the names of
its jitted programs (the trace's ``XLA Modules`` line) and of its Pallas
kernels (``XLA Ops``).  The first pattern that matches wins.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Tuple

# (layer, pattern over program names)
PROGRAMS: List[Tuple[str, re.Pattern]] = [
    ("decode", re.compile(r"paged_decode_step")),
    ("prefill", re.compile(r"prefill_kv_chunk|insert_prefill|seed_carry"
                           r"|grow_carry|slice_tokens|zeros_carry")),
    ("swap", re.compile(r"gather_swap|scatter_swap")),
]

# (layer, pattern over op names) for kernels named within a program
KERNELS: List[Tuple[str, re.Pattern]] = [
    ("paged_attention", re.compile(r"paged_attention")),
    ("swap_copy", re.compile(r"copy_runs")),
]


def program_layer(name: str) -> str:
    for layer, pat in PROGRAMS:
        if pat.search(name):
            return layer
    return "other"


def kernel_of(name: str) -> Optional[str]:
    for kernel, pat in KERNELS:
        if pat.search(name):
            return kernel
    return None


def op_programs(ops, modules) -> List[str]:
    """For each op (in ``ops`` order), the name of the program whose
    execution interval holds its start ("" if none)."""
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    out = []
    for _, s, _ in ops:
        i = bisect.bisect_right(starts, s) - 1
        out.append(mods[i][0] if i >= 0 and mods[i][2] >= s else "")
    return out


def in_window(events, lo: float, hi: float):
    return [e for e in events if e[1] >= lo and e[2] <= hi]


def program_time(red, dev: int, layer: str, lo: float, hi: float
                 ) -> Tuple[float, int]:
    """Seconds and count of ``layer``'s program executions in the window."""
    evs = [e for e in in_window(red.modules.get(dev, []), lo, hi)
           if program_layer(e[0]) == layer]
    return sum(e[2] - e[1] for e in evs) / 1e9, len(evs)


def kernel_time(red, dev: int, kernel: str, lo: float, hi: float
                ) -> Tuple[float, int]:
    evs = [e for e in in_window(red.ops.get(dev, []), lo, hi)
           if kernel_of(e[0]) == kernel]
    return sum(e[2] - e[1] for e in evs) / 1e9, len(evs)


def by_layer(red, dev: int, lo: float, hi: float) -> Dict[str, float]:
    ops = in_window(red.ops.get(dev, []), lo, hi)
    progs = op_programs(ops, red.modules.get(dev, []))
    out: Dict[str, float] = {}
    for (name, s, e), prog in zip(ops, progs):
        k = kernel_of(name) or program_layer(prog)
        out[k] = out.get(k, 0.0) + (e - s) / 1e9
    return out
