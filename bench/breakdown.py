"""The traced run's ``breakdown``: the device ops that took most time,
labelled with their layer, and the longest idle gaps of the device,
labelled with the benchmark span the host was in at the gap's middle."""
from __future__ import annotations

from typing import Dict, List

from bench.layers import in_window, kernel_of, op_programs, program_layer

TOP = 10


def breakdown(red, lo: float, hi: float, dev: int = 0) -> Dict[str, List]:
    ops = in_window(red.ops.get(dev, []), lo, hi)
    progs = op_programs(ops, red.modules.get(dev, []))
    tot: Dict[str, float] = {}
    for (name, s, e), prog in zip(ops, progs):
        # an op's name is its HLO text: keep the part before " = "
        label = f"{kernel_of(name) or program_layer(prog)}/" \
                f"{name.split(' = ')[0]}"
        tot[label] = tot.get(label, 0.0) + (e - s) / 1e9
    device_ops = sorted(tot.items(), key=lambda kv: -kv[1])[:TOP]
    idle = sorted(red.idle_gaps(dev, lo, hi), key=lambda g: g[0] - g[1])
    gaps = [[red.span_at((a + b) / 2), (b - a) / 1e9]
            for a, b in idle[:TOP]]
    return {"device_ops": [[k, v] for k, v in device_ops],
            "idle_gaps": gaps}
