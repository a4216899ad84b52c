"""Find a cell's pieces by name: the workload in ``BENCHMARK.json``, its
configuration file, its traffic mix, its correctness limit and its
metrics' readers.  Adding a cell, a mix, a configuration or a metric
adds files and entries; nothing here names one."""
from __future__ import annotations

import importlib
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
REPO = BENCH_DIR.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict                 # the configuration file, as run
    mix: Dict                    # the traffic file
    limits: Dict                 # the correctness limits
    end_to_end: List[Dict]       # metrics this cell reports, --trace 0
    per_layer: List[Dict]        # metrics this cell reports, --trace 1
    peaks: Optional[Dict] = None  # the chip's published peaks


def _reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_mix(name: str, traffic_dir: Path = BENCH_DIR / "traffic") -> Dict:
    mix = load_json(Path(traffic_dir) / f"{name}.json")
    mix["name"] = name
    return mix


def generator(mix: Dict):
    """The generator module a mix names."""
    return importlib.import_module(f"bench.traffic.{mix['generator']}")


def metric_reader(name: str):
    """``bench/metrics/<name>.py``'s ``read``."""
    return importlib.import_module(f"bench.metrics.{name}").read


def load_cell(workload: str, root: Path = REPO,
              benchmark: Optional[Dict] = None,
              traffic_dir: Optional[Path] = None) -> Cell:
    from bench.model import load_config
    bm = benchmark or load_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bm["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(by_name)})")
    w = by_name[workload]
    cfg = {c["name"]: c for c in bm["configs"]}[w["config"]]
    mix = load_mix(w["traffic"], traffic_dir or root / "bench" / "traffic")
    limits_path = root / "bench" / "limits" / f"{workload}.json"
    limits = load_json(limits_path) if limits_path.exists() else {}
    return Cell(name=workload, chips=int(w["chips"]),
                config=load_config(root / cfg["file"]), mix=mix,
                limits=limits,
                end_to_end=[m for m in bm["end_to_end"]
                            if _reports(m, workload)],
                per_layer=[m for m in bm["per_layer"]
                           if _reports(m, workload)])
