"""Run one benchmark cell once on the chip this process is started on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process touches the chip and starts no other.  It exits with 2, and
prints no result, unless JAX's first device is a TPU of a kind with
published peaks (``bench/peaks.py``) and there are as many chips as the
cell asks for.  The last line of standard output is the result: one
JSON object with ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` with ``--trace 1``) and, last, the numbers
the correctness check compared with their limits.  Progress and the
counts behind the metrics go to standard error.
"""
from __future__ import annotations

import time

PROC_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(REPO), str(REPO / "src")]

# traces of --trace 1 runs, inside the checkout (git ignores it)
TRACE_DIR = REPO / ".bench_trace"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.spec import load_cell
    cell = load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU; JAX's first device is "
              f"{devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    from bench.peaks import peaks_for
    try:
        cell.peaks = peaks_for(devices[0].device_kind)
    except ValueError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    from bench import harness
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         PROC_START, trace_dir=TRACE_DIR,
                         devices=devices[:cell.chips])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
