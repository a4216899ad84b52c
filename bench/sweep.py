"""Find the knee of an open-loop cell: one run at each session rate, in
one process, on the chip.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> --rates <r> [<r> ...]

The sessions in progress at the start scale with the rate (the mix's
``session_s`` times it).  For each rate one JSON line gives the end-to-end metrics and the backlog of
turns sent but not yet answered, sampled each second of the window,
with its least-squares slope, and the mean seconds from due to last
token of the turns finished in the window: the knee is the highest rate
whose backlog does not grow.  Exits 2 without a TPU.
"""
from __future__ import annotations

import time

PROC_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(REPO), str(REPO / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax
    from bench import harness, stats
    from bench.peaks import peaks_for
    from bench.spec import load_cell
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    start = PROC_START
    for rate in args.rates:
        cell = load_cell(args.workload)
        cell.peaks = peaks_for(devices[0].device_kind)
        cell.mix["rate_per_s"] = rate
        seen = {}
        run_client = harness.Client.run_until

        def keep(self, t_end, sample_from=None):
            seen["client"] = self
            return run_client(self, t_end, sample_from)

        harness.Client.run_until = keep
        res = harness.run(cell, args.seed, args.seconds, False, start,
                          devices=devices[:1])
        harness.Client.run_until = run_client
        b = seen["client"].backlog
        ts = [t - b[0][0] for t, _ in b]
        ys = [y for _, y in b]
        fin = [t["end"] - t["due"] for t in seen["client"].turns.values()
               if t["done"] and b and t["end"] >= b[0][0]]
        print(json.dumps({
            "rate_per_s": rate,
            "backlog_start": ys[0] if ys else None,
            "backlog_end": ys[-1] if ys else None,
            "backlog_slope_per_s": stats.slope(ts, ys),
            "turns_finished": len(fin),
            "turn_s": sum(fin) / len(fin) if fin else None,
            "correct": res["correct"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()}}),
            flush=True)
        start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
