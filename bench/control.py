"""Readings that set a cell's correctness limit, on the chip.

    python3 bench/control.py --workload <name> --seconds <s> --seeds <n> [<n> ...] [--fault <name>]

For each seed, one process runs the cell as ``run.py`` does (its own
size, traffic and window, here a short one) and reads, on the sample
the check compares, both the program's widest gap (the lower reading)
and the precision control's: the same reference computed in float8
(e4m3) in the program's place, whose argmax at each position is scored
by the float32 reference (the upper reading).  With ``--fault <name>``
the decode step is broken underneath (``bench/faults.py``) and the
program's reading is that of the broken step.  The benchmark's own runs
never run the control.  One JSON line per seed; exits 2 without a TPU.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", default=None,
                    choices=("state_unchanged", "half_batch",
                             "token_altered"))
    args = ap.parse_args(argv)

    import jax
    from bench import correct, harness
    from bench.peaks import peaks_for
    from bench.spec import load_cell
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    cell = load_cell(args.workload)
    cell.peaks = peaks_for(devices[0].device_kind)
    if args.fault:
        from bench.faults import plant
        plant(args.fault)
    readings = {}
    check = correct.check

    def check_and_control(cell, params, seqs, seed):
        verdict = check(cell, params, seqs, seed)
        picked = correct.sample(seqs, seed, cell.mix.get("check_tokens",
                                                          600))
        t = time.perf_counter()
        readings.update(
            program=verdict["compared"]["max_logit_gap"]["value"],
            tokens=verdict["compared"]["tokens_compared"]["value"])
        if args.fault:
            readings["fault"] = args.fault
        else:
            readings.update(
                control=correct.control_gap(params, cell.config, picked),
                control_s=time.perf_counter() - t)
        return verdict

    correct.check = check_and_control
    start = PROC_START
    for seed in args.seeds:
        readings.clear()
        res = harness.run(cell, seed, args.seconds, False, start,
                          devices=devices[:1])
        print(json.dumps({"seed": seed, **readings,
                          "correct": res["correct"],
                          "metrics": {k: v["value"] for k, v in
                                      res["metrics"].items()}}), flush=True)
        start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
