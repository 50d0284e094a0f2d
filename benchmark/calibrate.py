"""Readings the check's limits are set from (not part of a benchmark run).

    python3 benchmark/calibrate.py --workload <cell> --seeds 12 --controls 3 --seconds 4

For each of ``--seeds`` seeds, in one process: the cell's set-up, a short
window at the cell's own load and the check, which gives the program's
reading of every compared number; for the first ``--controls`` seeds also
each of the driver's controls (its reference in a precision below the
configuration's float32, put in the program's place), read by the same
check.  Prints one JSON line per seed and a summary.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=1_000_003)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--allow-cpu", action="store_true", help="tests only")
    args = ap.parse_args(argv)
    for p in (str(BENCH), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import harness

    readings = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        cell, _, driver = harness.prepare(ROOT, args.workload, seed, args.seconds,
                                          args.allow_cpu)
        state = driver.setup(cell)
        win = driver.window(state, args.seconds)
        driver.release(state)
        row = {"seed": seed, "attempted": win.attempted, "program": driver.check(state, win)}
        if i < args.controls:
            row["controls"] = {
                name: driver.check(state, dataclasses.replace(win, outputs=low))
                for name, low in driver.controls(state, win).items()}
        print(json.dumps(row), flush=True)
        readings.append(row)
    summary = {k: {"program_max": max(r["program"][k][0] for r in readings),
                   "program_min": min(r["program"][k][0] for r in readings),
                   "controls_min": {c: min(r["controls"][c][k][0] for r in readings
                                           if "controls" in r)
                                    for c in readings[0].get("controls", {})}}
               for k in readings[0]["program"]}
    print(json.dumps({"workload": args.workload, "seeds": len(readings), "summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
