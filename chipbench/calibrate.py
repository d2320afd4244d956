"""Readings for the check's limits: the program's and the control's.

    python3 chipbench/calibrate.py --workload <cell> --seconds <s> \
        --seeds 1 2 3 [--out chiprun_out/calib.jsonl]

For each seed, in one process: one run of the cell with a short window,
its numbers compared (the program's readings), then the control, the
reference in bfloat16 put in the program's place on the same rows (the
control's readings).  One JSON line per seed.  The benchmark's own runs
never run the control.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT)]

from chipbench import run as entry  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    entry.configure()
    from chipbench import harness
    cell = harness.load_cell(args.workload)
    for seed in args.seeds:
        line = harness.run(cell, seed, args.seconds, False,
                           started=time.perf_counter(), control=True)
        row = {"workload": args.workload, "seed": seed,
               "correct": line["correct"],
               "program": {k: v["value"] for k, v in line["check"].items()},
               "control": line["control"],
               "images_per_s": line["metrics"]["images_per_s"]["value"],
               "setup_s": line["metrics"]["setup_s"]["value"]}
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
