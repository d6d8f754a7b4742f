#!/usr/bin/env python3
"""Read the numbers a cell's limits are set from, on the chip.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 --seconds <s>

Runs the cell once per seed in one process (so set-up compiles once), each
run with its window, and after it the comparison of what the program served
with the plain reference and, in the same way, of the control (the reference
in float8, the precision below the bfloat16 the configurations state) put in
the program's place. Both are held to the cell's limits, so the control's
``correct`` has to read false. Prints one JSON line per seed. The
benchmark's own runs never run the control; the limits in ``bench/limits/``
and their readings in ``PERF.md`` come from this.
"""
import argparse
import json
import os
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            out = run.run_cell(args.workload, seed, args.seconds, False,
                               control=True)
        except run.BenchError as e:
            print(f"bench: {e}", file=sys.stderr, flush=True)
            return 2
        print(json.dumps({"seed": seed, "metrics": out["metrics"],
                          "memory_peak_bytes":
                              out["device"]["memory_peak_bytes"],
                          "correct": out["correct"], "checks": out["checks"],
                          "control": out["control"]}), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
