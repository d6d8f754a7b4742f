#!/usr/bin/env python3
"""Record one traced run of a cell on the chip and describe its trace.

    python3 bench/record_trace.py --workload <cell> --seed <n> \\
        --seconds <s> --out <file.xplane.pb>

Runs the cell as ``run.py --trace 1`` does, keeps the window's
``.xplane.pb`` at ``--out``, and prints what a reader of the trace needs
to see by hand before writing patterns against it: every plane and line,
and on each device plane the operation names (numbers stripped) with their
count, time, program and stats. The result line of the run comes last.
The self-tests' recorded trace (``tests/bench/data/``) is made so.
"""
import argparse
import collections
import json
import os
import re
import sys

import run


def describe(path: str) -> None:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = {line.name: list(line.events) for line in plane.lines}
        print(f"[plane] {plane.name}: " + ", ".join(
            f"{k} ({len(v)})" for k, v in lines.items()), flush=True)
        if not plane.name.startswith("/device:"):
            continue
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for e in lines.get(run.tr.MODULES_LINE, []))
        kinds = collections.OrderedDict()
        for e in lines.get(run.tr.OPS_LINE, []):
            mod = next((m[2] for m in mods if m[0] <= e.start_ns <= m[1]),
                       "")
            key = (re.sub(r"[(.]\d+\)?$", "", mod),
                   re.sub(r"[.\d]+$", "", e.name))
            k = kinds.setdefault(key, {"n": 0, "ns": 0, "name": e.name,
                                       "stats": None})
            k["n"] += 1
            k["ns"] += e.duration_ns
            if k["stats"] is None:
                try:
                    k["stats"] = [(n, str(v)[:160]) for n, v in e.stats]
                except Exception as err:       # opaque stats
                    k["stats"] = [("unreadable", repr(err))]
        for (mod, op), k in sorted(kinds.items(), key=lambda kv: -kv[1]["ns"]):
            print(f"[op] {mod} {op} x{k['n']} {k['ns'] * 1e-9:.6f} s "
                  f"e.g. {k['name']} stats {k['stats']}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    try:
        out = run.run_cell(args.workload, args.seed, args.seconds, True,
                           keep_trace=args.out)
    except run.BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    describe(args.out)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
