#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is a ``workloads`` entry of ``BENCHMARK.json``. The run makes the
configuration's weights on the chip from the seed, warms every program the
cell's traffic reaches, serves the traffic mix through the program's serve
engine (a ramp, then ``--seconds`` of measured window), then frees the
program and compares what it served with the plain reference.

Standard output: diagnostics, then as its last line one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics read from a profiler
trace of the window), ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``: each number compared with the reference beside its limit.
Standard error ends with the same numbers. With no TPU, too few chips, or a
device kind missing from ``bench/peaks.json`` the run exits 2 and prints no
result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import common, loop  # noqa: E402
from bench import trace as tr  # noqa: E402
from bench.common import FAILED, BenchError  # noqa: E402


def say(msg: str) -> None:
    print(msg, flush=True)


def cell_files(name: str) -> tuple:
    bench = common.benchmark()
    cell = common.find(bench["workloads"], name, "workload")
    cfg = common.load_json(common.named_file("configs", cell["config"]))
    mix = common.load_json(common.named_file("traffic", cell["traffic"]))
    limits = common.load_json(common.named_file("limits", name))
    return bench, cell, cfg, mix, limits


def cell_metrics(bench: dict, name: str, traced: bool) -> list:
    """The metrics a run of cell ``name`` reports: its end-to-end metrics,
    or with a trace the per-layer metrics that list it (or, with no
    ``workloads`` key, that move an end-to-end metric it reports)."""
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    if not traced:
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]


class Profiler:
    """The profiler around the window, with a ``bench.window`` span the
    reduction uses as the window's edges on the trace clock."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.span = None

    def open(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self.span = jax.profiler.TraceAnnotation(tr.WINDOW_SPAN)
        self.span.__enter__()

    def close(self) -> None:
        import jax
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()


def memory_peak(chips: int) -> int:
    import jax
    peaks = []
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run_cell(workload: str, seed: int, seconds: float, traced: bool,
             control: bool = False, keep_trace: str = None) -> dict:
    """One run of one cell on the chip; returns the result object. With
    ``control`` it also holds, under ``control``, the same comparison made
    of the control (the reference in the precision below the one the
    configuration states) put in the program's place. ``keep_trace``
    names a file the traced window's ``.xplane.pb`` is copied to."""
    return run_loaded(*cell_files(workload), seed, seconds, traced, control,
                      keep_trace=keep_trace)


def run_loaded(bench: dict, cell: dict, cfg: dict, mix: dict, limits: dict,
               seed: int, seconds: float, traced: bool, control: bool = False,
               device: dict = None, keep_trace: str = None) -> dict:
    """``run_cell`` on files already read. ``device`` given skips the look
    for a chip: the self-tests drive the rest of a run on the CPU so."""
    workload = cell["name"]
    if device is None:
        device = common.check_device(cell["chips"])
        say(f"[setup] compile cache: {common.enable_compile_cache()}")
    peak = common.peaks(device["kind"])
    compiles = common.compile_log()
    family = common.load_module("families", cfg["family"])
    session = family.Cell(cfg, mix, seed, limits, log=say)
    session.setup()
    say(f"[setup] programs obtained so far: {compiles.count} "
        f"({compiles.cache_hits} from the persistent cache)")

    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    prof = Profiler(log_dir) if traced else None
    n0 = compiles.count
    window = loop.drive(session, mix, seed, seconds,
                        on_open=prof.open if prof else None,
                        on_close=prof.close if prof else None)
    setup_s = window.t0 - T_START
    late = compiles.since(n0)
    say(f"[window] {window.seconds:.6f} s, {len(window.window_steps())} "
        f"engine steps; programs compiled or loaded inside it: {len(late)}"
        f"{' ' + str(sorted(set(late))) if late else ''}")
    statuses = {}
    for r in window.records:
        statuses[r.status or "IN_FLIGHT"] = statuses.get(
            r.status or "IN_FLIGHT", 0) + 1
    say(f"[window] requests by status (ramp included): {statuses}")
    device = dict(device, memory_peak_bytes=memory_peak(device["count"]))

    ctx = common.Context(cell, cfg, mix, peak, device["count"], window,
                         setup_s)
    result = {}
    if traced:
        ctx.trace = tr.load(log_dir, keep=keep_trace)
        shutil.rmtree(log_dir, ignore_errors=True)
        device["busy_s"] = tr.busy_s(ctx.trace)
        device["window_s"] = ctx.trace.window_s
        result["breakdown"] = tr.breakdown(ctx.trace)
    metrics = {}
    for m in cell_metrics(bench, workload, traced):
        value = common.load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    for line in ctx.notes:
        say(f"[metric] {line}")

    session.finish(window)
    ctx.trace = None
    gc.collect()
    failed = sum(1 for r in window.records if r.status in FAILED)
    out = {"attempted": len(window.records), "failed": failed,
           "metrics": metrics, "device": device,
           "compiles_in_window": len(late)}
    out.update(result)
    if control:
        out["control"] = judged(session.check(control=True))
    out.update(judged(session.check()))
    return out


def judged(checks: dict) -> dict:
    """``correct`` (every number within its limit) and the numbers beside
    their limits, under ``checks``."""
    return {"correct": all(v <= lim for v, lim in checks.values()),
            "checks": {k: {"value": v, "limit": lim}
                       for k, (v, lim) in checks.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
