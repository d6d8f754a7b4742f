#!/usr/bin/env python3
"""CI benchmark-regression gate over the BENCH_*.json smoke artifacts.

CI has always *uploaded* BENCH_kernels.json / BENCH_serve.json but never
checked them, so a perf regression in the paper's headline A/B (dense-bias
vs FlashBias factored-bias attention) or in serve decode throughput would
merge silently. This script fails the job when a gated metric drops more
than ``--tolerance`` (default 30%) below its committed baseline:

1. kernels: ``dense_vs_factored.speedup`` from BENCH_kernels.json — a
   dimensionless ratio of two jitted paths timed on the same machine, so
   it transfers across runner hardware far better than absolute timings
   (still within ~±20%: commit the low end of observed values).
2. serve: contiguous decode tokens/s at the highest measured occupancy
   from BENCH_serve.json ``points``. This is an absolute number: after a
   runner-hardware change, refresh the committed value (see below).
3. serve: ``lazy_vs_whole.ratio`` — lazy page growth must sustain
   whole-request-reservation decode throughput at occupancy 4. The two
   engines are timed interleaved (same load profile), so this ratio is
   noise-robust and needs no baseline.
4. serve: ``chunked_prefill.ratio`` — p99 decode-step latency under a
   concurrent long-prompt arrival, whole-prompt admission over chunked
   (ISSUE 7). Gated at a FIXED structural floor of 1.2 (not
   tolerance-scaled): chunked admission amortizing the arrival sits > 2,
   a degeneration into a monolithic prefill stall sits ~1.0.
5. serve: ``prefix_sharing.ratio`` — shared-prefix admission throughput,
   prefix cache on over off, at the 64-requests x 512-token-prefix point
   (ISSUE 9). Gated at a FIXED structural floor of 2.0: page sharing
   deletes ~8/9 of the prefill compute there (> 3 observed), while an
   admission path that silently stops matching sits ~1.0.
6. serve: ``guard_overhead.ratio`` — decode throughput with the ISSUE 10
   non-finite emission guards on over off. Gated at a FIXED floor of
   0.95: default-on fault containment may cost at most 5% of decode
   throughput. Interleaved, so no baseline is needed.
7. neural (``--neural``, opt-in): the Table 6 Pairformer inference A/B
   from BENCH_neural.json — dense-path time / FlashBias-neural-path time,
   a same-machine ratio gated against a committed conservative baseline
   (the neural path ran ungated since the bench landed, so a factor-MLP
   regression would have merged silently).
8. pairformer (``--pairformer``, opt-in): the ISSUE 6 batched-serve A/B
   from BENCH_pairformer.json. Two gates: the headline
   ``factored_vs_dense.ratio`` (factored factor-cache step vs the official
   recompute-from-z dataflow, interleaved, >= 1.0 within tolerance — the
   paper's Sec. 4.4 claim) and ``cached_ratio`` (factored vs the cached
   dense-bias variant) against a committed baseline as a factored-path
   regression tripwire.

Every loaded BENCH file is schema-validated first (``SCHEMAS``): each gate
reads a fixed key path, and a bench that silently stops emitting one — a
renamed field, an empty sweep — fails the run immediately instead of
passing vacuously. The opt-in gates only run when their flag is passed
(CI passes them explicitly); default invocations keep the core kernels +
serve gates.
``--serve-only`` drops the kernels gate entirely — the mesh-serve CI job
runs the serve bench without a kernels sweep artifact.

Note on the kernels headline: ``dense_vs_factored`` is the LARGEST point
of the seq-length sweep (``dense_vs_factored_sweep``) — the paper-scale
regime where bias IO dominates. Gating a small-N point would gate the
regime where the factored path legitimately loses.

Baselines live in ``benchmarks/baselines/*.baseline.json``. Refresh them
from the current BENCH files with::

    python scripts/check_bench.py --update-baseline

Run the smoke benchmarks first, on a quiet machine (or the CI runner class
the gate will run on), and eyeball the diff before committing: a baseline
captured during a load burst weakens the gate; one captured on faster
hardware than CI will flake it. The committed serve baseline is
deliberately conservative (low end of observed) — the gate exists to catch
integer-factor regressions (e.g. a factored path silently materializing
the dense bias), not 10% drift on shared runners.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

DEFAULT_BASELINE_DIR = os.path.join("benchmarks", "baselines")
KERNELS_BASELINE = "BENCH_kernels.baseline.json"
SERVE_BASELINE = "BENCH_serve.baseline.json"
NEURAL_BASELINE = "BENCH_neural.baseline.json"
PAIRFORMER_BASELINE = "BENCH_pairformer.baseline.json"


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


# Required key paths per BENCH suite, validated up front: "a.b" descends
# dicts, "a[].b" requires the key on every element of a non-empty list,
# "rows[name=X].k" requires a row dict named X carrying k. The gates below
# read exactly these paths — a bench refactor that silently drops one
# (renames "ratio", stops emitting sweep points) must fail the gate
# loudly at load time, not pass vacuously or die in a KeyError mid-check.
SCHEMAS: dict[str, tuple[str, ...]] = {
    "kernels": (
        "dense_vs_factored.speedup",
        "dense_vs_factored_sweep",
    ),
    "serve": (
        "points[].occupancy",
        "points[].decode_tokens_per_s",
        "lazy_vs_whole.ratio",
        "chunked_prefill.ratio",
        "prefix_sharing.ratio",
        "prefix_sharing.hit_rate",
        "guard_overhead.ratio",
    ),
    "neural": (
        "rows[name=table6_infer_dense_pairbias].us_per_call",
        "rows[name=table6_infer_flashbias_neural].us_per_call",
    ),
    "pairformer": (
        "factored_vs_dense.n_res",
        "factored_vs_dense.ratio",
        "factored_vs_dense.cached_ratio",
    ),
}


def _step_into(nodes: list, step: str) -> list | None:
    """Resolve one path step against every current node; None = missing."""
    out: list = []
    for node in nodes:
        if step.endswith("[]"):
            items = node.get(step[:-2]) if isinstance(node, dict) else None
            if not isinstance(items, list) or not items:
                return None
            out.extend(items)
        elif "[name=" in step:
            key, _, sel = step.partition("[name=")
            sel = sel.rstrip("]")
            items = node.get(key) if isinstance(node, dict) else None
            rows = [
                r
                for r in (items if isinstance(items, list) else [])
                if isinstance(r, dict) and r.get("name") == sel
            ]
            if not rows:
                return None
            out.extend(rows)
        else:
            if not isinstance(node, dict) or step not in node:
                return None
            out.append(node[step])
    return out


def schema_errors(suite: str, bench: dict) -> list[str]:
    """Which required key paths of ``suite`` are missing from ``bench``."""
    errors = []
    for path in SCHEMAS[suite]:
        nodes: list | None = [bench]
        for step in path.split("."):
            nodes = _step_into(nodes, step)
            if nodes is None:
                errors.append(f"{suite}: missing required key path '{path}'")
                break
    return errors


def kernels_speedup(bench: dict) -> float:
    """Factored-vs-dense speedup of the same attention workload."""
    return float(bench["dense_vs_factored"]["speedup"])


def serve_decode_point(bench: dict) -> tuple[int, float]:
    """(occupancy, decode tokens/s) of the highest-occupancy point."""
    point = max(bench["points"], key=lambda p: p["occupancy"])
    return int(point["occupancy"]), float(point["decode_tokens_per_s"])


def lazy_vs_whole_ratio(bench: dict) -> float:
    """Interleaved lazy/whole decode throughput ratio (ISSUE 4)."""
    return float(bench["lazy_vs_whole"]["ratio"])


def chunked_prefill_ratio(bench: dict) -> float:
    """Interleaved whole/chunked p99 decode-step latency ratio (ISSUE 7):
    tail latency under concurrent long-prompt admission. >> 1 when
    chunked prefill amortizes the arrival, ~1.0 when it degenerates into
    a monolithic prefill stall."""
    return float(bench["chunked_prefill"]["ratio"])


def prefix_sharing_ratio(bench: dict) -> float:
    """Interleaved cached/uncached shared-prefix admission throughput
    ratio (ISSUE 9): >= 2 when prefix hits skip the shared pages'
    prefill chunks, ~1.0 when admission stops matching."""
    return float(bench["prefix_sharing"]["ratio"])


def guard_overhead_ratio(bench: dict) -> float:
    """Interleaved guarded/unguarded decode throughput ratio (ISSUE 10):
    ~1.0 when the non-finite emission guard stays amortized behind the
    commit sync, below the floor when guarding starts costing real
    decode throughput."""
    return float(bench["guard_overhead"]["ratio"])


def neural_speedup(bench: dict) -> float:
    """Dense-path / FlashBias-neural-path time of the Table 6 inference
    A/B (same machine, same call) from the BENCH_neural row dump."""
    rows = {r["name"]: r for r in bench["rows"]}
    dense = float(rows["table6_infer_dense_pairbias"]["us_per_call"])
    flash = float(rows["table6_infer_flashbias_neural"]["us_per_call"])
    return dense / flash


def pairformer_headline(bench: dict) -> dict:
    """Largest-n_res factored-vs-dense point of the batched-serve sweep
    (ISSUE 6): ``ratio`` vs the official recompute dataflow, gated at
    1.0; ``cached_ratio`` vs the cached dense bias, gated on baseline."""
    return bench["factored_vs_dense"]


def check(
    name: str,
    current: float,
    floor: float,
    detail: str,
    failures: list,
) -> None:
    status = "ok" if current >= floor else "FAIL"
    print(f"[{status}] {name}: {current:.3f} (floor {floor:.3f}; {detail})")
    if current < floor:
        failures.append(name)


def update_baselines(
    kernels: dict,
    serve: dict,
    baseline_dir: str,
    neural: dict | None = None,
    pairformer: dict | None = None,
) -> None:
    os.makedirs(baseline_dir, exist_ok=True)
    occ, tps = serve_decode_point(serve)
    payloads = {
        KERNELS_BASELINE: {"speedup": kernels_speedup(kernels)},
        SERVE_BASELINE: {"occupancy": occ, "decode_tokens_per_s": tps},
    }
    if neural is not None:
        payloads[NEURAL_BASELINE] = {"speedup": neural_speedup(neural)}
    if pairformer is not None:
        payloads[PAIRFORMER_BASELINE] = {
            "cached_ratio": float(pairformer_headline(pairformer)["cached_ratio"])
        }
    for fname, payload in payloads.items():
        path = os.path.join(baseline_dir, fname)
        with open(path, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"wrote {path}: {payload}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--kernels", default="BENCH_kernels.json")
    ap.add_argument("--serve", default="BENCH_serve.json")
    ap.add_argument(
        "--neural",
        default=None,
        help="BENCH_neural.json path; enables the Table 6 speedup gate",
    )
    ap.add_argument(
        "--pairformer",
        default=None,
        help="BENCH_pairformer.json path; enables the batched-serve gates",
    )
    ap.add_argument(
        "--serve-only",
        action="store_true",
        help="gate only the BENCH_serve.json metrics (the mesh-serve CI "
        "job runs the serve bench without the kernels sweep)",
    )
    ap.add_argument("--baseline-dir", default=DEFAULT_BASELINE_DIR)
    ap.add_argument(
        "--tolerance",
        type=float,
        default=0.30,
        help="allowed fractional drop below baseline (default 0.30)",
    )
    ap.add_argument(
        "--update-baseline",
        action="store_true",
        help="rewrite the committed baselines from the current BENCH files",
    )
    args = ap.parse_args(argv)

    kernels = None if args.serve_only else _load(args.kernels)
    serve = _load(args.serve)
    neural = _load(args.neural) if args.neural else None
    pairformer = _load(args.pairformer) if args.pairformer else None

    suites = (
        ("kernels", kernels),
        ("serve", serve),
        ("neural", neural),
        ("pairformer", pairformer),
    )
    schema_failures = [
        err
        for suite, bench in suites
        if bench is not None
        for err in schema_errors(suite, bench)
    ]
    if schema_failures:
        for err in schema_failures:
            print(f"[FAIL] schema: {err}", file=sys.stderr)
        print(
            "BENCH schema validation FAILED: a bench stopped emitting a "
            "gated key — fix the bench (or the schema, if the rename is "
            "intentional) before trusting any gate below",
            file=sys.stderr,
        )
        return 1

    if args.update_baseline:
        assert kernels is not None, "--update-baseline needs the kernels file"
        update_baselines(
            kernels, serve, args.baseline_dir, neural=neural, pairformer=pairformer
        )
        return 0

    sb = _load(os.path.join(args.baseline_dir, SERVE_BASELINE))
    band = 1.0 - args.tolerance
    failures: list = []

    if kernels is not None:
        kb = _load(os.path.join(args.baseline_dir, KERNELS_BASELINE))
        check(
            "kernels dense-vs-factored speedup",
            kernels_speedup(kernels),
            band * float(kb["speedup"]),
            f"baseline {float(kb['speedup']):.3f}, tol {args.tolerance:.0%}",
            failures,
        )
    occ, tps = serve_decode_point(serve)
    if occ != int(sb["occupancy"]):
        print(
            f"[FAIL] serve occupancy mismatch: bench measured occupancy "
            f"{occ}, baseline holds occupancy {sb['occupancy']} — not "
            "comparable; re-run --update-baseline after changing the "
            "bench occupancies",
            file=sys.stderr,
        )
        failures.append("serve occupancy mismatch")
    check(
        f"serve decode tok/s @ occupancy {occ}",
        tps,
        band * float(sb["decode_tokens_per_s"]),
        f"baseline {float(sb['decode_tokens_per_s']):.1f} @ occupancy "
        f"{sb['occupancy']}, tol {args.tolerance:.0%}",
        failures,
    )
    check(
        "serve lazy-vs-whole decode ratio",
        lazy_vs_whole_ratio(serve),
        band,
        f"interleaved A/B, no baseline, tol {args.tolerance:.0%}",
        failures,
    )
    # fixed structural floor, NOT tolerance-scaled: the ratio sits > 2
    # when chunked admission amortizes the long-prompt stall and ~1.0
    # when it degenerates into a monolithic prefill — the gate separates
    # those regimes, it does not band a drifting measurement
    check(
        "serve chunked-prefill p99 stall ratio",
        chunked_prefill_ratio(serve),
        1.2,
        "interleaved A/B, structural floor 1.2",
        failures,
    )
    # fixed structural floor like chunked_prefill: sharing working sits
    # > 3 at the 64 x 512 point, admission silently not matching ~1.0
    check(
        "serve shared-prefix admission ratio",
        prefix_sharing_ratio(serve),
        2.0,
        "interleaved A/B, structural floor 2.0",
        failures,
    )
    # fixed floor: the guards must cost <= 5% decode throughput (the
    # price of default-on fault containment), independent of the runner
    # tolerance band — a noisy runner cancels out of the interleaved A/B
    check(
        "serve guarded-vs-unguarded decode ratio",
        guard_overhead_ratio(serve),
        0.95,
        "interleaved A/B, fixed floor 0.95 (guards cost <= 5%)",
        failures,
    )
    if neural is not None:
        nb = _load(os.path.join(args.baseline_dir, NEURAL_BASELINE))
        check(
            "neural dense-vs-flashbias inference speedup",
            neural_speedup(neural),
            band * float(nb["speedup"]),
            f"baseline {float(nb['speedup']):.3f}, tol {args.tolerance:.0%}",
            failures,
        )
    if pairformer is not None:
        head = pairformer_headline(pairformer)
        check(
            f"pairformer factored-vs-dense serve-step ratio "
            f"@ n_res {head['n_res']}",
            float(head["ratio"]),
            band,
            f"interleaved A/B vs official recompute path, no baseline, "
            f"tol {args.tolerance:.0%}",
            failures,
        )
        pb = _load(os.path.join(args.baseline_dir, PAIRFORMER_BASELINE))
        check(
            f"pairformer factored-vs-cached-bias ratio @ n_res "
            f"{head['n_res']}",
            float(head["cached_ratio"]),
            band * float(pb["cached_ratio"]),
            f"baseline {float(pb['cached_ratio']):.3f}, "
            f"tol {args.tolerance:.0%}",
            failures,
        )

    if failures:
        print(f"benchmark regression gate FAILED: {failures}", file=sys.stderr)
        print(
            "If this is expected (new runner hardware, intentional trade), "
            "refresh with: python scripts/check_bench.py --update-baseline",
            file=sys.stderr,
        )
        return 1
    print("benchmark regression gate passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
