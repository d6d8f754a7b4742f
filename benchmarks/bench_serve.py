"""Serve-engine benchmark: prefill tokens/s + decode tokens/s vs occupancy.

Measures the continuous-batching engine end to end (admission prefill,
jitted slot-batch decode, sampling, host loop) at several slot
occupancies, and writes ``BENCH_serve.json`` — the first entry of the
serving perf trajectory. One engine serves every occupancy (pinned
``prefill_len`` + n_slots-padded waves mean one compiled prefill program),
so timings are warm after the first throwaway wave.

CPU container caveat (benchmarks/common.py): numbers are relative A/B
trends between occupancies, NOT TPU performance.

    PYTHONPATH=src python -m benchmarks.bench_serve [--smoke] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from benchmarks.common import Row

DEFAULT_OUT = "BENCH_serve.json"


def collect(arch: str = "stablelm_12b", n_slots: int = 8,
            prompt_len: int = 32, steps: int = 12,
            occupancies=(1, 4, 8), page_size: int = 0,
            page_reservation: str = "lazy") -> dict:
    """Run the engine at each occupancy; returns the BENCH_serve payload.

    ``page_size`` > 0 measures the PAGED engine (pool sized to the same HBM
    as the contiguous layout, table width = one contiguous segment so the
    per-step logical view matches). ``page_reservation`` picks the
    admission policy of the paged engine: ``"whole"`` reserves a request's
    full footprint at admit (PR-3, emitted as ``paged_points``), ``"lazy"``
    reserves only prompt pages and grows per page boundary, preempting on
    pool exhaustion (ISSUE 4, emitted as ``lazy_points``).
    """
    from repro.configs import smoke_config
    from repro.models import get_model
    from repro.models.common import init_params
    from repro.serve import ServeEngine

    cfg = smoke_config(arch)
    model = get_model(cfg)
    params = init_params(model.template(), jax.random.PRNGKey(0))
    budget = steps + 4                       # never finishes mid-measurement
    max_len = prompt_len + budget + 8
    kw = {}
    if page_size:
        max_len = -(-max_len // page_size) * page_size
        kw = {"page_size": page_size,
              "pages_per_slot": max_len // page_size,
              "page_reservation": page_reservation}
    engine = ServeEngine(model, params, max_len=max_len,
                         n_slots=n_slots, prefill_len=prompt_len, **kw)
    rng = np.random.default_rng(0)

    def submit(n):
        return [engine.submit(
            rng.integers(0, cfg.vocab, (prompt_len,)).astype(np.int32),
            budget) for _ in range(n)]

    # throwaway wave: compiles prefill/insert/decode/sample once
    submit(1)
    engine.run()

    result = {"arch": cfg.name, "n_slots": n_slots,
              "prompt_len": prompt_len, "decode_steps": steps, "points": []}
    for occ in occupancies:
        assert occ <= n_slots, (occ, n_slots)
        submit(occ)
        t0 = time.monotonic()
        engine.admit()
        t_admit = time.monotonic() - t0
        engine.decode(); engine.decode()     # decode warmup (already jitted)
        ts = []
        for _ in range(steps):
            t0 = time.monotonic()
            engine.decode()                  # _sample_and_commit syncs
            ts.append(time.monotonic() - t0)
        t_step = min(ts)                     # best observed step: on a
        engine.run()                         # contended CPU runner this is
        result["points"].append({            # the only stable estimate the
            "occupancy": occ,                # CI regression gate can band
            "prefill_tokens_per_s": occ * prompt_len / t_admit,
            "decode_tokens_per_s": occ / t_step,
        })
    if page_size:
        result["page_stats"] = engine.page_stats()
    return result


def _interleaved_decode_ab(engines: dict, vocab: int, prompt_len: int,
                           steps: int, occupancy: int) -> tuple:
    """Shared harness for one-occupancy interleaved decode A/Bs.

    Both engines serve the identical workload and alternate timed decode
    steps, so both see the same machine-load profile — the ratio stays
    meaningful on a noisy CPU runner where two back-to-back ``collect``
    calls can land in different load bursts. Min-based timing per engine
    (the only load-robust estimator on a shared runner). ONE harness
    serves every A/B gate, so a methodology change (warmup count,
    estimator, drain) can never skew one gated ratio and not the other.

    Returns ``(tokens_per_s, outputs)``: dicts keyed like ``engines``,
    with each engine's best-step throughput and per-request output arrays.
    """
    budget = steps + 4
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, vocab, (prompt_len,)).astype(np.int32)
               for _ in range(occupancy)]
    best, rids = {}, {}
    for mode, eng in engines.items():
        rids[mode] = [eng.submit(p, budget) for p in prompts]
        eng.admit()
        eng.decode(); eng.decode()           # warm (compile + first growth)
        best[mode] = float("inf")
    for _ in range(steps):                   # interleave: same load profile
        for mode, eng in engines.items():
            t0 = time.monotonic()
            eng.decode()
            best[mode] = min(best[mode], time.monotonic() - t0)
    for eng in engines.values():
        eng.run()
    tps = {mode: occupancy / t for mode, t in best.items()}
    outs = {mode: [engines[mode].result(r) for r in rids[mode]]
            for mode in engines}
    return tps, outs


def compare_lazy_whole(arch: str = "stablelm_12b", n_slots: int = 4,
                       prompt_len: int = 16, steps: int = 16,
                       occupancy: int = 4, page_size: int = 16) -> dict:
    """Interleaved lazy-vs-whole A/B at one occupancy (ISSUE 4 headline).

    The CI gate (scripts/check_bench.py) holds ``ratio`` to a tolerance
    band: lazy growth must sustain whole-request-reservation throughput.
    Timing methodology: ``_interleaved_decode_ab``.
    """
    from repro.configs import smoke_config
    from repro.models import get_model
    from repro.models.common import init_params
    from repro.serve import ServeEngine

    cfg = smoke_config(arch)
    model = get_model(cfg)
    params = init_params(model.template(), jax.random.PRNGKey(0))
    max_len = -(-(prompt_len + steps + 12) // page_size) * page_size
    engines = {}
    for mode in ("whole", "lazy"):
        engines[mode] = ServeEngine(
            model, params, max_len=max_len, n_slots=n_slots,
            prefill_len=prompt_len, page_size=page_size,
            pages_per_slot=max_len // page_size, page_reservation=mode)
    tps, _ = _interleaved_decode_ab(engines, cfg.vocab, prompt_len, steps,
                                    occupancy)
    return {"occupancy": occupancy, "page_size": page_size,
            "whole_decode_tokens_per_s": tps["whole"],
            "lazy_decode_tokens_per_s": tps["lazy"],
            "ratio": tps["lazy"] / tps["whole"]}


def compare_chunked_prefill(arch: str = "stablelm_12b", n_slots: int = 4,
                            prompt_len: int = 16, long_prompt: int = 192,
                            steps: int = 40, chunk: int = 16,
                            rounds: int = 3) -> dict:
    """Decode-step tail latency under concurrent long-prompt admission
    (ISSUE 7 headline A/B).

    Two engines serve the identical workload: ``n_slots - 1`` short
    requests decoding, and ``rounds`` long prompts arriving mid-run. The
    whole-prompt engine stalls every in-flight decode for a full
    ``long_prompt`` prefill in each step that admits one; the chunked
    engine amortizes the same prompts ``chunk`` tokens per step,
    interleaved with decode. Both engines' ``step()`` latencies are timed
    interleaved (same load profile); the gated metric is

        ratio = whole_p99 / chunked_p99

    — structurally >> 1 when chunking amortizes (the whole engine's tail
    IS its prefill stall) and ~1.0 if chunked admission ever degenerates
    into a monolithic prefill, which is exactly the regression the CI
    gate (scripts/check_bench.py) exists to catch. The tail estimator is
    the ``rounds``-th largest step: the whole engine stalls once per
    arrival round so one stall always survives the trim, while up to
    ``rounds - 1`` transient host hiccups in either engine's samples are
    discarded (max has no noise immunity; min would erase the signal).
    Compile warmup runs the full arrival pattern once per engine first,
    so no measured step is a jit compile.
    """
    from repro.configs import smoke_config
    from repro.models import get_model
    from repro.models.common import init_params
    from repro.serve import ServeEngine

    cfg = smoke_config(arch)
    model = get_model(cfg)
    params = init_params(model.template(), jax.random.PRNGKey(0))
    max_len = long_prompt + steps + 16
    engines = {
        "whole": ServeEngine(model, params, max_len=max_len,
                             n_slots=n_slots),
        "chunked": ServeEngine(model, params, max_len=max_len,
                               n_slots=n_slots, prefill_chunk=chunk),
    }
    budget = steps + 8
    spacing = max(1, steps // rounds)
    lat = {mode: [] for mode in engines}

    def submit_short(eng, rng):
        eng.submit(rng.integers(0, cfg.vocab,
                                (prompt_len,)).astype(np.int32), budget)

    def submit_long(eng, rng):
        eng.submit(rng.integers(0, cfg.vocab,
                                (long_prompt,)).astype(np.int32), 4)

    for eng in engines.values():             # compile warmup: full pattern
        rng = np.random.default_rng(0)
        for _ in range(n_slots - 1):
            submit_short(eng, rng)
        for _ in range(4):
            eng.step()
        submit_long(eng, rng)
        eng.run()

    # interleaved measurement: alternate per-step so both engines see the
    # same machine-load profile (a tail estimator has no min()-style
    # noise immunity, so load parity is what keeps the ratio meaningful)
    rngs = {m: np.random.default_rng(0) for m in engines}
    for mode, eng in engines.items():
        for _ in range(n_slots - 1):
            submit_short(eng, rngs[mode])
        for _ in range(4):                   # in-flight before arrivals
            eng.step()
    for i in range(steps):
        for mode, eng in engines.items():
            if i % spacing == 0 and i // spacing < rounds:
                submit_long(eng, rngs[mode])
            t0 = time.monotonic()
            eng.step()
            lat[mode].append(time.monotonic() - t0)
    for eng in engines.values():
        eng.run()

    def tail(xs):
        return float(sorted(xs)[-rounds])

    whole, chunked = tail(lat["whole"]), tail(lat["chunked"])
    return {"long_prompt": long_prompt, "chunk": chunk, "steps": steps,
            "rounds": rounds,
            "whole_p99_step_ms": 1e3 * whole,
            "chunked_p99_step_ms": 1e3 * chunked,
            "ratio": whole / chunked}


def compare_prefix_sharing(arch: str = "stablelm_12b", n_slots: int = 4,
                           n_requests: int = 64, shared_prefix: int = 512,
                           tail_len: int = 16, budget: int = 4,
                           page_size: int = 64) -> dict:
    """Shared-prefix admission throughput, prefix cache on vs off
    (ISSUE 9 headline A/B).

    ``n_requests`` requests share a ``shared_prefix``-token common prefix
    (system-prompt traffic) with short unique tails. Both engines are
    paged + chunked (chunk = page size); the cached engine maps each hit's
    page table onto the already-landed prefix pages and prefills ONLY the
    novel tail, so it retires the queue in ~1 chunk step per request where
    the uncached engine pays ``shared_prefix / page_size`` chunk steps
    each. Engines are stepped alternately until each drains, accumulating
    per-engine wall time — same load profile, per the
    ``_interleaved_decode_ab`` methodology (drain lengths differ, so this
    A/B times whole steps rather than reusing that harness). The gated
    metric is

        ratio = cached admission tokens/s / uncached admission tokens/s

    — structurally >= 2 when sharing works (the cache deletes ~8/9 of all
    prefill compute at the 64 x 512 point) and ~1.0 if admission ever
    stops matching, which is the regression the CI gate
    (scripts/check_bench.py) exists to catch. Both engines decode the
    same ``budget`` tokens per request, so decode work cancels in the
    ratio; outputs are compared and reported (``outputs_identical``) —
    the hard bit-parity contract lives in tests/test_prefix_cache.py.

    One compile warmup pair runs first THROUGH the cached engine's index
    (steady-state serving: the measured window starts with the prefix
    already resident, as every request after the first would see it).
    """
    from repro.configs import smoke_config
    from repro.models import get_model
    from repro.models.common import init_params
    from repro.serve import ServeEngine

    cfg = smoke_config(arch)
    model = get_model(cfg)
    params = init_params(model.template(), jax.random.PRNGKey(0))
    max_len = shared_prefix + tail_len + budget + 8
    max_len = -(-max_len // page_size) * page_size
    engines = {}
    for mode in ("uncached", "cached"):
        engines[mode] = ServeEngine(
            model, params, max_len=max_len, n_slots=n_slots,
            prefill_len=shared_prefix + tail_len, page_size=page_size,
            pages_per_slot=max_len // page_size,
            prefill_chunk=page_size, prefix_cache=(mode == "cached"))

    rng = np.random.default_rng(0)
    common = rng.integers(0, cfg.vocab, (shared_prefix,)).astype(np.int32)
    prompts = [np.concatenate([common, rng.integers(
        0, cfg.vocab, (int(rng.integers(1, tail_len + 1)),)
    ).astype(np.int32)]) for _ in range(n_requests)]

    for eng in engines.values():             # compile warmup; also lands
        eng.submit(prompts[0], budget)       # the prefix in the cached
        eng.run()                            # engine's index (steady state)

    times = {mode: 0.0 for mode in engines}
    rids = {mode: [eng.submit(p, budget) for p in prompts]
            for mode, eng in engines.items()}
    live = dict(engines)
    while live:                              # alternate whole steps: same
        for mode, eng in list(live.items()): # load profile for both drains
            t0 = time.monotonic()
            eng.step()
            times[mode] += time.monotonic() - t0
            if not (len(eng.scheduler) or eng.occupancy):
                del live[mode]
    n_tok = sum(p.size for p in prompts)
    tps = {mode: n_tok / t for mode, t in times.items()}
    outs = {mode: [engines[mode].result(r) for r in rids[mode]]
            for mode in engines}
    identical = all(np.array_equal(a, b)
                    for a, b in zip(outs["cached"], outs["uncached"]))
    if not identical:
        print("# WARNING: prefix-sharing A/B greedy outputs diverged — "
              "sharing must be bit-exact; see tests/test_prefix_cache.py")
    pf = engines["cached"].page_stats()["prefix"]
    return {"n_requests": n_requests, "shared_prefix": shared_prefix,
            "page_size": page_size, "n_slots": n_slots,
            "uncached_admission_tokens_per_s": tps["uncached"],
            "cached_admission_tokens_per_s": tps["cached"],
            "hit_rate": pf["hit_rate"],
            "cow_copies": pf["cow_copies"],
            "evictions": pf["evictions"],
            "outputs_identical": identical,
            "ratio": tps["cached"] / tps["uncached"]}


def compare_guard_overhead(arch: str = "stablelm_12b", n_slots: int = 4,
                           prompt_len: int = 16, steps: int = 16,
                           occupancy: int = 4, page_size: int = 16) -> dict:
    """Decode throughput with the ISSUE-10 non-finite emission guards on
    vs off (interleaved A/B at the headline decode config).

    The guard's row-max reduction is fused into the sampling dispatch
    (``sample_tokens_guarded``) and its result rides the same host
    transfer as the tokens, so the guarded path keeps one device
    round-trip per step. The CI gate (scripts/check_bench.py) holds

        ratio = guarded decode tokens/s / unguarded decode tokens/s

    to >= 0.95: fault containment must cost at most 5% of decode
    throughput, or it doesn't get to default on. Timing methodology:
    ``_interleaved_decode_ab``. Outputs are also compared — on a healthy
    run the guard never trips, so committed tokens must be identical.
    """
    from repro.configs import smoke_config
    from repro.models import get_model
    from repro.models.common import init_params
    from repro.serve import ServeEngine

    cfg = smoke_config(arch)
    model = get_model(cfg)
    params = init_params(model.template(), jax.random.PRNGKey(0))
    max_len = -(-(prompt_len + steps + 12) // page_size) * page_size
    engines = {}
    for mode, guards in (("unguarded", False), ("guarded", True)):
        engines[mode] = ServeEngine(
            model, params, max_len=max_len, n_slots=n_slots,
            prefill_len=prompt_len, page_size=page_size,
            pages_per_slot=max_len // page_size, guards=guards)
    tps, outs = _interleaved_decode_ab(engines, cfg.vocab, prompt_len,
                                       steps, occupancy)
    identical = all(np.array_equal(a, b)
                    for a, b in zip(outs["guarded"], outs["unguarded"]))
    if not identical:
        print("# WARNING: guard A/B outputs diverged — a guard tripped on "
              "healthy logits; see tests/test_faults.py")
    return {"occupancy": occupancy, "page_size": page_size,
            "unguarded_decode_tokens_per_s": tps["unguarded"],
            "guarded_decode_tokens_per_s": tps["guarded"],
            "outputs_identical": identical,
            "ratio": tps["guarded"] / tps["unguarded"]}


def run(out_path: str = DEFAULT_OUT, smoke: bool = False):
    """benchmarks/run.py entry: emit BENCH_serve.json + CSV rows."""
    kw = ({"n_slots": 4, "prompt_len": 16, "steps": 16,
           "occupancies": (1, 2, 4)}
          if smoke else {})
    data = collect(**kw)
    ps = 16 if smoke else 64
    data["page_size"] = ps
    whole = collect(page_size=ps, page_reservation="whole", **kw)
    lazy = collect(page_size=ps, page_reservation="lazy", **kw)
    data["paged_points"] = whole["points"]          # PR-3 whole-reservation
    data["lazy_points"] = lazy["points"]            # ISSUE-4 lazy growth
    data["lazy_page_stats"] = lazy["page_stats"]
    # the A/B pins page_size=16 regardless of the trajectory ps: with the
    # default prompt/steps a 64-token page is never outgrown, and an A/B
    # whose lazy engine never grows or preempts measures nothing
    data["lazy_vs_whole"] = compare_lazy_whole(
        **{k: v for k, v in kw.items() if k != "occupancies"},
        occupancy=max(kw.get("occupancies", (4,))))
    # ISSUE 7: decode-step tail latency under a concurrent long-prompt
    # arrival — whole-prompt admission stalls the batch for one full
    # prefill, chunked admission amortizes it one chunk per step. The
    # long prompt stays long even in smoke: the stall IS the measurement.
    data["chunked_prefill"] = compare_chunked_prefill(
        **{k: v for k, v in kw.items() if k not in ("occupancies", "steps")},
        steps=24 if smoke else 40,
        long_prompt=128 if smoke else 192)
    # ISSUE 10: decode throughput with non-finite emission guards on vs
    # off — containment must stay within 5% of the unguarded engine.
    # steps pinned to 64 regardless of smoke: the
    # true per-step delta is small, and min-over-16 samples on a shared
    # CPU runner leaves ~5% jitter in the ratio — the gate's whole budget.
    data["guard_overhead"] = compare_guard_overhead(
        **{k: v for k, v in kw.items() if k not in ("occupancies", "steps")},
        steps=64, occupancy=max(kw.get("occupancies", (4,))))
    # ISSUE 9: shared-prefix admission throughput, prefix cache on vs off.
    # Deliberately NOT smoke-reduced: the acceptance point is 64 requests
    # over a 512-token common prefix, and shrinking either would gate a
    # different regime (short prefixes hide the chunk-step savings).
    data["prefix_sharing"] = compare_prefix_sharing()
    with open(out_path, "w") as f:
        json.dump(data, f, indent=2)
    rows = []
    for tag, points in (("", data["points"]),
                        ("_paged", data["paged_points"]),
                        ("_lazy", data["lazy_points"])):
        for p in points:
            occ = p["occupancy"]
            rows.append(Row(f"serve_prefill{tag}_occ{occ}",
                            1e6 / max(p["prefill_tokens_per_s"], 1e-9),
                            f"{p['prefill_tokens_per_s']:.1f}tok/s"))
            rows.append(Row(f"serve_decode{tag}_occ{occ}",
                            1e6 / max(p["decode_tokens_per_s"], 1e-9),
                            f"{p['decode_tokens_per_s']:.1f}tok/s"))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sizes for CI")
    ap.add_argument("--out", default=DEFAULT_OUT)
    args = ap.parse_args(argv)
    rows = run(out_path=args.out, smoke=args.smoke)
    for r in rows:
        print(r.csv())
    print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
