"""Shared pieces of the chip benchmark.

Everything a cell is made of is found by name: ``BENCHMARK.json`` names the
cell, its configuration (``bench/configs/<config>.json``) and its traffic
mix (``bench/traffic/<mix>.json``); the configuration names its family
(``bench/families/<family>.py``, the adapter onto the program, and
``bench/reference/<family>.py``, the plain reference), and each per-layer
metric is ``bench/metrics/<metric>.py``. This module holds what they share:
the file lookups, the device check, the peak table, the compile cache,
seeds and the statistics every metric uses.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
# terminal statuses of a request that did not end OK
FAILED = frozenset({"FAILED", "REJECTED", "TIMED_OUT", "CANCELLED"})


class BenchError(Exception):
    """A run that cannot produce a result: no chip, a name not found, a
    device kind missing from the peak table. ``run.py`` exits non-zero and
    prints no result line."""


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def find(items: list, name: str, what: str) -> dict:
    for item in items:
        if item["name"] == name:
            return item
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


def named_file(kind: str, name: str, suffix: str = ".json") -> pathlib.Path:
    """``bench/<kind>/<name><suffix>``; names are checked so a file name can
    never leave its directory."""
    if not name or "/" in name or name.startswith("."):
        raise BenchError(f"bad {kind} name {name!r}")
    path = BENCH / kind / f"{name}{suffix}"
    if not path.is_file():
        raise BenchError(f"{path.relative_to(ROOT)} does not exist")
    return path


def load_module(kind: str, name: str):
    """Import ``bench/<kind>/<name>.py`` as a module of its own."""
    path = named_file(kind, name, ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str) -> dict:
    """The chip's published peaks, keyed by JAX's ``device_kind``. A kind
    that is not in ``bench/peaks.json`` is an error, never a default."""
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} is not in "
                         f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def check_device(chips: int) -> dict:
    """The devices the cell runs on: TPUs only, at least ``chips`` of them.
    There is no CPU fallback."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise BenchError(f"JAX found no TPU (first device is "
                         f"{dev.platform!r}); the benchmark runs on TPUs only")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    peaks(dev.device_kind)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), with no minimum
    compile time or size, so every program a cell needs, small eager ones
    included, is read back from disk after the first run."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileLog:
    """Counts programs JAX obtains (compiled or read from the persistent
    cache) through its monitoring events. ``count`` read before and after
    the window gives the compiles inside it, which should be 0. One log
    per process: ``compile_log()``."""

    def __init__(self):
        import jax
        self.programs: list = []      # (time, function name, seconds)
        self.cache_hits = 0

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.programs.append((time.perf_counter(),
                                      kw.get("fun_name", "?"), duration))

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    @property
    def count(self) -> int:
        return len(self.programs)

    def since(self, n: int) -> list:
        return [name for _, name, _ in self.programs[n:]]


_COMPILE_LOG = None


def compile_log() -> CompileLog:
    global _COMPILE_LOG
    if _COMPILE_LOG is None:
        _COMPILE_LOG = CompileLog()
    return _COMPILE_LOG


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one purpose (``stream``) of a run; any whole
    seed works, beyond 32 bits too."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def seed_key(seed: int, stream: int):
    """A JAX PRNG key drawn from the same seed sequence."""
    import jax
    word = int(np.random.SeedSequence([int(seed), stream]).generate_state(
        1, np.uint32)[0])
    return jax.random.PRNGKey(word & 0x7FFFFFFF)


class Context:
    """What a metric reader sees: the cell's files, the chip's peaks, the
    window's records, the set-up time and, in a ``--trace 1`` run, the
    reduced trace. ``note`` adds a diagnostic line to the run's output."""

    def __init__(self, cell: dict, cfg: dict, mix: dict, peak: dict,
                 chips: int, window, setup_s: float, trace=None):
        self.cell, self.cfg, self.mix, self.peak = cell, cfg, mix, peak
        self.chips, self.window, self.setup_s = chips, window, setup_s
        self.trace = trace
        self.notes: list = []

    @property
    def model(self) -> dict:
        return self.cfg["model"]

    def note(self, line: str) -> None:
        self.notes.append(line)

    def roofline(self, name: str, flops: float, bytes_: float,
                 seconds: float):
        """Share (%) of the roofline: the least time the chip could take
        for the work, the larger of its operations over peak bf16 FLOP/s
        and its bytes over peak HBM bandwidth, over the time it took."""
        if seconds <= 0 or (flops <= 0 and bytes_ <= 0):
            return None
        t_flops = flops / (self.peak["bf16_flops_per_s"] * self.chips)
        t_bytes = bytes_ / (self.peak["hbm_bytes_per_s"] * self.chips)
        bound = "compute" if t_flops >= t_bytes else "memory"
        self.note(f"{name}: {flops:.6e} FLOP, {bytes_:.6e} B needed in "
                  f"{seconds:.6f} s of kernel time; {bound}-bound "
                  f"(compute {t_flops:.6f} s, memory {t_bytes:.6f} s)")
        return 100.0 * max(t_flops, t_bytes) / seconds


def percentile(values, p: float) -> float:
    """Nearest-rank percentile over every value (no interpolation): the
    smallest value with at least ``p`` percent of all values at or below
    it."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no values")
    rank = max(1, math.ceil(p / 100.0 * len(vals)))
    return float(vals[rank - 1])
