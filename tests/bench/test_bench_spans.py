"""The program's own spans (``serve.*``) in the trace and the readers built
on them: hand-made events first, then one traced SMOKE-size run per family
on the CPU, each shared by the tests of its module fixture."""
from typing import NamedTuple

import pytest

from bench import common, traffic
from bench import trace as tr
from bench.trace import Event, Trace
from conftest import CPU_DEVICE, load


class Span(NamedTuple):
    start: float
    end: float
    name: str
    args: dict


def program_spans(path):
    """Every ``serve.*`` host event of the ``.xplane.pb`` at ``path`` with
    its stats: the benchmark's reduction keeps a span's name and times,
    its attributes are read here from the file itself."""
    from jax.profiler import ProfileData
    return [Span(e.start_ns, e.start_ns + e.duration_ns, e.name,
                 dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("serve.")]


class _Ctx:
    def __init__(self, trace):
        self.trace = trace
        self.notes = []

    def note(self, line):
        self.notes.append(line)


def read(name, trace):
    return common.load_module("metrics", name).read(_Ctx(trace))


def test_host_ms_per_step_leaves_out_fetches():
    ms = 1e6
    host = [Event(-5 * ms, 1 * ms, "serve.step"),     # ends in: counted
            Event(2 * ms, 10 * ms, "serve.step"),
            Event(3 * ms, 4 * ms, "serve.sample"),
            Event(3.5 * ms, 4 * ms, "serve.fetch"),
            Event(6 * ms, 9 * ms, "serve.fetch"),
            Event(10.5 * ms, 11 * ms, "serve.fetch"),  # between steps
            Event(12 * ms, 30 * ms, "serve.step")]    # ends after
    t = Trace((0, 20 * ms), {}, {}, host)
    # (6 + (8 - 0.5 - 3)) / 2
    assert read("host_ms_per_step.decode", t) == pytest.approx(5.25)


@pytest.mark.parametrize("host", [
    [Event(0, 50, "bench.step"), Event(10, 20, "np.asarray")],
    [Event(0, 50, "bench.step"), Event(10, 20, "serve.fetch")],
    [Event(0, 50, "serve.step")]], ids=["none", "no-step", "outside"])
def test_host_ms_per_step_reports_nothing_without_steps_in_the_window(host):
    """A program that emits no spans (a build from before them), or no
    step that ends in the window: the reader returns None, so the line
    leaves the metric out."""
    assert read("host_ms_per_step.decode",
                Trace((100, 200), {}, {}, host)) is None
    assert read("host_ms_per_step.decode", None) is None


def traced(family, cfg, mix, limits, tmp, metrics, seconds):
    """One traced run; its result, the reduced trace and the program's
    spans with their attributes."""
    from bench import run
    bench = {"end_to_end": [], "per_layer": [
        {"name": n, "unit": "%", "workloads": ["smoke"]} for n in metrics]}
    cell = {"name": "smoke", "chips": 1}
    path = str(tmp / f"{family}.xplane.pb")
    out = run.run_loaded(bench, cell, cfg, mix, limits, 3, seconds, True,
                         device=dict(CPU_DEVICE), keep_trace=path)
    return out, tr.load(path), program_spans(path)


@pytest.fixture(scope="module")
def lm_run(tmp_path_factory):
    return traced("lm", load("lm_smoke"), load("lm_smoke_mix"),
                  {"max_logit_gap": 0.05, "sample_tokens": 16},
                  tmp_path_factory.mktemp("lm"),
                  ("host_ms_per_step.decode", "slot_occupancy"), 1.5)


def pair_mix():
    """Every wave the same sizes: as many sizes in the pool as slots and
    twice as many callers, each complex three steps (the comparison
    follows a complex through its first two)."""
    mix = load("pair_smoke_mix")
    n = load("pair_smoke")["engine"]["n_slots"]
    mix.update(clients=2 * n, pool=n, ramp_steps=1)
    mix["sizes"]["steps"] = 3
    return mix


@pytest.fixture(scope="module")
def pair_run(tmp_path_factory):
    return traced("pair", load("pair_smoke"), pair_mix(),
                  {"max_bias_rel_err": 0.03, "max_trunk_rel_err": 0.05,
                   "max_step_rel_err": 0.02, "sample_complexes": 1},
                  tmp_path_factory.mktemp("pair"), ("mfu.pair",), 1.5)


def inside(t, spans, names):
    """The program spans called each of ``names`` in the window, checked
    to lie inside it, and to be in the reduced trace under their names."""
    lo, hi = t.window
    got = {n: [e for e in spans if e.name == n and lo <= e.start <= hi]
           for n in names}
    for n, evs in got.items():
        assert evs, f"no {n} span"
        assert all(e.end <= hi for e in evs), n
        kept = {(e.start, e.end) for e in t.host if e.name == n}
        assert {(e.start, e.end) for e in evs} <= kept, n
    return got


def pad_share(waves, power):
    """Share (%) of the admission program's rows (``power`` 1) or pair
    cells (2) that were padding, from the waves' attributes."""
    real = sum(e.args["real" if power == 1 else "real_sq"] for e in waves)
    ran = sum(e.args["rows"] * e.args["row_len"] ** power for e in waves)
    return 100.0 * (1.0 - real / ran)


def test_lm_spans_lie_in_the_window_with_their_attributes(lm_run):
    _, t, spans = lm_run
    got = inside(t, spans, ("serve.step", "serve.prefill", "serve.decode",
                            "serve.fetch", "serve.commit", "serve.sample",
                            "serve.admit"))
    cfg = load("lm_smoke")["engine"]
    steps = [e.args["step"] for e in got["serve.step"]]
    assert steps == sorted(steps) and len(set(steps)) == len(steps)
    assert all(0 <= e.args["live"] <= cfg["n_slots"]
               for e in got["serve.step"])
    for e in got["serve.prefill"]:
        assert e.args["rows"] == cfg["n_slots"]
        assert e.args["row_len"] == cfg["prefill_len"]
        assert 1 <= e.args["requests"] <= cfg["n_slots"]
        assert e.args["requests"] <= e.args["real"] <= (
            e.args["requests"] * cfg["prefill_len"])
    assert 0 < pad_share(got["serve.prefill"], 1) < 100
    for e in got["serve.decode"]:
        assert 1 <= e.args["pages"] <= cfg["pages_per_slot"]
    assert sum(e.args["emitted"] for e in got["serve.commit"]) > 0
    rids = [str(e.args["rids"]).split(";") for e in got["serve.admit"]]
    assert all(r and all(x.isdigit() for x in r) for r in rids)


def test_lm_readers(lm_run):
    out, _, _ = lm_run
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(m) == {"host_ms_per_step.decode", "slot_occupancy"}
    assert m["host_ms_per_step.decode"] > 0
    assert out["correct"] is True


def test_pair_spans_and_pad_share(pair_run):
    out, t, spans = pair_run
    got = inside(t, spans, ("serve.step", "serve.prefill", "serve.decode",
                            "serve.fetch", "serve.commit"))
    cfg = load("pair_smoke")
    mix = pair_mix()
    n_slots, max_len = cfg["engine"]["n_slots"], cfg["engine"]["max_len"]
    sizes = traffic.stratified(mix["sizes"]["n_res"], mix["pool"])
    for e in got["serve.prefill"]:
        assert (e.args["rows"], e.args["row_len"]) == (n_slots, max_len)
        assert e.args["requests"] == n_slots
        assert e.args["real"] == sum(sizes)
        assert e.args["real_sq"] == sum(n * n for n in sizes)
    want = 100 * (1 - sum(n * n for n in sizes) / (n_slots * max_len ** 2))
    assert pad_share(got["serve.prefill"], 2) == pytest.approx(want)
    assert "mfu.pair" in out["metrics"]
    assert out["correct"] is True
