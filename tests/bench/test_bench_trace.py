"""The reduction from a profiler trace to busy time, kernel time,
collective time and the breakdown, on hand-made events and on a chip's
plane laid out as ``jax.profiler.ProfileData`` gives it."""
import pytest

from bench import trace as tr
from bench.trace import Event, Trace
from conftest import DATA


def made():
    ops = {"/device:TPU:0": [
        Event(0, 10, "fusion.1", "jit_step(1)"),
        Event(5, 20, "closed_call.3", "jit_step(1)", True),  # overlaps
        Event(30, 40, "all-reduce.7", "jit_step(1)"),
        Event(90, 120, "fusion.2", "jit__pf(2)"),                # clipped
    ]}
    modules = {"/device:TPU:0": [Event(0, 40, "jit_step(1)"),
                                 Event(90, 120, "jit__pf(2)")]}
    host = [Event(0, 100, "bench.step"), Event(45, 80, "sample_host_copy")]
    return Trace((0, 100), ops, modules, host)


def test_busy_is_the_union_inside_the_window():
    t = made()
    # [0, 20] + [30, 40] + [90, 100]
    assert tr.busy_s(t) == pytest.approx(40e-9)
    assert t.window_s == pytest.approx(100e-9)


def test_kernel_module_and_collective_time():
    t = made()
    assert tr.matching_s(t, kernel=True) == pytest.approx(15e-9)
    assert tr.matching_s(t, kernel=True, module=r"_pf\b") == 0.0
    assert tr.matching_s(t, r"^fusion", module=r"_pf\b") == pytest.approx(
        10e-9)
    assert tr.collective_s(t) == pytest.approx(10e-9)
    assert tr.matching_s(t, r"_pf\b", where="modules") == pytest.approx(
        10e-9)
    assert tr.idle_pct(t) == pytest.approx(60.0)


def test_breakdown_labels_idle_gaps_by_host_span():
    bd = tr.breakdown(made())
    kinds = dict(bd["device_ops"])
    assert kinds["jit_step:pallas_kernel"] == pytest.approx(15e-9)
    gaps = dict(bd["idle_gaps"])
    # idle [20, 30] under bench.step only; [40, 90]: the shorter copy
    # span covers its midpoint 65
    assert gaps["bench.step"] == pytest.approx(10e-9)
    assert gaps["sample_host_copy"] == pytest.approx(50e-9)


class _Ev:
    def __init__(self, name, start, dur, stats=()):
        self.name, self.start_ns, self.duration_ns = name, start, dur
        self.stats = list(stats)


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, lines):
        self.lines = lines


def test_device_plane_marks_kernels_and_programs():
    """A chip's plane as ``jax.profiler.ProfileData`` gives it: each
    operation gets the program execution that holds its start, and is a
    Pallas kernel by its ``closed_call`` name or a ``tpu_custom_call`` /
    ``pallas_call`` in its stats."""
    plane = _Plane([
        _Line(tr.MODULES_LINE, [_Ev("jit__lambda(7)", 0, 50),
                                _Ev("jit__pf(3)", 60, 40)]),
        _Line(tr.OPS_LINE, [
            _Ev("fusion.1", 0, 10),
            _Ev("closed_call.12", 10, 30),
            _Ev("custom-call.4", 70, 10,
                [("long_name", 'custom_call_target="tpu_custom_call"')]),
            _Ev("copy.2", 80, 5, [("tf_op", "jit(_pf)/transpose")]),
            _Ev("fusion.9", 55, 2)]),
    ])
    ops, mods = tr._device_plane(plane)
    got = {e.name: (e.module, e.kernel) for e in ops}
    assert got == {"fusion.1": ("jit__lambda(7)", False),
                   "closed_call.12": ("jit__lambda(7)", True),
                   "custom-call.4": ("jit__pf(3)", True),
                   "copy.2": ("jit__pf(3)", False),
                   "fusion.9": ("", False)}
    assert [m.name for m in mods] == ["jit__lambda(7)", "jit__pf(3)"]
    t = Trace((0, 100), {"/device:TPU:0": ops}, {"/device:TPU:0": mods}, [])
    assert tr.matching_s(t, kernel=True, module=r"_pf\b") == pytest.approx(
        10e-9)
    assert tr.matching_s(t, kernel=True, module=r"^(?!.*_pf\b)") == \
        pytest.approx(30e-9)


def test_hlo_text_names_nested_ops_and_program_share():
    """On the chip an operation is named by its HLO text, a loop holds the
    operations it runs, and a kernel is known by its custom-call target:
    the names are cut to the instruction, the breakdown counts operations
    that hold no other, and a program's share is a union, not a sum."""
    text = ('%closed_call.7 = bf16[8,32,8,128]{3,2,1,0} custom-call(%p), '
            'custom_call_target="tpu_custom_call"')
    plane = _Plane([
        _Line(tr.MODULES_LINE, [_Ev("jit__lambda(7)", 0, 60),
                                _Ev("jit__pf(3)", 70, 30)]),
        _Line(tr.OPS_LINE, [
            _Ev("%while.4 = (s32[]) while(%t), body=%region_2", 0, 50),
            _Ev(text, 5, 20),
            _Ev("%fusion.3 = bf16[8] fusion(%x), kind=kLoop", 30, 10),
            _Ev("%while.2 = (s32[]) while(%u)", 70, 30),
            _Ev("%custom-call.71 = f32[128] custom-call(%y)", 75, 20)]),
    ])
    ops, mods = tr._device_plane(plane)
    assert [(e.name, e.kernel) for e in ops] == [
        ("%while.4", False), ("%closed_call.7", True), ("%fusion.3", False),
        ("%while.2", False), ("%custom-call.71", False)]
    t = Trace((0, 100), {"/device:TPU:0": ops}, {"/device:TPU:0": mods},
              [Event(0, 100, "bench.step")])
    assert [e.name for e in ops if tr.leaf(e)] == [
        "%closed_call.7", "%fusion.3", "%custom-call.71"]
    kinds = dict(tr.breakdown(t)["device_ops"])
    assert kinds == pytest.approx({"jit__lambda:pallas_kernel": 20e-9,
                                   "jit__lambda:fusion": 10e-9,
                                   "jit__pf:custom-call": 20e-9})
    assert tr.busy_s(t) == pytest.approx(80e-9)
    assert tr.busy_s(t, module=r"_pf\b") == pytest.approx(30e-9)
    assert tr.matching_s(t, kernel=True) == pytest.approx(20e-9)


def test_idle_labels_take_the_shortest_covering_span():
    host = [Event(0, 1000, "bench.step"), Event(100, 300, "a"),
            Event(150, 250, "b"), Event(600, 700, "c")]
    gaps = [(130, 160), (190, 210), (280, 290), (640, 660), (800, 900),
            (1200, 1300)]
    assert tr._host_labels(host, gaps) == ["a", "b", "a", "c", "bench.step",
                                           "no host span"]


def test_recorded_chip_trace():
    """A 0.53 s window of ``gpt2-alibi.decode-short`` recorded on one TPU
    v5e by ``bench/record_trace.py`` (seed 4200000001): one device plane,
    the paged decode kernel found outside the prefill program and the
    ALiBi prefill kernel inside it, busy time inside the window, and idle
    gaps labelled by host spans."""
    t = tr.load(str(DATA / "lm_small.xplane.pb"))
    assert list(t.ops) == ["/device:TPU:0"]
    assert 0.5 < t.window_s < 0.6
    busy = tr.busy_s(t)
    assert 0.4 < busy < t.window_s
    decode = tr.matching_s(t, kernel=True, module=r"^(?!.*_pf\b)")
    prefill = tr.matching_s(t, kernel=True, module=r"_pf\b")
    assert 0.05 < decode < busy and 0 < prefill < decode
    bd = tr.breakdown(t)
    kinds = dict(bd["device_ops"])
    assert kinds["jit__lambda:pallas_kernel"] == pytest.approx(decode)
    assert kinds["jit__pf:pallas_kernel"] == pytest.approx(prefill)
    idle = dict(bd["idle_gaps"])
    assert "no host span" not in idle
    assert 0 < sum(idle.values()) <= t.window_s - busy + 1e-9
