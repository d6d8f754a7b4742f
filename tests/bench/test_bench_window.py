"""Window cutting and percentiles over every request."""
import pytest

from bench import common
from bench.loop import Record, Step, Window


def rec(prompt, times, submitted=0.0, finished=None, rid=0, status="OK"):
    return Record({"prompt": prompt}, rid=rid, submitted=submitted,
                  times=list(times), finished=finished, status=status)


def window():
    return Window(t0=10.0, t1=20.0, steps=[
        Step(9.5, 2, {"stepped": [5]}), Step(10.5, 2, {"stepped": [5, 7]}),
        Step(20.5, 1, {"stepped": [7]})], records=[
        rec(4, [9.0, 11.0, 12.0, 21.0], submitted=8.0),   # first before
        rec(6, [10.5, 13.0], submitted=10.0),               # inside
        rec(3, [15.0], submitted=14.5),
    ])


def test_tokens_and_gaps_inside_only():
    w = window()
    assert w.seconds == 10.0
    assert w.tokens() == 5                       # 11, 12, 10.5, 13, 15
    assert sorted(w.gaps()) == pytest.approx([1.0, 2.5])


class _Ctx:
    def __init__(self, w, steps):
        self.window, self.mix = w, {"sizes": {"steps": steps}}
        self.notes = []

    def note(self, line):
        self.notes.append(line)


def test_complexes_count_steps_of_complexes_that_did_not_fail():
    """Each refinement step counts 1/steps of its complex; a failed
    complex counts nothing and a retried one no more than its steps."""
    w = Window(t0=0.0, t1=2.0, steps=[
        Step(0.5, 3, {"stepped_rid": [1, 2, 3]}),
        Step(1.0, 3, {"stepped_rid": [1, 2, 3]}),
        Step(1.5, 2, {"stepped_rid": [1, 3]}),
        Step(2.5, 2, {"stepped_rid": [1, 3]})], records=[
        rec(0, [], rid=1, finished=1.6), rec(0, [], rid=2, status="FAILED"),
        rec(0, [], rid=3, status=None)])
    read = common.load_module("metrics", "complexes_per_s").read
    # rid 1: 3 steps capped at 2; rid 2 failed; rid 3: 3 steps, in flight
    assert read(_Ctx(w, 2)) == pytest.approx((2 + 2) / 2 / 2.0)
    assert read(_Ctx(w, 4)) == pytest.approx((3 + 3) / 4 / 2.0)


def test_decode_contexts_and_admissions():
    w = window()
    # tokens 1, 2 of the first request (prompt 4), token 1 of the second
    assert sorted(w.decode_contexts()) == [5, 6, 7]
    assert sorted(w.admitted_prompts()) == [3, 6]
    assert w.work("stepped") == [5, 7]


def test_percentile_nearest_rank():
    vals = list(range(1, 101))
    assert common.percentile(vals, 99) == 99
    assert common.percentile(vals, 95) == 95
    assert common.percentile([3.0], 99) == 3.0
    assert common.percentile([5, 1, 4, 2, 3], 50) == 3
    with pytest.raises(ValueError):
        common.percentile([], 50)
