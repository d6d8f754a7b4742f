"""The traffic generator: deterministic per seed, the same work for every
seed, any whole seed accepted."""
from collections import Counter

import pytest

from bench import traffic

MIX = {"loop": "closed", "clients": 4, "pool": 64,
       "sizes": {"prompt": {"median": 160, "sigma": 0.5, "min": 64,
                            "max": 256},
                 "output": {"median": 256, "sigma": 0.4, "min": 128,
                            "max": 512},
                 "steps": 200}}


def test_same_seed_same_requests():
    big = 2 ** 31 + 977          # more than 32 signed bits hold
    assert traffic.requests(MIX, big) == traffic.requests(MIX, big)


def test_seeds_reorder_the_same_multiset():
    a, b = traffic.requests(MIX, 1), traffic.requests(MIX, 2)
    assert a != b
    for key in ("prompt", "output", "steps"):
        assert Counter(r[key] for r in a) == Counter(r[key] for r in b)


def test_sizes_respect_clips_and_median():
    vals = traffic.stratified(MIX["sizes"]["prompt"], 1001)
    assert min(vals) >= 64 and max(vals) <= 256
    assert sorted(vals)[500] == 160
    assert traffic.largest(MIX, "output") <= 512
    assert traffic.stratified(200, 3) == [200, 200, 200]


def test_loop_refuses_a_mix_it_cannot_drive():
    from bench import loop
    with pytest.raises(ValueError, match="unknown loop"):
        loop.drive(None, dict(MIX, loop="open"), 1, 1.0)
