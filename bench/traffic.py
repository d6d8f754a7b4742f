"""The one traffic generator: every mix is a data file it reads.

A mix (``bench/traffic/<mix>.json``) gives

- ``loop``: ``"closed"`` (``clients`` callers, each sending its next
  request when the previous one ends);
- ``pool``: how many requests the mix holds;
- ``sizes``: each size of a request (prompt and output tokens, residues,
  refinement steps), either a whole number or a clipped lognormal
  ``{"median", "sigma", "min", "max"}``;
- ``ramp_steps``: engine steps served before the measured window opens,
  so the window sees a steady state.

Sizes are stratified: the pool holds the same
multiset of values for every seed (the quantiles of the distribution at
evenly spaced probabilities), and the seed only decides their order and the
request contents. Runs on different seeds therefore carry the same amount of
work, which keeps the spread between runs down to what the system adds.
"""
from __future__ import annotations

import math
import statistics

from bench.common import seed_rng

SIZE_STREAM = 1


def stratified(spec, n: int) -> list:
    """``n`` values of a size: a constant, or the quantiles of a clipped
    lognormal at probabilities (i + 1/2) / n."""
    if isinstance(spec, (int, float)):
        return [spec] * n
    normal = statistics.NormalDist()
    out = []
    for i in range(n):
        z = normal.inv_cdf((i + 0.5) / n)
        v = round(spec["median"] * math.exp(spec["sigma"] * z))
        out.append(int(min(max(v, spec["min"]), spec["max"])))
    return out


def requests(mix: dict, seed: int) -> list:
    """The mix's requests in the order this seed sends them: a list of
    ``{"index": i, <size name>: value, ...}``; each size is shuffled on its
    own, so the pairing of sizes changes with the seed too."""
    rng = seed_rng(seed, SIZE_STREAM)
    n = mix["pool"]
    columns = {}
    for name, spec in sorted(mix["sizes"].items()):
        vals = stratified(spec, n)
        columns[name] = [vals[j] for j in rng.permutation(n)]
    return [dict({k: col[i] for k, col in columns.items()}, index=i)
            for i in range(n)]


def largest(mix: dict, name: str) -> int:
    """The largest value a size takes in this mix (what set-up must warm)."""
    spec = mix["sizes"][name]
    return int(spec if isinstance(spec, (int, float)) else
               max(stratified(spec, mix["pool"])))
