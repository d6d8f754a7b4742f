"""Fixtures of the benchmark's self-tests: the repository root on the
path (the harness is the ``bench`` package), and the SMOKE-size cells in
``data/`` that drive the harness on the CPU."""
import json
import os
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# a CPU run under the v5e row of the peak table: it exercises the plumbing
# only, its times and shares are not device numbers
CPU_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def load(name: str) -> dict:
    with open(DATA / f"{name}.json") as f:
        return json.load(f)


@pytest.fixture
def smoke():
    """(cfg, mix) of a SMOKE-size cell by family."""
    def get(family: str):
        stem = {"lm": "lm_smoke", "pairformer": "pair_smoke"}[family]
        return load(stem), load(f"{stem}_mix")
    return get


def run_smoke(cfg, mix, limits, *, seed=3, seconds=1.0, traced=False,
              control=False, bench=None):
    from bench import run
    bench = bench or {"end_to_end": [], "per_layer": []}
    cell = {"name": f"{cfg['name']}.smoke", "chips": 1}
    return run.run_loaded(bench, cell, cfg, mix, limits, seed, seconds,
                          traced, control=control, device=dict(CPU_DEVICE))


os.environ.setdefault("TPU_LOG_DIR", "disabled")
