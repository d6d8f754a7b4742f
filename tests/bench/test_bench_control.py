"""The control: the plain reference put in the program's place in float8
e4m3, the precision below the bfloat16 the configurations state, read on
the served outputs of a SMOKE-size run. It has to come out as not correct
under a limit the program's own output passes. On the chip the same
readings, at each cell's size, set the cell's limit (PERF.md). The control
is judged by the run's own comparison, under the same limits."""
import pytest

from conftest import run_smoke

CASES = [("lm", {"max_logit_gap": 0.05, "sample_tokens": 40}),
         ("pairformer", {"max_bias_rel_err": 0.03, "max_trunk_rel_err": 0.05,
                         "max_step_rel_err": 0.02,
                         "sample_complexes": 2})]


@pytest.mark.parametrize("family,limits", CASES)
@pytest.mark.parametrize("seed", [11, 2 ** 31 + 5])
def test_control_fails_where_program_passes(smoke, family, limits, seed):
    cfg, mix = smoke(family)
    out = run_smoke(cfg, mix, limits, seed=seed, control=True)
    assert out["correct"] is True, out["checks"]
    ctl = out["control"]
    assert ctl["correct"] is False, ctl["checks"]
    assert set(ctl["checks"]) == set(out["checks"])
    assert all(c["limit"] == limits[k] for k, c in ctl["checks"].items())
