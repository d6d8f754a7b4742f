"""The comparison that decides ``correct`` fails when the timed path is
broken underneath: a run at SMOKE size on the CPU, the look for a chip
skipped, with each fault a serving cell can have planted in the program:
a step that returns its state unchanged, and a token or an answer altered
where it is produced. (Half a batch left out is a training fault; an
exchange between chips left out needs a cell on four chips.)"""
import jax.numpy as jnp
import pytest

from conftest import run_smoke

LM_LIMITS = {"max_logit_gap": 0.05, "sample_tokens": 40}
PAIR_LIMITS = {"max_bias_rel_err": 0.03, "max_trunk_rel_err": 0.05,
               "max_step_rel_err": 0.02, "sample_complexes": 2}


def _lm_state_unchanged(monkeypatch):
    from repro.models import lm
    orig = lm.decode_step

    def stale(params, cache, tokens, cfg, **kw):
        logits, _ = orig(params, cache, tokens, cfg, **kw)
        return logits, cache
    monkeypatch.setattr(lm, "decode_step", stale)


def _lm_token_altered(monkeypatch):
    from repro.serve import backend
    orig = backend.sample_tokens_guarded

    def shifted(logits, temps, topks, keys, vocab):
        toks, new_keys, peak = orig(logits, temps, topks, keys, vocab)
        return (toks + 1) % vocab, new_keys, peak
    monkeypatch.setattr(backend, "sample_tokens_guarded", shifted)


def _pair_state_unchanged(monkeypatch):
    from repro.models import pairformer
    monkeypatch.setattr(pairformer, "serve_step",
                        lambda params, cache, cfg: cache)


def _pair_answer_altered(monkeypatch):
    from repro.models import pairformer
    orig = pairformer.serve_step

    def altered(params, cache, cfg):
        out = orig(params, cache, cfg)
        return dict(out, s=jnp.roll(out["s"], 1, axis=1))
    monkeypatch.setattr(pairformer, "serve_step", altered)


@pytest.mark.parametrize("fault", [_lm_state_unchanged, _lm_token_altered])
def test_lm_fault_is_caught(smoke, monkeypatch, fault):
    fault(monkeypatch)
    cfg, mix = smoke("lm")
    out = run_smoke(cfg, mix, LM_LIMITS)
    assert out["correct"] is False
    assert out["checks"]["max_logit_gap"]["value"] > LM_LIMITS[
        "max_logit_gap"]


@pytest.mark.parametrize("fault", [_pair_state_unchanged,
                                   _pair_answer_altered])
def test_pair_fault_is_caught(smoke, monkeypatch, fault):
    fault(monkeypatch)
    cfg, mix = smoke("pairformer")
    out = run_smoke(cfg, mix, PAIR_LIMITS)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_clean_runs_pass_the_same_limits(smoke):
    for family, limits, key in (("lm", LM_LIMITS, "max_logit_gap"),
                                ("pairformer", PAIR_LIMITS,
                                 "max_step_rel_err")):
        cfg, mix = smoke(family)
        out = run_smoke(cfg, mix, limits)
        assert out["correct"] is True, (family, out["checks"])
        assert jnp.isfinite(out["checks"][key]["value"])
