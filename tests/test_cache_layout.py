"""The kv-head-major KV cache, held to a cache-free reference.

The caches live in the flash-decode kernels' kv-head-major layout from
allocation and the jitted decode step hands them over zero-copy. This
suite holds the serving path to a reference that keeps no cache at all:
a teacher-forced XLA ``model.prefill`` over prompt + served tokens, one
ragged row per decode step, read at its last position.

- greedy decode across full-KV / ring-KV / MoE / paged caches under GQA,
  and through the interpret-mode Pallas kernels (contiguous and paged):
  every served token's logits are within ``LOGIT_TOL`` of the
  reference's, and the token is the reference's argmax (a tie within
  ``LOGIT_TOL`` may go either way);
- preempt -> re-prefill -> resume still serves the reference's tokens;
- prefill writes the K/V rows that token-by-token decode writes;
- the paged gather cap resolves to ceil(max(lengths)/page_size) and never
  lets garbage table entries leak.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.kernels import ops
from repro.models import get_model
from repro.models.common import init_params
from repro.serve import ServeEngine

# The smoke configs run in f32. Decode and prefill sum the same logits in
# different orders (one cached row at a time against whole-sequence
# attention); on these models the two differ by <= 5e-7 (logits spread
# ~0.2), so 1e-5 leaves 20x and still catches a wrong key, position, page
# or dropped token (a dropped MoE token alone moves them by 6e-3).
LOGIT_TOL = 1e-5
KV_TOL = 1e-6      # K/V rows differ by <= 6e-8 (values ~0.1)


def _params(cfg):
    model = get_model(cfg)
    return model, init_params(model.template(), jax.random.PRNGKey(0))


def _prompts(cfg, n, t, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, (t,)).astype(np.int32)
            for _ in range(n)]


def _record_logits(be) -> dict:
    """Record, for every token the backend emits, the logits row it was
    sampled from: ``{rid: [row, ...]}`` in emission order."""
    seen, last = {}, {}
    sample, admit, step = be._sample, be.admit, be.step

    def recording_sample(logits2d, mask):
        last["logits"] = np.asarray(logits2d, np.float32)
        return sample(logits2d, mask)

    def recorded(phase, rids_of):
        def run(*args):
            toks, mask = phase(*args)
            rids = rids_of(*args)
            for s in np.nonzero(mask)[0]:
                seen.setdefault(rids[s], []).append(last["logits"][s])
            return toks, mask
        return run

    be._sample = recording_sample
    be.admit = recorded(admit, lambda wave, slots: {
        s: r.rid for s, r in zip(slots, wave)})
    be.step = recorded(step, lambda live: {
        s: st.req.rid for s, st in live.items()})
    return seen


def _generate(cfg, *, page_size=None, steps=8, n_slots=2, prompt_len=6,
              **eng_kw):
    """Serve ``n_slots`` random prompts greedily; returns the engine, the
    prompts, the served tokens and each served token's logits row."""
    model, params = _params(cfg)
    kw = dict(eng_kw)
    if page_size:
        kw.setdefault("page_size", page_size)
    eng = ServeEngine(model, params, max_len=32, n_slots=n_slots,
                      prefill_len=prompt_len, **kw)
    seen = _record_logits(eng.backend)
    prompts = _prompts(cfg, n_slots, prompt_len)
    outs = np.stack([r for r in eng.generate(prompts, steps)])
    assert sorted(seen) == list(range(n_slots)), sorted(seen)
    logits = [np.stack(seen[rid])[:, :cfg.vocab] for rid in range(n_slots)]
    return eng, prompts, outs, logits


def _prefix_logits(cfg, params, seq, ends):
    """The cache-free reference: the logits after each prefix ``seq[:e]``
    (``e`` in ``ends``), each one ragged row of a single XLA prefill."""
    model = get_model(cfg.replace(attn_impl="xla"))
    toks = np.zeros((len(ends), max(ends)), np.int32)
    for i, e in enumerate(ends):
        toks[i, :e] = seq[:e]
    logits, _ = model.prefill(params, {"tokens": jnp.asarray(toks)},
                              lengths=jnp.asarray(ends, jnp.int32))
    return np.asarray(logits[:, 0, :cfg.vocab], np.float32)


def _assert_matches_reference(cfg, params, prompt, served, logits, what):
    """``logits[t]`` — what produced ``served[t]`` — is within LOGIT_TOL of
    the reference after prompt + served[:t], and ``served[t]`` is the
    reference's argmax (a tie within LOGIT_TOL may go either way)."""
    ends = len(prompt) + np.arange(len(served))
    ref = _prefix_logits(cfg, params, np.concatenate([prompt, served]), ends)
    np.testing.assert_allclose(logits, ref, atol=LOGIT_TOL, err_msg=what)
    gap = ref.max(-1) - ref[np.arange(len(served)), served]
    assert np.all(gap <= LOGIT_TOL), (what, gap, served, ref.argmax(-1))


def _assert_serves_reference(cfg, eng, prompts, outs, logits):
    for row, (prompt, served) in enumerate(zip(prompts, outs)):
        _assert_matches_reference(cfg, eng.params, prompt, served,
                                  logits[row], f"request {row}")


class TestLayoutParity:
    """Served tokens and logits must match the cache-free reference."""

    @pytest.mark.parametrize("arch", ["command_r_plus_104b", "hymba_15b",
                                      "llama4_scout_17b_a16e"])
    def test_contiguous_families(self, arch):
        # command-r: full-KV GQA (H=8, KVH=2); hymba: ring-KV + SSM;
        # llama4: MoE full-KV GQA. Expert capacity is per sequence: a
        # prefill of s tokens drops those past an expert's capacity, a
        # one-token decode step never does. At capacity_factor = experts /
        # top_k no token is dropped, so the reference computes the function
        # decode does.
        cfg = smoke_config(arch)
        if cfg.n_experts:
            cfg = cfg.replace(capacity_factor=cfg.n_experts / cfg.top_k)
        _assert_serves_reference(cfg, *_generate(cfg))

    @pytest.mark.parametrize("arch", ["command_r_plus_104b"])
    def test_paged(self, arch):
        cfg = smoke_config(arch)
        served = _generate(cfg, page_size=8)
        assert served[0].backend.paged
        _assert_serves_reference(cfg, *served)

    def test_prefill_cache_is_the_same_tensor_transposed(self):
        """A prefill's cache holds, at each real position, the K/V rows
        that token-by-token decode writes there: same kv-head-major
        tensor, whichever program wrote it."""
        cfg = smoke_config("command_r_plus_104b")
        model, params = _params(cfg)
        toks = jnp.asarray(
            np.random.default_rng(1).integers(0, cfg.vocab, (2, 10)),
            jnp.int32)
        _, whole = model.prefill(params, {"tokens": toks}, max_len=16)
        _, cache = model.prefill(params, {"tokens": toks[:, :1]},
                                 max_len=16)
        for t in range(1, 10):
            _, cache = model.decode(params, cache, toks[:, t:t + 1])
        np.testing.assert_array_equal(np.asarray(cache["length"]), 10)
        for key in ("k", "v"):                  # (L, B, KVH, S, hd)
            assert whole[key].shape == cache[key].shape
            np.testing.assert_allclose(
                np.asarray(cache[key][:, :, :, :10]),
                np.asarray(whole[key][:, :, :, :10]), atol=KV_TOL,
                err_msg=key)

    @pytest.mark.parametrize("paged", [False, True])
    def test_interpret_mode_pallas(self, paged):
        """The zero-copy Pallas dispatch (not just the XLA fallback),
        contiguous and paged, under GQA + ALiBi: decode logits within
        LOGIT_TOL of the reference, and the same argmax."""
        cfg = smoke_config("command_r_plus_104b").replace(
            attn_impl="pallas_interpret", attn_chunk=8)
        model, params = _params(cfg)
        if paged:
            cache = model.init_paged_cache(2, 8, 8, 4)
        else:
            cache = model.init_cache(2, 16)
        prompt = np.random.default_rng(2).integers(0, cfg.vocab, (2, 8))
        _, wave = model.prefill(params, {"tokens": jnp.asarray(prompt,
                                                               jnp.int32)},
                                max_len=8)
        if paged:
            # the prompt's page, then the page decode grows into
            tables = np.full((2, 4), 8, np.int32)
            tables[:, :2] = [[0, 2], [1, 3]]
            cache = model.insert_paged(cache, wave, np.arange(2),
                                       jnp.asarray(tables))
        else:
            pad = [(0, 0)] * wave["k"].ndim
            pad[3] = (0, 8)                     # (L, B, KVH, S, hd)
            wave = dict(wave, k=jnp.pad(wave["k"], pad),
                        v=jnp.pad(wave["v"], pad))
            cache = model.insert_cache(cache, wave, np.arange(2))
        fed = np.asarray([[3], [5]], np.int32)
        logits = []
        for _ in range(3):
            lg, cache = model.decode(params, cache, jnp.asarray(fed[:, -1:]))
            logits.append(np.asarray(lg[:, 0, :cfg.vocab], np.float32))
            fed = np.concatenate(
                [fed, logits[-1].argmax(-1)[:, None].astype(np.int32)], 1)
        for row in range(2):
            # the served tokens start at the fed one; the row's logits
            # produced the tokens fed after it
            _assert_matches_reference(
                cfg, params, np.append(prompt[row], fed[row, 0]),
                fed[row, 1:], np.stack([lg[row] for lg in logits]),
                f"row {row}")

    def test_contiguous_cache_lane_padded_at_allocation_for_pallas(self):
        """stablelm-class head dims (not 128-multiples) must be lane-padded
        ONCE at allocation when Pallas runs — a raw-hd cache would be
        re-padded per decode step, the Θ(pool) cost the layout avoids.
        XLA backends keep raw hd (their einsums read unpadded directly);
        ring caches stay raw (dense XLA window path). Prefill must emit
        the same width so insert_cache lines up."""
        cfg = smoke_config("stablelm_12b").replace(  # hd=40: not aligned
            attn_impl="pallas_interpret", attn_chunk=8)
        model, params = _params(cfg)
        assert model.init_cache(2, 24)["k"].shape[-1] == 128
        toks = {"tokens": jnp.asarray(
            np.random.default_rng(3).integers(0, cfg.vocab, (2, 6)),
            jnp.int32)}
        _, cache = model.prefill(params, toks, max_len=24)
        assert cache["k"].shape[-1] == 128
        assert np.all(np.asarray(cache["k"][..., 40:]) == 0)   # inert pad
        cfg_xla = cfg.replace(attn_impl="xla")
        assert get_model(cfg_xla).init_cache(2, 24)["k"].shape[-1] == 40
        cfg_ring = smoke_config("hymba_15b").replace(
            attn_impl="pallas_interpret")
        assert get_model(cfg_ring).init_cache(2, 64)["k"].shape[-1] \
            == cfg_ring.resolved_head_dim

    def test_preempt_resume_parity(self):
        """Auto-preemption under lazy paging (tiny pool): the resumed
        requests still serve the reference's tokens."""
        cfg = smoke_config("command_r_plus_104b")
        served = _generate(cfg, page_size=4, steps=10, prompt_len=4,
                           n_pages=5, pages_per_slot=5)
        assert served[0].n_preemptions > 0, \
            "pool never ran dry: test is vacuous"
        _assert_serves_reference(cfg, *served)


def test_config_accepts_only_the_kernel_layout():
    """``cache_layout`` names the one layout; anything else is refused when
    the config is built, not silently served in the kernel layout."""
    cfg = smoke_config("command_r_plus_104b")
    assert cfg.replace(cache_layout="kernel") == cfg
    with pytest.raises(ValueError, match="cache_layout"):
        cfg.replace(cache_layout="legacy")


class TestPagedGatherCap:
    """ISSUE 5 satellite: the paged XLA fallback gathers at most
    ceil(max(lengths)/page_size) pages, not the full table width."""

    def test_static_cap_resolution(self):
        lengths = jnp.asarray([5, 17, 9], jnp.int32)
        assert ops._static_page_cap(lengths, 8, 64, None) == 3
        assert ops._static_page_cap(lengths, 8, 2, None) == 2   # clamped
        assert ops._static_page_cap(lengths, 8, 64, 7) == 7     # explicit
        assert ops._static_page_cap(jnp.zeros((2,), jnp.int32), 8, 64,
                                    None) == 1

    def test_traced_lengths_fall_back_to_table_width(self):
        caps = []

        def f(lengths):
            caps.append(ops._static_page_cap(lengths, 8, 64, None))
            return lengths

        jax.jit(f)(jnp.asarray([5, 17], jnp.int32))
        assert caps == [64]

    @pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
    def test_wide_garbage_table_cannot_leak(self, impl):
        """A page table far wider than any request, holding garbage ids
        past the mapped prefix, yields the same output as the exact one —
        the capped gather (XLA) or the clamped, length-masked block index
        maps (Pallas) discard all of it."""
        B, S, H, KVH, D, PS = 2, 32, 4, 2, 16, 8
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        k = jax.random.normal(ks[0], (B, KVH, S, D))
        v = jax.random.normal(ks[1], (B, KVH, S, D))
        q = jax.random.normal(ks[2], (B, 1, H, D))
        lengths = jnp.asarray([S, 13], jnp.int32)
        slopes = jnp.asarray(0.5 ** np.arange(1, H + 1), jnp.float32)
        p = S // PS
        # pools (KVH, n_pages, PS, D), request b's page j at b * p + j
        kp, vp = [x.reshape(B, KVH, p, PS, D).transpose(1, 0, 2, 3, 4)
                  .reshape(KVH, B * p, PS, D) for x in (k, v)]
        pt_exact = jnp.arange(B)[:, None] * p + jnp.arange(p)[None]
        pt_wide = jnp.concatenate(
            [pt_exact, jnp.full((B, 13), 10_000, jnp.int32)], axis=1)
        kw = {"slopes": slopes, "impl": impl}
        want = ops.flash_decode(q, kp, vp, lengths, page_table=pt_exact, **kw)
        got = ops.flash_decode(q, kp, vp, lengths, page_table=pt_wide, **kw)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    def test_engine_page_cap_is_pow2_of_longest(self):
        cfg = smoke_config("stablelm_12b")
        model, params = _params(cfg)
        eng = ServeEngine(model, params, max_len=32, n_slots=2,
                          prefill_len=6, page_size=4)
        assert eng._page_cap() == 1                  # nothing live yet
        for p in _prompts(cfg, 2, 6):
            eng.submit(p, 8)
        eng.admit()
        # longest live length 6 -> needs ceil(7/4)=2 pages -> pow2 cap 2
        assert eng._page_cap() == 2
        eng.run()
