"""Decode-vs-prefill parity + GQA factored-bias regressions (ISSUE 2).

For GQA (KVH < H), ragged per-request lengths and all three bias modes
(none / phi / alibi), ``flash_decode`` — on both the XLA and the
interpreted Pallas path — must match the LAST ROW of full causal
``flash_attention`` over each request's valid prefix, to fp32 tolerance.

Plus regression tests for two GQA phi_k bugs: the full-attention XLA path
used to collapse per-kv-head factors to kv head 0, and the Pallas decode
path used to raise on them.

The caches ``flash_decode`` reads are kv-head-major (``(B, KVH, S, *)``,
pools ``(KVH, n_pages, ps, *)``); the references read the token-major
tensors they were made from.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

B, S, H, KVH, D, R = 3, 48, 8, 2, 16, 4
G = H // KVH
LENGTHS = np.array([17, 48, 33], np.int32)     # ragged, incl. non-block-multiple


def _setup(key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, KVH, D))
    v = jax.random.normal(ks[2], (B, S, KVH, D))
    # PER-KV-HEAD factors: kv heads must get distinct rows for the
    # regression to bite (the old code used head 0's factors everywhere)
    pq = jax.random.normal(ks[3], (B, S, H, R))
    pk = jax.random.normal(ks[4], (B, S, KVH, R))
    slopes = jnp.asarray(0.5 ** np.arange(1, H + 1), jnp.float32)
    return q, k, v, pq, pk, slopes


def _hm(x):
    """(B, S, KVH, E) -> the caches' kv-head-major (B, KVH, S, E)."""
    return x.transpose(0, 2, 1, 3)


def _bias_kwargs(mode, pq, pk, slopes):
    if mode == "phi":
        return {"phi_q": pq, "phi_k": pk}
    if mode == "alibi":
        return {"slopes": slopes}
    return {}


class TestDecodePrefillParity:
    @pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
    @pytest.mark.parametrize("mode", ["none", "phi", "alibi"])
    def test_decode_matches_last_prefill_row(self, impl, mode):
        q, k, v, pq, pk, slopes = _setup()
        lengths = jnp.asarray(LENGTHS)
        bidx = jnp.arange(B)
        q_dec = q[bidx, LENGTHS - 1][:, None]               # (B,1,H,D)
        kw = _bias_kwargs(mode, pq, _hm(pk), slopes)
        if mode == "phi":
            kw["phi_q"] = pq[bidx, LENGTHS - 1][:, None]    # (B,1,H,R)
        out = ops.flash_decode(q_dec, _hm(k), _hm(v), lengths, impl=impl,
                               block_k=16, **kw)
        for b in range(B):
            n = int(LENGTHS[b])
            kw_b = _bias_kwargs(mode, pq[b:b + 1, :n], pk[b:b + 1, :n],
                                slopes)
            full = ops.flash_attention(q[b:b + 1, :n], k[b:b + 1, :n],
                                       v[b:b + 1, :n], mask_kind="causal",
                                       impl="xla", **kw_b)
            np.testing.assert_allclose(np.asarray(out[b, 0], np.float32),
                                       np.asarray(full[0, n - 1], np.float32),
                                       atol=3e-5,
                                       err_msg=f"{impl}/{mode}/req{b}")

    @pytest.mark.parametrize("mode", ["none", "phi", "alibi"])
    def test_xla_and_pallas_decode_agree(self, mode):
        q, k, v, pq, pk, slopes = _setup(key=1)
        lengths = jnp.asarray(LENGTHS)
        bidx = jnp.arange(B)
        q_dec = q[bidx, LENGTHS - 1][:, None]
        kw = _bias_kwargs(mode, pq, _hm(pk), slopes)
        if mode == "phi":
            kw["phi_q"] = pq[bidx, LENGTHS - 1][:, None]
        a = ops.flash_decode(q_dec, _hm(k), _hm(v), lengths, impl="xla",
                             block_k=16, **kw)
        b_ = ops.flash_decode(q_dec, _hm(k), _hm(v), lengths,
                              impl="pallas_interpret", block_k=16, **kw)
        np.testing.assert_allclose(a, b_, atol=3e-5)


class TestGQAPhiKRegressions:
    """The old code silently used kv head 0's key factors for every query
    head (_xla_path) and raised on (B, S, KVH, R) (decode Pallas path)."""

    def test_full_attention_per_kv_head_phi_k(self):
        q, k, v, pq, pk, _ = _setup(key=2)
        out = ops.flash_attention(q, k, v, pq, pk, mask_kind="causal",
                                  impl="xla")
        pk_full = jnp.repeat(pk, G, axis=2)                 # (B,S,H,R)
        want = ref.mha_reference(q, k, v, phi_q=pq, phi_k=pk_full,
                                 mask_kind="causal")
        np.testing.assert_allclose(out, want, atol=3e-5)
        # the head-0 collapse must actually produce DIFFERENT values here,
        # otherwise this regression test would pass vacuously
        pk_head0 = jnp.broadcast_to(pk[:, :, :1], pk_full.shape)
        wrong = ref.mha_reference(q, k, v, phi_q=pq, phi_k=pk_head0,
                                  mask_kind="causal")
        assert float(jnp.abs(want - wrong).max()) > 1e-2

    def test_full_attention_per_kv_head_phi_k_pallas(self):
        q, k, v, pq, pk, _ = _setup(key=3)
        out = ops.flash_attention(q, k, v, pq, pk, mask_kind="causal",
                                  impl="pallas_interpret",
                                  block_q=16, block_k=16)
        pk_full = jnp.repeat(pk, G, axis=2)
        want = ref.mha_reference(q, k, v, phi_q=pq, phi_k=pk_full,
                                 mask_kind="causal")
        np.testing.assert_allclose(out, want, atol=3e-5)

    @pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
    def test_decode_per_kv_head_phi_k(self, impl):
        """Old Pallas path: jnp.broadcast_to((B,S,KVH,R) -> (B,S,H,R))
        raises; old XLA path hit the same broadcast in core attention."""
        q, k, v, pq, pk, _ = _setup(key=4)
        lengths = jnp.asarray(LENGTHS)
        bidx = jnp.arange(B)
        q_dec = q[bidx, LENGTHS - 1][:, None]
        pq_dec = pq[bidx, LENGTHS - 1][:, None]
        out = ops.flash_decode(q_dec, _hm(k), _hm(v), lengths, phi_q=pq_dec,
                               phi_k=_hm(pk), impl=impl, block_k=16)
        want = ref.decode_reference(q_dec, k, v, lengths, phi_q=pq_dec,
                                    phi_k=jnp.repeat(pk, G, axis=2))
        np.testing.assert_allclose(out, want, atol=3e-5)


class TestPagedDecodeParity:
    """The paged path (page pool + page table + per-page factor slab) must
    agree with the contiguous path for every bias mode, on both impls, with
    physically scrambled pages."""

    PS = 16                                       # page_size == block_k

    def _paginate(self, k, v, n_extra=5, seed=0):
        p = S // self.PS
        n_pages = B * p + n_extra
        rng = np.random.RandomState(seed)
        perm = rng.permutation(n_pages)[:B * p].reshape(B, p)
        kp = np.array(jax.random.normal(jax.random.PRNGKey(90 + seed),
                                        (KVH, n_pages, self.PS, D)))
        vp = np.array(jax.random.normal(jax.random.PRNGKey(91 + seed),
                                        (KVH, n_pages, self.PS, D)))
        slab = np.zeros((n_pages, self.PS, 2), np.float32)
        pos = np.arange(S, dtype=np.float32)
        slab_log = np.stack([np.ones(S, np.float32), pos], -1)
        for b in range(B):
            for j in range(p):
                rows = slice(j * self.PS, (j + 1) * self.PS)
                kp[:, perm[b, j]] = np.asarray(_hm(k)[b, :, rows])
                vp[:, perm[b, j]] = np.asarray(_hm(v)[b, :, rows])
                slab[perm[b, j]] = slab_log[j * self.PS:(j + 1) * self.PS]
        return (jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(slab),
                jnp.asarray(perm, jnp.int32))

    @pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
    @pytest.mark.parametrize("mode", ["none", "phi", "alibi"])
    def test_paged_matches_contiguous(self, impl, mode):
        q, k, v, pq, pk, slopes = _setup(key=6)
        lengths = jnp.asarray(LENGTHS)
        bidx = jnp.arange(B)
        q_dec = q[bidx, LENGTHS - 1][:, None]
        kp, vp, slab, pt = self._paginate(k, v)
        kw_c = _bias_kwargs(mode, pq, _hm(pk), slopes)
        kw_p = dict(kw_c)
        if mode == "phi":
            # paged mode reads key factors from the per-page slab (here the
            # rank-2 ALiBi position factor [1, pos]); q factors are per head
            # and must match the slab's rank
            pq2 = jax.random.normal(jax.random.PRNGKey(66), (B, 1, H, 2))
            kw_c = {"phi_q": pq2,
                    "phi_k": jnp.broadcast_to(
                        jnp.stack([jnp.ones(S), jnp.arange(S, dtype=jnp.float32)],
                                  -1), (B, KVH, S, 2))}
            kw_p = {"phi_q": pq2, "phi_k": slab}
        want = ops.flash_decode(q_dec, _hm(k), _hm(v), lengths, impl="xla",
                                block_k=16, **kw_c)
        got = ops.flash_decode(q_dec, kp, vp, lengths, page_table=pt,
                               impl=impl, block_k=16, **kw_p)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=3e-5,
                                   err_msg=f"paged {impl}/{mode}")


class TestCarriedPoolDecodeStep:
    """``lm.decode_step`` carries the whole ``(L, ...)`` page pools through
    its layer scan, writes token rows into them in place and reads layer l
    out of the stack. It must give what a scan over the layers' pools
    gives, each layer's pools sliced out, decoded as a stack of one and
    stacked back: the same logits and pools, bitwise under XLA and to fp32
    tolerance in the interpreted kernel. Slot 0 writes the first row of a
    new page, slot 1 the middle of one, and slot 2 is frozen (length 0):
    exactly those two rows change, its pages stay as they were."""

    PS, N_PAGES, P_SLOT = 4, 12, 3
    LENGTHS = np.array([4, 6, 0], np.int32)      # next rows: 4 (page 1), 6
    TABLE = np.array([[7, 2, 9], [0, 11, 5], [3, 8, 1]], np.int32)

    @staticmethod
    def _per_layer(params, cache, tokens, cfg):
        """The reference: the layer scan slices each layer's pools out
        (``xs``), decodes them as a stack of one and stacks the results back
        (``ys``)."""
        from repro.models import lm
        active = cache["length"] > 0
        lengths = cache["length"] + active.astype(jnp.int32)
        x = lm._embed_in(params, tokens, None, cfg)
        phi = cache["pages_phi"]
        pos = lengths - 1
        page = jnp.where(active, cache["page_table"][jnp.arange(pos.size),
                                                     pos // phi.shape[1]],
                         phi.shape[0])
        row = jnp.zeros((pos.size, phi.shape[-1]), jnp.float32)
        row = row.at[:, 0].set(1.0).at[:, 1].set(pos.astype(jnp.float32))
        phi = phi.at[page, pos % phi.shape[1]].set(row, mode="drop")

        def body(x, inp):
            lp, cl = inp
            x, ncl = lm._layer_decode(
                lp, {k: v[None] for k, v in cl.items()}, x, lengths, cfg,
                active=active, page_table=cache["page_table"],
                phi_pages=phi, layer=0)
            return x, {k: v[0] for k, v in ncl.items()}

        pools = {k: cache[k] for k in ("pages_k", "pages_v")}
        x, pools = jax.lax.scan(body, x, (lm._compute_layers(params, cfg),
                                          pools))
        hid = lm.rmsnorm(x, params["final_norm"])
        logits = lm.unembed_logits(hid, params["embed"].astype(hid.dtype))
        return logits, {**cache, **pools, "pages_phi": phi,
                        "length": lengths}

    @pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
    @pytest.mark.parametrize("arch", [
        "gpt2_alibi_15b",                  # MHA
        "stablelm_12b",                    # GQA 4 / 2
        "command_r_plus_104b",             # GQA 8 / 2
    ], ids=["mha", "gqa", "gqa-8-2"])
    def test_matches_per_layer_loop(self, arch, impl):
        from repro.configs import smoke_config
        from repro.models import get_model, lm
        from repro.models.common import init_params
        cfg = smoke_config(arch).replace(attn_impl=impl)
        model = get_model(cfg)
        params = init_params(model.template(), jax.random.PRNGKey(3))
        cache = lm.init_paged_cache(cfg, 3, self.N_PAGES, self.PS,
                                    self.P_SLOT)
        ks = jax.random.split(jax.random.PRNGKey(4), 3)
        for key, leaf in zip(ks, ("pages_k", "pages_v", "pages_phi")):
            cache[leaf] = jax.random.normal(key, cache[leaf].shape,
                                            cache[leaf].dtype)
        cache["length"] = jnp.asarray(self.LENGTHS)
        cache["page_table"] = jnp.asarray(self.TABLE)
        tokens = jnp.asarray([[5], [17], [9]], jnp.int32)

        got_logits, got = jax.jit(functools.partial(
            lm.decode_step, cfg=cfg))(params, cache, tokens)
        want_logits, want = jax.jit(functools.partial(
            self._per_layer, cfg=cfg))(params, cache, tokens)

        check = (np.testing.assert_array_equal if impl == "xla" else
                 functools.partial(np.testing.assert_allclose, rtol=1e-5,
                                   atol=1e-5))
        check(np.asarray(got_logits), np.asarray(want_logits))
        for leaf in ("pages_k", "pages_v", "pages_phi", "length"):
            check(np.asarray(got[leaf]), np.asarray(want[leaf]),
                  err_msg=leaf)
        # the token rows landed at (page 2, row 0) and (page 11, row 2), in
        # every layer and kv head; nothing else moved, so the frozen slot's
        # pages are bit-identical to the input
        for leaf in ("pages_k", "pages_v"):      # (L, KVH, n_pages, ps, hd)
            diff = np.asarray(got[leaf]) != np.asarray(cache[leaf])
            rows = np.moveaxis(diff, (2, 3), (0, 1))
            changed = np.any(rows, axis=tuple(range(2, rows.ndim)))
            assert sorted(zip(*np.nonzero(changed))) == [(2, 0), (11, 2)]
            for page, row in ((2, 0), (11, 2)):  # every layer and kv head
                assert np.any(rows[page, row], axis=-1).all(), leaf


class TestFoldedHeadPagedDecode:
    """The paged kernel moves a block of ``Hb`` kv heads a grid step. It
    must match the XLA paged path in every bias mode with an inactive slot
    (length 0), a length of 1, lengths at a page boundary ±1, stale table
    entries past each slot's length (in the pool and out of it), a traced
    layer of a stack of 3, and a VMEM budget small enough that ``Hb`` is a
    proper divisor of ``KVH`` (the reduced-block branch)."""

    PS, N_PAGES, P_SLOT, N_LAYERS, LAYER, D = 8, 24, 4, 3, 1, 128
    LENGTHS = np.array([0, 1, 7, 8, 9, 25], np.int32)
    # (H, KVH): phi is MHA (G = 1, padded to 8), none GQA with G = 4
    HEADS = {"phi": (4, 4), "none": (16, 4), "alibi": (8, 4)}

    def _inputs(self, mode):
        h, kvh = self.HEADS[mode]
        b, ps = len(self.LENGTHS), self.PS
        ks = jax.random.split(jax.random.PRNGKey(7), 4)
        pool = (self.N_LAYERS, kvh, self.N_PAGES, ps, self.D)
        kp = jax.random.normal(ks[0], pool)
        vp = jax.random.normal(ks[1], pool)
        q = jax.random.normal(ks[2], (b, 1, h, self.D))
        rng = np.random.RandomState(3)
        # stale entries: in-pool pages of other slots and ids off the pool
        table = rng.randint(-3, self.N_PAGES + 5, (b, self.P_SLOT))
        live = rng.permutation(self.N_PAGES)
        for i, n in enumerate(self.LENGTHS):
            used = -(-int(n) // ps)
            table[i, :used], live = live[:used], live[used:]
        kw = {}
        if mode == "phi":
            # the layer- and head-shared slab of ALiBi key factors (1, pos)
            slab = np.zeros((self.N_PAGES, ps, 128), np.float32)
            slab[..., 0] = 1.0
            slab[..., 1] = rng.randint(0, 700, (self.N_PAGES, ps))
            kw = {"phi_q": jax.random.normal(ks[3], (b, 1, h, 2)) * 0.05,
                  "phi_k": jnp.asarray(slab)}
        elif mode == "alibi":
            kw = {"slopes": jnp.asarray(0.5 ** np.arange(1, h + 1),
                                        jnp.float32)}
        return q, kp, vp, jnp.asarray(table, jnp.int32), kw

    @pytest.mark.parametrize("budget", ["whole", "reduced"])
    @pytest.mark.parametrize("mode", ["phi", "none", "alibi"])
    def test_matches_xla_paged(self, mode, budget, monkeypatch):
        from repro.kernels import flash_decode as fd
        seen, real = [], fd._head_block
        monkeypatch.setattr(fd, "_head_block",
                            lambda *a, **k: seen.append(real(*a, **k))
                            or seen[-1])
        if budget == "reduced":
            # two heads' blocks fit, four do not (~45-55 KB a head here)
            monkeypatch.setattr(fd, "_VMEM_BUDGET", 120_000)
        q, kp, vp, table, kw = self._inputs(mode)
        lengths = jnp.asarray(self.LENGTHS)
        layer = jnp.asarray(self.LAYER, jnp.int32)
        call = functools.partial(ops.flash_decode, q, kp, vp, lengths,
                                 page_table=table, layer=layer, **kw)
        got = np.asarray(call(impl="pallas_interpret"))
        want = np.asarray(call(impl="xla"))
        kvh = self.HEADS[mode][1]
        assert seen == ([kvh] if budget == "whole" else [2]), seen
        live = self.LENGTHS > 0
        np.testing.assert_allclose(got[live], want[live], atol=3e-5,
                                   err_msg=f"{mode}/{budget}")
        # an inactive slot reads nothing and returns zeros, not NaN
        np.testing.assert_array_equal(got[~live], 0.0)
        # the layer index matters: another layer's pools give other values
        other = np.asarray(call(impl="xla", layer=jnp.asarray(0, jnp.int32)))
        assert np.abs(other[live] - want[live]).max() > 1e-2

    @pytest.mark.parametrize("cell", ["decode-short", "doc-qa"])
    def test_grid_has_one_step_per_slot_and_page(self, cell):
        """At the cells' widths every kv head fits one step: the grid is
        ``b x p_max`` steps, not ``b x kvh x p_max``."""
        import math
        from repro.kernels.flash_decode import flash_decode_paged_fwd
        if cell == "decode-short":    # gpt2_alibi_15b: 32 padded heads, phi
            b, kvh, g, p_max, n_pages, n_layers, phi = 8, 32, 8, 2, 32, 48, 1
        else:                         # granite: 8 kv heads, G 4 padded to 8
            b, kvh, g, p_max, n_pages, n_layers, phi = 32, 8, 8, 24, 768, 4, 0
        ps = d = 128
        sds = jax.ShapeDtypeStruct
        pool = sds((n_layers, kvh, n_pages, ps, d), jnp.bfloat16)
        args = [sds((b, kvh, g, d), jnp.bfloat16), pool, pool,
                sds((b,), jnp.int32), sds((b, p_max), jnp.int32)]
        if phi:
            args += [sds((b, kvh, g, 128), jnp.float32),
                     sds((1, n_pages, ps, 128), jnp.float32)]
        jaxpr = jax.make_jaxpr(lambda *a: flash_decode_paged_fwd(
            *a, layer=jnp.int32(1), scale=0.125))(*args)
        calls = [e for e in jaxpr.jaxpr.eqns
                 if e.primitive.name == "pallas_call"]
        assert [e.params["name"] for e in calls] == ["flash_decode_paged"]
        grid = calls[0].params["grid_mapping"].grid
        assert math.prod(grid) == b * p_max, grid
