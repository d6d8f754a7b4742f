"""Units for the statcheck static-analysis subsystem.

Three speed tiers: pure synthetic-jaxpr walker units (ms), AST-lint units
on inline snippets (ms), and real-backend contract checks (the legacy
tripwire, seconds) plus one subprocess mesh check (the device count must
be fixed before jax initializes, mirroring tests/test_sharding.py).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.statcheck.hostlint import lint_file, lint_tree
from repro.statcheck.jaxpr_rules import (
    count_primitive,
    eq3_fold_present,
    no_host_callback,
    no_pool_relayout,
    pool_threshold_for,
    walk_eqns,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------- jaxpr rules

class TestWalkEqns:
    def test_descends_into_scan_body(self):
        def f(xs):
            def body(c, x):
                return c, (x * 2.0).T
            return jax.lax.scan(body, 0.0, xs)

        jaxpr = jax.make_jaxpr(f)(jnp.zeros((3, 8, 16)))
        names = {e.primitive.name for e in walk_eqns(jaxpr)}
        # the transpose lives only inside the scan body
        assert "scan" in names and "transpose" in names

    def test_count_primitive_with_size_floor(self):
        def f(a, b):
            return a.T, b.T

        jaxpr = jax.make_jaxpr(f)(jnp.zeros((64, 64)), jnp.zeros((2, 2)))
        assert count_primitive(jaxpr, "transpose") == 2
        assert count_primitive(jaxpr, "transpose",
                               min_operand_size=1000) == 1


class TestNoPoolRelayout:
    def test_flags_pool_sized_transpose(self):
        jaxpr = jax.make_jaxpr(lambda x: x.T)(jnp.zeros((64, 64)))
        found = no_pool_relayout(jaxpr, 4096, program="t")
        assert len(found) == 1
        f = found[0]
        assert f.rule == "no-pool-relayout" and "transpose" in f.eqn

    def test_flags_inside_scan(self):
        """The legacy to_pool transpose lives inside the layer scan — the
        rule must see through it."""
        def f(xs):
            def body(c, x):
                return c, jnp.transpose(x, (1, 0, 2))
            return jax.lax.scan(body, 0.0, xs)

        jaxpr = jax.make_jaxpr(f)(jnp.zeros((2, 32, 8, 16)))
        assert no_pool_relayout(jaxpr, 32 * 8 * 16, program="t")

    def test_passes_token_sized_transpose(self):
        jaxpr = jax.make_jaxpr(lambda x: x.T)(jnp.zeros((4, 8)))
        assert no_pool_relayout(jaxpr, 4096, program="t") == []

    def test_flags_pool_sized_broadcast_and_convert(self):
        def f(x):
            y = jnp.broadcast_to(x[:, None], (64, 2, 64))
            return y.astype(jnp.bfloat16)

        jaxpr = jax.make_jaxpr(f)(jnp.zeros((64, 64)))
        rules_hit = {f.eqn.split(" ")[0]
                     for f in no_pool_relayout(jaxpr, 4096, program="t")}
        assert "broadcast_in_dim" in rules_hit
        assert "convert_element_type" in rules_hit


class TestNoHostCallback:
    def test_flags_pure_callback(self):
        def f(x):
            return jax.pure_callback(
                lambda v: v, jax.ShapeDtypeStruct((4,), jnp.float32), x)

        jaxpr = jax.make_jaxpr(f)(jnp.zeros((4,)))
        found = no_host_callback(jaxpr, program="t")
        assert found and found[0].rule == "no-host-callback"

    def test_clean_program_passes(self):
        jaxpr = jax.make_jaxpr(lambda x: x * 2)(jnp.zeros((4,)))
        assert no_host_callback(jaxpr, program="t") == []


class TestEq3Fold:
    def test_fold_concat_detected(self):
        def f(q, phi):
            return jnp.concatenate([q, phi], axis=-1)

        jaxpr = jax.make_jaxpr(f)(jnp.zeros((2, 4, 32)),
                                  jnp.zeros((2, 4, 8)))
        assert eq3_fold_present(jaxpr, 32, 8, program="t") == []

    def test_missing_fold_flagged(self):
        jaxpr = jax.make_jaxpr(lambda q: q @ q.T)(jnp.zeros((4, 32)))
        found = eq3_fold_present(jaxpr, 32, 8, program="t")
        assert found and found[0].rule == "eq3-fold"

    def test_wrong_width_concat_not_mistaken_for_fold(self):
        def f(q, phi):
            return jnp.concatenate([q, phi], axis=-1)

        jaxpr = jax.make_jaxpr(f)(jnp.zeros((2, 4, 32)),
                                  jnp.zeros((2, 4, 4)))   # rank 4, not 8
        assert eq3_fold_present(jaxpr, 32, 8, program="t")


class TestPoolThreshold:
    def test_kv_leaves_per_layer(self):
        cache = {"pages_k": jnp.zeros((2, 32, 4, 2, 40)),
                 "pages_v": jnp.zeros((2, 32, 4, 2, 40)),
                 "length": jnp.zeros((4,), jnp.int32)}
        assert pool_threshold_for(cache, 2) == 32 * 4 * 2 * 40

    def test_ssm_fallback(self):
        cache = {"ssm_h": jnp.zeros((2, 4, 8, 16)),
                 "length": jnp.zeros((4,), jnp.int32)}
        assert pool_threshold_for(cache, 2) == 4 * 8 * 16

    def test_none_when_nothing_pool_shaped(self):
        assert pool_threshold_for(
            {"length": jnp.zeros((4,), jnp.int32)}, 2) is None


# ------------------------------------------------------------- contracts

class TestContracts:
    def test_kernel_layout_clean(self):
        from repro.statcheck.contracts import check_family
        assert check_family("dense") == []

    def test_legacy_tripwire_fires(self):
        """The built-in negative test: a layer scan that transposes each
        layer's pool must trip the decode-step relayout rule at the dense
        family's real threshold."""
        from repro.statcheck.contracts import verify_tripwire
        assert verify_tripwire() == []   # empty = the tripwire DID fire


# -------------------------------------------------------------- hostlint

def _lint_src(tmp_path, source, **roles):
    p = tmp_path / "mod.py"
    p.write_text(textwrap.dedent(source))
    return lint_file(str(p), **roles)


class TestHostJnp:
    def test_flags_jax_import_and_use(self, tmp_path):
        found = _lint_src(tmp_path, """
            import jax.numpy as jnp
            def free(pages):
                return jnp.sum(pages)
            """, host=True)
        assert {f.rule for f in found} == {"host-jnp"}
        assert len(found) == 2      # the import and the use

    def test_suppression_comment(self, tmp_path):
        found = _lint_src(tmp_path, """
            import jax  # statcheck: allow(host-jnp)
            """, host=True)
        assert found == []

    def test_numpy_is_fine(self, tmp_path):
        found = _lint_src(tmp_path, """
            import numpy as np
            def free(pages):
                return np.sum(pages)
            """, host=True)
        assert found == []


class TestHostAssert:
    def test_flags_bare_assert(self, tmp_path):
        found = _lint_src(tmp_path, """
            def free(pages, refs):
                assert refs[pages[0]] > 0, "double free"
                refs[pages[0]] -= 1
            """, host=True)
        assert [f.rule for f in found] == ["host-assert"]
        assert "python -O" in found[0].message

    def test_suppression_comment(self, tmp_path):
        found = _lint_src(tmp_path, """
            def free(pages, refs):
                assert refs[pages[0]] > 0  # statcheck: allow(host-assert)
            """, host=True)
        assert found == []

    def test_typed_raise_is_fine(self, tmp_path):
        found = _lint_src(tmp_path, """
            def free(pages, refs):
                if refs[pages[0]] <= 0:
                    raise PoolError("double free")
            """, host=True)
        assert found == []

    def test_serve_only_module_exempt(self, tmp_path):
        # backend/sampling are serve (device code allowed) but not HOST:
        # jitted-side asserts there are trace-time shape checks, not
        # runtime accounting
        found = _lint_src(tmp_path, """
            def validate(x):
                assert x.ndim == 2
            """, serve=True)
        assert found == []


class TestHostSync:
    def test_flags_block_until_ready(self, tmp_path):
        found = _lint_src(tmp_path, """
            def step(self):
                self.logits.block_until_ready()
            """, serve=True)
        assert found and found[0].rule == "host-sync"

    def test_flags_asarray_on_device_state_in_loop(self, tmp_path):
        found = _lint_src(tmp_path, """
            import numpy as np
            def drain(self):
                out = []
                for _ in range(8):
                    out.append(np.asarray(self._cache["length"]))
                return out
            """, serve=True)
        assert found and found[0].rule == "host-sync"

    def test_asarray_outside_loop_passes(self, tmp_path):
        found = _lint_src(tmp_path, """
            import numpy as np
            def snapshot(self):
                return np.asarray(self._cache["length"])
            """, serve=True)
        assert found == []


class TestBlockspecBounds:
    def test_unclamped_index_map_flagged(self, tmp_path):
        found = _lint_src(tmp_path, """
            def make(n_pages):
                def m(b, j, pt_ref):
                    return (b, pt_ref[b, j], 0, 0)
                return m
            """, kernel=True)
        assert found and found[0].rule == "blockspec-bounds"

    def test_clamped_index_map_passes(self, tmp_path):
        found = _lint_src(tmp_path, """
            import jax.numpy as jnp
            def make(n_pages):
                def m(b, j, pt_ref):
                    return (b, jnp.clip(pt_ref[b, j], 0, n_pages - 1), 0, 0)
                return m
            """, kernel=True)
        assert found == []

    def test_kernel_body_exempt(self, tmp_path):
        # kernel bodies subscript refs but never return index tuples
        found = _lint_src(tmp_path, """
            def kernel(q_ref, o_ref):
                o_ref[...] = q_ref[...] * 2.0
            """, kernel=True)
        assert found == []


def test_repo_tree_is_lint_clean():
    """The satellite 'fix any host-path violations the lint finds' holds
    by construction: the live tree has zero findings."""
    assert lint_tree(REPO) == []


# ------------------------------------------------------------ mesh rules

class TestMeshRuleUnits:
    def test_check_collectives_text_rules(self):
        from repro.statcheck.mesh_rules import check_collectives
        good = "fusion all-reduce f32 all-gather"
        assert check_collectives(good, program="t") == []
        assert check_collectives("fusion add", program="t")  # none present
        assert check_collectives(good, program="t",
                                 expect_all=("reduce-scatter",))
        bad = check_collectives(good, program="t", forbid=("all-gather",))
        assert bad and bad[0].rule == "mesh-collectives"

    def test_state_axes_vocab_typo_flagged(self):
        from repro.dist.sharding import Rules
        from repro.statcheck.mesh_rules import check_state_axes
        rules = Rules()
        ok = {"pages_k": (None, None, None, "kv_heads", None)}
        assert check_state_axes(ok, rules, program="t") == []
        typo = {"pages_k": (None, None, None, "kv_head", None)}
        found = check_state_axes(typo, rules, program="t")
        assert found and found[0].rule == "state-axes-vocab"
        assert "kv_head" in found[0].message


MESH_CHECK = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys, json
    sys.path.insert(0, "src")
    import jax
    from repro.configs import smoke_config
    from repro.dist.sharding import Rules, make_mesh
    from repro.models import get_model
    from repro.models.common import init_params
    from repro.serve.backend import TokenDecodeBackend
    from repro.statcheck.mesh_rules import (check_backend_mesh,
                                            check_shard_divisibility)

    mesh = make_mesh((2, 2), ("data", "model"))
    cfg = smoke_config("stablelm_12b").replace(attn_impl="pallas_interpret")
    model = get_model(cfg)
    params = init_params(model.template(), jax.random.PRNGKey(0))
    be = TokenDecodeBackend(model, params, max_len=32, n_slots=4,
                            page_size=4, mesh=mesh, rules=Rules())
    clean = check_backend_mesh(be, program="dense/decode@(2,2)")

    # negative: a 3-wide dim mapped to a 2-wide mesh axis must be reported
    degrade = check_shard_divisibility(
        {"x": (3, 8)}, {"x": ("kv_heads", None)}, mesh, Rules(),
        program="t", allow=())
    print(json.dumps({"clean": [str(f) for f in clean],
                      "degrade_rules": [f.rule for f in degrade]}))
""")


def test_mesh_collectives_on_2x2_host_mesh():
    """check_backend_mesh passes on a real (2,2)-sharded dense backend and
    the divisibility audit fires on a non-divisible leaf (subprocess: the
    forced device count must precede jax init)."""
    out = subprocess.run(
        [sys.executable, "-c", MESH_CHECK],
        capture_output=True, text=True, timeout=600, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["clean"] == []
    assert rec["degrade_rules"] == ["shard-divisibility"]
