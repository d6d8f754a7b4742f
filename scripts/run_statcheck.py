#!/usr/bin/env python3
"""Static-contract gate over the serve stack (CI: static-contracts job).

Runs the three ``repro.statcheck`` layers and exits nonzero on any
finding, printing each as ``[rule] program: message [offending eqn]``:

1. AST host-path lint (stdlib-only — runs even without jax installed;
   the CI lint job calls ``--lint-only``).
2. Trace-time jaxpr contracts per cache family: the ISSUE-5 pool-relayout
   tripwire on the decode step, no host callbacks inside jit, the Eq. 3
   fold on the precision-free factored-bias path, the pow2 recompile-key
   bound — plus a built-in NEGATIVE test proving a per-layer pool
   transpose still trips the relayout rule (skip with ``--skip-negative``).
3. Mesh/HLO checks (``--mesh``, needs >= 4 devices — forces 4 host
   devices when real ones are absent): real collectives in the sharded
   decode HLO, state axes in the Rules vocabulary, no silent pool
   degradation.

Examples::

    PYTHONPATH=src python scripts/run_statcheck.py
    PYTHONPATH=src python scripts/run_statcheck.py --families dense,ring
    python scripts/run_statcheck.py --lint-only     # no jax needed
    PYTHONPATH=src python scripts/run_statcheck.py --mesh

The default ``--impl pallas_interpret`` traces the Pallas dispatch, so
CPU CI sees the exact decode jaxpr the TPU path would run (see
statcheck/README.md).
"""
from __future__ import annotations

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "src"))

DEFAULT_FAMILIES = "dense,moe,ring,ssm,pairformer"


def run_lint() -> list:
    from repro.statcheck.hostlint import lint_tree
    return lint_tree(REPO)


def run_contract_checks(families, impl, self_test) -> list:
    from repro.statcheck.contracts import run_contracts
    return run_contracts(families, impl=impl, self_test=self_test)


def run_mesh_checks(impl: str) -> list:
    """The serve_sharded collective assert as a statcheck rule: a (2, 2)
    mesh-sharded dense backend must compile real collectives."""
    import jax
    assert len(jax.devices()) >= 4, \
        f"mesh checks need >= 4 devices, got {len(jax.devices())}"
    from repro.configs import smoke_config
    from repro.dist.sharding import Rules, make_mesh
    from repro.models import get_model
    from repro.models.common import init_params
    from repro.serve.backend import TokenDecodeBackend
    from repro.statcheck.mesh_rules import check_backend_mesh

    mesh = make_mesh((2, 2), ("data", "model"))
    cfg = smoke_config("stablelm_12b").replace(attn_impl=impl)
    model = get_model(cfg)
    params = init_params(model.template(), jax.random.PRNGKey(0))
    be = TokenDecodeBackend(model, params, max_len=32, n_slots=4,
                            page_size=4, mesh=mesh, rules=Rules())
    return check_backend_mesh(be, program="dense/decode@(2,2)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--families", default=DEFAULT_FAMILIES,
                    help=f"comma-separated (default {DEFAULT_FAMILIES})")
    ap.add_argument("--impl", default="pallas_interpret",
                    help="attn_impl for the traced programs (default "
                    "pallas_interpret: the Pallas dispatch the chip runs)")
    ap.add_argument("--lint-only", action="store_true",
                    help="AST host-path lint only (no jax import)")
    ap.add_argument("--no-lint", action="store_true")
    ap.add_argument("--skip-negative", action="store_true",
                    help="skip the built-in relayout-tripwire self test")
    ap.add_argument("--mesh", action="store_true",
                    help="also compile the sharded decode on a (2,2) "
                    "mesh and check collectives (needs >= 4 devices)")
    args = ap.parse_args(argv)

    findings = []
    if not args.no_lint:
        findings += run_lint()
    if not args.lint_only:
        families = [f for f in args.families.split(",") if f]
        findings += run_contract_checks(families, args.impl,
                                        self_test=not args.skip_negative)
        if args.mesh:
            findings += run_mesh_checks(args.impl)

    for f in findings:
        print(f, file=sys.stderr)
    if findings:
        print(f"statcheck: {len(findings)} finding(s)", file=sys.stderr)
        return 1
    scope = "lint" if args.lint_only else \
        f"lint+contracts[{args.families}]" \
        if not args.no_lint else f"contracts[{args.families}]"
    print(f"statcheck passed ({scope})")
    return 0


if __name__ == "__main__":
    # forcing host devices must happen before jax initializes; only when
    # the mesh checks actually need them (mirrors examples/serve_sharded)
    if "--mesh" in sys.argv and "--xla_force_host_platform_device_count" \
            not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4")
    sys.exit(main())
