"""Staticcheck CLI: ``python -m flashmoe_tpu.staticcheck``.

Examples::

    python -m flashmoe_tpu.staticcheck --all        # every engine (default)
    python -m flashmoe_tpu.staticcheck --invariants # jaxpr knob matrix
    python -m flashmoe_tpu.staticcheck --census     # collective census
    python -m flashmoe_tpu.staticcheck --lint       # AST rules only
    python -m flashmoe_tpu.staticcheck --lint --paths somefile.py
    python -m flashmoe_tpu.staticcheck --all --json # machine-readable

Exit status: 0 = clean, 1 = violations (printed / in the JSON doc).
Runtime budget: the full ``--all`` run traces the whole invariant and
census matrices on a virtual 8-device CPU mesh in well under a minute
(~15 s invariants + ~5 s census + ~5 s lint on a laptop-class CPU) —
fast-lane material, and wired into tier-1 via tests/test_staticcheck.py.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys


def _ensure_virtual_mesh():
    """The tracing engines need >= 8 devices.  Mirror tests/conftest.py:
    force the virtual CPU backend — static analysis never needs silicon."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m flashmoe_tpu.staticcheck",
        description="static verification of the MoE knob matrix: jaxpr "
                    "invariants, collective census, AST lint")
    ap.add_argument("--all", action="store_true",
                    help="run every engine (default when none selected)")
    ap.add_argument("--invariants", action="store_true",
                    help="jaxpr invariant engine (backend x knob matrix)")
    ap.add_argument("--census", action="store_true",
                    help="collective census vs analysis/planner models")
    ap.add_argument("--lint", action="store_true",
                    help="AST lint (in-graph hygiene, decision names, "
                         "doc sync, slow-mark budget guard)")
    ap.add_argument("--paths", nargs="+", default=None,
                    help="restrict the lint to explicit files")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON document instead of text")
    args = ap.parse_args(argv)

    run_all = args.all or not (args.invariants or args.census or args.lint)
    violations = []
    doc: dict = {"engines": {}}

    if run_all or args.lint:
        from flashmoe_tpu.staticcheck.lint import run_lint

        v = run_lint(paths=args.paths)
        violations += v
        doc["engines"]["lint"] = {"violations": len(v)}

    if run_all or args.invariants or args.census:
        _ensure_virtual_mesh()

    if run_all or args.invariants:
        from flashmoe_tpu.staticcheck.invariants import run_invariants

        v = run_invariants()
        violations += v
        doc["engines"]["invariants"] = {"violations": len(v)}

    if run_all or args.census:
        from flashmoe_tpu.staticcheck.census import (
            report_table, run_census,
        )

        v, rows = run_census()
        violations += v
        doc["engines"]["census"] = {
            "violations": len(v),
            "rows": [dataclasses.asdict(r) for r in rows],
        }
        if not args.json:
            print("\n## collective census (traced graph vs "
                  "analysis/planner models)\n")
            print(report_table(rows))

    doc["violations"] = [dataclasses.asdict(v) for v in violations]
    doc["ok"] = not violations
    if args.json:
        json.dump(doc, sys.stdout)
        print()
    else:
        print()
        if violations:
            print(f"FAIL: {len(violations)} violation(s)")
            for v in violations:
                print(f"  {v}")
        else:
            engines = ", ".join(doc["engines"]) or "none"
            print(f"OK: no violations ({engines})")
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main())
