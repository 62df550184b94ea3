"""Chaos drill CLI: ``python -m flashmoe_tpu.chaos``.

Runs the fault matrix (:data:`flashmoe_tpu.chaos.FAULTS`) against a
small model and reports, per fault: recovery outcome, the tier that
absorbed it, loss-of-work, and the telemetry evidence.  Exit code 0 iff
every drilled fault recovered — CI-able.

``--obs-dir`` exports the postmortem artifacts next to the report:
``decisions.jsonl`` (every structured decision the drills produced —
planner fallbacks, checkpoint fallbacks, skipped updates) and
``drill_results.jsonl`` (one result object per fault).
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _ensure_virtual_devices(n: int = 2) -> None:
    """The ``device_loss`` drill shrinks the world across a restart,
    which needs at least two devices.  On a plain CPU host, ask XLA for
    virtual ones.  jax is already imported by the package ``__init__``
    at this point, but XLA only reads the flag when a BACKEND first
    initializes — so setting the env here still works as long as
    nothing has called into jax yet (harmlessly ignored otherwise)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip())


def main(argv=None) -> int:
    _ensure_virtual_devices()
    p = argparse.ArgumentParser(
        prog="python -m flashmoe_tpu.chaos",
        description="drill the fault-tolerance ladder (docs/RESILIENCE.md)")
    p.add_argument("--faults", default=None,
                   help="comma-separated subset (default: full matrix)")
    p.add_argument("--fault", action="append", default=None,
                   metavar="NAME",
                   help="drill a single fault (repeatable; composes "
                        "with --faults) — the CI fast path for smoking "
                        "one fault without the full slow matrix")
    p.add_argument("--steps", type=int, default=6,
                   help="training steps per drill (default 6)")
    p.add_argument("--checkpoint-every", type=int, default=2,
                   help="checkpoint interval (default 2)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--obs-dir", default=None,
                   help="export decisions.jsonl + drill_results.jsonl here")
    p.add_argument("--json", action="store_true",
                   help="print results as JSON instead of the table")
    args = p.parse_args(argv)

    from flashmoe_tpu.chaos import FAULTS
    from flashmoe_tpu.chaos.drill import run_drill

    faults = ([f.strip() for f in args.faults.split(",") if f.strip()]
              if args.faults else [])
    for f in args.fault or []:
        if f.strip() and f.strip() not in faults:
            faults.append(f.strip())
    if not args.faults and not args.fault:
        faults = list(FAULTS)
    if not faults:
        # '--faults ,' must not report "all recovered" over zero drills
        p.error(f"--faults selected no fault; known: {list(FAULTS)}")
    unknown = [f for f in faults if f not in FAULTS]
    if unknown:
        p.error(f"unknown fault(s) {unknown}; known: {list(FAULTS)}")

    results = [run_drill(f, num_steps=args.steps,
                         checkpoint_every=args.checkpoint_every,
                         seed=args.seed) for f in faults]

    if args.obs_dir:
        os.makedirs(args.obs_dir, exist_ok=True)
        with open(os.path.join(args.obs_dir, "decisions.jsonl"), "w") as f:
            for r in results:
                for d in r.decisions:
                    f.write(json.dumps(dict(d, fault=r.fault)) + "\n")
        with open(os.path.join(args.obs_dir,
                               "drill_results.jsonl"), "w") as f:
            for r in results:
                f.write(json.dumps(r.to_json()) + "\n")

    if args.json:
        print(json.dumps([r.to_json() for r in results], indent=2))
    else:
        w = max(len(r.fault) for r in results)
        print(f"{'fault':<{w}}  {'tier':<24} {'ok':<4} {'rerun':>5} "
              f"{'wall_s':>7}  evidence")
        for r in results:
            ev = ", ".join(r.evidence["decision_names"]) or "-"
            status = "PASS" if r.recovered else "FAIL"
            print(f"{r.fault:<{w}}  {r.expected_tier:<24} {status:<4} "
                  f"{r.steps_rerun:>5} {r.wall_s:>7.1f}  {ev}")
            if not r.recovered:
                print(f"{'':<{w}}    -> {r.reason}")
        n_ok = sum(r.recovered for r in results)
        print(f"\n{n_ok}/{len(results)} faults recovered at their "
              f"intended tier")
    return 0 if all(r.recovered for r in results) else 1


if __name__ == "__main__":
    sys.exit(main())
