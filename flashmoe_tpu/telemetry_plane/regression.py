"""Perf-regression sentry: the repo's durable performance trajectory.

Before this module a modeled-cost regression in a PR was only caught if
a golden number happened to move.  It gives the framework a memory:

* :func:`append_run` persists one run's metric points to
  ``obs/history.jsonl`` — one JSON line per run: ``{"run", "meta",
  "metrics": {key: {"value", "unit"}}}`` — keyed by the measurement-
  identity strings the bench/serving records already carry (the PR 5/6/
  12 convention: the ``metric`` field encodes path/d/chunks/wire/slices,
  so a compressed timing can never baseline an uncompressed one);
* :func:`collect_points` extracts those points from any record pile
  (bench records, serving sweep records, ledger rows, drill summaries);
* :func:`reference_points` computes the deterministic modeled points of
  the golden planner configs (``predicted_ms`` at the golden 8-rank
  mesh) — the CI-stable rows the committed baseline seed is built from;
* :func:`check_regression` compares the NEWEST run against a rolling
  baseline (median of up to ``baseline_n`` prior runs per key) with
  per-unit tolerances, emitting one ``regress.detected`` decision per
  offending metric.

CLI: ``python -m flashmoe_tpu.observe --regression [--ci] [history]``
renders the report; ``--ci`` exits rc 2 when anything regressed.
``bench.py --regression`` appends the run it just measured.
"""

from __future__ import annotations

import json
import math
import os
import time

#: default history location (relative to the repo/session cwd)
DEFAULT_HISTORY = os.path.join("obs", "history.jsonl")

#: relative tolerance per unit before a move counts as a regression;
#: ``_DIR`` says which direction is "worse" (+1 = higher is worse)
UNIT_TOLERANCE = {
    "ms": 0.15,
    "tokens_per_sec": 0.15,
    "ratio_vs_serialized": 0.15,
    "hidden_frac": 0.15,
    "frac": 0.15,
    "accept_rate": 0.15,
    "tokens_per_step": 0.15,
}
DEFAULT_TOLERANCE = 0.25
_DIR = {
    "ms": +1.0,                   # latency: up is worse
    "tokens_per_sec": -1.0,       # throughput: down is worse
    "ratio_vs_serialized": -1.0,  # overlap efficiency: down is worse
    "hidden_frac": -1.0,          # handoff overlap: less hidden = worse
    "frac": +1.0,                 # shed fraction: more shedding = worse
    "accept_rate": +1.0,          # break-even acceptance: up = speculation
                                  # pays later = worse
    "tokens_per_step": -1.0,      # speculation uplift: down is worse
}


def collect_points(records) -> dict[str, dict]:
    """Metric points of one run, keyed by their identity string.

    A point is any record with a string ``metric`` and a finite numeric
    ``value`` (skipped/partial/error records are not a run's numbers
    and must never enter the baseline).  Serving-drill summaries (``ttft_ms_p50`` et al. on a
    ``serve_load[...]`` record) ride along as derived points so the
    sentry watches tail latency, not just the headline value."""
    points: dict[str, dict] = {}
    for rec in records:
        if not isinstance(rec, dict):
            continue
        key = rec.get("metric")
        val = rec.get("value")
        if not isinstance(key, str) or rec.get("skipped") \
                or rec.get("partial") or rec.get("error"):
            continue
        if isinstance(val, bool) or not isinstance(val, (int, float)):
            continue
        points[key] = {"value": float(val),
                       "unit": str(rec.get("unit", ""))}
        for sub in ("ttft_ms_p50", "ttft_ms_p99", "tpot_ms_p50",
                    "predicted_ms"):
            sv = rec.get(sub)
            if isinstance(sv, (int, float)) and not isinstance(sv, bool):
                points[f"{key}.{sub}"] = {"value": float(sv),
                                          "unit": "ms"}
    return points


def reference_points(gen: str = "v5e") -> dict[str, dict]:
    """Deterministic modeled points for the golden planner configs:
    the resolved path's predicted latency on the golden 8-rank mesh.
    Pure cost-model output — stable across machines, which is what the
    committed baseline seed (and its clean-history CI gate) needs."""
    from flashmoe_tpu.config import BENCH_CONFIGS
    from flashmoe_tpu.planner.golden import GOLDEN_CONFIGS, GOLDEN_D
    from flashmoe_tpu.planner.model import predict_paths

    points: dict[str, dict] = {}
    for name in GOLDEN_CONFIGS:
        cfg = BENCH_CONFIGS[name].replace(ep=GOLDEN_D)
        preds = [p for p in predict_paths(cfg, GOLDEN_D, gen)
                 if p.feasible]
        if not preds:
            continue
        win = preds[0]
        points[f"planner_predicted_ms[{name},d={GOLDEN_D},{gen}]"] = {
            "value": round(win.total_ms, 4), "unit": "ms",
        }
        # quantized-store model points (ISSUE 15): the int8 winner's
        # total and the fused[rowwin] weight-stream time — the terms
        # the quant byte model owns, guarded by the sentry from day
        # one so a pricing regression trips `observe --regression
        # --ci` before any silicon measures it
        qcfg = cfg.replace(expert_quant="int8")
        qpreds = predict_paths(qcfg, GOLDEN_D, gen)
        qwin = next((p for p in qpreds if p.feasible), None)
        if qwin is not None:
            points[f"planner_predicted_ms[{name},d={GOLDEN_D},{gen},"
                   f"quant=int8]"] = {
                "value": round(qwin.total_ms, 4), "unit": "ms",
            }
        rw = next((p for p in qpreds if p.path == "fused[rowwin]"),
                  None)
        if rw is not None:
            from flashmoe_tpu.planner.model import _dtype_peak

            _, hbm_bs = _dtype_peak(gen, qcfg)
            points[f"quant_rowwin_weight_ms[{name},d={GOLDEN_D},{gen},"
                   f"quant=int8]"] = {
                "value": round(rw.cost.weight_bytes / hbm_bs * 1e3, 4),
                "unit": "ms",
            }
        # measured-latency plane (ISSUE 17): drive the golden handoff
        # through the virtual clock itself — first-token latency as a
        # request EXPERIENCES it (one decode tick with the modeled DCN
        # transfer overlapping it) and the fleet hidden fraction.
        # Pure vclock arithmetic over cost-model inputs: deterministic,
        # and a drift in EITHER the pricing or the clock's
        # hidden/exposed accounting moves these rows
        from flashmoe_tpu.fabric.vclock import VirtualClock
        from flashmoe_tpu.planner.golden import (
            GOLDEN_KV_PAGE, GOLDEN_KV_PAGES, _predicted_plan,
        )
        from flashmoe_tpu.planner.model import kv_handoff_ms

        base = BENCH_CONFIGS[name]
        tick = _predicted_plan(base, gen, "decode")["total_ms"]
        ms = kv_handoff_ms(base, GOLDEN_KV_PAGES, GOLDEN_KV_PAGE,
                           wire=None)
        vc = VirtualClock(tick_ms=tick)
        t0 = vc.now_ms()
        vc.on_handoff(ms)
        vc.complete_step()
        points[f"fabric_ttft_vclock_ms[{name},d={GOLDEN_D},{gen}]"] = {
            "value": round(vc.now_ms() - t0, 4), "unit": "ms",
        }
        hf = vc.hidden_fraction()
        points[f"fabric_handoff_hidden_frac[{name},d={GOLDEN_D},"
               f"{gen}]"] = {
            "value": round(hf if hf is not None else 1.0, 4),
            "unit": "hidden_frac",
        }
        # serving fault-tolerance plane (ISSUE 18): the modeled
        # replica-crash recovery latency — one decode tick of detection
        # delay (health probes run at step boundaries), the re-streamed
        # KV handoff to the adopting replica, and the first resumed
        # decode tick.  Pure cost-model + vclock arithmetic: a drift in
        # the DCN pricing or the tick model moves this row before any
        # chaos drill measures it
        points[f"fabric_recovery_ms[{name},d={GOLDEN_D},{gen}]"] = {
            "value": round(2 * tick + ms, 4), "unit": "ms",
        }
        # cross-process plane (ISSUE 19): the sub-step heartbeat
        # detection deadline (watchdog hysteresis x decode tick — the
        # virtual ms between a mid-step hang and the stall verdict)
        # and the modeled per-handoff socket-wire overhead for the
        # golden KV payload (tcp vs the free in-process wire).  Pure
        # arithmetic over committed constants: retuning the watchdog
        # default or the framing overhead model trips the sentry
        # before any drill measures it
        from flashmoe_tpu.fabric.leasestore import HeartbeatConfig
        from flashmoe_tpu.fabric.transport import wire_overhead_ms
        from flashmoe_tpu.planner.model import kv_page_mb

        hb = HeartbeatConfig()
        points[f"fabric_heartbeat_detect_ms[{name},d={GOLDEN_D},"
               f"{gen}]"] = {
            "value": round(hb.misses_to_stall * tick, 4), "unit": "ms",
        }
        payload_bytes = int(GOLDEN_KV_PAGES
                            * kv_page_mb(base, GOLDEN_KV_PAGE) * 2**20)
        points[f"fabric_wire_overhead_ms[{name},d={GOLDEN_D},{gen},"
               f"wire=tcp]"] = {
            "value": round(wire_overhead_ms(payload_bytes, "tcp"), 4),
            "unit": "ms",
        }
        # speculative-decoding plane (ISSUE 20): the break-even
        # acceptance of the golden verify depth (the floor the
        # controller's spec-morph trigger defends) and the modeled
        # tokens/step at the golden acceptance rate.  Pure cost-model
        # arithmetic: a verify-span pricing drift moves the break-even,
        # a draft-economics drift moves the uplift — either trips the
        # sentry before any acceptance-rate drill measures it
        from flashmoe_tpu.planner.golden import (
            GOLDEN_SPEC_ACCEPT, GOLDEN_SPEC_K,
        )
        from flashmoe_tpu.planner.model import (
            speculate_break_even, speculate_tokens_per_step,
        )

        points[f"decode_accept_rate[{name},d={GOLDEN_D},{gen},"
               f"spec=k{GOLDEN_SPEC_K}]"] = {
            "value": round(speculate_break_even(
                cfg, GOLDEN_D, gen, verify_tokens=GOLDEN_SPEC_K), 4),
            "unit": "accept_rate",
        }
        points[f"spec_tokens_per_step[{name},d={GOLDEN_D},{gen},"
               f"spec=k{GOLDEN_SPEC_K}]"] = {
            "value": round(speculate_tokens_per_step(
                GOLDEN_SPEC_ACCEPT, GOLDEN_SPEC_K), 4),
            "unit": "tokens_per_step",
        }
    # brownout shed fraction at the default BrownoutConfig against the
    # reference flood: deterministic hysteresis arithmetic — retuning
    # the admission controller's thresholds/debounce moves this row,
    # so an accidental "sheds half the traffic" default trips the
    # sentry before it ships
    from flashmoe_tpu.runtime.controller import BrownoutConfig

    points["fabric_shed_frac[brownout,reference]"] = {
        "value": round(_reference_shed_frac(BrownoutConfig()), 4),
        "unit": "frac",
    }
    return points


#: the reference flood behind ``fabric_shed_frac[brownout,reference]``:
#: per-step arrivals of a front-loaded burst with a long tail, served
#: at ``_REFERENCE_SERVICE_RATE`` requests/step
_REFERENCE_FLOOD = (8, 4, 4, 2, 2, 1, 1, 1, 0, 0, 0, 0)
_REFERENCE_SERVICE_RATE = 2.0


def _reference_shed_frac(bo) -> float:
    """Shed fraction of the reference flood under the hysteretic
    brownout controller — the same enter/exit discipline as
    ``FrontDoor.observe_brownout`` (breach debounce, calm debounce,
    cooldown, episode budget) run over a synthetic queue-depth
    trajectory in pure arithmetic."""
    depth = 0.0
    active = False
    breach = clear = episodes = 0
    cooldown_until = -1
    shed = offered = 0
    for step, a in enumerate(_REFERENCE_FLOOD):
        offered += a
        if active:
            shed += a
        else:
            depth += a
        depth = max(0.0, depth - _REFERENCE_SERVICE_RATE)
        if active:
            calm = depth < bo.queue_low
            clear = clear + 1 if calm else 0
            if clear >= bo.debounce_steps:
                active = False
                clear = 0
                cooldown_until = step + bo.cooldown_steps
        else:
            hot = depth > bo.queue_high
            if hot and step >= cooldown_until \
                    and episodes < bo.episode_budget:
                breach += 1
            else:
                breach = 0
            if breach >= bo.debounce_steps:
                active = True
                breach = 0
                episodes += 1
    return shed / offered if offered else 0.0


def append_run(path: str, points: dict[str, dict], *,
               run: str | None = None, meta: dict | None = None) -> dict:
    """Append one run line to the history (creating directories as
    needed).  Returns the entry written; a run with no points is not
    written (and returns {})."""
    if not points:
        return {}
    entry = {
        "run": run or time.strftime("%Y-%m-%dT%H:%M:%S"),
        "meta": dict(meta or {}),
        "metrics": points,
    }
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "a") as f:
        f.write(json.dumps(entry) + "\n")
    return entry


def load_history(path: str) -> list[dict]:
    """All run entries, oldest first.  Unparseable lines skipped (the
    observe.load_jsonl convention)."""
    if not os.path.exists(path):
        return []
    runs = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict) and isinstance(rec.get("metrics"),
                                                    dict):
                runs.append(rec)
    return runs


def _tolerance(unit: str, overrides: dict | None) -> float:
    if overrides and unit in overrides:
        return float(overrides[unit])
    return UNIT_TOLERANCE.get(unit, DEFAULT_TOLERANCE)


def check_regression(runs: list[dict], *, baseline_n: int = 5,
                     tolerances: dict | None = None,
                     metrics_obj=None) -> dict:
    """Judge the newest run against the rolling baseline.

    For every metric key the newest run shares with at least one prior
    run, baseline = median of that key's values over the last
    ``baseline_n`` prior runs; the move is a regression when it exceeds
    the unit's tolerance in the unit's "worse" direction (higher ms,
    lower tokens/s).  Each regression emits one registered
    ``regress.detected`` decision.  Returns the report dict the CLI
    renders (``regressions`` non-empty = rc 2 under ``--ci``)."""
    report = {"runs": len(runs), "compared": 0, "regressions": [],
              "improvements": [], "new_metrics": [], "rows": []}
    if len(runs) < 2:
        report["note"] = ("need >= 2 runs to compare (newest vs rolling "
                          "baseline); history has "
                          f"{len(runs)}")
        return report
    newest = runs[-1]
    prior = runs[:-1]
    for key, pt in sorted(newest["metrics"].items()):
        vals = [r["metrics"][key]["value"] for r in prior[-baseline_n:]
                if key in r.get("metrics", {})
                and isinstance(r["metrics"][key].get("value"),
                               (int, float))]
        if not vals:
            report["new_metrics"].append(key)
            continue
        vals.sort()
        # true median: even-sized windows average the middle pair (the
        # upper-middle element alone made the sentry more lenient
        # exactly when history is short)
        mid = len(vals) // 2
        baseline = (vals[mid] if len(vals) % 2
                    else (vals[mid - 1] + vals[mid]) / 2.0)
        value = float(pt["value"])
        unit = str(pt.get("unit", ""))
        tol = _tolerance(unit, tolerances)
        direction = _DIR.get(unit, +1.0)  # unknown units: up is worse
        if baseline == 0:
            # rel carries the CHANGE's sign only (any move off a zero
            # baseline is an unbounded relative change); finite
            # sentinel keeps the --json report valid JSON, and the
            # direction multiply below decides bad vs good exactly
            # once — a throughput recovery from a 0-baseline run is an
            # improvement, not a regression
            rel = 0.0 if value == 0 else math.copysign(1e9, value)
        else:
            rel = (value - baseline) / abs(baseline)
        worse = rel * direction       # positive = moved the bad way
        row = {"metric": key, "value": value, "baseline": baseline,
               "unit": unit, "rel_change": round(rel, 4),
               "tolerance": tol, "n_baseline": len(vals),
               "regressed": bool(worse > tol)}
        report["rows"].append(row)
        report["compared"] += 1
        if worse > tol:
            report["regressions"].append(row)
            mo = metrics_obj
            if mo is None:
                from flashmoe_tpu.utils import telemetry as _t

                mo = _t.metrics
            mo.decision(
                "regress.detected", metric=key, value=value,
                baseline=baseline, unit=unit,
                rel_change=row["rel_change"], tolerance=tol,
                run=newest.get("run"))
        elif -worse > tol:
            report["improvements"].append(row)
    return report


def render_text(report: dict) -> str:
    lines = [f"perf sentry: {report['runs']} runs on record, "
             f"{report['compared']} metrics compared, "
             f"{len(report['regressions'])} regression(s)"]
    if report.get("note"):
        lines.append(f"  {report['note']}")
    for row in report["regressions"]:
        lines.append(
            f"  REGRESSED {row['metric']}: {row['value']:g} {row['unit']}"
            f" vs baseline {row['baseline']:g} "
            f"({row['rel_change']:+.1%}, tol ±{row['tolerance']:.0%}, "
            f"n={row['n_baseline']})")
    for row in report["improvements"]:
        lines.append(
            f"  improved  {row['metric']}: {row['value']:g} "
            f"{row['unit']} vs {row['baseline']:g} "
            f"({row['rel_change']:+.1%})")
    if report["new_metrics"]:
        lines.append("  new (no baseline yet): "
                     + ", ".join(report["new_metrics"][:8])
                     + (" ..." if len(report["new_metrics"]) > 8 else ""))
    if not report["regressions"] and report["compared"]:
        lines.append("  all within tolerance")
    return "\n".join(lines)
