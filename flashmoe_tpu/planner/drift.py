"""Planner drift monitor: predicted vs measured, continuously.

PR 1's planner predicts per-path latency and selects execution paths
from those predictions (plus committed measurements).  Nothing, however,
measured the *prediction error in production* or said when the golden
tables have drifted from reality — the feedback loop RaMP
(arXiv:2604.26039) closes by selecting kernels from measured runtime
signals.  This module is that loop's sensor: every real timing that
flows through it is compared against the analytical prediction for the
same (config, path, d, generation) point, the relative error lands in
telemetry as a ``planner.drift`` decision (plus an error histogram), and
errors past a threshold raise a visible warning that the cost model /
golden tables need recalibration.

``python -m flashmoe_tpu.observe`` summarizes accumulated drift records
offline (:func:`drift_report`).
"""

from __future__ import annotations

import dataclasses
import os
import warnings

from flashmoe_tpu.config import MoEConfig
from flashmoe_tpu.utils.telemetry import metrics

# Relative-error tolerance before a drift warning fires.  The cost model
# is a roofline — it deliberately predicts a *bound*, so real kernels sit
# above it by a config-dependent factor; 0.5 flags only gross divergence
# (a schedule regression, a stale golden table, wrong generation pin),
# not normal roofline optimism.  FLASHMOE_DRIFT_THRESHOLD overrides.
DEFAULT_THRESHOLD = 0.5


def drift_threshold() -> float:
    try:
        return float(os.environ.get("FLASHMOE_DRIFT_THRESHOLD",
                                    DEFAULT_THRESHOLD))
    except ValueError:
        return DEFAULT_THRESHOLD


@dataclasses.dataclass(frozen=True)
class DriftRecord:
    """One predicted-vs-measured comparison."""

    path: str
    gen: str
    d: int
    predicted_ms: float
    measured_ms: float
    rel_error: float            # measured / predicted - 1 (signed)
    threshold: float
    exceeded: bool


def record_drift(cfg: MoEConfig, path: str, measured_ms: float, *,
                 d: int = 1, gen: str | None = None,
                 predicted_ms: float | None = None,
                 threshold: float | None = None,
                 warn: bool = True) -> DriftRecord:
    """Compare one measured latency against the planner's prediction.

    ``path`` is a planner path or family name ('explicit', 'fused',
    'collective', ...).  ``predicted_ms=None`` asks the cost model for
    the prediction (fastest row of the family at this point); pass the
    value a caller already computed to keep the two sides consistent.
    The comparison is recorded as a ``planner.drift`` telemetry decision
    and in the ``planner.drift_abs_rel_error`` histogram; past the
    threshold a RuntimeWarning names the likely causes.
    """
    from flashmoe_tpu import tuning

    gen = gen or tuning.generation()
    if predicted_ms is None:
        from flashmoe_tpu.planner.model import predict_paths

        preds = predict_paths(cfg, d, gen)
        match = [p for p in preds if p.path == path or p.family == path]
        if not match:
            raise ValueError(
                f"no prediction for path {path!r} at d={d}; candidates: "
                f"{sorted({p.path for p in preds})}")
        predicted_ms = min(p.total_ms for p in match)
    if predicted_ms <= 0:
        raise ValueError(f"predicted_ms must be > 0, got {predicted_ms}")
    threshold = drift_threshold() if threshold is None else threshold
    rel = measured_ms / predicted_ms - 1.0
    exceeded = abs(rel) > threshold
    rec = DriftRecord(path=path, gen=gen, d=int(d),
                      predicted_ms=float(predicted_ms),
                      measured_ms=float(measured_ms),
                      rel_error=float(rel), threshold=float(threshold),
                      exceeded=exceeded)
    metrics.decision(
        "planner.drift", path=path, gen=gen, d=int(d),
        predicted_ms=round(float(predicted_ms), 4),
        measured_ms=round(float(measured_ms), 4),
        rel_error=round(float(rel), 4), threshold=float(threshold),
        exceeded=exceeded,
        config=dict(e=cfg.num_experts, k=cfg.expert_top_k,
                    h=cfg.hidden_size, i=cfg.intermediate_size,
                    s=cfg.tokens, wire=cfg.wire_dtype or "off",
                    wire_combine=cfg.wire_dtype_combine or "off"))
    metrics.histogram("planner.drift_abs_rel_error", abs(rel))
    if exceeded and warn:
        warnings.warn(
            f"planner drift on {path!r} (gen={gen}, d={d}): measured "
            f"{measured_ms:.3f} ms vs predicted {predicted_ms:.3f} ms "
            f"({rel:+.0%}, threshold ±{threshold:.0%}) — the cost model "
            f"or golden tables may be stale for this shape; recalibrate "
            f"with `python -m flashmoe_tpu.planner --write-golden` or "
            f"pass a measured mxu_fraction", RuntimeWarning, stacklevel=2)
    return rec


@dataclasses.dataclass(frozen=True)
class PhaseDriftRecord:
    """One per-phase predicted-vs-measured comparison (the cost ledger,
    :mod:`flashmoe_tpu.profiler.ledger`)."""

    path: str
    phase: str
    gen: str
    d: int
    chunks: int
    wire: str
    predicted_ms: float
    measured_ms: float
    rel_error: float            # measured / predicted - 1 (signed)
    threshold: float
    exceeded: bool


def record_phase_drift(cfg: MoEConfig, path: str, phase: str,
                       measured_ms: float, *, predicted_ms: float,
                       d: int = 1, gen: str | None = None,
                       threshold: float | None = None,
                       warn: bool = True) -> PhaseDriftRecord:
    """Compare one measured MoE *phase* time (gate / dispatch a2a /
    expert FFN / combine a2a — the profiler's timeline,
    :mod:`flashmoe_tpu.profiler.spans`) against the analytical model's
    prediction of that same phase.

    This is :func:`record_drift` at phase granularity: where the
    end-to-end monitor can only say "the layer is slower than priced",
    per-phase drift says WHICH term of the cost model is wrong — an
    a2a leg drifting alone points at the transport model (or a sick
    link), the expert phase drifting alone at the roofline's
    mxu_fraction.  Recorded as a ``planner.phase_drift`` decision plus
    the ``planner.phase_drift_abs_rel_error`` histogram; warns past the
    threshold like its end-to-end sibling."""
    from flashmoe_tpu import tuning
    from flashmoe_tpu.ops import wire as wr

    gen = gen or tuning.generation()
    if predicted_ms <= 0:
        raise ValueError(f"predicted_ms must be > 0, got {predicted_ms}")
    threshold = drift_threshold() if threshold is None else threshold
    rel = measured_ms / predicted_ms - 1.0
    exceeded = abs(rel) > threshold
    wire_tag = (f"{wr.canonical_name(cfg.wire_dtype)}/"
                f"{wr.canonical_name(cfg.wire_dtype_combine)}")
    rec = PhaseDriftRecord(
        path=path, phase=phase, gen=gen, d=int(d),
        chunks=int(cfg.a2a_chunks or 1), wire=wire_tag,
        predicted_ms=float(predicted_ms), measured_ms=float(measured_ms),
        rel_error=float(rel), threshold=float(threshold),
        exceeded=exceeded)
    metrics.decision(
        "planner.phase_drift", path=path, phase=phase, gen=gen,
        d=int(d), chunks=rec.chunks, wire=wire_tag,
        predicted_ms=round(float(predicted_ms), 6),
        measured_ms=round(float(measured_ms), 6),
        rel_error=round(float(rel), 4), threshold=float(threshold),
        exceeded=exceeded,
        config=dict(e=cfg.num_experts, k=cfg.expert_top_k,
                    h=cfg.hidden_size, i=cfg.intermediate_size,
                    s=cfg.tokens))
    metrics.histogram("planner.phase_drift_abs_rel_error", abs(rel))
    if exceeded and warn:
        warnings.warn(
            f"phase drift on {path!r}/{phase} (gen={gen}, d={d}): "
            f"measured {measured_ms:.4f} ms vs predicted "
            f"{predicted_ms:.4f} ms ({rel:+.0%}, threshold "
            f"±{threshold:.0%}) — this phase's cost-model term is "
            f"stale for this shape", RuntimeWarning, stacklevel=2)
    return rec


@dataclasses.dataclass(frozen=True)
class OverlapDriftRecord:
    """One predicted-vs-measured overlap-fraction comparison (the
    chunked-pipeline validation loop)."""

    path: str
    gen: str
    d: int
    chunks: int
    predicted_fraction: float
    measured_fraction: float
    rel_error: float            # measured / predicted - 1 (signed)
    threshold: float
    exceeded: bool


def record_overlap_drift(path: str, measured_fraction: float, *,
                         predicted_fraction: float, gen: str, d: int,
                         chunks: int = 1,
                         threshold: float | None = None,
                         warn: bool = True) -> OverlapDriftRecord:
    """Compare a measured overlap efficiency (``measure_overlap``)
    against the analytic bound for the same schedule
    (``overlap.chunked_overlap_bound`` for the chunked XLA pipeline,
    ``overlap.overlap_bound`` for the fused kernel).

    Same contract as :func:`record_drift`, on the dimensionless overlap
    fraction: a ``planner.overlap_drift`` telemetry decision, an
    ``planner.overlap_drift_abs_rel_error`` histogram observation, and
    a RuntimeWarning past the threshold — a chunked schedule whose
    measured hiding falls far short of the priced hiding means the
    pipeline model (or the chunk pick it drives) is stale for this
    shape."""
    if predicted_fraction <= 0:
        raise ValueError(
            f"predicted_fraction must be > 0, got {predicted_fraction}")
    threshold = drift_threshold() if threshold is None else threshold
    rel = measured_fraction / predicted_fraction - 1.0
    exceeded = abs(rel) > threshold
    rec = OverlapDriftRecord(
        path=path, gen=gen, d=int(d), chunks=int(chunks),
        predicted_fraction=float(predicted_fraction),
        measured_fraction=float(measured_fraction),
        rel_error=float(rel), threshold=float(threshold),
        exceeded=exceeded)
    metrics.decision(
        "planner.overlap_drift", path=path, gen=gen, d=int(d),
        chunks=int(chunks),
        predicted_fraction=round(float(predicted_fraction), 4),
        measured_fraction=round(float(measured_fraction), 4),
        rel_error=round(float(rel), 4), threshold=float(threshold),
        exceeded=exceeded)
    metrics.histogram("planner.overlap_drift_abs_rel_error", abs(rel))
    if exceeded and warn:
        warnings.warn(
            f"overlap-fraction drift on {path!r} (gen={gen}, d={d}, "
            f"chunks={chunks}): measured {measured_fraction:.3f} vs "
            f"predicted {predicted_fraction:.3f} ({rel:+.0%}, threshold "
            f"±{threshold:.0%}) — the chunked-pipeline model may be "
            f"stale for this shape; re-sweep a2a_chunks on hardware "
            f"(tuning_data README) or recalibrate with a measured "
            f"mxu_fraction", RuntimeWarning, stacklevel=2)
    return rec


def drift_report(records: list[dict]) -> dict:
    """Summarize the ``planner.drift`` decisions in a pile of JSONL
    records (decision logs, flight-recorder dumps — other records are
    skipped).  Per (path, gen): count, mean/worst |relative error|, and
    how many comparisons exceeded their threshold."""
    by_key: dict[str, dict] = {}
    n = exceeded = 0
    for d in records:
        if d.get("decision") != "planner.drift":
            continue
        n += 1
        exceeded += bool(d.get("exceeded"))
        key = f"{d.get('path', '?')}@{d.get('gen', '?')}"
        b = by_key.setdefault(key, {
            "path": d.get("path", "?"), "gen": d.get("gen", "?"),
            "n": 0, "exceeded": 0, "mean_abs_rel_error": 0.0,
            "worst_rel_error": 0.0,
        })
        rel = float(d.get("rel_error", 0.0))
        b["n"] += 1
        b["exceeded"] += bool(d.get("exceeded"))
        b["mean_abs_rel_error"] += abs(rel)
        if abs(rel) > abs(b["worst_rel_error"]):
            b["worst_rel_error"] = rel
    for b in by_key.values():
        b["mean_abs_rel_error"] = round(b["mean_abs_rel_error"] / b["n"], 4)
        b["worst_rel_error"] = round(b["worst_rel_error"], 4)
    return {"n": n, "exceeded": exceeded,
            "by_path": dict(sorted(by_key.items()))}
