"""Analytical performance planner: predicted per-path latency +
automatic path selection.

Synthesizes the byte/FLOP accounting (:mod:`flashmoe_tpu.analysis`),
the overlap bounds (:mod:`flashmoe_tpu.parallel.overlap`), the
per-generation link/peak tables (:mod:`flashmoe_tpu.parallel.topology`)
and measured tuning entries (:mod:`flashmoe_tpu.tuning`) into a
predicted end-to-end latency per execution path, and a selection policy
(predicted winner, measured-winner override) that
``parallel/ep.py`` / ``models/transformer.py`` (``moe_backend='auto'``)
and the serving engine consult.

CLI::

    python -m flashmoe_tpu.planner --config reference --d 8

Model details: :mod:`flashmoe_tpu.planner.model` docstring and
``docs/PLANNER.md``.
"""

from flashmoe_tpu.planner.adapt import (  # noqa: F401
    MorphPlan, measured_ledger, replan,
)
from flashmoe_tpu.planner.drift import (  # noqa: F401
    DriftRecord, OverlapDriftRecord, drift_report, record_drift,
    record_overlap_drift,
)
from flashmoe_tpu.planner.model import (  # noqa: F401
    BACKEND_OF, PathPrediction, explain_table, predict_paths,
)
from flashmoe_tpu.planner.select import (  # noqa: F401
    Selection, resolve_moe_backend, resolve_moe_plan, select_path,
)
