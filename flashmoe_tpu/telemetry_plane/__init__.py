"""Live telemetry plane: what the job looks like WHILE it runs.

Everything observability built so far is post-hoc: the flight recorder
ring, the phase ledger, Perfetto exports, and crash postmortems are all
artifacts you read after the fact.  This package is the live half of
that story (docs/OBSERVABILITY.md "Live telemetry plane"):

* :mod:`flashmoe_tpu.telemetry_plane.sketch` — bounded-memory streaming
  aggregation: a dependency-free P²-style quantile sketch (O(1) memory
  rolling p50/p90/p99 instead of full-history percentiles) and a
  bucketed windowed rate (tokens/s, admits/s, evictions/s).  Exposed
  through :meth:`flashmoe_tpu.utils.telemetry.Metrics.sketch`.
* :mod:`flashmoe_tpu.telemetry_plane.tracing` — request-scoped
  distributed tracing for the serving engine: a trace context minted at
  ``serve.admit`` and threaded through the whole request lifecycle
  (queued → admit → prefill → per-step decode → (evict → re-queue →
  re-prefill)* → retire), recorded via the existing telemetry
  span-listener hook (chainable with a PR 8 :class:`PhaseTimeline`, so
  the two join), exported as one Perfetto track per request through
  :func:`flashmoe_tpu.profiler.export.request_trace_document` and
  rendered by ``python -m flashmoe_tpu.observe --trace <rid>``.
* :mod:`flashmoe_tpu.telemetry_plane.server` — stdlib ``http.server``
  scrape endpoints on a background thread: ``/metrics`` (Prometheus
  text exposition, ``text/plain; version=0.0.4``), ``/healthz`` (SLO
  episode state, controller budgets/cooldowns, last checkpoint step,
  queue/occupancy), ``/vars`` (JSON snapshot of the resolved plan and
  active knobs).  Default off everywhere = zero threads = byte-identical
  behavior; armed via ``--telemetry-port`` on the train and serving
  CLIs.  Per-host JSONL shard helpers feed ``observe --merge``.

Import the submodules directly — this ``__init__`` stays import-light
(the sketch is pulled lazily by :class:`Metrics` on first use).
"""
