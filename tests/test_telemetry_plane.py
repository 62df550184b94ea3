"""Live telemetry plane (flashmoe_tpu/telemetry_plane/): quantile
sketch equivalence, exposition-spec compliance, scrape endpoints,
request tracing and shard merge.

The CI-shaped acceptance lives here and in tests/test_serving.py
(tracer drill + mid-drill scrape on the real engine); this file covers
the plane's own mechanics.
"""

import json
import os
import re
import urllib.request

import pytest

from flashmoe_tpu.telemetry_plane.sketch import (
    EXACT_N, P2Quantile, QuantileSketch, WindowedRate,
)
from flashmoe_tpu.utils.telemetry import (
    Metrics, PROM_CONTENT_TYPE, escape_label_value,
)


# ----------------------------------------------------------------------
# Streaming quantile sketch
# ----------------------------------------------------------------------

def test_sketch_exact_below_threshold_matches_pctl():
    """Below EXACT_N observations the sketch IS the nearest-rank
    percentile — the loadgen.pctl definition — so every CI-sized drill
    reports identical numbers through either surface."""
    import random

    from flashmoe_tpu.serving.loadgen import pctl

    rng = random.Random(7)
    vals = [rng.uniform(0.5, 200.0) for _ in range(EXACT_N - 1)]
    s = QuantileSketch()
    for v in vals:
        s.observe(v)
    for q in (0.5, 0.9, 0.99):
        assert s.quantile(q) == pytest.approx(pctl(vals, q), abs=1e-3)
    assert s.mean == pytest.approx(sum(vals) / len(vals))
    assert s.summary()["count"] == len(vals)


def test_sketch_p2_error_band_latency_shaped():
    """Beyond the exact buffer, P² estimates stay within the documented
    ~10% relative band on latency-shaped (lognormal) data, and inside
    the observed range by construction."""
    import random

    rng = random.Random(3)
    vals = [rng.lognormvariate(1.0, 0.6) for _ in range(5000)]
    s = QuantileSketch()
    for v in vals:
        s.observe(v)
    exact = sorted(vals)
    for q in (0.5, 0.9, 0.99):
        true = exact[int(q * len(exact))]
        est = s.quantile(q)
        assert min(vals) <= est <= max(vals)
        assert abs(est - true) / true < 0.10, (q, est, true)
    # monotone across tracked quantiles
    assert s.quantile(0.5) <= s.quantile(0.9) <= s.quantile(0.99)


def test_p2_cell_validation_and_tiny_streams():
    with pytest.raises(ValueError, match="quantile"):
        P2Quantile(1.5)
    c = P2Quantile(0.5)
    assert c.value() is None
    for v in (3.0, 1.0):
        c.observe(v)
    assert c.value() in (1.0, 3.0)
    s = QuantileSketch()
    assert s.quantile(0.5) is None and s.summary() == {"count": 0}


def test_windowed_rate_bounded_buckets():
    t = [100.0]
    r = WindowedRate(window_s=10.0, clock=lambda: t[0])
    for _ in range(5):
        r.add(10)
        t[0] += 1.0
    assert r.rate() == pytest.approx(50 / 5.0)
    t[0] += 100.0                      # window empties
    assert r.rate() == 0.0
    # memory stays O(window): thousands of events, few buckets
    for i in range(5000):
        r.add(1)
        t[0] += 0.001
    assert len(r._buckets) <= 12
    with pytest.raises(ValueError):
        WindowedRate(window_s=0)


# ----------------------------------------------------------------------
# Exposition-spec compliance (satellite)
# ----------------------------------------------------------------------

def test_escape_label_value_hostile():
    assert escape_label_value('a"b') == r'a\"b'
    assert escape_label_value("a\nb") == r"a\nb"
    assert escape_label_value("a\\b") == r"a\\b"
    # backslash first: an already-escaped \n must not double-decode
    assert escape_label_value("\\n") == r"\\n"


def test_prometheus_exposition_compliance_hostile_labels():
    """# HELP + # TYPE per family, sketch summaries with quantile
    labels, hostile label values escaped to single parseable lines,
    and the documented content type constant."""
    m = Metrics()
    m.count("steps")
    m.labeled_gauge("build_info", 1.0,
                    host='evil"host\nwith\\stuff', slice="s/0")
    for v in range(100):
        m.sketch("serve.ttft_ms", float(v))
    with m.timer("fwd"):
        pass
    m.histogram("step_ms", 2.0, buckets=(1.0, 5.0))
    text = m.prometheus_text()
    assert PROM_CONTENT_TYPE == "text/plain; version=0.0.4"
    # every family carries HELP and TYPE
    for fam, kind in (("flashmoe_steps_total", "counter"),
                      ("flashmoe_build_info", "gauge"),
                      ("flashmoe_serve_ttft_ms", "summary"),
                      ("flashmoe_fwd_seconds", "summary"),
                      ("flashmoe_step_ms", "histogram")):
        assert f"# TYPE {fam} {kind}" in text
        assert f"# HELP {fam} " in text
    assert r'host="evil\"host\nwith\\stuff"' in text
    assert 'flashmoe_serve_ttft_ms{quantile="0.5"}' in text
    assert "flashmoe_serve_ttft_ms_count 100" in text
    # exposition grammar: one sample per line, no raw newlines leaked
    sample = re.compile(
        r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9.eE+-]+$")
    for line in text.strip().splitlines():
        if not line.startswith("#"):
            assert sample.match(line), line


def test_metrics_summary_carries_sketch_stats():
    m = Metrics()
    for v in (1.0, 2.0, 3.0):
        m.sketch("x", v)
    s = m.summary()
    assert s["x_count"] == 3 and s["x_mean"] == pytest.approx(2.0)
    assert "x_p99" in s


# ----------------------------------------------------------------------
# Scrape server
# ----------------------------------------------------------------------

def _get(url):
    with urllib.request.urlopen(url, timeout=5) as r:
        return r.status, r.read().decode(), r.headers.get("Content-Type")


def test_telemetry_server_endpoints():
    from flashmoe_tpu.telemetry_plane.server import TelemetryServer

    m = Metrics()
    m.gauge("lr", 0.1)
    with TelemetryServer(0, metrics_obj=m,
                         health_fn=lambda: {"queue_depth": 3},
                         vars_fn=lambda: {"plan": ["collective", 1]}) \
            as srv:
        code, body, ctype = _get(f"{srv.url}/metrics")
        assert code == 200 and ctype == PROM_CONTENT_TYPE
        assert "flashmoe_lr" in body
        code, body, _ = _get(f"{srv.url}/healthz")
        hz = json.loads(body)
        assert code == 200 and hz["ok"] is True
        assert hz["queue_depth"] == 3
        code, body, _ = _get(f"{srv.url}/vars")
        assert json.loads(body)["plan"] == ["collective", 1]
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(f"{srv.url}/nope")
        assert e.value.code == 404
    # start/stop narrate themselves on the served registry
    names = [d["decision"] for d in m.decisions]
    assert names.count("telemetry.server_start") == 1
    assert names.count("telemetry.server_stop") == 1


def test_maybe_server_none_is_off():
    from flashmoe_tpu.telemetry_plane.server import maybe_server

    assert maybe_server(None) is None


def test_host_shard_path_sanitized(tmp_path, monkeypatch):
    from flashmoe_tpu.telemetry_plane.server import host_shard_path

    monkeypatch.setenv("FLASHMOE_HOST_ID", "slice-0/host 1")
    p = host_shard_path(str(tmp_path))
    assert os.path.basename(p) == "telemetry.slice-0_host_1.jsonl"
    assert host_shard_path(str(tmp_path), "h7").endswith(
        "telemetry.h7.jsonl")


# ----------------------------------------------------------------------
# Request tracer mechanics (engine-level drill in test_serving.py)
# ----------------------------------------------------------------------

def _scripted_trace():
    """A hand-driven lifecycle with one eviction, on a fake clock."""
    from flashmoe_tpu.telemetry_plane.tracing import RequestTracer

    t = [0.0]
    m = Metrics()
    tr = RequestTracer(metrics_obj=m, clock=lambda: t[0])

    def span(name, dur):
        tok = tr.span_enter(name)
        t[0] += dur
        tr.span_exit(name, tok)

    tr.on_arrival(7)
    t[0] += 0.002
    tr.begin_step(0, [])
    tr.on_admit(7, 0, resumed=False)
    span("serve.prefill", 0.003)
    span("serve.decode", 0.001)
    tr.end_step()
    tr.begin_step(1, [7])
    span("serve.decode", 0.001)
    tr.on_evict(7, 1)
    tr.end_step()
    t[0] += 0.050                       # the eviction gap
    tr.begin_step(2, [])
    tr.on_admit(7, 2, resumed=True)
    span("serve.prefill", 0.002)
    span("serve.decode", 0.001)
    tr.on_retire(7, 2, tokens=3, ttft_ms=1.0, tpot_ms=0.5)
    tr.end_step()
    return tr, m


def test_tracer_lifecycle_contiguous_with_eviction_gap():
    tr, m = _scripted_trace()
    assert tr.validate() == []
    track = tr.request_track(7)
    names = [s["name"] for s in track]
    assert names[0] == "serve.queued"
    gaps = [s for s in track if s["name"] == "serve.queued"
            and s.get("resumed")]
    assert len(gaps) == 1
    assert gaps[0]["dur_ms"] == pytest.approx(50.0, rel=1e-3)
    st = tr.requests[7]
    assert st.trace_id == "req7-0" and st.evictions == 1
    trace_dec = m.last_decision("serve.trace")
    assert trace_dec["rid"] == 7 and trace_dec["evictions"] == 1
    assert trace_dec["spans"] == len(track)


def test_tracer_validate_catches_orphans_and_holes():
    tr, _ = _scripted_trace()
    # un-covered hole: delete the gap span
    st = tr.requests[7]
    st.spans = [s for s in st.spans
                if not (s["name"] == "serve.queued"
                        and s.get("resumed"))]
    problems = tr.validate()
    assert any("resumed queued spans" in p for p in problems)
    assert any("uncovered gap" in p for p in problems)


def test_tracer_perfetto_export_validates(tmp_path):
    from flashmoe_tpu.profiler.export import (
        request_trace_events, validate_trace, write_request_trace,
    )

    tr, _ = _scripted_trace()
    events = request_trace_events(tr)
    pids = {e["pid"] for e in events}
    assert len(pids) == 1               # one track per request
    assert any(e["name"] == "serve.queued [resumed]" for e in events)
    path = tmp_path / "req.json"
    doc = write_request_trace(tr, str(path))
    assert validate_trace(doc) == []
    assert validate_trace(json.loads(path.read_text())) == []


def test_tracer_chains_to_phase_timeline():
    """The tracer installs OVER an armed PhaseTimeline and forwards —
    phase profiling and request tracing compose."""
    from flashmoe_tpu.profiler import spans as prof
    from flashmoe_tpu.telemetry_plane.tracing import RequestTracer
    from flashmoe_tpu.utils.telemetry import get_span_listener

    tl = prof.PhaseTimeline()
    prof.install(tl)
    try:
        tr = RequestTracer().install()
        assert get_span_listener() is tr
        tl.begin_step(0)
        tr.begin_step(0, [])
        tr.on_admit(1, 0, resumed=False)
        tok = tr.span_enter("serve.prefill")
        tr.span_exit("serve.prefill", tok)
        tr.end_step()
        tl.end_step()
        tr.uninstall()
        assert get_span_listener() is tl
        assert any(s["name"] == "serve.prefill" for s in tl.spans)
        assert any(s["name"] == "serve.prefill"
                   for s in tr.request_track(1))
    finally:
        prof.uninstall()


# ----------------------------------------------------------------------
# observe --trace / --merge
# ----------------------------------------------------------------------

def test_observe_trace_and_merge(tmp_path, capsys):
    from flashmoe_tpu import observe

    tr, _ = _scripted_trace()
    shard = tmp_path / "telemetry.h0.jsonl"
    tr.export_jsonl(str(shard))
    rc = observe.main(["--trace", "7", "--json", str(shard)])
    assert rc == 0
    rep = json.loads(capsys.readouterr().out.strip())
    assert rep["found"] and rep["evictions"] == 1
    assert rep["eviction_gap_ms"] == pytest.approx(50.0, rel=1e-3)
    # unknown rid: rc 2 and the known list is named
    assert observe.main(["--trace", "99", str(shard)]) == 2
    assert "traced requests: 7" in capsys.readouterr().out

    shard2 = tmp_path / "telemetry.h1.jsonl"
    shard2.write_text('{"step": 3, "loss": 1.0}\n')
    rc = observe.main(["--merge", "--json", str(shard), str(shard2)])
    assert rc == 0
    rep = json.loads(capsys.readouterr().out.strip())
    assert set(rep["hosts"]) == {"h0", "h1"}
    assert rep["hosts"]["h1"]["records"] == 1
    assert rep["records"] == rep["hosts"]["h0"]["records"] + 1

    # one mode at a time
    with pytest.raises(SystemExit):
        observe.main(["--merge", "--serving", str(shard)])


def test_tracer_evictee_leaves_step_window():
    """An evicted request stops riding the step at the eviction
    instant (code-review finding): no serve.decode span lands after
    its eviction, and its serve.step span ends where the eviction gap
    opens — decode slices never overlap the visible gap."""
    from flashmoe_tpu.telemetry_plane.tracing import RequestTracer

    t = [0.0]
    tr = RequestTracer(metrics_obj=Metrics(), clock=lambda: t[0])

    def span(name, dur):
        tok = tr.span_enter(name)
        t[0] += dur
        tr.span_exit(name, tok)

    tr.on_arrival(1)
    tr.begin_step(0, [])
    tr.on_admit(1, 0, resumed=False)
    span("serve.prefill", 0.002)
    span("serve.decode", 0.001)
    tr.end_step()
    tr.begin_step(1, [1])
    t[0] += 0.001
    tr.on_evict(1, 1)              # evicted BEFORE this step's decode
    evict_ms = t[0] * 1e3
    span("serve.decode", 0.005)    # the survivors' decode
    tr.end_step()
    track = tr.request_track(1)
    step1 = [s for s in track if s["name"] == "serve.step"
             and s["step"] == 1]
    assert len(step1) == 1
    assert step1[0]["ts_ms"] + step1[0]["dur_ms"] == \
        pytest.approx(evict_ms, abs=1e-6)
    decodes_step1 = [s for s in track if s["name"] == "serve.decode"
                     and s["step"] == 1]
    assert decodes_step1 == []     # the post-evict decode is not ours
    assert tr.requests[1].open_queued == pytest.approx(evict_ms)
