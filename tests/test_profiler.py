"""Phase-level profiler: timeline mechanics, cost ledger, Perfetto
export schema, SLO watchdog, flight-ring offset export, and crash
postmortem bundles (docs/OBSERVABILITY.md, phase-profiler sections)."""

import json
import time

import jax
import jax.numpy as jnp
import pytest

from flashmoe_tpu.config import MoEConfig
from flashmoe_tpu.profiler import spans
from flashmoe_tpu.profiler.export import (
    trace_document, validate_trace, write_trace,
)
from flashmoe_tpu.profiler.ledger import (
    PHASES, ledger_config, phase_ledger, predicted_phase_ms,
    run_ledger_matrix,
)
from flashmoe_tpu.profiler.slo import (
    SLOConfig, SLOWatchdog, _parse_flat_yaml,
)
from flashmoe_tpu.profiler.spans import PhaseTimeline, merged_phase
from flashmoe_tpu.utils.telemetry import (
    FlightRecorder, Metrics, metrics as global_metrics, trace_span,
)


# ----------------------------------------------------------------------
# timeline mechanics (pure host)
# ----------------------------------------------------------------------

def test_merged_phase():
    assert merged_phase("moe.expert.3") == "moe.expert"
    assert merged_phase("moe.expert") == "moe.expert"
    assert merged_phase("moe.a2a_dispatch.12") == "moe.a2a_dispatch"
    assert merged_phase("train.step") == "train.step"


def test_timeline_records_spans_only_inside_steps():
    tl = PhaseTimeline(label="t")
    with spans.profiling(tl):
        # outside any step: trace_span must record nothing (jit
        # TRACE-time spans would otherwise pollute the data)
        with trace_span("moe.gate"):
            pass
        assert tl.spans == []
        tl.begin_step(0)
        with trace_span("moe.gate"):
            time.sleep(0.002)
        with trace_span("moe.expert.0"):
            pass
        with trace_span("moe.expert.1"):
            pass
        rec = tl.end_step()
    # unarmed again: spans silently off
    with trace_span("moe.gate"):
        pass
    assert [s["name"] for s in tl.spans] == \
        ["moe.gate", "moe.expert.0", "moe.expert.1"]
    # chunked sub-spans merge onto their base phase in the step totals
    assert set(rec["phases"]) == {"moe.gate", "moe.expert"}
    assert rec["phases"]["moe.gate"] >= 2.0  # ms
    assert rec["wall_ms"] >= rec["phases"]["moe.gate"]
    assert tl.phase_means()["moe.gate"] == rec["phases"]["moe.gate"]


def test_timeline_sections_and_counters_without_steps():
    tl = PhaseTimeline()
    with spans.profiling(tl):
        with spans.section("train.data_pull", step=7):
            pass
        tl.counter("moe.load_imbalance", 2.5, step=7)
    assert tl.sections[0]["name"] == "train.data_pull"
    assert tl.sections[0]["step"] == 7
    assert tl.counters[0]["value"] == 2.5
    # no timeline armed: section() is a free nullcontext
    with spans.section("train.data_pull"):
        pass
    assert len(tl.sections) == 1


def test_fence_is_noop_without_timeline_and_blocks_with():
    x = jnp.ones((4,))
    assert spans.fence(x) is x
    tl = PhaseTimeline()
    with spans.profiling(tl):
        assert spans.fence((x, {"a": x})) is not None


# ----------------------------------------------------------------------
# predicted per-phase costs + ledger join
# ----------------------------------------------------------------------

def test_predicted_phase_ms_positive_all_phases():
    cfg = ledger_config(2)
    for path in ("collective", "ragged"):
        pred = predicted_phase_ms(cfg, d=2, gen="v5e", path=path)
        assert set(pred) == set(PHASES)
        assert all(v > 0 for v in pred.values()), pred
    # single chip: no exchange legs priced
    pred1 = predicted_phase_ms(cfg, d=1, gen="v5e")
    assert set(pred1) == {"moe.gate", "moe.expert"}


def test_phase_ledger_joins_and_records_decisions():
    cfg = ledger_config(2)
    tl = PhaseTimeline(label="fabricated")
    tl.begin_step(0)
    for ph in PHASES:
        tok = tl.span_enter(ph)
        tl.span_exit(ph, tok)
    tl.end_step()
    n0 = len(global_metrics.decisions)
    rows, overlap = phase_ledger(tl, cfg, d=2, gen="v5e",
                                 path="collective", warn=False)
    assert [r["phase"] for r in rows] == list(PHASES)
    assert overlap is None  # no overlapped_ms on the timeline
    new = [d for d in global_metrics.decisions[n0:]
           if d["decision"] == "planner.phase_drift"]
    assert len(new) == len(PHASES)
    assert {d["phase"] for d in new} == set(PHASES)
    for r in rows:
        assert r["predicted_ms"] > 0
        assert "rel_error" in r and "exceeded" in r


def test_ledger_matrix_quick_point_end_to_end(devices, tmp_path):
    """The fast-lane acceptance point: flat x serial x wire-off profiled
    EAGERLY on the virtual mesh with profile_phases=True — all four
    phases measured, the per-step phase sum bounded by the step wall
    time, artifacts written and schema-valid, `observe --ledger`
    renders them.  (The full flat/hierarchical/ragged x chunks x wire
    matrix is the slow test below.)"""
    obs = tmp_path / "obs"
    records = run_ledger_matrix(str(obs), quick=True, steps=1,
                                overlapped=False, warn=False)
    assert len(records) == 1
    rec = records[0]
    assert set(rec["phases"]) == set(PHASES)
    assert all(v > 0 for v in rec["phases"].values())
    # fenced phases are disjoint sub-intervals of the profiled step
    assert sum(rec["phases"].values()) <= rec["step_ms"] * 1.05
    # artifacts: ledger rows for every phase + a valid Chrome trace
    rows = [json.loads(line) for line in
            (obs / "ledger.jsonl").read_text().splitlines()]
    assert {r["phase"] for r in rows if "phase" in r} == set(PHASES)
    doc = json.loads((obs / "trace.json").read_text())
    assert validate_trace(doc) == []
    assert (obs / "flight.jsonl").exists()

    from flashmoe_tpu import observe

    assert observe.main(["--ledger", str(obs / "ledger.jsonl")]) == 0
    led = observe.ledger_report(rows)
    assert led["n"] == len(PHASES)
    # both vocabularies ride every row: the matrix point name the docs
    # speak, and the planner path it joins against
    assert led["points"][0]["point"] == "flat"
    assert led["points"][0]["path"] == "collective"
    assert all(r["point"] == "flat" for r in rows if "phase" in r)


@pytest.mark.slow
def test_ledger_matrix_full_acceptance(devices, tmp_path):
    """Acceptance matrix: flat/hierarchical/ragged x {serial, chunked}
    x {wire off, e4m3} all measured, joined, and exported — with the
    measured-overlap cross-check against chunked_overlap_bound."""
    obs = tmp_path / "obs"
    records = run_ledger_matrix(str(obs), steps=1, overlapped=True,
                                warn=False)
    assert len(records) == 12  # 3 paths x 2 chunk settings x 2 wires
    for rec in records:
        assert set(rec["phases"]) == set(PHASES), rec["metric"]
        assert rec["overlap"] is not None  # every point is d > 1
    assert {r["path"] for r in records} == {"flat", "hierarchical",
                                            "ragged"}
    assert {r["a2a_chunks"] for r in records} == {1, 2}
    assert {r["wire_dtype"] for r in records} == {"off", "e4m3"}
    doc = json.loads((obs / "trace.json").read_text())
    assert validate_trace(doc) == []
    # one Perfetto process per matrix point
    pids = {e["pid"] for e in doc["traceEvents"]}
    assert len(pids) == 12


# ----------------------------------------------------------------------
# Perfetto / Chrome-trace export
# ----------------------------------------------------------------------

def _toy_timeline():
    tl = PhaseTimeline(label="toy")
    tl.begin_step(0)
    for ph in ("moe.gate", "moe.expert.0", "moe.expert.1"):
        tok = tl.span_enter(ph)
        time.sleep(0.001)
        tl.span_exit(ph, tok)
    tl.end_step()
    with tl.section("train.checkpoint", step=0):
        pass
    tl.counter("moe.load_imbalance", 1.5, step=0)
    return tl


def test_trace_export_schema_and_content(tmp_path):
    tl = _toy_timeline()
    path = tmp_path / "trace.json"
    doc = write_trace(tl, str(path), labels=["point one"])
    assert validate_trace(doc) == []
    on_disk = json.loads(path.read_text())
    events = on_disk["traceEvents"]
    names = {e["name"] for e in events}
    assert {"process_name", "thread_name", "moe.gate",
            "train.checkpoint", "moe.load_imbalance"} <= names
    # chunked sub-slices carry their merged base phase in args
    sub = next(e for e in events if e["name"] == "moe.expert.1")
    assert sub["args"]["phase"] == "moe.expert"
    assert sub["tid"] == 0 and sub["dur"] > 0
    sec = next(e for e in events if e["name"] == "train.checkpoint")
    assert sec["tid"] == 1
    counter = next(e for e in events if e["ph"] == "C")
    assert counter["args"]["value"] == 1.5
    # multi-timeline merge: one pid per timeline
    doc2 = trace_document([tl, _toy_timeline()])
    assert {e["pid"] for e in doc2["traceEvents"]} == {0, 1}


def test_trace_validation_rejects_malformed():
    assert validate_trace({"nope": 1})
    assert validate_trace({"traceEvents": [{"ph": "Q", "name": "x",
                                            "pid": 0}]})
    bad_dur = {"traceEvents": [
        {"ph": "X", "name": "x", "pid": 0, "tid": 0, "ts": 1.0,
         "dur": 0}]}
    assert any("dur" in e for e in validate_trace(bad_dur))
    bad_ts = {"traceEvents": [
        {"ph": "X", "name": "x", "pid": 0, "tid": 0, "ts": -5,
         "dur": 1}]}
    assert any("ts" in e for e in validate_trace(bad_ts))
    bad_counter = {"traceEvents": [
        {"ph": "C", "name": "c", "pid": 0, "ts": 1.0,
         "args": {"value": "high"}}]}
    assert any("numeric" in e for e in validate_trace(bad_counter))


# ----------------------------------------------------------------------
# SLO watchdog
# ----------------------------------------------------------------------

def test_slo_config_validation():
    with pytest.raises(ValueError, match="step_ms"):
        SLOConfig(step_ms=-1.0)
    with pytest.raises(ValueError, match="consecutive"):
        SLOConfig(step_ms=1.0, consecutive=0)
    with pytest.raises(ValueError, match="unknown SLO keys"):
        SLOConfig.from_dict({"step_milliseconds": 5})
    cfg = SLOConfig.from_dict({"step_ms": 10, "phase_ms":
                               {"moe.expert": 5}})
    assert cfg.phase_budgets == {"moe.expert": 5.0}


def test_slo_yaml_sidecar_fallback_parser(tmp_path):
    text = """
# budgets for the nightly job
step_ms: 250
consecutive: 2
demote_backend: ragged
phase_ms:
  moe.expert: 120
  moe.a2a_dispatch: 40.5
"""
    raw = _parse_flat_yaml(text)
    assert raw["step_ms"] == 250
    assert raw["phase_ms"]["moe.a2a_dispatch"] == 40.5
    p = tmp_path / "slo.yaml"
    p.write_text(text)
    cfg = SLOConfig.from_yaml(str(p))
    assert cfg.step_ms == 250.0
    assert cfg.consecutive == 2
    assert cfg.demote_backend == "ragged"
    assert cfg.phase_budgets["moe.expert"] == 120.0


def test_slo_breach_recover_episodes_and_escalation():
    from flashmoe_tpu.planner.select import (
        failed_backends, reset_path_failures,
    )

    m = Metrics()
    wd = SLOWatchdog(SLOConfig(step_ms=10.0, consecutive=2,
                               demote_backend="fused",
                               phase_ms=(("moe.expert", 5.0),)), m)
    try:
        assert wd.observe_step(0, 3.0) == []          # in budget
        ev = wd.observe_step(1, 50.0,
                             phases={"moe.expert": 7.0})
        assert {e["target"] for e in ev} == {"step", "moe.expert"}
        assert wd.consecutive_breaches == 1
        assert "fused" not in failed_backends()       # not yet
        wd.observe_step(2, 50.0)
        assert wd.consecutive_breaches == 2
        # consecutive budget hit: escalated into path demotion, once
        assert "fused" in failed_backends()
        assert m.counters["slo.escalations"] == 1
        wd.observe_step(3, 60.0)
        assert m.counters["slo.escalations"] == 1     # same episode
        # recovery closes the episode (and the phase target separately)
        wd.observe_step(4, 2.0, phases={"moe.expert": 1.0})
        recs = [d for d in m.decisions
                if d["decision"] == "slo.recovered"]
        assert {r["target"] for r in recs} == {"step", "moe.expert"}
        assert wd.consecutive_breaches == 0
        breaches = [d for d in m.decisions
                    if d["decision"] == "slo.breach"]
        assert breaches[0]["measured_ms"] == 50.0
        assert breaches[0]["budget_ms"] == 10.0
    finally:
        reset_path_failures()


def test_slow_step_chaos_fault_trips_slo_and_demotes(devices, tmp_path):
    """The acceptance wiring: an injected slow_step chaos fault makes a
    step blow its SLO budget -> slo.breach -> report_path_failure
    demotes the named backend through the PR 3 machinery."""
    from flashmoe_tpu.chaos import FaultPlan, wrap_step
    from flashmoe_tpu.planner.select import (
        failed_backends, reset_path_failures,
    )
    from flashmoe_tpu.runtime.resilient import (
        ResilienceConfig, resilient_train,
    )
    from flashmoe_tpu.runtime.trainer import TrainState

    state = TrainState(params={"w": jnp.zeros((4,))},
                       opt_state={"m": jnp.zeros((4,))},
                       step=jnp.zeros((), jnp.int32))

    def step_fn(s, b):
        return (TrainState(s.params, s.opt_state, s.step + 1, s.guard),
                {"loss": jnp.float32(1.0)})

    # The budget is held on the WALL clock: 100 ms under a 0.3 s sleep
    # leaves the stalled step a factor of three over it on any machine.
    # What the clock does NOT promise is that the other steps stay under
    # it: with six test workers on the cores, step 0 (the first dispatch
    # of these eager operations) took over 100 ms in the driver's run and
    # breached first.  So the operations are dispatched once beforehand,
    # and the stalled step is looked for AMONG the breaches, not at their
    # head: the wiring is "the slow step breaches and the backend is
    # demoted", whichever step opened the episode.
    step_fn(state, {"x": 0})
    wrapped = wrap_step(step_fn, FaultPlan("slow_step", step=1,
                                           sleep_s=0.3))
    rcfg = ResilienceConfig(checkpoint_dir=str(tmp_path / "ck"),
                            checkpoint_every=100)
    g0 = len(global_metrics.decisions)
    try:
        resilient_train(
            state, wrapped, iter(lambda: {"x": 0}, None), num_steps=4,
            rcfg=rcfg,
            slo=SLOConfig(step_ms=100.0, consecutive=1,
                          demote_backend="ragged"))
        breaches = [d for d in global_metrics.decisions[g0:]
                    if d["decision"] == "slo.breach"]
        stalled = [b for b in breaches if b["step"] == 1]
        assert stalled and stalled[0]["measured_ms"] >= 300.0 > 100.0 \
            == stalled[0]["budget_ms"]
        assert "ragged" in failed_backends()
        fallbacks = [d for d in global_metrics.decisions[g0:]
                     if d["decision"] == "planner.fallback"]
        assert any(d.get("failed") == "ragged" for d in fallbacks)
    finally:
        reset_path_failures()


# ----------------------------------------------------------------------
# flight-ring offset export (the mode-"w" data-loss fix)
# ----------------------------------------------------------------------

def test_flight_offset_export_loses_nothing_across_ring_wrap(tmp_path):
    rec = FlightRecorder(capacity=4)
    path = str(tmp_path / "flight.jsonl")
    for i in range(3):
        rec.record(step=i)
    cursor = rec.export_jsonl(path, start=0)
    assert cursor == 3
    # four more records: the ring WRAPS (steps 0-2 rotate out), but they
    # were already flushed — the legacy mode-"w" snapshot would have
    # discarded them here
    for i in range(3, 7):
        rec.record(step=i)
    assert len(rec) == 4 and rec.total_recorded == 7
    cursor = rec.export_jsonl(path, start=cursor)
    assert cursor == 7
    steps = [json.loads(line)["step"]
             for line in open(path).read().splitlines()]
    # two exports across a wrap: every record exactly once, in order
    assert steps == list(range(7))


def test_flight_export_gap_is_counted_not_silent(tmp_path):
    rec = FlightRecorder(capacity=2)
    for i in range(5):
        rec.record(step=i)
    lost0 = global_metrics.counters.get("flight.export_lost", 0)
    cursor = rec.export_jsonl(str(tmp_path / "f.jsonl"), start=0)
    assert cursor == 5
    # steps 0-2 were never flushed and already rotated out: visible loss
    assert global_metrics.counters["flight.export_lost"] - lost0 == 3


def test_flight_snapshot_mode_unchanged(tmp_path):
    rec = FlightRecorder(capacity=4)
    for i in range(6):
        rec.record(step=i)
    path = str(tmp_path / "snap.jsonl")
    assert rec.export_jsonl(path) == 4
    assert rec.export_jsonl(path) == 4  # truncates, not appends
    steps = [json.loads(line)["step"]
             for line in open(path).read().splitlines()]
    assert steps == [2, 3, 4, 5]


def test_trainer_periodic_flush_survives_ring_wrap(devices, tmp_path):
    """runtime.trainer.train(flight_flush_every=n) with a ring smaller
    than the run: the flight JSONL still carries EVERY step."""
    from flashmoe_tpu.parallel.mesh import make_mesh
    from flashmoe_tpu.runtime.trainer import train

    cfg = MoEConfig(num_experts=4, expert_top_k=2, hidden_size=64,
                    intermediate_size=128, sequence_len=32, num_layers=1,
                    moe_frequency=1, vocab_size=256, num_heads=2,
                    drop_tokens=False, is_training=True,
                    dtype=jnp.float32, param_dtype=jnp.float32)
    mesh = make_mesh(cfg, dp=1, devices=jax.devices()[:1])

    def batches():
        i = 0
        while True:
            yield {"tokens": jax.random.randint(
                jax.random.PRNGKey(i), (1, 33), 0, 256)}
            i += 1

    path = tmp_path / "flight.jsonl"
    train(cfg, mesh, batches(), num_steps=5,
          recorder=FlightRecorder(capacity=2),
          flight_path=str(path), flight_flush_every=2)
    steps = [json.loads(line)["step"]
             for line in path.read_text().splitlines()]
    assert steps == [0, 1, 2, 3, 4]


# ----------------------------------------------------------------------
# crash postmortem bundles
# ----------------------------------------------------------------------

def test_postmortem_bundle_roundtrip(tmp_path):
    from flashmoe_tpu import observe
    from flashmoe_tpu.profiler import postmortem as pm

    m = Metrics()
    m.decision("planner.path_select", backend="ragged")
    cfg = MoEConfig(num_experts=4, expert_top_k=2, hidden_size=64,
                    intermediate_size=128, sequence_len=16)
    tl = _toy_timeline()
    try:
        raise RuntimeError("synthetic crash for the bundle test")
    except RuntimeError as e:
        bundle = pm.write_bundle(
            str(tmp_path / "pmd"), error=e, cfg=cfg, metrics_obj=m,
            history=[{"loss": 1.5}, {"loss": 1.25}], timeline=tl,
            step=7, extra={"retries": 3})
    assert bundle is not None and pm.is_bundle(bundle)
    assert pm.find_bundles(str(tmp_path / "pmd")) == [bundle]
    loaded = pm.load_bundle(bundle)
    assert "synthetic crash" in loaded["manifest"]["error"]
    assert loaded["manifest"]["step"] == 7
    assert loaded["config"]["num_experts"] == 4
    assert loaded["flight"][-1]["loss"] == 1.25
    assert "RuntimeError" in loaded["traceback"]
    assert validate_trace(loaded["trace"]) == []
    assert any(d["decision"] == "postmortem.saved"
               for d in loaded["decisions"])
    rep = observe.postmortem_report(loaded)
    assert rep["step"] == 7
    assert rep["last_losses"] == [1.5, 1.25]
    assert rep["config"]["num_experts"] == 4
    assert rep["extra"] == {"retries": 3}
    text = observe.render_postmortem_text(rep)
    assert "synthetic crash" in text
    # the CLI path
    assert observe.main(["--postmortem", str(tmp_path / "pmd")]) == 0
    assert observe.main(["--postmortem", str(tmp_path / "empty")]) == 2


def test_postmortem_written_when_chaos_fault_exhausts_retries(
        devices, tmp_path):
    """A chaos device_loss that outlives the retry budget kills the
    in-job recovery — the StepFailure must leave a parseable bundle
    behind (and carry its path on the exception)."""
    from flashmoe_tpu.chaos import FaultPlan, make_injector
    from flashmoe_tpu.profiler import postmortem as pm
    from flashmoe_tpu.runtime.resilient import (
        ResilienceConfig, StepFailure, resilient_train,
    )
    from flashmoe_tpu.runtime.trainer import TrainState

    state = TrainState(params={"w": jnp.zeros((4,))},
                       opt_state={"m": jnp.zeros((4,))},
                       step=jnp.zeros((), jnp.int32))

    def step_fn(s, b):
        return (TrainState(s.params, s.opt_state, s.step + 1, s.guard),
                {"loss": jnp.float32(1.0)})

    rcfg = ResilienceConfig(checkpoint_dir=str(tmp_path / "ck"),
                            checkpoint_every=100, max_retries=1,
                            emergency_save=False)
    plan = FaultPlan("device_loss", step=1)
    cfg = MoEConfig(num_experts=4, expert_top_k=2, hidden_size=64,
                    intermediate_size=128, sequence_len=16)
    pm_dir = str(tmp_path / "postmortem")
    metrics = Metrics()
    with pytest.raises(StepFailure) as exc:
        resilient_train(state, step_fn, iter(lambda: {"x": 0}, None),
                        num_steps=4, rcfg=rcfg, metrics=metrics,
                        fail_injector=make_injector(plan, rcfg),
                        postmortem_dir=pm_dir, cfg=cfg)
    bundles = pm.find_bundles(pm_dir)
    assert len(bundles) == 1
    assert getattr(exc.value, "postmortem_bundle", None) == bundles[0]
    loaded = pm.load_bundle(bundles[0])
    assert "device loss" in loaded["manifest"]["error"]
    assert loaded["manifest"]["extra"]["num_steps"] == 4
    assert loaded["config"]["num_experts"] == 4
    assert any(d["decision"] == "postmortem.saved"
               for d in loaded["decisions"])


def test_no_postmortem_on_recovered_failure(devices, tmp_path):
    """A transient failure absorbed by restore-and-retry is NOT a death:
    the bundle dir must stay empty (the chaos matrix asserts this per
    fault; this is the unit-level version)."""
    from flashmoe_tpu.profiler import postmortem as pm
    from flashmoe_tpu.runtime.resilient import (
        ResilienceConfig, resilient_train,
    )
    from flashmoe_tpu.runtime.trainer import TrainState

    state = TrainState(params={"w": jnp.zeros((4,))},
                       opt_state={"m": jnp.zeros((4,))},
                       step=jnp.zeros((), jnp.int32))

    def step_fn(s, b):
        return (TrainState(s.params, s.opt_state, s.step + 1, s.guard),
                {"loss": jnp.float32(1.0)})

    fired = {"n": 0}

    def inject_once(i):
        if i == 1 and not fired["n"]:
            fired["n"] += 1
            raise RuntimeError("transient")

    pm_dir = str(tmp_path / "postmortem")
    rcfg = ResilienceConfig(checkpoint_dir=str(tmp_path / "ck"),
                            checkpoint_every=2, max_retries=2)
    final, _ = resilient_train(
        state, step_fn, iter(lambda: {"x": 0}, None), num_steps=4,
        rcfg=rcfg, fail_injector=inject_once, postmortem_dir=pm_dir)
    assert int(final.step) == 4
    assert pm.find_bundles(pm_dir) == []
