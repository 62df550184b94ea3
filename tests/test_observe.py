"""Observability subsystem: in-graph MoE stats, flight recorder,
Prometheus exposition, planner drift monitor, and the observe CLI."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashmoe_tpu.config import MoEConfig
from flashmoe_tpu.models.reference import init_moe_params
from flashmoe_tpu.ops.moe import moe_layer
from flashmoe_tpu.ops.stats import MoEStats, moe_stats
from flashmoe_tpu.utils.telemetry import (
    FlightRecorder, Histogram, Metrics, metrics as global_metrics,
)

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)


# ----------------------------------------------------------------------
# In-graph stats: known routing -> exact histogram / drop fraction
# ----------------------------------------------------------------------

def _routed_setup():
    """Deterministic routing: gate_w reads the expert id off the one-hot
    token, so expert loads are exactly the planted choice vector."""
    cfg = MoEConfig(num_experts=4, expert_top_k=1, hidden_size=64,
                    intermediate_size=64, sequence_len=16,
                    capacity_factor=1.0, collect_stats=True, **F32)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    gw = np.zeros((64, 4), np.float32)
    for e in range(4):
        gw[e, e] = 10.0
    params["gate_w"] = jnp.asarray(gw)
    # 10 tokens to expert 0, 2 each to 1/2/3; capacity_for(16) = 8
    choice = [0] * 10 + [1, 1, 2, 2, 3, 3]
    x = np.zeros((16, 64), np.float32)
    for i, c in enumerate(choice):
        x[i, c] = 1.0
    return cfg, params, jnp.asarray(x)


def _check_exact(st):
    np.testing.assert_array_equal(np.asarray(st.expert_load),
                                  [10.0, 2.0, 2.0, 2.0])
    # capacity 8: expert 0 drops 2 of 10 -> 2/16 dropped, 14/32 slots used
    assert float(st.dropped_fraction) == pytest.approx(2 / 16)
    assert float(st.capacity_utilization) == pytest.approx(14 / 32)
    assert float(st.imbalance) == pytest.approx(10 / 4)
    assert float(st.topk_confidence) == pytest.approx(1.0)
    assert float(st.router_entropy) > 0


def test_stats_known_routing_exact():
    cfg, params, x = _routed_setup()
    assert cfg.capacity_for(16) == 8
    _check_exact(moe_layer(params, x, cfg, use_pallas=False).stats)


def test_stats_under_jit():
    cfg, params, x = _routed_setup()
    st = jax.jit(
        lambda xx: moe_layer(params, xx, cfg, use_pallas=False).stats
    )(x)
    _check_exact(st)


def test_stats_under_vmap():
    cfg, params, x = _routed_setup()
    st = jax.vmap(
        lambda xx: moe_layer(params, xx, cfg, use_pallas=False).stats
    )(jnp.stack([x, x, x]))
    assert st.expert_load.shape == (3, 4)
    np.testing.assert_array_equal(np.asarray(st.expert_load[1]),
                                  [10.0, 2.0, 2.0, 2.0])
    np.testing.assert_allclose(np.asarray(st.dropped_fraction),
                               [2 / 16] * 3, rtol=1e-6)


def test_stats_dropless_reports_no_drops():
    cfg, params, x = _routed_setup()
    r_like = moe_layer(params, x, cfg, use_pallas=False)
    st = moe_stats(
        type("R", (), {
            "expert_counts": r_like.stats.expert_load,
            "combine_weights": jnp.ones((16, 1), jnp.float32),
            "probs_mean": jnp.zeros((4,), jnp.float32),
        })(), cfg, None)
    assert float(st.dropped_fraction) == 0.0
    assert float(st.capacity_utilization) == 1.0


def test_stats_off_by_default():
    cfg, params, x = _routed_setup()
    o = moe_layer(params, x, cfg.replace(collect_stats=False),
                  use_pallas=False)
    assert o.stats is None


# ----------------------------------------------------------------------
# EP layer: flag off is bit-identical with no extra collectives
# ----------------------------------------------------------------------

def _prim_counts(jaxpr, acc=None):
    acc = {} if acc is None else acc
    for eqn in jaxpr.eqns:
        acc[eqn.primitive.name] = acc.get(eqn.primitive.name, 0) + 1
        for v in eqn.params.values():
            vs = v if isinstance(v, (list, tuple)) else (v,)
            for item in vs:
                if hasattr(item, "jaxpr"):
                    _prim_counts(item.jaxpr, acc)
                elif hasattr(item, "eqns"):
                    _prim_counts(item, acc)
    return acc


COLLECTIVES = ("all_to_all", "psum", "pmean", "all_gather", "ppermute",
               "ragged_all_to_all")


def test_ep_stats_off_bit_identical_no_extra_collectives(devices, jitted):
    from flashmoe_tpu.parallel.ep import ep_moe_layer
    from flashmoe_tpu.parallel.mesh import make_mesh

    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=64,
                    intermediate_size=128, sequence_len=256, ep=8, **F32)
    mesh = make_mesh(cfg, dp=1, devices=devices[:8])
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (cfg.tokens, 64),
                          jnp.float32)

    def collectives(c):
        jx = jax.make_jaxpr(
            lambda p, xx: ep_moe_layer(p, xx, c, mesh))(params, x)
        pc = _prim_counts(jx.jaxpr)
        return {k: v for k, v in pc.items() if k in COLLECTIVES}

    off = collectives(cfg)
    # the stats-off graph is exactly the pre-observability graph: the
    # two slab exchanges plus the three aux/z/counts reductions
    assert off == {"all_to_all": 2, "psum": 3}
    on = collectives(cfg.replace(collect_stats=True))
    assert on["all_to_all"] == 2  # stats never add an exchange

    o_off = jitted(ep_moe_layer, cfg, mesh)(params, x)
    o_on = jitted(ep_moe_layer, cfg.replace(collect_stats=True),
                  mesh)(params, x)
    assert o_off.stats is None
    np.testing.assert_array_equal(np.asarray(o_off.out),
                                  np.asarray(o_on.out))
    # global stats line up with the psum'd counts the layer already emits
    np.testing.assert_array_equal(np.asarray(o_on.stats.expert_load),
                                  np.asarray(o_on.expert_counts,
                                             dtype=np.float32))
    assert float(o_on.stats.expert_load.sum()) == cfg.tokens * 2


# ----------------------------------------------------------------------
# Flight recorder + histogram + Prometheus exposition
# ----------------------------------------------------------------------

def test_flight_recorder_ring_bounds(tmp_path):
    fr = FlightRecorder(capacity=16)
    for i in range(100):
        fr.record(step=i, loss=float(i))
    assert len(fr) == 16 and fr.capacity == 16
    assert fr.records[0]["step"] == 84
    assert fr.records[-1]["step"] == 99
    path = str(tmp_path / "flight.jsonl")
    assert fr.export_jsonl(path) == 16
    lines = [json.loads(l) for l in open(path)]
    assert [l["step"] for l in lines] == list(range(84, 100))


def test_histogram_percentiles():
    h = Histogram(buckets=(1.0, 2.0, 5.0, 10.0))
    for v in (0.5, 1.5, 1.6, 4.0, 9.0, 20.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 6
    assert s["sum"] == pytest.approx(36.6)
    assert s["min"] == 0.5 and s["max"] == 20.0
    assert s["p50"] <= s["p99"] <= 20.0


def test_prometheus_exposition_format():
    import re

    m = Metrics()
    m.count("steps")
    m.count("steps")
    m.gauge("lr", 3e-4)
    m.histogram("step_ms", 3.0, buckets=(1.0, 5.0, 10.0))
    m.histogram("step_ms", 7.0, buckets=(1.0, 5.0, 10.0))
    with m.timer("fwd"):
        pass
    text = m.prometheus_text()
    assert "# TYPE flashmoe_steps_total counter" in text
    assert "flashmoe_steps_total 2.0" in text
    assert "# TYPE flashmoe_lr gauge" in text
    assert "# TYPE flashmoe_step_ms histogram" in text
    assert 'flashmoe_step_ms_bucket{le="5"} 1' in text
    assert 'flashmoe_step_ms_bucket{le="+Inf"} 2' in text
    assert "flashmoe_step_ms_count 2" in text
    assert "# TYPE flashmoe_fwd_seconds summary" in text
    # every sample line obeys the exposition grammar
    sample = re.compile(
        r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9.eE+-]+$")
    for line in text.strip().splitlines():
        if not line.startswith("#"):
            assert sample.match(line), line


def test_metric_name_sanitized():
    m = Metrics()
    m.count("planner.drift/err-rate")
    text = m.prometheus_text()
    assert "flashmoe_planner_drift_err_rate_total" in text


# ----------------------------------------------------------------------
# Drift monitor
# ----------------------------------------------------------------------

def test_drift_monitor_thresholding():
    from flashmoe_tpu.planner.drift import record_drift

    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=256,
                    intermediate_size=512, sequence_len=256, **F32)
    n0 = len(global_metrics.decisions)
    # within threshold: no warning, decision recorded
    rec = record_drift(cfg, "explicit", measured_ms=1.2, gen="v5e",
                       predicted_ms=1.0, threshold=0.5)
    assert not rec.exceeded
    assert rec.rel_error == pytest.approx(0.2)
    with pytest.warns(RuntimeWarning, match="planner drift"):
        rec = record_drift(cfg, "explicit", measured_ms=2.0, gen="v5e",
                           predicted_ms=1.0, threshold=0.5)
    assert rec.exceeded
    new = global_metrics.decisions[n0:]
    assert [d["decision"] for d in new] == ["planner.drift"] * 2
    assert new[-1]["exceeded"] is True
    assert new[-1]["measured_ms"] == 2.0


def test_drift_predicts_when_not_given():
    from flashmoe_tpu.planner.drift import record_drift

    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=256,
                    intermediate_size=512, sequence_len=256, **F32)
    rec = record_drift(cfg, "explicit", measured_ms=1e9, gen="v5e",
                       warn=False)
    assert rec.predicted_ms > 0
    assert rec.exceeded  # a second per layer is drift by any threshold


def test_drift_report_over_mixed_records():
    from flashmoe_tpu.planner.drift import drift_report

    records = [
        {"decision": "planner.drift", "path": "explicit", "gen": "v5e",
         "rel_error": 0.4, "exceeded": False},
        {"decision": "planner.drift", "path": "explicit", "gen": "v5e",
         "rel_error": -0.8, "exceeded": True},
        {"decision": "planner.drift", "path": "explicit", "gen": "v5e",
         "predicted_ms": 1.0, "measured_ms": 2.0, "rel_error": 1.0,
         "exceeded": True},
        {"unrelated": True},
    ]
    rep = drift_report(records)
    assert rep["n"] == 3 and rep["exceeded"] == 2
    b = rep["by_path"]["explicit@v5e"]
    assert b["n"] == 3
    assert b["worst_rel_error"] == pytest.approx(1.0)


# ----------------------------------------------------------------------
# Observe CLI
# ----------------------------------------------------------------------

def _synthetic_flight(tmp_path):
    """Two steps of a hand-computed routing case: E=4, 16 assignments
    per step, loads [10, 2, 2, 2] at capacity 8 -> dropped 2/16."""
    path = str(tmp_path / "flight.jsonl")
    with open(path, "w") as f:
        for step in range(2):
            f.write(json.dumps({
                "step": step, "loss": 3.0 - step, "step_ms": 12.5,
                "moe": [{
                    "layer": 0, "expert_load": [10.0, 2.0, 2.0, 2.0],
                    "dropped_fraction": 0.125,
                    "capacity_utilization": 14 / 32,
                    "imbalance": 2.5, "router_entropy": 1.0,
                    "topk_confidence": 1.0,
                }],
            }) + "\n")
    return path


def test_observe_cli_summarizes_synthetic_dump(tmp_path, capsys):
    from flashmoe_tpu import observe

    path = _synthetic_flight(tmp_path)
    assert observe.main([path, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["flight_steps"] == 2
    # nonzero expert-load histogram, summed over steps
    assert doc["imbalance"]["expert_load"] == [20.0, 4.0, 4.0, 4.0]
    assert doc["imbalance"]["imbalance"] == pytest.approx(2.5)
    # drop-rate figure matches the hand-computed routing case
    assert doc["drops"]["mean_dropped_fraction"] == pytest.approx(0.125)
    assert doc["drops"]["timeline"][0]["dropped_fraction"] == \
        pytest.approx(0.125)
    assert doc["phases"]["step_ms"] == pytest.approx(12.5)


def test_observe_cli_text_output(tmp_path, capsys):
    from flashmoe_tpu import observe

    path = _synthetic_flight(tmp_path)
    assert observe.main([path]) == 0
    out = capsys.readouterr().out
    assert "expert load histogram" in out
    assert "drop rate: mean 0.125" in out


def test_observe_wire_report(tmp_path, capsys):
    """Flight records carrying the wire round-trip error surface in the
    wire report (and the text rendering); wire-off dumps report none."""
    from flashmoe_tpu import observe

    path = str(tmp_path / "flight.jsonl")
    with open(path, "w") as f:
        for step, err in enumerate([0.0, 0.021, 0.025]):
            f.write(json.dumps({
                "step": step,
                "moe": [{"expert_load": [1.0], "wire_rtq_error": err}],
            }) + "\n")
    assert observe.main([path, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["wire"]["steps_with_wire"] == 2  # the 0.0 step = wire off
    assert doc["wire"]["max_rtq_error"] == pytest.approx(0.025)
    assert doc["wire"]["mean_rtq_error"] == pytest.approx(0.023)
    assert observe.main([path]) == 0
    assert "wire compression" in capsys.readouterr().out
    # a wire-off dump carries no wire section in the text rendering
    off = _synthetic_flight(tmp_path)
    assert observe.main([off]) == 0
    assert "wire compression" not in capsys.readouterr().out


def test_observe_cli_rejects_empty(tmp_path, capsys):
    from flashmoe_tpu import observe

    p = str(tmp_path / "empty.jsonl")
    open(p, "w").close()
    assert observe.main([p]) == 2


def test_observe_resilience_report(tmp_path, capsys):
    """The decision stream of a preempted-then-resumed run summarizes
    into the resilience narrative (docs/RESILIENCE.md decisions)."""
    from flashmoe_tpu import observe

    decisions = [
        {"decision": "preempt.notice", "source": "SIGTERM",
         "grace_s": 30.0},
        {"decision": "preempt.drain", "step": 4, "source": "SIGTERM",
         "remaining_grace_s": 28.5},
        {"decision": "supervisor.resume", "incarnation": 1, "step": 4,
         "world": 4, "ep": 2, "dp": 2},
        {"decision": "supervisor.resume", "incarnation": 2, "step": 6,
         "world": 2, "ep": 2, "dp": 1},
        {"decision": "trainer.grad_skip", "step": 5, "grad_norm": 1e9},
        {"decision": "checkpoint.fallback", "corrupt_step": 8,
         "restored_step": 6, "lost_steps": 2},
    ]
    p = str(tmp_path / "decisions.jsonl")
    with open(p, "w") as f:
        for d in decisions:
            f.write(json.dumps(d) + "\n")
    assert observe.main([p, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    res = doc["resilience"]
    assert res["events"] == {"trainer.grad_skip": 1,
                             "checkpoint.fallback": 1,
                             "preempt.notice": 1, "preempt.drain": 1,
                             "supervisor.resume": 2}
    assert res["drains"] == [{"step": 4, "source": "SIGTERM",
                              "remaining_grace_s": 28.5}]
    assert res["worlds"] == [2, 4]  # the elastic re-fold is visible
    assert res["resumes"][1]["ep"] == 2 and res["resumes"][1]["dp"] == 1

    assert observe.main([p]) == 0
    out = capsys.readouterr().out
    assert "resilience events:" in out
    assert "drain at step 4 (SIGTERM), 28.5s grace left" in out
    assert "resume #2 at step 6: world=2 (ep=2 x dp=1)" in out


# ----------------------------------------------------------------------
# End to end: trainer flight recorder -> observe summary
# ----------------------------------------------------------------------

def test_trainer_flight_recorder_end_to_end(tmp_path, devices):
    from flashmoe_tpu import observe
    from flashmoe_tpu.parallel.mesh import make_mesh
    from flashmoe_tpu.runtime.trainer import train

    cfg = MoEConfig(num_experts=4, expert_top_k=2, hidden_size=64,
                    intermediate_size=128, sequence_len=32, num_layers=1,
                    moe_frequency=1, vocab_size=512, num_heads=2,
                    capacity_factor=1.0, is_training=True, ep=4,
                    collect_stats=True, **F32)
    mesh = make_mesh(cfg, dp=1, devices=devices[:4])

    def batches():
        k = jax.random.PRNGKey(0)
        while True:
            k, sk = jax.random.split(k)
            yield {"tokens": jax.random.randint(sk, (1, 33), 0, 512)}

    fp = str(tmp_path / "flight.jsonl")
    _, hist = train(cfg, mesh, batches(), num_steps=1, log_every=1,
                    flight_path=fp)
    assert "moe" in hist[-1] and hist[-1]["moe"][0]["expert_load"]

    records = observe.load_jsonl([fp])
    assert len(records) == 1
    doc = observe.summarize(records)
    assert doc["flight_steps"] == 1
    # one step routes 32 tokens x top-2 = 64 assignments
    assert doc["imbalance"]["total_assignments"] == pytest.approx(64.0)
    assert sum(doc["imbalance"]["expert_load"]) > 0
    assert doc["drops"]["mean_dropped_fraction"] is not None


def test_adaptation_report_timeline_with_before_after():
    from flashmoe_tpu.observe import adaptation_report

    flight = [
        {"step": s,
         "moe": [{"layer": 0, "imbalance": 4.0 if s < 5 else 1.2,
                  "dropped_fraction": 0.3 if s < 5 else 0.0}]}
        for s in range(10)
    ]
    records = flight + [
        {"decision": "controller.morph", "step": 5, "trigger": "skew",
         "backend": "local", "dropless": True,
         "overrides": {"drop_tokens": False}, "reason": "drills"},
        {"decision": "controller.cooldown", "step": 7,
         "trigger": "skew", "until": 9},
        {"decision": "controller.demotion_reset", "incarnation": 1,
         "world": 2, "dropped": ["fused"]},
    ]
    rep = adaptation_report(records)
    assert rep["actions"] == {"controller.morph": 1,
                              "controller.cooldown": 1,
                              "controller.demotion_reset": 1}
    morph = next(t for t in rep["timeline"]
                 if t["decision"] == "controller.morph")
    assert morph["before"]["imbalance"] > morph["after"]["imbalance"]
    assert morph["before"]["dropped_fraction"] > \
        morph["after"]["dropped_fraction"]
    # the summary document carries the section
    from flashmoe_tpu.observe import render_text, summarize

    text = render_text(summarize(records))
    assert "self-healing controller" in text
    assert "morph" in text


def test_adaptation_report_empty_without_controller_decisions():
    from flashmoe_tpu.observe import adaptation_report

    rep = adaptation_report([{"decision": "planner.drift"}])
    assert rep == {"actions": {}, "timeline": []}
