"""Self-healing runtime controller (flashmoe_tpu/runtime/controller.py):
trigger dynamics, action planning, live-state re-placement, replica
routing, drift-corrected replan, and manifest persistence.

The end-to-end chaos proofs (sustained skew must morph, a slow device
must re-place, through a real resilient training job) live in the
slow-marked drills of tests/test_chaos.py; this file covers the
controller's host-side machinery fast."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashmoe_tpu.config import MoEConfig
from flashmoe_tpu.planner import adapt
from flashmoe_tpu.runtime.controller import (
    ControllerConfig, MorphAction, ReplaceAction, RuntimeController,
    permute_expert_state,
)
from flashmoe_tpu.utils.telemetry import Metrics


def _cfg(**over):
    base = dict(num_experts=8, expert_top_k=2, hidden_size=64,
                intermediate_size=128, sequence_len=64,
                dtype=jnp.float32, param_dtype=jnp.float32,
                collect_stats=True, is_training=True)
    base.update(over)
    return MoEConfig(**base)


def _stats(load, dropped=0.0):
    load = np.asarray(load, dtype=np.float64)
    mean = max(float(load.mean()), 1e-9)
    return {"expert_load": load.tolist(),
            "dropped_fraction": float(dropped),
            "imbalance": float(load.max()) / mean}


def _ctrl(cfg=None, ccfg=None, **kw):
    m = Metrics()
    c = RuntimeController(cfg or _cfg(), ccfg or ControllerConfig(
        debounce_steps=2, cooldown_steps=4, baseline_steps=2,
        ema_decay=0.5), metrics=m, **kw)
    return c, m


# ----------------------------------------------------------------------
# Config validation
# ----------------------------------------------------------------------

def test_controller_config_validation():
    with pytest.raises(ValueError, match="debounce"):
        ControllerConfig(debounce_steps=0)
    with pytest.raises(ValueError, match="ema_decay"):
        ControllerConfig(ema_decay=1.5)
    with pytest.raises(ValueError, match="slow_factor"):
        ControllerConfig(slow_factor=0.9)


def test_expert_replicas_config_validation():
    with pytest.raises(ValueError, match="own slot"):
        _cfg(expert_replicas=((2, 2),))
    with pytest.raises(ValueError, match="out of range"):
        _cfg(expert_replicas=((0, 9),))
    with pytest.raises(ValueError, match="twice"):
        _cfg(expert_replicas=((0, 3), (1, 3)))
    with pytest.raises(ValueError, match="chains"):
        _cfg(expert_replicas=((0, 1), (1, 2)))
    with pytest.raises(ValueError, match="exactly one replica"):
        # the parity split supports one replica per hot expert; a
        # second pair for the same expert would get zero traffic
        _cfg(expert_replicas=((0, 1), (0, 2)))
    with pytest.raises(ValueError, match="int pairs"):
        _cfg(expert_replicas=((0,),))
    assert _cfg(expert_replicas=((0, 3), (1, 4))).expert_replicas


# ----------------------------------------------------------------------
# Trigger dynamics: debounce, hysteresis, cooldown, budgets
# ----------------------------------------------------------------------

def test_skew_trigger_debounces_and_resets_on_clear():
    c, _ = _ctrl()
    skewed = {"moe_stats": [_stats([60, 1, 1, 1, 1, 1, 1, 1], 0.3)]}
    calm = {"moe_stats": [_stats(np.ones(8), 0.0)]}
    c.observe_step(0, 10.0, skewed)
    assert c._skew_run == 1
    assert c.maybe_act(1) is None          # below the debounce window
    # hysteresis: a clear observation resets the run; the EMA is decayed
    # far enough by repeated calm steps that the condition truly clears
    for s in range(1, 6):
        c.observe_step(s, 10.0, calm)
    assert c._skew_run == 0
    assert c.maybe_act(6) is None


def test_one_step_blip_never_triggers():
    c, m = _ctrl()
    calm = {"moe_stats": [_stats(np.ones(8), 0.0)]}
    blip = {"moe_stats": [_stats([60, 1, 1, 1, 1, 1, 1, 1], 0.5)]}
    for s in range(4):
        c.observe_step(s, 10.0, calm)
    c.observe_step(4, 10.0, blip)
    for s in range(5, 12):
        c.observe_step(s, 10.0, calm)
        assert c.maybe_act(s + 1) is None
    assert c.morphs_used == 0 and c.replaces_used == 0
    assert not [d for d in m.decisions
                if d["decision"].startswith("controller.")]


def test_morph_fires_after_debounce_and_respects_budget_and_cooldown():
    c, m = _ctrl(ccfg=ControllerConfig(
        debounce_steps=2, cooldown_steps=4, baseline_steps=2,
        ema_decay=0.5, morph_budget=1, enable_replace=False))
    skewed = {"moe_stats": [_stats([60, 1, 1, 1, 1, 1, 1, 1], 0.3)]}
    c.observe_step(0, 10.0, skewed)
    c.observe_step(1, 10.0, skewed)
    act = c.maybe_act(2)
    assert isinstance(act, MorphAction) and act.needs_rebuild
    assert act.overrides == {"drop_tokens": False}
    assert c.cfg_overrides == {"drop_tokens": False}
    rec = m.last_decision("controller.morph")
    assert rec is not None and rec["dropless"] and rec["trigger"] == "skew"
    # cooldown: triggers inside the window are recorded, not acted on
    c.observe_step(2, 10.0, skewed)
    c.observe_step(3, 10.0, skewed)
    assert c.maybe_act(4) is None
    cd = m.last_decision("controller.cooldown")
    assert cd is not None and cd["trigger"] == "skew"
    # budget spent: even past the cooldown no second morph fires
    for s in range(4, 12):
        c.observe_step(s, 10.0, skewed)
    assert c.maybe_act(12) is None
    assert c.morphs_used == 1


def test_morph_requires_rebuild_capability():
    c, m = _ctrl(ccfg=ControllerConfig(
        debounce_steps=1, cooldown_steps=2, baseline_steps=2,
        ema_decay=0.5, enable_replace=False))
    skewed = {"moe_stats": [_stats([60, 1, 1, 1, 1, 1, 1, 1], 0.3)]}
    c.observe_step(0, 10.0, skewed)
    assert c.maybe_act(1, can_rebuild=False) is None
    assert c.morphs_used == 0


def test_slow_trigger_plans_replacement_with_rates():
    rates = np.array([0.25, 1.0, 1.0, 1.0])
    c, m = _ctrl(cfg=_cfg(expert_top_k=1),
                 ccfg=ControllerConfig(
                     debounce_steps=2, cooldown_steps=4,
                     baseline_steps=2, ema_decay=0.5,
                     enable_morph=False),
                 n_devices=4, rates_fn=lambda: rates)
    hot = {"moe_stats": [_stats([64, 0, 0, 0, 0, 0, 0, 0])]}
    c.observe_step(0, 10.0, hot)    # baseline (fast)
    c.observe_step(1, 10.0, hot)
    c.observe_step(2, 900.0, hot)   # the device degrades
    c.observe_step(3, 900.0, hot)
    act = c.maybe_act(4)
    assert isinstance(act, ReplaceAction)
    assert sorted(act.perm) == list(range(8))
    assert act.perm != tuple(range(8))
    # hot expert leaves the slow device (slots 0..1)
    new_hot = act.perm.index(0)
    assert new_hot // 2 != 0
    # a dead slot carries the replica, on another device
    assert act.replica_pairs
    h, v = act.replica_pairs[0]
    assert h == new_hot and v // 2 != new_hot // 2
    assert act.overrides["expert_replicas"] == act.replica_pairs
    rec = m.last_decision("controller.replace")
    assert rec["rates"] == rates.tolist()
    assert rec["trigger"] == "slow"


def test_default_rates_fn_is_live_probe_with_chaos_seam():
    """ISSUE 12 satellite: a controller constructed WITHOUT rates_fn
    must re-probe per-device throughput on the slow trigger
    (runtime/throughput.device_rates).  The probe_rates chaos seam
    supplies the degraded reading (what a genuinely slow chip would
    hand the probe), and the resulting re-placement must consume it —
    the decision record carries the probed vector."""
    from flashmoe_tpu.chaos import inject
    from flashmoe_tpu.runtime import throughput

    inject.arm("probe_rates", rates=(0.25, 1.0, 1.0, 1.0))
    try:
        # the seam short-circuits before any backend work
        rates = throughput.device_rates(_cfg(), 4)
        assert list(rates) == [0.25, 1.0, 1.0, 1.0]
        c, m = _ctrl(cfg=_cfg(expert_top_k=1),
                     ccfg=ControllerConfig(
                         debounce_steps=2, cooldown_steps=4,
                         baseline_steps=2, ema_decay=0.5,
                         enable_morph=False),
                     n_devices=4)          # NO rates_fn: default probe
        hot = {"moe_stats": [_stats([64, 0, 0, 0, 0, 0, 0, 0])]}
        c.observe_step(0, 10.0, hot)
        c.observe_step(1, 10.0, hot)
        c.observe_step(2, 900.0, hot)
        c.observe_step(3, 900.0, hot)
        act = c.maybe_act(4)
        assert isinstance(act, ReplaceAction)
        # hot expert leaves the probed-slow device (slots 0..1)
        assert act.perm.index(0) // 2 != 0
        rec = m.last_decision("controller.replace")
        assert rec["rates"] == [0.25, 1.0, 1.0, 1.0]
    finally:
        inject.disarm("probe_rates")


def test_probe_failure_degrades_to_uniform_rates(monkeypatch):
    """A raising probe must never block the step boundary: re-placement
    degrades to uniform rates and records controller.probe_error."""
    from flashmoe_tpu.runtime import throughput

    def boom(*a, **kw):
        raise RuntimeError("probe died")

    monkeypatch.setattr(throughput, "device_rates", boom)
    c, m = _ctrl(cfg=_cfg(expert_top_k=1),
                 ccfg=ControllerConfig(
                     debounce_steps=2, cooldown_steps=4,
                     baseline_steps=2, ema_decay=0.5,
                     enable_morph=False),
                 n_devices=4)
    hot = {"moe_stats": [_stats([64, 0, 0, 0, 0, 0, 0, 0])]}
    c.observe_step(0, 10.0, hot)
    c.observe_step(1, 10.0, hot)
    c.observe_step(2, 900.0, hot)
    c.observe_step(3, 900.0, hot)
    act = c.maybe_act(4)
    assert isinstance(act, ReplaceAction)  # uniform-rate rebalance
    err = m.last_decision("controller.probe_error")
    assert err is not None and "probe died" in err["reason"]
    assert m.last_decision("controller.replace")["rates"] is None


def test_replace_noop_when_layout_already_balanced():
    c, m = _ctrl(ccfg=ControllerConfig(
        debounce_steps=2, cooldown_steps=4, baseline_steps=2,
        ema_decay=0.5, enable_morph=False), n_devices=4)
    balanced = {"moe_stats": [_stats(np.ones(8))]}
    c.observe_step(0, 10.0, balanced)
    c.observe_step(1, 10.0, balanced)
    c.observe_step(2, 900.0, balanced)  # slow, but nothing to re-place
    c.observe_step(3, 900.0, balanced)
    assert c.maybe_act(4) is None
    assert c.replaces_used == 0
    cd = m.last_decision("controller.cooldown")
    assert cd is not None and "noop" in cd["reason"]


def test_action_resets_baseline_for_the_new_regime():
    c, _ = _ctrl(ccfg=ControllerConfig(
        debounce_steps=1, cooldown_steps=3, baseline_steps=2,
        ema_decay=0.5, enable_replace=False))
    skewed = {"moe_stats": [_stats([60, 1, 1, 1, 1, 1, 1, 1], 0.3)]}
    c.observe_step(0, 10.0, skewed)
    assert isinstance(c.maybe_act(1), MorphAction)
    assert c.baseline_ms is None and c.step_ms_ema is None


# ----------------------------------------------------------------------
# Persistence: state_dict round trip + monotonic budgets
# ----------------------------------------------------------------------

def test_state_dict_roundtrip_and_monotonic_budgets():
    c, _ = _ctrl()
    c.overrides = {"drop_tokens": False,
                   "expert_replicas": ((2, 5),)}
    c.morphs_used, c.replaces_used = 1, 2
    sd = c.state_dict()
    import json

    json.dumps(sd)  # manifest-ready
    c2, _ = _ctrl()
    c2.load_state_dict(sd)
    assert c2.cfg_overrides == c.overrides
    assert isinstance(c2.overrides["expert_replicas"], tuple)
    # budgets never refill on a rewind to an older manifest
    c2.morphs_used = 5
    c2.load_state_dict(sd)
    assert c2.morphs_used == 5 and c2.replaces_used == 2
    # a manifest without replicas clears the replica map
    c2.load_state_dict({"overrides": {"drop_tokens": False}})
    assert "expert_replicas" not in c2.cfg_overrides


def test_manifest_carries_controller_state(tmp_path, devices):
    from flashmoe_tpu.runtime import checkpoint as ckpt
    from flashmoe_tpu.runtime.trainer import init_state, make_optimizer

    cfg = _cfg(num_layers=1, vocab_size=256, num_heads=2)
    opt = make_optimizer(cfg, total_steps=4)
    state = init_state(jax.random.PRNGKey(0), cfg, opt)
    d = str(tmp_path / "ckpt")
    cs = {"overrides": {"drop_tokens": False}, "morphs_used": 1,
          "replaces_used": 0, "timeline": []}
    ckpt.save(d, state, step=2, controller_state=cs)
    assert ckpt.load_controller_state(d, 2) == cs
    # legacy manifests answer None, not an error
    ckpt.save(d, state, step=3)
    assert ckpt.load_controller_state(d, 3) is None


# ----------------------------------------------------------------------
# Live-state re-placement + replica routing
# ----------------------------------------------------------------------

def test_permute_expert_state_preserves_function():
    from flashmoe_tpu.models import transformer
    from flashmoe_tpu.runtime.trainer import init_state, make_optimizer

    cfg = _cfg(num_layers=1, vocab_size=256, num_heads=2,
               collect_stats=False, drop_tokens=False)
    opt = make_optimizer(cfg, total_steps=4)
    state = init_state(jax.random.PRNGKey(0), cfg, opt)
    toks = jax.random.randint(jax.random.PRNGKey(1),
                              (2, cfg.sequence_len), 0, 256)
    base, _ = transformer.forward(state.params, toks, cfg)
    perm = (3, 1, 0, 2, 7, 6, 5, 4)
    st2 = permute_expert_state(state, cfg, perm)
    out, _ = transformer.forward(st2.params, toks, cfg)
    # identical function; numerics equivalent up to router-softmax
    # reassociation (the expert-axis sums reorder)
    np.testing.assert_allclose(np.asarray(base), np.asarray(out),
                               rtol=2e-5, atol=2e-5)
    # params AND their optimizer moments moved together
    w = np.asarray(state.params["layers"][0]["moe"]["w_up"])
    w2 = np.asarray(st2.params["layers"][0]["moe"]["w_up"])
    np.testing.assert_array_equal(w2, w[list(perm)])
    gw = np.asarray(state.params["layers"][0]["moe"]["gate_w"])
    gw2 = np.asarray(st2.params["layers"][0]["moe"]["gate_w"])
    np.testing.assert_array_equal(gw2, gw[:, list(perm)])
    mus = [x for x in jax.tree_util.tree_leaves(state.opt_state)
           if getattr(x, "shape", None) == w.shape]
    mus2 = [x for x in jax.tree_util.tree_leaves(st2.opt_state)
            if getattr(x, "shape", None) == w.shape]
    assert mus and len(mus) == len(mus2)
    for a, b in zip(mus, mus2):
        np.testing.assert_array_equal(np.asarray(b),
                                      np.asarray(a)[list(perm)])


def test_permute_rejects_non_permutation():
    from flashmoe_tpu.runtime.trainer import init_state, make_optimizer

    cfg = _cfg(num_layers=1, vocab_size=256, num_heads=2)
    state = init_state(jax.random.PRNGKey(0), cfg,
                       make_optimizer(cfg, total_steps=4))
    with pytest.raises(ValueError, match="permutation"):
        permute_expert_state(state, cfg, (0, 0, 1, 2, 3, 4, 5, 6))


def test_replica_routing_splits_hot_and_preserves_hot_tokens():
    """With the victim's FFN weights overwritten by the hot expert's
    copy, every token routed to the hot expert computes bit-identically
    (one value-identical replica processes it), and the physical load
    histogram shows the split."""
    from flashmoe_tpu.models.reference import init_moe_params
    from flashmoe_tpu.ops.gate import router
    from flashmoe_tpu.ops.moe import moe_layer

    cfg = _cfg(drop_tokens=False, collect_stats=True)
    p = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (cfg.tokens, cfg.hidden_size), jnp.float32)
    r = router(x, jnp.asarray(p["gate_w"], jnp.float32), cfg,
               use_pallas=False)
    hot = int(np.bincount(
        np.asarray(r.expert_idx).ravel(), minlength=8).argmax())
    victim = int(np.bincount(
        np.asarray(r.expert_idx).ravel(), minlength=8).argmin())
    base = moe_layer(p, x, cfg, use_pallas=False)

    p2 = dict(p)
    for k in ("w_up", "b_up", "w_down", "b_down"):
        arr = np.asarray(p[k]).copy()
        arr[victim] = arr[hot]
        p2[k] = jnp.asarray(arr)
    cfg_r = cfg.replace(expert_replicas=((hot, victim),))
    rep = moe_layer(p2, x, cfg_r, use_pallas=False)

    # tokens that never touched the victim expert are bit-identical
    touched = np.any(np.asarray(r.expert_idx) == victim, axis=1)
    np.testing.assert_array_equal(np.asarray(base.out)[~touched],
                                  np.asarray(rep.out)[~touched])
    # the hot slot's physical load split across the replica pair
    load_b = np.asarray(base.stats.expert_load)
    load_r = np.asarray(rep.stats.expert_load)
    assert load_r[hot] < load_b[hot]
    assert load_r[victim] > load_b[victim]
    assert load_r.sum() == load_b.sum()


def test_replicas_off_is_default_and_router_untouched():
    from flashmoe_tpu.models.reference import init_moe_params
    from flashmoe_tpu.ops.gate import apply_replicas, router

    cfg = _cfg()
    assert cfg.expert_replicas == ()
    p = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (cfg.tokens, cfg.hidden_size), jnp.float32)
    r = router(x, jnp.asarray(p["gate_w"], jnp.float32), cfg,
               use_pallas=False)
    assert apply_replicas(r, cfg) is r


# ----------------------------------------------------------------------
# Drift-corrected replan (planner/adapt.py)
# ----------------------------------------------------------------------

def test_replan_single_chip_dropless_flip():
    plan = adapt.replan(_cfg(), 1, prefer_dropless=True)
    assert plan.overrides == {"drop_tokens": False}
    assert plan.dropless and plan.mode == "dropless"
    # already dropless: nothing to do
    plan2 = adapt.replan(_cfg(drop_tokens=False), 1,
                         prefer_dropless=True)
    assert plan2.is_noop


def test_replan_prefers_ragged_for_drop_trigger_at_width():
    cfg = _cfg(num_experts=16, ep=8, sequence_len=128)
    plan = adapt.replan(cfg, 8, gen="v5e", prefer_dropless=True)
    assert plan.dropless
    assert plan.overrides.get("drop_tokens") is False
    if plan.backend == "ragged":
        assert plan.overrides.get("moe_backend") == "ragged"


def test_replan_measured_ledger_demotes_slow_path():
    """A measured cost far above every alternative MUST move the
    selection off the running path — the measurement corrects the
    running family's prior and then competes against the other
    families' priors (select_path's measured-winner rule would instead
    re-elect the only-measured degraded path: the bug this pins)."""
    cfg = _cfg(num_experts=16, ep=8, sequence_len=128,
               moe_backend="collective")
    fam = adapt.current_family(cfg, 8)
    assert fam == "collective"
    plan = adapt.replan(cfg, 8, gen="v5e",
                        measured_ms=adapt.measured_ledger(fam, 1e6))
    assert plan.mode == "reselect"
    assert plan.backend != "collective"
    assert plan.overrides.get("moe_backend") == plan.backend
    assert plan.predicted_ms < 1e6
    # a healthy measurement re-elects the running path (noop)
    plan2 = adapt.replan(cfg, 8, gen="v5e",
                         measured_ms=adapt.measured_ledger(fam, 1e-6))
    assert plan2.is_noop


# ----------------------------------------------------------------------
# DCN wire morph (ISSUE 13: phase-ledger a2a dominance -> wire_dtype_dcn)
# ----------------------------------------------------------------------

_A2A_HEAVY = {"phase_ms": {"moe.gate": 1.0, "moe.a2a_dispatch": 5.0,
                           "moe.expert": 2.0, "moe.a2a_combine": 4.0,
                           "moe.combine": 0.5}}
_A2A_LIGHT = {"phase_ms": {"moe.gate": 1.0, "moe.a2a_dispatch": 0.5,
                           "moe.expert": 9.0, "moe.a2a_combine": 0.5,
                           "moe.combine": 0.5}}


def test_wire_morph_fires_on_sustained_a2a_dominance():
    c, m = _ctrl(ccfg=ControllerConfig(
        debounce_steps=2, cooldown_steps=4, baseline_steps=2,
        ema_decay=0.5, enable_morph=False, enable_replace=False),
        slices=2)
    c.observe_step(0, 10.0, _A2A_HEAVY)
    assert c._a2a_run == 1
    assert c.maybe_act(1) is None          # below the debounce window
    c.observe_step(1, 10.0, _A2A_HEAVY)
    act = c.maybe_act(2)
    assert isinstance(act, MorphAction) and act.needs_rebuild
    assert act.overrides == {"wire_dtype_dcn": "e4m3"}
    assert act.trigger == "a2a"
    assert c.cfg_overrides == {"wire_dtype_dcn": "e4m3"}
    rec = m.last_decision("controller.wire_morph")
    assert rec is not None and rec["trigger"] == "a2a"
    assert rec["a2a_share_ema"] is not None
    # the morphed config actually constructs (runner rebuild path)
    assert c.apply_to(c.cfg).wire_dtype_dcn == "e4m3"
    # knob now on: the trigger can never re-arm (no oscillation), and
    # the budget is spent regardless
    for s in range(2, 20):
        c.observe_step(s, 10.0, _A2A_HEAVY)
    assert c._a2a_run == 0
    assert c.maybe_act(20) is None
    assert c.wire_morphs_used == 1


def test_wire_morph_needs_multislice_and_resets_on_clear():
    # single-slice job: the signal may spike but the morph never arms
    c, m = _ctrl(ccfg=ControllerConfig(debounce_steps=1,
                                       enable_morph=False,
                                       enable_replace=False))
    c.observe_step(0, 10.0, _A2A_HEAVY)
    assert c._a2a_run == 0 and c.maybe_act(1) is None
    assert not [d for d in m.decisions
                if d["decision"] == "controller.wire_morph"]
    # multi-slice: hysteresis — a clear observation resets the run
    c2, _ = _ctrl(ccfg=ControllerConfig(
        debounce_steps=3, enable_morph=False, enable_replace=False),
        slices=4)
    c2.observe_step(0, 10.0, _A2A_HEAVY)
    c2.observe_step(1, 10.0, _A2A_HEAVY)
    c2.observe_step(2, 10.0, _A2A_LIGHT)
    assert c2._a2a_run == 0


def test_wire_morph_respects_cooldown_and_persists():
    c, m = _ctrl(ccfg=ControllerConfig(
        debounce_steps=1, cooldown_steps=6, baseline_steps=2,
        ema_decay=0.5, enable_morph=False, enable_replace=False,
        wire_morph_dtype="bf16", wire_morph_budget=2), slices=2)
    c.observe_step(0, 10.0, _A2A_HEAVY)
    act = c.maybe_act(1)
    assert act is not None
    assert act.overrides == {"wire_dtype_dcn": "bf16"}
    # cooldown: a re-trigger inside the window is recorded, not acted
    # (the knob is on now, so the trigger clears anyway; drop it back
    # off to prove the window itself suppresses)
    c.overrides.pop("wire_dtype_dcn")
    c.observe_step(1, 10.0, _A2A_HEAVY)
    assert c.maybe_act(2) is None
    cd = m.last_decision("controller.cooldown")
    assert cd is not None and cd["trigger"] == "a2a"
    # manifest round trip keeps the spent budget (monotonic)
    sd = c.state_dict()
    assert sd["wire_morphs_used"] == 1
    c2, _ = _ctrl(slices=2)
    c2.load_state_dict(sd)
    assert c2.wire_morphs_used == 1


def test_wire_morph_slices_autodetect(monkeypatch, devices):
    """Production wiring: a controller built WITHOUT slices= (the
    resilient_train / trainer call sites) auto-detects the multi-slice
    topology from the bootstrapped GroupPlan / mocked detection, so
    the wire-morph axis arms on real multi-slice jobs."""
    from flashmoe_tpu.runtime.controller import detected_slices

    monkeypatch.delenv("FLASHMOE_MOCK_SLICES", raising=False)
    assert detected_slices() == 1
    assert RuntimeController(_cfg()).slices == 1
    monkeypatch.setenv("FLASHMOE_MOCK_SLICES", "2")
    assert detected_slices() == 2
    assert RuntimeController(_cfg()).slices == 2
    # detection must never block a step boundary: garbage mock -> 1
    monkeypatch.setenv("FLASHMOE_MOCK_SLICES", "banana")
    assert detected_slices() == 1


# ----------------------------------------------------------------------
# Speculation morph trigger (ISSUE 20)
# ----------------------------------------------------------------------

def _spec_ctrl(**cc):
    base = dict(debounce_steps=2, cooldown_steps=4, baseline_steps=2,
                ema_decay=0.5, enable_spec_morph=True,
                spec_accept_floor=0.5)
    base.update(cc)
    return _ctrl(ccfg=ControllerConfig(**base))


def test_spec_morph_fires_after_debounce_with_budget():
    from flashmoe_tpu.runtime.controller import SpecMorphAction

    c, m = _spec_ctrl()
    # no-draft observations (None) never debounce toward a morph
    c.observe_spec(0, None)
    assert c._spec_lo_run == 0
    c.observe_spec(0, 0.2)
    assert c.maybe_morph_spec(1) is None      # below the window
    c.observe_spec(1, 0.9)                    # recovery resets the run
    assert c._spec_lo_run == 0
    c.observe_spec(2, 0.2)
    c.observe_spec(3, 0.1)
    act = c.maybe_morph_spec(4)
    assert isinstance(act, SpecMorphAction) and act.kind == "off"
    assert act.trigger == "accept_low"
    rec = m.last_decision("controller.spec_morph")
    assert rec is not None and rec["kind"] == "off"
    assert rec["break_even"] == 0.5
    assert c.spec_morphs_used == 1
    assert c.snapshot()["budgets"]["spec_morph"] == 0
    # budget spent: sustained low acceptance never double-fires
    for s in range(10, 20):
        c.observe_spec(s, 0.0)
    assert c.maybe_morph_spec(20) is None


def test_spec_morph_respects_cooldown_and_spec_off():
    c, m = _spec_ctrl(spec_morph_budget=2)
    c.observe_spec(0, 0.1)
    c.observe_spec(1, 0.1)
    assert c.maybe_morph_spec(2) is not None
    # inside the cooldown window: suppressed (and logged once)
    c.observe_spec(3, 0.1)
    c.observe_spec(4, 0.1)
    assert c.maybe_morph_spec(4) is None
    cd = [d for d in m.decisions
          if d["decision"] == "controller.cooldown"
          and d.get("trigger") == "spec"]
    assert len(cd) == 1
    # spec already off: never acts, whatever the run length
    c.observe_spec(20, 0.0)
    c.observe_spec(21, 0.0)
    assert c.maybe_morph_spec(22, spec_on=False) is None
    # disabled trigger: no action either
    c2, _ = _spec_ctrl(enable_spec_morph=False)
    c2.observe_spec(0, 0.0)
    c2.observe_spec(1, 0.0)
    assert c2.maybe_morph_spec(2) is None


def test_spec_floor_resolution_and_state_roundtrip():
    # no configured floor: the planner break-even feeds the trigger
    c, _ = _spec_ctrl(spec_accept_floor=None)
    c.observe_spec(0, 0.3, break_even=0.4)
    assert c._spec_lo_run == 1
    c.observe_spec(1, 0.3, break_even=0.2)    # above break-even: reset
    assert c._spec_lo_run == 0
    # neither floor nor break-even: observation folds EMA, no trigger
    c.observe_spec(2, 0.1)
    assert c._spec_lo_run == 0
    assert c.spec_accept_ema is not None
    with pytest.raises(ValueError, match="spec_accept_floor"):
        ControllerConfig(spec_accept_floor=1.5)
    # persistence: spec_morphs_used survives a state roundtrip and
    # stays monotonic
    a, _ = _spec_ctrl()
    a.observe_spec(0, 0.1)
    a.observe_spec(1, 0.1)
    assert a.maybe_morph_spec(2) is not None
    b, _ = _spec_ctrl()
    b.load_state_dict(a.state_dict())
    assert b.spec_morphs_used == 1
    assert b.maybe_morph_spec(10) is None     # budget rides the state
