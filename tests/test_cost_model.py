"""The hardware-independent perf harness (VERDICT r4 next #2): the
analytical byte model's orderings are the claims the kernels were built
on — assert them so a refactor that silently regresses traffic fails CI,
and cross-check the model against XLA's own compiled cost analysis where
HLO can see the whole path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashmoe_tpu.analysis import (
    PathCost, candidate_table, layer_flops, path_costs, xla_cost,
)
from flashmoe_tpu.config import BENCH_CONFIGS, MoEConfig

REF = BENCH_CONFIGS["reference"]
F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)


def test_gather_moves_fewer_bytes_than_explicit():
    """The gather-fused kernel exists to kill the [E, C, H] dispatch
    buffer's write+read; the model must show exactly that delta and
    nothing else moving."""
    ex = path_costs(REF, "explicit")
    ga = path_costs(REF, "gather")
    assert ga.total_bytes < ex.total_bytes
    assert ga.dispatch_bytes == 0.0
    assert ex.dispatch_bytes > 0.0
    # identical FLOPs: it is a data-movement optimization
    assert ga.flops == ex.flops


def test_in_kernel_combine_clears_post_kernel_critical_path():
    """The sorted-return combine's entire point: the combine traffic
    runs inside the kernel (overlapping returns), so nothing remains on
    the post-kernel critical path; the slab variant leaves the full XLA
    combine there."""
    d = 8
    cfg = REF.replace(ep=d)
    slab = path_costs(cfg, "fused", d_world=d)
    fused = path_costs(cfg, "fused_combine", d_world=d)
    assert fused.post_kernel_bytes == 0.0
    assert slab.post_kernel_bytes > 0.0
    # the in-kernel combine reads token-sorted rows (S*K) + a 4-byte
    # weight per row; the XLA combine reads the whole padded slab
    # (slots >= S*K).  At CF=1 slots == S*K exactly, so the sorted read
    # ties and only the tiny weight column separates them
    assert fused.combine_bytes <= slab.combine_bytes * 1.001
    # with real capacity padding the sorted read is strictly smaller
    padded = cfg.replace(capacity_factor=2.0)
    assert (path_costs(padded, "fused_combine", d_world=d).combine_bytes
            < path_costs(padded, "fused", d_world=d).combine_bytes)


def test_fused_weight_restreaming_is_exposed_not_hidden(monkeypatch):
    """The fused kernel's per-source schedules re-stream every local
    expert's weights once per source rank — d_world x the grouped
    kernels' once-per-expert reads (code-review r5 finding #1).  The
    model must CHARGE that, not hide it; and the round-5 arrival-batched
    schedule (own slab at step 0, remotes expert-major at the final
    step) must bring it down to exactly 2x — the schedule's entire
    reason to exist."""
    from flashmoe_tpu import tuning

    d = 8
    cfg = REF.replace(ep=d)
    monkeypatch.delenv("FLASHMOE_FUSED_BATCHED", raising=False)
    xla = path_costs(cfg, "xla", d_world=d)
    # default at d >= 3: the batched schedule, two weight streams
    fused = path_costs(cfg, "fused", d_world=d)
    assert fused.weight_bytes == 2 * xla.weight_bytes
    # per-source schedule (batched disabled): the honest d x cost
    monkeypatch.setenv("FLASHMOE_FUSED_BATCHED", "0")
    tuning._load.cache_clear()
    per_src = path_costs(cfg, "fused", d_world=d)
    monkeypatch.delenv("FLASHMOE_FUSED_BATCHED")
    assert per_src.weight_bytes == d * xla.weight_bytes
    # at a single chip there is one source: compute-side traffic (minus
    # the local slab round-trips counted as comm) matches the baseline
    f1 = path_costs(REF, "fused", d_world=1)
    x1 = path_costs(REF, "xla", d_world=1)
    assert f1.weight_bytes == x1.weight_bytes
    assert f1.total_bytes - f1.comm_bytes <= x1.total_bytes * 1.01


def test_resident_schedule_flattens_weight_bytes(tmp_path, monkeypatch):
    """VERDICT r4 weak #4 / next #4: with n_row_tiles > 1 the streaming
    schedule pays n_row_tiles x the weight HBM traffic; the
    weights-resident schedule must hold weight bytes flat (one read per
    expert) at the cost of re-streamed activations."""
    import json

    from flashmoe_tpu import tuning

    # deepseek-ish shape: per-(rank, expert) capacity spans many row
    # tiles, the exact case the resident schedule exists for
    cfg = MoEConfig(num_experts=8, expert_top_k=4, hidden_size=1024,
                    intermediate_size=1408, sequence_len=8192,
                    capacity_factor=1.0, drop_tokens=True, ep=2)

    def with_knob(resident):
        p = tmp_path / f"t{resident}.json"
        p.write_text(json.dumps({"generation": "x", "entries": [{
            "kernel": "fused_ep", "match": {},
            "set": {"weights_resident": resident}}]}))
        monkeypatch.setenv("FLASHMOE_TUNING_FILE", str(p))
        tuning._load.cache_clear()
        try:
            return path_costs(cfg, "fused", d_world=2)
        finally:
            monkeypatch.delenv("FLASHMOE_TUNING_FILE")
            tuning._load.cache_clear()

    resident = with_knob(True)
    streaming = with_knob(False)
    assert resident.weight_bytes < streaming.weight_bytes
    # flat = one stream of each expert's matrices per SOURCE slab (the
    # per-source d_world factor is inherent to the slab grid — see
    # test_fused_weight_restreaming_is_exposed_not_hidden); the resident
    # schedule removes the per-row-tile factor on top of it
    d = 2
    nlx = cfg.num_experts // d
    w_once = nlx * 2 * cfg.hidden_size * cfg.intermediate_size * \
        jnp.dtype(cfg.dtype).itemsize
    assert resident.weight_bytes == w_once * d
    # the trade is explicit: activations re-stream
    assert resident.activation_bytes >= streaming.activation_bytes
    # and at this shape the heuristic chooser must agree with the knob
    monkeypatch.delenv("FLASHMOE_TUNING_FILE", raising=False)
    tuning._load.cache_clear()
    auto = path_costs(cfg, "fused", d_world=2)
    assert auto.weight_bytes == resident.weight_bytes


def test_total_bytes_accounting_is_consistent():
    for p in ("xla", "explicit", "gather", "fused", "fused_combine"):
        c = path_costs(REF.replace(ep=4), p, d_world=4)
        assert isinstance(c, PathCost)
        assert c.total_bytes == pytest.approx(
            c.weight_bytes + c.activation_bytes + c.dispatch_bytes
            + c.comm_bytes + c.combine_bytes)
        assert c.post_kernel_bytes <= c.total_bytes
        assert c.flops > 0


def test_xla_cost_analysis_matches_flop_model():
    """Cross-check the analytical FLOP model against the compiler's own
    cost analysis of the XLA path (HLO sees this path end to end; no
    custom calls hide work).  Small config so the 1-core CPU compile
    stays quick."""
    from flashmoe_tpu.models.reference import init_moe_params
    from flashmoe_tpu.ops.moe import moe_layer

    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=256,
                    intermediate_size=512, sequence_len=256,
                    capacity_factor=1.0, drop_tokens=True, **F32)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (cfg.tokens, cfg.hidden_size), jnp.float32)

    cost = xla_cost(
        lambda p, xx: moe_layer(p, xx, cfg, use_pallas=False).out,
        params, x)
    if cost["flops"] is None:
        pytest.skip("backend cost model reports no flops")
    model = layer_flops(cfg)
    # the XLA path runs the FFN over every padded capacity slot (slots
    # >= S*K) plus routing/one-hot bookkeeping, so the compiled count
    # brackets the model from above but must stay the same order
    assert cost["flops"] >= 0.8 * model
    assert cost["flops"] <= 6.0 * model


def test_xla_dispatch_bytes_match_model():
    """Where HLO sees a whole stage, the byte model must agree with the
    compiler, not just order paths: the dispatch build (plan + gather
    into the capacity buffer) is pure XLA, and its modeled term
    (s*h + slots*h elements) lands within a few percent of the
    compiled cost analysis — anchoring the modeled terms the custom
    calls hide."""
    from flashmoe_tpu.ops import dispatch as dsp

    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=256,
                    intermediate_size=512, sequence_len=256,
                    capacity_factor=1.0, drop_tokens=True, **F32)
    cap = cfg.capacity_for(cfg.tokens)

    def build(x, eidx):
        plan = dsp.make_plan(eidx, cfg, cap)
        return dsp.dispatch(x, plan, cfg, cap)

    x = jax.ShapeDtypeStruct((cfg.tokens, cfg.hidden_size), jnp.float32)
    ei = jax.ShapeDtypeStruct((cfg.tokens, cfg.expert_top_k), jnp.int32)
    cost = xla_cost(build, x, ei)
    if cost["bytes"] is None:
        pytest.skip("backend cost model reports no bytes")
    s, h = cfg.tokens, cfg.hidden_size
    slots = cfg.num_experts * cap
    model = (s * h + slots * h) * 4
    # loose bracket: routing bookkeeping (sorts, index planes) adds a
    # few percent on top of the modeled activation movement
    assert model * 0.9 <= cost["bytes"] <= model * 1.5, \
        (cost, model)


def test_schedule_resolution_decision_table(monkeypatch):
    """The decision table: which FFN schedule each bench config
    resolves to at d=8.  Since ISSUE 12 the mixtral row is the
    row-windowed schedule's reason to exist: its 14336-wide expert
    hidden slab exceeds VMEM for every weights-once schedule (batched /
    resident stay infeasible), but the window-major rowwin schedule
    bounds weight traffic at exactly 2x the collective path — the
    ACCEPTANCE CRITERION pin: <= 2.5x, vs the 40x the stream fallback
    pays."""
    from flashmoe_tpu.analysis import _geom
    from flashmoe_tpu.parallel.fused import schedule_table

    monkeypatch.delenv("FLASHMOE_FUSED_BATCHED", raising=False)
    monkeypatch.delenv("FLASHMOE_FUSED_ROWWIN", raising=False)
    assert _geom(REF, 8)["schedule"] == "batched"
    assert _geom(BENCH_CONFIGS["deepseek"], 8)["schedule"] == "batched"
    assert _geom(BENCH_CONFIGS["weak_scaling_256"], 8)["schedule"] == \
        "batched"
    mix = _geom(BENCH_CONFIGS["mixtral"], 8)
    assert mix["schedule"] == "rowwin"
    t = schedule_table(BENCH_CONFIGS["mixtral"], 8)
    assert not t["feasible"]["batched"] and not t["feasible"]["resident"]
    assert t["feasible"]["rowwin"] and t["kw"] is not None
    fused = path_costs(BENCH_CONFIGS["mixtral"], "fused", d_world=8)
    coll = path_costs(BENCH_CONFIGS["mixtral"], "xla", d_world=8)
    # the ISSUE 12 acceptance bar: modeled mixtral-at-ep=8 fused weight
    # traffic under rowwin <= 2.5x the collective path's (exactly 2x:
    # one K-windowed pass for the own slab, one for the remote batch)
    assert fused.weight_bytes <= 2.5 * coll.weight_bytes
    assert fused.weight_bytes == 2 * coll.weight_bytes
    # the stream fallback's honest 40x stays exposed, not hidden
    stream = path_costs(BENCH_CONFIGS["mixtral"], "fused", d_world=8,
                        schedule="stream")
    assert stream.weight_bytes > 20 * coll.weight_bytes


def test_rowwin_prices_activation_restreaming(monkeypatch):
    """The rowwin schedule's byte trade must be charged, not hidden:
    weight bytes collapse to the 2-pass bound, while the activation
    column grows by the per-window x re-reads AND the f32 partial-sum
    round-trips at every interior window boundary — the term that must
    be charged before any row-windowed rescue is believed."""
    from flashmoe_tpu.analysis import _geom

    monkeypatch.delenv("FLASHMOE_FUSED_BATCHED", raising=False)
    mix = BENCH_CONFIGS["mixtral"]
    g = _geom(mix, 8, schedule="rowwin")
    n_win = g["n_i_chunks"]
    assert n_win > 1  # i=14336 can never be one VMEM window
    rw = path_costs(mix, "fused", d_world=8, schedule="rowwin")
    st = path_costs(mix, "fused", d_world=8, schedule="stream")
    assert rw.weight_bytes < st.weight_bytes
    assert rw.activation_bytes > st.activation_bytes
    slots = 8 * (mix.num_experts // 8) * g["cap"]
    # the accumulator term is exactly (n_win - 1) read+write f32 passes
    acc_bytes = (n_win - 1) * slots * g["h"] * 8.0
    base = path_costs(mix, "fused", d_world=8, schedule="batched")
    # batched at the same window count would re-read x the same number
    # of times (n_i_chunks differs though); assert the rowwin total
    # includes the acc term by reconstruction instead
    x_reads = slots * g["h"] * g["dt"] * n_win
    gate = mix.tokens // 8 * g["h"] * g["dt"] + g["h"] * mix.num_experts * g["dt"]
    y_stage = slots * g["h"] * g["dt"]
    assert rw.activation_bytes == pytest.approx(
        gate + x_reads + y_stage + acc_bytes)
    assert base.flops == rw.flops  # a data-movement schedule, not math


def test_candidate_table_renders():
    t = candidate_table(REF.replace(ep=8), d_world=8)
    assert "fused_combine" in t and "| path |" in t


def test_overlap_bound_reference_v5e8(monkeypatch):
    """The analytical bound a hardware --overlap run is judged against
    (VERDICT r4 next #8), per FFN schedule.  Per-source at the reference
    config on v5e-8 is compute-bound at roofline (C > t_x + C/d), so it
    should hide (almost) all communication: OE well above 1.25.  The
    batched schedule trades some of that overlap for its 2x weight
    streams (only the own slab's C/d hides arrivals, and returns issue
    per expert, so the tail waits t_x/nlx), so its bound sits strictly
    lower — both are reported so a measurement is judged against the
    schedule that actually ran."""
    from flashmoe_tpu.parallel.overlap import overlap_bound

    monkeypatch.delenv("FLASHMOE_FUSED_BATCHED", raising=False)
    b = overlap_bound(REF, 8, "v5e", schedule="per_source")
    assert b["compute_bound"]
    assert 1.25 <= b["overlap_efficiency_bound"] <= 2.0
    # the default resolution at d=8 is the batched schedule
    bb = overlap_bound(REF, 8, "v5e")
    assert bb["schedule"] == "batched"
    assert 1.0 <= bb["overlap_efficiency_bound"] < \
        b["overlap_efficiency_bound"]
    # calibrated at the measured round-2 mxu_util (0.512): compute
    # stretches, comm stays — the bound must drop toward serialized
    cal = overlap_bound(REF, 8, "v5e", mxu_fraction=0.512,
                        schedule="per_source")
    assert cal["overlap_efficiency_bound"] < b["overlap_efficiency_bound"]
    assert cal["overlap_efficiency_bound"] >= 1.0
    # more ranks shrink per-rank compute faster than per-rank comm
    # (b_dir ~ (d-1)/d), pushing toward the comm-bound regime
    b64 = overlap_bound(REF, 64, "v5e", schedule="per_source")
    assert b64["t_x_ms"] / b64["compute_ms"] > \
        b["t_x_ms"] / b["compute_ms"]
