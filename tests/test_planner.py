"""Planner CI gates: golden predicted-latency tables, error paths,
measured-override precedence, and byte-model consistency.

The golden comparison is the review gate the ISSUE asks for: any change
that moves a canonical prediction > 0.1% or flips a predicted winner
fails here and must ship a regenerated ``golden.json``
(``python -m flashmoe_tpu.planner --write-golden``) in the same PR.
"""

import json

import jax.numpy as jnp
import pytest

from flashmoe_tpu.analysis import a2a_transport_cost, path_costs
from flashmoe_tpu.config import BENCH_CONFIGS, MoEConfig
from flashmoe_tpu.planner.golden import (
    GOLDEN_D, GOLDEN_GENS, GOLDEN_RTOL, golden_snapshot, load_golden,
)
from flashmoe_tpu.planner.model import explain_table, predict_paths
from flashmoe_tpu.planner.select import (
    _cached_backend, resolve_moe_backend, select_path,
)
from flashmoe_tpu.utils.telemetry import metrics

REF = BENCH_CONFIGS["reference"]


@pytest.fixture(autouse=True)
def _hermetic(monkeypatch):
    """The model consults env knobs and caches; pin both per test."""
    for var in ("FLASHMOE_FUSED_BATCHED", "FLASHMOE_TUNING_FILE",
                "FLASHMOE_TPU_GEN", "FLASHMOE_MOCK_SLICES"):
        monkeypatch.delenv(var, raising=False)
    from flashmoe_tpu import tuning

    tuning._load.cache_clear()
    _cached_backend.cache_clear()
    yield
    tuning._load.cache_clear()
    _cached_backend.cache_clear()


# ----------------------------------------------------------------------
# Golden tables
# ----------------------------------------------------------------------

def test_golden_tables_match_model():
    """Recompute every golden prediction and compare: terms within
    GOLDEN_RTOL, winners and feasibility exactly — across every
    (config, generation, wire-dtype, chunk-count) point."""
    live, frozen = golden_snapshot(), load_golden()
    assert live["d"] == frozen["d"] == GOLDEN_D
    assert set(live["configs"]) == set(frozen["configs"])
    for cname, gens in frozen["configs"].items():
        for gen, wires in gens.items():
            for wname, chunks in wires.items():
                for chname, g in chunks.items():
                    l = live["configs"][cname][gen][wname][chname]
                    assert l["winner"] == g["winner"], (
                        f"predicted winner flipped for {cname}@{gen}"
                        f"[wire={wname},chunks={chname}]: "
                        f"{g['winner']} -> {l['winner']}; "
                        f"if intentional, regenerate with python -m "
                        f"flashmoe_tpu.planner --regen-golden and "
                        f"justify in the PR")
                    assert l["backend"] == g["backend"]
                    assert set(l["paths"]) == set(g["paths"])
                    for pname, terms in g["paths"].items():
                        lt = l["paths"][pname]
                        assert lt["feasible"] == terms["feasible"], (
                            cname, gen, wname, chname, pname)
                        for term, want in terms.items():
                            if term == "feasible":
                                continue
                            assert lt[term] == pytest.approx(
                                want, rel=GOLDEN_RTOL, abs=1e-9), (
                                f"{cname}@{gen}[{wname},{chname}]"
                                f"/{pname}.{term}")


def test_golden_tables_cover_wire_dimension():
    """CI gate for the knob dimension itself: every golden (config, gen)
    point must carry every GOLDEN_WIRES variant, so a future knob added
    to GOLDEN_WIRES cannot silently skip the CI-gated tables — and the
    compressed variant must actually be cheaper on the wire."""
    from flashmoe_tpu.planner.golden import GOLDEN_WIRES

    frozen = load_golden()
    assert set(GOLDEN_WIRES) >= {"off", "e4m3"}
    for cname, gens in frozen["configs"].items():
        for gen, wires in gens.items():
            assert set(wires) == set(GOLDEN_WIRES), (cname, gen)
            off = wires["off"]["serial"]["paths"]["collective"]
            on = wires["e4m3"]["serial"]["paths"]["collective"]
            assert on["ici_ms"] < off["ici_ms"], (cname, gen)
            assert on["hbm_ms"] < off["hbm_ms"], (cname, gen)
            # the fused rows are disqualified under compression
            for pname, terms in \
                    wires["e4m3"]["serial"]["paths"].items():
                if pname.startswith("fused"):
                    assert not terms["feasible"], (cname, gen, pname)


def test_golden_tables_cover_chunk_dimension():
    """CI gate for the chunked-pipeline dimension: every golden
    (config, gen, wire) point carries exactly the chunk variants the
    config supports (golden_chunk_variants — mixtral's nLx=1 at d=8
    cannot chunk), and on the multi-chip golden configs the chunked
    overlap-adjusted prediction must beat the serial one (the
    acceptance bar for the schedule's pricing)."""
    from flashmoe_tpu.config import BENCH_CONFIGS
    from flashmoe_tpu.planner.golden import (
        GOLDEN_CHUNKS, golden_chunk_variants,
    )

    frozen = load_golden()
    assert set(GOLDEN_CHUNKS) >= {"serial", "c4"}
    for cname, gens in frozen["configs"].items():
        want = set(golden_chunk_variants(BENCH_CONFIGS[cname]))
        for gen, wires in gens.items():
            for wname, chunks in wires.items():
                assert set(chunks) == want, (cname, gen, wname)
                if "c4" not in chunks:
                    continue
                ser = chunks["serial"]["paths"]
                c4 = chunks["c4"]["paths"]
                for pname in ("collective", "ragged"):
                    # chunking pays n x alpha on the wire but hides the
                    # exchange behind the FFN: total drops, ici rises
                    assert c4[pname]["total_ms"] < \
                        ser[pname]["total_ms"], (cname, gen, wname,
                                                 pname)
                    assert c4[pname]["ici_ms"] > \
                        ser[pname]["ici_ms"], (cname, gen, wname, pname)
                # fused rows are chunk-independent: identical pricing
                for pname, terms in ser.items():
                    if pname.startswith("fused"):
                        assert c4[pname] == terms, (cname, gen, wname,
                                                    pname)
    # mixtral (nLx=1 at d=8) must be the config that skips c4 — the
    # skip rule is exercised, not vacuous
    assert "c4" not in frozen["configs"]["mixtral"]["v5e"]["off"]
    assert "c4" in frozen["configs"]["reference"]["v5e"]["off"]


def test_golden_tables_cover_schedule_dimension():
    """CI gate for the fused-schedule axis (ISSUE 12): every golden
    point must carry a row for EVERY fused schedule — batched,
    resident, stream, AND rowwin — so a schedule added to the kernel
    cannot silently skip the CI-gated tables; and the mixtral verdict
    must be the recorded QUANTITATIVE race the rowwin schedule turned
    it into (a feasible fused[rowwin] row priced against the collective
    transports, whichever way selection lands), not the old categorical
    'no weights-once schedule feasible'."""
    frozen = load_golden()
    want = {"fused[batched]", "fused[resident]", "fused[stream]",
            "fused[rowwin]"}
    for cname, gens in frozen["configs"].items():
        for gen, wires in gens.items():
            for wname, chunks in wires.items():
                for chname, g in chunks.items():
                    assert want <= set(g["paths"]), (cname, gen, wname,
                                                     chname)
    mix = frozen["configs"]["mixtral"]["v5e"]["off"]["serial"]["paths"]
    assert mix["fused[rowwin]"]["feasible"]
    assert not mix["fused[batched]"]["feasible"]
    assert not mix["fused[resident]"]["feasible"]
    # the race is quantitative: the rowwin row carries a real latency,
    # and the recorded winner is whoever won it
    assert mix["fused[rowwin]"]["total_ms"] > 0
    winner = frozen["configs"]["mixtral"]["v5e"]["off"]["serial"]["winner"]
    assert winner in ("collective", "ragged", "fused[rowwin]",
                      "fused_combine")


def test_planted_vmem_infeasible_rowwin_row():
    """ISSUE 12 satellite: a config whose hidden size starves even the
    minimal (row tile, K-window) pair must surface as an
    infeasible-WITH-REASON fused[rowwin] planner row — never a crash,
    never a silently missing row."""
    cfg = MoEConfig(num_experts=8, expert_top_k=2,
                    hidden_size=2 ** 17, intermediate_size=2 ** 17,
                    sequence_len=128, capacity_factor=1.0,
                    dtype=jnp.float32, param_dtype=jnp.float32)
    preds = {p.path: p for p in predict_paths(cfg, 8, "v5e")}
    row = preds["fused[rowwin]"]
    assert not row.feasible
    assert "rowwin infeasible" in row.note
    assert "VMEM" in row.note
    # every weights-once schedule is out too; the collective transports
    # remain the feasible fallback
    assert not preds["fused[batched]"].feasible
    assert preds["collective"].feasible


def test_d8_canonical_breakdown_all_generations():
    """The acceptance-criteria surface: at d=8 on every supported
    generation the reference config gets a full breakdown (compute,
    HBM, ICI, DCN, overlap-adjusted total) and a named feasible
    winner."""
    for gen in GOLDEN_GENS:
        preds = predict_paths(REF, 8, gen)
        assert {"collective", "ragged", "fused[batched]",
                "fused[resident]", "fused[stream]", "fused[rowwin]",
                "fused_combine"} <= {p.path for p in preds}
        winner = next(p for p in preds if p.feasible)
        assert winner.total_ms > 0
        for p in preds:
            assert p.compute_ms > 0 and p.hbm_ms > 0
            assert p.serial_ms >= max(p.compute_ms, p.hbm_ms)
            if p.feasible:
                assert p.total_ms <= p.serial_ms + 1e-9
        table = explain_table(preds)
        for col in ("compute ms", "HBM ms", "ICI ms", "DCN ms",
                    "predicted ms"):
            assert col in table


def test_cli_prints_table_and_winner(capsys):
    from flashmoe_tpu.planner.__main__ import main

    assert main(["--config", "reference", "--d", "8"]) == 0
    out = capsys.readouterr().out
    for gen in GOLDEN_GENS:
        assert f"gen={gen}" in out
    assert "predicted winner:" in out
    assert "| ICI ms | DCN ms |" in out


# ----------------------------------------------------------------------
# Error paths
# ----------------------------------------------------------------------

def test_unknown_generation_is_a_clean_valueerror():
    with pytest.raises(ValueError, match="v5e"):
        predict_paths(REF, 8, "v7x")
    from flashmoe_tpu.parallel.overlap import overlap_bound

    with pytest.raises(ValueError, match="supported"):
        overlap_bound(REF, 8, "cpu")


def test_divisibility_errors():
    with pytest.raises(ValueError, match="divisible"):
        predict_paths(REF, 6, "v5e")            # E=64 % 6 != 0
    with pytest.raises(ValueError, match="slices"):
        predict_paths(REF, 8, "v5e", slices=3)  # 8 % 3 != 0
    with pytest.raises(ValueError, match="inner"):
        a2a_transport_cost(8, 3, 1e6)           # no silent //


def test_mock_slices_garbage_is_loud_but_never_blocks_trace(monkeypatch):
    """Hardened mock parsing (ISSUE 13 satellite): garbage raises a
    ValueError naming the world size at the detection layer, while the
    planner's auto resolution — which must never die inside a trace —
    degrades to the single-slice flat pricing."""
    from flashmoe_tpu.parallel.topology import slice_structure
    from flashmoe_tpu.planner.select import resolve_moe_plan

    monkeypatch.setenv("FLASHMOE_MOCK_SLICES", "banana")
    with pytest.raises(ValueError, match="8 devices"):
        slice_structure(devices=list(range(8)))
    backend, _ = resolve_moe_plan(REF.replace(moe_backend="auto", ep=8))
    assert backend in ("collective", "ragged", "fused")
    monkeypatch.setenv("FLASHMOE_MOCK_SLICES", "2")
    assert slice_structure(devices=list(range(8))) == (2, 4)


# ----------------------------------------------------------------------
# Selection policy
# ----------------------------------------------------------------------

def test_predicted_winner_when_no_measurements():
    sel = select_path(REF, 8, "v5e", record=False)
    assert sel.mode == "predicted"
    assert sel.winner == sel.predicted_winner
    assert sel.measured == {} and sel.measured_ms is None


def test_measured_override_precedence():
    """A measured entry beats the prediction — even when the model
    disagrees — but never resurrects an infeasible path."""
    pred = select_path(REF, 8, "v5e", record=False)
    loser = ("fused" if pred.predicted_winner != "fused[batched]"
             else "collective")
    sel = select_path(REF, 8, "v5e", measured={loser: 0.001},
                      record=False)
    assert sel.mode == "measured" and sel.winner == loser
    assert sel.measured_ms == 0.001
    # infeasible family: measurement ignored, prediction stands
    mix = BENCH_CONFIGS["mixtral"]
    sel2 = select_path(mix, 8, "v5e", slices=2,   # fused: intra-slice only
                       measured={"fused": 0.001}, record=False)
    assert sel2.winner != "fused"


def test_measured_override_from_tuning_table(tmp_path, monkeypatch):
    from flashmoe_tpu import tuning

    tbl = tmp_path / "table.json"
    tbl.write_text(json.dumps({"generation": "v5e", "entries": [{
        "kernel": "path_latency",
        "match": {"path": "ragged", "h": REF.hidden_size,
                  "i": REF.intermediate_size, "d": 8},
        "measured_ms": 0.0005}]}))
    monkeypatch.setenv("FLASHMOE_TUNING_FILE", str(tbl))
    tuning._load.cache_clear()
    got = tuning.measured_path_latencies(
        "v5e", h=REF.hidden_size, i=REF.intermediate_size, d=8)
    assert got == {"ragged": 0.0005}
    sel = select_path(REF, 8, "v5e", record=False)
    assert sel.mode == "measured" and sel.winner == "ragged"
    assert sel.backend == "ragged"


def test_selection_decision_lands_in_telemetry():
    n0 = len(metrics.decisions)
    sel = select_path(REF, 8, "v5e")
    assert len(metrics.decisions) == n0 + 1
    rec = metrics.last_decision("planner.path_select")
    assert rec["winner"] == sel.winner
    assert rec["mode"] == "predicted"
    assert {"compute_ms", "hbm_ms", "ici_ms", "dcn_ms",
            "total_ms"} <= set(rec["breakdown"][0])
    assert metrics.counters["decision.planner.path_select"] >= 1


def test_auto_backend_resolution(monkeypatch):
    cfg = REF.replace(moe_backend="auto", ep=8)
    backend = resolve_moe_backend(cfg)
    assert backend in ("collective", "ragged", "fused")
    # explicit configs pass through untouched (no planner involved)
    assert resolve_moe_backend(REF.replace(moe_backend="fused",
                                           ep=8)) == "fused"
    # tp > 1 short-circuits to the only composing transport
    assert resolve_moe_backend(
        REF.replace(moe_backend="auto", ep=4, tp=2)) == "collective"
    # shared experts can never land on the ragged layer
    ds = BENCH_CONFIGS["deepseek"].replace(moe_backend="auto")
    assert resolve_moe_backend(ds) in ("collective", "fused")


# ----------------------------------------------------------------------
# Consistency with the analysis byte model
# ----------------------------------------------------------------------

def test_planner_bytes_agree_with_analysis():
    """The planner never re-derives bytes: every row's PathCost must be
    exactly what analysis.path_costs prices for that path."""
    d = 8
    byte_path = {"collective": ("explicit", None),
                 "hierarchical": ("explicit", None),
                 "ragged": ("ragged", None),
                 "fused[batched]": ("fused", "batched"),
                 "fused[resident]": ("fused", "resident"),
                 "fused[stream]": ("fused", "stream"),
                 "fused[rowwin]": ("fused", "rowwin"),
                 "fused_combine": ("fused_combine", None)}
    for p in predict_paths(REF, d, "v5e", slices=2):
        ap, sched = byte_path[p.path]
        want = path_costs(REF, ap, d_world=d, schedule=sched)
        assert p.cost.total_bytes == want.total_bytes, p.path
        assert p.cost.flops == want.flops


def test_fused_combine_return_bytes_not_overstated():
    """At capacity_factor > 1 the sorted-return
    combine sends only the routed rows back, so its comm must be
    strictly below the slab path's."""
    cfg = REF.replace(capacity_factor=2.0)
    fc = path_costs(cfg, "fused_combine", d_world=8)
    fu = path_costs(cfg, "fused", d_world=8)
    assert fc.comm_bytes < fu.comm_bytes
    # and at cf=1 the two coincide (slots == rows)
    assert path_costs(REF, "fused_combine", d_world=8).comm_bytes == \
        path_costs(REF, "fused", d_world=8).comm_bytes


def test_single_chip_paths():
    preds = predict_paths(REF, 1, "v5e")
    assert {p.path for p in preds} == {"xla", "explicit", "gather"}
    assert all(p.ici_ms == 0 and p.dcn_ms == 0 for p in preds)
    # training excludes the inference-only gather kernel
    tr = predict_paths(REF.replace(is_training=True), 1, "v5e")
    assert not next(p for p in tr if p.path == "gather").feasible


def test_hierarchical_beats_flat_on_dcn_messages():
    """Multi-slice: the two-stage path's whole point is fewer DCN
    alpha payments; at small slabs it must predict faster than flat
    collective."""
    cfg = MoEConfig(num_experts=16, expert_top_k=2, hidden_size=256,
                    intermediate_size=512, sequence_len=2048,
                    capacity_factor=1.0, dtype=jnp.bfloat16)
    preds = {p.path: p for p in predict_paths(cfg, 16, "v5e", slices=4)}
    assert preds["hierarchical"].dcn_ms < preds["collective"].dcn_ms
    assert not preds["fused[batched]"].feasible  # intra-slice only


# ----------------------------------------------------------------------
# Multi-slice scale-out (ISSUE 13): per-hop wires, DP allreduce,
# EP-vs-DP-across-DCN trade, golden slices dimension
# ----------------------------------------------------------------------

def test_hierarchical_dcn_wire_shrinks_dcn_term_only():
    """wire_dtype_dcn prices the DCN hop at the fp8 row size: the
    hierarchical row's dcn_ms shrinks, its ici_ms is untouched, and
    the flat row never sees the knob (no re-encode hop)."""
    base = {p.path: p for p in predict_paths(REF, 8, "v5e", slices=4)}
    dcn = {p.path: p for p in predict_paths(
        REF.replace(wire_dtype_dcn="e4m3"), 8, "v5e", slices=4)}
    assert dcn["hierarchical"].dcn_ms < base["hierarchical"].dcn_ms
    assert dcn["hierarchical"].ici_ms == base["hierarchical"].ici_ms
    assert dcn["collective"].dcn_ms == base["collective"].dcn_ms
    assert "dcn:e4m3" in dcn["hierarchical"].wire
    # the fused rows are disqualified under any wire, dcn included
    for pname, p in dcn.items():
        if pname.startswith("fused"):
            assert not p.feasible, pname


def test_dcn_wire_discount_not_priced_at_one_rank_per_slice():
    """slices == d degenerates the two-stage exchange to flat (the
    layer gates on 1 < dcn_inner < d), so the planner must not price
    the DCN-wire discount there."""
    base = {p.path: p for p in predict_paths(REF, 8, "v5e", slices=8)}
    dcn = {p.path: p for p in predict_paths(
        REF.replace(wire_dtype_dcn="e4m3"), 8, "v5e", slices=8)}
    assert dcn["hierarchical"].dcn_ms == base["hierarchical"].dcn_ms
    assert "inert" in dcn["hierarchical"].note


def test_dp_allreduce_priced_from_decider_ring_model():
    """The DP axis's gradient ring (decider.ring_allreduce_ms): 0 for
    inference/dp=1, DCN pricing > ICI pricing, and the term rides every
    row of a prediction set identically (never flips a path winner)."""
    from flashmoe_tpu.planner.model import dp_allreduce_ms

    tr = REF.replace(is_training=True)
    assert dp_allreduce_ms(REF, 4, "v5e") == 0.0          # inference
    assert dp_allreduce_ms(tr, 1, "v5e") == 0.0           # no dp axis
    ici = dp_allreduce_ms(tr, 4, "v5e", over_dcn=False)
    dcn = dp_allreduce_ms(tr, 4, "v5e", over_dcn=True)
    assert 0.0 < ici < dcn
    preds = predict_paths(tr, 8, "v5e", dp=4, dp_over_dcn=True)
    assert all(p.dp_allreduce_ms == pytest.approx(dcn) for p in preds)
    bare = {p.path: p.total_ms for p in predict_paths(tr, 8, "v5e")}
    for p in preds:
        assert p.total_ms == pytest.approx(bare[p.path] + dcn, rel=1e-6)


def test_scaleout_plan_trades_ep_against_dp_across_dcn():
    """The EP-vs-DP-across-DCN trade: a training job with a heavy
    gradient keeps the DP ring off DCN (ep_across_dcn); the same job in
    inference mode — no allreduce at all — packs the a2a inside a slice
    (dp_across_dcn).  Both mappings priced, loser recorded."""
    from flashmoe_tpu.planner.select import scaleout_plan

    cfg = REF.replace(ep=8)
    train = scaleout_plan(cfg.replace(is_training=True), 32, 4, "v5e",
                          record=False)
    assert train.mapping == "ep_across_dcn"
    assert (train.ep, train.dp) == (8, 4)
    assert train.a2a_slices == 4 and not train.dp_over_dcn
    assert train.alternative_ms is not None
    assert train.predicted_ms < train.alternative_ms
    infer = scaleout_plan(cfg, 32, 4, "v5e", record=False)
    assert infer.mapping == "dp_across_dcn"
    assert infer.a2a_slices == 1 and infer.dp_over_dcn
    with pytest.raises(ValueError, match="slices"):
        scaleout_plan(cfg, 32, 5, "v5e", record=False)


def test_scaleout_decision_lands_in_telemetry():
    from flashmoe_tpu.planner.select import scaleout_plan

    scaleout_plan(REF.replace(ep=8), 32, 4, "v5e")
    rec = metrics.last_decision("planner.scaleout")
    assert rec is not None and rec["mapping"] in ("ep_across_dcn",
                                                  "dp_across_dcn")
    assert rec["n_slices"] == 4 and rec["predicted_ms"] > 0


def test_golden_slices_dimension_gates_dcn_wire():
    """The golden `slices` dimension (ISSUE 13 acceptance): every
    (config, gen) point freezes the planner's picks at 1/2/4/8 slices,
    matches the live model, and at the 4-slice point the
    hierarchical+e4m3-DCN-hop row beats flat-uncompressed on modeled
    DCN ms."""
    from flashmoe_tpu.planner.golden import GOLDEN_SLICES, golden_snapshot

    live, frozen = golden_snapshot(), load_golden()
    assert set(live["slices"]) == set(frozen["slices"])
    for cname, gens in frozen["slices"].items():
        for gen, points in gens.items():
            assert set(points) == {str(s) for s in GOLDEN_SLICES}
            for s, g in points.items():
                l = live["slices"][cname][gen][s]
                for plan_key in ("plan", "plan_dcn"):
                    assert l[plan_key]["winner"] == g[plan_key]["winner"], (
                        f"slices winner flipped for {cname}@{gen}"
                        f"[slices={s},{plan_key}]: "
                        f"{g[plan_key]['winner']} -> "
                        f"{l[plan_key]['winner']}; regenerate with "
                        f"python -m flashmoe_tpu.planner --regen-golden")
                    assert l[plan_key]["chunks"] == g[plan_key]["chunks"]
                    assert l[plan_key]["total_ms"] == pytest.approx(
                        g[plan_key]["total_ms"], rel=GOLDEN_RTOL)
                for term in ("flat_dcn_ms", "hier_dcn_ms"):
                    if g[term] is None:
                        assert l[term] is None and s == "1"
                    else:
                        assert l[term] == pytest.approx(
                            g[term], rel=GOLDEN_RTOL)
                assert l["hier_dcn_wins"] == g["hier_dcn_wins"]
            # THE acceptance criterion: 4-slice mesh, fp8 DCN hop +
            # per-slice-pair aggregation beats flat-uncompressed
            p4 = points["4"]
            assert p4["hier_dcn_wins"] is True, (cname, gen)
            assert p4["hier_dcn_ms"] < p4["flat_dcn_ms"], (cname, gen)


def test_select_path_keys_measurements_on_dcn_wire(tmp_path,
                                                   monkeypatch):
    """A latency measured with the DCN-hop wire on never overrides a
    selection without it (and vice versa) — the wire_dcn key rides the
    measurement identity like wire/wire_combine/chunks."""
    from flashmoe_tpu import tuning

    tbl = tmp_path / "table.json"
    tbl.write_text(json.dumps({"generation": "v5e", "entries": [{
        "kernel": "path_latency",
        "match": {"path": "collective", "h": REF.hidden_size,
                  "i": REF.intermediate_size, "d": 8,
                  "wire_dcn": "e4m3"},
        "measured_ms": 0.001}]}))
    monkeypatch.setenv("FLASHMOE_TUNING_FILE", str(tbl))
    tuning._load.cache_clear()
    sel_off = select_path(REF, 8, "v5e", record=False)
    assert sel_off.mode == "predicted"       # dcn-wire entry ignored
    sel_on = select_path(REF.replace(wire_dtype_dcn="e4m3"), 8, "v5e",
                         record=False)
    assert sel_on.mode == "measured"
    assert sel_on.measured_ms == pytest.approx(0.001)


# ----------------------------------------------------------------------
# Speculative decoding economics (ISSUE 20)
# ----------------------------------------------------------------------

def test_golden_tables_cover_speculate_dimension():
    """CI gate for the speculation axis: every golden (config, gen)
    point carries the k=GOLDEN_SPEC_K verify pricing, the uplift at
    the golden acceptance beats 1x, and the break-even acceptance sits
    below the golden acceptance — speculation must PAY at the golden
    point, or the regenerated table fails review here."""
    from flashmoe_tpu.planner.golden import (
        GOLDEN_CONFIGS, GOLDEN_SPEC_ACCEPT, GOLDEN_SPEC_K,
    )

    frozen = load_golden()
    assert set(frozen["speculate"]) == set(GOLDEN_CONFIGS)
    for cname, gens in frozen["speculate"].items():
        assert set(gens) == set(GOLDEN_GENS), cname
        for gen, pt in gens.items():
            assert pt["verify_tokens"] == GOLDEN_SPEC_K
            assert pt["accept_rate"] == GOLDEN_SPEC_ACCEPT
            # the verify span must price as a span, not k+1 steps
            assert 1.0 <= pt["cost_ratio"] < GOLDEN_SPEC_K + 1
            assert pt["uplift"] > 1.0, (cname, gen)
            assert pt["break_even_accept"] < GOLDEN_SPEC_ACCEPT, \
                (cname, gen)
            assert pt["pays"] is True, (cname, gen)


def test_speculate_model_math():
    """E[n] closed form, bisection break-even, and the verify_tokens
    pricing axis on decode shapes."""
    from flashmoe_tpu.planner.model import (
        decode_shape, predict_paths, speculate_break_even,
        speculate_tokens_per_step, speculate_uplift,
    )

    cfg = BENCH_CONFIGS["reference"].replace(ep=8)
    # E[n](p) = (1 - p^(k+1)) / (1 - p); exact at the endpoints
    assert speculate_tokens_per_step(0.0, 3) == pytest.approx(1.0)
    assert speculate_tokens_per_step(1.0, 3) == pytest.approx(4.0)
    assert speculate_tokens_per_step(0.5, 3) == pytest.approx(1.875)
    # verify_tokens multiplies decode tokens AFTER d-rounding
    s1 = decode_shape(cfg, 8, decode_tokens=64)
    s4 = decode_shape(cfg, 8, decode_tokens=64, verify_tokens=3)
    assert s4.tokens == 4 * s1.tokens
    up = speculate_uplift(cfg, 8, "v5e", decode_tokens=64,
                          verify_tokens=3, accept_rate=0.7)
    assert up["cost_ratio"] == pytest.approx(
        up["tk_ms"] / up["t1_ms"])
    assert up["uplift"] == pytest.approx(
        up["tokens_per_step"] / up["cost_ratio"])
    be = speculate_break_even(cfg, 8, "v5e", decode_tokens=64,
                              verify_tokens=3)
    # the break-even acceptance exactly repays the verify span
    eq = speculate_uplift(cfg, 8, "v5e", decode_tokens=64,
                          verify_tokens=3, accept_rate=be)
    assert eq["uplift"] == pytest.approx(1.0, abs=1e-6)
    with pytest.raises(ValueError, match="verify_tokens"):
        decode_shape(cfg, 8, verify_tokens=-1)
    with pytest.raises(ValueError, match="decode"):
        predict_paths(cfg, 8, "v5e", verify_tokens=3)  # not decode mode


def test_select_path_spec_measurement_identity(tmp_path, monkeypatch):
    """The spec tag rides the measured-latency shape key: a spec=off
    tuning entry must never price a verify-span selection, and
    vice versa."""
    import json as _json

    from flashmoe_tpu import tuning
    from flashmoe_tpu.planner.select import (
        _shape_key, select_path, spec_tag,
    )

    assert spec_tag(None) == "off" and spec_tag(3) == "v3"
    cfg = BENCH_CONFIGS["reference"].replace(ep=8)
    key_off = _shape_key(cfg, 8)
    key_on = _shape_key(cfg, 8, spec="v3")
    assert key_off["spec"] == "off" and key_on["spec"] == "v3"
    assert {k: v for k, v in key_on.items() if k != "spec"} \
        == {k: v for k, v in key_off.items() if k != "spec"}
    # a measured entry tagged spec=off only matches the off selection
    # (the decode selection keys on the DECODE-shaped config: s = the
    # per-step token count, not the training sequence)
    from flashmoe_tpu.planner.model import decode_shape

    dkey = _shape_key(decode_shape(cfg, 8, 64), 8)
    path = str(tmp_path / "v5e.json")
    with open(path, "w") as f:
        _json.dump({"generation": "v5e", "entries": [
            {"kernel": "path_latency",
             "match": dict(dkey, path="collective"),
             "measured_ms": 0.001}]}, f)
    monkeypatch.setenv("FLASHMOE_TUNING_FILE", path)
    tuning._load.cache_clear()
    sel_off = select_path(cfg, 8, "v5e", mode="decode",
                          decode_tokens=64, record=False)
    sel_on = select_path(cfg, 8, "v5e", mode="decode",
                         decode_tokens=64, verify_tokens=3,
                         record=False)
    assert sel_off.mode == "measured"
    assert sel_on.mode == "predicted"
