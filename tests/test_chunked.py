"""Chunked double-buffered EP dispatch (``MoEConfig.a2a_chunks``):
config validation, bit-identity of the chunked pipeline against the
serial schedule (flat / hierarchical / ragged, with and without the
fp8 wire), planner pricing + chunk sweep, measurement keying, the
overlap bound, and the overlap drift monitor."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashmoe_tpu.config import BENCH_CONFIGS, MoEConfig
from flashmoe_tpu.models.reference import init_moe_params
from flashmoe_tpu.parallel.ep import ep_moe_layer
from flashmoe_tpu.parallel.mesh import make_mesh
from flashmoe_tpu.parallel.ragged_ep import ragged_ep_moe_layer

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)
REF = BENCH_CONFIGS["reference"]


@pytest.fixture(autouse=True)
def _hermetic(monkeypatch):
    from flashmoe_tpu import tuning
    from flashmoe_tpu.planner.select import _cached_backend

    for var in ("FLASHMOE_TUNING_FILE", "FLASHMOE_TPU_GEN",
                "FLASHMOE_MOCK_SLICES"):
        monkeypatch.delenv(var, raising=False)
    tuning._load.cache_clear()
    _cached_backend.cache_clear()
    yield
    tuning._load.cache_clear()
    _cached_backend.cache_clear()


# ----------------------------------------------------------------------
# Config validation: clear ValueError at config time, not a shape error
# inside the pipeline loop
# ----------------------------------------------------------------------

def test_config_validates_chunk_counts():
    with pytest.raises(ValueError, match="positive int"):
        MoEConfig(a2a_chunks=0, **F32)
    with pytest.raises(ValueError, match="positive int"):
        MoEConfig(a2a_chunks=-2, **F32)
    # E=8, ep=2 -> nLx=4: 3 does not divide
    with pytest.raises(ValueError, match="divide the local-expert"):
        MoEConfig(num_experts=8, ep=2, a2a_chunks=3, **F32)
    # mixtral shape: nLx=1 at ep=8 has no chunk axis
    with pytest.raises(ValueError, match="divide the local-expert"):
        BENCH_CONFIGS["mixtral"].replace(a2a_chunks=2)
    # valid counts construct and stay hashable (jit static args)
    hash(MoEConfig(num_experts=8, ep=2, a2a_chunks=4, **F32))
    hash(MoEConfig(num_experts=8, ep=2, a2a_chunks=1, **F32))


# ----------------------------------------------------------------------
# Bit-identity: chunked on vs off (the a2a_chunks=None guarantee)
# ----------------------------------------------------------------------

def _setup(ep=2, **over):
    base = dict(num_experts=8, expert_top_k=2, hidden_size=64,
                intermediate_size=128, sequence_len=32 * ep,
                drop_tokens=False, ep=ep, **F32)
    base.update(over)
    cfg = MoEConfig(**base)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (cfg.tokens, cfg.hidden_size), jnp.float32)
    return cfg, params, x


def test_chunked_serial_invariants_via_staticcheck(devices):
    """Serial-schedule identity for the chunk knob across EVERY
    registered EP backend (flat / hierarchical / ragged) — delegated to
    the staticcheck invariant engine, which replaced the hand-rolled
    per-layer assertions this file used to carry: ``a2a_chunks=None``
    is the dataclass default (equal frozen config => one jit cache
    entry => same bits by construction) and ``a2a_chunks=1`` traces to
    the byte-identical jaxpr, while the on-trace's all_to_all count
    scales exactly with the chunk count.  The chunked-ON numeric
    equality against the serial schedule stays execution-tested below
    (slow): a re-ordered schedule being bit-exact is a claim about
    arithmetic, not structure."""
    from flashmoe_tpu.staticcheck.invariants import run_invariants

    assert run_invariants(knobs=["a2a_chunks"], devices=devices,
                          include_coverage=False) == []


def test_ep_chunked_bit_identical_hierarchical_and_wire(devices, jitted):
    """Chunked + two-stage (intra/inter-slice) exchange + fp8 wire:
    every chunk carries payload AND scales through both hops — outputs
    bit-identical to the serial schedule at the same knobs."""
    cfg, params, x = _setup(ep=4)
    mesh = make_mesh(cfg, dp=1, devices=devices[:4])
    hoff = jitted(ep_moe_layer, cfg, mesh, dcn_inner=2)(params, x)
    hon = jitted(ep_moe_layer, cfg.replace(a2a_chunks=2), mesh,
                 dcn_inner=2)(params, x)
    np.testing.assert_array_equal(np.asarray(hoff.out),
                                  np.asarray(hon.out))
    wired = cfg.replace(wire_dtype="e4m3", wire_dtype_combine="e5m2")
    woff = jitted(ep_moe_layer, wired, mesh)(params, x)
    won = jitted(ep_moe_layer, wired.replace(a2a_chunks=2), mesh)(params, x)
    np.testing.assert_array_equal(np.asarray(woff.out),
                                  np.asarray(won.out))


def test_ragged_chunked_bit_identical(devices, jitted):
    """The ragged row exchanges mirror the pipeline: per-chunk
    offsets/sizes derived from the gathered count matrix move exactly
    the serial schedule's rows — with and without the fp8 wire."""
    cfg, params, x = _setup()
    mesh = make_mesh(cfg, dp=1, devices=devices[:2])
    off = jitted(ragged_ep_moe_layer, cfg, mesh, exchange="dense")(params, x)
    for n in (2, 4):
        on = jitted(ragged_ep_moe_layer, cfg.replace(a2a_chunks=n), mesh,
                    exchange="dense")(params, x)
        np.testing.assert_array_equal(np.asarray(off.out),
                                      np.asarray(on.out))
    wired = cfg.replace(wire_dtype="e4m3")
    woff = jitted(ragged_ep_moe_layer, wired, mesh,
                  exchange="dense")(params, x)
    won = jitted(ragged_ep_moe_layer, wired.replace(a2a_chunks=2), mesh,
                 exchange="dense")(params, x)
    np.testing.assert_array_equal(np.asarray(woff.out),
                                  np.asarray(won.out))


def test_ep_chunked_grad_finite(devices):
    """Training through the chunked pipeline: grads flow through the
    per-chunk param slices and stay finite."""
    cfg, params, x = _setup(is_training=True, a2a_chunks=2)
    mesh = make_mesh(cfg, dp=1, devices=devices[:2])

    def loss(p):
        o = ep_moe_layer(p, x, cfg, mesh)
        return jnp.sum(o.out.astype(jnp.float32) ** 2) + o.aux_loss

    g = jax.jit(jax.grad(loss))(params)
    for leaf in jax.tree_util.tree_leaves(g):
        assert np.isfinite(np.asarray(leaf)).all()


def test_per_chunk_trace_spans(monkeypatch, devices):
    """Per-chunk phases (moe.a2a_dispatch.k / moe.expert.k /
    moe.a2a_combine.k) wrap the pipeline so xprof and the observe phase
    breakdown see pipeline occupancy.  Trace-only: spans fire at trace
    time, no compile."""
    import contextlib

    from flashmoe_tpu.parallel import ep as ep_mod
    from flashmoe_tpu.parallel import ragged_ep as ragged_mod
    from flashmoe_tpu.utils import telemetry as tel

    seen = []

    @contextlib.contextmanager
    def spy(name):
        seen.append(name)
        yield

    monkeypatch.setattr(ep_mod, "trace_span", spy)
    monkeypatch.setattr(ragged_mod, "trace_span", spy)
    monkeypatch.setattr(tel, "trace_span", spy)
    cfg, params, x = _setup(a2a_chunks=2)
    mesh = make_mesh(cfg, dp=1, devices=devices[:2])
    jax.make_jaxpr(lambda p, xx: ep_moe_layer(p, xx, cfg, mesh))(params, x)
    for k in range(2):
        for phase in ("a2a_dispatch", "expert", "a2a_combine"):
            assert f"moe.{phase}.{k}" in seen, (phase, k, seen)
    seen.clear()
    jax.make_jaxpr(lambda p, xx: ragged_ep_moe_layer(
        p, xx, cfg, mesh, exchange="dense"))(params, x)
    for k in range(2):
        for phase in ("a2a_dispatch", "expert", "a2a_combine"):
            assert f"moe.{phase}.{k}" in seen, (phase, k, seen)


def test_runtime_divisibility_error(devices):
    """A chunk count the ACTUAL mesh cannot divide fails with the clear
    ValueError at trace time, not a shape error inside the loop: a
    cfg.ep=1 config passes the config-time check with any divisor of E,
    but the shard body re-checks against the mesh's real ep width."""
    cfg, params, x = _setup(ep=2, num_experts=8)
    mesh = make_mesh(cfg, dp=1, devices=devices[:2])
    # config-time ok (ep=1 -> nLx=8, 8 divides); mesh nLx=4 does not
    cfg8 = cfg.replace(ep=1, a2a_chunks=8)
    with pytest.raises(ValueError, match="divide the local-expert"):
        jax.make_jaxpr(
            lambda p, xx: ep_moe_layer(p, xx, cfg8, mesh))(params, x)
    with pytest.raises(ValueError, match="divide the local-expert"):
        jax.make_jaxpr(lambda p, xx: ragged_ep_moe_layer(
            p, xx, cfg8, mesh, exchange="dense"))(params, x)


# ----------------------------------------------------------------------
# Planner pricing: chunked-leg costs + overlap-adjusted makespan
# ----------------------------------------------------------------------

def test_chunked_transport_alpha_overhead():
    from flashmoe_tpu.analysis import a2a_transport_cost

    base = a2a_transport_cost(8, 2, 1e6, gen="v5e", links=4)
    ch = a2a_transport_cost(8, 2, 1e6, gen="v5e", links=4, chunks=4)
    # beta unchanged, alpha x4: strictly more expensive per leg ...
    assert ch["flat"]["dcn_ms"] > base["flat"]["dcn_ms"]
    assert ch["flat"]["ici_ms"] > base["flat"]["ici_ms"]
    assert ch["flat"]["dcn_messages"] == 4 * base["flat"]["dcn_messages"]
    with pytest.raises(ValueError, match="chunks"):
        a2a_transport_cost(8, 2, 1e6, chunks=0)


def test_chunked_pipeline_formula():
    from flashmoe_tpu.analysis import chunked_pipeline_ms

    # n=1 is exactly the serial sum
    assert chunked_pipeline_ms(3.0, 1.0, 1.0, 1) == 5.0
    # compute-bound: chip + E/n
    assert chunked_pipeline_ms(4.0, 1.0, 1.0, 2) == pytest.approx(5.0)
    # wire-bound: E + chip/n
    assert chunked_pipeline_ms(1.0, 4.0, 4.0, 2) == pytest.approx(8.5)
    # always <= serial at equal leg costs
    for n in (2, 4, 8):
        assert chunked_pipeline_ms(3.0, 1.0, 1.0, n) < 5.0
    with pytest.raises(ValueError, match="chunks"):
        chunked_pipeline_ms(1.0, 1.0, 1.0, 0)


def test_planner_chunked_beats_serial_on_golden_configs():
    """Acceptance bar: with a2a_chunks >= 2 the overlap-adjusted
    prediction beats the serial prediction on the golden v5e/v5p
    multi-chip configs, for both XLA transports."""
    from flashmoe_tpu.planner.model import predict_paths

    for cname in ("reference", "deepseek"):
        cfg = BENCH_CONFIGS[cname]
        for gen in ("v5e", "v5p"):
            off = {p.path: p for p in predict_paths(cfg, 8, gen)}
            on = {p.path: p for p in predict_paths(
                cfg.replace(a2a_chunks=4), 8, gen)}
            for path in ("collective", "ragged"):
                assert on[path].total_ms < off[path].total_ms, (
                    cname, gen, path)
                # the pipeline pays its alpha overhead visibly ...
                assert on[path].ici_ms > off[path].ici_ms
                # ... and stays below its own no-overlap makespan
                assert on[path].total_ms < on[path].serial_ms
                assert on[path].a2a_chunks == 4
                assert "chunked a2a x4" in on[path].note
            # fused rows ignore the knob: identical pricing, chunks=1
            for path, p in on.items():
                if path.startswith("fused"):
                    assert p.a2a_chunks == 1
                    assert p.total_ms == off[path].total_ms


def test_planner_rejects_indivisible_chunks():
    from flashmoe_tpu.planner.model import predict_paths

    with pytest.raises(ValueError, match="divide the local-expert"):
        # 16 divides E=64 (so the ep=1 config constructs) but not the
        # d=8 local-expert axis E//d = 8
        predict_paths(REF.replace(ep=1, a2a_chunks=16), 8, "v5e")


def test_chunked_composes_with_wire_pricing():
    from flashmoe_tpu.planner.model import predict_paths

    on = {p.path: p for p in predict_paths(
        REF.replace(a2a_chunks=4, wire_dtype="e4m3"), 8, "v5e")}
    both_off = {p.path: p for p in predict_paths(REF, 8, "v5e")}
    assert on["collective"].total_ms < both_off["collective"].total_ms
    assert on["collective"].wire == "e4m3/off"
    assert on["collective"].a2a_chunks == 4
    for pname, p in on.items():
        if pname.startswith("fused"):
            assert not p.feasible  # wire still disqualifies fused


# ----------------------------------------------------------------------
# Selection: the auto chunk sweep + measured override keying
# ----------------------------------------------------------------------

def test_select_sweeps_chunks_and_resolves_plan():
    from flashmoe_tpu.planner.select import (
        resolve_moe_plan, select_path,
    )

    sel = select_path(REF, 8, "v5e", record=False, sweep_chunks=True)
    ns = [n for n, _ in sel.chunk_sweep]
    assert 1 in ns and len(ns) > 1
    assert sel.a2a_chunks > 1  # chunking wins at v5e on this shape
    # the sweep's serial entry matches the unswept selection
    serial = select_path(REF, 8, "v5e", record=False)
    assert dict(sel.chunk_sweep)[1] == pytest.approx(
        serial.predicted_ms, abs=1e-6)
    assert serial.a2a_chunks == 1 and serial.chunk_sweep == ((
        1, round(serial.predicted_ms, 6)),)
    # an explicit cfg.a2a_chunks pins the sweep
    pinned = select_path(REF.replace(a2a_chunks=2), 8, "v5e",
                         record=False, sweep_chunks=True)
    assert [n for n, _ in pinned.chunk_sweep] == [2]
    assert pinned.a2a_chunks == 2
    # auto resolution returns (backend, chunks)
    backend, chunks = resolve_moe_plan(
        REF.replace(moe_backend="auto", ep=8))
    assert backend in ("collective", "ragged", "fused")
    if backend == "fused":
        assert chunks is None
    else:
        assert chunks is None or chunks > 1
    # explicit configs pass through untouched
    assert resolve_moe_plan(
        REF.replace(moe_backend="collective", ep=8, a2a_chunks=2)
    ) == ("collective", 2)


def test_auto_layer_threads_chunk_pick(monkeypatch, devices):
    """auto_ep_moe_layer threads the planner's chunk pick into the
    layer config (trace-only: the chunked graph has 2n all_to_alls)."""
    from flashmoe_tpu.parallel import ep as ep_mod

    cfg, params, x = _setup(ep=2, num_experts=8,
                            moe_backend="auto")
    mesh = make_mesh(cfg, dp=1, devices=devices[:2])
    monkeypatch.setattr(ep_mod, "resolve_moe_plan",
                        lambda c, m=None: ("collective", 2))
    jx = jax.make_jaxpr(lambda p, xx: ep_mod.auto_ep_moe_layer(
        p, xx, cfg, mesh))(params, x)
    n_a2a = str(jx).count("all_to_all")
    assert n_a2a == 4  # 2 legs x 2 chunks


def test_measured_override_keyed_by_chunks(tmp_path, monkeypatch):
    """A path latency measured at chunks=4 never overrides a serial
    selection (and vice versa)."""
    from flashmoe_tpu import tuning
    from flashmoe_tpu.planner.select import _cached_backend, select_path

    shape = dict(h=REF.hidden_size, i=REF.intermediate_size, d=8)
    tbl = tmp_path / "table.json"
    tbl.write_text(json.dumps({"generation": "v5e", "entries": [
        {"kernel": "path_latency",
         "match": dict(shape, path="ragged", chunks=4),
         "measured_ms": 0.0001},
        {"kernel": "path_latency",          # legacy: implicit serial
         "match": dict(shape, path="collective"),
         "measured_ms": 0.0002},
    ]}))
    monkeypatch.setenv("FLASHMOE_TUNING_FILE", str(tbl))
    tuning._load.cache_clear()
    _cached_backend.cache_clear()
    # serial query: only the legacy entry applies
    assert tuning.measured_path_latencies(
        "v5e", **shape) == {"collective": 0.0002}
    # chunked query: only the chunks=4 entry applies
    assert tuning.measured_path_latencies(
        "v5e", **shape, chunks=4) == {"ragged": 0.0001}
    # through the sweep: the chunks=4 measurement wins overall and
    # carries its chunk identity into the selection
    sel = select_path(REF, 8, "v5e", record=False, sweep_chunks=True)
    assert (sel.mode, sel.winner) == ("measured", "ragged")
    assert sel.a2a_chunks == 4 and sel.measured_ms == 0.0001


# ----------------------------------------------------------------------
# Overlap bound + drift monitor
# ----------------------------------------------------------------------

def test_chunked_overlap_bound_pieces():
    from flashmoe_tpu.parallel.overlap import chunked_overlap_bound

    serial = chunked_overlap_bound(REF, 8, "v5e", 1)
    assert serial["overlap_efficiency_bound"] == pytest.approx(1.0)
    b4 = chunked_overlap_bound(REF, 8, "v5e", 4)
    assert b4["overlap_efficiency_bound"] > 1.0
    # the bound mirrors the operational metric: (C + E) / T
    assert b4["overlap_efficiency_bound"] == pytest.approx(
        b4["serial_ms"] / b4["t_overlapped_ms"])
    # upper bound shape: never above (a+b)/max(a,b)
    a = b4["compute_ms"]
    e = b4["leg_dispatch_ms"] + b4["leg_combine_ms"]
    assert b4["overlap_efficiency_bound"] <= (a + e) / max(a, e) + 1e-9
    # ragged slabs are smaller at cf>1 configs; both paths priced
    rag = chunked_overlap_bound(BENCH_CONFIGS["deepseek"], 8, "v5e", 4,
                                path="ragged")
    assert rag["path"] == "ragged" and rag["t_overlapped_ms"] > 0
    with pytest.raises(ValueError):
        chunked_overlap_bound(REF, 8, "v7x", 2)
    with pytest.raises(ValueError, match="chunks"):
        chunked_overlap_bound(REF, 8, "v5e", 0)
    with pytest.raises(ValueError, match="fused"):
        chunked_overlap_bound(REF, 8, "v5e", 2, path="fused")


def test_overlap_drift_record_and_warning():
    from flashmoe_tpu.planner.drift import record_overlap_drift
    from flashmoe_tpu.utils.telemetry import metrics

    rec = record_overlap_drift(
        "collective", 1.30, predicted_fraction=1.40, gen="v5e", d=8,
        chunks=4)
    assert not rec.exceeded
    assert rec.rel_error == pytest.approx(1.30 / 1.40 - 1.0)
    d = metrics.last_decision("planner.overlap_drift")
    assert d["chunks"] == 4 and d["path"] == "collective"
    with pytest.warns(RuntimeWarning, match="overlap-fraction drift"):
        bad = record_overlap_drift(
            "collective", 0.5, predicted_fraction=1.8, gen="v5e", d=8,
            chunks=4)
    assert bad.exceeded
    with pytest.raises(ValueError, match="predicted_fraction"):
        record_overlap_drift("collective", 1.0,
                             predicted_fraction=0.0, gen="v5e", d=8)


def test_measure_overlap_ragged_arm_and_chunk_passthrough(devices):
    """The ragged overlap arm runs end to end on the virtual mesh and
    the a2a_chunks passthrough reaches the overlapped leg; the fused
    arm refuses the knob."""
    from flashmoe_tpu.parallel.overlap import measure_overlap

    cfg = MoEConfig(num_experts=4, expert_top_k=2, hidden_size=64,
                    intermediate_size=128, sequence_len=64,
                    capacity_factor=1.0, drop_tokens=True, ep=2, **F32)
    mesh = make_mesh(cfg, dp=1, devices=devices[:2])
    m = measure_overlap(cfg, mesh, path="ragged", trials=1, chain=2,
                        a2a_chunks=2)
    assert m["path"] == "ragged" and m["a2a_chunks"] == 2
    assert m["t_overlapped_ms"] > 0 and m["overlap_efficiency"] > 0
    with pytest.raises(ValueError, match="fused"):
        measure_overlap(cfg, mesh, path="fused", a2a_chunks=2)
    with pytest.raises(ValueError, match="unknown path"):
        measure_overlap(cfg, mesh, path="sideways")
