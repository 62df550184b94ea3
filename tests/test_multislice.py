"""Two-stage ICI+DCN transport (VERDICT r4 missing #2 / next #5).

The reference resolves P2P vs remote per peer at init
(``bootstrap.cuh:442-446``) and branches transport at every send
(``os/packet.cuh:221-258``).  The TPU equivalent: when the ep axis spans
slices, the collective path's all-to-all decomposes into an intra-slice
ICI exchange + ONE aggregated DCN message per slice pair
(``parallel/ep.py:_hierarchical_a2a``), selected automatically from the
detected slice blocking (``topology.slice_structure``) the way the
arrival-order schedule is published.  The virtual 8-device CPU mesh
mocks a 2x4 "two-slice" job via ``FLASHMOE_MOCK_SLICES``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashmoe_tpu.config import MoEConfig
from flashmoe_tpu.models.reference import init_moe_params, reference_moe
from flashmoe_tpu.parallel.ep import ep_moe_layer
from flashmoe_tpu.parallel.mesh import make_mesh
from flashmoe_tpu.parallel.topology import slice_structure

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)


def _setup(cfg, seed=0):
    pk, xk = jax.random.split(jax.random.PRNGKey(seed))
    params = init_moe_params(pk, cfg)
    x = jax.random.normal(xk, (cfg.tokens, cfg.hidden_size), jnp.float32)
    return params, x


def test_hierarchical_a2a_matches_flat_and_oracle(devices, jitted):
    """The two-stage exchange is a pure re-decomposition: bit-identical
    routing to the flat all-to-all, oracle-correct output, both
    directions (dispatch and combine-return)."""
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                    intermediate_size=256, sequence_len=256,
                    drop_tokens=False, ep=8, **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg, dp=1, devices=devices[:8])
    flat = jitted(ep_moe_layer, cfg, mesh, dcn_inner=0)(params, x)
    hier = jitted(ep_moe_layer, cfg, mesh, dcn_inner=4)(params, x)
    np.testing.assert_allclose(np.asarray(hier.out), np.asarray(flat.out),
                               rtol=1e-6, atol=1e-6)
    want, _ = reference_moe(params, x, cfg)
    np.testing.assert_allclose(np.asarray(hier.out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("inner", [2, 4])
def test_hierarchical_a2a_other_factorizations(inner, devices, jitted):
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                    intermediate_size=256, sequence_len=256,
                    capacity_factor=1.0, drop_tokens=True, ep=8, **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg, dp=1, devices=devices[:8])
    flat = jitted(ep_moe_layer, cfg, mesh, dcn_inner=0)(params, x)
    hier = jitted(ep_moe_layer, cfg, mesh, dcn_inner=inner)(params, x)
    np.testing.assert_allclose(np.asarray(hier.out), np.asarray(flat.out),
                               rtol=1e-6, atol=1e-6)


def test_slice_structure_detection(monkeypatch, devices):
    """Mocked two-slice blocking is detected; single-slice returns
    None; malformed mocks are a clear ValueError naming the world size
    (ISSUE 13 satellite — the pre-hardening guard silently ran the
    flat transport on a mis-typed mock)."""
    monkeypatch.delenv("FLASHMOE_MOCK_SLICES", raising=False)
    assert slice_structure(devices[:8]) is None  # CPU: one process
    monkeypatch.setenv("FLASHMOE_MOCK_SLICES", "2")
    assert slice_structure(devices[:8]) == (2, 4)
    monkeypatch.setenv("FLASHMOE_MOCK_SLICES", "8")
    assert slice_structure(devices[:8]) == (8, 1)
    monkeypatch.setenv("FLASHMOE_MOCK_SLICES", "1")
    assert slice_structure(devices[:8]) is None  # explicit single slice
    for bad in ("3", "-2", "0", "banana", "2.5"):
        monkeypatch.setenv("FLASHMOE_MOCK_SLICES", bad)
        with pytest.raises(ValueError, match="8 devices"):
            slice_structure(devices[:8])


def test_bootstrap_publishes_dcn_inner(monkeypatch, devices, jitted):
    """An initialized runtime on a mocked 2-slice job publishes
    ranks-per-slice, ep_moe_layer picks it up by default (same pattern
    as the arrival-order table), and the gated accessor refuses meshes
    whose device order differs from jax.devices()."""
    from flashmoe_tpu.runtime import bootstrap

    monkeypatch.setenv("FLASHMOE_MOCK_SLICES", "2")
    monkeypatch.setattr(bootstrap, "_runtime", None)
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                    intermediate_size=256, sequence_len=256,
                    drop_tokens=False, ep=8, **F32)
    rt = bootstrap.initialize(cfg, use_decider=False, measure=False)
    try:
        assert rt.dcn_inner == 4
        mesh = make_mesh(cfg, dp=1, devices=jax.devices()[:8])
        assert bootstrap.current_dcn_inner(mesh, 8) == 4
        # permuted mesh: the blocking indexes jax.devices() order
        perm = list(jax.devices()[:8])
        perm[0], perm[1] = perm[1], perm[0]
        mesh_p = make_mesh(cfg, dp=1, devices=perm)
        assert bootstrap.current_dcn_inner(mesh_p, 8) is None
        # end to end: the default path must produce oracle output while
        # riding the published two-stage exchange
        params, x = _setup(cfg)
        out = jitted(ep_moe_layer, cfg, mesh)(params, x)
        want, _ = reference_moe(params, x, cfg)
        np.testing.assert_allclose(np.asarray(out.out), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
    finally:
        monkeypatch.setattr(bootstrap, "_runtime", None)


def test_transport_cost_model_prefers_aggregation():
    """The modeled reason the two-stage exchange exists: identical
    cross-slice bytes, inner-times fewer DCN messages — so at MoE slab
    sizes (sub-MB per peer) the alpha savings dominate the extra
    in-slice hop and the hierarchical total wins."""
    from flashmoe_tpu.analysis import a2a_transport_cost

    c = a2a_transport_cost(8, 4, slab_bytes=256 * 1024, gen="v5e")
    assert c["hierarchical"]["dcn_messages"] * 4 == c["flat"]["dcn_messages"]
    assert c["hierarchical"]["total_ms"] < c["flat"]["total_ms"]
    # same bytes must cross DCN either way (aggregation, not elision):
    # beta terms equal once the alpha terms are stripped
    strip = lambda leg, n_msg: leg["dcn_ms"] - n_msg * (10.0 / 1e3)
    np.testing.assert_allclose(
        strip(c["flat"], c["flat"]["dcn_messages"]),
        strip(c["hierarchical"], c["hierarchical"]["dcn_messages"]),
        rtol=1e-9,
    )
    # at very large slabs the extra in-slice traffic can flip the total:
    # the model must expose that crossover rather than hide it
    big = a2a_transport_cost(8, 4, slab_bytes=64 * 2**20, gen="v5e")
    assert big["hierarchical"]["ici_ms"] > big["flat"]["ici_ms"]


# ----------------------------------------------------------------------
# Per-hop wire dtypes (MoEConfig.wire_dtype_dcn, ISSUE 13)
# ----------------------------------------------------------------------

def test_dcn_wire_inert_on_flat_and_off_identical(devices, jitted):
    """wire_dtype_dcn must be a pure DCN-hop knob: on the flat exchange
    it is inert (bit-identical output), and on the hierarchical
    exchange the default None traces/computes exactly the single-dtype
    path."""
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                    intermediate_size=256, sequence_len=256,
                    drop_tokens=False, ep=8, **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg, dp=1, devices=devices[:8])
    flat = jitted(ep_moe_layer, cfg, mesh, dcn_inner=0)(params, x)
    flat_knob = jitted(ep_moe_layer, cfg.replace(wire_dtype_dcn="e4m3"), mesh,
                       dcn_inner=0)(params, x)
    np.testing.assert_array_equal(np.asarray(flat_knob.out),
                                  np.asarray(flat.out))
    hier = jitted(ep_moe_layer, cfg, mesh, dcn_inner=4)(params, x)
    hier_none = jitted(ep_moe_layer, cfg.replace(wire_dtype_dcn=None), mesh,
                       dcn_inner=4)(params, x)
    np.testing.assert_array_equal(np.asarray(hier_none.out),
                                  np.asarray(hier.out))


def test_dcn_wire_fp8_hop_close_to_oracle_with_per_hop_error(devices, jitted):
    """An fp8 DCN hop under a raw ICI hop: output stays close to the
    oracle (one fp8 round trip per leg), and MoEStats reports the two
    hops' round-trip errors separately — ici proxy 0 (leg wire off),
    dcn proxy > 0."""
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                    intermediate_size=256, sequence_len=256,
                    drop_tokens=False, ep=8, collect_stats=True,
                    wire_dtype_dcn="e4m3", **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg, dp=1, devices=devices[:8])
    out = jitted(ep_moe_layer, cfg, mesh, dcn_inner=4)(params, x)
    want, _ = reference_moe(params, x, cfg)
    np.testing.assert_allclose(np.asarray(out.out), np.asarray(want),
                               atol=0.25)
    assert float(out.stats.wire_rtq_error) == 0.0
    assert 0.0 < float(out.stats.wire_rtq_error_dcn) < 0.1
    # both wires on: both proxies populated, independently
    both = cfg.replace(wire_dtype="bf16")
    ob = jitted(ep_moe_layer, both, mesh, dcn_inner=4)(params, x)
    assert float(ob.stats.wire_rtq_error) > 0.0
    assert float(ob.stats.wire_rtq_error_dcn) > 0.0


def test_dcn_wire_split_hops_through_chunked_pipeline(devices, jitted):
    """The per-hop codec composes with the chunked double-buffered
    pipeline: every chunk re-encodes its DCN hop, output stays close
    to the serial split-wire result."""
    cfg = MoEConfig(num_experts=16, expert_top_k=2, hidden_size=128,
                    intermediate_size=256, sequence_len=256,
                    capacity_factor=1.0, drop_tokens=True, ep=8,
                    wire_dtype_dcn="e4m3", **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg, dp=1, devices=devices[:8])
    serial = jitted(ep_moe_layer, cfg, mesh, dcn_inner=4)(params, x)
    chunked = jitted(ep_moe_layer, cfg.replace(a2a_chunks=2), mesh,
                     dcn_inner=4)(params, x)
    np.testing.assert_allclose(np.asarray(chunked.out),
                               np.asarray(serial.out),
                               rtol=1e-6, atol=1e-6)


def test_dcn_wire_rejected_with_fused_backend():
    with pytest.raises(ValueError, match="fused"):
        MoEConfig(num_experts=8, ep=8, moe_backend="fused",
                  wire_dtype_dcn="e4m3")


def test_transport_cost_prices_dcn_hop_at_its_own_wire():
    """a2a_transport_cost(dcn_slab_bytes=): the hierarchical DCN term
    serializes at the dcn-wire slab while flat (no re-encode hop) and
    the ICI stage stay at the leg slab — the modeled reason
    fp8-across-DCN + aggregation beats flat-uncompressed."""
    from flashmoe_tpu.analysis import a2a_transport_cost

    raw, fp8 = 256 * 1024, 66 * 1024
    base = a2a_transport_cost(8, 2, raw, gen="v5e")
    comp = a2a_transport_cost(8, 2, raw, gen="v5e",
                              dcn_slab_bytes=fp8)
    assert comp["hierarchical"]["dcn_ms"] < base["hierarchical"]["dcn_ms"]
    assert comp["hierarchical"]["ici_ms"] == base["hierarchical"]["ici_ms"]
    assert comp["flat"] == base["flat"]


def test_wire_row_bytes_per_hop():
    from flashmoe_tpu.analysis import wire_row_bytes

    cfg = MoEConfig(num_experts=8, hidden_size=128,
                    wire_dtype_dcn="e4m3", **F32)
    assert wire_row_bytes(cfg, "dispatch", "ici") == 128 * 4
    assert wire_row_bytes(cfg, "dispatch", "dcn") == 128 * 1 + 4
    # inherit: no override -> both hops price identically
    off = cfg.replace(wire_dtype_dcn=None, wire_dtype="bf16")
    assert wire_row_bytes(off, "dispatch", "dcn") \
        == wire_row_bytes(off, "dispatch", "ici") == 128 * 2
    with pytest.raises(ValueError, match="hop"):
        wire_row_bytes(cfg, "dispatch", "sideways")


# ----------------------------------------------------------------------
# Decider-driven DP x EP group formation at bootstrap (ISSUE 13)
# ----------------------------------------------------------------------

def test_mock_slices_feed_dcn_edges_into_adjacency(monkeypatch, devices):
    """device_slice_ids honors the mock, and ici_adjacency prices
    cross-block pairs at DCN cost — the Decider sees a genuinely
    heterogeneous fabric on the virtual mesh."""
    from flashmoe_tpu.parallel.topology import (
        device_slice_ids, ici_adjacency,
    )

    monkeypatch.setenv("FLASHMOE_MOCK_SLICES", "2")
    assert device_slice_ids(devices[:8]) == [0] * 4 + [1] * 4
    adj = ici_adjacency(devices[:8], platform="v5e")
    # cross-slice = DCN (10us, 25GB/s); in-slice = v5e ICI (1us, 45GB/s)
    assert adj.alpha[0, 7] > adj.alpha[0, 1]
    assert adj.beta[0, 7] > adj.beta[0, 1]


def test_form_groups_ep_across_dcn_on_mocked_mesh(monkeypatch, devices):
    """On a cheap-DCN mock the Decider merges across slices: one EP
    group spanning both, classified ep_across_dcn with the two-stage
    blocking published."""
    from flashmoe_tpu.runtime.bootstrap import form_groups

    monkeypatch.setenv("FLASHMOE_MOCK_SLICES", "2")
    cfg = MoEConfig(num_experts=8, hidden_size=128,
                    intermediate_size=256, sequence_len=128, **F32)
    plan = form_groups(cfg, devices[:8])
    assert plan.mapping == "ep_across_dcn"
    assert (plan.dp, plan.ep) == (1, 8)
    assert plan.dcn_inner == 4
    assert plan.slices == (2, 4)


def test_form_groups_dp_across_dcn_when_dcn_expensive(monkeypatch,
                                                      devices):
    """With the DCN edges priced prohibitively (and per-slice memory
    sufficient), the Decider keeps one EP group per slice — DP crosses
    DCN, the a2a never leaves ICI, and the Runtime adopts the
    factorization (ep folded to the group size)."""
    from flashmoe_tpu.parallel.topology import (
        ici_adjacency, measured_worker_attrs,
    )
    from flashmoe_tpu.runtime import bootstrap

    monkeypatch.setenv("FLASHMOE_MOCK_SLICES", "2")
    monkeypatch.setenv("FLASHMOE_MEMORY_GB", "64")
    cfg = MoEConfig(num_experts=8, hidden_size=128,
                    intermediate_size=256, sequence_len=128, **F32)
    adj = ici_adjacency(devices[:8], platform="v5e")
    sids = [0] * 4 + [1] * 4
    for i in range(8):
        for j in range(8):
            if sids[i] != sids[j]:
                adj.alpha[i, j] *= 1e4
                adj.beta[i, j] *= 1e4
    workers = measured_worker_attrs(devices[:8], cfg, probe=False)
    plan = bootstrap.form_groups(cfg, devices[:8], adj=adj,
                                 workers=workers)
    assert plan.mapping == "dp_across_dcn"
    assert (plan.dp, plan.ep) == (2, 4)
    assert plan.dcn_inner is None
    assert plan.groups == [[0, 1, 2, 3], [4, 5, 6, 7]]


def test_initialize_records_groups_and_respects_pinned_ep(monkeypatch,
                                                          devices):
    """The bootstrap records a bootstrap.groups decision; an explicit
    user ep is never overridden by the Decider's factorization."""
    from flashmoe_tpu.runtime import bootstrap
    from flashmoe_tpu.utils.telemetry import metrics

    monkeypatch.setenv("FLASHMOE_MOCK_SLICES", "2")
    monkeypatch.setattr(bootstrap, "_runtime", None)
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                    intermediate_size=256, sequence_len=128, ep=8,
                    **F32)
    rt = bootstrap.initialize(cfg, measure=False)
    try:
        assert rt.cfg.ep == 8              # pinned ep stands
        assert rt.group_plan is not None
        rec = metrics.last_decision("bootstrap.groups")
        assert rec is not None
        assert rec["ep_pinned"] is True
        assert rec["slices"] == [2, 4]
    finally:
        monkeypatch.setattr(bootstrap, "_runtime", None)


def test_assign_experts_sliced_colocates_hot_pairs():
    """The slice-aware cost-sorted multiset: the two hottest experts
    (a top-2 routing companion pair) land in the SAME slice, the
    slices stay load-balanced, and the assignment is deterministic."""
    from flashmoe_tpu.parallel.decider import assign_experts_sliced

    group = list(range(8))
    rates = [1.0] * 8
    slice_of = [0] * 4 + [1] * 4
    costs = [100.0, 90.0, 10.0, 9.0, 8.0, 7.0, 6.0, 5.0]
    out = assign_experts_sliced(group, rates, 8, slice_of, costs)
    slice_of_expert = {e: slice_of[d] for d, es in out.items()
                      for e in es}
    # the hot pair co-locates; every expert assigned exactly once
    assert slice_of_expert[0] == slice_of_expert[1]
    assert sorted(e for es in out.values() for e in es) == list(range(8))
    # load balance: the other slice carries the cold tail, not nothing
    loads = {0: 0.0, 1: 0.0}
    for e, s in slice_of_expert.items():
        loads[s] += costs[e]
    assert min(loads.values()) > 0
    out2 = assign_experts_sliced(group, rates, 8, slice_of, costs)
    assert out == out2


def test_decide_routes_sliced_assignment(monkeypatch, devices):
    """decide(slice_of=, expert_costs=) on a group spanning slices
    uses the slice-aware assignment (hot pair in one slice)."""
    from flashmoe_tpu.parallel.decider import decide
    from flashmoe_tpu.parallel.topology import (
        WorkerAttr, ici_adjacency,
    )

    monkeypatch.setenv("FLASHMOE_MOCK_SLICES", "2")
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                    intermediate_size=256, sequence_len=128, **F32)
    adj = ici_adjacency(devices[:8], platform="v5e")
    workers = [WorkerAttr(throughput=1.0, memory_gb=64.0)] * 8
    costs = [100.0, 90.0, 10.0, 9.0, 8.0, 7.0, 6.0, 5.0]
    slice_of = [0] * 4 + [1] * 4
    p = decide(adj, workers, cfg, expert_costs=costs,
               slice_of=slice_of)
    owner = {e: d for d, es in p.local_experts.items() for e in es
             if d in p.groups[0]}
    assert slice_of[owner[0]] == slice_of[owner[1]]
