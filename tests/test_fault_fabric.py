"""Fault-tolerant serving fabric (PR 18): the serving-side recovery
ladder.

Fast lanes drill each mechanism directly — the CRC'd failable handoff
transport (tamper => exactly one retry, bit-equal payload), silent
replica crash => probe detection => front-of-queue migration with
token-bit-equal streams, hysteretic brownout shedding, and the
lease-replicated front-door cluster's epoch-bumped failover — all on
mocked ``FLASHMOE_MOCK_FABRIC`` worlds stepping a
:class:`VirtualClock` (trace validation needs virtual time: sibling
jit compiles hole a wall-clock timeline).  PR 19 adds the
cross-process arms: the REAL tcp socket wire (cut mid-stream =>
reconnect + retry, bit-equal payload), the sub-step heartbeat
watchdog (a mid-step hang the probes cannot see), and the external
fenced lease store (tests/test_leasestore.py owns the store itself).
The slow lane runs the eight serving chaos-matrix drills end to end
(``@pytest.mark.slow`` per the lint's tier-1 budget guard).
"""

import dataclasses
import os

import jax
import numpy as np
import pytest

from flashmoe_tpu.chaos import EXPECTED_TIER, FAULTS, FaultPlan
from flashmoe_tpu.fabric import (
    FrontDoor, FrontDoorCluster, HandoffTransport, HandoffTransportError,
    ServingFabric, VirtualClock,
)
from flashmoe_tpu.fabric.handoff import encode_kv_run
from flashmoe_tpu.fabric.router import ReplicaRouter
from flashmoe_tpu.fabric.topo import ENV_MOCK_FABRIC
from flashmoe_tpu.fabric.transport import (
    encode_frames, verify_frames,
)
from flashmoe_tpu.models.transformer import init_params
from flashmoe_tpu.runtime.controller import BrownoutConfig
from flashmoe_tpu.serving.engine import ServeConfig, ServingEngine
from flashmoe_tpu.serving.loadgen import build_requests, tiny_config
from flashmoe_tpu.utils.integrity import crc32_bytes, crc32_pages
from flashmoe_tpu.utils.telemetry import DECISION_NAMES, Metrics

CFG = tiny_config()
SERVE = ServeConfig(max_batch=2, page_size=8, num_pages=64,
                    max_pages_per_slot=4, ctx_bucket_pages=1,
                    prompt_bucket=8)

SERVING_FAULTS = ("replica_crash", "handoff_corrupt",
                  "handoff_timeout", "frontdoor_loss",
                  "net_partition", "lease_split_brain",
                  "replica_stall", "lease_torn_write")


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def trace():
    return build_requests(6, vocab=CFG.vocab_size, prompt_len=8,
                          max_new=4, seed=0, arrival_every=1)


@pytest.fixture(scope="module")
def baseline(params, trace):
    """The gold standard: the same seeded trace through one
    uninterrupted single-pool engine."""
    reqs, arrivals = trace
    eng = ServingEngine(params, CFG, SERVE, metrics_obj=Metrics())
    out = eng.run(reqs, arrivals)
    eng.close()
    return out


@pytest.fixture()
def mock2(monkeypatch):
    monkeypatch.setenv(ENV_MOCK_FABRIC, "2")


def _assert_bit_equal(outputs, baseline):
    assert sorted(outputs) == sorted(baseline)
    for rid in baseline:
        assert outputs[rid] == baseline[rid], f"rid {rid} diverged"


# ----------------------------------------------------------------------
# CRC helpers + wire frames (pure unit)
# ----------------------------------------------------------------------

def test_crc32_pages_splits_and_detects_flips():
    data = bytes(range(251)) * 4
    crcs = crc32_pages(data, 4)
    assert len(crcs) == 4
    # whole-buffer checksum is NOT the concatenation trivially, but a
    # one-byte flip must change exactly the page that holds it
    flipped = bytearray(data)
    flipped[300] ^= 0xFF
    crcs2 = crc32_pages(bytes(flipped), 4)
    diff = [i for i, (a, b) in enumerate(zip(crcs, crcs2)) if a != b]
    assert diff == [300 // (len(data) // 4)]
    # degenerate shapes stay defined
    assert crc32_pages(b"", 3) == (crc32_bytes(b""),) * 3
    assert len(crc32_pages(data, 1)) == 1


def test_wire_frames_roundtrip_and_verify():
    k = jax.random.normal(jax.random.PRNGKey(2), (2, 2, 16, 4))
    v = jax.random.normal(jax.random.PRNGKey(3), (2, 2, 16, 4))
    payload = encode_kv_run(np.asarray(k), np.asarray(v), 8, None)
    frames = encode_frames(payload)
    assert verify_frames(frames) == []
    # stamp garbage into the k frame: verify names (field, page)
    bad = dataclasses.replace(
        frames["k"], buf=b"\x00" * len(frames["k"].buf))
    assert frames["k"].buf != bad.buf
    broken = dict(frames, k=bad)
    named = verify_frames(broken)
    assert named and all(f == "k" for f, _ in named)


# ----------------------------------------------------------------------
# HandoffTransport (no engine)
# ----------------------------------------------------------------------

def _payload(seed=4):
    k = np.asarray(jax.random.normal(jax.random.PRNGKey(seed),
                                     (2, 2, 16, 4)))
    v = np.asarray(jax.random.normal(jax.random.PRNGKey(seed + 1),
                                     (2, 2, 16, 4)))
    return encode_kv_run(k, v, 8, None)


def test_transport_clean_send_is_bit_identical():
    mx = Metrics()
    t = HandoffTransport(metrics_obj=mx)
    p = _payload()
    res = t.send(p, modeled_ms=0.5, rid=0)
    assert res.attempts == 1 and res.retries == 0
    assert res.retry_ms == 0.0
    np.testing.assert_array_equal(np.asarray(res.payload.k),
                                  np.asarray(p.k))
    np.testing.assert_array_equal(np.asarray(res.payload.v),
                                  np.asarray(p.v))
    assert t.snapshot()["retries_total"] == 0
    assert not [d for d in mx.decisions
                if d["decision"] == "fabric.handoff_retry"]


def test_transport_tamper_trips_crc_and_retries_exactly_once():
    mx = Metrics()
    t = HandoffTransport(
        metrics_obj=mx,
        tamper_fn=lambda index, attempt: index == 0 and attempt == 1)
    p = _payload()
    res = t.send(p, modeled_ms=0.5, rid=7, replica=1)
    assert res.attempts == 2 and res.retries == 1
    assert res.corrupt_pages > 0 and res.timeouts == 0
    assert res.retry_ms > 0.5  # wasted wire + backoff
    np.testing.assert_array_equal(np.asarray(res.payload.k),
                                  np.asarray(p.k))
    corrupt = [d for d in mx.decisions
               if d["decision"] == "fabric.handoff_corrupt"]
    retry = [d for d in mx.decisions
             if d["decision"] == "fabric.handoff_retry"]
    assert len(corrupt) == 1 and corrupt[0]["bad_page_count"] > 0
    assert len(retry) == 1 and retry[0]["reason"] == "corrupt"
    assert retry[0]["rid"] == 7 and retry[0]["replica"] == 1
    # the second transfer is clean: fault fired on transfer 0 only
    res2 = t.send(_payload(8), modeled_ms=0.5, rid=8)
    assert res2.retries == 0


def test_transport_timeout_plan_and_budget_exhaustion():
    mx = Metrics()
    t = HandoffTransport(
        metrics_obj=mx, max_retries=2, timeout_ms=10.0, backoff_ms=2.0,
        plan=FaultPlan("handoff_timeout", step=0, duration=1))
    res = t.send(_payload(), modeled_ms=0.5)
    assert res.timeouts == 1 and res.retries == 1
    assert res.retry_ms == pytest.approx(10.0 + 2.0)
    # a persistent fault (once=False) exhausts the bounded budget
    t2 = HandoffTransport(
        metrics_obj=mx, max_retries=2,
        plan=FaultPlan("handoff_timeout", step=0, duration=1,
                       once=False))
    with pytest.raises(HandoffTransportError, match="retry budget"):
        t2.send(_payload())
    assert t2.timeout_total == 3  # 1 first attempt + 2 retries


def test_transport_backoff_caps_and_validates():
    t = HandoffTransport(backoff_ms=5.0, backoff_cap_ms=12.0)
    assert t._backoff(1) == 5.0
    assert t._backoff(2) == 10.0
    assert t._backoff(3) == 12.0  # capped, not 20
    with pytest.raises(ValueError, match="only injects"):
        HandoffTransport(plan=FaultPlan("nan_grad"))
    with pytest.raises(ValueError, match="max_retries"):
        HandoffTransport(max_retries=-1)
    with pytest.raises(ValueError, match="wire"):
        HandoffTransport(wire="carrier_pigeon")


# ----------------------------------------------------------------------
# The socket wire (real localhost TCP, no engine)
# ----------------------------------------------------------------------

def test_tcp_wire_clean_roundtrip_bit_identical():
    """A clean tcp send really crosses a kernel socket and comes back
    byte-equal — same payload contract as the in-process wire."""
    mx = Metrics()
    t = HandoffTransport(metrics_obj=mx, wire="tcp")
    try:
        p = _payload()
        res = t.send(p, modeled_ms=0.5, rid=0)
        assert res.attempts == 1 and res.retries == 0
        np.testing.assert_array_equal(np.asarray(res.payload.k),
                                      np.asarray(p.k))
        np.testing.assert_array_equal(np.asarray(res.payload.v),
                                      np.asarray(p.v))
        snap = t.snapshot()
        assert snap["wire"] == "tcp" and snap["reset_total"] == 0
        assert snap["wire_drops"] == 0
    finally:
        t.close()


def test_tcp_wire_killed_mid_transfer_retries_bit_equal():
    """The wire is cut MID-STREAM (partial bytes really reach the
    receiver's socket, then the connection dies): the receiver
    discards the torn transfer, the sender reconnects and the retry
    delivers a bit-equal payload with the wasted time priced."""
    mx = Metrics()
    t = HandoffTransport(metrics_obj=mx, wire="tcp",
                         plan=FaultPlan("net_partition", step=0,
                                        duration=1))
    try:
        p = _payload()
        res = t.send(p, modeled_ms=0.5, rid=3, replica=1)
        assert res.attempts == 2 and res.retries == 1
        assert res.retry_ms > 0.5      # modeled wire time + backoff
        np.testing.assert_array_equal(np.asarray(res.payload.k),
                                      np.asarray(p.k))
        np.testing.assert_array_equal(np.asarray(res.payload.v),
                                      np.asarray(p.v))
        parts = [d for d in mx.decisions
                 if d["decision"] == "fabric.partition"]
        retries = [d for d in mx.decisions
                   if d["decision"] == "fabric.handoff_retry"]
        assert len(parts) == 1 and parts[0]["injected"] is True
        assert parts[0]["wire"] == "tcp"
        assert parts[0]["dropped_bytes"] > 0
        assert len(retries) == 1 and retries[0]["reason"] == "reset"
        # the receiver really saw (and refused) a partial stream
        assert t.snapshot()["wire_drops"] == 1
        # the next transfer is clean: the reconnect healed the wire
        res2 = t.send(_payload(8), modeled_ms=0.5, rid=4)
        assert res2.retries == 0
    finally:
        t.close()


def test_inproc_partition_plan_needs_no_socket():
    """net_partition on the in-process wire models the drop (no
    partial bytes exist to count) — the retry ladder is identical."""
    mx = Metrics()
    t = HandoffTransport(metrics_obj=mx,
                         plan=FaultPlan("net_partition", step=0,
                                        duration=1))
    res = t.send(_payload(), modeled_ms=0.5)
    assert res.retries == 1
    parts = [d for d in mx.decisions
             if d["decision"] == "fabric.partition"]
    assert len(parts) == 1 and parts[0]["wire"] == "inproc"
    assert parts[0]["dropped_bytes"] is None
    assert t.snapshot()["wire_drops"] == 0
    t.close()                      # idempotent on the socketless wire
    t.close()


# ----------------------------------------------------------------------
# Router fencing + engine evacuate/adopt (no fabric)
# ----------------------------------------------------------------------

def test_router_mark_failed_fences_and_last_death_raises():
    depths = {0: 5, 1: 1, 2: 3}
    router = ReplicaRouter(
        [lambda i=i: {"queue_depth": depths[i], "active_requests": 0}
         for i in range(3)], metrics_obj=Metrics(), affinity=False)
    assert router.route(100) == 1          # JSQ picks the shallowest
    router.mark_failed(1)
    assert router.failed() == (1,)
    for rid in range(101, 110):
        assert router.route(rid) != 1      # the corpse never serves
    router.mark_failed(2)
    assert all(router.route(rid) == 0 for rid in range(110, 115))
    router.mark_failed(0)
    with pytest.raises(RuntimeError, match="every replica has failed"):
        router.route(200)
    assert router.snapshot()["failed"] == [0, 1, 2]


def test_engine_evacuate_returns_all_and_adopt_front(params, trace):
    reqs, _ = trace
    eng = ServingEngine(params, CFG, SERVE, metrics_obj=Metrics())
    for r in reqs[:4]:
        eng.submit(r)
    for _ in range(2):          # some admitted, some still queued
        eng.step()
    inflight, queued = eng.evacuate()
    assert len(inflight) + len(queued) == 4 - len(eng.outputs)
    assert not eng.pending()    # nothing left behind on the corpse
    # in-flight victims carry their delivered tokens in the resumed
    # prompt (the bit-equal migration invariant)
    for entry in inflight:
        assert len(entry.req.prompt) >= len(entry.orig.prompt)
    adopter = ServingEngine(params, CFG, SERVE, metrics_obj=Metrics())
    tail = reqs[4]
    adopter.submit(tail)
    for entry in inflight:
        adopter.adopt(entry, front=True)
    # front adoption queues ahead of the local arrival and admits
    # immediately (arrival_step clamped to the adopter's clock);
    # each front insert prepends, so the head is the LAST adoptee
    head = adopter.queue[0]
    assert head.orig.rid == inflight[-1].orig.rid
    assert head.arrival_step <= adopter.step_idx
    assert adopter.stats["adopted"] == len(inflight)
    eng.close()
    adopter.close()


# ----------------------------------------------------------------------
# Fast per-fault smokes (mocked fabric, virtual clock)
# ----------------------------------------------------------------------

def test_fabric_crash_migration_bit_equal(params, trace, baseline,
                                          mock2):
    reqs, arrivals = trace
    mx = Metrics()
    fab = ServingFabric(params, CFG, SERVE, metrics_obj=mx,
                        vclock=VirtualClock(),
                        fault_plan=FaultPlan("replica_crash", step=3,
                                             expert=0))
    door = FrontDoor(fab)
    out = door.run(reqs, arrivals)
    errs = door.validate()
    door.close()
    fab.close()
    _assert_bit_equal(out, baseline)
    assert errs == []
    crash = [d for d in mx.decisions
             if d["decision"] == "fabric.replica_crash"]
    mig = [d for d in mx.decisions if d["decision"] == "fabric.migrate"]
    assert len(crash) == 1 and crash[0]["replica"] == 0
    assert mig and all(d["from_replica"] == 0 for d in mig)
    assert fab.migrated == len(mig)
    assert fab.router.failed() == (0,)


def test_fabric_transport_corrupt_retries_and_bit_equal(params, trace,
                                                        baseline,
                                                        mock2):
    reqs, arrivals = trace
    mx = Metrics()
    t = HandoffTransport(metrics_obj=mx,
                         plan=FaultPlan("handoff_corrupt", step=1,
                                        duration=2))
    fab = ServingFabric(params, CFG, SERVE, metrics_obj=mx,
                        vclock=VirtualClock(), transport=t)
    door = FrontDoor(fab)
    out = door.run(reqs, arrivals)
    errs = door.validate()
    door.close()
    fab.close()
    _assert_bit_equal(out, baseline)
    assert errs == []
    assert t.retries_total == 2      # one retry per faulted transfer
    drift = [d for d in mx.decisions
             if d["decision"] == "fabric.handoff_drift"]
    perturbed = [d for d in drift if d["retry_ms"] > 0]
    assert len(perturbed) == 2       # retry cost priced into the clock
    assert fab.handoff.snapshot()["transport"]["corrupt_total"] > 0


def test_frontdoor_brownout_sheds_and_recovers(params, mock2):
    flood, _ = build_requests(10, vocab=CFG.vocab_size, prompt_len=8,
                              max_new=6, seed=1, arrival_every=0)
    arrivals = [0, 0, 0, 0, 2, 2, 3, 3, 4, 5]
    mx = Metrics()
    fab = ServingFabric(params, CFG, SERVE, metrics_obj=mx,
                        vclock=VirtualClock())
    door = FrontDoor(fab, brownout=BrownoutConfig(
        queue_high=2.0, queue_low=0.5, debounce_steps=1,
        cooldown_steps=2, episode_budget=2))
    out = door.run(flood, arrivals)
    errs = door.validate()
    snap = door.brownout_snapshot()
    door.close()
    fab.close()
    assert errs == []
    shed = [d for d in mx.decisions
            if d["decision"] == "frontdoor.shed"]
    trans = [d["state"] for d in mx.decisions
             if d["decision"] == "frontdoor.brownout"]
    assert snap["shed"] == len(shed) >= 1
    assert "enter" in trans and "exit" in trans
    # conservation: every offered request either completed or was shed
    assert len(out) + len(door.shed_rids) == len(flood)
    # admitted requests were never touched by the brownout
    assert all(rid not in out for rid in door.shed_rids)


def test_frontdoor_brownout_degrade_caps_tokens(params, mock2):
    flood, _ = build_requests(8, vocab=CFG.vocab_size, prompt_len=8,
                              max_new=6, seed=2, arrival_every=0)
    arrivals = [0, 0, 0, 0, 2, 2, 3, 4]
    mx = Metrics()
    fab = ServingFabric(params, CFG, SERVE, metrics_obj=mx,
                        vclock=VirtualClock())
    door = FrontDoor(fab, brownout=BrownoutConfig(
        queue_high=2.0, queue_low=0.5, mode="degrade",
        degrade_max_new=2, debounce_steps=1, cooldown_steps=2))
    out = door.run(flood, arrivals)
    door.close()
    fab.close()
    degraded = [d for d in mx.decisions
                if d["decision"] == "frontdoor.shed"
                and d["mode"] == "degrade"]
    assert degraded and door.degraded_rids
    assert all(d["max_new_tokens"] == 2 for d in degraded)
    # degraded requests complete (short), nothing is dropped; outputs
    # echo the 8-token prompt, so the cap shows as prompt + 2
    assert len(out) == len(flood)
    for d in degraded:
        assert len(out[d["rid"]]) <= 8 + 2


def test_frontdoor_cluster_failover_bit_equal(params, trace, baseline,
                                              mock2):
    reqs, arrivals = trace
    mx = Metrics()
    fab = ServingFabric(params, CFG, SERVE, metrics_obj=mx,
                        vclock=VirtualClock())
    cl = FrontDoorCluster(fab, n_doors=2, n_shards=8, metrics_obj=mx)
    out = cl.run(reqs, arrivals, fail_at=2, fail_peer=0)
    errs = cl.validate()
    snap = cl.snapshot()
    doc = cl.fleet_trace_document()
    cl.close()
    fab.close()
    _assert_bit_equal(out, baseline)
    assert errs == []                # zero orphan spans post-failover
    assert doc["traceEvents"]
    fo = [d for d in mx.decisions
          if d["decision"] == "frontdoor.failover"]
    assert fo and all(d["from_peer"] == 0 and d["to_peer"] != 0
                      for d in fo)
    assert all(d["epoch"] >= 1 for d in fo)
    assert snap["max_epoch"] >= 1 and snap["dead"] == [0]
    # every lease ended up owned by a survivor
    assert all(lease["owner"] != 0 for lease in cl.leases.values())


def test_fabric_replica_stall_heartbeat_migration_bit_equal(
        params, trace, baseline, mock2):
    """A replica hangs MID-STEP: its probe still answers, so only the
    sub-step heartbeat deadline catches it — then the same
    fence+evacuate+adopt migration as a probed crash, token-bit-equal."""
    from flashmoe_tpu.fabric import HeartbeatConfig

    reqs, arrivals = trace
    mx = Metrics()
    fab = ServingFabric(params, CFG, SERVE, metrics_obj=mx,
                        vclock=VirtualClock(),
                        heartbeat=HeartbeatConfig(misses_to_stall=2),
                        fault_plan=FaultPlan("replica_stall", step=3,
                                             expert=0))
    door = FrontDoor(fab)
    out = door.run(reqs, arrivals)
    errs = door.validate()
    door.close()
    fab.close()
    _assert_bit_equal(out, baseline)
    assert errs == []
    stalls = [d for d in mx.decisions
              if d["decision"] == "fabric.heartbeat_stall"]
    misses = [d for d in mx.decisions
              if d["decision"] == "fabric.heartbeat_miss"]
    crash = [d for d in mx.decisions
             if d["decision"] == "fabric.replica_crash"]
    assert len(stalls) == 1 and stalls[0]["replica"] == 0
    assert stalls[0]["detect_ms"] > 0
    # detection is LATE by design: the hysteresis window, not the
    # hang step (the probe can never see a stall)
    assert stalls[0]["step"] > 3
    assert len(misses) == 2        # misses_to_stall consecutive
    assert len(crash) == 1 and fab.router.failed() == (0,)
    assert 0 in fab._stalled


def test_fabric_heartbeat_off_is_default_and_invisible(params, trace,
                                                       baseline, mock2):
    """heartbeat=None (the default) installs NO engine callback and
    no store file — the probe-only path byte-identical to PR 18."""
    reqs, arrivals = trace
    fab = ServingFabric(params, CFG, SERVE, metrics_obj=Metrics(),
                        vclock=VirtualClock())
    assert fab.hb_watchdog is None
    assert all(e._heartbeat is None for e in fab.engines)
    door = FrontDoor(fab)
    out = door.run(reqs, arrivals)
    door.close()
    fab.close()
    _assert_bit_equal(out, baseline)


def test_fabric_heartbeat_armed_clean_run_bit_equal(params, trace,
                                                    baseline, mock2):
    """Heartbeats on with NO fault: zero misses, zero stalls, outputs
    bit-equal — the watchdog never false-positives on a healthy
    fleet."""
    from flashmoe_tpu.fabric import HeartbeatConfig

    reqs, arrivals = trace
    mx = Metrics()
    fab = ServingFabric(params, CFG, SERVE, metrics_obj=mx,
                        vclock=VirtualClock(),
                        heartbeat=HeartbeatConfig())
    store_path = fab._own_store_path
    assert store_path and os.path.exists(store_path)
    door = FrontDoor(fab)
    out = door.run(reqs, arrivals)
    door.close()
    fab.close()
    _assert_bit_equal(out, baseline)
    assert not [d for d in mx.decisions
                if d["decision"] in ("fabric.heartbeat_miss",
                                     "fabric.heartbeat_stall")]
    assert not os.path.exists(store_path)   # close() reaped the store


def test_frontdoor_cluster_store_parity_with_in_memory(params, trace,
                                                       baseline, mock2,
                                                       tmp_path):
    """The externally-stored lease table is a drop-in for the
    in-memory one: same failover decisions (shard/epoch/peers), same
    tokens, plus fencing on the store."""
    from flashmoe_tpu.fabric import LeaseStore, StaleLeaseError

    reqs, arrivals = trace

    def run_cluster(store):
        mx = Metrics()
        fab = ServingFabric(params, CFG, SERVE, metrics_obj=mx,
                            vclock=VirtualClock())
        cl = FrontDoorCluster(fab, n_doors=2, n_shards=8,
                              metrics_obj=mx, store=store)
        out = cl.run(reqs, arrivals, fail_at=2, fail_peer=0)
        snap = cl.snapshot()
        cl.close()
        fab.close()
        fo = [{k: d[k] for k in ("shard", "from_peer", "to_peer",
                                 "epoch")}
              for d in mx.decisions
              if d["decision"] == "frontdoor.failover"]
        return out, fo, snap

    store = LeaseStore(str(tmp_path / "leases.bin"),
                       metrics_obj=Metrics())
    out_mem, fo_mem, _ = run_cluster(None)
    out_ext, fo_ext, snap = run_cluster(store)
    _assert_bit_equal(out_mem, baseline)
    _assert_bit_equal(out_ext, baseline)
    assert fo_ext == fo_mem          # identical failover ledger
    assert snap["external_store"]
    # the store remembers across instances, and fences stale epochs
    reopened = LeaseStore(str(tmp_path / "leases.bin"),
                          metrics_obj=Metrics())
    moved = sorted(d["shard"] for d in fo_ext)
    assert moved and all(reopened.leases()[s].owner != 0
                         and reopened.leases()[s].epoch >= 1
                         for s in moved)
    shard = moved[0]
    with pytest.raises(StaleLeaseError):
        reopened.write_lease(shard, 0,
                             reopened.leases()[shard].epoch)


def test_frontdoor_cluster_validates_and_fences(params, mock2):
    fab = ServingFabric(params, CFG, SERVE, metrics_obj=Metrics(),
                        vclock=VirtualClock())
    cl = FrontDoorCluster(fab, n_doors=2, n_shards=8,
                          metrics_obj=Metrics())
    with pytest.raises(ValueError, match="door"):
        FrontDoorCluster(fab, n_doors=0)
    cl.fail_door(0)
    with pytest.raises(RuntimeError, match="last live"):
        cl.fail_door(1)
    cl.close()
    fab.close()


# ----------------------------------------------------------------------
# Registry / matrix bookkeeping
# ----------------------------------------------------------------------

def test_serving_faults_registered_with_tiers():
    for fault in SERVING_FAULTS:
        assert fault in FAULTS
        assert EXPECTED_TIER[fault].startswith("fabric:")
    for name in ("fabric.handoff_corrupt", "fabric.handoff_retry",
                 "fabric.migrate", "fabric.replica_crash",
                 "fabric.partition", "fabric.heartbeat_miss",
                 "fabric.heartbeat_stall", "frontdoor.brownout",
                 "frontdoor.failover", "frontdoor.fence",
                 "frontdoor.lease_repair", "frontdoor.shed"):
        assert name in DECISION_NAMES


def test_brownout_config_validates():
    with pytest.raises(ValueError):
        BrownoutConfig(queue_high=2.0, queue_low=3.0)
    with pytest.raises(ValueError):
        BrownoutConfig(mode="panic")
    with pytest.raises(ValueError):
        BrownoutConfig(degrade_max_new=0)
    with pytest.raises(ValueError):
        BrownoutConfig(episode_budget=0)


# ----------------------------------------------------------------------
# The chaos-matrix drills (slow lane)
# ----------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("fault", SERVING_FAULTS)
def test_serving_fault_drill_recovers(fault):
    from flashmoe_tpu.chaos.drill import run_drill

    r = run_drill(fault)
    assert r.recovered, f"{fault}: {r.reason}"
    ev = r.evidence
    assert ev["bit_equal_to_baseline"] is True
    assert ev["trace_errors"] == []
    assert ev["fleet_trace_events"] > 0
    if fault == "replica_crash":
        assert ev["crashes"] == 1 and ev["migrations"] >= 1
    elif fault in ("handoff_corrupt", "handoff_timeout"):
        assert ev["retries"] == 2 and ev["retried_drift"] == 2
    elif fault == "frontdoor_loss":
        assert ev["failovers"] >= 1
    elif fault == "net_partition":
        # real socket cuts: partial bytes crossed, retried as resets
        assert ev["partitions"] == 2 and ev["retries"] == 2
        assert ev["retried_drift"] == 2
    elif fault == "lease_split_brain":
        assert ev["zombie_attempts"] >= 1
        assert ev["zombie_refused"] == ev["zombie_attempts"]
        assert ev["fences"] == ev["zombie_refused"]
    elif fault == "replica_stall":
        assert ev["stalls"] == 1 and ev["heartbeat_misses"] >= 2
        assert ev["crashes"] == 1 and ev["migrations"] >= 1
    elif fault == "lease_torn_write":
        assert ev["lease_repairs"] >= 1 and ev["torn_bytes"] > 0
        assert ev["restored_epoch"] == 1 and ev["failovers"] >= 1


# ----------------------------------------------------------------------
# Speculative decoding under faults (ISSUE 20)
# ----------------------------------------------------------------------

def _spec_serve(k: int = 3) -> ServeConfig:
    from flashmoe_tpu.serving.speculate import SpecConfig

    return dataclasses.replace(SERVE, speculate=SpecConfig(draft_tokens=k))


@pytest.fixture(scope="module")
def spec_trace():
    """Repetitive prompts (tiled bigram motifs): the n-gram drafter has
    suffix matches to propose from, so the fault drills exercise real
    acceptance instead of the empty-draft fallthrough."""
    return build_requests(6, vocab=CFG.vocab_size, prompt_len=8,
                          max_new=6, seed=3, arrival_every=1,
                          repetitive=True)


@pytest.fixture(scope="module")
def spec_baseline(params, spec_trace):
    """Gold standard for the speculative drills: the same trace through
    one uninterrupted NON-speculative engine — exact rejection sampling
    must hold through crashes and morphs, not just clean runs."""
    reqs, arrivals = spec_trace
    eng = ServingEngine(params, CFG, SERVE, metrics_obj=Metrics())
    out = eng.run(reqs, arrivals)
    eng.close()
    return out


@pytest.mark.slow
def test_fabric_crash_migration_spec_bit_equal(params, spec_trace,
                                               spec_baseline, mock2):
    """A replica dies mid-stream with speculation armed: the migrated
    requests re-prefill on the adopter, the DraftState rebuilds from
    ``prompt + emitted``, and every stream stays token-bit-equal to the
    non-speculative single-engine oracle."""
    reqs, arrivals = spec_trace
    mx = Metrics()
    fab = ServingFabric(params, CFG, _spec_serve(), metrics_obj=mx,
                        vclock=VirtualClock(),
                        fault_plan=FaultPlan("replica_crash", step=3,
                                             expert=0))
    door = FrontDoor(fab)
    out = door.run(reqs, arrivals)
    errs = door.validate()
    summ = fab.summary()
    door.close()
    fab.close()
    _assert_bit_equal(out, spec_baseline)
    assert errs == []
    crash = [d for d in mx.decisions
             if d["decision"] == "fabric.replica_crash"]
    assert len(crash) == 1 and crash[0]["replica"] == 0
    assert [d for d in mx.decisions
            if d["decision"] == "fabric.migrate"]
    # not vacuous: drafts flowed (and some were accepted) fleet-wide
    assert summ["spec"]["spec_drafted"] > 0
    assert summ["spec"]["spec_accepted"] > 0
    assert summ["spec"]["spec_on"] == [True, True]


@pytest.mark.slow
def test_fabric_spec_morph_drill_zero_lost_tokens(params, spec_trace,
                                                  spec_baseline, mock2):
    """The controller drill the ISSUE names: a fleet running with an
    unreachable acceptance floor morphs speculation OFF on every
    replica at once (a per-replica split would fork measurement
    identity), loses zero tokens, and stays bit-equal — exact
    rejection sampling makes the morph free."""
    from flashmoe_tpu.runtime.controller import (
        ControllerConfig, RuntimeController,
    )

    reqs, arrivals = spec_trace
    mx = Metrics()
    cc = ControllerConfig(enable_spec_morph=True, spec_accept_floor=0.99,
                          debounce_steps=1, cooldown_steps=2)
    ctl = RuntimeController(CFG, cc, metrics=mx)
    fab = ServingFabric(params, CFG, _spec_serve(), metrics_obj=mx,
                        vclock=VirtualClock(), controller=ctl)
    door = FrontDoor(fab)
    out = door.run(reqs, arrivals)
    errs = door.validate()
    summ = fab.summary()
    door.close()
    fab.close()
    _assert_bit_equal(out, spec_baseline)        # zero lost tokens
    assert errs == []
    assert ctl.spec_morphs_used == 1
    assert summ["spec"]["spec_on"] == [False, False]
    morphs = [d for d in mx.decisions
              if d["decision"] == "controller.spec_morph"]
    assert len(morphs) == 1
    assert morphs[0]["trigger"] == "accept_low"
