"""Interconnect topology: analytic ICI cost model + DCN probing.

The reference measures its network empirically: a device kernel times
pairwise small/large NVSHMEM puts and slope-intercept fits alpha (latency,
ms) / beta (ms/MB) per peer (``csrc/include/flashmoe/topo.cuh:43-82``), with
block-specialized publishers for remote vs P2P paths, and each rank
broadcasting its adjacency row (``topo.cuh:207-262``).

On TPU the intra-slice network is a known torus: geometry comes from
``device.coords`` and per-generation link specs, so the alpha-beta adjacency
matrix is *derived*, not probed (no warm-up kernels, no measurement noise).
Probing remains meaningful across slices (DCN), where
:func:`probe_dcn_costs` times real transfers the same way the reference
does — but over XLA collectives.

The produced ``Adjacency`` feeds the Decider
(:mod:`flashmoe_tpu.parallel.decider`) exactly like the reference's
``adjMatrix`` feeds ``Decider::operator()``.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

# Per-generation link characteristics (one-way, per ICI link).
# Sources: public TPU system papers / scaling-book numbers; conservative.
_ICI_SPECS = {
    # gen: (latency_us, GB/s per link direction)
    "v4": (1.0, 50.0),
    "v5e": (1.0, 45.0),
    "v5p": (1.0, 90.0),
    "v6e": (1.0, 90.0),
    "cpu": (10.0, 10.0),  # virtual/testing backend
}
_DCN_SPEC = (10.0, 25.0)  # (latency_us, GB/s) per host NIC, conservative

# Per-chip compute / memory peaks (public spec sheets, bf16 matmul) —
# the roofline ceilings the analytical planner prices against.  One
# table for every consumer (overlap bound, bench MXU label, planner):
# a generation added here becomes plannable everywhere at once.
_PEAK_TFLOPS = {"v4": 275.0, "v5e": 197.0, "v5p": 459.0, "v6e": 918.0}
_HBM_GBPS = {"v4": 1228.0, "v5e": 819.0, "v5p": 2765.0, "v6e": 1638.0}


def chip_spec(gen: str) -> tuple[float, float]:
    """(peak bf16 TFLOP/s, HBM GB/s) for a TPU generation.

    Raises ``ValueError`` naming the supported set for anything else —
    the planner and overlap bound call this with arbitrary user strings,
    and a bare ``KeyError`` carried no hint of what is accepted."""
    if gen not in _PEAK_TFLOPS:
        raise ValueError(
            f"unknown TPU generation {gen!r}; supported: "
            f"{', '.join(sorted(_PEAK_TFLOPS))}")
    return _PEAK_TFLOPS[gen], _HBM_GBPS[gen]


def ici_spec(gen: str) -> tuple[float, float]:
    """(latency us, GB/s per link direction) of a generation's ICI.
    An unknown generation is an error that names it, as in
    :func:`chip_spec` — never priced as some other chip."""
    if gen not in _ICI_SPECS:
        raise ValueError(
            f"unknown generation {gen!r}; ICI specs exist for: "
            f"{', '.join(sorted(_ICI_SPECS))}")
    return _ICI_SPECS[gen]


def tpu_generation(device) -> str:
    """Map a device to a generation key for the spec tables.

    ``device.platform`` is only 'tpu'/'cpu' — the generation lives in
    ``device_kind`` (e.g. "TPU v5e", "TPU v5 lite", "TPU v5p").  A TPU
    whose kind is not in the table is an error that names the kind: its
    peaks and link speeds are not known, and no other chip's stand in."""
    if device.platform == "cpu":
        return "cpu"
    kind = (getattr(device, "device_kind", "") or "").lower()
    if "v5e" in kind or "v5 lite" in kind or "v5lite" in kind:
        return "v5e"
    if "v5p" in kind or kind.endswith("v5") or "v5 pod" in kind:
        return "v5p"
    if "v6e" in kind or "v6 lite" in kind or "trillium" in kind:
        return "v6e"
    if "v4" in kind:
        return "v4"
    raise ValueError(
        f"unknown device_kind {device.device_kind!r} on platform "
        f"{device.platform!r}: no entry in the spec tables of "
        f"flashmoe_tpu/parallel/topology.py (known: "
        f"{', '.join(sorted(_PEAK_TFLOPS))})")


@dataclasses.dataclass
class WorkerAttr:
    """Per-device attributes for the Decider (the reference's
    ``WorkerAttribute`` {throughput, memoryCapacity}, ``topo.cuh:26-41``)."""

    throughput: float  # expert-FFN throughput, experts/ms (higher = faster)
    memory_gb: float


@dataclasses.dataclass
class Adjacency:
    """alpha[i,j] ms latency, beta[i,j] ms/MB inverse bandwidth."""

    alpha: np.ndarray
    beta: np.ndarray

    @property
    def n(self) -> int:
        return self.alpha.shape[0]

    def transfer_ms(self, i: int, j: int, mbytes: float) -> float:
        return float(self.alpha[i, j] + self.beta[i, j] * mbytes)

    def export(self, path: str, rank: int = 0):
        """Dump the adjacency to text (the reference's ``exportTopo``
        debug dump, ``bootstrap.cuh:69-96``, which writes
        ``adjMatrix_Rank{r}.txt`` per rank)."""
        with open(path, "w") as f:
            f.write(f"# adjacency rank={rank} n={self.n}\n")
            f.write("# alpha (ms)\n")
            for row in self.alpha:
                f.write(" ".join(f"{v:.6f}" for v in row) + "\n")
            f.write("# beta (ms/MB)\n")
            for row in self.beta:
                f.write(" ".join(f"{v:.6f}" for v in row) + "\n")


def default_ring(n: int) -> np.ndarray:
    """The fused kernel's default source schedule: row r processes
    sources (r, r+1, ..., r-1) — ``order[r, s] = (r + s) mod n``.  The
    single definition both :mod:`flashmoe_tpu.runtime.bootstrap` (to
    suppress redundant tables) and the kernel launcher compare against."""
    r = np.arange(n, dtype=np.int32)
    return (r[:, None] + r[None, :]) % n


def arrival_order(adj: Adjacency, payload_mb: float,
                  stagger_ms: float = 0.0) -> np.ndarray:
    """Per-rank source-processing order for the fused RDMA kernel, sorted
    by predicted slab arrival time.

    Row r is a permutation of ranks starting with r (the own slab is
    local); the remaining sources are ordered by the alpha-beta transfer
    estimate of their slab to r (+ ``stagger_ms`` x ring distance for the
    send-issue stagger of the kernel's phase 1).  On a homogeneous ICI
    torus this reduces to ring order; with heterogeneous links (e.g. a
    DCN hop between slices) slow sources sink to the end so fast slabs
    are never stalled behind them.

    This is the static counterpart of the reference's subscriber, which
    consumes packets in physical arrival order
    (``csrc/include/flashmoe/os/subscriber.cuh:333-451``): a Pallas
    kernel cannot poll semaphores without blocking, so the expected order
    is bound at trace time from the same measured topology the Decider
    uses.  Mispredictions cost stall time but never correctness (every
    slab's recv semaphore is awaited; bound quantified in
    ``scripts/skew_sim.py``).
    """
    n = adj.n
    order = np.empty((n, n), dtype=np.int32)
    for r in range(n):
        others = [s for s in range(n) if s != r]
        # sender s issues its copy toward r at phase-1 step
        # (r - s - 1) mod n (the kernel sends dst = my+1, my+2, ...), so
        # that is the issue-stagger penalty direction; ties keep the
        # kernel's default ring order so stagger_ms=0 is zero-diff
        issue_step = lambda s: (r - s - 1) % n
        ring_dist = lambda s: (s - r) % n
        others.sort(key=lambda s: (adj.transfer_ms(s, r, payload_mb)
                                   + stagger_ms * issue_step(s),
                                   ring_dist(s)))
        order[r, 0] = r
        order[r, 1:] = others
    return order


def _mock_slices(n: int) -> int | None:
    """Parse ``FLASHMOE_MOCK_SLICES`` against a world of ``n`` devices.

    Returns the slice count, or ``None`` when the mock is unset (or
    asks for a single slice — no blocking).  Malformed values are a
    configuration error the job must see at bootstrap, not a silent
    fall-back to the flat transport (the pre-hardening guard was
    parse-only): a non-integer, a non-positive count, or a count that
    does not divide the world size all raise a ``ValueError`` naming
    the world size and the accepted format (docs/PLANNER.md)."""
    import os

    raw = os.environ.get("FLASHMOE_MOCK_SLICES")
    if raw is None or raw.strip() == "":
        return None
    try:
        outer = int(raw)
    except ValueError:
        raise ValueError(
            f"FLASHMOE_MOCK_SLICES={raw!r} is not an integer; the mock "
            f"format is a single positive slice count dividing the "
            f"world size ({n} devices), e.g. FLASHMOE_MOCK_SLICES=2")
    if outer < 1:
        raise ValueError(
            f"FLASHMOE_MOCK_SLICES={outer} must be >= 1 (a positive "
            f"slice count dividing the world size, {n} devices)")
    if outer > 1 and n % outer:
        raise ValueError(
            f"FLASHMOE_MOCK_SLICES={outer} does not divide the world "
            f"size ({n} devices); pick a divisor of {n} so every mocked "
            f"slice holds the same contiguous rank block")
    return outer if outer > 1 else None


def device_slice_ids(devices=None) -> list:
    """Per-device slice membership ids, the ONE resolution every
    consumer shares: ``FLASHMOE_MOCK_SLICES`` (validated by
    :func:`_mock_slices`) partitions the device list into equal
    contiguous blocks; otherwise ``device.slice_index`` with a
    ``process_index`` fallback (0 for non-device objects).  Both the
    blocking detector (:func:`slice_structure`) and the adjacency
    builder (:func:`ici_adjacency`) read membership through this
    helper, so a mocked topology gets DCN-priced edges in the Decider's
    adjacency exactly like a real multislice job."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    mock = _mock_slices(n)
    if mock is not None:
        inner = n // mock
        return [i // inner for i in range(n)]
    sids = [getattr(d, "slice_index", None) for d in devices]
    if any(s is None for s in sids):
        sids = [getattr(d, "process_index", 0) for d in devices]
    return sids


def slice_structure(devices=None) -> tuple[int, int] | None:
    """Detect a (num_slices, ranks_per_slice) blocking of the device
    list, or None when it is a single slice / irregular.

    This is the trigger for the two-stage ICI+DCN exchange
    (:func:`flashmoe_tpu.parallel.ep._hierarchical_a2a`): the TPU
    analogue of the reference resolving P2P vs remote per peer at init
    (``bootstrap.cuh:442-446``) and branching transport per send
    (``os/packet.cuh:221-258``).  Slice membership comes from
    ``device.slice_index`` (fallback ``process_index``); the blocking
    must be contiguous and equal-sized (rank = slice * inner + i), which
    is how jax orders devices on multislice jobs — an interleaved
    ordering returns None and the flat all-to-all stands (correct on any
    layout, just not DCN-message-aggregated).

    ``FLASHMOE_MOCK_SLICES=k`` partitions the first ``n`` devices into
    ``k`` equal contiguous "slices" regardless of their real topology —
    the virtual-mesh hook (CPU devices all share process 0) used by the
    multislice tests and the chaos drills.  Malformed mock values
    (non-integer, non-positive, non-divisor of ``n``) raise a
    ``ValueError`` naming the world size (:func:`_mock_slices`) — a
    mis-typed mock must fail the bootstrap, not silently run the flat
    transport.
    """
    devices = list(devices if devices is not None else jax.devices())
    return contiguous_blocking(device_slice_ids(devices))


def contiguous_blocking(sids) -> tuple[int, int] | None:
    """(num_blocks, block_size) of a contiguous equal-sized blocking of
    a slice-id sequence, or None when it is single-valued / irregular —
    the structural half of :func:`slice_structure`, public so the
    bootstrap can derive the blocking of an ep PREFIX from the WORLD's
    slice ids (re-running the mock on a subset would mis-partition it,
    and reject world-valid mocks whose count does not divide the
    subset)."""
    sids = list(sids)
    n = len(sids)
    uniq = sorted(set(sids))
    if len(uniq) <= 1:
        return None
    inner = n // len(uniq)
    if inner * len(uniq) != n:
        return None
    # contiguous equal blocks in device order
    for b in range(len(uniq)):
        block = sids[b * inner:(b + 1) * inner]
        if len(set(block)) != 1:
            return None
    return len(uniq), inner


def _torus_hops(a, b, dims):
    """Minimal hop count between coords on a (possibly wrap-around) torus."""
    hops = 0
    for x, y, d in zip(a, b, dims):
        delta = abs(x - y)
        hops += min(delta, d - delta) if d > 2 else delta
    return hops


def ici_adjacency(devices=None, platform: str | None = None) -> Adjacency:
    """Analytic alpha-beta adjacency for the device set.

    Devices on the same slice get torus-hop-scaled ICI costs; devices on
    different slices (different ``slice_index``/process — or different
    mocked blocks under ``FLASHMOE_MOCK_SLICES``, via
    :func:`device_slice_ids`) get DCN costs.  The mock therefore feeds
    the Decider a genuinely heterogeneous adjacency, so DP x EP group
    formation is CI-testable on the virtual CPU mesh.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    plat = platform or tpu_generation(devices[0])
    lat_us, bw = ici_spec(plat)
    dcn_lat_us, dcn_bw = _DCN_SPEC

    coords = []
    slice_ids = device_slice_ids(devices)
    dims = None
    for d in devices:
        c = getattr(d, "coords", None)
        coords.append(tuple(c) if c is not None else (getattr(d, "id", 0),))
    if coords and all(len(c) == len(coords[0]) for c in coords):
        dims = tuple(
            max(c[k] for c in coords) + 1 for k in range(len(coords[0]))
        )

    alpha = np.zeros((n, n))
    beta = np.zeros((n, n))
    for i in range(n):
        for j in range(n):
            if i == j:
                continue
            if slice_ids[i] != slice_ids[j]:
                alpha[i, j] = dcn_lat_us / 1e3
                beta[i, j] = 1e3 / (dcn_bw * 1e3)  # ms per MB
            else:
                hops = max(
                    1, _torus_hops(coords[i], coords[j], dims or (n,))
                )
                alpha[i, j] = hops * lat_us / 1e3
                # bandwidth is per link; multi-hop paths share links, model
                # as single-link bandwidth with per-hop latency
                beta[i, j] = 1e3 / (bw * 1e3)
    return Adjacency(alpha, beta)


def probe_dcn_costs(sizes_mb=(0.25, 4.0), trials: int = 3,
                    max_pairwise: int = 8):
    """Measure the cross-process alpha-beta adjacency with timed transfers.

    The analogue of the reference's topology-discovery kernel
    (``topo.cuh:207-262``): where each GPU rank times one-sided puts to
    every peer and broadcasts its adjacency row, here each process pair is
    timed with a real cross-process ``ppermute`` carrying only that pair's
    payload (collectives being two-sided, every rank participates in each
    probe anyway, so every process observes every pair's wall time and no
    row broadcast is needed).  Two payload sizes give a slope-intercept
    alpha (ms) / beta (ms/MB) fit per pair.

    Up to ``max_pairwise`` processes every ordered pair is probed
    individually (O(P^2) probes); beyond that, pairs at equal ring offset
    are probed concurrently (O(P) probes — each rank sends to rank+k, so
    the per-offset wall time upper-bounds every pair at that offset).

    Returns (alpha[P, P], beta[P, P]) ndarrays, or None single-process.
    """
    import functools

    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    p = jax.process_count()
    if p <= 1:
        return None
    devs = jax.devices()
    # one representative device per process (DCN cost is host-level)
    rep = {}
    for d in devs:
        rep.setdefault(d.process_index, d)
    reps = [rep[i] for i in sorted(rep)]
    mesh = Mesh(np.array(reps), ("x",))
    spec = NamedSharding(mesh, PartitionSpec("x"))

    @functools.lru_cache(maxsize=None)
    def probe_fn(perm, rows):
        def body(s):
            return jax.lax.ppermute(s, "x", perm=list(perm))
        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=PartitionSpec("x", None),
            out_specs=PartitionSpec("x", None), check_vma=False,
        ))

    def timed(perm, mb):
        rows = max(1, int(mb * 1024 * 1024 // (4 * 128)))
        x = jax.device_put(
            jnp.zeros((p * rows, 128), jnp.float32), spec
        )
        f = probe_fn(perm, rows)
        jax.block_until_ready(f(x))  # compile + warm
        ts = []
        for _ in range(trials):
            t0 = time.perf_counter()
            jax.block_until_ready(f(x))
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return ts[len(ts) // 2] * 1e3  # ms

    alpha = np.zeros((p, p))
    beta = np.zeros((p, p))
    small, large = sizes_mb[0], sizes_mb[-1]
    if p <= max_pairwise:
        pairs = [(i, j) for i in range(p) for j in range(p) if i != j]
        for i, j in pairs:
            t_s = timed(((i, j),), small)
            t_l = timed(((i, j),), large)
            b = max((t_l - t_s) / (large - small), 0.0)
            alpha[i, j] = max(t_s - b * small, 0.0)
            beta[i, j] = b
    else:
        for k in range(1, p):
            perm = tuple((i, (i + k) % p) for i in range(p))
            t_s = timed(perm, small)
            t_l = timed(perm, large)
            b = max((t_l - t_s) / (large - small), 0.0)
            a = max(t_s - b * small, 0.0)
            for i in range(p):
                alpha[i, (i + k) % p] = a
                beta[i, (i + k) % p] = b
    return alpha, beta


def merge_dcn_costs(adj: Adjacency, dcn, devices=None) -> Adjacency:
    """Replace the analytic cross-process entries of ``adj`` with measured
    (alpha[P,P], beta[P,P]) DCN costs from :func:`probe_dcn_costs`."""
    if dcn is None:
        return adj
    d_alpha, d_beta = dcn
    devices = list(devices if devices is not None else jax.devices())
    alpha, beta = adj.alpha.copy(), adj.beta.copy()
    for i, di in enumerate(devices):
        for j, dj in enumerate(devices):
            pi, pj = di.process_index, dj.process_index
            if pi != pj:
                alpha[i, j] = d_alpha[pi, pj]
                beta[i, j] = d_beta[pi, pj]
    return Adjacency(alpha, beta)


def device_memory_gb(device) -> float:
    """Usable memory for one device, measured live when the runtime exposes
    it (the reference's ``estimateMemory`` sizes capacity from actually-free
    VRAM, ``bootstrap.cuh:98-111``), else a per-generation table.
    ``FLASHMOE_MEMORY_GB`` overrides (tests / chaos drills)."""
    import os

    override = os.environ.get("FLASHMOE_MEMORY_GB")
    if override:
        return float(override)
    try:
        stats = device.memory_stats()
        if stats:
            limit = stats.get("bytes_limit") or stats.get(
                "bytes_reservable_limit")
            used = stats.get("bytes_in_use", 0)
            if limit:
                return (limit - used) / 1e9
    except Exception:
        pass
    return {
        "v4": 32.0, "v5e": 16.0, "v5p": 95.0, "v6e": 32.0,
    }.get(tpu_generation(device), 16.0)


def measured_worker_attrs(devices=None, cfg=None,
                          probe: bool = False) -> list[WorkerAttr]:
    """Per-device throughput/memory attributes.

    With ``probe=True`` the expert-FFN throughput is *measured* on this
    process's backend (:mod:`flashmoe_tpu.runtime.throughput`, the
    reference's ``mT`` probe) and, in multi-process jobs, exchanged so
    every process sees every worker's real rate — heterogeneous workers
    then shift the Decider's rate-proportional expert assignment.
    ``FLASHMOE_THROUGHPUT_SCALE`` scales this process's measured rate
    (fault/skew injection for tests, like the reference's synthetic
    ``testDecider`` workers).
    """
    import os

    devices = list(devices if devices is not None else jax.devices())
    throughput = 1.0
    if probe:
        from flashmoe_tpu.config import MoEConfig
        from flashmoe_tpu.runtime.throughput import measure_expert_throughput

        pcfg = cfg if cfg is not None else MoEConfig()
        if devices[0].platform == "cpu":
            # the virtual backend only needs *relative* rates; shrink the
            # synthetic workload so bootstrap stays fast
            pcfg = pcfg.replace(
                hidden_size=min(512, pcfg.hidden_size),
                intermediate_size=min(512, pcfg.intermediate_size),
            )
        throughput = measure_expert_throughput(
            pcfg, experts=min(4, pcfg.num_experts), rows_per_expert=64,
        )
    throughput *= float(os.environ.get("FLASHMOE_THROUGHPUT_SCALE", "1.0"))

    per_process = {jax.process_index(): throughput}
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        rates = multihost_utils.process_allgather(
            np.array([throughput], np.float64)
        ).reshape(-1)
        per_process = {i: float(r) for i, r in enumerate(rates)}

    return [
        WorkerAttr(
            throughput=per_process.get(d.process_index, throughput),
            memory_gb=device_memory_gb(d),
        )
        for d in devices
    ]
