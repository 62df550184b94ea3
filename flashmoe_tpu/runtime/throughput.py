"""Per-device expert-FFN throughput probe.

The reference measures each GPU's expert throughput at bootstrap with a
synthetic workload: 64 warmup + 16 timed runs of the standalone ``expert``
kernel, median latency -> ``WorkerAttribute.throughput`` in experts/ms
(``csrc/include/flashmoe/throughput.cuh:51-170``), feeding the Decider's
rate-proportional expert assignment.

The TPU version times the same synthetic grouped FFN through the real
kernel path.  Iterations are chained inside one jit and two chain lengths
differenced, which takes dispatch and readback out of the reading
(never yet run on real devices: ROADMAP S1 replaces it with a host
clock around ``block_until_ready``).
Results are
cached per (device-kind, config shape) since homogeneous slices need one
probe, not one per chip — except :func:`device_rates`' per-DEVICE probes,
which exist precisely to spot the chip that stopped matching its kind
(the self-healing controller's slow-device trigger re-probes through it:
ISSUE 12 satellite / ROADMAP item 3 follow-up).
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from flashmoe_tpu.config import MoEConfig
from flashmoe_tpu.models.reference import init_moe_params
from flashmoe_tpu.ops import expert as exp

_cache: dict = {}


def _measure(cfg: MoEConfig, e: int, rows_per_expert: int, chain: int,
             trials: int) -> float:
    """One uncached probe on whatever device jax currently dispatches
    to (callers pin with ``jax.default_device``)."""
    pcfg = cfg.replace(num_experts=e, num_shared_experts=0)
    params = init_moe_params(jax.random.PRNGKey(0), pcfg)
    params = jax.tree_util.tree_map(lambda p: p.astype(cfg.dtype), params)
    xs = jax.random.normal(
        jax.random.PRNGKey(1), (e, rows_per_expert, cfg.hidden_size),
        cfg.dtype,
    )

    def chained(n):
        def run(p, xs):
            def body(xs, _):
                if jax.default_backend() == "tpu":
                    y = exp.capacity_buffer_ffn_pallas(xs, p, pcfg)
                else:
                    y = exp.expert_ffn_dense(xs, p, pcfg)
                return y.astype(xs.dtype), None
            xs, _ = jax.lax.scan(body, xs, None, length=n)
            return xs.astype(jnp.float32).sum()
        return jax.jit(run)

    def med(f):
        float(f(params, xs))  # compile+warm
        ts = []
        for _ in range(trials):
            t0 = time.perf_counter()
            float(f(params, xs))
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return ts[len(ts) // 2]

    t1, tn = med(chained(1)), med(chained(chain))
    # under host noise the long chain can read no slower than the short
    # one; the difference is then no reading at all (it used to clamp to
    # 1e-9 s, an infinite rate that took every expert) — fall back to the
    # long chain's own mean
    per_iter = (tn - t1) / (chain - 1) if tn > t1 else tn / chain
    return e / (per_iter * 1e3)  # experts per ms


def measure_expert_throughput(cfg: MoEConfig, *, experts: int | None = None,
                              rows_per_expert: int = 256,
                              chain: int = 8, trials: int = 3,
                              device=None) -> float:
    """Median throughput in experts/ms for this device kind.

    ``device``: pin the probe to ONE device (``jax.default_device``)
    and cache per device id instead of per kind — the form
    :func:`device_rates` uses to spot a degraded chip inside an
    otherwise homogeneous slice (a kind-keyed cache would return the
    first chip's number for every peer)."""
    e = experts or min(cfg.num_experts, 8)
    dev0 = device if device is not None else jax.devices()[0]
    key = (("dev", dev0.id) if device is not None else dev0.device_kind,
           e, rows_per_expert, cfg.hidden_size, cfg.intermediate_size,
           str(cfg.dtype))
    if key in _cache:
        return _cache[key]
    if device is not None:
        with jax.default_device(device):
            t = _measure(cfg, e, rows_per_expert, chain, trials)
    else:
        t = _measure(cfg, e, rows_per_expert, chain, trials)
    _cache[key] = t
    return t


def device_rates(cfg: MoEConfig, n_devices: int, *,
                 rows_per_expert: int = 64, chain: int = 4,
                 trials: int = 2, fresh: bool = False):
    """Live per-device throughput vector ``[n_devices]`` (experts/ms) —
    the self-healing controller's DEFAULT ``rates_fn`` on the
    slow-device trigger (ROADMAP item 3 follow-up: production
    re-placement re-probes instead of relying on drill-injected rates).
    Probes each local device individually (per-device cache keys);
    devices beyond the local count reuse the local readings in order
    (the homogeneous-host assumption every multi-host probe makes).

    Deliberately light defaults (64 rows, 4-chain, 2 trials): the probe
    runs at a rare step-boundary decision, not in the step loop, and
    relative rates are what the Decider consumes.  ``fresh=True`` drops
    the per-device cache entries first — a RE-probe must see today's
    silicon, not bootstrap's.

    Chaos seam: an armed ``probe_rates`` injection point
    (:mod:`flashmoe_tpu.chaos.inject`) supplies the reading a degraded
    chip WOULD produce, without touching the backend — how the
    ``slow_device`` drill exercises this exact production path (the
    host-sleep stall it injects is invisible to a real CPU probe, but a
    real TPU slow chip is exactly what the per-device probe exists to
    see)."""
    import numpy as np

    from flashmoe_tpu.chaos import inject

    if inject.is_armed("probe_rates"):
        armed = np.asarray(
            inject.spec("probe_rates").get("rates", ()), dtype=np.float64)
        if armed.size:
            out = np.ones(n_devices, dtype=np.float64) * armed[-1]
            out[:min(n_devices, armed.size)] = armed[:n_devices]
            return out
    devs = jax.local_devices()
    distinct = devs[:min(n_devices, len(devs))] or devs[:1]
    if fresh:
        # drop each DISTINCT device's cache entry once, before any
        # probing — popping inside the rank loop would re-measure the
        # same physical device once per logical rank mapped onto it
        # (and let timing noise hand the Decider different rates for
        # the same chip)
        for dev in distinct:
            _cache.pop((("dev", dev.id), min(cfg.num_experts, 8),
                        rows_per_expert, cfg.hidden_size,
                        cfg.intermediate_size, str(cfg.dtype)), None)
    readings = [
        measure_expert_throughput(
            cfg, rows_per_expert=rows_per_expert, chain=chain,
            trials=trials, device=dev)
        for dev in distinct
    ]
    return np.asarray(
        [readings[i % len(readings)] for i in range(n_devices)],
        dtype=np.float64)
