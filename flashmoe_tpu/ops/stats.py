"""In-graph MoE routing statistics — the flight recorder's data plane.

The reference reads ``%globaltimer`` inside its kernels and wraps every
host phase in NVTX ranges (``csrc/include/flashmoe/telemetry.cuh``)
because distributed-MoE performance lives or dies on runtime state that
is invisible from outside: expert load imbalance, dropped tokens, and
capacity waste.  This module computes those quantities *inside the
compiled graph*, from values the layers already materialize (router
counts, combine weights, the capacity constant) — no extra HBM traffic
beyond a few scalar reductions, jit- and vmap-safe, and entirely absent
from the graph unless ``MoEConfig.collect_stats`` is set.

Consumers: ``ops/moe.py`` / ``parallel/ep.py`` / ``parallel/fused.py`` /
``parallel/ragged_ep.py`` attach a :class:`MoEStats` to their
``MoEOutput``; ``models/transformer.py`` threads per-layer stats into
the loss metrics; ``runtime/trainer.py`` lands them in the flight
recorder (:mod:`flashmoe_tpu.utils.telemetry`), which
``python -m flashmoe_tpu.observe`` summarizes offline.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from flashmoe_tpu.config import MoEConfig


class MoEStats(NamedTuple):
    """One MoE layer's routing health, all float32 so every field psums /
    pmeans uniformly across expert-parallel ranks.

    expert_load:          [E] pre-drop (token, k) selections per expert.
    dropped_fraction:     [] fraction of routed assignments dropped at
                          the capacity clamp (0 on dropless paths).
    capacity_utilization: [] kept rows / (E * capacity) buffer slots
                          (1.0 on dropless paths — no fixed buffer).
    imbalance:            [] max over experts / mean — 1.0 is perfectly
                          balanced, E is total collapse onto one expert.
    router_entropy:       [] entropy (nats) of the router's expert
                          distribution: the mean softmax probabilities
                          when the gate reports them, else the empirical
                          selection distribution.  ln(E) = uniform.
    topk_confidence:      [] mean normalized weight of each token's
                          top-1 expert (1.0 = the top expert takes all).
    masked_experts:       [] tier-0 degradation (ops/health.py): sick
                          (non-finite-output) experts masked this step —
                          per-rank contributions summed across ep ranks,
                          0.0 unless ``degrade_unhealthy_experts`` fired.
    masked_fraction:      [] fraction of (token, k) assignments whose
                          expert contribution was zeroed by the tier-0
                          mask (0.0 when every expert is healthy).
    wire_rtq_error:       [] round-trip quantization error of the EP
                          wire-dtype compression (ops/wire.py): mean
                          relative L1 error of encode+decode over the
                          dispatched payload, pmeaned across ranks.
                          0.0 when ``wire_dtype`` is off (or the layer
                          has no exchange).
    wire_rtq_error_dcn:   [] same proxy for the CROSS-SLICE hop's own
                          wire (``MoEConfig.wire_dtype_dcn``) on a
                          two-stage multi-slice exchange: how lossy the
                          fp8-across-DCN hop is on live traffic,
                          separately from the in-slice hop.  0.0 when
                          the DCN override is off or the exchange is
                          flat.
    quant_error:          [] round-trip error proxy of the quantized
                          expert weight store (flashmoe_tpu/quant/,
                          ``MoEConfig.expert_quant``): max over this
                          layer's FFN weight matrices of the store's
                          relative L1 round-trip error.  Real loss on
                          fake-quant runs; ~0 on pre-quantized states
                          (the baked loss lives in the state's quant
                          metadata).  0.0 when expert_quant is off.
    """

    expert_load: jnp.ndarray
    dropped_fraction: jnp.ndarray
    capacity_utilization: jnp.ndarray
    imbalance: jnp.ndarray
    router_entropy: jnp.ndarray
    topk_confidence: jnp.ndarray
    masked_experts: jnp.ndarray
    masked_fraction: jnp.ndarray
    wire_rtq_error: jnp.ndarray
    wire_rtq_error_dcn: jnp.ndarray
    quant_error: jnp.ndarray


def load_imbalance(expert_load) -> jnp.ndarray:
    """max/mean load factor of an [E] load vector (f32 scalar)."""
    load = expert_load.astype(jnp.float32)
    mean = jnp.mean(load, axis=-1)
    return jnp.max(load, axis=-1) / jnp.maximum(mean, 1e-9)


def dist_entropy(weights) -> jnp.ndarray:
    """Entropy (nats) of an unnormalized nonnegative [E] weight vector."""
    w = weights.astype(jnp.float32)
    p = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    return -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.maximum(p, 1e-30)),
                              0.0), axis=-1)


def router_entropy(probs_mean, expert_load) -> jnp.ndarray:
    """Entropy of the router's expert distribution.

    Prefers the gate's mean softmax probabilities; the inference-mode
    tiled gate reports ``probs_mean`` as zeros when its stats pass is
    skipped, in which case the empirical selection distribution stands
    in (a ``where``-select so the choice stays jit-safe)."""
    have_probs = jnp.sum(probs_mean.astype(jnp.float32), axis=-1) > 0
    return jnp.where(have_probs, dist_entropy(probs_mean),
                     dist_entropy(expert_load))


def drop_stats(expert_load, cfg: MoEConfig, capacity: int | None
               ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(dropped_fraction, capacity_utilization) of an [E] load vector
    against a per-expert ``capacity``; ``None`` = dropless path."""
    load = expert_load.astype(jnp.float32)
    total = jnp.maximum(jnp.sum(load, axis=-1), 1.0)
    if capacity is None:
        zero = jnp.zeros(total.shape, jnp.float32)
        return zero, jnp.ones(total.shape, jnp.float32)
    kept = jnp.sum(jnp.minimum(load, jnp.float32(capacity)), axis=-1)
    dropped = 1.0 - kept / total
    util = kept / jnp.float32(cfg.num_experts * capacity)
    return dropped, util


def moe_stats(router_out, cfg: MoEConfig, capacity: int | None
              ) -> MoEStats:
    """Stats for one token shard from its RouterOutput.

    ``capacity`` is the per-expert buffer size the dispatch will clamp
    against (the same constant :func:`flashmoe_tpu.ops.dispatch.make_plan`
    receives), or ``None`` on dropless paths.  Every output is a pure
    function of the router's existing outputs, so attaching stats can
    never perturb the layer's numerics.
    """
    load = router_out.expert_counts.astype(jnp.float32)
    dropped, util = drop_stats(load, cfg, capacity)
    # combine weights are sorted by the top-k extraction: slot 0 is each
    # token's strongest expert, pre-normalized over the k survivors
    conf = jnp.mean(router_out.combine_weights[..., 0].astype(jnp.float32),
                    axis=-1)
    zero = jnp.zeros(dropped.shape, jnp.float32)
    return MoEStats(
        expert_load=load,
        dropped_fraction=dropped,
        capacity_utilization=util,
        imbalance=load_imbalance(load),
        router_entropy=router_entropy(router_out.probs_mean, load),
        topk_confidence=conf,
        # tier-0 degradation counters: filled in by the layer via
        # with_degradation() after its health check runs (the check needs
        # the expert OUTPUTS, which do not exist yet at routing time)
        masked_experts=zero,
        masked_fraction=zero,
        # wire-compression error: filled in by the EP layers via
        # with_wire_error() once the dispatch payload exists (the
        # _dcn twin covers the cross-slice hop's own wire)
        wire_rtq_error=zero,
        wire_rtq_error_dcn=zero,
        # quantized-weight store error: filled in by the layers via
        # with_quant_error() when expert_quant is on
        quant_error=zero,
    )


def with_degradation(stats: MoEStats, masked_experts,
                     masked_fraction) -> MoEStats:
    """Attach tier-0 degradation counters (ops/health.py) to a stats
    tuple — a plain _replace, split out so layers read declaratively."""
    return stats._replace(
        masked_experts=jnp.asarray(masked_experts, jnp.float32),
        masked_fraction=jnp.asarray(masked_fraction, jnp.float32),
    )


def with_wire_error(stats: MoEStats, wire_rtq_error=None,
                    reduce_axes=None, *, dcn_error=None) -> MoEStats:
    """Attach the wire-compression round-trip error
    (:func:`flashmoe_tpu.ops.wire.roundtrip_error`) to a stats tuple.
    Inside a shard_map body pass ``reduce_axes`` to pmean the per-shard
    proxy across ranks (every rank holds the same token count).
    ``dcn_error`` carries the cross-slice hop's own proxy
    (``wire_rtq_error_dcn``, the ``wire_dtype_dcn`` hop); either side
    may be ``None`` to leave its field untouched."""
    import jax

    def _red(v):
        v = jnp.asarray(v, jnp.float32)
        return (jax.lax.pmean(v, reduce_axes)
                if reduce_axes is not None else v)

    fields = {}
    if wire_rtq_error is not None:
        fields["wire_rtq_error"] = _red(wire_rtq_error)
    if dcn_error is not None:
        fields["wire_rtq_error_dcn"] = _red(dcn_error)
    return stats._replace(**fields) if fields else stats


def with_quant_error(stats: MoEStats, quant_error,
                     reduce_axes=None) -> MoEStats:
    """Attach the quantized-weight round-trip error proxy
    (:func:`flashmoe_tpu.quant.state.weight_quant_error`) to a stats
    tuple.  Inside a shard_map body pass ``reduce_axes`` to pmean the
    per-shard proxy (each rank measures its own expert shard)."""
    import jax

    if quant_error is None:
        return stats
    v = jnp.asarray(quant_error, jnp.float32)
    if reduce_axes is not None:
        v = jax.lax.pmean(v, reduce_axes)
    return stats._replace(quant_error=v)


def reduce_stats(local: MoEStats, probs_mean, reduce_axes) -> MoEStats:
    """Cross-rank reduction of per-shard stats inside a shard_map body.

    The load histogram psums; ratio scalars pmean (every rank holds the
    same token count, so the mean of per-shard fractions is the exact
    global fraction); imbalance and entropy are recomputed from the
    GLOBAL load so a skew concentrated on one rank is never averaged
    away.  Only called when ``cfg.collect_stats`` — the stats-off graph
    contains none of these collectives."""
    import jax

    g_load = jax.lax.psum(local.expert_load, reduce_axes)
    g_probs = jax.lax.pmean(probs_mean.astype(jnp.float32), reduce_axes)
    return MoEStats(
        expert_load=g_load,
        dropped_fraction=jax.lax.pmean(local.dropped_fraction, reduce_axes),
        capacity_utilization=jax.lax.pmean(local.capacity_utilization,
                                           reduce_axes),
        imbalance=load_imbalance(g_load),
        router_entropy=router_entropy(g_probs, g_load),
        topk_confidence=jax.lax.pmean(local.topk_confidence, reduce_axes),
        # tier-0 degradation counters and the wire-error proxy pass
        # through untouched: they are zeros unless their feature flag is
        # on, and the layer reduces them itself in that case — reducing
        # constants here would add collectives to every stats-on graph
        # for nothing
        masked_experts=local.masked_experts,
        masked_fraction=local.masked_fraction,
        wire_rtq_error=local.wire_rtq_error,
        wire_rtq_error_dcn=local.wire_rtq_error_dcn,
        quant_error=local.quant_error,
    )


def stats_to_host(stats: MoEStats) -> dict:
    """One flight-recorder-ready dict (python floats/lists) per layer."""
    import jax
    import numpy as np

    # ONE bulk device->host transfer for the whole tuple — per-field
    # float() calls would each block on their own copy, inflating the
    # very step time the flight recorder is measuring
    host = jax.device_get(stats)
    return {
        "expert_load": np.asarray(host.expert_load,
                                  dtype=np.float64).tolist(),
        "dropped_fraction": float(host.dropped_fraction),
        "capacity_utilization": float(host.capacity_utilization),
        "imbalance": float(host.imbalance),
        "router_entropy": float(host.router_entropy),
        "topk_confidence": float(host.topk_confidence),
        "masked_experts": float(host.masked_experts),
        "masked_fraction": float(host.masked_fraction),
        "wire_rtq_error": float(host.wire_rtq_error),
        "wire_rtq_error_dcn": float(host.wire_rtq_error_dcn),
        "quant_error": float(host.quant_error),
    }


def speculation_summary(records) -> dict:
    """Aggregate speculative-decoding acceptance stats from flight records.

    Host-side consumer twin of ``serving.speculate.spec_stats_fields``:
    the engine folds per-slot counters into ``serve_request`` records and
    per-step ``spec_tokens``/``spec_on`` into step records; this reduces a
    recorder dump (or any iterable of such dicts) back into one summary the
    report surface (``observe.py``) can print without
    re-deriving engine internals.
    """
    drafted = 0
    accepted = 0
    requests = 0
    spec_steps = 0
    steps_on = 0
    extra = 0
    morphs = 0
    for rec in records:
        kind = rec.get("kind")
        if kind == "serve_request" and "spec_drafted" in rec:
            requests += 1
            drafted += int(rec.get("spec_drafted") or 0)
            accepted += int(rec.get("spec_accepted") or 0)
        elif kind == "serve_step" and "spec_tokens" in rec:
            if rec.get("spec_on"):
                steps_on += 1
            n = int(rec.get("spec_tokens") or 0)
            if n > 0:
                spec_steps += 1
                extra += n
        elif "controller.spec_morph" in (rec.get("decision"),
                                         rec.get("name")):
            morphs += 1
    rate = (accepted / drafted) if drafted else 0.0
    per_step = 1.0 + extra / spec_steps if spec_steps else 1.0
    return {
        "spec_requests": requests,
        "spec_drafted": drafted,
        "spec_accepted": accepted,
        "accept_rate": round(rate, 6),
        "spec_tokens_per_step": round(per_step, 6),
        "spec_steps": spec_steps,
        "steps_spec_on": steps_on,
        "spec_morphs": morphs,
    }
