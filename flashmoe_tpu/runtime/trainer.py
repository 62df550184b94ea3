"""Training loop: sharded optax train step over the device mesh.

The reference models DP training costs in its Decider (gradient-buffer
sizing ``types.cuh:491-493``, ring-allreduce pricing
``os/decider/functions.cuh:28-32``) but executes no training.  This module
is the executed version: a jit-compiled train step whose gradient averaging
over dp *is* the allreduce the Decider prices, inserted by XLA from the
sharding layout (params replicated over dp -> psum of grads over dp).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flashmoe_tpu.config import MoEConfig
from flashmoe_tpu.models import transformer
from flashmoe_tpu.parallel.mesh import transformer_param_specs
from flashmoe_tpu.utils.telemetry import trace_span


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jax.Array
    # tier-1 gradient-guard EMA state (GuardState) when the step was
    # built with a GradGuardConfig; None otherwise — a None leaf is an
    # empty pytree node, so guard-free states keep the pre-guard tree
    # structure (checkpoints, shardings, donation all unchanged)
    guard: Any = None


class GuardState(NamedTuple):
    """Running statistics for the tier-1 gradient anomaly guard."""

    norm_ema: jax.Array  # EMA of the (finite, accepted) grad norms
    seen: jax.Array      # accepted steps feeding the EMA (warmup gate)


@dataclasses.dataclass(frozen=True)
class GradGuardConfig:
    """Tier-1 fault tolerance: per-step gradient anomaly guard.

    A non-finite gradient or a grad-norm spike costs ONE skipped
    optimizer update (params/opt-state/EMA carried through a
    ``jnp.where`` select inside the compiled step) instead of a
    checkpoint rewind — the middle rung between tier-0 expert masking
    and tier-2 restore-and-retry (docs/RESILIENCE.md).

    ``spike_factor``: skip when grad_norm > spike_factor * EMA (only
    once ``warmup_steps`` accepted norms have seeded the EMA).
    ``ema_decay``: EMA decay per accepted step; skipped steps do not
    contaminate the EMA.
    """

    skip_nonfinite: bool = True
    spike_factor: float = 10.0
    ema_decay: float = 0.99
    warmup_steps: int = 10


def init_guard_state() -> GuardState:
    return GuardState(jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32))


def make_optimizer(cfg: MoEConfig, lr: float = 3e-4,
                   weight_decay: float = 0.1,
                   warmup_steps: int = 100,
                   total_steps: int = 10000) -> optax.GradientTransformation:
    sched = optax.warmup_cosine_decay_schedule(
        0.0, lr, warmup_steps, max(total_steps, warmup_steps + 1)
    )
    return optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(sched, b1=0.9, b2=0.95, weight_decay=weight_decay),
    )


def init_state(key, cfg: MoEConfig, optimizer,
               guard: GradGuardConfig | None = None) -> TrainState:
    params = transformer.init_params(key, cfg)
    return TrainState(params, optimizer.init(params),
                      jnp.zeros((), jnp.int32),
                      init_guard_state() if guard is not None else None)


def state_shardings(state: TrainState, cfg: MoEConfig, mesh: Mesh):
    """NamedShardings for the train state: params per the transformer
    specs, optimizer moments following their parameters, step replicated."""
    pspecs = transformer_param_specs(cfg)

    def to_sharding(spec):
        return NamedSharding(mesh, spec)

    param_sh = jax.tree_util.tree_map(
        to_sharding, pspecs,
        is_leaf=lambda x: isinstance(x, P),
    )

    # Optimizer moments mirror the param tree (optax states embed it as a
    # subtree), so match by KEY PATH, not by array shape: a moment leaf
    # whose trailing path equals a param's path (and shape agrees) gets
    # that param's sharding; everything else (counts, scalars) replicates.
    # Shape-only matching silently aliases two same-shaped params with
    # different shardings (e.g. an ep-sharded and a replicated tensor).
    flat_sh = jax.tree_util.tree_flatten_with_path(
        param_sh, is_leaf=lambda x: isinstance(x, NamedSharding)
    )[0]
    flat_p = jax.tree_util.tree_flatten_with_path(state.params)[0]
    by_path = {
        tuple(str(k) for k in path): (leaf.shape, sh)
        for (path, leaf), (_, sh) in zip(flat_p, flat_sh)
    }

    def match(path, leaf):
        key = tuple(str(k) for k in path)
        for start in range(len(key)):
            hit = by_path.get(key[start:])
            if hit is not None and getattr(leaf, "shape", None) == hit[0]:
                return hit[1]
        return NamedSharding(mesh, P())

    opt_sh = jax.tree_util.tree_map_with_path(match, state.opt_state)
    guard_sh = jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P()), state.guard)
    return TrainState(param_sh, opt_sh, NamedSharding(mesh, P()), guard_sh)


def make_train_step(cfg: MoEConfig, mesh: Mesh, optimizer,
                    use_pallas: bool | None = None,
                    guard: GradGuardConfig | None = None) -> Callable:
    """Build the jitted, mesh-sharded train step.

    Returns step(state, batch) -> (state, metrics).  Batch tokens shard
    over dp; XLA inserts the dp gradient allreduce from the sharding
    layout.

    ``guard`` arms the tier-1 gradient anomaly guard: the state must
    then carry a :class:`GuardState` (``init_state(..., guard=guard)``),
    and the metrics gain ``grad_ok`` (1.0 = update applied, 0.0 = update
    skipped in-graph) plus ``grad_norm_ema``.  ``guard=None`` builds the
    exact pre-guard step — bit-identical training.
    """
    # Training entry point implies is_training: without this, a hand-built
    # config silently differentiates through the inference-selected FFN path
    # (extra forward recompute in the VJP) instead of the residual-saving
    # training kernels (round-2 advisor finding).
    if not cfg.is_training:
        cfg = cfg.replace(is_training=True)

    def step_fn(state: TrainState, batch):
        with trace_span("train.forward_backward"):
            (loss, metrics), grads = jax.value_and_grad(
                transformer.loss_fn, has_aux=True
            )(state.params, batch, cfg, mesh, use_pallas)
        with trace_span("train.optimizer"):
            return _apply(state, loss, metrics, grads)

    def _apply(state: TrainState, loss, metrics, grads):
        from flashmoe_tpu.chaos import inject as chaos_inject

        if (chaos_inject.is_armed("nan_grad")
                or chaos_inject.is_armed("grad_spike")):
            grads = chaos_inject.poison_grads(grads, state.step)
        gnorm = optax.global_norm(grads)
        if guard is None:
            updates, opt_state = optimizer.update(
                grads, state.opt_state, state.params
            )
            params = optax.apply_updates(state.params, updates)
            metrics = dict(metrics, loss=loss, grad_norm=gnorm)
            return TrainState(params, opt_state, state.step + 1,
                              state.guard), metrics

        # ---- tier-1 guard: decide, then select — all in-graph ----
        gs: GuardState = state.guard
        finite = jnp.isfinite(gnorm)
        warm = gs.seen >= guard.warmup_steps
        spike = warm & (gnorm > guard.spike_factor
                        * jnp.maximum(gs.norm_ema, 1e-30))
        ok = (finite if guard.skip_nonfinite else jnp.bool_(True)) & ~spike
        # a non-finite gradient must never flow into the optimizer even
        # when its update is discarded: moment EMAs computed from NaN
        # grads would be selected away here, but XLA may still fuse the
        # NaN into reused subexpressions; feed zeros on skipped steps
        safe_grads = jax.tree_util.tree_map(
            lambda g: jnp.where(ok, g, jnp.zeros((), g.dtype))
            if jnp.issubdtype(jnp.asarray(g).dtype, jnp.inexact) else g,
            grads,
        )
        updates, new_opt = optimizer.update(
            safe_grads, state.opt_state, state.params
        )
        new_params = optax.apply_updates(state.params, updates)
        sel = functools.partial(
            jax.tree_util.tree_map, lambda n, o: jnp.where(ok, n, o))
        params = sel(new_params, state.params)
        opt_state = sel(new_opt, state.opt_state)
        decay = jnp.float32(guard.ema_decay)
        seeded = gs.seen > 0
        ema_next = jnp.where(seeded,
                             decay * gs.norm_ema + (1 - decay) * gnorm,
                             gnorm.astype(jnp.float32))
        new_guard = GuardState(
            jnp.where(ok, ema_next, gs.norm_ema),
            gs.seen + ok.astype(gs.seen.dtype),
        )
        metrics = dict(metrics, loss=loss, grad_norm=gnorm,
                       grad_ok=ok.astype(jnp.float32),
                       grad_norm_ema=new_guard.norm_ema)
        return TrainState(params, opt_state, state.step + 1,
                          new_guard), metrics

    batch_sharding = {"tokens": NamedSharding(mesh, P("dp", None))}
    return jax.jit(
        step_fn,
        in_shardings=(None, batch_sharding),
        donate_argnums=(0,),
    )


def host_metrics(step_metrics: dict, moe_layers=None) -> dict:
    """Device step metrics -> one JSON-ready dict: scalars to floats,
    per-layer MoEStats (``moe_stats``, present when cfg.collect_stats)
    to the flight-recorder ``moe`` schema that
    ``python -m flashmoe_tpu.observe`` consumes.

    ``moe_layers``: the transformer layer index per stats entry
    (``cfg.moe_layer_indices`` — forward only collects stats for MoE
    layers, so position i of the tuple is that sequence's i-th layer);
    None falls back to the positional index."""
    from flashmoe_tpu.ops.stats import stats_to_host

    out: dict = {}
    for k, v in step_metrics.items():
        if k == "moe_stats":
            out["moe"] = [
                dict(layer=(moe_layers[i] if moe_layers is not None
                            and i < len(moe_layers) else i),
                     **stats_to_host(st))
                for i, st in enumerate(v)
            ]
        else:
            out[k] = float(v)
    return out


def train(cfg: MoEConfig, mesh: Mesh, data_iter, num_steps: int,
          key=None, log_every: int = 10, state: TrainState | None = None,
          use_pallas: bool | None = None,
          recorder: "FlightRecorder | None" = None,
          flight_path: str | None = None,
          flight_flush_every: int = 0,
          guard: GradGuardConfig | None = None,
          slo=None, controller=None, telemetry_port: int | None = None):
    """Simple host training loop (see runtime.worker for the CLI).

    ``recorder``: a :class:`flashmoe_tpu.utils.telemetry.FlightRecorder`
    capturing EVERY step (ring-bounded), independent of ``log_every``;
    with ``flight_path`` one is created if needed and its JSONL is
    exported there when the loop ends — the artifact
    ``python -m flashmoe_tpu.observe`` summarizes.  Set
    ``cfg.collect_stats`` to include the in-graph MoE stats per record.

    ``flight_flush_every``: > 0 flushes the recorder to ``flight_path``
    every that many steps via the OFFSET-AWARE append mode
    (:meth:`FlightRecorder.export_jsonl` with ``start``), so records
    that rotate out of the bounded ring between flushes are already on
    disk — the legacy end-of-run snapshot silently discarded them.

    ``slo``: a :class:`flashmoe_tpu.profiler.slo.SLOConfig` (or a
    prebuilt :class:`~flashmoe_tpu.profiler.slo.SLOWatchdog`): every
    step's wall time is judged against the step budget (``slo.breach`` /
    ``slo.recovered`` decisions, consecutive-breach escalation into
    planner path demotion).  Arming an SLO times every step.

    ``controller``: a :class:`flashmoe_tpu.runtime.controller.
    RuntimeController` closes the telemetry loop on this plain host
    loop too — the loop owns cfg/mesh/optimizer, so morphs rebuild the
    jitted step in place and re-placements permute the live state
    (checkpoint-free runs get no durable plan; production jobs should
    prefer ``resilient_train``/``supervise``, which persist controller
    actions in checkpoint manifests).  Arming a controller times every
    step.

    ``telemetry_port``: arm the live scrape server
    (telemetry_plane/server.py) for the loop's duration — ``/metrics``
    (the global registry), ``/healthz`` (step progress + SLO episode +
    controller budgets), ``/vars`` (the shape being trained).  Default
    ``None`` = no thread, byte-identical behavior.

    When a profiler timeline is armed (:func:`flashmoe_tpu.profiler.
    spans.profiling`), the loop's host work is recorded as
    ``train.data_pull`` / ``train.step`` sections.
    """
    import time

    from flashmoe_tpu.profiler import spans as prof
    from flashmoe_tpu.utils.telemetry import (
        FlightRecorder, compile_totals, metrics as tm, watch_compiles,
    )

    key = key if key is not None else jax.random.PRNGKey(0)
    optimizer = make_optimizer(cfg, total_steps=num_steps)
    if state is None:
        state = init_state(key, cfg, optimizer, guard=guard)
        sh = state_shardings(state, cfg, mesh)
        state = jax.device_put(state, sh)
    step = make_train_step(cfg, mesh, optimizer, use_pallas=use_pallas,
                           guard=guard)
    if flight_path is not None and recorder is None:
        recorder = FlightRecorder()
    watchdog = _as_watchdog(slo)
    watch_compiles()
    history = []
    flushed = 0  # offset-aware export cursor (absolute record index)
    progress = {"step": 0}
    server = None
    if telemetry_port is not None:
        from flashmoe_tpu.runtime.telemetry_hooks import train_server

        server = train_server(telemetry_port, cfg, mesh,
                              num_steps=num_steps, progress=progress,
                              watchdog=watchdog, controller=controller)
    try:
        for i in range(num_steps):
            progress["step"] = i
            with prof.section("train.data_pull", step=i):
                batch = next(data_iter)
            log_step = i % log_every == 0 or i == num_steps - 1
            tl = prof.active()
            if recorder is not None or log_step or watchdog is not None \
                    or tl is not None or controller is not None:
                # block before reading the clock: jit dispatch is async, so
                # an unsynchronized timer would record ~0 host-dispatch ms.
                # With a recorder every step is timed exactly; log-only runs
                # time the logged step plus whatever backlog drained with it.
                t0 = time.perf_counter()
                compiles0, compile_s0 = compile_totals()
                if tl is not None:
                    # an armed timeline gets per-step records; any phases
                    # measured inside (eager fenced runs — under jit the
                    # phase dict stays empty) feed the SLO phase budgets
                    tl.begin_step(i)
                with prof.section("train.step", step=i):
                    state, metrics = step(state, batch)
                    jax.block_until_ready(metrics)
                phases = tl.end_step()["phases"] if tl is not None else None
                step_ms = (time.perf_counter() - t0) * 1e3
                # bounded: the histogram aggregates, no per-step list grows
                tm.histogram("trainer.step_ms", step_ms)
                if watchdog is not None:
                    watchdog.observe_step(i, step_ms, phases=phases)
                if controller is not None:
                    controller.observe_step(i, step_ms, metrics)
                    act = controller.maybe_act(i + 1)
                    if act is not None:
                        # self-healing action at the step boundary: permute
                        # the live state (re-placement) and/or re-jit onto
                        # the controller's accumulated config overrides
                        state = controller.apply_action(act, state)
                        if act.needs_rebuild:
                            step = make_train_step(
                                cfg.replace(**controller.cfg_overrides),
                                mesh, optimizer, use_pallas=use_pallas,
                                guard=guard)
                if recorder is not None or log_step:
                    # the full device->host metrics pull (per-layer MoEStats
                    # when collect_stats is on) only happens when someone
                    # consumes it; a watchdog alone needs just step_ms
                    rec = host_metrics(metrics,
                                       moe_layers=cfg.moe_layer_indices)
                    rec["step_ms"] = step_ms
                    # which step compiled (a new shape, a rebuilt step)
                    compiles1, compile_s1 = compile_totals()
                    rec["compiles"] = int(compiles1 - compiles0)
                    rec["compile_ms"] = (compile_s1 - compile_s0) * 1e3
                    if rec.get("grad_ok", 1.0) == 0.0:
                        # tier-1 guard fired: the skipped update is a
                        # structured decision so a postmortem can answer
                        # "which steps were dropped and why" without
                        # replaying the run
                        tm.decision("trainer.grad_skip", step=i,
                                    grad_norm=rec.get("grad_norm"),
                                    grad_norm_ema=rec.get("grad_norm_ema"))
                    if recorder is not None:
                        recorder.record(step=i, **rec)
                        if flight_path is not None and flight_flush_every > 0 \
                                and (i + 1) % flight_flush_every == 0:
                            flushed = recorder.export_jsonl(flight_path,
                                                            start=flushed)
                    if log_step:
                        history.append(rec)
            else:
                with prof.section("train.step", step=i):
                    state, metrics = step(state, batch)
        if flight_path is not None and recorder is not None:
            if flight_flush_every > 0:
                recorder.export_jsonl(flight_path, start=flushed)
            else:
                recorder.export_jsonl(flight_path)
        return state, history
    finally:
        if server is not None:
            server.stop()


def _as_watchdog(slo):
    """Accept an SLOConfig, a prebuilt SLOWatchdog, or None."""
    if slo is None:
        return None
    from flashmoe_tpu.profiler.slo import SLOConfig, SLOWatchdog

    if isinstance(slo, SLOConfig):
        return SLOWatchdog(slo)
    return slo
