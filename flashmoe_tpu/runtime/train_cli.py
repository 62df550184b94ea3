"""End-to-end training CLI.

Composes the whole framework: preset or JSON config -> runtime bootstrap
(mesh + placement) -> native data loader -> sharded optax train step ->
resilient loop with periodic orbax checkpoints and metrics JSONL.

Usage:
  python -m flashmoe_tpu.runtime.train_cli --preset mixtral-8x7b \
      --data tokens.bin --steps 1000 --batch 8 --checkpoint-dir ckpt/
  python -m flashmoe_tpu.runtime.train_cli --config cfg.json --synthetic

``--synthetic`` trains on random tokens (the reference worker's random-
tensor mode, ``flashmoe/worker.py:56-58``) for smoke runs without data.
"""

from __future__ import annotations

import argparse
import itertools
import json
import sys
import time

import jax
import jax.numpy as jnp

from flashmoe_tpu.config import MoEConfig
from flashmoe_tpu.models.presets import PRESETS
from flashmoe_tpu.runtime import bootstrap
from flashmoe_tpu.runtime.data import TokenLoader
from flashmoe_tpu.runtime.resilient import (
    ResilienceConfig, resilient_train, scalar_metrics,
)
from flashmoe_tpu.runtime.trainer import (
    GradGuardConfig, init_state, make_optimizer, make_train_step,
    state_shardings,
)
from flashmoe_tpu.utils.telemetry import Metrics


def _synthetic_batches(cfg: MoEConfig, batch: int):
    for i in itertools.count():
        yield {"tokens": jax.random.randint(
            jax.random.PRNGKey(i), (batch, cfg.sequence_len + 1), 0,
            cfg.vocab_size,
        )}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    src = ap.add_mutually_exclusive_group()
    src.add_argument("--preset", choices=sorted(PRESETS))
    src.add_argument("--config", help="flashmoe-style config JSON path")
    ap.add_argument("--data", help="binary int32 token file")
    ap.add_argument("--synthetic", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=100)
    ap.add_argument("--async-save", action="store_true",
                    help="hand checkpoint serialization to the background "
                         "writer; the step loop pays only the host "
                         "snapshot (docs/RESILIENCE.md)")
    ap.add_argument("--grace-s", type=float, default=30.0,
                    help="preemption grace window: SIGTERM/SIGUSR1 drain "
                         "a final checkpoint + data-loader cursor inside "
                         "this budget instead of dying mid-write")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-jsonl", default=None)
    ap.add_argument("--telemetry-port", type=int, default=None,
                    metavar="PORT",
                    help="serve live /metrics, /healthz and /vars on "
                         "this port for the run's duration (0 = "
                         "ephemeral; default off = no thread, "
                         "byte-identical training)")
    ap.add_argument("--grad-guard", action="store_true",
                    help="tier-1 gradient anomaly guard: skip non-finite/"
                         "spiking updates in-graph (docs/RESILIENCE.md)")
    ap.add_argument("--grad-spike-factor", type=float, default=10.0)
    ap.add_argument("--num-layers", type=int, default=None,
                    help="override (e.g. shrink a preset for a smoke run)")
    ap.add_argument("--set", action="append", default=[],
                    metavar="FIELD=VALUE",
                    help="override any MoEConfig field (repeatable), e.g. "
                         "--set sequence_len=256 --set hidden_size=512")
    args = ap.parse_args(argv)

    if args.preset:
        cfg = PRESETS[args.preset]()
    elif args.config:
        cfg = MoEConfig.from_json(args.config)
    else:
        cfg = MoEConfig()
    overrides = {"is_training": True}
    if args.num_layers:
        overrides["num_layers"] = args.num_layers
    for kv in args.set:
        k, _, v = kv.partition("=")
        cur = getattr(cfg, k)  # raises on unknown field
        if isinstance(cur, bool):
            overrides[k] = v.lower() in ("1", "true", "yes")
        elif isinstance(cur, int):
            overrides[k] = int(v)
        elif isinstance(cur, float):
            overrides[k] = float(v)
        else:
            overrides[k] = v
    cfg = cfg.replace(**overrides)

    rt = bootstrap.initialize(cfg)
    cfg = rt.cfg
    mesh = rt.mesh
    print(f"mesh={dict(mesh.shape)} experts={cfg.num_experts} "
          f"layers={cfg.num_layers}", file=sys.stderr)

    if args.data and not args.synthetic:
        data = TokenLoader(args.data, args.batch, cfg.sequence_len)
    else:
        data = _synthetic_batches(cfg, args.batch)

    optimizer = make_optimizer(cfg, lr=args.lr, total_steps=args.steps)
    guard = (GradGuardConfig(spike_factor=args.grad_spike_factor)
             if args.grad_guard else None)
    state = init_state(jax.random.PRNGKey(0), cfg, optimizer, guard=guard)
    state = jax.device_put(state, state_shardings(state, cfg, mesh))
    step = make_train_step(cfg, mesh, optimizer, guard=guard)

    metrics = Metrics()
    if args.checkpoint_dir:
        from flashmoe_tpu.runtime.preempt import PreemptionListener

        rcfg = ResilienceConfig(
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_every=args.checkpoint_every,
            async_save=args.async_save,
        )
        # a TokenLoader's cursor rides every checkpoint manifest via
        # resilient_train (state_dict/load_state_dict), so a restarted
        # CLI run continues the exact token stream
        preempt = PreemptionListener(grace_s=args.grace_s).install()
        try:
            state, history = resilient_train(
                state, step, data, args.steps, rcfg=rcfg,
                metrics=metrics, preempt=preempt, cfg=cfg,
                telemetry_port=args.telemetry_port,
            )
        finally:
            preempt.uninstall()
        if preempt.requested:
            print(f"preempted: drained at step {int(state.step)} "
                  f"(checkpoint + loader state in "
                  f"{args.checkpoint_dir}); re-run to resume",
                  file=sys.stderr)
    else:
        server = None
        if args.telemetry_port is not None:
            from flashmoe_tpu.runtime.telemetry_hooks import train_server

            progress = {"step": 0}
            server = train_server(args.telemetry_port, cfg, mesh,
                                  num_steps=args.steps,
                                  progress=progress,
                                  metrics_obj=metrics)
        history = []
        # a step is timed from the completion of the one before to its
        # own, with the next one already dispatched: the call itself
        # returns at once (the first time holds the compile)
        ahead = None
        t_done = time.perf_counter()

        def completed(waited_for):
            nonlocal t_done
            jax.block_until_ready(waited_for)
            now = time.perf_counter()
            metrics.times["step"].append(now - t_done)
            t_done = now

        try:
            for i in range(args.steps):
                if server is not None:
                    progress["step"] = i
                state, m = step(state, next(data))
                if ahead is not None:
                    completed(ahead)
                ahead = m["loss"]
                if i % args.log_every == 0 or i == args.steps - 1:
                    # scalar-safe: array-valued metrics (per-expert
                    # stats when collect_stats is on) must not crash
                    # the logger
                    rec = scalar_metrics(m)
                    history.append(rec)
                    print(json.dumps({"step": i, **rec}),
                          file=sys.stderr)
            if ahead is not None:
                completed(ahead)
        finally:
            if server is not None:
                server.stop()

    summary = dict(metrics.summary(),
                   final_loss=history[-1].get("loss") if history else None,
                   steps=args.steps)
    if args.metrics_jsonl:
        metrics.dump_jsonl(args.metrics_jsonl, steps=args.steps)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    from flashmoe_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
