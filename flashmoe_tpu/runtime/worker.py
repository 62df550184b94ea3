"""Per-process worker entry point.

Mirrors the reference worker (``flashmoe/worker.py:11-75``): initialize the
runtime, build random inputs/weights sized from the config, run the MoE
forward (optionally a timed benchmark loop), print per-rank timing, and
finalize.

Usage:  python -m flashmoe_tpu.runtime.worker [config.json] [--bench]
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp

from flashmoe_tpu.config import MoEConfig
from flashmoe_tpu.models.reference import init_moe_params
from flashmoe_tpu.ops.moe import moe_layer
from flashmoe_tpu.parallel.ep import ep_moe_layer
from flashmoe_tpu.runtime import bootstrap


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("config", nargs="?", default=None,
                    help="path to a flashmoe-style config JSON")
    ap.add_argument("--bench", action="store_true",
                    help="timed loop (skip + trials) like forwardHostBench")
    ap.add_argument("--trials", type=int, default=32)
    ap.add_argument("--skip", type=int, default=8)
    args = ap.parse_args(argv)

    cfg = MoEConfig.from_json(args.config) if args.config else MoEConfig()
    rt = bootstrap.initialize(cfg)
    cfg = rt.cfg

    key = jax.random.PRNGKey(rt.process_id)
    params = init_moe_params(key, cfg)
    params = jax.tree_util.tree_map(lambda p: p.astype(cfg.dtype), params)
    x = jax.random.normal(
        jax.random.PRNGKey(rt.process_id + 1),
        (cfg.tokens, cfg.hidden_size), cfg.dtype,
    )

    if cfg.ep > 1 and len(jax.devices()) >= cfg.ep:
        fwd = jax.jit(
            lambda p, x: ep_moe_layer(p, x, cfg, rt.mesh).out
        )
    else:
        fwd = jax.jit(lambda p, x: moe_layer(p, x, cfg).out)

    out = fwd(params, x)
    jax.block_until_ready(out)

    if args.bench:
        for _ in range(args.skip):
            out = fwd(params, x)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(args.trials):
            out = fwd(params, x)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / args.trials
        print(json.dumps({
            "rank": rt.process_id,
            "moe_fwd_ms": round(dt * 1e3, 3),
            "tokens": cfg.tokens,
            "num_experts": cfg.num_experts,
            "devices": len(jax.devices()),
        }))
    else:
        print(json.dumps({
            "rank": rt.process_id,
            "output_shape": list(out.shape),
            "finite": bool(jnp.isfinite(out).all()),
            "num_local_experts": rt.num_local_experts,
        }))
    bootstrap.finalize()
    return 0


if __name__ == "__main__":
    from flashmoe_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
