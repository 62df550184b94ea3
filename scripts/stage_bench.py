"""Per-stage latency breakdown of the fused MoE forward on live hardware.

Times cumulative prefixes of the pipeline (router | +plan | +dispatch |
+ffn | +combine) with the chained-scan method from bench.py; successive
differences isolate each stage.  Used to target the roofline gap
(BASELINE.md: measured 2.75 ms vs ~1.8 ms roofline on the reference
config).

Usage: python scripts/stage_bench.py [--trials 5] [--chain 8]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from flashmoe_tpu.config import BENCH_CONFIGS
from flashmoe_tpu.models.reference import init_moe_params
from flashmoe_tpu.ops import dispatch as dsp
from flashmoe_tpu.ops import expert as exp
from flashmoe_tpu.ops.gate import router


def make_prefix(params, cfg, depth: int, cap: int, path: str):
    """Prefix through `depth` stages, ending in a scalar that feeds the
    chain carry (dependency without materialization).

    ``path='gather'`` times the default inference pipeline (dispatch
    indices feed the gather-fused kernel, no [E, C, H] HBM buffer);
    ``path='explicit'`` times the training-shape pipeline (explicit
    dispatch buffer + grouped kernel).
    """

    def fn(x):
        r = router(x, params["gate_w"], cfg, use_pallas=True)
        if depth == 0:
            return r.combine_weights.astype(jnp.float32).sum()
        plan = dsp.make_plan(r.expert_idx, cfg, cap)
        if depth == 1:
            return (plan.position.sum() + r.combine_weights.sum()).astype(
                jnp.float32)
        if path == "gather":
            src_tok, _ = dsp.dispatch_indices(plan, cfg, cap)
            if depth == 2:
                return (src_tok.sum() + plan.position.sum()
                        + r.combine_weights.sum()).astype(jnp.float32)
            ybuf, cap_p = exp.capacity_ffn_gather(
                x.astype(cfg.dtype), plan, cfg, cap, params)
            if depth == 3:
                return ybuf.astype(jnp.float32).sum()
            out = dsp.combine(ybuf, plan, r.combine_weights, cfg, cap_p)
            return out.sum()
        xbuf = dsp.dispatch(x.astype(cfg.dtype), plan, cfg, cap)
        if depth == 2:
            return xbuf.astype(jnp.float32).sum()
        ybuf = exp.capacity_buffer_ffn_pallas(xbuf, params, cfg)
        if depth == 3:
            return ybuf.astype(jnp.float32).sum()
        out = dsp.combine(ybuf, plan, r.combine_weights, cfg, cap)
        return out.sum()

    return fn


def chained(fn, x0, iters: int):
    def run(x):
        def body(c, _):
            s = fn(c)
            return c * (1.0 + 0.0 * s.astype(c.dtype)), None
        c, _ = jax.lax.scan(body, x, None, length=iters)
        return c.astype(jnp.float32).sum()
    return jax.jit(run)


def time_chain(fn, x, trials: int):
    float(fn(x))
    ts = []
    for _ in range(trials):
        t0 = time.perf_counter()
        float(fn(x))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=5)
    ap.add_argument("--chain", type=int, default=8,
                    help="longer chain length for the differencing pair "
                         "(must be >= 2)")
    ap.add_argument("--config", default="reference")
    ap.add_argument("--path", choices=["gather", "explicit", "combine"],
                    default="gather",
                    help="'combine' times the fused layer with in-kernel "
                         "vs XLA combine instead of stage prefixes")
    args = ap.parse_args()
    if args.chain < 2:
        ap.error("--chain must be >= 2 (per-iteration time comes from "
                 "differencing two chain lengths)")
    if args.path == "combine":
        combine_modes(args)
        return

    cfg = BENCH_CONFIGS[args.config].replace(ep=1)
    cap = cfg.capacity_for(cfg.tokens)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    params = jax.tree_util.tree_map(lambda p: p.astype(cfg.dtype), params)
    x = jax.random.normal(
        jax.random.PRNGKey(1), (cfg.tokens, cfg.hidden_size), cfg.dtype)

    # router alone is known-negligible (~0 ms: one [S,H]x[H,E] GEMM);
    # three prefixes bound the interesting stages with 6 compiles instead
    # of 10
    stage2 = ("router+plan+indices" if args.path == "gather"
              else "router+plan+dispatch")
    names = {2: stage2, 3: "+ffn", 4: "+combine"}
    prev = 0.0
    for depth, name in names.items():
        fn = make_prefix(params, cfg, depth, cap, args.path)
        t1 = time_chain(chained(fn, x, 1), x, args.trials)
        tn = time_chain(chained(fn, x, args.chain), x, args.trials)
        t = max(tn - t1, 0.0) / (args.chain - 1)
        print(json.dumps({
            "prefix": name, "cum_ms": round(t * 1e3, 3),
            "stage_ms": round((t - prev) * 1e3, 3),
        }), flush=True)
        prev = t


def combine_modes(args):
    """Decision row: the fused RDMA layer with the in-kernel
    sorted-return combine (FLASHMOE_FUSED_COMBINE=1) vs the XLA combine.

    Since the round-5 restructure the in-kernel combine REQUIRES a
    multi-rank ep world (at one rank there is no return traffic to
    overlap and the gate falls back to the XLA combine by design), so
    this row can only be measured with >= 2 chips: both "modes" on one
    chip would time the identical kernel and report a noise winner.
    With one device the record says so explicitly instead."""
    from flashmoe_tpu.parallel.fused import fused_ep_moe_layer
    from flashmoe_tpu.parallel.mesh import make_mesh

    def bail(**why):
        print(json.dumps({
            "bench": "fused_combine_modes", "config": args.config, **why,
        }), flush=True)

    n_dev = len(jax.devices())
    if n_dev < 2:
        bail(requires_multichip=True,
             note="in-kernel combine is ep>1-only since the round-5 "
                  "sorted-return restructure; 1 device present — both "
                  "modes would time the identical kernel")
        return
    base = BENCH_CONFIGS[args.config]
    if base.num_experts % 2:
        bail(error=f"num_experts={base.num_experts} not divisible by "
                   f"ep=2")
        return
    cfg = base.replace(ep=2)
    # the gate can also fall back on SMEM/VMEM infeasibility — detect it
    # up front so the record never reports a noise winner between two
    # identical kernels (review r5 pass 6 #2)
    from flashmoe_tpu.parallel.ep import local_capacity
    from flashmoe_tpu.parallel.fused import _fuse_combine_budget_ok

    s_loc = cfg.tokens // cfg.ep
    cap_pad = -(-local_capacity(cfg, s_loc) // 32) * 32
    if not _fuse_combine_budget_ok(cfg, s_loc, cfg.hidden_size,
                                   cfg.intermediate_size, cap_pad):
        bail(combine_infeasible=True,
             note="combine maps/chunks exceed the SMEM/VMEM budget at "
                  "this config; the gate would fall back to the XLA "
                  "combine for both modes")
        return
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    params = jax.tree_util.tree_map(lambda p: p.astype(cfg.dtype), params)
    x = jax.random.normal(
        jax.random.PRNGKey(1), (cfg.tokens, cfg.hidden_size), cfg.dtype)
    mesh = make_mesh(cfg, dp=1, devices=jax.devices()[:cfg.ep])
    out = {}
    for mode in ("0", "1"):
        os.environ["FLASHMOE_FUSED_COMBINE"] = mode
        try:
            def fn(c):
                o = fused_ep_moe_layer(params, c, cfg, mesh)
                return o.out.astype(jnp.float32).sum()

            t1 = time_chain(chained(fn, x, 1), x, args.trials)
            tn = time_chain(chained(fn, x, args.chain), x, args.trials)
            out[mode] = max(tn - t1, 0.0) / (args.chain - 1)
        finally:
            os.environ.pop("FLASHMOE_FUSED_COMBINE", None)
    print(json.dumps({
        "bench": "fused_combine_modes", "config": args.config,
        "xla_combine_ms": round(out["0"] * 1e3, 3),
        "in_kernel_combine_ms": round(out["1"] * 1e3, 3),
        "winner": "in_kernel" if out["1"] < out["0"] else "xla",
    }), flush=True)


if __name__ == "__main__":
    main()
