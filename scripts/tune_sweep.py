"""Measure per-generation kernel block sizes and commit them to the
tuning table (``flashmoe_tpu/tuning.py`` — the TPU analogue of the
reference's per-arch trait table, ``csrc/include/flashmoe/arch.cuh:
95-222``, whose geometry was likewise chosen offline per architecture).

Sweeps, on the real chip:
  * capacity_ffn — (block_m, block_i) of the grouped capacity-buffer FFN
    kernel at each bench shape;
  * fused_ep     — (cm, bi_cap) of the fused RDMA kernel's compute loop
    (swept on a 1-rank mesh: transfer legs vanish, the streamed-weight /
    row-tile geometry being tuned is identical);
  * fused_tiles  — (cm row tile, kw K-window) of the row-windowed
    schedule's IO-aware chooser (``--stage tiles``; the rowwin schedule
    is pinned via ``MoEConfig.fused_schedule`` and each candidate pair
    forced through a throwaway ``fused_tiles`` table, same 1-rank-mesh
    rationale — the window/accumulator traffic being tuned is
    transfer-free).

Winners are written to ``flashmoe_tpu/tuning_data/<gen>.json`` (one
``{"kernel", "match", "set", "measured_ms"}`` entry per shape), which
ships with the package and is consulted at trace time.

A sweep times the chip: a non-``--interpret`` run that finds no TPU
prints one error record and exits 2 (never a ``skipped`` record, never a
CPU timing written into the table).

Usage: python scripts/tune_sweep.py [--trials 3] [--chain 8] [--dry]
                                    [--stage all|capacity|fused|tiles]
Prints one JSON line per (kernel, shape, candidate) measurement.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from flashmoe_tpu import tuning
from flashmoe_tpu.config import MoEConfig
from flashmoe_tpu.models.reference import init_moe_params

# shapes worth a table row: the reference bench config and the Mixtral
# FFN dims (BASELINE.json configs 2 and 3)
SHAPES = [
    dict(h=2048, i=2048, e=64, cap=256),
    dict(h=4096, i=14336, e=8, cap=2048),
]


def _chain_time(fn, args, trials, chain):
    def run(*a):
        def body(c, _):
            return c * (1.0 + 0.0 * fn(*a).astype(c.dtype)), None
        c, _ = jax.lax.scan(body, jnp.float32(1.0), None, length=chain)
        return c

    j = jax.jit(run)
    float(j(*args))
    ts = []
    for _ in range(trials):
        t0 = time.perf_counter()
        float(j(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] / chain


def sweep_capacity(shape, dtype, trials, chain):
    from flashmoe_tpu.ops.expert import grouped_ffn

    h, i, e, cap = shape["h"], shape["i"], shape["e"], shape["cap"]
    cfg = MoEConfig(num_experts=e, expert_top_k=1, hidden_size=h,
                    intermediate_size=i, dtype=dtype,
                    param_dtype=jnp.float32)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    params = jax.tree_util.tree_map(lambda p: p.astype(dtype), params)
    best = None
    for bm, bi in itertools.product((128, 256, 512), (256, 512)):
        if cap % bm and bm % cap:
            continue
        cp = ((cap + bm - 1) // bm) * bm
        x = jax.random.normal(jax.random.PRNGKey(1), (e * cp, h), dtype)
        gid = jnp.arange(e * (cp // bm), dtype=jnp.int32) // (cp // bm)

        def fn(xx):
            return grouped_ffn(
                xx, gid, params["w_up"], params["b_up"], params["w_down"],
                params["b_down"], None, act_name=cfg.hidden_act,
                gated=False, block_m=bm, block_i=bi,
            ).astype(jnp.float32).sum()

        t = _chain_time(fn, (x,), trials, chain)
        row = {"kernel": "capacity_ffn", "h": h, "i": i, "block_m": bm,
               "block_i": bi, "ms": round(t * 1e3, 4)}
        print(json.dumps(row), flush=True)
        if best is None or t < best[0]:
            best = (t, {"block_m": bm, "block_i": bi})
    return {"kernel": "capacity_ffn",
            "match": {"h": h, "i": i, "dtype": jnp.dtype(dtype).name},
            "set": best[1], "measured_ms": round(best[0] * 1e3, 4)}


def sweep_fused(shape, dtype, trials, chain, interpret=False):
    from flashmoe_tpu.parallel.fused import fused_ep_moe_layer
    from flashmoe_tpu.parallel.mesh import make_mesh

    h, i, e = shape["h"], shape["i"], shape["e"]
    cfg = MoEConfig(num_experts=e, expert_top_k=2, hidden_size=h,
                    intermediate_size=i, sequence_len=2048,
                    capacity_factor=1.0, drop_tokens=True, ep=1,
                    dtype=dtype, param_dtype=jnp.float32)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    params = jax.tree_util.tree_map(lambda p: p.astype(dtype), params)
    x = jax.random.normal(jax.random.PRNGKey(1), (cfg.tokens, h), dtype)
    mesh = make_mesh(cfg, dp=1, devices=jax.devices()[:1])
    tmp = "/tmp/flashmoe_tune_candidate.json"
    best = None
    cap = cfg.capacity_for(cfg.tokens)
    cap_pad = -(-cap // 32) * 32
    wr_was_swept = False
    try:
        for cm, bic in itertools.product((128, 256), (256, 512)):
            # the per-source weights-resident schedule only differs when
            # the capacity spans multiple row tiles — sweep it there so
            # its crossover becomes a measured row, not a heuristic
            # (the arrival-batched schedule needs >= 2 chips: at ep=1
            # the schedules coincide, so it has no single-chip row).
            # Gate on the EFFECTIVE cm (a tuned cm that does not divide
            # the padded capacity is discarded by _resolve_tiles) and on
            # VMEM feasibility — a wr=True row whose budget fails would
            # silently re-measure the stream schedule and let timing
            # noise write an unmeasured bit (review r5 pass 3 #2/#3).
            from flashmoe_tpu.parallel.fused import _resident_budget_ok

            eff_cm = cm if cap_pad % cm == 0 else next(
                t for t in (256, 128, 64, 32, 16, 8) if cap_pad % t == 0)
            eff_bi = min(bic, i)
            wr_feasible = (
                cap_pad // eff_cm > 1
                and _resident_budget_ok(
                    cap_pad, h, i, jnp.dtype(dtype).itemsize, False,
                    eff_cm, eff_bi, False, cfg.expert_top_k,
                    hid_rows=cap_pad)[0]
            )
            wr_opts = (False, True) if wr_feasible else (False,)
            wr_was_swept = wr_was_swept or len(wr_opts) > 1
            for wr in wr_opts:
                with open(tmp, "w") as f:
                    json.dump({"entries": [{
                        "kernel": "fused_ep",
                        "match": {"h": h, "i": i,
                                  "dtype": jnp.dtype(dtype).name},
                        "set": {"cm": cm, "bi_cap": bic,
                                "weights_resident": wr},
                    }]}, f)
                os.environ["FLASHMOE_TUNING_FILE"] = tmp
                tuning._load.cache_clear()

                def fn(xx):
                    return fused_ep_moe_layer(
                        params, xx, cfg, mesh,
                        interpret=interpret).out.astype(jnp.float32).sum()

                t = _chain_time(fn, (x,), trials, chain)
                row = {"kernel": "fused_ep", "h": h, "i": i, "cm": cm,
                       "bi_cap": bic, "weights_resident": wr,
                       "ms": round(t * 1e3, 4)}
                print(json.dumps(row), flush=True)
                if best is None or t < best[0]:
                    best = (t, {"cm": cm, "bi_cap": bic,
                                "weights_resident": wr})
    finally:
        os.environ.pop("FLASHMOE_TUNING_FILE", None)
        tuning._load.cache_clear()
    winner = dict(best[1])
    if not wr_was_swept:
        # a bit that was never measured must not override the deployment
        # heuristic at other capacities (review r5 pass 3 #1)
        winner.pop("weights_resident", None)
    return {"kernel": "fused_ep",
            "match": {"h": h, "i": i, "dtype": jnp.dtype(dtype).name},
            "set": winner, "measured_ms": round(best[0] * 1e3, 4)}


def sweep_tiles(shape, dtype, trials, chain, interpret=False):
    """Measure (cm, kw) candidates of the row-windowed schedule's
    IO-aware tile chooser at ``shape`` and return the winning
    ``fused_tiles`` entry, or None when the shape has no feasible
    rowwin geometry / fewer than two candidates worth ranking.  Each
    candidate pair is forced through a throwaway table +
    ``fused_schedule='rowwin'`` so the measurement times exactly the
    geometry the committed entry would select."""
    from flashmoe_tpu.parallel.fused import (
        fused_ep_moe_layer, rowwin_sweep_candidates,
    )
    from flashmoe_tpu.parallel.mesh import make_mesh

    h, i, e = shape["h"], shape["i"], shape["e"]
    cfg = MoEConfig(num_experts=e, expert_top_k=2, hidden_size=h,
                    intermediate_size=i, sequence_len=2048,
                    capacity_factor=1.0, drop_tokens=True, ep=1,
                    fused_schedule="rowwin",
                    dtype=dtype, param_dtype=jnp.float32)
    cap_pad = -(-cfg.capacity_for(cfg.tokens) // 32) * 32
    dt = jnp.dtype(dtype).itemsize
    # the kernel's own grid, per-kw best-cm (see fused.py) — shared
    # with bench.py --tiles so the enumerations cannot drift
    cands = rowwin_sweep_candidates(cap_pad, h, i, dt, cfg.gated_ffn,
                                    False, cfg.expert_top_k)
    if len(cands) < 2:
        print(json.dumps({"kernel": "fused_tiles", "h": h, "i": i,
                          "skipped": True,
                          "reason": f"{len(cands)} feasible (cm, kw) "
                                    f"candidates at this shape"}),
              flush=True)
        return None
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    params = jax.tree_util.tree_map(lambda p: p.astype(dtype), params)
    x = jax.random.normal(jax.random.PRNGKey(1), (cfg.tokens, h), dtype)
    mesh = make_mesh(cfg, dp=1, devices=jax.devices()[:1])
    tmp = "/tmp/flashmoe_tune_tiles_candidate.json"
    best = None
    try:
        for cm, kw in cands:
            with open(tmp, "w") as f:
                json.dump({"entries": [{
                    "kernel": "fused_tiles",
                    "match": {"h": h, "i": i,
                              "dtype": jnp.dtype(dtype).name},
                    "set": {"cm": cm, "kw": kw},
                }]}, f)
            os.environ["FLASHMOE_TUNING_FILE"] = tmp
            tuning._load.cache_clear()

            def fn(xx):
                return fused_ep_moe_layer(
                    params, xx, cfg, mesh,
                    interpret=interpret).out.astype(jnp.float32).sum()

            t = _chain_time(fn, (x,), trials, chain)
            row = {"kernel": "fused_tiles", "h": h, "i": i, "cm": cm,
                   "kw": kw, "schedule": "rowwin",
                   "ms": round(t * 1e3, 4)}
            print(json.dumps(row), flush=True)
            if best is None or t < best[0]:
                best = (t, {"cm": cm, "kw": kw})
    finally:
        os.environ.pop("FLASHMOE_TUNING_FILE", None)
        tuning._load.cache_clear()
    return {"kernel": "fused_tiles",
            "match": {"h": h, "i": i, "dtype": jnp.dtype(dtype).name},
            "set": best[1], "measured_ms": round(best[0] * 1e3, 4)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--chain", type=int, default=8)
    ap.add_argument("--dry", action="store_true",
                    help="sweep without writing the table")
    ap.add_argument("--interpret", action="store_true",
                    help="interpret-mode structural dry run (timings "
                         "meaningless; implies --dry)")
    ap.add_argument("--stage", default="all",
                    choices=["all", "capacity", "fused", "tiles"],
                    help="which kernel family to sweep (tiles = the "
                         "rowwin schedule's fused_tiles (cm, kw) pairs)")
    args = ap.parse_args(argv)
    if args.interpret:
        args.dry = True

    if not args.interpret:
        # a sweep times the chip: without one there is nothing to tune
        dev = jax.devices()[0]
        if dev.platform != "tpu":
            print(json.dumps({
                "metric": f"tune_sweep[{args.stage}]",
                "value": -1, "unit": "ms",
                "error": f"needs a TPU; JAX found platform "
                         f"{dev.platform!r} ({dev.device_kind})",
            }), flush=True)
            sys.exit(2)

    dtype = jnp.bfloat16
    entries = []
    for shape in SHAPES:
        if args.stage in ("all", "capacity"):
            entries.append(sweep_capacity(shape, dtype, args.trials,
                                          args.chain))
        if args.stage in ("all", "fused"):
            entries.append(sweep_fused(shape, dtype, args.trials,
                                       args.chain,
                                       interpret=args.interpret))
        if args.stage in ("all", "tiles"):
            ent = sweep_tiles(shape, dtype, args.trials, args.chain,
                              interpret=args.interpret)
            if ent is not None:
                entries.append(ent)
    gen = tuning.generation()
    if args.dry:
        print(json.dumps({"generation": gen, "entries": entries}))
    else:
        path = tuning.save_entries(gen, entries)
        print(json.dumps({"written": path, "n": len(entries)}))


if __name__ == "__main__":
    main()
