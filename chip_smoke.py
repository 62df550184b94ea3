#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main path through the entry points a user calls, at
published widths (depth, batch and sequence cut to one 16 GB chip and said
so), with weights from ``--seed``, and checks what comes out by the repo's
own means.  It never runs on the CPU: the first thing it does with JAX is
to assert the platform, and every kernel claim is checked in the compiled
program (``tpu_custom_call``), so interpret mode cannot pass for the chip.

    python chip_smoke.py              # one chip: layer, serve,
                                      # serve_hybrid, train
    python chip_smoke.py --only serve_hybrid    # that phase alone
    python chip_smoke.py --only serve_sdar      # the serve phase's sdar cases
    python chip_smoke.py --only serve_trinity   # window + full layers, two pools
    python chip_smoke.py --chips 4    # one host, four chips: ep4_layer,
                                      # ep4_fused, ep4_serve (builder-run)

Each phase prints one JSON line (phase, ok, what was cut from the preset,
compile and run seconds, the comparison's error).  A phase that fails is
printed with its error, the rest still run, and the exit code is non-zero.
The last line of a passing run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import io
import json
import os
import sys
import threading
import time
import traceback

import numpy as np

# bf16 carries 8 bits of mantissa; sums over top-k experts and a few
# layers stay within a few of its ulps of the reference
BF16_TOL = 3e-2
# true float32 (matmul precision "highest"): two arms of one model agree
# to accumulation order
F32_TOL = 1e-3
# a routing choice closer than this (relative) is a tie either side may
# break differently; such tokens are counted and printed, not compared
TIE_TOL = 1e-3

SERVE_LAYERS = 3          # of 28: f32 weights are 2.16 GB a layer
SERVE_PAGES = 2048        # x 16 tokens of KV beside them (0.75 GB)
TRAIN_BATCH, TRAIN_SEQ = 2, 4096   # of 8 x 8192: see phase_train
FUSED_LIMIT_S = 300       # wall clock given to the in-kernel RDMA path


def emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-9))


# ----------------------------------------------------------------------
# layer: fm.moe_layer against the dense oracle
# ----------------------------------------------------------------------

def oracle_layer(params, x, cfg):
    """``models/reference.reference_moe`` with the capacity rule added.

    The dense oracle evaluates every expert on every token and knows
    nothing of capacity, while both benchmark configs drop
    (``drop_tokens=True``).  So its parts are put together here —
    ``reference_gate``, ``expert_ffn``, ``shared_expert_ffn`` — with the
    one rule the layer adds (``ops/dispatch.py``): assignments rank
    k-major then by token (every first choice outranks every second), a
    rank at or past ``capacity_for(S)`` is dropped, and the surviving
    weights are renormalised.  Without drops this IS ``reference_moe``
    (``tests/test_chip_contract.py`` holds it to that).

    Returns ``(out [S, H] f32, ambiguous [S] bool)``: a token is
    ambiguous when two of its top-(k+1) router probabilities are within
    ``TIE_TOL`` of each other, or when one of its assignments ranks as
    close to the capacity edge of its expert as that expert has such
    tied tokens among its candidates (each of them can move the queue
    behind it by one) — there the oracle and the kernel may legitimately
    route or drop differently."""
    import jax
    import jax.numpy as jnp

    from flashmoe_tpu.models.reference import (
        expert_ffn, reference_gate, shared_expert_ffn,
    )

    s, e, k = x.shape[0], cfg.num_experts, cfg.expert_top_k
    _, top_idx, probs, _ = jax.jit(
        lambda x, w: reference_gate(x, w, cfg))(x, params["gate_w"])
    top_idx, probs = np.asarray(top_idx), np.asarray(probs, np.float64)
    top_p = np.take_along_axis(probs, top_idx, axis=1)
    lead = -np.sort(-probs, axis=1)[:, :k + 1]
    tie = ((lead[:, :-1] - lead[:, 1:]) < TIE_TOL * lead[:, :-1]).any(1)
    valid = np.ones((s, k), bool)
    if cfg.drop_tokens:
        cap = cfg.capacity_for(s)
        ef = top_idx.T.reshape(-1)
        order = np.argsort(ef, kind="stable")
        starts = np.searchsorted(ef[order], np.arange(e))
        rank = np.empty(s * k, np.int64)
        rank[order] = np.arange(s * k) - starts[ef[order]]
        rank = rank.reshape(k, s).T
        valid = rank < cap
        cand = np.argsort(-probs, axis=1)[:, :k + 1]
        slack = np.bincount(cand[tie].reshape(-1), minlength=e)
        tie |= (np.abs(rank - cap + 0.5) < slack[top_idx]).any(1)
    w = np.where(valid, top_p / top_p.sum(1, keepdims=True), 0.0)
    w = w / np.maximum(w.sum(1, keepdims=True), 1e-20)
    cw = np.zeros((s, e), np.float32)
    np.put_along_axis(cw, top_idx, w.astype(np.float32), axis=1)

    @jax.jit
    def dense(params, x, cw):
        xs = x.astype(cfg.dtype)

        def one(acc, i):
            y = expert_ffn(xs, params, cfg, i).astype(jnp.float32)
            return acc + cw[:, i][:, None] * y, None

        out, _ = jax.lax.scan(one, jnp.zeros(x.shape, jnp.float32),
                              jnp.arange(e))
        if cfg.num_shared_experts:
            out = out + shared_expert_ffn(xs, params, cfg).astype(out.dtype)
        return out

    return dense(params, x, jnp.asarray(cw)), tie


def compare_rows(got, want, ambiguous):
    """Row-wise comparison within BF16_TOL of the output's scale.
    Returns (worst error over unambiguous rows, failing unambiguous
    rows, ambiguous rows, failing ambiguous rows)."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    scale = max(float(np.max(np.abs(want))), 1e-9)
    over = np.max(np.abs(got - want), axis=1) / scale
    clear = ~np.asarray(ambiguous)
    return (float(over[clear].max()), int((over[clear] > BF16_TOL).sum()),
            int((~clear).sum()), int((over[~clear] > BF16_TOL).sum()))


def _layer_case(name, cfg, cut, seed):
    import jax
    import jax.numpy as jnp

    import flashmoe_tpu as fm
    from flashmoe_tpu.models.reference import init_moe_params

    params = init_moe_params(jax.random.PRNGKey(seed), cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (cfg.tokens, cfg.hidden_size),
                          jnp.float32).astype(cfg.dtype)
    # use_pallas left at its default: the chip decides (ops/moe.py)
    fn = jax.jit(lambda p, x: fm.moe_layer(p, x, cfg).out)
    t0 = time.perf_counter()
    compiled = fn.lower(params, x).compile()
    compile_s = time.perf_counter() - t0
    kernels = compiled.as_text().count("tpu_custom_call")
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(params, x))
    run_s = time.perf_counter() - t0
    want, ambiguous = oracle_layer(params, x, cfg)
    err, bad, n_amb, amb_bad = compare_rows(out, want, ambiguous)
    finite = bool(jnp.isfinite(out.astype(jnp.float32)).all())
    ok = (kernels > 0 and finite and bad == 0
          and out.shape == (cfg.tokens, cfg.hidden_size)
          and n_amb <= cfg.tokens // 4)
    emit({"phase": "layer", "config": name, "ok": ok, "cut": cut,
          "widths": {"E": cfg.num_experts, "k": cfg.expert_top_k,
                     "H": cfg.hidden_size, "I": cfg.intermediate_size,
                     "S": cfg.tokens, "dtype": jnp.dtype(cfg.dtype).name},
          "tpu_custom_calls": kernels, "compile_s": round(compile_s, 3),
          "run_s": round(run_s, 4), "rel_err_vs_oracle": err,
          "tolerance": BF16_TOL, "rows_over_tolerance": bad,
          "rows_ambiguous_not_held_to_it": n_amb,
          "of_which_over_tolerance": amb_bad, "finite": finite})
    return ok


def phase_layer(seed):
    from flashmoe_tpu.config import BENCH_CONFIGS

    ok = _layer_case("reference", BENCH_CONFIGS["reference"], "none", seed)
    ok &= _layer_case("deepseek", BENCH_CONFIGS["deepseek"].replace(ep=1),
                      "ep 8 -> 1 (one chip)", seed)
    return ok


# ----------------------------------------------------------------------
# serve: ServingEngine against generate(), one request at a time
# ----------------------------------------------------------------------

def serve_config(num_layers=SERVE_LAYERS, **overrides):
    from flashmoe_tpu.models.presets import PRESETS

    return PRESETS["deepseek-moe-16b"](num_layers=num_layers, **overrides)


def serve_requests(vocab, seed):
    """Four requests of four prompt lengths; two join mid-flight."""
    from flashmoe_tpu.serving.engine import Request

    rng = np.random.default_rng(seed)
    lens, news, arrivals = (5, 19, 33, 12), (12, 10, 8, 12), (0, 0, 2, 4)
    reqs = [Request(rid=i, prompt=tuple(int(t) for t in
                                        rng.integers(1, vocab, n)),
                    max_new_tokens=m)
            for i, (n, m) in enumerate(zip(lens, news))]
    return reqs, list(arrivals)


def run_engine(params, cfg, serve, reqs, arrivals, mesh=None):
    """Drive ``submit``/``run`` and keep the logits each token was
    sampled from, where the engine leaves them visible between steps
    (every token but a request's first, whose prefill logits are made
    and consumed inside one step)."""
    from flashmoe_tpu.serving.engine import ServingEngine
    from flashmoe_tpu.utils.telemetry import Metrics

    engine = ServingEngine(params, cfg, serve, mesh=mesh,
                           metrics_obj=Metrics())   # this run's counters
    seen = {r.rid: {} for r in reqs}

    def watch():
        for i in engine._decoding():
            s = engine.slots[i]
            seen[s.orig.rid][engine._delivered(s)] = np.asarray(
                engine._logits[i])
        return False

    try:
        outputs = engine.run(reqs, arrivals, until=watch)
        summary = engine.summary()
        # the steps whose decode program read each slot's pages in place
        summary["decode_kernel_steps"] = engine.metrics.counters.get(
            "serve.decode_kernel_steps", 0)
        # ... and those dispatched ahead of the step's read-back
        summary["decode_ahead_steps"] = engine.metrics.counters.get(
            "serve.decode_ahead_steps", 0)
    finally:
        engine.close()
    return outputs, seen, summary


@contextlib.contextmanager
def gather_arm():
    """Programs traced inside take the plain form of the cached attention
    (store, gather the context, attend in XLA) whatever the backend: what
    the decode kernel and a long span's flash kernel are held against.
    The engine's programs are traced anew on either side."""
    from flashmoe_tpu.ops import attention
    from flashmoe_tpu.serving import engine as eng

    def retrace():
        for program in (eng._prefill_padded, *eng._INPLACE.values()):
            program.clear_cache()

    rules = attention.kv_attention_arm, attention.span_attention_arm
    attention.kv_attention_arm = lambda *a, **k: "gather"
    attention.span_attention_arm = lambda *a, **k: "xla"
    retrace()
    try:
        yield
    finally:
        attention.kv_attention_arm, attention.span_attention_arm = rules
        retrace()


def compare_arms(reqs, kernel, gather, tol):
    """The engine's streams on the kernel's arm against its streams on
    the gather arm: (requests whose tokens differ, rows compared, the
    largest distance of two rows over the row's scale, rows further than
    ``tol``).  Rows are compared while the two streams agree: the same
    tokens fed, so the same context."""
    (k_out, k_seen), (g_out, g_seen) = kernel, gather
    differ, errs = [], []
    for r in reqs:
        a, b = list(k_out[r.rid]), list(g_out[r.rid])
        t0 = len(r.prompt)
        same = next((j for j in range(min(len(a), len(b)) - t0)
                     if a[t0 + j] != b[t0 + j]), None)
        if same is not None or len(a) != len(b):
            differ.append(r.rid)
        for j in sorted(set(k_seen[r.rid]) & set(g_seen[r.rid])):
            if same is None or j <= same:
                row = g_seen[r.rid][j]
                errs.append(float(np.max(np.abs(k_seen[r.rid][j] - row))
                                  / np.max(np.abs(row))))
    errs = np.asarray(errs)
    return (differ, len(errs), float(errs.max()) if len(errs) else None,
            int((errs > tol).sum()))


def check_streams(cfg, params, reqs, outputs, seen, tol, own_row=False):
    """Engine streams against ``generate()`` and its logits.

    Tokens: ``generate()`` one request at a time, greedy.  Logits: the
    reference's own single-pass prefill (``prefill_forward`` +
    ``lm_logits_span``, what ``generate()`` is built of) over the
    engine's stream gives the reference logits at every position.  Every
    engine token must be the reference's argmax or within ``tol`` of it
    (a near-tie: printed, no failure), and so must ``generate()``'s own
    token where the two streams first part.  With ``own_row`` a token
    further off is no failure where the engine's own row is visible and
    the token is that row's best: the sampler was right, the ROW differs
    (by at least half the token's gap), and the rows have their own rule.
    Returns the failures, the near-ties (such tokens among them, marked),
    the token mismatches, and how far the engine's own logits, where
    visible, are from the reference's at each position (all as fractions
    of the largest reference logit)."""
    import jax
    import jax.numpy as jnp

    from flashmoe_tpu.models.generate import (
        generate, init_cache, lm_logits_span, prefill_forward,
    )

    t_pad = 64

    @jax.jit
    def ref_logits(params, toks):
        x, _ = prefill_forward(params, cfg, toks, init_cache(cfg, 1, t_pad))
        return lm_logits_span(params, cfg, x)[0]

    bad, near_ties, token_mismatch, logit_errs = [], [], [], []
    for r in reqs:
        got = list(outputs[r.rid])
        t0 = len(r.prompt)
        if len(got) != t0 + r.max_new_tokens:
            bad.append(f"rid {r.rid}: {len(got) - t0} of "
                       f"{r.max_new_tokens} tokens")
            continue
        want = np.asarray(jax.jit(
            lambda p, prompt, n=r.max_new_tokens: generate(
                p, prompt, cfg, max_new_tokens=n))(
            params, jnp.asarray([r.prompt], jnp.int32)))[0].tolist()
        padded = np.zeros((1, t_pad), np.int32)
        padded[0, :len(got)] = got
        ref = np.asarray(ref_logits(params, jnp.asarray(padded)))
        scale = float(np.max(np.abs(ref[t0 - 1:len(got) - 1])))
        first_diff = next((j for j in range(r.max_new_tokens)
                           if got[t0 + j] != want[t0 + j]), None)
        for j in range(r.max_new_tokens):
            row = ref[t0 + j - 1]
            gap = float(row.max() - row[got[t0 + j]]) / scale
            own = seen[r.rid].get(j)
            if gap > tol and own_row and own is not None \
                    and got[t0 + j] == int(np.argmax(own)):
                near_ties.append({"rid": r.rid, "token": j, "gap": gap,
                                  "best_of_own_row": True})
            elif gap > tol:
                bad.append(f"rid {r.rid} token {j}: {got[t0 + j]} is "
                           f"{gap:.4f} of scale below the reference argmax")
            elif gap > 0:
                near_ties.append({"rid": r.rid, "token": j, "gap": gap})
            if j == first_diff:
                # same context up to here: generate()'s own token must be
                # a near-tie with the engine's, or one of them is wrong
                wgap = float(row.max() - row[want[t0 + j]]) / scale
                token_mismatch.append({"rid": r.rid, "token": j,
                                       "engine": got[t0 + j],
                                       "generate": want[t0 + j],
                                       "gap": max(gap, wgap)})
                if wgap > tol:
                    bad.append(f"rid {r.rid} token {j}: generate() gave "
                               f"{want[t0 + j]}, {wgap:.4f} of scale below "
                               f"the reference argmax")
            if j in seen[r.rid]:
                logit_errs.append(
                    float(np.max(np.abs(seen[r.rid][j] - row))) / scale)
    return bad, near_ties, token_mismatch, logit_errs


def _serve_case(params, cfg, serve, seed, cut, extra):
    """One engine run and its check.  ``float32`` runs under matmul
    precision "highest" and is held to F32_TOL at every position: any
    fault of paging, positions or batching shows there.  ``bfloat16`` is
    the preset as published: the engine batches four slots and pads
    prompts where ``generate()`` runs one exact-length request, so the
    two arms round differently.  A configuration with NO router is held
    to BF16_TOL at every token and every visible row all the same: what
    is left between the arms is rounding.  Behind a router with seeded
    (flat) weights a top-k choice now and then falls the other way in one
    arm — one expert in k differs and that position's row moves by more
    than bf16 rounding (first seen on the chip: top-6 of 64, 2 of 38
    rows, 0.054 and 0.170 of scale; top-8 of 512 in groups, 5 of 38 rows
    up to 0.23 and one token 0.035 below the reference's best, while the
    same mixers over dense layers agreed everywhere: ``serve_hybrid``).
    There three rows in four are held to BF16_TOL and every row to half
    the logits' scale (a fault of paging or position is an error of the
    whole scale); every token is held to BF16_TOL of the reference
    argmax or, where its own row is visible, to being that row's best
    (``check_streams``: the row's distance is then the rows' to judge)."""
    import jax
    import jax.numpy as jnp

    strict = cfg.dtype == jnp.float32
    routed = bool(cfg.moe_layer_indices)
    tol = F32_TOL if strict else BF16_TOL
    reqs, arrivals = serve_requests(cfg.vocab_size, seed)
    with (jax.default_matmul_precision("highest") if strict
          else contextlib.nullcontext()):
        t0 = time.perf_counter()
        outputs, seen, summary = run_engine(params, cfg, serve, reqs,
                                            arrivals)
        run_s = time.perf_counter() - t0
        # the same requests with the decode step on the gather arm: the
        # Pallas kernel (which tier-1 sees in interpret mode only)
        # against the plain form, at this page shape, on the chip
        with gather_arm():
            g_outputs, g_seen, g_summary = run_engine(params, cfg, serve,
                                                      reqs, arrivals)
        differ, arm_rows, arm_err, arm_over = compare_arms(
            reqs, (outputs, seen), (g_outputs, g_seen), tol)
        t0 = time.perf_counter()
        bad, near_ties, token_mismatch, errs = check_streams(
            cfg, params, reqs, outputs, seen, tol,
            own_row=routed and not strict)
        check_s = time.perf_counter() - t0
    errs = np.asarray(errs)
    over = int((errs > tol).sum())
    if strict or not routed:
        logits_ok = over == 0
    else:
        logits_ok = over <= len(errs) // 4 and float(errs.max()) <= 0.5
    # the arms: the kernel ran on every decoding step and on none of the
    # gather arm's; float32 serves the same tokens; the rows agree within
    # the tolerance wherever the two fed the same tokens (behind a router
    # a flipped choice moves a row, as against generate(): three in four)
    arms_ok = (summary["decode_kernel_steps"] > 0
               and g_summary["decode_kernel_steps"] == 0
               and arm_rows > 0 and (not differ or not strict)
               and (arm_over == 0 if strict or not routed
                    else arm_over <= arm_rows // 4 and arm_err <= 0.5))
    ok = (not bad and logits_ok and len(errs) > 0 and arms_ok
          and summary["completed"] == len(reqs)
          and summary["max_active"] > 1)
    emit({"phase": "serve", "dtype": jnp.dtype(cfg.dtype).name, "ok": ok,
          "cut": cut,
          "routed_layers": len(cfg.moe_layer_indices),
          "widths": {"H": cfg.hidden_size, "I": cfg.intermediate_size,
                     "E": cfg.num_experts, "shared": cfg.num_shared_experts,
                     "k": cfg.expert_top_k, "heads": cfg.num_heads,
                     "vocab": cfg.vocab_size},
          "requests": len(reqs), "completed": summary["completed"],
          "max_active": summary["max_active"],
          "prompt_lens": [len(r.prompt) for r in reqs],
          "arrival_steps": arrivals, "steps": summary["steps"],
          "run_s_with_compile": round(run_s, 3),
          "reference_s_with_compile": round(check_s, 3),
          "tokens_equal_generate": not token_mismatch,
          "token_mismatches_vs_generate": token_mismatch,
          "near_ties": near_ties[:8], "tolerance": tol,
          "logits_compared": len(errs), "logits_over_tolerance": over,
          "logits_rel_err_median": float(np.median(errs)),
          "logits_rel_err_max": float(errs.max()),
          "failures": bad[:8],
          "decode_ahead_steps": summary["decode_ahead_steps"],
          "arms": {"kernel_steps": summary["decode_kernel_steps"],
                   "gather_arm_kernel_steps":
                       g_summary["decode_kernel_steps"],
                   "requests_whose_tokens_differ": differ,
                   "rows_compared": arm_rows, "rows_rel_err_max": arm_err,
                   "rows_over_tolerance": arm_over, "ok": arms_ok},
          **extra})
    return ok


def phase_serve(seed):
    import jax
    import jax.numpy as jnp

    from flashmoe_tpu.models.transformer import init_params
    from flashmoe_tpu.serving import engine as eng

    cfg = serve_config()
    params = init_params(jax.random.PRNGKey(seed), cfg)
    serve = eng.ServeConfig(max_batch=4, page_size=16, num_pages=SERVE_PAGES,
                            max_pages_per_slot=4, ctx_bucket_pages=4,
                            prompt_bucket=16)
    # what one decode step holds on the device, counted by the compiler
    cache = jax.eval_shape(
        lambda: eng.init_paged_cache(cfg, serve.num_pages, serve.page_size))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.int32)
    t0 = time.perf_counter()
    mem = eng._paged_decode_step.lower(
        params, cfg, cache, i32(serve.max_batch),
        i32(serve.max_batch, serve.max_pages_per_slot),
        i32(serve.max_batch)).compile().memory_analysis()
    compile_s = time.perf_counter() - t0
    step_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    cut = (f"num_layers 28 -> {SERVE_LAYERS} (f32 weights and a "
           f"{SERVE_PAGES}-page KV pool are "
           f"{mem.argument_size_in_bytes / 2**30:.2f} GiB; one decode step "
           f"holds {step_bytes / 2**30:.2f} GiB by memory_analysis)")
    ok = _serve_case(params, cfg.replace(dtype=jnp.float32), serve, seed,
                     cut + "; dtype bf16 -> f32, matmul precision highest",
                     {})
    gc.collect()
    ok &= _serve_case(params, cfg, serve, seed, cut,
                      {"decode_step_compile_s": round(compile_s, 3)})
    del params
    gc.collect()
    return phase_serve_sdar(seed) and ok


def phase_serve_sdar(seed):
    """The serving phase's ``sdar`` cases alone (``--only serve_sdar``):
    generation by diffusion over blocks, one published layer of
    SDAR-30B-A3B-Chat at full widths, every expert, the whole vocabulary,
    float32 and bfloat16."""
    import jax
    import jax.numpy as jnp

    from flashmoe_tpu.models.presets import PRESETS
    from flashmoe_tpu.models.transformer import init_params

    sdar = PRESETS["sdar-30b-a3b-chat"](num_layers=1)
    params = init_params(jax.random.PRNGKey(seed + 1), sdar)
    ok = _sdar_case(params, sdar.replace(dtype=jnp.float32), seed)
    gc.collect()
    ok &= _sdar_case(params, sdar, seed)
    return ok


def _sdar_case(params, cfg, seed):
    """The engine's block-diffusion path at SDAR-30B-A3B-Chat's widths, ONE
    published layer: tokens and reveal steps against ``generate_blocks``
    (a request at a time over the dense cache), and the kernel's arm
    (``fm_paged_decode`` at a span of two blocks under the block mask:
    a block's commit beside the next block's first step)
    against the gather arm.  ``float32`` under matmul precision "highest"
    must agree token for token; ``bfloat16`` reports how many do (a
    flipped choice compounds through the blocks that follow: the cell's
    teacher-forced check, not this one, judges bfloat16)."""
    import jax
    import jax.numpy as jnp

    from flashmoe_tpu.models.generate import generate_blocks
    from flashmoe_tpu.serving import engine as eng
    from flashmoe_tpu.utils.telemetry import Metrics

    strict = cfg.dtype == jnp.float32
    serve = eng.ServeConfig(max_batch=4, page_size=16, num_pages=SERVE_PAGES,
                            max_pages_per_slot=4, ctx_bucket_pages=2,
                            prompt_bucket=16, denoise_steps=2)
    reqs, arrivals = serve_requests(cfg.vocab_size, seed)

    def run():
        engine = eng.ServingEngine(params, cfg, serve,
                                   metrics_obj=Metrics())  # this run's
        try:
            out = engine.run(reqs, arrivals)
            return (out, dict(engine.reveal_steps),
                    engine.metrics.counters.get("serve.decode_kernel_steps",
                                                0),
                    engine.metrics.counters.get("serve.fused_commits", 0))
        finally:
            engine.close()

    with (jax.default_matmul_precision("highest") if strict
          else contextlib.nullcontext()):
        t0 = time.perf_counter()
        out, steps, kernel_steps, fused = run()
        run_s = time.perf_counter() - t0
        with gather_arm():
            g_out, g_steps, g_kernel_steps, _ = run()
        want = {}
        for r in reqs:
            toks, at = generate_blocks(
                params, jnp.asarray([r.prompt], jnp.int32), cfg,
                max_new_tokens=r.max_new_tokens, denoise_steps=2)
            want[r.rid] = (np.asarray(toks)[0].tolist(),
                           np.asarray(at)[0].tolist())
    new = sum(r.max_new_tokens for r in reqs)
    same = lambda a, b: sum(x == y for x, y in zip(a, b))
    tok_eq = sum(same(out[r.rid][len(r.prompt):],
                      want[r.rid][0][len(r.prompt):]) for r in reqs)
    step_eq = sum(same(steps[r.rid], want[r.rid][1]) for r in reqs)
    arm_eq = sum(same(out[r.rid], g_out[r.rid]) - len(r.prompt)
                 for r in reqs)
    ok = (kernel_steps > 0 and g_kernel_steps == 0 and fused > 0
          and all(len(out[r.rid]) == len(r.prompt) + r.max_new_tokens
                  for r in reqs)
          and (not strict or (tok_eq == step_eq == arm_eq == new)))
    emit({"phase": "serve", "case": "sdar",
          "dtype": jnp.dtype(cfg.dtype).name, "ok": ok,
          "widths": {"H": cfg.hidden_size, "I": cfg.intermediate_size,
                     "E": cfg.num_experts, "k": cfg.expert_top_k,
                     "heads": cfg.num_heads,
                     "kv_heads": cfg.resolved_num_kv_heads,
                     "head_dim": cfg.resolved_head_dim,
                     "vocab": cfg.vocab_size, "block": cfg.block_length},
          "new_tokens": new, "tokens_equal_generate": tok_eq,
          "reveal_steps_equal_generate": step_eq,
          "tokens_equal_gather_arm": arm_eq,
          "kernel_steps": kernel_steps, "fused_commits": fused,
          "gather_arm_kernel_steps": g_kernel_steps,
          "run_s_with_compile": round(run_s, 3)})
    return ok


def phase_serve_trinity(seed):
    """Window layers beside a full layer at Trinity-Large-Preview's widths
    (``--only serve_trinity``): ONE window layer and ONE full layer, both
    mixture layers with 32 of 256 experts held, an eighth of the
    vocabulary; float32 (weights and activations, matmul precision
    "highest") and bfloat16; the weights and the plain reference are
    ``benchmark/lib/reference_trinity.py``'s."""
    import importlib.util

    import jax.numpy as jnp

    from flashmoe_tpu.models.presets import PRESETS
    from flashmoe_tpu.ops import moe

    spec = importlib.util.spec_from_file_location(
        "benchlib_reference_trinity", os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "benchmark", "lib",
            "reference_trinity.py"))
    ref = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = ref
    spec.loader.exec_module(ref)
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmark", "configs",
                           "trinity_large.json")) as f:
        conf = json.load(f)
    ok = True
    for dtype in ("float32", "bfloat16"):
        file = dict(conf, num_hidden_layers=2, num_dense_layers=0,
                    layer_kinds=["sliding_attention", "full_attention"],
                    served={"param_dtype": dtype})
        dims = ref.model_dims(file)
        cfg = PRESETS["trinity-large-preview"](
            num_layers=2, first_k_dense=0, layer_mixers=("swa", "mha"),
            experts_held=32, vocab_size=dims["vocab"],
            dtype=jnp.dtype(dtype).type, param_dtype=jnp.dtype(dtype).type)
        params = ref.make_params(seed + 54, dims)
        # float32 expert matrices of 3072 x 3072 under "highest" ask
        # Mosaic for 124 MB of VMEM in ``fm_ffn_fwd`` (the described
        # chip's compiler refuses the chunk program), so the float32 case
        # computes its routed rows through ``ragged_dot`` and holds the
        # attention and the two pools to the reference; bfloat16, the
        # served form, runs the kernel
        form = moe.routed_rows_form
        if dtype == "float32":
            moe.routed_rows_form = lambda cfg: "routed_rows"
        try:
            ok &= _trinity_case(params, cfg, ref, dims, seed)
        finally:
            moe.routed_rows_form = form
        del params
        gc.collect()
    return ok


def _trinity_case(params, cfg, ref, dims, seed):
    """The engine over TWO page pools at published widths: a prompt of
    4500 tokens in five chunks (the window of 4096 is crossed inside the
    prompt, the pages behind it go back), one of 3900 (crossed while it
    decodes 300 tokens) and one of 700 (a whole prompt, never crossed);
    every visible row of logits against the plain reference's forward over
    the engine's own stream, and the kernels' arm (``fm_paged_decode`` and
    ``fm_flash_span`` under a window) against the gather arm.  ``float32``
    is held to F32_TOL at every row; ``bfloat16`` three rows in four to
    BF16_TOL and every row to half the logits' scale, every token to
    BF16_TOL of the reference's best (``_serve_case``'s rule behind a
    router)."""
    import jax
    import jax.numpy as jnp

    from flashmoe_tpu.serving.engine import Request, ServeConfig

    strict = cfg.dtype == jnp.float32
    tol = F32_TOL if strict else BF16_TOL
    rng = np.random.default_rng(seed)
    lens, news = (4500, 3900, 700), (60, 300, 40)
    reqs = [Request(rid=i, prompt=tuple(int(t) for t in
                                        rng.integers(1, cfg.vocab_size, n)),
                    max_new_tokens=m)
            for i, (n, m) in enumerate(zip(lens, news))]
    serve = ServeConfig(max_batch=4, page_size=16, num_pages=1024,
                        max_pages_per_slot=320, ctx_bucket_pages=64,
                        prompt_bucket=256, prefill_chunk=1024)
    with (jax.default_matmul_precision("highest") if strict
          else contextlib.nullcontext()):
        t0 = time.perf_counter()
        outputs, seen, summary = run_engine(params, cfg, serve, reqs,
                                            [0, 0, 3])
        run_s = time.perf_counter() - t0
        with gather_arm():
            g_out, g_seen, g_summary = run_engine(params, cfg, serve, reqs,
                                                  [0, 0, 3])
    differ, n_rows, arm_err, arm_far = compare_arms(
        reqs, (outputs, seen), (g_out, g_seen), tol)
    row_errs, gaps = [], []
    for r in reqs:
        got, t0_ = list(outputs[r.rid]), len(r.prompt)
        n = len(got) - t0_
        t_run = -(-len(got) // 256) * 256
        toks = np.zeros((t_run,), np.int32)
        toks[:len(got)] = got
        want = np.asarray(ref.forward_logits(
            params, dims, jnp.asarray(toks),
            jnp.arange(t0_ - 1, t0_ + n - 1)))
        scale = float(np.abs(want).max())
        gaps += [float(want[j].max() - want[j, got[t0_ + j]]) / scale
                 for j in range(n)]
        row_errs += [float(np.abs(seen[r.rid][j] - want[j]).max()) / scale
                     for j in sorted(seen[r.rid]) if j < n]
    row_errs, gaps = np.asarray(row_errs), np.asarray(gaps)
    complete = all(len(outputs[r.rid]) == len(r.prompt) + r.max_new_tokens
                   for r in reqs)
    if strict:
        ok = bool(complete and not differ and row_errs.max() <= tol
                  and gaps.max() <= tol and (arm_err or 0) <= tol)
    else:
        ok = bool(complete and np.quantile(row_errs, 0.75) <= tol
                  and row_errs.max() <= 0.5 and gaps.max() <= tol
                  and arm_far <= n_rows // 4)
    ok &= summary["decode_kernel_steps"] > 0
    ok &= g_summary["decode_kernel_steps"] == 0
    emit({"phase": "serve", "case": "trinity",
          "dtype": jnp.dtype(cfg.dtype).name, "ok": ok,
          "widths": {"H": cfg.hidden_size, "I": cfg.intermediate_size,
                     "E": cfg.num_experts, "held": cfg.experts_held,
                     "k": cfg.expert_top_k, "heads": cfg.num_heads,
                     "kv_heads": cfg.resolved_num_kv_heads,
                     "head_dim": cfg.resolved_head_dim,
                     "window": cfg.attn_window, "vocab": cfg.vocab_size},
          "cut": {"num_layers": "2 of 60: a window and a full layer"},
          "prompts": lens, "new_tokens": news,
          "rows_vs_reference": {"n": len(row_errs),
                                "max": float(row_errs.max()),
                                "p75": float(np.quantile(row_errs, 0.75))},
          "served_gap_max": float(gaps.max()),
          "arms": {"requests_differ": differ, "rows": n_rows,
                   "max": arm_err, "far": arm_far},
          "kernel_steps": summary["decode_kernel_steps"],
          "evictions": summary["evictions"],
          "run_s_with_compile": round(run_s, 3)})
    return ok


def phase_serve_hybrid(seed):
    """The engine over two kinds of state: Ling-3.0-flash's widths, three
    layers (a dense 'kda' layer, a mixture 'kda' layer, a mixture 'mla'
    layer), one chip's quarter of the experts and of the vocabulary;
    per-slot delta-rule state beside a latent pool of ONE layer, whole
    and chunked prefill (the prompt of 33 crosses a 16-token chunk), four
    slots.  Against ``generate()`` and its logits, as :func:`phase_serve`.
    Then the other pairing: LFM2-24B-A2B's widths, per-slot convolution
    inputs beside a K/V pool of 64-wide heads; then NVIDIA-Nemotron-3-Nano-
    30B-A3B's: a state-space state beside a two-head K/V pool, every
    layer a mixer or a mixture alone; then LongCat-Flash-Omni's: a mixture
    joined a sublayer after it is read, identity experts."""
    import jax
    import jax.numpy as jnp

    from flashmoe_tpu.models.presets import PRESETS
    from flashmoe_tpu.models.transformer import init_params
    from flashmoe_tpu.serving import engine as eng

    cfg = PRESETS["ling-3.0-flash"](
        num_layers=3, first_k_dense=1, layer_mixers=("kda", "kda", "mla"),
        experts_held=128, vocab_size=39296)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    serve = eng.ServeConfig(max_batch=4, page_size=16, num_pages=256,
                            max_pages_per_slot=4, ctx_bucket_pages=4,
                            prompt_bucket=16, prefill_chunk=16)
    cut = ("num_layers 42 -> 3 (layers 0, 6 and 11: dense kda, mixture "
           "kda, mixture mla), experts 512 -> 128 held (routed over 512 in "
           "8 groups), vocab 157184 -> 39296: f32 weights "
           f"{sum(a.nbytes for a in jax.tree.leaves(params)) / 2**30:.2f} "
           "GiB")
    ok = _serve_case(params, cfg.replace(dtype=jnp.float32), serve, seed,
                     cut + "; dtype bf16 -> f32, matmul precision highest",
                     {"phase_of": "serve_hybrid"})
    gc.collect()
    # bf16, the same three mixers over DENSE layers (no router): the
    # per-slot state, the convolution's carried inputs across the chunk,
    # the latent pool and the batching, held to BF16_TOL at every token
    # and every row.  What the routed case below reads beyond that is the
    # router's (8 of 512 experts inside 4 of 8 groups, sigmoid scores a
    # few 1e-3 apart: the arms' roundings flip a choice)
    dense = cfg.replace(first_k_dense=cfg.num_layers)
    ok &= _serve_case(init_params(jax.random.PRNGKey(seed), dense), dense,
                      serve, seed, cut + "; every layer dense (no router)",
                      {"phase_of": "serve_hybrid"})
    gc.collect()
    ok &= _serve_case(params, cfg, serve, seed, cut,
                      {"phase_of": "serve_hybrid"})
    del params
    gc.collect()
    # the other pairing of the hybrid cache: LFM2-24B-A2B's widths, three
    # layers (a dense 'conv' layer, a mixture attention layer with q/k
    # norm over 8 K/V heads of 64, a mixture 'conv' layer), every expert:
    # per-slot convolution inputs beside a K/V pool whose rows hold two
    # heads; the decode kernel handed those rows against the gather arm
    cfg = PRESETS["lfm2-24b-a2b"](
        num_layers=3, first_k_dense=1, layer_mixers=("conv", "mha", "conv"))
    params = init_params(jax.random.PRNGKey(seed), cfg)
    cut = ("LFM2-24B-A2B, num_layers 40 -> 3 (layers 0, 2 and 3: dense "
           "conv, mixture attention, mixture conv), 64 experts and the "
           "whole vocabulary: f32 weights "
           f"{sum(a.nbytes for a in jax.tree.leaves(params)) / 2**30:.2f} "
           "GiB")
    ok &= _serve_case(params, cfg.replace(dtype=jnp.float32), serve, seed,
                      cut + "; dtype bf16 -> f32, matmul precision highest",
                      {"phase_of": "serve_hybrid"})
    gc.collect()
    ok &= _serve_case(params, cfg, serve, seed, cut,
                      {"phase_of": "serve_hybrid"})
    del params
    gc.collect()
    # a third pairing, and layers that are ONE thing: NVIDIA-Nemotron-3-
    # Nano-30B-A3B's widths, four layers ``MEM*`` (a state-space mixer, a
    # mixture of ungated relu^2 experts of width 1856 with 64 of 128 held,
    # a second mixer, attention of 32 query heads over 2 K/V heads with no
    # rotary embedding), half the vocabulary: a float32 state [64, 64, 128]
    # a slot beside a K/V pool of ONE layer, the state carried across the
    # 16-token chunk, the experts through ``fm_ffn_fwd`` at a width of 14.5
    # lanes
    cfg = PRESETS["nemotron-3-nano-30b-a3b"](
        pattern="MEM*", experts_held=64, vocab_size=65536)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    cut = ("NVIDIA-Nemotron-3-Nano-30B-A3B, num_layers 52 -> 4 (MEM*), "
           "experts 128 -> 64 held (routed over 128), vocab 131072 -> "
           "65536: f32 weights "
           f"{sum(a.nbytes for a in jax.tree.leaves(params)) / 2**30:.2f} "
           "GiB")
    ok &= _serve_case(params, cfg.replace(dtype=jnp.float32), serve, seed,
                      cut + "; dtype bf16 -> f32, matmul precision highest",
                      {"phase_of": "serve_hybrid"})
    gc.collect()
    ok &= _serve_case(params, cfg, serve, seed, cut,
                      {"phase_of": "serve_hybrid"})
    del params
    gc.collect()
    # a changed residual path and experts that compute nothing:
    # LongCat-Flash-Omni's widths, ONE published layer = two latent
    # sublayers of 64 heads with both rank scales, two dense FFNs of 12288
    # and one mixture read at the first and joined after the second, a
    # 768-wide softmax router of which 256 outputs are identity experts,
    # 16 of 512 FFN experts held, an eighth of the vocabulary: a latent
    # pool of TWO layers, ``fm_latent_decode`` at a [64, 640] query a slot
    # against the gather arm, the routed rows through ``fm_ffn_fwd`` in
    # windows of the plan (``ops/moe.rows_plan``)
    cfg = PRESETS["longcat-flash"](num_layers=2, experts_held=16,
                                   vocab_size=16384)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    cut = ("LongCat-Flash-Omni, num_layers 28 -> 1 published (2 "
           "sublayers: 'dense+moe', 'dense+join'), FFN experts 512 -> 16 "
           "held (routed over 768 outputs, 256 of them identity), vocab "
           "131072 -> 16384: f32 weights "
           f"{sum(a.nbytes for a in jax.tree.leaves(params)) / 2**30:.2f} "
           "GiB")
    ok &= _serve_case(params, cfg.replace(dtype=jnp.float32), serve, seed,
                      cut + "; dtype bf16 -> f32, matmul precision highest",
                      {"phase_of": "serve_hybrid"})
    gc.collect()
    ok &= _serve_case(params, cfg, serve, seed, cut,
                      {"phase_of": "serve_hybrid"})
    return ok


# ----------------------------------------------------------------------
# train: the real CLI, three steps; step 0 against the XLA path
# ----------------------------------------------------------------------

def phase_train(seed):
    """``--batch``/``sequence_len`` are cut from 8 x 8192 because the
    state alone (f32 weights + Adam moments of 64 experts) is 8.77 GB:
    the chip's compiler counts 20.3 GB for 1 x 8192, 16.3 GB for
    3 x 4096 and 14.3 GB for 2 x 4096 of its 15.75 GB."""
    del seed  # the CLI seeds its own state (PRNGKey(0)) and batches
    import jax

    from flashmoe_tpu.runtime import bootstrap, train_cli
    from flashmoe_tpu.runtime.trainer import (
        init_state, make_optimizer, make_train_step,
    )

    os.makedirs("chiprun_out", exist_ok=True)
    argv = ["--preset", "flashmoe-reference", "--synthetic", "--steps", "3",
            "--log-every", "1", "--metrics-jsonl",
            os.path.join("chiprun_out", "train_metrics.jsonl"),
            "--batch", str(TRAIN_BATCH), "--set",
            f"sequence_len={TRAIN_SEQ}"]
    log, out = io.StringIO(), io.StringIO()
    t0 = time.perf_counter()
    with contextlib.redirect_stderr(log), contextlib.redirect_stdout(out):
        rc = train_cli.main(argv)
    cli_s = time.perf_counter() - t0
    steps = [json.loads(line) for line in log.getvalue().splitlines()
             if line.startswith('{"step"')]
    losses = [s["loss"] for s in steps]
    summary = json.loads(out.getvalue().strip().splitlines()[-1])
    gc.collect()

    # the same step 0 through trainer.make_train_step(use_pallas=False)
    rt = bootstrap.get_runtime()
    cfg, mesh = rt.cfg, rt.mesh
    opt = make_optimizer(cfg, lr=3e-4, total_steps=3)
    batch = next(train_cli._synthetic_batches(cfg, TRAIN_BATCH))

    def compile_step(use_pallas):
        step = make_train_step(cfg, mesh, opt, use_pallas=use_pallas)
        shapes = jax.eval_shape(
            lambda: init_state(jax.random.PRNGKey(0), cfg, opt))
        t0 = time.perf_counter()
        compiled = step.lower(shapes, batch).compile()
        return (compiled, compiled.as_text().count("tpu_custom_call"),
                time.perf_counter() - t0)

    xla_step, xla_kernels, xla_compile_s = compile_step(False)
    _, m = xla_step(init_state(jax.random.PRNGKey(0), cfg, opt), batch)
    xla_loss = float(m["loss"])
    del xla_step, m
    gc.collect()
    _, pallas_kernels, pallas_compile_s = compile_step(None)
    err = abs(losses[0] - xla_loss) / abs(xla_loss) if losses else None
    ok = (rc == 0 and len(losses) == 3 and bool(np.isfinite(losses).all())
          and err is not None and err <= BF16_TOL
          and pallas_kernels > 0 and xla_kernels == 0)
    emit({"phase": "train", "ok": ok, "argv": argv,
          "cut": f"batch 8 -> {TRAIN_BATCH}, sequence_len 8192 -> "
                 f"{TRAIN_SEQ} (f32 state 8.77 GB; see phase_train)",
          "widths": {"E": cfg.num_experts, "k": cfg.expert_top_k,
                     "H": cfg.hidden_size, "I": cfg.intermediate_size,
                     "layers": cfg.num_layers, "vocab": cfg.vocab_size},
          "losses": losses, "cli_s_three_steps_with_compile": round(cli_s, 3),
          "final_loss_from_cli_summary": summary.get("final_loss"),
          "step0_loss_xla_path": xla_loss, "step0_rel_err": err,
          "tolerance": BF16_TOL,
          "tpu_custom_calls": {"default": pallas_kernels,
                               "use_pallas_false": xla_kernels},
          "step_compile_s": {"default": round(pallas_compile_s, 3),
                             "use_pallas_false": round(xla_compile_s, 3)}})
    return ok


# ----------------------------------------------------------------------
# --chips 4: expert parallelism over the mesh
# ----------------------------------------------------------------------

def _ep4_setup(seed):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from flashmoe_tpu.config import BENCH_CONFIGS
    from flashmoe_tpu.models.reference import init_moe_params
    from flashmoe_tpu.parallel.mesh import make_mesh

    cfg = BENCH_CONFIGS["reference"].replace(ep=4)
    mesh = make_mesh(cfg, dp=1)
    # made on the host and placed shard by shard: nothing is ever whole
    # on the first chip
    with jax.default_device(jax.devices("cpu")[0]):
        host = init_moe_params(jax.random.PRNGKey(seed), cfg)
        x_host = jax.random.normal(
            jax.random.PRNGKey(seed + 1), (cfg.tokens, cfg.hidden_size),
            jnp.float32).astype(cfg.dtype)
    params = {k: jax.device_put(v, NamedSharding(
        mesh, P() if k == "gate_w" else P("ep"))) for k, v in host.items()}
    x = jax.device_put(x_host, NamedSharding(mesh, P("ep", None)))
    return cfg, mesh, host, x_host, params, x


def phase_ep4_layer(seed, shared):
    """(a) ``ep_moe_layer`` (collective transport, Pallas experts) against
    the one-chip ``moe_layer`` run shard by shard at the per-rank
    capacity — the repo's own EP equivalence (tests/test_ep.py)."""
    import jax
    import jax.numpy as jnp

    import flashmoe_tpu as fm
    from flashmoe_tpu.parallel.ep import ep_moe_layer, local_capacity

    cfg, mesh, host, x_host, params, x = shared
    fn = jax.jit(lambda p, x: ep_moe_layer(p, x, cfg, mesh,
                                           use_pallas=True).out)
    t0 = time.perf_counter()
    compiled = fn.lower(params, x).compile()
    compile_s = time.perf_counter() - t0
    text = compiled.as_text()
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(params, x))
    run_s = time.perf_counter() - t0
    shared.append(out)

    s_loc = cfg.tokens // 4
    cap = local_capacity(cfg, s_loc)
    one = jax.devices()[0]
    cfg1 = cfg.replace(ep=1)
    p1 = jax.device_put(host, one)
    single = jax.jit(lambda p, x: fm.moe_layer(p, x, cfg1, capacity=cap).out)
    want = jnp.concatenate([
        single(p1, jax.device_put(x_host[r * s_loc:(r + 1) * s_loc], one))
        for r in range(4)])
    err = _rel_err(out, want)
    out_devs = sorted(s.device.id for s in out.addressable_shards)
    experts_per_dev = {s.device.id: s.data.shape[0]
                      for s in params["w_up"].addressable_shards}
    spread = (len(set(out_devs)) == 4
              and sorted(experts_per_dev.values()) == [16] * 4
              and all(s.data.shape[0] == s_loc
                      for s in out.addressable_shards))
    ok = (spread and err <= BF16_TOL and "tpu_custom_call" in text
          and "all-to-all" in text)
    emit({"phase": "ep4_layer", "ok": ok, "cut": "none (reference, ep=4)",
          "moe_backend": cfg.moe_backend, "output_devices": out_devs,
          "experts_per_device": experts_per_dev,
          "all_to_all_ops": text.count("all-to-all("),
          "tpu_custom_calls": text.count("tpu_custom_call"),
          "compile_s": round(compile_s, 3), "run_s": round(run_s, 4),
          "rel_err_vs_one_chip": err, "tolerance": BF16_TOL})
    return ok


def phase_ep4_fused(seed, shared):
    """(b) ``fused_ep_moe_layer`` (in-kernel RDMA, ``interpret=False``)
    against (a), under a wall-clock limit of its own: a semaphore that
    is never signalled must become a failure, not a hung call."""
    import jax

    from flashmoe_tpu.parallel.fused import fused_ep_moe_layer

    cfg, mesh, _, _, params, x = shared[:6]
    if len(shared) < 7:
        emit({"phase": "ep4_fused", "ok": False,
              "outcome": "not run: (a) gave nothing to compare with"})
        return False
    want = shared[6]
    box = {}

    def work():
        try:
            fn = jax.jit(lambda p, x: fused_ep_moe_layer(
                p, x, cfg, mesh, interpret=False).out)
            t0 = time.perf_counter()
            compiled = fn.lower(params, x).compile()
            box["compile_s"] = round(time.perf_counter() - t0, 3)
            box["tpu_custom_calls"] = compiled.as_text().count(
                "tpu_custom_call")
            t0 = time.perf_counter()
            out = jax.block_until_ready(compiled(params, x))
            box["run_s"] = round(time.perf_counter() - t0, 4)
            box["rel_err_vs_ep4_layer"] = _rel_err(out, want)
        except Exception as e:  # noqa: BLE001 — the outcome IS the result
            box["error"] = f"{type(e).__name__}: {str(e)[:600]}"

    t = threading.Thread(target=work, daemon=True)
    t.start()
    t.join(FUSED_LIMIT_S)
    if t.is_alive():
        # the chip is wedged under this thread: say so and leave at once,
        # nothing after this could run on it
        emit({"phase": "ep4_fused", "ok": False, "outcome": "timeout",
              "limit_s": FUSED_LIMIT_S, **box})
        sys.stdout.flush()
        os._exit(1)
    if "error" in box:
        outcome = "refused" if "compile_s" not in box else "failed"
    elif box["rel_err_vs_ep4_layer"] > BF16_TOL:
        outcome = "mismatch"
    else:
        outcome = "passes"
    ok = outcome == "passes" and box.get("tpu_custom_calls", 0) > 0
    emit({"phase": "ep4_fused", "ok": ok, "outcome": outcome,
          "cut": "none (reference, ep=4)", "tolerance": BF16_TOL, **box})
    return ok


def phase_ep4_serve(seed, shared):
    """(c) ``ServeConfig(ep_shards=4)`` decode at the serve phase's
    widths, token streams against the one-chip engine's.  The engine
    refuses shared experts under EP decode, so the preset as published
    is tried first (its refusal is the finding) and the path is then
    driven with the two shared experts left out, said in ``cut``."""
    del shared
    import jax
    from jax.sharding import Mesh, NamedSharding

    from flashmoe_tpu.models.transformer import init_params
    from flashmoe_tpu.serving import engine as eng

    knobs = dict(max_batch=4, page_size=16, num_pages=SERVE_PAGES,
                 max_pages_per_slot=4, ctx_bucket_pages=4, prompt_bucket=16)
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("ep",))
    cpu = jax.devices("cpu")[0]

    def drive(cfg, label, cut):
        try:  # what the engine refuses, it refuses before it reads weights
            eng.ServingEngine({}, cfg, eng.ServeConfig(ep_shards=4, **knobs),
                              mesh=mesh).close()
        except ValueError as e:
            emit({"phase": label, "ok": False, "outcome": "refused",
                  "cut": cut, "error": f"ValueError: {str(e)[:600]}"})
            return False
        except Exception:  # noqa: BLE001 — not a refusal: drive it for real
            pass
        reqs, arrivals = serve_requests(cfg.vocab_size, seed)
        with jax.default_device(cpu):
            host = init_params(jax.random.PRNGKey(seed), cfg)
        t0 = time.perf_counter()
        try:
            specs = eng._ep_param_specs(host, cfg)
            sharded = jax.tree.map(
                lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
                host, specs)
            got, seen, summ = run_engine(
                sharded, cfg, eng.ServeConfig(ep_shards=4, **knobs),
                reqs, arrivals, mesh=mesh)
        except Exception as e:  # noqa: BLE001 — the outcome IS the result
            emit({"phase": label, "ok": False, "outcome": "refused"
                  if isinstance(e, ValueError) else "failed", "cut": cut,
                  "error": f"{type(e).__name__}: {str(e)[:600]}"})
            return False
        ep_s = time.perf_counter() - t0
        per_dev = {s.device.id: s.data.shape[0] for s in
                   sharded["layers"][0]["moe"]["w_up"].addressable_shards}
        del sharded
        gc.collect()
        one = jax.device_put(host, jax.devices()[0])
        want, _, _ = run_engine(one, cfg, eng.ServeConfig(**knobs),
                                reqs, arrivals)
        differ = [r.rid for r in reqs
                  if list(got[r.rid]) != list(want[r.rid])]
        # where a stream parts from the one-chip engine's, it must part at
        # a near-tie of the reference's logits (as in the serve phase)
        bad, near_ties, _, errs = check_streams(cfg, one, reqs, got, seen,
                                                BF16_TOL)
        ok = (not bad and summ["completed"] == len(reqs)
              and sorted(per_dev.values()) == [cfg.num_experts // 4] * 4)
        emit({"phase": label, "ok": ok, "cut": cut,
              "outcome": ("mismatch" if not ok else "passes" if not differ
                          else "passes, streams part at near-ties"),
              "completed": summ["completed"], "requests": len(reqs),
              "experts_per_device": per_dev,
              "streams_differing_from_one_chip_engine": differ,
              "near_ties": near_ties[:8], "failures": bad[:8],
              "logits_compared": len(errs),
              "logits_over_tolerance": int((np.asarray(errs)
                                            > BF16_TOL).sum()),
              "logits_rel_err_max": float(max(errs)) if errs else None,
              "tolerance": BF16_TOL, "ep4_s_with_compile": round(ep_s, 3)})
        return ok

    depth = f"num_layers 28 -> {SERVE_LAYERS}"
    ok = drive(serve_config(), "ep4_serve", depth)
    ok &= drive(serve_config(num_shared_experts=0), "ep4_serve_no_shared",
                depth + ", num_shared_experts 2 -> 0 (EP decode has no "
                        "shared-expert arm)")
    return ok


# ----------------------------------------------------------------------

ONE_CHIP = {"layer": phase_layer, "serve": phase_serve,
            "serve_hybrid": phase_serve_hybrid, "train": phase_train}
#: parts of a phase that ``--only`` may name alone (a whole run has them
#: inside their phase)
PARTS = {"serve_sdar": phase_serve_sdar,
         "serve_trinity": phase_serve_trinity}
FOUR_CHIPS = {"ep4_layer": phase_ep4_layer, "ep4_fused": phase_ep4_fused,
              "ep4_serve": phase_ep4_serve}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--only", default="",
                    help="comma-separated phases to run, of the chips' own")
    args = ap.parse_args(argv)

    import jax

    from flashmoe_tpu.utils.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py needs a TPU; JAX found platform "
              f"{dev.platform!r}.  It never runs on the CPU.",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} chips; JAX found "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    hits = {"hit": 0, "miss": 0}

    def count(event, **_):
        if event.endswith("/cache_hits"):
            hits["hit"] += 1
        elif event.endswith("/cache_misses"):
            hits["miss"] += 1

    jax.monitoring.register_event_listener(count)

    phases = ONE_CHIP if args.chips == 1 else FOUR_CHIPS
    if args.only:
        known = dict(phases, **(PARTS if args.chips == 1 else {}))
        phases = {name: known[name] for name in args.only.split(",")}
    shared = None
    failed = []
    t_all = time.perf_counter()
    for name in phases:
        t0 = time.perf_counter()
        try:
            if args.chips == 1:
                ok = phases[name](args.seed)
            else:
                if shared is None:
                    shared = list(_ep4_setup(args.seed))
                ok = phases[name](args.seed, shared)
        except Exception as e:  # noqa: BLE001 — printed, counted, exit != 0
            traceback.print_exc()
            emit({"phase": name, "ok": False,
                  "error": f"{type(e).__name__}: {str(e)[:600]}"})
            ok = False
        if not ok:
            failed.append(name)
        print(f"# {name}: {'ok' if ok else 'FAILED'} in "
              f"{time.perf_counter() - t0:.1f} s", file=sys.stderr,
              flush=True)
        gc.collect()
    emit({"phase": "summary", "failed": failed, "phases": list(phases),
          "total_s": round(time.perf_counter() - t_all, 3),
          "compile_cache_dir": cache_dir, "compile_cache_hits": hits["hit"],
          "compile_cache_misses": hits["miss"],
          "peak_bytes_in_use": (dev.memory_stats() or {}).get(
              "peak_bytes_in_use")})
    if failed:
        return 1
    emit({"ok": True, "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
