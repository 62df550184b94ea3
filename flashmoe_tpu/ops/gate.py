"""Fused MoE gate (router): GEMM + softmax + top-k + expert counts.

TPU-native re-design of the reference's ``FusedGate``
(``csrc/include/flashmoe/moe/gate.cuh:93-720``), which fuses the gate GEMM
with an in-register online softmax, online top-k, and a CUB BlockScan token
compaction, using a block-ring over SMs when E exceeds one CUDA tile
(``gate.cuh:229-269, 321-390``).

On TPU none of that choreography is needed: one Pallas grid step owns a full
``[BLOCK_M, E_padded]`` logits tile in VMEM, so softmax and top-k are simple
vector ops after an MXU matmul — the "multi-block ring" collapses to a wider
lane dimension.  The kernel additionally accumulates the two statistics the
reference gathers for its aux loss (``gate.cuh:273-299``): per-expert
softmax-probability sums and per-expert top-k selection counts.

Two implementations with identical semantics:
  * :func:`router_xla` — plain jnp/lax, used as fallback and oracle.
  * :func:`router_pallas` — fused Pallas kernel (matmul + softmax + top-k +
    stats in one VMEM-resident pass).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flashmoe_tpu.config import BLOCK_M, LANE, MoEConfig
from flashmoe_tpu.utils.telemetry import trace_span


class RouterOutput(NamedTuple):
    """Routing decisions for one token shard.

    combine_weights: [S, K] normalized weights of the selected experts.
    expert_idx:      [S, K] int32 selected expert ids.
    expert_counts:   [E]    int32 number of (token, k) selections per expert.
    probs_mean:      [E]    mean softmax probability per expert (aux loss).
    aux_loss:        []     load-balancing loss (Switch-style).
    z_loss:          []     router z-loss (0 unless enabled).
    """

    combine_weights: jax.Array
    expert_idx: jax.Array
    expert_counts: jax.Array
    probs_mean: jax.Array
    aux_loss: jax.Array
    z_loss: jax.Array


def _finish(cfg: MoEConfig, top_p, top_idx, probs_sum, counts, zsum, s_tokens):
    """Shared epilogue: normalize top-k weights (unless the config says
    the published model does not: ``norm_topk_prob``), scale them by
    ``routed_scaling_factor``, form aux/z losses."""
    if cfg.norm_topk_prob:
        denom = jnp.sum(top_p, axis=-1, keepdims=True)
        top_p = top_p / jnp.maximum(denom, 1e-20)
    if cfg.routed_scaling_factor != 1.0:
        top_p = top_p * cfg.routed_scaling_factor
    combine_weights = top_p.astype(cfg.accum_dtype)
    probs_mean = probs_sum / s_tokens
    density = counts.astype(cfg.accum_dtype) / (s_tokens * cfg.expert_top_k)
    # Switch-transformer load-balance loss: E * sum(density * mean_prob).
    aux = cfg.router_width * jnp.sum(density * probs_mean) * cfg.expert_top_k
    z = (zsum / s_tokens) * cfg.router_z_loss_coef
    return RouterOutput(
        combine_weights=combine_weights,
        expert_idx=top_idx.astype(jnp.int32),
        expert_counts=counts.astype(jnp.int32),
        probs_mean=probs_mean,
        aux_loss=aux.astype(cfg.accum_dtype),
        z_loss=z.astype(cfg.accum_dtype),
    )


# ----------------------------------------------------------------------
# XLA reference path
# ----------------------------------------------------------------------

def limit_to_groups(select, cfg: MoEConfig):
    """Group-limited selection.  select: [S, E] selection scores, the
    experts in ``n_group`` equal consecutive groups.  A group scores the
    sum of its two largest; the ``topk_group`` best groups are kept and
    every expert outside them gets -inf, so the top-k that follows is
    taken inside them."""
    with trace_span("moe.route_groups"):
        s, g = select.shape[0], cfg.n_group
        grouped = select.reshape(s, g, -1)
        group_score = jnp.sum(
            jax.lax.top_k(grouped, min(2, grouped.shape[-1]))[0], axis=-1)
        _, kept = jax.lax.top_k(group_score, cfg.topk_group)     # [S, tg]
        keep = jnp.any(kept[:, :, None] == jnp.arange(g)[None, None, :],
                       axis=1)                                   # [S, g]
        return jnp.where(keep[:, :, None], grouped,
                         -jnp.inf).reshape(select.shape)


def router_xla(x, gate_w, cfg: MoEConfig, gate_bias=None) -> RouterOutput:
    """Router in plain XLA ops. x: [S, H], gate_w: [H, E] (E the router's
    width: ``cfg.router_width``, the zero-compute experts of
    ``cfg.zero_experts`` behind the FFN experts; scores, selection, counts
    and statistics run over all of it, and a chosen index >=
    ``num_experts`` is the caller's to read as the identity).

    ``cfg.router_score='sigmoid'``: the scores are sigmoid(logits); the
    top-k is taken over ``scores + gate_bias`` (``gate_bias`` [E]: the
    published ``e_score_correction_bias``, which steers the SELECTION and
    nothing else) and the combine weights are the chosen experts' own
    scores, normalised and scaled in :func:`_finish`.  ``cfg.n_group`` >
    1 limits the choice to the best groups (:func:`limit_to_groups`); one
    group traces what it always traced.  The load-balance
    statistics read the scores normalised over the experts."""
    s = x.shape[0]
    logits = jnp.dot(
        x.astype(cfg.accum_dtype),
        gate_w.astype(cfg.accum_dtype),
        preferred_element_type=cfg.accum_dtype,
    )
    from flashmoe_tpu.chaos import inject as chaos_inject

    if chaos_inject.is_armed("skewed_routing"):  # trace-time check only
        logits = chaos_inject.poison_logits(logits)
    if (cfg.router_score == "sigmoid" or gate_bias is not None
            or cfg.n_group > 1):
        scores = (jax.nn.sigmoid(logits) if cfg.router_score == "sigmoid"
                  else jax.nn.softmax(logits, axis=-1))
        select = scores if gate_bias is None else (
            scores + gate_bias.astype(scores.dtype)[None, :])
        if cfg.n_group > 1:
            select = limit_to_groups(select, cfg)
        _, top_idx = jax.lax.top_k(select, cfg.expert_top_k)
        top_p = jnp.take_along_axis(scores, top_idx, axis=-1)
        probs = scores / jnp.maximum(
            jnp.sum(scores, axis=-1, keepdims=True), 1e-20)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_idx = jax.lax.top_k(probs, cfg.expert_top_k)
    counts = jnp.sum(
        jax.nn.one_hot(top_idx, cfg.router_width, dtype=jnp.int32),
        axis=(0, 1))
    zsum = jnp.sum(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return _finish(cfg, top_p, top_idx, jnp.sum(probs, axis=0), counts, zsum, s)


# ----------------------------------------------------------------------
# Pallas fused kernel
# ----------------------------------------------------------------------

def _gate_kernel(x_ref, w_ref, top_p_ref, top_i_ref, stats_ref, *, k, e, px):
    """One grid step: route BLOCK_M tokens.

    stats_ref accumulates [3, PX]: row 0 = sum of softmax probs, row 1 =
    top-k selection counts, row 2 = z-loss partial (lane 0 only).
    """
    logits = jnp.dot(
        x_ref[:].astype(jnp.float32),
        w_ref[:].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )  # [BM, PX]
    bm = logits.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (bm, px), 1)
    neg = jnp.float32(-1e30)
    logits = jnp.where(col < e, logits, neg)

    # numerically-stable softmax over the (padded) expert axis
    m = jnp.max(logits, axis=-1, keepdims=True)
    ex = jnp.where(col < e, jnp.exp(logits - m), 0.0)
    se = jnp.sum(ex, axis=-1, keepdims=True)
    probs = ex / se

    # z-loss partial: logsumexp = m + log(se)  (kept 2D for TPU layouts)
    lse = m + jnp.log(se)
    zpart = jnp.sum(jnp.square(lse))

    # iterative top-k (K is small and static -> unrolled)
    p = probs
    sel_count = jnp.zeros((bm, px), jnp.float32)
    top_ps, top_is = [], []
    for _ in range(k):
        mx = jnp.max(p, axis=-1, keepdims=True)
        is_max = (p == mx) & (col < e)
        idx = jnp.min(jnp.where(is_max, col, px), axis=-1, keepdims=True)
        hit = col == idx
        top_ps.append(mx)
        top_is.append(idx)
        sel_count = sel_count + hit.astype(jnp.float32)
        p = jnp.where(hit, neg, p)
    top_p_ref[:] = jnp.concatenate(top_ps, axis=1)
    top_i_ref[:] = jnp.concatenate(top_is, axis=1)

    first = pl.program_id(0) == 0

    @pl.when(first)
    def _():
        stats_ref[:] = jnp.zeros_like(stats_ref)

    row = jax.lax.broadcasted_iota(jnp.int32, (8, px), 0)
    lane0 = jax.lax.broadcasted_iota(jnp.int32, (8, px), 1) == 0
    update = (
        jnp.where(row == 0, jnp.sum(probs, axis=0)[None, :], 0.0)
        + jnp.where(row == 1, jnp.sum(sel_count, axis=0)[None, :], 0.0)
        + jnp.where((row == 2) & lane0, zpart, 0.0)
    )
    stats_ref[:] = stats_ref[:] + update


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def router_pallas(x, gate_w, cfg: MoEConfig, interpret: bool = False
                  ) -> RouterOutput:
    """Fused gate on TPU. x: [S, H], gate_w: [H, E]. S must divide by 8."""
    s, h = x.shape
    e, k = cfg.num_experts, cfg.expert_top_k
    px = max(LANE, ((e + LANE - 1) // LANE) * LANE)
    if s % 8:
        raise ValueError(f"token count {s} must be a multiple of 8")
    # largest power-of-two row tile (<= BLOCK_M) dividing S, so any S % 8 == 0
    # token count works without padding
    bm = next(b for b in (128, 64, 32, 16, 8) if s % b == 0)
    w_pad = jnp.zeros((h, px), gate_w.dtype).at[:, :e].set(gate_w)

    grid = (s // bm,)
    top_p, top_i, stats = pl.pallas_call(
        functools.partial(_gate_kernel, k=k, e=e, px=px),
        name="fm_router",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, h), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((h, px), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, k), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((8, px), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s, k), jnp.float32),
            jax.ShapeDtypeStruct((s, k), jnp.int32),
            jax.ShapeDtypeStruct((8, px), jnp.float32),
        ],
        interpret=interpret,
    )(x, w_pad)

    probs_sum = stats[0, :e]
    counts = stats[1, :e].astype(jnp.int32)
    zsum = stats[2, 0]
    return _finish(cfg, top_p, top_i, probs_sum, counts, zsum, s)


# ----------------------------------------------------------------------
# Two-pass expert-tiled gate: E beyond one VMEM tile
# ----------------------------------------------------------------------
#
# The reference handles E > one CUDA tile with a block-ring: phase 1
# passes an (max, sum) baton around SMs to form the global softmax
# normalizer, phase 2 rings the top-k (``gate.cuh:93-467``).  The TPU
# equivalent tiles the EXPERT axis across grid steps of one core:
#
#   pass 1 (grid nt x nj, experts inner): logits tile GEMM -> online
#     softmax running (m, se) in VMEM scratch + running top-k merged
#     tile-by-tile (the baton is just kernel-resident state); logits are
#     spilled to HBM so pass 2 need not redo the GEMM.
#   pass 2 (grid nj x nt, tokens inner): re-reads each logits tile with
#     the final (m, se) to accumulate the exact per-expert probability
#     sums / selection counts / z-loss the aux losses need (these are
#     sums over tokens of globally-normalized probs, so they cannot be
#     finalized inside pass 1's running rescale).

_ET = 512  # expert-tile width (lanes) of the two-pass gate


def _gate_pass1_kernel(x_ref, w_ref, *refs, k, e, et, spill):
    """``spill`` controls whether the logits tile is written to HBM for
    pass 2 (training/z-loss stats); inference skips the output entirely —
    at E=16k, S=8k that is a ~0.5 GB write per layer."""
    if spill:
        logits_ref, m_ref, se_ref, tv_ref, ti_ref = refs[:5]
        mrun, serun, topv, topi = refs[5:]
    else:
        logits_ref = None
        m_ref, se_ref, tv_ref, ti_ref = refs[:4]
        mrun, serun, topv, topi = refs[4:]
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    bm = x_ref.shape[0]
    neg = jnp.float32(-1e30)

    @pl.when(j == 0)
    def _():
        mrun[:] = jnp.full_like(mrun, neg)
        serun[:] = jnp.zeros_like(serun)
        topv[:] = jnp.full_like(topv, neg)
        topi[:] = jnp.full_like(topi, -1)

    logits = jnp.dot(
        x_ref[:].astype(jnp.float32), w_ref[:].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )  # [bm, et]
    col = jax.lax.broadcasted_iota(jnp.int32, (bm, et), 1)
    gcol = col + j * et
    logits = jnp.where(gcol < e, logits, neg)
    if spill:
        logits_ref[:] = logits

    # online (max, sum) update with rescale — the softmax baton
    m_old = mrun[:, 0:1]
    mt = jnp.max(logits, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_old, mt)
    ex = jnp.where(gcol < e, jnp.exp(logits - m_new), 0.0)
    se_new = (serun[:, 0:1] * jnp.exp(m_old - m_new)
              + jnp.sum(ex, axis=-1, keepdims=True))
    mrun[:] = jnp.broadcast_to(m_new, mrun.shape)
    serun[:] = jnp.broadcast_to(se_new, serun.shape)

    # tile top-k by logit (same order as by prob), then merge with the
    # carried top-k.  Expert ranges of carried vs tile candidates are
    # disjoint, so indices never collide.
    p = logits
    cand_v, cand_i = [], []
    for _ in range(k):
        mx = jnp.max(p, axis=-1, keepdims=True)
        is_mx = (p == mx) & (gcol < e)
        idx = jnp.min(jnp.where(is_mx, gcol, jnp.int32(2**30)),
                      axis=-1, keepdims=True)
        ok = idx < jnp.int32(2**30)
        cand_v.append(jnp.where(ok, mx, neg))
        cand_i.append(jnp.where(ok, idx, -1))
        p = jnp.where(gcol == idx, neg, p)

    lane = jax.lax.broadcasted_iota(jnp.int32, topv.shape, 1)
    cv, ci = topv[:], topi[:]
    for t in range(k):
        cv = jnp.where(lane == k + t, cand_v[t], cv)
        ci = jnp.where(lane == k + t, cand_i[t], ci)
    nv = jnp.full_like(cv, neg)
    ni = jnp.full_like(ci, -1)
    for t in range(k):
        mx = jnp.max(cv, axis=-1, keepdims=True)
        lsel = jnp.min(jnp.where(cv == mx, lane, jnp.int32(2**30)),
                       axis=-1, keepdims=True)
        hit = lane == lsel
        isel = jnp.max(jnp.where(hit, ci, -1), axis=-1, keepdims=True)
        nv = jnp.where(lane == t, mx, nv)
        ni = jnp.where(lane == t, isel, ni)
        cv = jnp.where(hit, neg, cv)
    topv[:] = nv
    topi[:] = ni

    @pl.when(j == nj - 1)
    def _():
        m_ref[:] = mrun[:]
        se_ref[:] = serun[:]
        tv_ref[:] = topv[:]
        ti_ref[:] = topi[:]


def _gate_pass2_kernel(logits_ref, m_ref, se_ref, ti_ref, stats_ref, *,
                       k, e, et):
    j = pl.program_id(0)
    ii = pl.program_id(1)
    bm = logits_ref.shape[0]

    @pl.when(ii == 0)
    def _():
        stats_ref[:] = jnp.zeros_like(stats_ref)

    m = m_ref[:, 0:1]
    se = se_ref[:, 0:1]
    col = jax.lax.broadcasted_iota(jnp.int32, (bm, et), 1)
    gcol = col + j * et
    probs = jnp.where(gcol < e,
                      jnp.exp(logits_ref[:] - m) / jnp.maximum(se, 1e-30),
                      0.0)
    sel = jnp.zeros((bm, et), jnp.float32)
    for t in range(k):
        sel = sel + (gcol == ti_ref[:, t:t + 1]).astype(jnp.float32)
    # z-loss partial once per token tile (tile j==0 carries it)
    lse = m + jnp.log(jnp.maximum(se, 1e-30))
    zpart = jnp.sum(jnp.square(lse)) * jnp.where(j == 0, 1.0, 0.0)
    row = jax.lax.broadcasted_iota(jnp.int32, (8, et), 0)
    lane0 = jax.lax.broadcasted_iota(jnp.int32, (8, et), 1) == 0
    stats_ref[:] = stats_ref[:] + (
        jnp.where(row == 0, jnp.sum(probs, axis=0)[None, :], 0.0)
        + jnp.where(row == 1, jnp.sum(sel, axis=0)[None, :], 0.0)
        + jnp.where((row == 2) & lane0, zpart, 0.0)
    )


def router_pallas_tiled(x, gate_w, cfg: MoEConfig, interpret: bool = False,
                        need_stats: bool | None = None) -> RouterOutput:
    """Two-pass fused gate for E beyond the single-tile VMEM budget.
    x: [S, H], gate_w: [H, E];  S % 8 == 0, E > _ET recommended.

    ``need_stats=None`` resolves OUTSIDE the jitted core (env vars read
    inside a jit bind at trace time and then stick in the cache):
    training / z-loss configs, ``cfg.collect_stats`` (the flight
    recorder's router-entropy signal wants real probability sums), and
    ``FLASHMOE_GATE_STATS=1`` get the stats pass; plain inference skips
    it (aux fields report zero)."""
    if need_stats is None:
        import os as _os

        need_stats = (cfg.is_training or cfg.router_z_loss_coef > 0
                      or cfg.collect_stats
                      or _os.environ.get("FLASHMOE_GATE_STATS") == "1")
    return _router_pallas_tiled_jit(x, gate_w, cfg, interpret,
                                    bool(need_stats))


@functools.partial(jax.jit,
                   static_argnames=("cfg", "interpret", "need_stats"))
def _router_pallas_tiled_jit(x, gate_w, cfg: MoEConfig, interpret: bool,
                             need_stats: bool) -> RouterOutput:
    s, h = x.shape
    e, k = cfg.num_experts, cfg.expert_top_k
    if s % 8:
        raise ValueError(f"token count {s} must be a multiple of 8")
    if 2 * k > LANE:
        # the carried+candidate top-k merge lives in lanes [0, 2k) of a
        # LANE-wide scratch; beyond that candidates would silently drop
        raise ValueError(f"top_k {k} exceeds the merge buffer ({LANE // 2})")
    et = _ET
    nj = (e + et - 1) // et
    px = nj * et
    bm = next(b for b in (128, 64, 32, 16, 8) if s % b == 0)
    nt = s // bm
    w_pad = jnp.zeros((h, px), gate_w.dtype).at[:, :e].set(gate_w)

    lane_spec = pl.BlockSpec((bm, LANE), lambda i, j: (i, 0),
                             memory_space=pltpu.VMEM)
    lane_shape = jax.ShapeDtypeStruct((s, LANE), jnp.float32)
    out_specs = [lane_spec] * 4
    out_shape = [lane_shape, lane_shape, lane_shape,
                 jax.ShapeDtypeStruct((s, LANE), jnp.int32)]
    if need_stats:
        out_specs = [pl.BlockSpec((bm, et), lambda i, j: (i, j),
                                  memory_space=pltpu.VMEM)] + out_specs
        out_shape = [jax.ShapeDtypeStruct((s, px), jnp.float32)] + out_shape
    res = pl.pallas_call(
        functools.partial(_gate_pass1_kernel, k=k, e=e, et=et,
                          spill=need_stats),
        name="fm_router_pass1",
        grid=(nt, nj),
        in_specs=[
            pl.BlockSpec((bm, h), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((h, et), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bm, LANE), jnp.float32),
            pltpu.VMEM((bm, LANE), jnp.float32),
            pltpu.VMEM((bm, LANE), jnp.float32),
            pltpu.VMEM((bm, LANE), jnp.int32),
        ],
        interpret=interpret,
    )(x, w_pad)
    if need_stats:
        logits, m, se, tv, ti = res
    else:
        m, se, tv, ti = res

    top_l = tv[:, :k]
    top_i = ti[:, :k].astype(jnp.int32)
    top_p = jnp.exp(top_l - m[:, 0:1]) / jnp.maximum(se[:, 0:1], 1e-30)

    if need_stats:
        stats = pl.pallas_call(
            functools.partial(_gate_pass2_kernel, k=k, e=e, et=et),
            name="fm_router_pass2",
            grid=(nj, nt),
            in_specs=[
                pl.BlockSpec((bm, et), lambda j, i: (i, j),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((bm, LANE), lambda j, i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((bm, LANE), lambda j, i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((bm, LANE), lambda j, i: (i, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((8, et), lambda j, i: (0, j),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((8, px), jnp.float32),
            interpret=interpret,
        )(logits, m, se, ti)
        probs_sum = stats[0, :e]
        counts = stats[1, :e].astype(jnp.int32)
        zsum = stats[2, 0]
    else:
        # selection counts are cheap XLA-side; prob sums / z-loss are
        # training-only and reported as zero (aux_loss = 0 at inference —
        # under AD the custom_vjp still backs through router_xla)
        counts = jnp.zeros((e,), jnp.int32).at[top_i.reshape(-1)].add(1)
        probs_sum = jnp.zeros((e,), jnp.float32)
        zsum = jnp.float32(0.0)
    return _finish(cfg, top_p, top_i, probs_sum, counts, zsum, s)


# The kernel has no autodiff rule; under AD the fused router runs its
# forward and recomputes the backward through router_xla (identical math).
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _router_pallas_ad(x, gate_w, cfg: MoEConfig, interpret: bool):
    return router_pallas(x, gate_w, cfg, interpret=interpret)


def _router_fwd(x, gate_w, cfg, interpret):
    return router_pallas(x, gate_w, cfg, interpret=interpret), (x, gate_w)


def _router_bwd(cfg, interpret, res, ct):
    x, gate_w = res
    _, vjp_fn = jax.vjp(lambda xx, w: router_xla(xx, w, cfg), x, gate_w)
    return vjp_fn(ct)


_router_pallas_ad.defvjp(_router_fwd, _router_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _router_tiled_ad(x, gate_w, cfg: MoEConfig, interpret: bool):
    return router_pallas_tiled(x, gate_w, cfg, interpret=interpret)


def _router_tiled_fwd(x, gate_w, cfg, interpret):
    return (router_pallas_tiled(x, gate_w, cfg, interpret=interpret),
            (x, gate_w))


_router_tiled_ad.defvjp(_router_tiled_fwd, _router_bwd)


def gate_vmem_bytes(s: int, h: int, e: int, dtype) -> int:
    """Static VMEM estimate of the fused gate's working set: the weight
    tile [H, PX], the token tile [BM, H], and ~4 [BM, PX]-sized f32
    intermediates (logits, exp, probs, selection mask)."""
    px = max(LANE, ((e + LANE - 1) // LANE) * LANE)
    bm = next(b for b in (128, 64, 32, 16, 8) if s % b == 0) if s % 8 == 0 \
        else 128
    item = jnp.dtype(dtype).itemsize
    return h * px * item + bm * h * item + 4 * bm * px * 4 + 8 * px * 4


# Single-tile gate ceiling: the kernel holds the full padded-E logits tile
# in VMEM, so it serves E up to a few thousand (h=2048 bf16: E <= ~4k).
# Past the budget the router switches to the two-pass expert-tiled kernel
# (:func:`router_pallas_tiled`) — the TPU form of the reference's
# multi-block ring (gate.cuh:93-467).
_GATE_VMEM_BUDGET = 12 * 2**20


def apply_replicas(out: RouterOutput, cfg: MoEConfig) -> RouterOutput:
    """Split hot-expert traffic across its replica slots
    (``cfg.expert_replicas``, written by the self-healing controller's
    re-placement action — :mod:`flashmoe_tpu.runtime.controller`).

    For each static (hot, slot) pair, tokens whose top-k selected
    ``hot`` are remapped to ``slot`` by token parity — a deterministic
    half/half split.  The controller guarantees ``slot``'s FFN weights
    are a value-identical copy of ``hot``'s, so every token is processed
    by exactly one replica with the same math and the combine merges
    contributions unchanged; only the *physical* load (and therefore
    capacity drops and per-device work) splits.  ``expert_counts`` is
    recomputed over the remapped slots so the dispatch plan, MoEStats
    load histogram, and the controller's own feedback all see physical
    slot load; ``aux_loss``/``probs_mean`` keep the router's logical
    view (computed pre-remap).  Empty map = identity (no ops added)."""
    if not cfg.expert_replicas:
        return out
    idx = out.expert_idx
    pos = jnp.arange(idx.shape[0], dtype=idx.dtype)[:, None]
    for hot, slot in cfg.expert_replicas:
        take = (idx == hot) & (pos % 2 == 1)
        idx = jnp.where(take, jnp.asarray(slot, idx.dtype), idx)
    counts = jnp.sum(
        jax.nn.one_hot(idx, cfg.num_experts, dtype=jnp.int32),
        axis=(0, 1))
    return out._replace(expert_idx=idx, expert_counts=counts)


def router(x, gate_w, cfg: MoEConfig, use_pallas: bool = True,
           interpret: bool = False, gate_bias=None,
           zero_ok: bool = False) -> RouterOutput:
    """Dispatch to a fused kernel on TPU, XLA fallback elsewhere.
    Differentiable on all paths.  Large-E configs beyond the single-tile
    kernel's VMEM budget (:func:`gate_vmem_bytes`) use the two-pass
    expert-tiled kernel.

    The Pallas kernels are SOFTMAX kernels with no selection bias: a
    config with ``router_score='sigmoid'`` or ``router_bias`` takes the
    XLA arm (:func:`router_xla`) on every path, whatever ``use_pallas``
    says.  A ``router_bias`` config must be handed its ``gate_bias``: a
    caller that has none to pass (the mesh paths) is refused here rather
    than routed without it.  A config with zero-compute experts
    (``cfg.zero_experts``) is routed for a caller that says it adds the
    identity term (``zero_ok``: the routed rows of ``ops/moe.py``) and
    refused for every other: their dispatch indexes ``num_experts``
    outputs."""
    from flashmoe_tpu.chaos import inject as chaos_inject

    if cfg.router_bias and gate_bias is None:
        raise NotImplementedError(
            "this config routes with a selection bias (router_bias) and "
            "the caller passed no gate_bias: only ops/moe.py:moe_layer "
            "carries it (the expert-parallel layers do not yet)")
    if cfg.zero_experts:
        if not zero_ok or use_pallas:
            raise NotImplementedError(
                "this config routes over zero-compute experts "
                "(zero_experts) and the caller cannot express them: only "
                "the routed rows of ops/moe.py:moe_layer (routed_rows=True, "
                "use_pallas=False) add the identity term; the capacity "
                "arm, the Pallas routers (softmax kernels over num_experts) "
                "and the expert-parallel layers do not")
        return router_xla(x, gate_w, cfg, gate_bias)
    if (cfg.router_score != "softmax" or gate_bias is not None
            or cfg.n_group > 1):
        return apply_replicas(router_xla(x, gate_w, cfg, gate_bias), cfg)
    if chaos_inject.is_armed("skewed_routing") and use_pallas:
        # the skew fault biases router LOGITS (router_xla hook); the
        # fused gate kernels compute logits in-kernel, so chaos drills
        # route through the XLA gate while this point is armed
        return apply_replicas(router_xla(x, gate_w, cfg), cfg)
    on_tpu = interpret or jax.default_backend() == "tpu"
    s, h = x.shape
    if not (use_pallas and s % 8 == 0 and on_tpu):
        return apply_replicas(router_xla(x, gate_w, cfg), cfg)
    fits = gate_vmem_bytes(s, h, cfg.num_experts, x.dtype) \
        <= _GATE_VMEM_BUDGET
    if fits:
        return apply_replicas(_router_pallas_ad(x, gate_w, cfg, interpret),
                              cfg)
    if 2 * cfg.expert_top_k > LANE:
        # the tiled kernel's carried+candidate top-k merge holds 2k lanes;
        # beyond that use the XLA path instead of raising (advisor r4 #4)
        return apply_replicas(router_xla(x, gate_w, cfg), cfg)
    return apply_replicas(_router_tiled_ad(x, gate_w, cfg, interpret), cfg)
