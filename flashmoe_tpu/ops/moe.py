"""Single-device MoE layer: gate -> dispatch -> grouped FFN -> combine.

This is the TPU equivalent of one launch of the reference's fused kernel
``moe::forward`` (``csrc/include/flashmoe/moe/moe.cuh:71-144``) in the
single-PE case: the same four stages, expressed as a jit-compiled dataflow
that XLA fuses and schedules (the in-kernel OS/scheduler/subscriber machinery
of ``csrc/include/flashmoe/os/`` exists to do dynamic tile scheduling that
the XLA/Pallas pipeline provides natively).

The E==1 degenerate case routes to :func:`dense_ffn`, mirroring the
reference's ``fffn`` kernel fallback (``moe/fffn.cuh:24-167``,
``moe.cuh:174-177``).

The expert-parallel multi-device layer lives in
:mod:`flashmoe_tpu.parallel.ep` and reuses these stages around the
all-to-all.
"""

from __future__ import annotations

import os
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from flashmoe_tpu.config import BLOCK_M, MoEConfig
from flashmoe_tpu.models.reference import activation_fn, shared_expert_ffn
from flashmoe_tpu.ops import dispatch as dsp
from flashmoe_tpu.ops import expert as exp
from flashmoe_tpu.ops import ragged as rag
from flashmoe_tpu.ops.gate import router
from flashmoe_tpu.utils.telemetry import trace_span


def _gather_fused(cfg: MoEConfig) -> bool:
    """Whether inference routes through the gather-fused FFN kernel.

    Opt-in (config field, or FLASHMOE_GATHER_FUSED=1) until the kernel has a
    winning stage_bench row on real TPU; the explicit-dispatch path is the
    hardware-validated default (round-2 advisor finding)."""
    if cfg.gather_fused is not None:
        return cfg.gather_fused
    return os.environ.get("FLASHMOE_GATHER_FUSED") == "1"


class MoEOutput(NamedTuple):
    out: jnp.ndarray  # [S, H]
    aux_loss: jnp.ndarray
    z_loss: jnp.ndarray
    expert_counts: jnp.ndarray  # [E]
    # MoEStats (ops/stats.py) when cfg.collect_stats, else None — a None
    # leaf is an empty pytree node, so the default changes no existing
    # sharding spec or custom-VJP structure
    stats: Any = None


def dense_ffn(params, x, cfg: MoEConfig):
    """E==1 dense fallback (the reference's ``fffn`` path)."""
    act = activation_fn(cfg.hidden_act)
    up = jnp.dot(x, params["w_up"][0].astype(x.dtype),
                 preferred_element_type=cfg.accum_dtype)
    up = up + params["b_up"][0].astype(cfg.accum_dtype)
    if cfg.gated_ffn:
        g = jnp.dot(x, params["w_gate"][0].astype(x.dtype),
                    preferred_element_type=cfg.accum_dtype)
        hidden = act(g) * up
    else:
        hidden = act(up)
    down = jnp.dot(hidden.astype(x.dtype), params["w_down"][0].astype(x.dtype),
                   preferred_element_type=cfg.accum_dtype)
    down = down + params["b_down"][0].astype(cfg.accum_dtype)
    return down.astype(x.dtype)


def routed_rows_ffn(params, x, r, cfg: MoEConfig):
    """Dropless expert FFN over exactly the ROUTED rows, in plain XLA.

    The capacity arm computes ``E x capacity`` rows, and a dropless
    config's capacity is the token count: ``E x S`` rows where ``S x K``
    are routed (32 x too many at 256 experts top-8, and [E, S, .] buffers
    beside the weights).  Here the ``S x K`` (token, choice) rows are
    sorted by expert and the three products are ``jax.lax.ragged_dot``
    (on a TPU XLA's own grouped matmul, which reads an expert's weights
    only if rows reached it; elsewhere a masked dense product).  No row is
    padded, none is dropped.  x: [S, H]; r: the router's output.  Returns
    [S, H] float32, the weighted sum of every token's K expert outputs.

    A config that holds a SHARE of the experts (``cfg.experts_held``: the
    weights of experts ``expert_first`` .. + ``experts_held`` - 1, routed
    over all ``num_experts``) computes the rows that fall on its own
    experts: the others sort behind the last group, belong to no group of
    the grouped product and count as zero in the sum.  What the absent
    experts would have added is left out."""
    s, h = x.shape
    k = cfg.expert_top_k
    act = activation_fn(cfg.hidden_act)
    f32 = dict(preferred_element_type=cfg.accum_dtype)
    with trace_span("moe.dispatch"):
        flat_e = r.expert_idx.reshape(-1)              # row t*K + j
        sizes = r.expert_counts                        # rows an expert
        here = None
        if cfg.experts_held:
            first, held = cfg.expert_first, cfg.experts_held
            here = (flat_e >= first) & (flat_e < first + held)
            flat_e = jnp.where(here, flat_e - first, held)
            sizes = sizes[first:first + held]
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        xs = x.astype(cfg.dtype)[order // k]           # [S*K, H]
    with trace_span("moe.expert"):
        up = jax.lax.ragged_dot(xs, params["w_up"].astype(xs.dtype), sizes,
                                **f32)
        up = up + params["b_up"].astype(cfg.accum_dtype)[sorted_e]
        if cfg.gated_ffn:
            g = jax.lax.ragged_dot(
                xs, params["w_gate"].astype(xs.dtype), sizes, **f32)
            hidden = act(g) * up
        else:
            hidden = act(up)
        y = jax.lax.ragged_dot(
            hidden.astype(xs.dtype), params["w_down"].astype(xs.dtype),
            sizes, **f32)
        y = (y + params["b_down"].astype(cfg.accum_dtype)[sorted_e]
             ).astype(xs.dtype)
    with trace_span("moe.combine"):
        back = jnp.argsort(order)                      # row t*K + j again
        if here is not None:
            # a row of no group holds whatever the product left there
            y = jnp.where(here[order][:, None], y, jnp.zeros((), y.dtype))
        return jnp.einsum(
            "skh,sk->sh", y[back].reshape(s, k, h).astype(jnp.float32),
            r.combine_weights.astype(jnp.float32),
            preferred_element_type=jnp.float32)


def _moe_layer_impl(params, x, cfg: MoEConfig, use_pallas: bool,
                    capacity: int | None, interpret: bool,
                    routed_rows: bool = False) -> MoEOutput:
    # quantized expert storage (flashmoe_tpu/quant/): resolve the FFN
    # weights to their dequant-in-compute form — payloads dequantize,
    # full-precision params fake-quant in-graph.  Called
    # UNCONDITIONALLY: with the knob off it returns the dict untouched
    # (bit-identical graph, invariant-engine-proven) but REFUSES a
    # quantized state whose scales would otherwise be silently ignored
    # (code-review finding).
    from flashmoe_tpu import quant as qt

    qerr = (qt.weight_quant_error(params, cfg)
            if cfg.expert_quant is not None and cfg.collect_stats
            else None)
    params = qt.ffn_compute_params(params, cfg)
    # the paper's four stages under the names the mesh paths use
    # (parallel/ep.py): trace-time scopes, in every operation's op_name
    with trace_span("moe.gate"):
        r = router(x, params["gate_w"], cfg, use_pallas=use_pallas,
                   interpret=interpret,
                   gate_bias=params["gate_bias"] if cfg.router_bias
                   else None)
    s, h = x.shape
    if cfg.experts_held and not routed_rows:
        raise NotImplementedError(
            "a share of the experts (experts_held) is computed over the "
            "routed rows only (routed_rows=True: ops/moe.routed_rows_ffn); "
            "the capacity and Pallas arms index every expert's weights")
    if routed_rows and (use_pallas or cfg.drop_tokens
                        or capacity is not None
                        or cfg.degrade_unhealthy_experts):
        raise ValueError(
            "routed_rows is the dropless plain-XLA arm: it takes no "
            "use_pallas, no drop_tokens config, no capacity and no "
            "degrade_unhealthy_experts")
    dropless = routed_rows or (
        use_pallas and not cfg.drop_tokens and capacity is None)
    stats = None
    if cfg.collect_stats:
        # in-graph routing health (ops/stats.py): pure function of the
        # router outputs + the same capacity constant the dispatch clamps
        # against, so the layer's numerics cannot shift
        from flashmoe_tpu.ops.stats import moe_stats

        stats_cap = None if dropless else (
            capacity if capacity is not None else cfg.capacity_for(s))
        stats = moe_stats(r, cfg, stats_cap)
    degrade = cfg.degrade_unhealthy_experts
    combine_w = r.combine_weights
    if degrade:
        from flashmoe_tpu.ops import health as hlt
    if routed_rows:
        out = routed_rows_ffn(params, x, r, cfg)
    elif dropless:
        # dropless: ragged expert-sorted grouping + block-sparse grouped FFN
        # (S*K + E*block rows instead of the capacity path's E*S)
        bm = BLOCK_M if s >= BLOCK_M else max(8, ((s + 7) // 8) * 8)
        with trace_span("moe.dispatch"):
            plan = rag.make_ragged_plan(r.expert_idx, cfg, bm)
        # identical weight/config tail for both kernel entries, so the
        # training and inference arms cannot drift numerically
        ffn_tail = (
            params["w_up"].astype(cfg.dtype), params["b_up"],
            params["w_down"].astype(cfg.dtype), params["b_down"],
            params["w_gate"].astype(cfg.dtype) if cfg.gated_ffn else None,
            cfg.hidden_act, cfg.gated_ffn, bm, exp.DEFAULT_BLOCK_I,
            interpret,
        )
        if not cfg.is_training and _gather_fused(cfg):
            # inference: gather fused into the kernel via the plan's
            # inverse map — no [T_pad, H] grouped buffer in HBM
            with trace_span("moe.expert"):
                ybuf = exp.grouped_ffn_tokens_ad(
                    x.astype(cfg.dtype), plan.src_tok, plan.tile_gid,
                    *ffn_tail)
        else:
            with trace_span("moe.dispatch"):
                xbuf = rag.ragged_dispatch(x.astype(cfg.dtype), plan, cfg,
                                           bm)
            with trace_span("moe.expert"):
                ybuf = exp.grouped_ffn_ad(xbuf, plan.tile_gid, *ffn_tail)
        if degrade:
            # tier-0 (ops/health.py): ragged_combine does not
            # renormalize, so the mask renormalizes survivors itself
            healthy = hlt.expert_health_tiles(ybuf, plan.tile_gid,
                                              cfg.num_experts, bm)
            ybuf, combine_w = hlt.degrade_outputs(
                ybuf, combine_w, r.expert_idx, healthy, renormalize=True)
        with trace_span("moe.combine"):
            out = rag.ragged_combine(ybuf, plan, combine_w, cfg)
    else:
        # capacity from the ACTUAL token count of this call, not the config's
        # nominal sequence length (callers pass batched shards of any size)
        cap = capacity if capacity is not None else cfg.capacity_for(s)
        with trace_span("moe.dispatch"):
            plan = dsp.make_plan(r.expert_idx, cfg, cap)
        if use_pallas and not cfg.is_training and _gather_fused(cfg):
            # inference: gather fused into the kernel — the [E, C, H]
            # dispatch buffer never hits HBM (training keeps the explicit
            # dispatch so the fused backward has its residuals)
            with trace_span("moe.expert"):
                ybuf, cap_p = exp.capacity_ffn_gather(
                    x.astype(cfg.dtype), plan, cfg, cap, params,
                    interpret=interpret)
        else:
            with trace_span("moe.dispatch"):
                xbuf = dsp.dispatch(x.astype(cfg.dtype), plan, cfg, cap)
            with trace_span("moe.expert"):
                if use_pallas:
                    ybuf = exp.capacity_buffer_ffn_ad(xbuf, params, cfg,
                                                      interpret=interpret)
                else:
                    ybuf = exp.expert_ffn_dense(xbuf, params, cfg)
            cap_p = cap
        from flashmoe_tpu.chaos import inject as chaos_inject

        if chaos_inject.is_armed("nan_expert"):  # trace-time check only
            ybuf = chaos_inject.poison_expert(ybuf)
        if degrade:
            # tier-0 (ops/health.py): dsp.combine renormalizes the
            # surviving weights itself
            healthy = hlt.expert_health_capacity(ybuf)
            ybuf, combine_w = hlt.degrade_outputs(ybuf, combine_w,
                                                  r.expert_idx, healthy)
        with trace_span("moe.combine"):
            out = dsp.combine(ybuf, plan, combine_w, cfg, cap_p)
    if degrade and stats is not None:
        stats = hlt.attach_degradation(stats, healthy, r.expert_idx)
    if qerr is not None and stats is not None:
        from flashmoe_tpu.ops.stats import with_quant_error

        stats = with_quant_error(stats, qerr)
    if cfg.num_shared_experts:
        with trace_span("moe.shared"):
            out = out + shared_expert_ffn(
                x.astype(cfg.dtype), params, cfg).astype(out.dtype)
    return MoEOutput(
        out.astype(cfg.dtype),
        r.aux_loss * cfg.aux_loss_coef,
        r.z_loss,
        r.expert_counts,
        stats,
    )


#: what the serving span gives the capacity arm (:func:`expert_arm`): its
#: largest ``[E, capacity, H]`` buffer, its most rows for one routed row
_CAPACITY_BUFFER_BYTES, _CAPACITY_ROWS_A_ROUTED_ROW = 128 * 1024 * 1024, 24


def expert_arm(cfg: MoEConfig, s: int) -> str:
    """The arm a span of ``s`` rows takes through a layer's experts in
    plain XLA (the serving path: ``models/generate.span_forward``), ONE
    rule over what the shapes say each arm computes and holds.

    ``"capacity"`` (``E x capacity(s)`` rows, dispatched into an
    ``[E, capacity, H]`` buffer) while that buffer is at most 128 MiB and
    its rows at most 24 times the ``s x K`` routed rows; ``"routed_rows"``
    (:func:`routed_rows_ffn`, XLA's grouped matmul) beyond either, and
    always for a config that holds a share of its experts (the capacity
    arm indexes every expert's weights).  Why the buffer: on the chip the
    grouped matmul costs a tile of up to 512 rows a GROUP whatever its
    rows (64 experts of 2048 x 1536, top-4: 3.9-4.6 ms a layer from 128 to
    1024 tokens), while the capacity arm's rows ride under the weights'
    stream up to the chip's ridge and grow with ``E x s`` after it (1.9,
    4.7, 9.8 ms at 128, 512, 1024): they cross near 512 tokens, 128 MiB.
    Why the rows (E / K when nothing drops): at 10.7 and 16 the capacity
    arm won every span under 512 tokens; at 32 (256 experts top-8) a
    decode step was a tie alone and LOST in its program (PERF.md section
    6, PR 33).  A dense layer and a rule that drops keep that arm."""
    if (cfg.drop_tokens or cfg.degrade_unhealthy_experts
            or cfg.num_experts == 1):
        return "capacity"
    if cfg.experts_held:
        return "routed_rows"
    rows = cfg.num_experts * cfg.capacity_for(s)
    fits = (rows * cfg.hidden_size * jnp.dtype(cfg.dtype).itemsize
            <= _CAPACITY_BUFFER_BYTES)
    near = rows <= _CAPACITY_ROWS_A_ROUTED_ROW * s * cfg.expert_top_k
    return "capacity" if fits and near else "routed_rows"


def moe_layer(params, x, cfg: MoEConfig, *, use_pallas: bool | None = None,
              capacity: int | None = None, interpret: bool = False,
              routed_rows: bool = False) -> MoEOutput:
    """One MoE layer over a token shard x: [S, H].

    ``use_pallas`` selects the fused Pallas gate + grouped-FFN kernels;
    ``None`` (default) auto-selects: Pallas on TPU (or when ``interpret``),
    XLA elsewhere.  The XLA path is the oracle in tests.  Both paths are
    differentiable: the fused path composes per-component custom VJPs —
    the dominant FFN gradients run through the Pallas backward kernels
    (``grouped_matmul``/``tgmm`` with residuals saved in the forward,
    :mod:`flashmoe_tpu.ops.expert`), while the cheap gate/dispatch/combine
    stages differentiate through XLA.

    ``routed_rows`` (with ``use_pallas=False``, a dropless config and no
    ``capacity``) computes the experts over exactly the ``S x K`` routed
    rows (:func:`routed_rows_ffn`) where the capacity arm computes
    ``E x S``: the serving path asks for it by :func:`expert_arm`
    (``models/generate.span_forward``); every other caller keeps the arm
    it had.
    """
    if use_pallas is None:
        use_pallas = interpret or jax.default_backend() == "tpu"
    s, h = x.shape
    zero = jnp.zeros((), cfg.accum_dtype)
    if cfg.num_experts == 1:
        out = dense_ffn(params, x, cfg)
        return MoEOutput(out, zero, zero, jnp.full((1,), s, jnp.int32))
    return _moe_layer_impl(params, x, cfg, use_pallas, capacity, interpret,
                           routed_rows)
