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

import functools
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

    Opt-in (config field, or FLASHMOE_GATHER_FUSED=1) until the kernel wins
    a measurement on the chip; the explicit-dispatch path is the
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
    """Dropless expert FFN over exactly the ROUTED rows.

    The capacity arm computes ``E x capacity`` rows, and a dropless
    config's capacity is the token count: ``E x S`` rows where ``S x K``
    are routed (32 x too many at 256 experts top-8, and [E, S, .] buffers
    beside the weights).  Here the ``S x K`` (token, choice) rows are
    sorted by expert, ONE stable sort, and computed in one of two forms
    (:func:`routed_rows_form`): on a TPU whose widths tile, ONE launch of
    the grouped Pallas kernel over rows padded to whole tiles an expert
    (:func:`_rows_kernel_ffn`), which streams each touched expert's
    weights once; everywhere else three ``jax.lax.ragged_dot`` over the
    rows as sorted (a masked dense product off the TPU; on it XLA's
    grouped matmul, a tile of up to 512 rows a GROUP whatever its rows),
    the form the kernel is held against.  The mathematics is one: bf16
    operands, float32 accumulation and biases, the hidden activations
    rounded to the compute dtype before the down product.  No row is
    dropped.  x: [S, H]; r: the router's output.  Returns [S, H] float32,
    the weighted sum of every token's K expert outputs.

    A config that holds a SHARE of the experts (``cfg.experts_held``: the
    weights of experts ``expert_first`` .. + ``experts_held`` - 1, routed
    over all ``num_experts``) computes the rows that fall on its own
    experts: the others sort behind the last group, belong to no group
    (and to no tile) and count as zero in the sum.  What the absent
    experts would have added is left out.

    A chosen ZERO-COMPUTE expert (``cfg.zero_experts``: an index >=
    ``num_experts``) sorts behind the last group in the same way, so it
    costs no row of the plan, no tile and no gather; it is the identity,
    and its part of the sum is the token's input times the weights of
    its identity choices, added here in float32 for EVERY token, held
    share or not."""
    s, h = x.shape
    k = cfg.expert_top_k
    with trace_span("moe.dispatch"):
        flat_e = r.expert_idx.reshape(-1)              # row t*K + j
        sizes = r.expert_counts                        # rows an output
        here = None
        if cfg.experts_held or cfg.zero_experts:
            first = cfg.expert_first
            held = cfg.experts_held or cfg.num_experts
            here = (flat_e >= first) & (flat_e < first + held)
            flat_e = jnp.where(here, flat_e - first, held)
            sizes = sizes[first:first + held]
    ffn = (params["w_up"].astype(cfg.dtype), params["b_up"],
           params["w_down"].astype(cfg.dtype), params["b_down"],
           params["w_gate"].astype(cfg.dtype) if cfg.gated_ffn else None)
    rows = (x.astype(cfg.dtype), flat_e, sizes, *ffn)
    kernel = dict(k=k, act_name=cfg.hidden_act, block_m=rows_block_m(cfg, s),
                  interpret=jax.default_backend() != "tpu")

    def combine(y):
        with trace_span("moe.combine"):
            if here is not None:
                # a row of no group holds whatever the product left there
                y = jnp.where(here[:, None], y, jnp.zeros((), y.dtype))
            return jnp.einsum(
                "skh,sk->sh", y.reshape(s, k, h).astype(jnp.float32),
                r.combine_weights.astype(jnp.float32),
                preferred_element_type=jnp.float32)

    plan = rows_plan(cfg, s)
    if routed_rows_form(cfg) != "routed_kernel":
        out = combine(_rows_ragged_ffn(*rows, k=k, act_name=cfg.hidden_act))
    elif plan < s * k:
        # few of the routed rows fall on the experts here: a plan of the
        # rows they expect, walked as often as the rows there are need
        out = _rows_kernel_waves(*rows, r.combine_weights, plan_rows=plan,
                                 **kernel)
    else:
        out = combine(_rows_kernel_ffn(*rows, **kernel))
    if not cfg.zero_experts:
        return out
    with trace_span("moe.zero"):
        w_zero = jnp.sum(
            jnp.where(r.expert_idx >= cfg.num_experts,
                      r.combine_weights.astype(jnp.float32), 0.0), axis=-1)
        return out + w_zero[:, None] * x.astype(jnp.float32)


def _rows_ragged_ffn(x, flat_e, sizes, w_up, b_up, w_down, b_down, w_gate, *,
                     k, act_name):
    """The routed rows through three ``jax.lax.ragged_dot``: x [S, H] at
    the compute dtype; flat_e [S*K] the group of row ``t*K + j`` (a row of
    no group: ``len(sizes)``); sizes [G].  Returns the rows' outputs
    [S*K, H] in that order, at the compute dtype."""
    act = activation_fn(act_name)
    f32 = dict(preferred_element_type=jnp.float32)
    with trace_span("moe.dispatch"):
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        xs = x[order // k]                             # [S*K, H]
    with trace_span("moe.expert"):
        up = jax.lax.ragged_dot(xs, w_up, sizes, **f32)
        up = up + b_up.astype(jnp.float32)[sorted_e]
        if w_gate is not None:
            hidden = act(jax.lax.ragged_dot(xs, w_gate, sizes, **f32)) * up
        else:
            hidden = act(up)
        y = jax.lax.ragged_dot(hidden.astype(xs.dtype), w_down, sizes, **f32)
        y = (y + b_down.astype(jnp.float32)[sorted_e]).astype(xs.dtype)
    with trace_span("moe.combine"):
        return y[jnp.argsort(order)]                   # row t*K + j again


@functools.partial(jax.jit, static_argnames=("k", "act_name", "block_m",
                                             "interpret"))
def _rows_kernel_ffn(x, flat_e, sizes, w_up, b_up, w_down, b_down, w_gate, *,
                     k, act_name, block_m, interpret):
    """:func:`_rows_ragged_ffn`'s contract through ONE launch of the
    grouped Pallas kernel (``ops/expert.grouped_ffn``: up, gate,
    activation and down fused, the ``[rows, I]`` intermediate never in
    HBM).  The sorted rows are padded to whole tiles of ``block_m`` an
    expert (``ops/ragged.sorted_rows_plan``: from the one sort); a tile
    DMAs its own expert's weights, consecutive tiles of an expert fetch
    them once, an expert with no rows has no tile and is never read, and
    the tiles past the last populated one skip their body and fetch no
    weights.  The intermediate axis is ONE chunk wherever an expert's
    matrices, double buffered, fit the kernel's VMEM ceiling (every width
    served but H 6144 x I 2048, LongCat's: four chunks,
    :func:`expert_chunks`), and an expert then streams once however many
    tiles it has; walked in more, it streams once a TILE it has, less a
    chunk a tile after its first (``expert._chunk_under_live``): once an
    expert in a decode step, whose 16-row tiles no expert fills twice,
    1.3 times in a 1024-token chunk of LongCat's routing (PERF.md section
    6, PR 48).  Jitted with the
    layer's weights as operands: the mixture layers of a program share
    one traced and lowered function."""
    n = flat_e.shape[0]
    with trace_span("moe.dispatch"):
        order = jnp.argsort(flat_e, stable=True)
        back = jnp.argsort(order)
        n_tiles = rag.sorted_rows_tiles(n, sizes.shape[0], block_m)
        src, tile_gid, live, starts, pad_starts = rag.sorted_rows_plan(
            order, sizes, block_m, n_tiles)
        xbuf = x[src // k]                             # [T_pad, H]
    with trace_span("moe.expert"):
        ybuf = exp.grouped_ffn(
            xbuf, tile_gid, w_up, b_up, w_down, b_down, w_gate, live,
            act_name=act_name, gated=w_gate is not None, block_m=block_m,
            block_i=w_up.shape[2], interpret=interpret)
    with trace_span("moe.combine"):
        # a row of no group indexes past the groups: clamped, and masked
        # by the caller
        group = jnp.minimum(flat_e, sizes.shape[0] - 1)
        at = pad_starts[group] + back - starts[group]
        return ybuf[jnp.clip(at, 0, ybuf.shape[0] - 1)]


@functools.partial(jax.jit, static_argnames=("k", "act_name", "block_m",
                                             "plan_rows", "interpret"))
def _rows_kernel_waves(x, flat_e, sizes, w_up, b_up, w_down, b_down, w_gate,
                       weights, *, k, act_name, block_m, plan_rows,
                       interpret):
    """The routed rows' weighted sum [S, H] float32 through the grouped
    kernel where FEW of the ``S x K`` rows belong to a group here (a share
    of the experts held, zero-compute experts): the padded layout of
    :func:`_rows_kernel_ffn` is walked in windows of the tiles that
    ``plan_rows`` rows need (``ops/ragged.sorted_rows_plan`` with a first
    tile), ONE launch a window, as many windows as the populated tiles
    take: one when the groups hold what they expect, none when they hold
    nothing, more when a step's routing is lopsided.  So the gather, the
    kernel's grid and its buffers are sized by the rows the groups expect
    and not by rows that sort behind the last group; the result is the
    one layout's whatever the windows.  weights: [S, K] float32; a row of
    no group (``flat_e == len(sizes)``) adds nothing."""
    n, groups = flat_e.shape[0], sizes.shape[0]
    s, h = x.shape
    n_tiles = rag.sorted_rows_tiles(plan_rows, groups, block_m)
    with trace_span("moe.dispatch"):
        order = jnp.argsort(flat_e, stable=True)
        back = jnp.argsort(order)
        tiles = (sizes.astype(jnp.int32) + block_m - 1) // block_m
        tile_ends = jnp.cumsum(tiles)
        starts = jnp.cumsum(sizes.astype(jnp.int32)) - sizes
        group = jnp.minimum(flat_e, groups - 1)
        # where a row lies in the padded layout; behind it for no group
        at = jnp.where(flat_e < groups,
                       (tile_ends - tiles)[group] * block_m + back
                       - starts[group], -1)
        windows = (tile_ends[-1] + n_tiles - 1) // n_tiles

    def window(i, acc):
        with trace_span("moe.dispatch"):
            src, tile_gid, live, _, _ = rag.sorted_rows_plan(
                order, sizes, block_m, n_tiles, first_tile=i * n_tiles)
            xbuf = x[src // k]                         # [n_tiles * bm, H]
        with trace_span("moe.expert"):
            ybuf = exp.grouped_ffn(
                xbuf, tile_gid, w_up, b_up, w_down, b_down, w_gate, live,
                act_name=act_name, gated=w_gate is not None,
                block_m=block_m, block_i=w_up.shape[2], interpret=interpret)
        with trace_span("moe.combine"):
            local = at - i * n_tiles * block_m
            mine = (local >= 0) & (local < ybuf.shape[0])
            y = jnp.where(mine[:, None],
                          ybuf[jnp.clip(local, 0, ybuf.shape[0] - 1)],
                          jnp.zeros((), ybuf.dtype))
            return acc + jnp.einsum(
                "skh,sk->sh", y.reshape(s, k, h).astype(jnp.float32),
                weights.astype(jnp.float32),
                preferred_element_type=jnp.float32)

    return jax.lax.fori_loop(0, windows, window,
                             jnp.zeros((s, h), jnp.float32))


def rows_plan(cfg: MoEConfig, s: int) -> int:
    """Rows the routed-rows kernel's plan is laid out for, of a span of
    ``s`` rows: the ``s x K`` routed rows; or, where the router's width
    says that the experts here expect under a quarter of them (a share of
    the experts held, zero-compute outputs beside them), four times what
    they expect, walked in as many windows as the rows that do come need
    (:func:`_rows_kernel_waves`)."""
    n = s * cfg.expert_top_k
    here = cfg.experts_held or cfg.num_experts
    return min(n, 4 * -(-n * here // cfg.router_width))


def rows_block_m(cfg: MoEConfig, s: int) -> int:
    """Rows of a tile of the routed-rows kernel for a span of ``s`` rows:
    twice the rows an expert expects (``s x K`` over the router's width,
    zero-compute outputs included: for a config that holds a share of the
    experts, the rows that fall here over the experts held), as a
    power-of-two count of the packed tiles the compute dtype
    allows (16 rows of bf16) and at most 256.  A decode step's 1-4 rows
    an expert take the smallest legal tile; a 1024-token chunk's 32-64
    take 64-128, so that nearly every expert is ONE tile."""
    block = 32 // jnp.dtype(cfg.dtype).itemsize
    while (block * cfg.router_width < 2 * s * cfg.expert_top_k
           and block < 256):
        block *= 2
    return block


def expert_chunks(cfg: MoEConfig, s: int) -> int:
    """Chunks of the intermediate axis that the routed-rows kernel's
    launch of a span of ``s`` rows walks (``ops/expert._ffn_chunks`` on
    the shapes :func:`_rows_kernel_ffn` hands it: a tile of
    :func:`rows_block_m` rows, the experts' matrices at the compute dtype
    and the stored width, all of it asked for): 1 where an expert's
    matrices, double buffered, fit the kernel's VMEM ceiling."""
    stored = cfg.intermediate_size + cfg.intermediate_pad
    block_m = rows_block_m(cfg, s)
    x = jax.ShapeDtypeStruct((block_m, cfg.hidden_size), cfg.dtype)
    w = jax.ShapeDtypeStruct((1, cfg.hidden_size, stored), cfg.dtype)
    bi, _ = exp._ffn_chunks(x, w, w if cfg.gated_ffn else None, block_m,
                            stored, cfg.gated_ffn)
    return stored // bi


def routed_rows_form(cfg: MoEConfig) -> str:
    """The form :func:`routed_rows_ffn` computes in: ``"routed_kernel"``
    (the grouped Pallas kernel) on a TPU when the widths tile (H and the
    intermediate width AS STORED, ``intermediate_size +
    intermediate_pad``, whole lanes: a weight block is whole tiles),
    ``"routed_rows"`` (plain ``ragged_dot``) everywhere else.  Why the
    stored width and not a block of the array's own last dimension, which
    Mosaic compiles at 1856 = 14.5 lanes: the chip keeps an ``[E, H, I]``
    array whose I is no whole lanes H-minor, and copied all of it into
    row-major order before EVERY launch (0.64 GB a layer, PERF.md section
    6, PR 39); a config with such a width stores zero columns beside it
    (``MoEConfig.intermediate_pad``)."""
    stored = cfg.intermediate_size + cfg.intermediate_pad
    lanes = cfg.hidden_size % 128 == 0 and stored % 128 == 0
    return ("routed_kernel" if lanes and jax.default_backend() == "tpu"
            else "routed_rows")


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
                   else None, zero_ok=routed_rows)
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
            "routed_rows is the dropless serving arm, its form its own "
            "(routed_rows_form): it takes no use_pallas, no drop_tokens "
            "config, no capacity and no degrade_unhealthy_experts")
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


#: what the serving span gives the capacity arm against the ``ragged_dot``
#: form of the routed rows (:func:`expert_arm`): its largest
#: ``[E, capacity, H]`` buffer, its most rows for one routed row
_CAPACITY_BUFFER_BYTES, _CAPACITY_ROWS_A_ROUTED_ROW = 128 * 1024 * 1024, 24


def expert_arm(cfg: MoEConfig, s: int) -> str:
    """The arm a span of ``s`` rows takes through a layer's experts in
    the serving path (``models/generate.span_forward``), ONE rule over
    the backend and what the shapes say each arm computes and holds.

    ``"routed_kernel"`` (:func:`routed_rows_ffn` through the grouped
    Pallas kernel) wherever :func:`routed_rows_form` says the kernel runs:
    raced on the chip against both other arms, one mixture layer alone and
    four in one program, it won at EVERY span (PERF.md section 6, PR 36;
    ms a layer in the program, capacity | ``ragged_dot`` | kernel:
    ``lfm2_24b`` 128 tokens 1.88 | 3.87 | 1.74, 512 4.66 | 4.16 | 1.91,
    1024 9.77 | 4.60 | 2.19; ``dsmoe16b`` 32 tokens 1.59 | 3.39 | 1.49,
    512 4.58 | 5.95 | 2.00, 2048 18.1 | 9.61 | 3.47; ``joyai_flash`` 32
    tokens 3.44 | 4.12 | 2.25, 256 6.63 | 8.98 | 3.49, 1024 22.0 | 10.1 |
    4.31: the launch alone at 85-90 % of the touched weights' stream),
    and in the whole decode programs of the two cells whose steps the
    capacity arm held (``decode_device_ms`` 11.82 -> 11.41 and 17.41 ->
    16.28 ms), so no span is kept from it.

    Where the kernel does not run (another backend, widths that are no
    whole lanes) the choice is the one PR 33's race set, which this race
    read again: ``"capacity"`` (``E x capacity(s)`` rows, dispatched into
    an ``[E, capacity, H]`` buffer, in plain XLA) while that buffer is at
    most 128 MiB and its rows at most 24 times the ``s x K`` routed rows,
    ``"routed_rows"`` (the ``ragged_dot`` form) beyond either.  Why the
    buffer: on the chip ``ragged_dot`` costs a tile of up to 512 rows a
    GROUP whatever its rows (3.9-4.6 ms a layer from 128 to 1024 tokens at
    64 experts of 2048 x 1536), while the capacity arm's rows ride under
    the weights' stream up to the chip's ridge and grow with ``E x s``
    after it (1.9, 4.7, 9.8 ms): they cross near 512 tokens, 128 MiB.
    Why the rows (E / K when nothing drops): at 10.7 and 16 the capacity
    arm won every span under 512 tokens, at 32 (256 experts top-8) its
    ``w_down`` product runs at half its stream.  A config that holds a
    share of its experts, or routes over zero-compute experts, takes the
    routed rows always (the capacity arm indexes every expert's weights
    and ``num_experts`` outputs); a dense layer and a rule that drops
    keep the capacity arm."""
    if (cfg.drop_tokens or cfg.degrade_unhealthy_experts
            or cfg.num_experts == 1):
        return "capacity"
    form = routed_rows_form(cfg)
    if form == "routed_kernel" or cfg.experts_held or cfg.zero_experts:
        return form
    rows = cfg.num_experts * cfg.capacity_for(s)
    fits = (rows * cfg.hidden_size * jnp.dtype(cfg.dtype).itemsize
            <= _CAPACITY_BUFFER_BYTES)
    near = rows <= _CAPACITY_ROWS_A_ROUTED_ROW * s * cfg.expert_top_k
    return "capacity" if fits and near else form


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
    rows (:func:`routed_rows_ffn`, in the form :func:`routed_rows_form`
    says) where the capacity arm computes
    ``E x S``: the serving path asks for it by :func:`expert_arm`
    (``models/generate.span_forward``); every other caller keeps the arm
    it had.
    """
    if use_pallas is None:
        use_pallas = interpret or jax.default_backend() == "tpu"
    s, h = x.shape
    zero = jnp.zeros((), cfg.accum_dtype)
    if cfg.num_experts == 1:
        with trace_span("ffn.dense"):
            out = dense_ffn(params, x, cfg)
        return MoEOutput(out, zero, zero, jnp.full((1,), s, jnp.int32))
    return _moe_layer_impl(params, x, cfg, use_pallas, capacity, interpret,
                           routed_rows)
