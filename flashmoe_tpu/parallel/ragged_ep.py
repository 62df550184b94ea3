"""Distributed dropless MoE: ragged all-to-all expert parallelism.

The capacity-based EP layer (:mod:`flashmoe_tpu.parallel.ep`) pads every
(rank, expert) slab to a fixed capacity — simple, static, but with
``drop_tokens=False`` it ships ``E x S_loc`` rows per rank regardless of
routing.  The reference ships exactly ``routedTokens`` per packet (the
dynamic size rides in the signal payload, ``types.cuh:299-334``) and its
receivers decode variable-size packets.  This module is that capability on
TPU: variable-size expert transfers under static *bounds* instead of static
*shapes*.

Per rank: assignments sort by global expert id (destination-major), so each
destination's rows are contiguous; counts exchange over the ``ep`` axis
establishes every pairwise transfer size; ``jax.lax.ragged_all_to_all``
moves exactly the routed rows (TPU path — XLA:CPU lacks the op, so tests
exercise the same layout logic through a dense-padded ``all_to_all``
fallback); arithmetic (no sort) regroups the received source-major rows
into tile-padded expert-major segments for the grouped Pallas FFN; the
whole dance then runs in reverse.

All shapes are static upper bounds; ``recv_bound`` defaults to the true
worst case (every token in the ep group routed to one rank).

With ``MoEConfig.a2a_chunks = n`` the exchanges run as a chunked
software pipeline mirroring :mod:`flashmoe_tpu.parallel.ep`: the
local-expert axis splits into ``n`` chunks, each with its own
row-exchange -> regroup -> grouped-FFN -> return-exchange chain over
the chunk's rows only (offsets/sizes derived per chunk from one
all-gathered count matrix).  The chains are independent in the graph,
so chunk ``k+1``'s ragged transfer can overlap chunk ``k``'s FFN.
``None`` (default) keeps the serial schedule bit-identical.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from flashmoe_tpu.config import BLOCK_M, MoEConfig
from flashmoe_tpu.ops import expert as exp
from flashmoe_tpu.ops import ragged as rag
from flashmoe_tpu.ops import stats as st
from flashmoe_tpu.ops import wire as wr
from flashmoe_tpu.ops.gate import router
from flashmoe_tpu.ops.moe import MoEOutput
from flashmoe_tpu.profiler import spans as prof
from flashmoe_tpu.utils.telemetry import trace_span


#: metadata collectives the dense-arm layouts trade beyond the payload
#: exchanges — contract constants the collective census
#: (``analysis.comm_census`` / :mod:`flashmoe_tpu.staticcheck.census`)
#: reconciles against the traced graph: the serial schedule gathers the
#: [D] send sizes and all-to-alls the [D, nLx] count matrix; the chunked
#: schedule replaces both with ONE all_gather of the count matrix.
META_COLLECTIVES_SERIAL = {"all_gather": 1, "all_to_all": 1}
META_COLLECTIVES_CHUNKED = {"all_gather": 1, "all_to_all": 0}


def _row_exchange(arr, *, axis: str, d: int, exchange: str,
                  block_rows: int, out_bound: int,
                  send_offsets, send_sizes, remote_offsets,
                  recv_sizes, recv_offsets):
    """Move ragged row blocks of ``arr`` ([N, W], any W / dtype) between
    ranks.  Rank-local blocks start at ``send_offsets`` with
    ``send_sizes`` rows; block ``p`` lands at ``remote_offsets[p]`` of
    peer ``p``'s ``[out_bound, W]`` output, which locally holds
    ``recv_sizes`` rows per source starting at ``recv_offsets``
    (``recv_offsets`` being the local cumsum view ``remote_offsets``
    describes remotely).  One implementation for both transfer
    directions and for the payload AND the fp8 scale sidecar, so the
    two can never take different routes.

    ``exchange='ragged'`` is the TPU ``ragged_all_to_all``; ``'dense'``
    pads each block to ``block_rows`` rows and compacts after a dense
    ``all_to_all`` (CPU fallback — identical layout logic)."""
    w = arr.shape[1]
    if exchange == "ragged":
        return jax.lax.ragged_all_to_all(
            arr, jnp.zeros((out_bound, w), arr.dtype),
            send_offsets, send_sizes, remote_offsets, recv_sizes,
            axis_name=axis,
        )
    blocks = jnp.zeros((d, block_rows, w), arr.dtype)

    def fill(peer, blocks):
        rows = jax.lax.dynamic_slice(
            jnp.pad(arr, ((0, block_rows), (0, 0))),
            (send_offsets[peer], 0), (block_rows, w),
        )
        mask = (jnp.arange(block_rows) < send_sizes[peer])[:, None]
        return blocks.at[peer].set(jnp.where(mask, rows, 0))

    blocks = jax.lax.fori_loop(0, d, fill, blocks)
    got = jax.lax.all_to_all(
        blocks.reshape(d, 1, block_rows, w), axis, split_axis=0,
        concat_axis=0, tiled=False,
    ).reshape(d, block_rows, w)
    buf = jnp.zeros((out_bound, w), arr.dtype)

    def compact(peer, buf):
        rows = got[peer]
        idx = jnp.where(
            jnp.arange(block_rows) < recv_sizes[peer],
            recv_offsets[peer] + jnp.arange(block_rows),
            out_bound,  # dropped
        )
        return buf.at[idx].set(rows, mode="drop")

    return jax.lax.fori_loop(0, d, compact, buf)


def _wired_row_exchange(arr, wire_dtype, **kw):
    """:func:`_row_exchange` with the wire codec applied at the
    boundary: rows quantize to ``wire_dtype`` before the transfer and
    dequantize after; fp8 per-row scales ride an identical second
    exchange as a [N, 1] column.  ``wire_dtype=None`` is the raw path —
    the exact pre-compression graph."""
    if wire_dtype is None:
        return _row_exchange(arr, **kw)
    payload, scales = wr.encode(arr, wire_dtype)
    payload = _row_exchange(payload, **kw)
    if scales is None:
        return wr.decode(payload, None, arr.dtype)
    scales = _row_exchange(scales[:, None], **kw)
    return wr.decode(payload, scales[:, 0], arr.dtype)


def _pad_rows(arr, out_rows: int):
    """Shape ``arr`` ([N, W]) to exactly ``out_rows`` rows (pad with
    zeros / truncate) — the exchange-elided stand-in for a row transfer
    on the overlap measurement's compute-only leg (the result is
    numerically meaningless, the shapes and every other stage are
    exact)."""
    n = arr.shape[0]
    if n >= out_rows:
        return arr[:out_rows]
    return jnp.pad(arr, ((0, out_rows - n), (0, 0)))


def _regroup_maps(recv_cmat, recv_offsets, recv_sizes, recv_bound: int,
                  block_m: int):
    """Src-major -> tile-padded expert-major scatter targets for one
    (chunk of the) local-expert axis.

    ``recv_cmat`` [D, nE]: rows per (source, local expert in this
    chunk); ``recv_offsets``/``recv_sizes`` [D]: where each source's
    block sits in the chunk's src-major receive buffer.  Returns
    (target [recv_bound], grouped_rows, tile_gid) with the dropped-row
    sentinel at ``grouped_rows`` (strictly out of range for the
    scatter's drop mode)."""
    d, ne = recv_cmat.shape
    etot = jnp.sum(recv_cmat, axis=0)  # [nE]
    epad = ((etot + block_m - 1) // block_m) * block_m
    eseg = (jnp.cumsum(epad) - epad).astype(jnp.int32)  # [nE]
    pre = (jnp.cumsum(recv_cmat, axis=0) - recv_cmat)  # rows before src s
    intra = (jnp.cumsum(recv_cmat, axis=1) - recv_cmat)  # within-src starts

    rows = jnp.arange(recv_bound, dtype=jnp.int32)
    src_of = jnp.clip(
        jnp.searchsorted(
            (recv_offsets + recv_sizes).astype(jnp.int32), rows,
            side="right",
        ).astype(jnp.int32),
        0, d - 1,
    )
    w = rows - recv_offsets[src_of]  # offset within the src block
    cum_intra = jnp.cumsum(recv_cmat, axis=1)  # [D, nE] ends
    e_of = jnp.sum(
        w[:, None] >= cum_intra[src_of], axis=1
    ).astype(jnp.int32)
    e_of = jnp.clip(e_of, 0, ne - 1)
    i_of = w - intra[src_of, e_of]
    total_recv = jnp.sum(recv_sizes)

    # grouped buffer: per-expert tile padding can push targets past
    # recv_bound, so the buffer is recv_bound (tile-rounded) plus one tile
    # per expert, and the dropped-row sentinel is grouped_rows itself —
    # strictly out of range for the scatter's drop mode
    grouped_rows = (
        ((recv_bound + block_m - 1) // block_m) * block_m
        + ne * block_m
    )
    target = jnp.where(
        rows < total_recv,
        eseg[e_of] + pre[src_of, e_of] + i_of,
        grouped_rows,  # out of range -> dropped
    )
    # tile group ids from padded segment ends
    n_tiles = grouped_rows // block_m
    tile_starts = jnp.arange(n_tiles, dtype=jnp.int32) * block_m
    seg_ends = eseg + epad
    tile_gid = jnp.clip(
        jnp.sum(tile_starts[:, None] >= seg_ends[None, :], axis=1),
        0, ne - 1,
    ).astype(jnp.int32)
    return target, grouped_rows, tile_gid, total_recv


def _grouped_ffn(x_grp, tile_gid, weights, cfg: MoEConfig, *,
                 use_pallas: bool, interpret: bool, block_m: int):
    """Grouped expert FFN on a tile-padded expert-major buffer, with
    ``weights`` = (w_up, b_up, w_down, b_down, w_gate-or-None) covering
    exactly the experts ``tile_gid`` indexes (the full local shard, or
    one pipeline chunk's slice)."""
    w_up, b_up, w_down, b_down, w_gate = weights
    if use_pallas:
        # _ad variant: Pallas forward AND Pallas backward (grouped_matmul/
        # tgmm with saved residuals) — the dropless path trains through
        # the kernels too
        return exp.grouped_ffn_ad(
            x_grp, tile_gid,
            w_up.astype(cfg.dtype), b_up,
            w_down.astype(cfg.dtype), b_down,
            None if w_gate is None else w_gate.astype(cfg.dtype),
            cfg.hidden_act, cfg.gated_ffn, block_m,
            exp.DEFAULT_BLOCK_I, interpret,
        )
    # XLA fallback: per-row weight selection via one-hot (test path)
    ne = w_up.shape[0]
    sel = jax.nn.one_hot(
        jnp.repeat(tile_gid, block_m), ne, dtype=x_grp.dtype
    )  # [rows, nE]
    up_w = jnp.einsum("rn,nhi->rhi", sel, w_up.astype(x_grp.dtype))
    up = jnp.einsum("rh,rhi->ri", x_grp, up_w) + sel @ b_up.astype(x_grp.dtype)
    from flashmoe_tpu.models.reference import activation_fn
    act = activation_fn(cfg.hidden_act)
    if cfg.gated_ffn:
        g_w = jnp.einsum("rn,nhi->rhi", sel,
                         w_gate.astype(x_grp.dtype))
        hid = act(jnp.einsum("rh,rhi->ri", x_grp, g_w)) * up
    else:
        hid = act(up)
    dn_w = jnp.einsum("rn,nih->rih", sel,
                      w_down.astype(x_grp.dtype))
    return (jnp.einsum("ri,rih->rh", hid, dn_w)
            + sel @ b_down.astype(x_grp.dtype))


def _chunked_ragged_exchange(params, xs, cmat, input_offsets,
                             cfg: MoEConfig, *, axis: str, d: int,
                             nlx: int, n_chunks: int, h: int,
                             n_assign: int, recv_bound: int,
                             exchange: str, block_m: int,
                             use_pallas: bool, interpret: bool,
                             wire_disp, wire_comb, w_gate_p,
                             skip_exchange: bool):
    """Chunked double-buffered ragged EP: ``n_chunks`` independent
    row-exchange -> regroup -> grouped-FFN -> return-exchange chains,
    one per local-expert sub-range (the :mod:`flashmoe_tpu.parallel.ep`
    pipeline mirrored onto variable-size transfers).

    One ``all_gather`` of the [dest, local-expert] count matrix replaces
    the serial path's (send-size gather + count a2a): every chunk's
    send/recv offsets and sizes derive from it arithmetically, because a
    chunk's rows are contiguous within each destination block of the
    expert-sorted staging buffer ``xs``.  Returns (ys [n_assign, H] in
    the original expert-sorted layout — the disjoint per-chunk returns
    summed — and the stats-gated combine wire error, or None)."""
    nc = nlx // n_chunks
    my = jax.lax.axis_index(axis)
    # all ranks' count matrices: all_cmat[s, p, le] = rows s sends to
    # dest p for p's local expert le
    all_cmat = jax.lax.all_gather(cmat, axis)  # [D_src, D_dst, nLx]
    # exclusive prefixes along the local-expert axis: where a chunk
    # starts inside each (src, dest) block
    cmat_pre = (jnp.cumsum(cmat, axis=1) - cmat).astype(jnp.int32)
    all_pre = (jnp.cumsum(all_cmat, axis=2) - all_cmat).astype(jnp.int32)
    all_send = jnp.sum(all_cmat, axis=2)  # [D_src, D_dst] totals
    # rank s staged its block for dest p at excl-cumsum over dests
    dest_pre = (jnp.cumsum(all_send, axis=1)
                - all_send).astype(jnp.int32)  # [D_src, D_dst]
    recv_cmat = all_cmat[:, my, :]  # [D_src, nLx] rows sent to me

    ys = jnp.zeros((n_assign, h), xs.dtype)
    comb_err = None
    for ck in range(n_chunks):
        lo = ck * nc
        # -- per-chunk transfer geometry (all arithmetic, no collective)
        send_sizes_c = jnp.sum(
            cmat[:, lo:lo + nc], axis=1).astype(jnp.int32)  # [D]
        send_offsets_c = (input_offsets + cmat_pre[:, lo]).astype(
            jnp.int32)
        all_send_c = jnp.sum(all_cmat[:, :, lo:lo + nc], axis=2)
        recv_sizes_c = all_send_c[:, my].astype(jnp.int32)
        recv_offsets_c = (jnp.cumsum(recv_sizes_c)
                          - recv_sizes_c).astype(jnp.int32)
        out_offsets_c = (
            jnp.cumsum(all_send_c, axis=0) - all_send_c
        )[my].astype(jnp.int32)

        # -- forward rows for this chunk (read straight out of xs: the
        # chunk's rows are contiguous within each dest block)
        with trace_span(f"moe.a2a_dispatch.{ck}"):
            if skip_exchange:
                x_recv_c = _pad_rows(xs, recv_bound)
            else:
                x_recv_c = _wired_row_exchange(
                    xs, wire_disp, axis=axis, d=d, exchange=exchange,
                    block_rows=n_assign, out_bound=recv_bound,
                    send_offsets=send_offsets_c, send_sizes=send_sizes_c,
                    remote_offsets=out_offsets_c,
                    recv_sizes=recv_sizes_c,
                    recv_offsets=recv_offsets_c,
                )
            if cfg.profile_phases:
                prof.fence(x_recv_c)

        # -- regroup + FFN on the chunk's experts only
        rows = jnp.arange(recv_bound, dtype=jnp.int32)
        target, grouped_rows, tile_gid, total_recv = _regroup_maps(
            recv_cmat[:, lo:lo + nc], recv_offsets_c, recv_sizes_c,
            recv_bound, block_m)
        x_grp = jnp.zeros((grouped_rows, h), xs.dtype)
        x_grp = x_grp.at[target].set(x_recv_c, mode="drop")
        with trace_span(f"moe.expert.{ck}"):
            y_grp = _grouped_ffn(
                x_grp, tile_gid,
                (params["w_up"][lo:lo + nc], params["b_up"][lo:lo + nc],
                 params["w_down"][lo:lo + nc],
                 params["b_down"][lo:lo + nc],
                 None if w_gate_p is None else w_gate_p[lo:lo + nc]),
                cfg, use_pallas=use_pallas, interpret=interpret,
                block_m=block_m)
            if cfg.profile_phases:
                prof.fence(y_grp)

        # -- return: back to each source's original staging slots
        y_src_major = y_grp[target.clip(0, grouped_rows - 1)]
        y_src_major = jnp.where(
            (rows < total_recv)[:, None], y_src_major, 0
        ).astype(xs.dtype)
        # rank s staged its chunk-ck rows for me at its dest-block start
        # plus the chunk's intra-block prefix
        rev_out_offsets_c = (dest_pre[:, my]
                             + all_pre[:, my, lo]).astype(jnp.int32)
        if cfg.collect_stats and wire_comb is not None:
            err_k = wr.roundtrip_error(y_src_major, wire_comb)
            comb_err = (err_k if comb_err is None
                        else jnp.maximum(comb_err, err_k))
        with trace_span(f"moe.a2a_combine.{ck}"):
            if skip_exchange:
                ys_c = _pad_rows(y_src_major, n_assign)
            else:
                ys_c = _wired_row_exchange(
                    y_src_major, wire_comb, axis=axis, d=d,
                    exchange=exchange,
                    block_rows=n_assign, out_bound=n_assign,
                    send_offsets=recv_offsets_c, send_sizes=recv_sizes_c,
                    remote_offsets=rev_out_offsets_c,
                    recv_sizes=send_sizes_c,
                    recv_offsets=send_offsets_c,
                )
            if cfg.profile_phases:
                prof.fence(ys_c)
        # chunks return disjoint row ranges (zeros elsewhere): summing
        # reassembles the full expert-sorted ys
        ys = ys + ys_c
    return ys, comb_err


def _ragged_ep_shard(params, x, cfg: MoEConfig, *, axis: str,
                     use_pallas: bool, interpret: bool, exchange: str,
                     block_m: int, reduce_axes,
                     skip_exchange: bool = False):
    d = jax.lax.axis_size(axis)
    s_loc, h = x.shape
    e = cfg.num_experts
    nlx = e // d
    n_assign = s_loc * cfg.expert_top_k
    recv_bound = d * n_assign  # worst case: everyone routes to me
    # quantized expert storage (flashmoe_tpu/quant/): resolve the FFN
    # weight shard to its dequant-in-compute form up front — the
    # chunked pipeline's per-chunk weight slices then slice plain
    # compute arrays (no scale keys left downstream).  Called
    # UNCONDITIONALLY: off returns the dict untouched (bit-identical
    # graph) but a quantized state under a quant-off config is refused
    # instead of matmuling raw payloads (code-review finding).
    from flashmoe_tpu import quant as qt

    quant_err = (qt.weight_quant_error(params, cfg)
                 if cfg.expert_quant is not None and cfg.collect_stats
                 else None)
    params = qt.ffn_compute_params(params, cfg)
    wire_disp = wr.resolve(cfg.wire_dtype)
    wire_comb = wr.resolve(cfg.wire_dtype_combine)
    n_chunks = cfg.a2a_chunks or 1
    if n_chunks > 1 and nlx % n_chunks:
        raise ValueError(
            f"a2a_chunks={n_chunks} does not divide the local-expert "
            f"axis (num_experts={e} // ep={d} = {nlx}); pick a divisor "
            f"or leave a2a_chunks=None for the serial schedule")

    # phase spans mirror parallel/ep.py: named HLO scopes for xprof, and
    # — with cfg.profile_phases — fenced boundaries for the host-side
    # phase timeline (flashmoe_tpu/profiler; fences no-op on tracers,
    # so the traced graph is identical with the knob on or off)
    with trace_span("moe.gate"):
        r = router(x, params["gate_w"], cfg, use_pallas=use_pallas,
                   interpret=interpret)
        if cfg.profile_phases:
            prof.fence(r)

    # ---- local expert-sorted layout (contiguous, unpadded: block "1") ----
    with trace_span("moe.dispatch"):
        plan = rag.make_ragged_plan(r.expert_idx, cfg, 1)
        xs = rag.ragged_dispatch(x.astype(cfg.dtype), plan, cfg, 1)
        xs = xs[:n_assign]  # block_m=1 upper bound equals exact total
        if cfg.profile_phases:
            prof.fence(xs)
    counts = plan.counts  # [E] rows per global expert
    cmat = counts.reshape(d, nlx)  # [dest, local expert]
    send_sizes = jnp.sum(cmat, axis=1).astype(jnp.int32)  # [D]
    input_offsets = (jnp.cumsum(send_sizes) - send_sizes).astype(jnp.int32)

    wire_err = None
    if cfg.collect_stats and wire_disp is not None:
        wire_err = wr.roundtrip_error(xs, wire_disp)

    w_gate_p = params.get("w_gate", None) if cfg.gated_ffn else None

    if n_chunks > 1:
        ys, comb_err = _chunked_ragged_exchange(
            params, xs, cmat, input_offsets, cfg,
            axis=axis, d=d, nlx=nlx, n_chunks=n_chunks, h=h,
            n_assign=n_assign, recv_bound=recv_bound, exchange=exchange,
            block_m=block_m, use_pallas=use_pallas, interpret=interpret,
            wire_disp=wire_disp, wire_comb=wire_comb,
            w_gate_p=w_gate_p, skip_exchange=skip_exchange)
        if comb_err is not None:
            wire_err = (comb_err if wire_err is None
                        else jnp.maximum(wire_err, comb_err))
    else:
        with trace_span("moe.a2a_dispatch"):
            # ---- exchange sizes ----
            # all ranks' send matrices: S[s, d] = rows s sends to d
            all_send = jax.lax.all_gather(send_sizes, axis)  # [D, D]
            my = jax.lax.axis_index(axis)
            recv_sizes = all_send[:, my].astype(jnp.int32)  # [D] per src
            recv_offsets = (jnp.cumsum(recv_sizes)
                            - recv_sizes).astype(jnp.int32)
            # where my block starts on each destination = earlier sources
            out_offsets = (
                jnp.cumsum(all_send, axis=0) - all_send
            )[my].astype(jnp.int32)  # [D]
            # per-(src, my local expert) counts, for regrouping
            recv_cmat = jax.lax.all_to_all(
                cmat.reshape(d, 1, nlx), axis, split_axis=0,
                concat_axis=0, tiled=False,
            ).reshape(d, nlx)

            # ---- forward data exchange: src-major ragged layout ----
            if skip_exchange:
                x_recv = _pad_rows(xs, recv_bound)
            else:
                x_recv = _wired_row_exchange(
                    xs, wire_disp, axis=axis, d=d, exchange=exchange,
                    block_rows=n_assign, out_bound=recv_bound,
                    send_offsets=input_offsets, send_sizes=send_sizes,
                    remote_offsets=out_offsets, recv_sizes=recv_sizes,
                    recv_offsets=recv_offsets,
                )
            if cfg.profile_phases:
                prof.fence(x_recv)

        with trace_span("moe.expert"):
            # ---- regroup src-major -> tile-padded expert-major ----
            rows = jnp.arange(recv_bound, dtype=jnp.int32)
            target, grouped_rows, tile_gid, total_recv = _regroup_maps(
                recv_cmat, recv_offsets, recv_sizes, recv_bound, block_m)
            x_grp = jnp.zeros((grouped_rows, h), xs.dtype)
            x_grp = x_grp.at[target].set(x_recv, mode="drop")

            # ---- expert FFN on the local shard of weights ----
            y_grp = _grouped_ffn(
                x_grp, tile_gid,
                (params["w_up"], params["b_up"], params["w_down"],
                 params["b_down"], w_gate_p),
                cfg, use_pallas=use_pallas, interpret=interpret,
                block_m=block_m)
            if cfg.profile_phases:
                prof.fence(y_grp)

        with trace_span("moe.a2a_combine"):
            # ---- return path: expert-major -> src-major -> ragged back
            y_src_major = y_grp[target.clip(0, grouped_rows - 1)]
            y_src_major = jnp.where(
                (rows < total_recv)[:, None], y_src_major, 0
            ).astype(xs.dtype)

            # returned rows must land where the source originally staged
            # them: on rank s that's s's input_offsets[my] = exclusive
            # row-cumsum of its send sizes — from the gathered matrix
            rev_out_offsets = (
                jnp.cumsum(all_send, axis=1) - all_send
            )[:, my].astype(jnp.int32)
            if cfg.collect_stats and wire_comb is not None:
                comb_err = wr.roundtrip_error(y_src_major, wire_comb)
                wire_err = (comb_err if wire_err is None
                            else jnp.maximum(wire_err, comb_err))
            if skip_exchange:
                ys = _pad_rows(y_src_major, n_assign)
            else:
                ys = _wired_row_exchange(
                    y_src_major, wire_comb, axis=axis, d=d,
                    exchange=exchange,
                    block_rows=n_assign, out_bound=n_assign,
                    send_offsets=recv_offsets, send_sizes=recv_sizes,
                    remote_offsets=rev_out_offsets, recv_sizes=send_sizes,
                    recv_offsets=input_offsets,
                )
            if cfg.profile_phases:
                prof.fence(ys)

    # ---- combine in the original expert-sorted layout ----
    with trace_span("moe.combine"):
        healthy = None
        combine_w = r.combine_weights
        if cfg.degrade_unhealthy_experts:
            # tier-0 (ops/health.py): ys is expert-sorted by GLOBAL
            # expert with per-expert row counts in plan.counts (block-1
            # layout: padded == exact), so segment health maps rows ->
            # experts; the ragged combine does not renormalize, so the
            # mask does
            from flashmoe_tpu.ops import health as hlt

            healthy = hlt.expert_health_segments(ys, plan.counts)
            ys, combine_w = hlt.degrade_outputs(
                ys, combine_w, r.expert_idx, healthy, renormalize=True)
        out = rag.ragged_combine(ys, plan, combine_w, cfg)
        if cfg.profile_phases:
            prof.fence(out)

    aux = jax.lax.pmean(r.aux_loss, reduce_axes) * cfg.aux_loss_coef
    z = jax.lax.pmean(r.z_loss, reduce_axes)
    cnts = jax.lax.psum(r.expert_counts, reduce_axes)
    stats = None
    if cfg.collect_stats:
        # dropless: capacity=None reports zero drops / full utilization
        local = st.moe_stats(r, cfg, None)
        stats = st.reduce_stats(local, r.probs_mean, reduce_axes)
        if healthy is not None:
            from flashmoe_tpu.ops import health as hlt

            stats = hlt.attach_degradation(stats, healthy, r.expert_idx,
                                           reduce_axes)
        if wire_err is not None:
            stats = st.with_wire_error(stats, wire_err, reduce_axes)
        if quant_err is not None:
            stats = st.with_quant_error(stats, quant_err, reduce_axes)
    return MoEOutput(out.astype(cfg.dtype), aux, z, cnts, stats)


def decode_moe_rows(params, x, cfg: MoEConfig, *, axis: str = "ep",
                    exchange: str | None = None,
                    block_m: int = BLOCK_M) -> MoEOutput:
    """Run the ragged EP MoE on LOCAL batch rows from inside an
    ENCLOSING ``shard_map`` — the serving engine's EP-sharded decode
    step, where the caller already owns the mesh and this layer is one
    stage of a larger sharded body (attention + paged KV around it).

    ``params`` are the local expert shard (``gate_w`` replicated);
    ``x``: ``[b_local, H]`` decode rows.  Decode batches are
    token-count-tiny, so the XLA grouped path (no Pallas) is always the
    right arm here, exactly as in the unsharded decode step."""
    if cfg.num_shared_experts:
        raise NotImplementedError("shared experts stay outside this layer")
    if exchange is None:
        exchange = "ragged" if jax.default_backend() == "tpu" else "dense"
    return _ragged_ep_shard(
        params, x, cfg, axis=axis, use_pallas=False, interpret=False,
        exchange=exchange, block_m=block_m, reduce_axes=(axis,))


def ragged_ep_moe_layer(params, x, cfg: MoEConfig, mesh: Mesh, *,
                        use_pallas: bool = False, interpret: bool = False,
                        exchange: str | None = None,
                        block_m: int = BLOCK_M,
                        token_axes: tuple[str, ...] = ("ep",),
                        skip_exchange: bool = False) -> MoEOutput:
    """Dropless expert-parallel MoE over the ``ep`` axis.

    ``exchange``: "ragged" (TPU ``ragged_all_to_all``) or "dense" (padded
    ``all_to_all`` fallback — same layout logic, used on backends without
    the ragged op).  Default picks by backend.

    ``skip_exchange`` elides the row transfers (metadata collectives
    stay) while keeping every other stage and shape — the compute-only
    leg of the overlap measurement (:mod:`flashmoe_tpu.parallel.overlap`);
    the result is numerically meaningless.
    """
    if cfg.num_shared_experts:
        raise NotImplementedError("shared experts stay outside this layer")
    if exchange is None:
        exchange = "ragged" if jax.default_backend() == "tpu" else "dense"

    body = functools.partial(
        _ragged_ep_shard, cfg=cfg, axis="ep", use_pallas=use_pallas,
        interpret=interpret, exchange=exchange, block_m=block_m,
        reduce_axes=token_axes, skip_exchange=skip_exchange,
    )
    pspecs = {k: P("ep") if k != "gate_w" else P() for k in params}
    stats_specs = (st.MoEStats(*([P()] * len(st.MoEStats._fields)))
                   if cfg.collect_stats else None)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspecs, P(token_axes, None)),
        out_specs=MoEOutput(P(token_axes, None), P(), P(), P(),
                            stats_specs),
        check_vma=False,
    )
    return fn(params, x)
