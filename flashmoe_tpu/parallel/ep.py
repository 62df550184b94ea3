"""Expert-parallel MoE layer: shard_map + all-to-all over the TPU mesh.

TPU-native re-design of the reference's distributed core: there, the gate's
``tokenIds`` compaction feeds ``packet::dispatch`` which writes each expert's
tokens straight into peer GPUs' symmetric-heap cells with NVSHMEM
put-with-signal (``csrc/include/flashmoe/os/packet.cuh:20-286``), expert FFNs
run as scheduled tiles, and results return by the same transport before a
scatter-add combine (``os/processor/processor.cuh:711-767``).

Here the same movement is an SPMD program over the ``ep`` mesh axis:

  1. every rank routes its local token shard (full-E routing decisions),
  2. scatters tokens into a capacity-padded ``[E, C_loc, H]`` buffer,
  3. ``jax.lax.all_to_all`` over ``ep`` exchanges expert-major slabs —
     XLA lowers this to ICI-optimal transfers (the analogue of the
     NVSHMEM heap cells being sliced per (peer, expert-slot, capacity),
     ``types.cuh:1014-1032``),
  4. local experts run the grouped FFN on ``[nLx, D*C_loc, H]``,
  5. the reverse all-to-all returns results and each rank combines its own
     tokens with deterministic weighted gathers.

Compute/communication overlap — the reference's headline trick — is XLA's
latency-hiding scheduler's job at this level (it overlaps the all-to-all
with surrounding compute); the fused Pallas path in
:mod:`flashmoe_tpu.parallel.fused` goes further with device-initiated
remote DMA inside the kernel.

Both exchanges optionally compress their payload to a narrow wire dtype
(``MoEConfig.wire_dtype`` / ``wire_dtype_combine`` —
:mod:`flashmoe_tpu.ops.wire`): rows quantize just before the a2a and
dequantize just after, halving (bf16) or quartering (fp8 + f32 per-row
scale sidecar) the ICI/DCN bytes while every compute stage stays at the
compute dtype.  Off by default; the wire-off graph is bit-identical.
On a multi-slice (two-stage) exchange, ``MoEConfig.wire_dtype_dcn``
additionally re-encodes the CROSS-SLICE hop at its own (narrower)
dtype — fp8 across DCN while the in-slice ICI hop stays bf16/f32 —
on both legs; default None inherits the leg wire (graph-identical to
the single-dtype build).

With ``MoEConfig.a2a_chunks = n`` the exchange additionally runs as a
chunked software pipeline (Comet, arXiv 2502.19811): the ``[D, nLx, C,
H]`` slab splits into ``n`` chunks along the local-expert axis and each
chunk runs its own dispatch-a2a -> expert-FFN -> combine-a2a chain.
The ``n`` chains are independent in the graph (unrolled, no carried
state), so XLA's latency-hiding scheduler can issue chunk ``k+1``'s
all-to-all while chunk ``k``'s GEMMs occupy the MXU — on both legs,
for the flat and the hierarchical exchange, with the wire codec
encoding/decoding per chunk inside the pipeline.  ``None`` (default)
keeps the serial single-slab schedule bit-identical to previous
builds; the planner prices the pipeline and picks ``n`` under
``moe_backend='auto'`` (:mod:`flashmoe_tpu.planner`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flashmoe_tpu.config import MoEConfig
from flashmoe_tpu.models.reference import shared_expert_ffn
from flashmoe_tpu.ops import dispatch as dsp
from flashmoe_tpu.ops import expert as exp
from flashmoe_tpu.ops import stats as st
from flashmoe_tpu.ops import wire as wr
from flashmoe_tpu.ops.gate import router
from flashmoe_tpu.ops.moe import MoEOutput, dense_ffn
from flashmoe_tpu.profiler import spans as prof
from flashmoe_tpu.utils.telemetry import trace_span


#: reduction collectives one EP-layer forward traces to, knobs off: the
#: aux-loss pmean, the z-loss pmean, and the expert-count psum (pmean
#: lowers to psum + div).  A contract constant, not documentation:
#: ``analysis.comm_census`` expects exactly this many psum eqns and the
#: collective census (:mod:`flashmoe_tpu.staticcheck.census`) fails CI
#: when the traced graph disagrees — add a reduction, update this, and
#: the census diff shows the new collective was priced on purpose.
EXPECTED_PSUMS = 3


def local_capacity(cfg: MoEConfig, s_local: int) -> int:
    """Per-(rank, expert) capacity over a local token shard (EC formula of
    ``types.cuh:497-499`` applied shard-locally)."""
    return cfg.capacity_for(s_local)


def _hierarchical_a2a(t, axis: str, d: int, inner: int, *, reverse: bool):
    """Two-stage all-to-all over a (outer x inner) factorization of the ep
    axis — the multi-slice pattern: the inner stage rides ICI within a
    slice, the outer stage sends one aggregated message per slice pair
    over DCN instead of ``inner**2`` small ones (the ICI-vs-DCN duality of
    the reference's P2P-vs-IBGDA transports, ``bootstrap.cuh:442-446``).

    t: [D, ...] dest-major slabs (rank = outer * inner + inner_idx).
    Returns [D, ...] source-major, identical to a flat all_to_all.
    Composed from :func:`_hier_stage` (one definition of the group
    structure) so the per-hop wire path can never drift from it.
    """
    stages = ["inner", "outer"]
    if reverse:
        stages = stages[::-1]
    for stage in stages:
        t = _hier_stage(t, axis, d, inner, stage=stage)
    return t


def _hier_stage(t, axis: str, d: int, inner: int, *, stage: str):
    """ONE hop of the two-stage exchange on a ``[D, ...]`` dest-major
    array: ``stage='inner'`` is the within-slice ICI exchange,
    ``stage='outer'`` the cross-slice DCN exchange.  Composing
    inner-then-outer (or the reverse) reproduces
    :func:`_hierarchical_a2a` exactly; the split exists so the per-hop
    wire codec (``MoEConfig.wire_dtype_dcn``) can re-encode at the hop
    boundary."""
    outer = d // inner
    rest = t.shape[1:]
    t = t.reshape((outer, inner) + rest)
    if stage == "inner":
        ax = 1
        groups = [[o * inner + i for i in range(inner)]
                  for o in range(outer)]
    else:
        ax = 0
        groups = [[o * inner + j for o in range(outer)]
                  for j in range(inner)]
    t = jax.lax.all_to_all(
        t, axis, split_axis=ax, concat_axis=ax, tiled=False,
        axis_index_groups=groups,
    )
    return t.reshape((d,) + rest)


def _staged_wired(t, wire_dtype, axis: str, d: int, inner: int, *,
                  stage: str):
    """One hierarchical hop with its own wire: encode at ``wire_dtype``
    (None = raw), exchange payload (+fp8 scale sidecar) over that hop
    only, decode back to the compute dtype before the next hop."""
    if wire_dtype is None:
        return _hier_stage(t, axis, d, inner, stage=stage)
    payload, scales = wr.encode(t, wire_dtype)
    payload = _hier_stage(payload, axis, d, inner, stage=stage)
    if scales is not None:
        scales = _hier_stage(scales, axis, d, inner, stage=stage)
    return wr.decode(payload, scales, t.dtype)


def _exchange(t, axis: str, d: int, dcn_inner: int | None, *,
              reverse: bool):
    """One a2a hop of a ``[D, ...]`` dest-major array: the two-stage
    ICI+DCN decomposition when a slice blocking is known, the flat
    ``all_to_all`` otherwise.  Shape-generic so the wire codec's payload
    and scale sidecar ride the identical route."""
    if dcn_inner is not None and 1 < dcn_inner < d:
        return _hierarchical_a2a(t, axis, d, dcn_inner, reverse=reverse)
    return jax.lax.all_to_all(
        t, axis, split_axis=0, concat_axis=0, tiled=False,
    )


def _wired_exchange(t, wire_dtype, axis: str, d: int,
                    dcn_inner: int | None, *, reverse: bool,
                    wire_dcn=None):
    """Exchange ``t`` ([D, ..., H], rows on the last axis), quantized to
    ``wire_dtype`` for the wire only (``None`` = raw — the graph is then
    exactly the pre-compression one).  For fp8 wires the per-row f32
    scales ride the same (flat or hierarchical) route as the payload, so
    both hops of the two-stage exchange stay consistent.

    ``wire_dcn`` (resolved ``MoEConfig.wire_dtype_dcn``): a distinct
    wire for the CROSS-SLICE hop of the hierarchical exchange.  None
    inherits ``wire_dtype`` — one encode covers both hops and the graph
    is byte-identical to the single-dtype build (the default path
    below, unchanged).  Set (and a slice blocking active), each hop
    encodes independently: the ICI stage at the leg wire, the DCN stage
    at ``wire_dcn`` — so e.g. an fp8 DCN hop under a raw/bf16 in-slice
    hop.  Inert on the flat exchange (no DCN hop exists)."""
    hier = dcn_inner is not None and 1 < dcn_inner < d
    if wire_dcn is not None and hier:
        stages = [("inner", wire_dtype), ("outer", wire_dcn)]
        if reverse:
            stages = stages[::-1]
        for stage, wd in stages:
            t = _staged_wired(t, wd, axis, d, dcn_inner, stage=stage)
        return t
    if wire_dtype is None:
        return _exchange(t, axis, d, dcn_inner, reverse=reverse)
    payload, scales = wr.encode(t, wire_dtype)
    payload = _exchange(payload, axis, d, dcn_inner, reverse=reverse)
    if scales is not None:
        scales = _exchange(scales, axis, d, dcn_inner, reverse=reverse)
    return wr.decode(payload, scales, t.dtype)


def _ep_moe_shard(params, x, cfg: MoEConfig, *, axis: str, use_pallas: bool,
                  reduce_axes: tuple[str, ...] = ("ep",),
                  tp_axis: str | None = None,
                  dcn_inner: int | None = None,
                  interpret: bool = False,
                  skip_exchange: bool = False):
    """Per-rank body (runs inside shard_map over the ep axis).

    x: [S_loc, H] local tokens; params: expert weights sharded on axis 0
    (leading dim nLx), gate replicated.  With ``tp_axis``, each expert's
    intermediate dimension is additionally Megatron-split across tp ranks
    (column-parallel up/gate, row-parallel down, one psum per FFN).

    ``skip_exchange`` elides both all-to-alls while keeping every other
    stage and shape identical — the compute-only leg of the overlap-
    efficiency measurement (:mod:`flashmoe_tpu.parallel.overlap`); the
    result is numerically meaningless (tokens meet the wrong experts).
    """
    d = jax.lax.axis_size(axis)
    s_loc, h = x.shape
    e, nlx = cfg.num_experts, cfg.num_experts // d
    cap = local_capacity(cfg, s_loc)
    # quantized expert storage (flashmoe_tpu/quant/): resolve this
    # rank's FFN weight shard to its dequant-in-compute form before
    # any slicing/exchange logic sees it — payloads (and their _qscale
    # siblings, sharded P('ep') like everything else) dequantize here;
    # full-precision params fake-quant in-graph.  Called
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
    # the DCN-hop override only exists on a two-stage exchange; resolve
    # it to None otherwise so the flat transport traces the identical
    # graph whatever the knob says (it has no DCN hop to re-encode)
    hier_on = dcn_inner is not None and 1 < dcn_inner < d
    wire_dcn = wr.resolve(cfg.wire_dtype_dcn) if hier_on else None

    # phase spans mirror the reference's NVTX "Flashmoe" domain
    # (telemetry.cuh): named HLO scopes so xprof traces show gate /
    # dispatch / a2a / expert / combine as distinct phases.  Pure
    # metadata — no ops added, the stats-off graph is unchanged.  With
    # cfg.profile_phases the spans additionally fence (prof.fence:
    # block_until_ready on concrete eager values, a no-op on tracers),
    # so a host-armed PhaseTimeline measures real per-phase wall time
    # — the xprof-free phase timeline of flashmoe_tpu/profiler.
    with trace_span("moe.gate"):
        r = router(x, params["gate_w"], cfg, use_pallas=use_pallas,
                   interpret=interpret)
        if cfg.profile_phases:
            prof.fence(r)
    with trace_span("moe.dispatch"):
        plan = dsp.make_plan(r.expert_idx, cfg, cap)
        xbuf = dsp.dispatch(x.astype(cfg.dtype), plan, cfg, cap)  # [E, C, H]
        if cfg.profile_phases:
            prof.fence(xbuf)

    from flashmoe_tpu.chaos import inject as chaos_inject

    ffn_params = params
    if tp_axis is not None:
        # row-parallel down bias: each tp rank contributes 1/tp of it so
        # the psum reconstructs it exactly once
        tp = jax.lax.axis_size(tp_axis)
        ffn_params = dict(params, b_down=params["b_down"] / tp)

    def ffn(buf, p):
        """Expert FFN on a [nE, D*C, H] buffer with nE-leading params —
        one definition for the serial slab and every pipeline chunk."""
        if use_pallas:
            y = exp.capacity_buffer_ffn_ad(buf, p, cfg, interpret)
        else:
            y = exp.expert_ffn_dense(buf, p, cfg)
        if tp_axis is not None:
            y = jax.lax.psum(y, tp_axis)
        return y

    n_chunks = cfg.a2a_chunks or 1
    if n_chunks > 1 and nlx % n_chunks:
        raise ValueError(
            f"a2a_chunks={n_chunks} does not divide the local-expert "
            f"axis (num_experts={e} // ep={d} = {nlx}); pick a divisor "
            f"or leave a2a_chunks=None for the serial schedule")

    # exchange expert-major slabs: [E, C, H] -> [D, nLx, C, H] received
    wire_err = None
    dcn_err = None
    send = xbuf.reshape(d, nlx, cap, h)
    if cfg.collect_stats and wire_disp is not None:
        # round-trip error proxy on the payload actually shipped —
        # stats-gated, so the stats-off graph carries no extra pass
        wire_err = wr.roundtrip_error(send, wire_disp)
    if cfg.collect_stats and wire_dcn is not None:
        # per-hop proxy for the DCN stage's own wire (wire_dtype_dcn):
        # the same send payload quantized at the cross-slice dtype, so
        # the flight recorder sees each hop's loss separately
        dcn_err = wr.roundtrip_error(send, wire_dcn)

    if n_chunks > 1:
        # Chunked double-buffered pipeline (Comet, arXiv 2502.19811):
        # n independent dispatch-a2a -> FFN -> combine-a2a chains over
        # local-expert sub-slabs.  Unrolled on purpose — no carried
        # state between chunks, so the latency-hiding scheduler is free
        # to run chunk k+1's exchange under chunk k's GEMMs.  Per-chunk
        # trace spans make pipeline occupancy visible in xprof.
        ffn_keys = ("w_up", "w_gate", "b_up", "w_down", "b_down")
        comb_err = None
        nc = nlx // n_chunks
        ybacks = []
        for ck in range(n_chunks):
            lo = ck * nc
            with trace_span(f"moe.a2a_dispatch.{ck}"):
                send_k = send[:, lo:lo + nc]
                if skip_exchange:
                    recv_k = send_k
                else:
                    recv_k = _wired_exchange(send_k, wire_disp, axis, d,
                                             dcn_inner, reverse=False,
                                             wire_dcn=wire_dcn)
                if cfg.profile_phases:
                    prof.fence(recv_k)
            p_k = {kk: (v[lo:lo + nc] if kk in ffn_keys else v)
                   for kk, v in ffn_params.items()}
            with trace_span(f"moe.expert.{ck}"):
                ybuf_k = recv_k.transpose(1, 0, 2, 3).reshape(
                    nc, d * cap, h)
                yloc_k = ffn(ybuf_k, p_k)
                if cfg.profile_phases:
                    prof.fence(yloc_k)
            if chaos_inject.is_armed("nan_expert"):  # trace-time check
                # same pre-exchange poisoning as the serial branch; the
                # chunk covers local experts [lo, lo+nc) of this owner
                yloc_k = chaos_inject.poison_local_expert(
                    yloc_k, axis, e, local_offset=lo, local_total=nlx)
            with trace_span(f"moe.a2a_combine.{ck}"):
                ysend_k = yloc_k.reshape(nc, d, cap, h).transpose(
                    1, 0, 2, 3)
                if cfg.collect_stats and wire_comb is not None:
                    err_k = wr.roundtrip_error(ysend_k, wire_comb)
                    comb_err = (err_k if comb_err is None
                                else jnp.maximum(comb_err, err_k))
                if cfg.collect_stats and wire_dcn is not None:
                    errd_k = wr.roundtrip_error(ysend_k, wire_dcn)
                    dcn_err = (errd_k if dcn_err is None
                               else jnp.maximum(dcn_err, errd_k))
                if skip_exchange:
                    yback_k = ysend_k
                else:
                    yback_k = _wired_exchange(ysend_k, wire_comb, axis,
                                              d, dcn_inner, reverse=True,
                                              wire_dcn=wire_dcn)
                if cfg.profile_phases:
                    prof.fence(yback_k)
            ybacks.append(yback_k)
        # [D, nc, C, H] chunks -> [D, nLx, C, H] -> [E, C, H]: global
        # expert id = owner_rank * nLx + local index, so chunks stack
        # along the local-expert axis
        ybuf = jnp.concatenate(ybacks, axis=1).reshape(e, cap, h)
        if comb_err is not None:
            wire_err = (comb_err if wire_err is None
                        else jnp.maximum(wire_err, comb_err))
    else:
        with trace_span("moe.a2a_dispatch"):
            if skip_exchange:
                recv = send
            else:
                recv = _wired_exchange(send, wire_disp, axis, d,
                                       dcn_inner, reverse=False,
                                       wire_dcn=wire_dcn)
                # [D, nLx, C, H] — dim 0 now indexes source rank
            if cfg.profile_phases:
                prof.fence(recv)
        with trace_span("moe.expert"):
            ybuf_in = recv.transpose(1, 0, 2, 3).reshape(nlx, d * cap, h)
            yloc = ffn(ybuf_in, ffn_params)
            if cfg.profile_phases:
                prof.fence(yloc)

        if chaos_inject.is_armed("nan_expert"):  # trace-time check only
            # poison BEFORE the return exchange: the fault originates at
            # the sick expert's owner and must cross the transport —
            # wire compression included — before the health mask sees it
            # (the chaos drill's through-the-wire guarantee,
            # tests/test_chaos.py).  The armed spec names a GLOBAL
            # expert id, exactly as at the [E, C, H] hook site in
            # ops/moe.py.
            yloc = chaos_inject.poison_local_expert(yloc, axis, e)

        # reverse: [nLx, D*C, H] -> [D, nLx, C, H] -> a2a -> [E, C, H]
        with trace_span("moe.a2a_combine"):
            ysend = yloc.reshape(nlx, d, cap, h).transpose(1, 0, 2, 3)
            if cfg.collect_stats and wire_comb is not None:
                comb_err = wr.roundtrip_error(ysend, wire_comb)
                wire_err = (comb_err if wire_err is None
                            else jnp.maximum(wire_err, comb_err))
            if cfg.collect_stats and wire_dcn is not None:
                errd = wr.roundtrip_error(ysend, wire_dcn)
                dcn_err = (errd if dcn_err is None
                           else jnp.maximum(dcn_err, errd))
            if skip_exchange:
                yback = ysend
            else:
                yback = _wired_exchange(ysend, wire_comb, axis, d,
                                        dcn_inner, reverse=True,
                                        wire_dcn=wire_dcn)
                # [D, nLx, C, H] — dim 0 indexes expert-owner rank
            if cfg.profile_phases:
                prof.fence(yback)
        ybuf = yback.reshape(e, cap, h)

    healthy = None
    combine_w = r.combine_weights
    if cfg.degrade_unhealthy_experts:
        # tier-0 (ops/health.py): ybuf rows are THIS rank's tokens'
        # results per global expert, so each rank detects and masks its
        # own exposure to a sick expert locally — no extra collective
        from flashmoe_tpu.ops import health as hlt

        healthy = hlt.expert_health_capacity(ybuf)
        ybuf, combine_w = hlt.degrade_outputs(ybuf, combine_w,
                                              r.expert_idx, healthy)
    with trace_span("moe.combine"):
        out = dsp.combine(ybuf, plan, combine_w, cfg, cap)
        if cfg.num_shared_experts:
            out = out + shared_expert_ffn(
                x.astype(cfg.dtype), params, cfg
            ).astype(out.dtype)
        if cfg.profile_phases:
            prof.fence(out)

    aux = jax.lax.pmean(r.aux_loss, reduce_axes) * cfg.aux_loss_coef
    z = jax.lax.pmean(r.z_loss, reduce_axes)
    counts = jax.lax.psum(r.expert_counts, reduce_axes)
    stats = None
    if cfg.collect_stats:
        local = st.moe_stats(r, cfg, cap)
        stats = st.reduce_stats(local, r.probs_mean, reduce_axes)
        if healthy is not None:
            from flashmoe_tpu.ops import health as hlt

            stats = hlt.attach_degradation(stats, healthy, r.expert_idx,
                                           reduce_axes)
        if wire_err is not None or dcn_err is not None:
            stats = st.with_wire_error(stats, wire_err, reduce_axes,
                                       dcn_error=dcn_err)
        if quant_err is not None:
            stats = st.with_quant_error(stats, quant_err, reduce_axes)
    return MoEOutput(out.astype(cfg.dtype), aux, z, counts, stats)


def ep_moe_layer(params, x, cfg: MoEConfig, mesh: Mesh, *,
                 use_pallas: bool = False,
                 token_axes: tuple[str, ...] = ("ep",),
                 tp: bool | None = None,
                 dcn_inner: int | None = None,
                 interpret: bool = False,
                 skip_exchange: bool = False) -> MoEOutput:
    """Expert-parallel MoE layer over a global token batch.

    x: [S, H] global tokens, sharded over ``token_axes`` (e.g.
    ``('dp', 'ep')`` inside a data-parallel model — the all-to-all then
    runs within each dp group).  Expert params shard over 'ep' and are
    replicated across the other axes, except with ``tp`` (default: on when
    the mesh's tp axis > 1), where each expert's intermediate dimension is
    Megatron-split over 'tp' as well.

    ``dcn_inner``: ranks per slice when the ep axis spans slices — the
    all-to-all then runs as a two-stage (intra-slice, inter-slice)
    decomposition aggregating DCN traffic per slice pair.  Default
    (None): a bootstrapped runtime that detected a multislice blocking
    (``topology.slice_structure``) publishes it, the way it publishes the
    arrival-order schedule; pass ``0`` to force the flat exchange.
    """
    if dcn_inner is None:
        from flashmoe_tpu.runtime.bootstrap import current_dcn_inner

        dcn_inner = current_dcn_inner(mesh, mesh.shape.get("ep", 1))
    elif dcn_inner == 0:
        dcn_inner = None
    if cfg.num_experts == 1:
        return MoEOutput(
            dense_ffn(params, x, cfg),
            jnp.zeros((), cfg.accum_dtype), jnp.zeros((), cfg.accum_dtype),
            jnp.full((1,), x.shape[0], jnp.int32),
        )

    use_tp = tp if tp is not None else (
        "tp" in mesh.shape and mesh.shape["tp"] > 1
    )
    tp_specs = {
        "w_up": P("ep", None, "tp"),
        "w_gate": P("ep", None, "tp"),
        "b_up": P("ep", "tp"),
        "w_down": P("ep", "tp", None),
        "b_down": P("ep", None),
    }
    pspecs = {}
    for k in params:
        if k == "gate_w" or k.startswith("shared"):
            pspecs[k] = P()
        elif use_tp and k in tp_specs:
            pspecs[k] = tp_specs[k]
        else:
            pspecs[k] = P("ep")
    body = functools.partial(
        _ep_moe_shard, cfg=cfg, axis="ep", use_pallas=use_pallas,
        reduce_axes=token_axes, tp_axis="tp" if use_tp else None,
        dcn_inner=dcn_inner, interpret=interpret,
        skip_exchange=skip_exchange,
    )
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


def resolve_moe_backend(cfg: MoEConfig, mesh: Mesh | None = None) -> str:
    """The concrete moe_backend this layer stack should run.

    Pass-through for explicit configs; ``moe_backend='auto'`` consults
    the analytical planner (:mod:`flashmoe_tpu.planner.select`) — the
    predicted per-path latency winner, overridden by measured entries
    when the tuning table covers this shape.  The
    decision and its full breakdown land in telemetry
    (``metrics.decision('planner.path_select', ...)``)."""
    from flashmoe_tpu.planner.select import resolve_moe_backend as _resolve

    return _resolve(cfg, mesh)


def resolve_moe_plan(cfg: MoEConfig, mesh: Mesh | None = None, *,
                     mode: str | None = None,
                     decode_tokens: int | None = None
                     ) -> tuple[str, int | None]:
    """(moe_backend, a2a_chunks) an ``moe_backend='auto'`` config should
    run: the planner's path winner plus its chunked-pipeline pick for
    the XLA transports (``None`` = serial).  Explicit configs pass
    through with their own ``cfg.a2a_chunks``.  ``mode`` selects the
    pricing regime (None reads ``cfg.serving_mode`` — a decode-phase
    config resolves a decode-priced plan; ``decode_tokens`` is the
    per-step decode batch)."""
    from flashmoe_tpu.planner.select import resolve_moe_plan as _resolve

    return _resolve(cfg, mesh, mode=mode, decode_tokens=decode_tokens)


def apply_chunk_pick(cfg: MoEConfig, backend: str,
                     chunks: int | None) -> MoEConfig:
    """Thread the planner's chunked-pipeline pick into a layer config
    (the shard bodies read ``cfg.a2a_chunks``).  An explicit
    ``cfg.a2a_chunks`` — or a backend/shape the pick cannot serve —
    passes through untouched; the one guard both call sites
    (``auto_ep_moe_layer``, the transformer's FFN block) must share."""
    if (chunks and chunks > 1 and cfg.a2a_chunks is None
            and backend in ("collective", "ragged")
            and cfg.num_experts // max(cfg.ep, 1) % chunks == 0):
        return cfg.replace(a2a_chunks=chunks)
    return cfg


def auto_ep_moe_layer(params, x, cfg: MoEConfig, mesh: Mesh, *,
                      use_pallas: bool = False,
                      token_axes: tuple[str, ...] = ("ep",),
                      interpret: bool = False,
                      collective_id: int = 7) -> MoEOutput:
    """Expert-parallel MoE layer on the planner-selected path.

    Same contract as :func:`ep_moe_layer`; the transport (collective /
    ragged / fused RDMA) — and the chunked-pipeline depth for the XLA
    transports — is chosen by :func:`resolve_moe_plan` for this
    (cfg, mesh) instead of being hard-coded by the caller."""
    backend, chunks = resolve_moe_plan(cfg, mesh)
    cfg = apply_chunk_pick(cfg, backend, chunks)
    try:
        if backend == "fused":
            from flashmoe_tpu.parallel.fused import fused_ep_moe_layer

            return fused_ep_moe_layer(params, x, cfg, mesh,
                                      token_axes=token_axes,
                                      collective_id=collective_id,
                                      interpret=interpret)
        if backend == "ragged":
            from flashmoe_tpu.parallel.ragged_ep import ragged_ep_moe_layer

            return ragged_ep_moe_layer(params, x, cfg, mesh,
                                       use_pallas=use_pallas,
                                       interpret=interpret,
                                       token_axes=token_axes)
    except Exception as e:  # noqa: BLE001 — tier-2 path fallback
        # a specialized transport failing at trace time demotes to the
        # collective baseline (and is remembered, so the next resolution
        # never retries it) instead of killing the step — the RaMP-style
        # runtime path polymorphism of docs/RESILIENCE.md
        from flashmoe_tpu.planner.select import report_path_failure

        report_path_failure(backend, f"{type(e).__name__}: {e}")
    return ep_moe_layer(params, x, cfg, mesh, use_pallas=use_pallas,
                        token_axes=token_axes, interpret=interpret)
