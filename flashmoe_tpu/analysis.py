"""Hardware-independent performance evidence: HLO cost analysis + an
analytical HBM-byte/FLOP model of every candidate execution path.

Four rounds of this framework shipped kernels whose relative performance
was argued from design notes ("dispatch/combine HBM traffic is the gap")
while no chip could be reached.  This module converts those arguments
into checked numbers two ways:

  * :func:`xla_cost` measures a compiled XLA path's FLOPs / bytes with
    ``jit(...).lower().compile().cost_analysis()`` — real compiler
    numbers, available on any backend (CPU included), no execution.
  * :func:`path_costs` prices each candidate path's HBM traffic from the
    kernels' actual DMA structure (every term cites the code that moves
    those bytes).  Pallas kernels are custom calls the HLO analysis
    cannot see into, so their traffic is modeled, not measured — but
    modeled from the DMA calls in the source, and the orderings the
    model implies are asserted in ``tests/test_cost_model.py``, giving
    every hardware-blind round a perf-regression gate (VERDICT r4 next
    #2).

The reference's analogue of this accounting is the roofline analysis in
the FlashDMoE paper (arXiv:2506.04667 §5) — the repo itself ships only
measured plots (``/root/reference/README.md:29-46``).

Byte conventions: HBM bytes only (VMEM traffic is free at this
granularity); a remote DMA is counted once as a read on the sender and
once as a write on the receiver, which matches per-chip HBM pressure on
a torus where every hop is chip-to-chip.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from flashmoe_tpu.config import MoEConfig


def xla_cost(fn, *abstract_args) -> dict:
    """FLOPs / bytes-accessed of ``fn`` compiled at abstract shapes.

    ``abstract_args`` are ``jax.ShapeDtypeStruct``s (or arrays); nothing
    executes.  Returns ``{"flops": float, "bytes": float}``; either can
    be ``None`` when the backend's cost model omits the key."""
    compiled = jax.jit(fn).lower(*abstract_args).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {
        "flops": ca.get("flops"),
        "bytes": ca.get("bytes accessed"),
    }


def wire_row_bytes(cfg: MoEConfig, leg: str = "dispatch",
                   hop: str = "ici") -> float:
    """Bytes ONE token row occupies on the EP all-to-all wire for
    ``leg`` ('dispatch' | 'combine'): ``H x wire itemsize`` plus the
    4-byte f32 per-row scale sidecar for fp8 wires
    (:mod:`flashmoe_tpu.ops.wire`), or ``H x compute itemsize`` when the
    leg's wire is off.  Every comm term below — and the planner's slab
    serialization (:mod:`flashmoe_tpu.planner.model`) — prices the
    exchange through this one function, so the byte model can never
    disagree with the codec about what actually crosses the wire.

    ``hop`` selects the stage of a two-stage multi-slice exchange being
    priced: ``'ici'`` (default — also the flat exchange, which carries
    the leg wire end to end) or ``'dcn'``, where
    ``MoEConfig.wire_dtype_dcn`` overrides the leg wire when set (None
    inherits — both hops then price identically, matching the codec's
    single-encode path)."""
    from flashmoe_tpu.ops import wire as wr

    if leg not in ("dispatch", "combine"):
        raise ValueError(f"unknown wire leg {leg!r}")
    if hop not in ("ici", "dcn"):
        raise ValueError(f"unknown wire hop {hop!r}")
    name = cfg.wire_dtype if leg == "dispatch" else cfg.wire_dtype_combine
    if hop == "dcn" and cfg.wire_dtype_dcn is not None:
        name = cfg.wire_dtype_dcn
    wd = wr.resolve(name)
    return (wr.payload_row_bytes(wd, cfg.hidden_size, cfg.dtype)
            + wr.scale_bytes(wd))


def expert_weight_stream_bytes(cfg: MoEConfig, nlx: int, *,
                               quantized: bool = True) -> float:
    """HBM bytes ONE stream of ``nlx`` local experts' FFN weights
    costs.  With ``MoEConfig.expert_quant`` set (and ``quantized`` —
    the engine being priced actually streams the narrow store), each
    element moves at the store width (1 B for int8/e4m3,
    :func:`flashmoe_tpu.quant.core.weight_itemsize`) plus the f32
    per-output-channel scale sidecar; otherwise at the compute width.
    Every weight term in :func:`path_costs` prices through this one
    function, so the byte model can never disagree with the store
    about what actually streams.

    ``quantized=False`` is the honesty valve for engines that
    boundary-dequantize (the fused weights-once schedules — see
    ``parallel/fused.py:_fused_shard``): they stream compute-width
    weights even under a quantized store."""
    h, i = cfg.hidden_size, cfg.intermediate_size
    dt = jnp.dtype(cfg.dtype).itemsize
    w_mult = 3 if cfg.gated_ffn else 2
    if cfg.expert_quant is None or not quantized:
        return float(nlx * w_mult * h * i * dt)
    from flashmoe_tpu.quant import core as qcore

    wdt = qcore.weight_itemsize(cfg.expert_quant, cfg.dtype)
    # per-output-channel f32 scales: I channels each for up (+gate),
    # H for down — the tiny sidecar the stream also reads
    chans = (2 if cfg.gated_ffn else 1) * i + h
    return float(nlx * (w_mult * h * i * wdt
                        + qcore.scale_overhead_bytes(cfg.expert_quant,
                                                     chans)))


def layer_flops(cfg: MoEConfig, tokens: int | None = None) -> float:
    """Model FLOPs of one MoE-layer forward: gate GEMM + routed expert
    FFN (2 GEMMs, or 3 with the gated/SwiGLU branch), matching the
    reference config surface (``csrc/flashmoe_config.json``)."""
    s = tokens if tokens is not None else cfg.tokens
    gate = 2.0 * s * cfg.hidden_size * cfg.num_experts
    rows = s * cfg.expert_top_k
    gemms = 3 if cfg.gated_ffn else 2
    ffn = gemms * 2.0 * rows * cfg.hidden_size * cfg.intermediate_size
    return gate + ffn


@dataclasses.dataclass(frozen=True)
class PathCost:
    """HBM traffic decomposition of one candidate path (bytes, per chip).

    ``post_kernel_bytes`` is the subset of ``total_bytes`` that sits on
    the critical path AFTER the compute kernel finishes (an XLA combine
    stage's read+write cannot overlap the kernel; the in-kernel combine's
    traffic can).  ``weight_bytes`` is broken out because the streaming
    schedule multiplies it by ``n_row_tiles`` (VERDICT r4 weak #4)."""

    path: str
    weight_bytes: float
    activation_bytes: float
    dispatch_bytes: float
    comm_bytes: float
    combine_bytes: float
    post_kernel_bytes: float
    flops: float

    @property
    def total_bytes(self) -> float:
        return (self.weight_bytes + self.activation_bytes
                + self.dispatch_bytes + self.comm_bytes
                + self.combine_bytes)


def _geom(cfg: MoEConfig, d_world: int, fuse_combine: bool = False,
          schedule: str | None = None):
    """Shared geometry: local tokens, per-(rank, expert) capacity, row
    tiling, and the fused kernel's FFN schedule — resolved through the
    kernel's own public :func:`flashmoe_tpu.parallel.fused.
    schedule_table` (ISSUE 12 satellite: this module used to import the
    private ``_fused_schedule``/``_resolve_tiles`` helpers directly, so
    analysis/planner/census could drift from the geometry the kernel
    actually launches).  ``fuse_combine`` must mirror the path being
    priced, because the combine chunks claim VMEM the schedule gate
    accounts for (a mismatch here once under-charged the fused_combine
    table 4x; code-review r5 pass 2 finding #2).

    ``schedule`` overrides the kernel's own resolution ('batched',
    'resident', 'stream', 'rowwin') so the planner can price every
    schedule, not just the one the heuristics would pick; None keeps the
    kernel's choice.  For rowwin, ``bi`` is the IO-aware chooser's
    K-window width and ``n_i_chunks`` the window count."""
    from flashmoe_tpu.parallel.fused import schedule_table

    t = schedule_table(cfg, d_world, fuse_combine=fuse_combine,
                       schedule=schedule)
    return dict(s_loc=t["s_loc"], h=t["h"], i=t["i"], dt=t["dt"],
                cap=t["cap"], cap_raw=t["cap_raw"], cm=t["cm"],
                bi=t["bi"], gated=t["gated"], schedule=t["priced"],
                n_row_tiles=t["n_row_tiles"],
                n_i_chunks=t["n_i_chunks"])


def path_costs(cfg: MoEConfig, path: str, d_world: int = 1,
               schedule: str | None = None) -> PathCost:
    """Analytical per-chip HBM bytes for one forward of ``path``.

    Paths (single-chip unless noted):
      xla            dense-dispatch XLA baseline (``ops/moe.py``,
                     ``use_pallas=False``)
      explicit       capacity-buffer dispatch + grouped Pallas FFN
                     (``ops/expert.py:grouped_ffn``)
      gather         gather-fused inference kernel — rows pulled in-kernel,
                     no [E, C, H] dispatch buffer
                     (``ops/expert.py:grouped_ffn_tokens``)
      fused          RDMA kernel + XLA combine, d_world ranks
                     (``parallel/fused.py``, slab returns)
      fused_combine  RDMA kernel with the in-kernel sorted-return combine
                     (``parallel/fused.py`` + ``dispatch.sorted_return_maps``)

    ``schedule`` (fused paths only) forces the FFN schedule being priced;
    None resolves the kernel's actual choice.
    """
    g = _geom(cfg, d_world, fuse_combine=(path == "fused_combine"),
              schedule=schedule if path in ("fused", "fused_combine")
              else None)
    s, h, i, dt, cap = g["s_loc"], g["h"], g["i"], g["dt"], g["cap"]
    k = cfg.expert_top_k
    e = cfg.num_experts
    nlx = e // d_world
    rows = s * k                       # routed rows on this chip's tokens
    slots = d_world * nlx * cap        # slab slots touching this chip
    # EP exchange traffic of the XLA transports (d_world > 1): each a2a
    # leg reads the send buffer and writes the receive buffer — counted
    # once each per the module's remote-DMA convention, at the WIRE
    # row size (= compute row size when wire_dtype is off), so turning
    # compression on shrinks this term by the wire/compute itemsize
    # ratio (plus the fp8 scale sidecar).
    a2a_row = (wire_row_bytes(cfg, "dispatch")
               + wire_row_bytes(cfg, "combine")) if d_world > 1 else 0.0
    # weight bytes of the experts THIS chip computes, once per stream —
    # at the QUANTIZED store width when expert_quant is on.  Modeling
    # assumption (docs/PERF.md): dequant-in-compute reads the payload
    # at 1 B/elem with the convert fused into the matmul's operand
    # stream — exact for the rowwin streamer (in-VMEM dequant) and the
    # XLA einsum arm; the grouped Pallas kernels currently materialize
    # the dequantized copy layer-side, so their realized saving is
    # smaller than modeled until they grow an int8 arm.  The fused
    # weights-once schedules boundary-dequantize and are priced at
    # compute width below.
    w_once = expert_weight_stream_bytes(cfg, nlx)
    # Weight-streaming multiplicity differs per engine:
    #   * the grouped kernels (ops/expert.py) sort rows by expert, so a
    #     weight block is fetched once per consecutive expert run —
    #     explicit/gather/xla read weights ONCE per expert;
    #   * the fused RDMA kernel's multiplicity depends on its FFN
    #     schedule (parallel/fused.py:_fused_schedule): the per-source
    #     schedules re-stream every local expert's weights once per
    #     source rank — d_world x (times n_row_tiles when streaming
    #     per row tile); the round-5 arrival-batched schedule processes
    #     the own slab at step 0 and every remote slab expert-major at
    #     the final step, streaming weights exactly TWICE.  The d_world
    #     factor was this model's headline finding and motivated the
    #     batched schedule.  The row-windowed schedule (ISSUE 12)
    #     makes the same 2-pass guarantee WITHOUT holding anything
    #     weights-once in VMEM:
    #     window-major / row-minor order streams each K-window once per
    #     pass (own slab at step 0, batched remotes at the final step),
    #     so its weight column matches batched — the d x n_row_tiles
    #     collapse that rescues mixtral-width experts from the 40x
    #     stream column.
    fused_streams = {
        "batched": 2 if d_world > 1 else 1,
        "resident": d_world,
        "rowwin": 2 if d_world > 1 else 1,
        "stream": d_world * g["n_row_tiles"],
    }[g["schedule"]]
    gate_bytes = s * h * dt + h * e * dt
    flops = layer_flops(cfg, tokens=s)

    if path == "xla":
        # dense dispatch builds [E, C, H] with a gather, the einsum FFN
        # streams weights once (read buf + write y), the combine gathers
        # k rows per token.  XLA may additionally materialize the
        # [slots, i] hidden when fusion fails — NOT charged, keeping the
        # baseline's modeled bytes a lower bound so beating it
        # analytically means beating its best case.
        dispatch = s * h * dt + slots * h * dt        # read x, write buf
        ffn = slots * h * dt + slots * h * dt         # read buf, write y
        combine = rows * h * dt + s * h * 4
        return PathCost(path, w_once, gate_bytes + ffn, dispatch,
                        0.0, combine, combine, flops)
    if path == "explicit":
        dispatch = s * h * dt + slots * h * dt
        combine = rows * h * dt + s * h * 4
        # both a2a legs move full capacity slabs (ep._ep_moe_shard) —
        # at the layer's UNPADDED capacity: the XLA transport exchanges
        # the [E, C, H] buffer as-is; only the fused kernel RDMAs
        # 32-padded slabs.  This term used to charge the padded
        # capacity, overpricing e.g. deepseek's C=60 exchange by 64/60
        # — caught by the collective census
        # (flashmoe_tpu/staticcheck/census.py) reconciling this model
        # against the planner's slab_bytes and the lowered graph.
        comm = 2 * (d_world * nlx * g["cap_raw"]) * a2a_row
        return PathCost(path, w_once,
                        gate_bytes + slots * h * dt + slots * h * dt,
                        dispatch, comm, combine, combine, flops)
    if path == "gather":
        # no dispatch buffer: the kernel's per-row DMAs read exactly the
        # routed rows (ops/expert.py:grouped_ffn_tokens)
        combine = rows * h * dt + s * h * 4
        return PathCost(path, w_once,
                        gate_bytes + rows * h * dt + rows * h * dt,
                        0.0, 0.0, combine, combine, flops)
    if path == "ragged":
        # dropless ragged EP (parallel/ragged_ep.py): tokens sort into
        # expert-contiguous rows with NO capacity padding — under the
        # uniform-routing expectation exactly the s*k routed rows move
        # (a skewed batch moves more; this prices the expectation, the
        # same stance the capacity paths take on padding).  Build the
        # sorted send rows, FFN reads/writes them, combine gathers k
        # rows per token.
        dispatch = s * h * dt + rows * h * dt
        combine = rows * h * dt + s * h * 4
        # both ragged a2a legs move exactly the routed rows
        comm = 2 * rows * a2a_row
        return PathCost(path, w_once,
                        gate_bytes + rows * h * dt + rows * h * dt,
                        dispatch, comm, combine, combine, flops)
    if path in ("fused", "fused_combine"):
        # dispatch builds x_send; phase-1 RDMAs read x_send and write
        # x_recv on the peers (slots bytes each side); the FFN streams
        # x_recv once (two-pass schedules: n_i_chunks times) + weights;
        # stage to y_stage and return-RDMA to the source (read + write)
        dispatch = s * h * dt + slots * h * dt
        comm = 2 * slots * h * dt                     # x out + x in
        x_refactor = (g["n_i_chunks"] if g["schedule"] != "stream" else 1)
        act_bytes = (gate_bytes + slots * h * dt * x_refactor
                     + slots * h * dt)                # x_recv reads + y_stage
        if g["schedule"] == "rowwin":
            # the honest price of window-major row-windowing: every
            # resident row round-trips its f32 partial sum through the
            # HBM accumulator at each INTERIOR window boundary (the
            # first window starts from zero, the last folds straight
            # into y_stage) — 4 B read + 4 B write per element per
            # boundary.  The model must charge this term before the
            # 2x weight column can be believed.
            act_bytes += (g["n_i_chunks"] - 1) * slots * h * 8.0
        if path == "fused_combine":
            # sorted per-row returns carry only the rows actually routed
            # (dispatch.sorted_return_maps): rows*h out + rows*h in — the
            # slab path below returns full capacity-padded slabs, which
            # overstated this path's comm at capacity_factor > 1
            comm += 2 * rows * h * dt                 # y back out + in
        else:
            comm += 2 * slots * h * dt                # y back out + in
        if path == "fused":
            combine = slots * h * dt + s * h * 4      # XLA reads y_recv
            post = combine
        else:
            # drain combine reads the sorted rows + writes out f32 —
            # inside the kernel, off the post-kernel critical path
            combine = rows * h * dt + (rows * 4) + s * h * 4
            post = 0.0
        # only the rowwin streamer fetches the quantized store
        # in-kernel; the weights-once schedules boundary-dequantize
        # (parallel/fused.py:_fused_shard) and stream compute-width
        # weights, so their column must not claim the int8 discount
        w_stream = expert_weight_stream_bytes(
            cfg, nlx, quantized=(g["schedule"] == "rowwin"))
        return PathCost(path, w_stream * fused_streams, act_bytes,
                        dispatch, comm, combine, post, flops)
    raise ValueError(f"unknown path {path!r}")


def a2a_transport_cost(d: int, inner: int, slab_bytes: float,
                       gen: str = "v5e", links: int = 1,
                       chunks: int = 1,
                       dcn_slab_bytes: float | None = None) -> dict:
    """Model the flat vs two-stage (ICI+DCN) all-to-all on a ``d``-rank
    ep axis spanning ``d // inner`` slices, per rank per direction
    (``parallel/ep.py:_hierarchical_a2a``; the reference's per-peer
    P2P-vs-IBGDA transport split, ``bootstrap.cuh:442-446`` /
    ``os/packet.cuh:221-258``).

    ``slab_bytes`` is one (dest-rank) slab.  Flat: one message per peer
    — ``d - inner`` of them cross DCN.  Hierarchical: stage 1 reorders
    within the slice over ICI ((inner-1) messages of outer slabs), stage
    2 sends ONE aggregated message per remote slice ((outer-1) messages
    of inner slabs) — identical cross-slice bytes, ``inner``x fewer DCN
    messages, so the alpha term shrinks by (inner-1)(outer-1) DCN
    latencies at the price of (outer-1) extra in-slice slab transfers.

    ``links``: ICI links per chip striping each in-slice transfer (the
    beta term divides; per-message alpha and the host-NIC DCN path do
    not) — pass the mesh's link count so single-slice and multi-slice
    predictions stay comparable (planner code-review finding).

    ``chunks``: the chunked-pipeline depth (``MoEConfig.a2a_chunks``) —
    each per-peer slab splits into ``chunks`` messages of
    ``slab_bytes / chunks``, so the beta (serialization) terms are
    unchanged while every per-message alpha multiplies by ``chunks``.
    This is the chunking overhead the planner's overlap-adjusted
    makespan (:mod:`flashmoe_tpu.planner.model`) charges against the
    pipeline's hiding: more chunks hide more compute but pay more
    message latencies — the IO-aware tradeoff SonicMoE's tile knob
    makes (arXiv 2512.14080).

    ``dcn_slab_bytes``: the per-dest slab at the CROSS-SLICE hop's own
    wire row size (``MoEConfig.wire_dtype_dcn`` via
    :func:`wire_row_bytes` ``hop='dcn'``; default None = ``slab_bytes``
    — the inherit case).  Only the hierarchical DCN stage re-encodes,
    so only its serialization term uses it; the flat exchange carries
    the leg wire across DCN unchanged — which is exactly the modeled
    gap an fp8 DCN hop opens over flat (docs/PERF.md "Multi-slice
    scale-out").
    """
    from flashmoe_tpu.parallel.topology import _DCN_SPEC, ici_spec

    if inner < 1 or d % inner:
        raise ValueError(
            f"ep axis d={d} is not divisible into slices of inner={inner} "
            f"ranks; the two-stage decomposition needs d % inner == 0")
    if chunks < 1:
        raise ValueError(f"chunks={chunks} must be >= 1")
    a_ici, bw_ici = ici_spec(gen)
    a_dcn, bw_dcn = _DCN_SPEC
    a_ici, a_dcn = a_ici / 1e3, a_dcn / 1e3              # ms
    a_ici, a_dcn = a_ici * chunks, a_dcn * chunks        # n msgs/peer
    bw_ici = bw_ici * 1e6 * max(links, 1)                # B/ms, striped
    bw_dcn = bw_dcn * 1e6                                # B/ms
    outer = d // inner
    dcn_slab = slab_bytes if dcn_slab_bytes is None else dcn_slab_bytes
    flat = {
        "dcn_messages": (d - inner) * chunks,
        "dcn_ms": (d - inner) * (a_dcn + slab_bytes / bw_dcn),
        "ici_ms": (inner - 1) * (a_ici + slab_bytes / bw_ici),
    }
    hier = {
        "dcn_messages": (outer - 1) * chunks,
        "dcn_ms": (outer - 1) * (a_dcn + inner * dcn_slab / bw_dcn),
        "ici_ms": (inner - 1) * (a_ici + outer * slab_bytes / bw_ici),
    }
    for c in (flat, hier):
        c["total_ms"] = c["dcn_ms"] + c["ici_ms"]
    return {"flat": flat, "hierarchical": hier}


def comm_census(cfg: MoEConfig, d: int, path: str) -> dict:
    """Expected *lowered-graph* collective census of one XLA-transport
    MoE layer at ``(cfg, d ranks)`` — the statically-checkable
    counterpart of :func:`path_costs`'s HBM comm model, consumed by
    :mod:`flashmoe_tpu.staticcheck.census` which reconciles it against
    the jaxpr the layer actually traces to.

    Two model sources are deliberately combined and cross-checked
    against each other here: per-leg wire bytes come from the planner's
    slab accounting (``planner.model.slab_bytes``, the quantity the
    ici/dcn terms serialize) while the total is asserted against this
    module's :func:`path_costs` ``comm_bytes`` (the read+write HBM
    convention: exactly 2x the one-sided wire bytes).  A change that
    moves one model but not the other — the class of drift that
    once under-charged the fused_combine table 4x — fails here before
    any graph is even traced.

    Paths: ``collective`` (flat a2a), ``hierarchical`` (two-stage
    exchange — each stage moves the full local buffer, so the graph
    carries 2x the flat leg bytes: the documented staging cost of
    aggregating DCN messages), ``ragged`` (dense fallback arm — the CPU
    trace pads every transfer to the worst-case bound, so graph bytes
    are exactly ``d x chunks`` times the uniform-routing expectation
    ``path_costs`` prices; the TPU ``ragged_all_to_all`` arm moves the
    data-dependent exact rows instead).

    Returns per-rank expectations::

        legs          {dispatch: bytes, combine: bytes}  wire payload
                      + fp8 scale sidecar per leg, as traced
        a2a_eqns      all_to_all count (payload + sidecar + metadata)
        gather_eqns   all_gather count (ragged count-matrix machinery)
        meta_bytes    metadata collective bytes per primitive
                      (counts/sizes, not token rows)
        psum_eqns     loss/count reductions (EXPECTED_PSUMS contract)
        bound_factor  graph-bytes / model-expectation per leg (1 for
                      the capacity paths; d x chunks for ragged-dense)
        model_comm_bytes   path_costs(...).comm_bytes, for reference
    """
    from flashmoe_tpu.ops import wire as wr
    from flashmoe_tpu.parallel.ep import EXPECTED_PSUMS
    from flashmoe_tpu.planner.model import slab_bytes

    if path not in ("collective", "hierarchical", "ragged"):
        raise ValueError(
            f"comm_census covers the XLA transports only, not {path!r} "
            f"(the fused RDMA kernel is a custom call the jaxpr census "
            f"cannot see into; its traffic is modeled in path_costs)")
    chunks = cfg.a2a_chunks or 1
    stages = 2 if path == "hierarchical" else 1
    wires = {"dispatch": wr.resolve(cfg.wire_dtype),
             "combine": wr.resolve(cfg.wire_dtype_combine)}
    cost = path_costs(cfg, "ragged" if path == "ragged" else "explicit",
                      d_world=d)

    legs: dict[str, float] = {}
    a2a = 0
    if path == "ragged":
        n_assign = (cfg.tokens // d) * cfg.expert_top_k
        bound_factor = float(d * chunks)
        for leg, wd in wires.items():
            legs[leg] = bound_factor * n_assign * (
                wr.payload_row_bytes(wd, cfg.hidden_size, cfg.dtype)
                + wr.scale_bytes(wd))
            a2a += chunks * (1 + (1 if wr.is_fp8(wd) else 0))
        nlx = cfg.num_experts // d
        if chunks > 1:
            # one all_gather of the [dest, nLx] count matrix
            # (ragged_ep._chunked_ragged_exchange) derives every chunk's
            # offsets; no metadata a2a
            gather_eqns, meta_a2a = 1, 0
            meta_bytes = {"all_gather": float(d * nlx * 4),
                          "all_to_all": 0.0}
        else:
            # serial: all_gather of the [D] send sizes + one
            # count-matrix a2a (ragged_ep._ragged_ep_shard)
            gather_eqns, meta_a2a = 1, 1
            meta_bytes = {"all_gather": float(d * 4),
                          "all_to_all": float(d * nlx * 4)}
        a2a += meta_a2a
    else:
        bound_factor = 1.0
        gather_eqns = 0
        meta_bytes = {"all_gather": 0.0, "all_to_all": 0.0}
        if path == "hierarchical":
            # per-hop staging (ISSUE 13): the inner (ICI) stage moves
            # the leg-wire buffer, the outer (DCN) stage the DCN-wire
            # buffer (wire_dtype_dcn; equal when it inherits — the
            # codec's single-encode path, where this reduces exactly to
            # the old stages x flat formula)
            wd_dcn = wr.resolve(cfg.wire_dtype_dcn)
            for leg, wd in wires.items():
                legs[leg] = d * (slab_bytes(cfg, d, leg=leg, hop="ici")
                                 + slab_bytes(cfg, d, leg=leg,
                                              hop="dcn"))
                hop_dcn = wd_dcn if wd_dcn is not None else wd
                a2a += chunks * ((1 + (1 if wr.is_fp8(wd) else 0))
                                 + (1 + (1 if wr.is_fp8(hop_dcn)
                                         else 0)))
        else:
            # flat transports carry the leg wire end to end; the DCN
            # override has no hop to re-encode and must price as off
            for leg, wd in wires.items():
                legs[leg] = d * slab_bytes(cfg, d, leg=leg)
                a2a += chunks * (1 + (1 if wr.is_fp8(wd) else 0))

    # cross-check the two model sources against each other: the graph
    # legs must equal the HBM model's one-sided bytes times the
    # documented structural multipliers.  The hierarchical per-hop
    # variant derives each hop's side from path_costs independently —
    # the ICI hop from the config as-is, the DCN hop from the config
    # with the resolved DCN wire as its leg wire — so planner slabs and
    # the HBM model still cross-check per hop.
    if path == "hierarchical":
        cfg_dcn = (cfg.replace(wire_dtype=cfg.wire_dtype_dcn,
                               wire_dtype_combine=cfg.wire_dtype_dcn,
                               wire_dtype_dcn=None)
                   if cfg.wire_dtype_dcn is not None else cfg)
        cost_dcn = path_costs(cfg_dcn, "explicit", d_world=d)
        want = (cost.comm_bytes + cost_dcn.comm_bytes) / 2.0
    else:
        want = cost.comm_bytes / 2.0 * stages * bound_factor
    got = sum(legs.values())
    if abs(got - want) > 1e-6 * max(want, 1.0):
        raise AssertionError(
            f"analysis/planner byte models disagree for {path!r} at "
            f"d={d}: planner slabs give {got:.1f} B of graph wire "
            f"bytes, path_costs.comm_bytes implies {want:.1f} B — one "
            f"model moved without the other")
    return {
        "path": path, "chunks": chunks, "stages": stages, "legs": legs,
        "a2a_eqns": a2a, "gather_eqns": gather_eqns,
        "meta_bytes": meta_bytes, "psum_eqns": EXPECTED_PSUMS,
        "bound_factor": bound_factor,
        "model_comm_bytes": cost.comm_bytes,
    }


def chunked_pipeline_ms(chip_ms: float, dispatch_leg_ms: float,
                        combine_leg_ms: float, chunks: int) -> float:
    """Makespan of the chunked double-buffered EP schedule
    (``MoEConfig.a2a_chunks``) on the XLA transports — the
    overlap-adjusted cost the planner uses in place of the serial
    ``chip + dispatch + combine`` sum.

    ``dispatch_leg_ms`` / ``combine_leg_ms`` are the FULL chunked leg
    times (alpha already multiplied by ``chunks`` —
    :func:`a2a_transport_cost`); each chunk's share is ``leg / chunks``.
    Two-resource pipeline bound over ``chunks`` independent
    a2a -> FFN -> a2a chains:

      * compute-bound: the MXU runs continuously once chunk 0's
        dispatch lands, and the last chunk's combine trails it —
        ``chip + (dispatch + combine) / n``;
      * wire-bound: the wire runs continuously except for chunk 0's
        FFN fill — ``dispatch + combine + chip / n``.

    ``chunks=1`` reduces exactly to the serial makespan, so one formula
    prices both schedules."""
    if chunks < 1:
        raise ValueError(f"chunks={chunks} must be >= 1")
    e_total = dispatch_leg_ms + combine_leg_ms
    return max(chip_ms + e_total / chunks, e_total + chip_ms / chunks)


def candidate_table(cfg: MoEConfig, d_world: int = 1) -> str:
    """Markdown table of every path's modeled bytes at ``cfg``."""
    paths = ["xla", "explicit", "gather", "ragged", "fused",
             "fused_combine"]
    lines = [
        f"| path | weights MB | acts MB | dispatch MB | comm MB | "
        f"combine MB | total MB | post-kernel MB |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for p in paths:
        c = path_costs(cfg, p, d_world=d_world)
        mb = lambda b: f"{b / 2**20:.1f}"
        lines.append(
            f"| {p} | {mb(c.weight_bytes)} | {mb(c.activation_bytes)} | "
            f"{mb(c.dispatch_bytes)} | {mb(c.comm_bytes)} | "
            f"{mb(c.combine_bytes)} | {mb(c.total_bytes)} | "
            f"{mb(c.post_kernel_bytes)} |")
    return "\n".join(lines)


def main():
    import argparse

    from flashmoe_tpu.config import BENCH_CONFIGS

    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="reference",
                    choices=sorted(BENCH_CONFIGS.keys()))
    ap.add_argument("--d-world", type=int, default=1)
    args = ap.parse_args()
    cfg = BENCH_CONFIGS[args.config]
    print(f"# {args.config}: E={cfg.num_experts} k={cfg.expert_top_k} "
          f"H={cfg.hidden_size} I={cfg.intermediate_size} S={cfg.tokens} "
          f"d_world={args.d_world}")
    print(candidate_table(cfg, d_world=args.d_world))


if __name__ == "__main__":
    main()
