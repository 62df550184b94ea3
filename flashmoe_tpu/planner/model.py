"""Predicted end-to-end latency per execution path — the roofline
synthesis layer.

Rounds 1-5 built the ingredients separately: per-path HBM bytes and
FLOPs (:mod:`flashmoe_tpu.analysis`), the fused kernel's schedule-aware
overlap bound (:mod:`flashmoe_tpu.parallel.overlap`), per-generation
link/peak tables (:mod:`flashmoe_tpu.parallel.topology`), and the
ICI+DCN two-stage transport model (``analysis.a2a_transport_cost``).
The round-5 verdict's highest-leverage gap: nowhere did the framework
combine its bytes and its overlap bound into a predicted per-path
latency and state which path should win.  This module is that
combination — one number per candidate path, decomposed into the terms
that produce it, so the prediction is arguable line by line.

Latency model (per chip, one MoE-layer forward, ``d`` expert-parallel
ranks, uniform routing):

  compute_ms   ``PathCost.flops`` at the generation's peak matmul
               throughput x ``mxu_fraction`` (1.0 = roofline; pass a
               measured ``mxu_util`` for a calibrated prediction).
               f32 runs at half the bf16 peak.
  hbm_ms       ``PathCost.total_bytes`` at the generation's HBM
               bandwidth — the analysis module's per-path accounting,
               consumed verbatim so the planner can never drift from
               the CI-gated byte model.
  chip_ms      max(compute_ms, hbm_ms): the on-chip roofline (MXU and
               HBM pipelines overlap within a kernel).
  ici_ms       wire serialization of the expert all-to-all on this
               rank's ICI links, both directions, alpha included.  Each
               leg serializes at its own wire-dtype row size
               (``MoEConfig.wire_dtype`` / ``wire_dtype_combine``,
               priced via ``analysis.wire_row_bytes``), so fp8/bf16
               payload compression shrinks this term — and disqualifies
               the fused RDMA rows, whose transport moves raw slabs.
  dcn_ms       cross-slice share of that exchange when the ep axis
               spans slices (``a2a_transport_cost``: flat per-peer
               messages for the collective path, one aggregated message
               per slice pair for the hierarchical path).
  serial_ms    chip_ms + ici_ms + dcn_ms — the no-overlap makespan.
  total_ms     the overlap-adjusted prediction:
               * collective / ragged / hierarchical, serial schedule
                 (``a2a_chunks`` off): = serial_ms.  The dispatch
                 exchange must land before the FFN and the return
                 exchange starts after it, so within one layer XLA
                 cannot hide either leg (its latency-hiding scheduler
                 overlaps across surrounding ops, which this per-layer
                 model conservatively ignores);
               * same paths with ``MoEConfig.a2a_chunks = n``: the
                 chunked-pipeline makespan
                 (``analysis.chunked_pipeline_ms``) — chunk k's FFN
                 hides chunk k+1's exchange on both legs, at the price
                 of n per-peer message alphas per leg
                 (``a2a_transport_cost(chunks=n)``);
               * fused[schedule]: the kernel's arrival overlap, the
                 same makespan shapes as ``overlap.overlap_bound`` with
                 chip_ms in place of pure compute —
                 per-source (resident/stream):
                   T = max(chip, t_x + chip/d) + t_x/(d-1)
                 arrival-batched:
                   T = max(chip/d, t_x) + (d-1)/d * chip + t_x/nLx
                 row-windowed (rowwin): the batched makespan with the
                 finer per-row-tile return tail
                   T = max(chip/d, t_x) + (d-1)/d * chip
                       + t_x/(nLx * n_row_tiles)
                 where t_x is the one-direction egress serialization.

Every path the framework can execute is a row; rows the configuration
cannot run (VMEM-infeasible schedule, fused across DCN, gather kernel
in training) are kept but marked infeasible with the reason, so the
explain-table shows WHY a path is out, not just that it is.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from flashmoe_tpu.analysis import (
    PathCost, a2a_transport_cost, chunked_pipeline_ms, path_costs,
)
from flashmoe_tpu.config import MoEConfig

# planner path name -> the moe_backend string that runs it
BACKEND_OF = {
    "collective": "collective",
    "hierarchical": "collective",   # same layer, two-stage dcn_inner a2a
    "ragged": "ragged",
    "fused[batched]": "fused",
    "fused[resident]": "fused",
    "fused[stream]": "fused",
    "fused[rowwin]": "fused",
    "fused_combine": "fused",
    # single-chip paths (d == 1): ops/moe.py dispatch, not an ep backend
    "xla": "local",
    "explicit": "local",
    "gather": "local",
}


@dataclasses.dataclass(frozen=True)
class PathPrediction:
    """One explain-table row: the predicted latency decomposition of a
    single candidate path."""

    path: str
    backend: str
    schedule: str | None       # fused rows: the FFN schedule priced
    compute_ms: float
    hbm_ms: float
    ici_ms: float
    dcn_ms: float
    serial_ms: float           # no-overlap makespan
    total_ms: float            # overlap-adjusted prediction
    feasible: bool
    note: str                  # why infeasible / which overlap model
    cost: PathCost             # the byte decomposition priced
    wire: str = "off/off"      # wire dtypes priced (dispatch/combine
                               # legs, canonical names; "off/off" = raw)
    a2a_chunks: int = 1        # chunked-pipeline depth priced (XLA
                               # transports; 1 = serial schedule; the
                               # fused rows always carry 1 — their
                               # in-kernel transport ignores the knob)
    dp_allreduce_ms: float = 0.0  # DP gradient-ring share included in
                               # serial_ms/total_ms (0 unless the
                               # caller priced a dp axis; same value on
                               # every row of one prediction set)
    quant: str = "off"         # expert-weight store priced
                               # (MoEConfig.expert_quant canonical
                               # name; "off" = full-precision weights)

    @property
    def family(self) -> str:
        """Path name without the schedule qualifier ('fused[batched]'
        -> 'fused') — the granularity measurements are recorded at."""
        return self.path.split("[")[0]


def _dtype_peak(gen: str, cfg: MoEConfig) -> tuple[float, float]:
    """(peak FLOP/s at cfg.dtype, HBM B/s) — ValueError on unknown gen."""
    from flashmoe_tpu.parallel.topology import chip_spec

    peak_tf, hbm_gb = chip_spec(gen)
    if jnp.dtype(cfg.dtype).itemsize >= 4:  # staticcheck: ok static config dtype — host metadata, never a tracer
        peak_tf /= 2.0              # f32 runs the MXU at half rate
    return peak_tf * 1e12, hbm_gb * 1e9


def _ici_link(gen: str) -> tuple[float, float]:
    """(alpha_ms, one-way B/ms per link)."""
    from flashmoe_tpu.parallel.topology import ici_spec

    lat_us, gbps = ici_spec(gen)
    return lat_us / 1e3, gbps * 1e6


def a2a_leg_ms(slab: float, kind: str, *, d: int, gen: str,
               slices: int = 1, links: int = 4,
               chunks: int = 1,
               dcn_slab: float | None = None) -> tuple[float, float]:
    """(ici_ms, dcn_ms) of ONE exchange leg moving a ``slab`` of bytes
    at its wire row size, per-message alpha multiplied by the chunk
    count (``analysis.a2a_transport_cost``).  Public because it is THE
    per-leg pricing formula: ``predict_paths`` prices every XLA row
    through it and the profiler's cost ledger
    (:func:`flashmoe_tpu.profiler.ledger.predicted_phase_ms`) prices
    each measured a2a phase through the same call, so planner and
    ledger can never price the same bytes differently.  ``kind``
    selects the ``a2a_transport_cost`` row when the exchange spans
    slices (> 1); single-slice legs use the closed flat form.
    ``dcn_slab``: the slab at the CROSS-SLICE hop's own wire row size
    (``MoEConfig.wire_dtype_dcn``; None = inherit ``slab``) — only the
    hierarchical DCN stage re-encodes, so only that row's dcn term
    moves."""
    a_ici, bw_link = _ici_link(gen)
    if slices > 1:
        t = a2a_transport_cost(d, d // slices, slab, gen=gen,
                               links=links, chunks=chunks,
                               dcn_slab_bytes=dcn_slab)[kind]
        return t["ici_ms"], t["dcn_ms"]
    return (d - 1) * (chunks * a_ici + slab / (bw_link * links)), 0.0


def slab_bytes(cfg: MoEConfig, d: int, *, padded: bool = False,
               leg: str = "dispatch", hop: str = "ici") -> float:
    """One (dest-rank) capacity slab: the unit both exchanges move.
    Public because the collective census
    (:mod:`flashmoe_tpu.staticcheck.census` via ``analysis.comm_census``)
    reconciles the lowered graph's all_to_all operand bytes against
    exactly ``d x slab_bytes`` per exchange leg — the planner's pricing
    unit is statically checked against what the layer actually sends.

    ``padded``: the fused kernel RDMAs capacity padded to a 32-multiple
    (the same padding ``analysis._geom`` prices); the collective layer
    exchanges the unpadded ``[E, C, H]`` buffer (``ep._ep_moe_shard``).
    ``leg`` selects which exchange is priced: rows serialize at that
    leg's WIRE row size (``analysis.wire_row_bytes`` — compute row size
    when ``wire_dtype`` is off), so compression shrinks the ici/dcn
    terms by the wire/compute itemsize ratio.  ``hop`` ('ici'/'dcn')
    selects the stage of a two-stage multi-slice exchange: 'dcn'
    prices at the ``wire_dtype_dcn`` override when set."""
    from flashmoe_tpu.analysis import wire_row_bytes
    from flashmoe_tpu.parallel.ep import local_capacity

    s_loc = cfg.tokens // d
    cap = local_capacity(cfg, s_loc)
    nlx = cfg.num_experts // d
    if padded:
        # fused kernel slabs: raw compute rows, 32-padded — the RDMA
        # transport never compresses (config.py rejects fused + wire)
        cap = -(-cap // 32) * 32
        return nlx * cap * cfg.hidden_size * jnp.dtype(cfg.dtype).itemsize
    return nlx * cap * wire_row_bytes(cfg, leg, hop)


def dp_allreduce_ms(cfg: MoEConfig, dp: int, gen: str, *,
                    over_dcn: bool = False, links: int = 4) -> float:
    """Per-step gradient-allreduce time of the DP axis, priced from the
    Decider's ring model (:func:`flashmoe_tpu.parallel.decider.
    ring_allreduce_ms`, the reference's ``ARArgs`` pricing): ``2(G-1)``
    chunks of ``grad / G`` over the bottleneck hop — the host DCN NIC
    when the DP groups live on different slices (``over_dcn=True``),
    the chip's striped ICI links otherwise.  0 for inference jobs or
    ``dp <= 1``.

    This is the term that lets the planner trade EP-across-DCN against
    DP-across-DCN (``select.scaleout_plan``): packing the ep axis
    inside a slice frees the a2a from DCN but pushes the gradient ring
    across it — whichever axis moves fewer bytes per step should own
    the slow hop."""
    if dp <= 1 or not cfg.is_training:
        return 0.0
    from flashmoe_tpu.parallel.decider import ring_allreduce_ms
    from flashmoe_tpu.parallel.topology import _DCN_SPEC, ici_spec

    grad_mb = (cfg.param_count
               * jnp.dtype(cfg.param_dtype).itemsize) / 1e6
    if over_dcn:
        lat_us, gbps = _DCN_SPEC
        beta = 1e3 / (gbps * 1e3)                       # ms per MB
    else:
        lat_us, gbps = ici_spec(gen)
        beta = 1e3 / (gbps * 1e3 * max(links, 1))
    return ring_allreduce_ms(grad_mb, dp, beta, lat_us / 1e3)


def kv_page_mb(cfg: MoEConfig, page_size: int, *, wire=None) -> float:
    """MB one KV page pair (K + V, all layers) weighs on the handoff
    wire: ``2 x L x N_kv x page x D`` elements at the wire's row
    itemsize, plus the per-(layer, page) f32 ``_qscale`` sidecars the
    fp8 wires add (one per K row and one per V row — the fabric codec
    quantizes each (layer, page) block as ONE wire row)."""
    from flashmoe_tpu.ops import wire as wr

    wire_dt = wr.resolve(wire) if isinstance(wire, str) else wire
    nkv, dh = cfg.resolved_num_kv_heads, cfg.resolved_head_dim
    row = nkv * int(page_size) * dh
    per_layer = 2 * (wr.payload_row_bytes(wire_dt, row, cfg.dtype)
                     + wr.scale_bytes(wire_dt))
    return cfg.num_layers * per_layer / 1e6


def kv_handoff_ms(cfg: MoEConfig, pages: int, page_size: int, *,
                  wire=None) -> float:
    """Modeled DCN time to stream one finished prefill's ``pages`` KV
    pages from the prefill pool to a decode replica: one message (the
    run ships as a unit) over the host NIC —
    ``_DCN_SPEC`` alpha + bytes / DCN bandwidth, the same spec that
    prices ``dp_allreduce_ms``'s DCN arm and the cross-slice a2a hop.
    The fabric records this per handoff (``fabric.handoff``) and the
    golden ``fabric`` dimension gates it against the decode-step
    objective it must hide under."""
    from flashmoe_tpu.parallel.topology import _DCN_SPEC

    lat_us, gbps = _DCN_SPEC
    mb = max(int(pages), 0) * kv_page_mb(cfg, page_size, wire=wire)
    return lat_us / 1e3 + (mb / 1e3) / gbps * 1e3


#: Default per-step decode token count priced when ``mode='decode'``
#: and no explicit decode batch is given.  Decode steps move the decode
#: BATCH through the layer (each token then fans out ``top_k`` exchange
#: rows) — not B x S like training — so this is the token count every
#: decode-mode term is priced at.
DECODE_TOKENS_DEFAULT = 64


def decode_shape(cfg: MoEConfig, d: int = 1,
                 decode_tokens: int | None = None,
                 verify_tokens: int | None = None) -> MoEConfig:
    """The per-STEP problem a decode engine actually runs: ``tokens`` =
    the decode batch (``decode_tokens``, rounded up so the ranks
    divide it), inference mode.  This is the config the planner prices
    when ``mode='decode'`` — per-step tokens = batch x ``top_k``
    exchange rows, the regime where per-message alphas dominate the
    tiny slabs and the training-shaped schedule sweeps pick wrong
    (RaMP, arXiv 2604.26039; the reference's inference-mode Decider
    specialization, ``decider.cuh:177-268``).

    ``verify_tokens`` (ISSUE 20): drafted tokens ``k`` a speculative
    verify step scores on top of the canonical token — every slot
    feeds a ``k + 1`` position span, so the step moves
    ``decode_tokens x (k + 1)`` token rows through the layer.  The
    decode-vs-verify cost RATIO at this shape is the whole economics
    of speculation: at wire/HBM-bound decode shapes it sits near 1."""
    toks = int(decode_tokens if decode_tokens else DECODE_TOKENS_DEFAULT)
    if toks < 1:
        raise ValueError(f"decode_tokens={decode_tokens!r} must be >= 1")
    if verify_tokens is not None and int(verify_tokens) < 0:
        raise ValueError(
            f"verify_tokens={verify_tokens!r} must be >= 0")
    d = max(int(d), 1)
    toks = -(-toks // d) * d          # ranks must divide the step batch
    toks *= 1 + int(verify_tokens or 0)
    return cfg.replace(sequence_len=toks, mini_batch=1,
                       is_training=False)


def predict_paths(cfg: MoEConfig, d: int = 1, gen: str = "v5e", *,
                  slices: int = 1, links: int = 4,
                  mxu_fraction: float = 1.0, mode: str = "training",
                  decode_tokens: int | None = None,
                  verify_tokens: int | None = None,
                  dp: int = 1, dp_over_dcn: bool = False
                  ) -> list[PathPrediction]:
    """Predict every candidate path's latency at (cfg, d ranks, gen).

    ``slices``: how many DCN-connected slices the ep axis spans (1 =
    single slice); ``links``: ICI links per chip serving the exchange;
    ``mxu_fraction``: achieved fraction of peak matmul throughput.
    Rows are returned fastest-first among feasible, infeasible last.

    ``dp`` / ``dp_over_dcn``: price the DP axis's per-step gradient
    allreduce (:func:`dp_allreduce_ms`, training only) into every row —
    a constant across paths, so it never flips a path winner, but it
    makes predictions comparable ACROSS slice mappings: EP spanning the
    slices (``slices>1, dp_over_dcn=False``) vs EP packed per slice
    with the DP ring riding DCN (``slices=1, dp_over_dcn=True``) — the
    trade ``select.scaleout_plan`` makes.

    ``mode``: the pricing regime — ``'training'`` (default) prices the
    config's own B x S step; ``'decode'`` re-shapes it first
    (:func:`decode_shape`: per-step tokens = ``decode_tokens``, the
    decode batch — times ``verify_tokens + 1`` when a speculative
    verify span is priced); ``'prefill'`` keeps the full-sequence
    shape but prices inference-mode feasibility (the gather kernel
    qualifies).
    """
    if mode not in ("training", "prefill", "decode"):
        raise ValueError(
            f"mode {mode!r} not in ('training', 'prefill', 'decode')")
    if verify_tokens and mode != "decode":
        raise ValueError("verify_tokens prices the speculative verify "
                         "span — decode mode only")
    if mode == "decode":
        cfg = decode_shape(cfg, d, decode_tokens, verify_tokens)
    elif mode == "prefill" and cfg.is_training:
        cfg = cfg.replace(is_training=False)
    peak_fs, hbm_bs = _dtype_peak(gen, cfg)   # validates gen first
    if d < 1:
        raise ValueError(f"d={d} must be >= 1")
    if d > 1 and cfg.num_experts % d:
        raise ValueError(f"E={cfg.num_experts} not divisible by d={d}")
    if d > 1 and cfg.tokens % d:
        raise ValueError(f"S={cfg.tokens} not divisible by d={d}")
    if slices < 1 or d % slices:
        raise ValueError(f"d={d} not divisible into {slices} slices")
    mxu_fraction = max(min(mxu_fraction, 1.0), 1e-6)
    a_ici, bw_link = _ici_link(gen)
    rows = []

    from flashmoe_tpu.ops import wire as wr

    wire_tag = (f"{wr.canonical_name(cfg.wire_dtype)}/"
                f"{wr.canonical_name(cfg.wire_dtype_combine)}")
    wire_dcn_tag = wr.canonical_name(cfg.wire_dtype_dcn)
    if wire_dcn_tag != "off":
        wire_tag += f"/dcn:{wire_dcn_tag}"
    wire_on = wire_tag != "off/off"
    from flashmoe_tpu.quant import core as qcore

    quant_tag = qcore.canonical_name(cfg.expert_quant)
    ar_ms = dp_allreduce_ms(cfg, dp, gen, over_dcn=dp_over_dcn,
                            links=links)
    n_chunks = cfg.a2a_chunks or 1
    if n_chunks > 1 and d > 1 and (cfg.num_experts // d) % n_chunks:
        raise ValueError(
            f"a2a_chunks={n_chunks} does not divide the local-expert "
            f"axis (num_experts={cfg.num_experts} // d={d} = "
            f"{cfg.num_experts // d})")

    def mk(path, cost, ici_ms, dcn_ms, total_ms=None, schedule=None,
           feasible=True, note="", wire="off/off", chunks=1):
        compute_ms = cost.flops / (peak_fs * mxu_fraction) * 1e3
        hbm_ms = cost.total_bytes / hbm_bs * 1e3
        chip_ms = max(compute_ms, hbm_ms)
        # the DP gradient ring serializes after the step's MoE work on
        # every path alike (ar_ms = 0 unless a dp axis was priced)
        serial_ms = chip_ms + ici_ms + dcn_ms + ar_ms
        rows.append(PathPrediction(
            path=path, backend=BACKEND_OF[path], schedule=schedule,
            compute_ms=compute_ms, hbm_ms=hbm_ms, ici_ms=ici_ms,
            dcn_ms=dcn_ms, serial_ms=serial_ms,
            total_ms=serial_ms if total_ms is None else total_ms + ar_ms,
            feasible=feasible, note=note, cost=cost, wire=wire,
            a2a_chunks=chunks, dp_allreduce_ms=ar_ms,
            quant=quant_tag))
        return rows[-1]

    if d == 1:
        for p in ("xla", "explicit", "gather"):
            infeas = p == "gather" and cfg.is_training
            mk(p, path_costs(cfg, p, d_world=1), 0.0, 0.0,
               feasible=not infeas,
               note="inference-only kernel" if infeas else "on-chip roofline")
        rows.sort(key=lambda r: (not r.feasible, r.total_ms))
        return rows

    from flashmoe_tpu.parallel.fused import schedule_table

    def one_leg(slab, dcn_slab=None, *, kind):
        return a2a_leg_ms(slab, kind, d=d, gen=gen, slices=slices,
                          links=links, chunks=n_chunks,
                          dcn_slab=dcn_slab)

    def xla_row(path, cost, slab_by_leg, kind, note):
        """One XLA-transport row: legs priced separately (each at its
        own wire row size and chunked alpha), summed for the ici/dcn
        report; with a2a_chunks > 1 the overlap-adjusted total is the
        chunked-pipeline makespan (``analysis.chunked_pipeline_ms``)
        instead of the serial sum — chunk k's FFN hides chunk k+1's
        exchange on both legs.  ``slab_by_leg`` entries are either a
        slab or a (slab, dcn_slab) pair — the hierarchical row prices
        its DCN hop at the ``wire_dtype_dcn`` row size."""
        legs = [one_leg(*(slab if isinstance(slab, tuple) else (slab,)),
                        kind=kind) for slab in slab_by_leg]
        ici = sum(l[0] for l in legs)
        dcn = sum(l[1] for l in legs)
        total = None
        if n_chunks > 1:
            compute_ms = cost.flops / (peak_fs * mxu_fraction) * 1e3
            chip_ms = max(compute_ms, cost.total_bytes / hbm_bs * 1e3)
            total = chunked_pipeline_ms(chip_ms, sum(legs[0]),
                                        sum(legs[1]), n_chunks)
            note += f" [chunked a2a x{n_chunks} pipeline]"
        mk(path, cost, ici, dcn, total_ms=total, wire=wire_tag,
           note=note, chunks=n_chunks)

    slab_legs = [slab_bytes(cfg, d, leg="dispatch"),
                 slab_bytes(cfg, d, leg="combine")]
    wire_note = f" [wire {wire_tag}]" if wire_on else ""

    # --- collective EP: capacity slabs, flat all_to_all ---------------
    coll_note = ("capacity slabs" if n_chunks > 1 else
                 "serialized a2a (XLA cannot hide it within the layer)")
    xla_row("collective", path_costs(cfg, "explicit", d_world=d),
            slab_legs, "flat", coll_note + wire_note)

    # --- hierarchical two-stage ICI+DCN (multi-slice only) ------------
    if slices > 1:
        # the DCN hop serializes at its own wire row size when
        # wire_dtype_dcn is set (fp8 across DCN under a raw/bf16 ICI
        # hop); the ICI hop stays at the leg wire.  At inner=1 (one
        # rank per slice) the decomposition degenerates to the flat
        # exchange — the layer gates the two-stage path on
        # 1 < dcn_inner < d and never re-encodes there, so the row
        # must not price a discount the transport cannot deliver.
        dcn_applies = d // slices > 1 and wire_dcn_tag != "off"
        hier_legs = [(slab_bytes(cfg, d, leg=leg),
                      slab_bytes(cfg, d, leg=leg,
                                 hop="dcn" if dcn_applies else "ici"))
                     for leg in ("dispatch", "combine")]
        hier_note = "one aggregated DCN message per slice pair"
        if dcn_applies:
            hier_note += f" [dcn hop {wire_dcn_tag}]"
        elif wire_dcn_tag != "off":
            hier_note += " [dcn wire inert: one rank per slice]"
        xla_row("hierarchical", path_costs(cfg, "explicit", d_world=d),
                hier_legs, "hierarchical", hier_note + wire_note)

    # --- ragged / dropless EP: routed rows, no capacity padding -------
    from flashmoe_tpu.analysis import wire_row_bytes

    rag = path_costs(cfg, "ragged", d_world=d)
    rag_rows = (cfg.tokens // d) * cfg.expert_top_k / d
    xla_row("ragged", rag,
            [rag_rows * wire_row_bytes(cfg, "dispatch"),
             rag_rows * wire_row_bytes(cfg, "combine")], "flat",
            "uniform-routing expectation; skew moves more" + wire_note)

    # --- fused RDMA: one row per FFN schedule -------------------------
    meta = schedule_table(cfg, d)
    # rowwin geometry resolved ONCE (its tile search + tuning lookup is
    # the priciest resolution); reused by the fused[rowwin] row and a
    # rowwin-resolved fused_combine row alike
    nrt_rowwin = (meta if meta["priced"] == "rowwin"
                  else schedule_table(cfg, d,
                                      schedule="rowwin"))["n_row_tiles"]
    nlx = max(cfg.num_experts // d, 1)
    # the fused kernel RDMAs 32-padded slabs (analysis._geom pricing)
    pslab = slab_bytes(cfg, d, padded=True)
    t_x = (d - 1) * (a_ici + pslab / (bw_link * links))

    def fused_total(cost, sched):
        compute_ms = cost.flops / (peak_fs * mxu_fraction) * 1e3
        chip = max(compute_ms, cost.total_bytes / hbm_bs * 1e3)
        if sched == "batched":
            return (max(chip / d, t_x) + (d - 1) / d * chip + t_x / nlx)
        if sched == "rowwin":
            # batched-pass makespan; the last K-window returns row tiles
            # as it finishes them, so only the final tile's rows trail
            return (max(chip / d, t_x) + (d - 1) / d * chip
                    + t_x / max(nlx * nrt_rowwin, 1))
        return max(chip, t_x + chip / d) + t_x / max(d - 1, 1)

    def fused_why_out(sched=None):
        if wire_on:
            # the in-kernel RDMA moves raw slabs; config.py rejects the
            # combination outright, so the planner must never pick it
            return "wire-dtype compression is XLA-transport only"
        if slices > 1:
            return "fused RDMA is intra-slice only"
        if sched == "rowwin":
            # the one schedule whose VMEM footprint is capacity- and
            # width-independent: infeasibility means even the minimum
            # (row tile, K-window) pair cannot fit
            return ("rowwin infeasible: no (row tile, K-window) pair "
                    "fits the window double-buffer + accumulator "
                    "VMEM budget")
        if sched in ("batched", "resident"):
            return (f"{sched} infeasible: the weights-once hidden slab "
                    f"exceeds the VMEM budget (rowwin/stream remain)")
        return "VMEM budget exceeded"

    for sched in ("batched", "resident", "stream", "rowwin"):
        cost = path_costs(cfg, "fused", d_world=d, schedule=sched)
        ok = meta["feasible"][sched] and slices == 1 and not wire_on
        note = ("in-kernel arrival overlap" if ok
                else fused_why_out(sched))
        mk(f"fused[{sched}]", cost, 2 * t_x, 0.0,
           total_ms=fused_total(cost, sched), schedule=sched,
           feasible=ok, note=note)

    # --- fused + in-kernel combine at the resolved schedule -----------
    sched = meta["schedule"]
    cost = path_costs(cfg, "fused_combine", d_world=d)
    # the sorted-return combine has no quant arm: the layer forces the
    # XLA combine whenever expert_quant is on (parallel/fused.py), so
    # this row must be infeasible there — a selected plan the engine
    # silently downgrades is the modeled-vs-run divergence this PR
    # refuses everywhere else (code-review finding)
    base_ok = meta["feasible"][sched] and slices == 1 and not wire_on
    ok = base_ok and quant_tag == "off"
    if ok:
        fc_note = "sorted per-row returns; combine off the critical path"
    elif base_ok:
        fc_note = ("in-kernel combine has no quant arm; the layer runs "
                   "fused + XLA combine under expert_quant")
    else:
        fc_note = fused_why_out(sched)
    mk("fused_combine", cost, 2 * t_x, 0.0,
       total_ms=fused_total(cost, sched), schedule=sched, feasible=ok,
       note=fc_note)

    rows.sort(key=lambda r: (not r.feasible, r.total_ms))
    return rows


# ----------------------------------------------------------------------
# Speculative-decode economics (ISSUE 20)
# ----------------------------------------------------------------------

def speculate_tokens_per_step(accept_rate: float, k: int) -> float:
    """Expected tokens emitted per verify step when ``k`` drafts ride
    the span and each draft position accepts independently with
    probability ``accept_rate`` (the prefix-acceptance model): the
    canonical token always lands, plus the geometric accepted prefix —
    ``(1 - p^(k+1)) / (1 - p)``, saturating at ``k + 1`` when p = 1."""
    p = min(max(float(accept_rate), 0.0), 1.0)
    k = int(k)
    if k < 0:
        raise ValueError(f"k={k} must be >= 0")
    if p >= 1.0:
        return float(k + 1)
    return (1.0 - p ** (k + 1)) / (1.0 - p)


def _best_decode_ms(cfg: MoEConfig, d: int, gen: str, *,
                    decode_tokens: int | None,
                    verify_tokens: int | None) -> float:
    rows = predict_paths(cfg, d, gen, mode="decode",
                         decode_tokens=decode_tokens,
                         verify_tokens=verify_tokens)
    best = next((r for r in rows if r.feasible), rows[0])
    return best.total_ms


def speculate_uplift(cfg: MoEConfig, d: int = 1, gen: str = "v5e", *,
                     decode_tokens: int | None = None,
                     verify_tokens: int = 3,
                     accept_rate: float = 0.7) -> dict:
    """Modeled tokens/step uplift of draft-then-verify at
    ``accept_rate``: expected emitted tokens per step times the
    one-token/verify-span cost ratio —
    ``E[n](p) x t1 / tk``.  The reference kernel's wire/HBM-bound
    decode step makes ``tk / t1`` sit near 1 at decode shapes (the
    weights stream past once either way), which is why speculation
    pays at all; the golden ``speculate`` dimension freezes this."""
    k = int(verify_tokens)
    if k < 1:
        raise ValueError(f"verify_tokens={verify_tokens} must be >= 1")
    t1 = _best_decode_ms(cfg, d, gen, decode_tokens=decode_tokens,
                         verify_tokens=None)
    tk = _best_decode_ms(cfg, d, gen, decode_tokens=decode_tokens,
                         verify_tokens=k)
    e_n = speculate_tokens_per_step(accept_rate, k)
    cost_ratio = tk / t1 if t1 > 0 else float("inf")
    return {
        "verify_tokens": k,
        "accept_rate": float(accept_rate),
        "t1_ms": t1,
        "tk_ms": tk,
        "cost_ratio": cost_ratio,
        "tokens_per_step": e_n,
        "uplift": e_n / cost_ratio if cost_ratio else float("inf"),
    }


def speculate_break_even(cfg: MoEConfig, d: int = 1, gen: str = "v5e",
                         *, decode_tokens: int | None = None,
                         verify_tokens: int = 3) -> float:
    """The acceptance rate at which speculation exactly pays for its
    verify span: solves ``E[n](p) = tk / t1`` for p by bisection
    (E[n] is strictly increasing in p).  Below this the controller's
    spec-morph trigger switches speculation off
    (``controller.spec_morph``); returns 1.0 when even perfect
    acceptance cannot pay (cost ratio > k + 1) and 0.0 when the span
    is literally free (ratio <= 1)."""
    k = int(verify_tokens)
    if k < 1:
        raise ValueError(f"verify_tokens={verify_tokens} must be >= 1")
    t1 = _best_decode_ms(cfg, d, gen, decode_tokens=decode_tokens,
                         verify_tokens=None)
    tk = _best_decode_ms(cfg, d, gen, decode_tokens=decode_tokens,
                         verify_tokens=k)
    ratio = tk / t1 if t1 > 0 else float("inf")
    if ratio <= 1.0:
        return 0.0
    if ratio >= k + 1:
        return 1.0
    lo, hi = 0.0, 1.0
    for _ in range(60):
        mid = (lo + hi) / 2
        if speculate_tokens_per_step(mid, k) < ratio:
            lo = mid
        else:
            hi = mid
    return (lo + hi) / 2


def explain_table(preds: list[PathPrediction], *, markdown: bool = True
                  ) -> str:
    """Render predictions as the explain-table the CLI and docs show."""
    hdr = ("| path | compute ms | HBM ms | ICI ms | DCN ms | serial ms "
           "| predicted ms | note |")
    lines = [hdr, "|---|---|---|---|---|---|---|---|"]
    for p in preds:
        star = "" if p.feasible else " (infeasible)"
        lines.append(
            f"| {p.path}{star} | {p.compute_ms:.3f} | {p.hbm_ms:.3f} | "
            f"{p.ici_ms:.3f} | {p.dcn_ms:.3f} | {p.serial_ms:.3f} | "
            f"{p.total_ms:.3f} | {p.note} |")
    return "\n".join(lines)
