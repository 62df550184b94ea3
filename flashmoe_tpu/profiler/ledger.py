"""Predicted-vs-actual cost ledger: join the measured phase timeline
against the analytical planner, phase by phase.

PR 2's drift monitor compares END-TO-END latency against the planner —
it can say "this layer is 2x the prediction" but not which term of the
cost model is lying.  This module closes that gap: the profiler's
phase timeline (:mod:`flashmoe_tpu.profiler.spans`) measures gate /
dispatch-a2a / expert-FFN / combine-a2a individually, and the ledger
prices each phase with the same ingredients the planner's
:func:`~flashmoe_tpu.planner.model.predict_paths` uses (roofline
compute+HBM for the on-chip phases, per-leg wire serialization for the
exchanges), emitting one ``planner.phase_drift`` decision per phase.
An a2a leg drifting alone points at the transport model or a sick
link; the expert phase drifting alone points at the roofline's
mxu_fraction — per-phase drift supersedes end-to-end drift as the
tuning-override signal (docs/PLANNER.md).

The ledger also cross-checks the chunked-overlap story: the fenced
timeline's serialized phase sum over the same computation's *jitted*
(overlap-scheduled) step time is a measured overlap fraction, judged
against ``overlap.chunked_overlap_bound`` through the existing
``planner.overlap_drift`` monitor — the only way to *verify* the
Comet-style pipeline is hiding communication rather than just being
modeled to.

``run_ledger_matrix`` drives the acceptance matrix — flat /
hierarchical / ragged x {serial, chunked} x {wire off, e4m3} — on the
virtual CPU mesh, writing ``ledger.jsonl`` +
``trace.json`` artifacts that ``python -m flashmoe_tpu.observe
--ledger`` summarizes.
"""

from __future__ import annotations

import os
import time

from flashmoe_tpu.config import MoEConfig
from flashmoe_tpu.profiler.spans import PhaseTimeline

#: the four phases of the reference kernel's thesis — the ledger's join
#: keys (scatter/gather phases ``moe.dispatch``/``moe.combine`` are
#: measured too but priced inside the on-chip roofline terms)
PHASES = ("moe.gate", "moe.a2a_dispatch", "moe.expert",
          "moe.a2a_combine")


def predicted_phase_ms(cfg: MoEConfig, d: int = 1, gen: str = "v5e", *,
                       path: str = "collective", slices: int = 1,
                       links: int = 4,
                       mxu_fraction: float = 1.0) -> dict[str, float]:
    """Per-phase predicted latency (ms) at (cfg, d ranks, gen) — the
    planner's cost decomposition re-cut along the profiler's phase
    boundaries, from the same primitives (``topology`` peaks,
    ``planner.model.slab_bytes``, ``analysis.wire_row_bytes``, and the
    per-leg formula ``planner.model.a2a_leg_ms``) so ledger and
    planner can never price the same bytes differently."""
    import jax.numpy as jnp

    from flashmoe_tpu.analysis import wire_row_bytes
    from flashmoe_tpu.planner.model import (
        _dtype_peak, a2a_leg_ms, slab_bytes,
    )

    peak_fs, hbm_bs = _dtype_peak(gen, cfg)
    peak_fs *= max(min(mxu_fraction, 1.0), 1e-6)
    d = max(d, 1)
    s_loc = max(cfg.tokens // d, 1)
    h, i_dim, e = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts
    dt = jnp.dtype(cfg.dtype).itemsize
    n = cfg.a2a_chunks or 1

    # gate: router logits GEMM on local tokens (+ x and gate_w reads)
    gate_fl = 2.0 * s_loc * h * e
    gate_by = s_loc * h * dt + h * e * 4
    out = {"moe.gate": max(gate_fl / peak_fs, gate_by / hbm_bs) * 1e3}

    # expert FFN: routed rows this rank computes under uniform routing
    rows = s_loc * cfg.expert_top_k
    gemms = 3 if cfg.gated_ffn else 2
    ffn_fl = gemms * 2.0 * rows * h * i_dim
    nlx = max(e // d, 1)
    w_by = gemms * nlx * h * i_dim * dt        # local weights, once
    act_by = (2 * h + i_dim) * rows * dt       # rows in/out + hidden
    out["moe.expert"] = max(ffn_fl / peak_fs,
                            (w_by + act_by) / hbm_bs) * 1e3

    if d > 1:
        def leg(which: str) -> float:
            # the DCN-wire override only applies where the layer runs
            # the two-stage exchange (1 < inner < d, ep.py transport —
            # the same guard predict_paths uses: never price a discount
            # the transport cannot deliver).  The ragged transport is
            # flat-only (no per-hop codec), so it never re-encodes.
            hop = ("dcn" if path != "ragged"
                   and d // max(slices, 1) > 1 else "ici")
            if path == "ragged":
                slab = rows / d * wire_row_bytes(cfg, which)
                dcn_slab = rows / d * wire_row_bytes(cfg, which, hop)
            else:
                slab = slab_bytes(cfg, d, leg=which)
                dcn_slab = slab_bytes(cfg, d, leg=which, hop=hop)
            # THE per-leg formula (planner.model.a2a_leg_ms): ledger
            # and planner can never price the same bytes differently
            # (the dcn slab rides the wire_dtype_dcn row size when the
            # cross-slice hop re-encodes)
            ici, dcn = a2a_leg_ms(slab, "hierarchical", d=d, gen=gen,
                                  slices=slices, links=links, chunks=n,
                                  dcn_slab=dcn_slab)
            return ici + dcn

        out["moe.a2a_dispatch"] = leg("dispatch")
        out["moe.a2a_combine"] = leg("combine")
    return out


def profile_moe_phases(cfg: MoEConfig, mesh, *, path: str = "collective",
                       steps: int = 1, dcn_inner: int | None = None,
                       seed: int = 0, overlapped: bool = True,
                       recorder=None, label: str = "") -> PhaseTimeline:
    """Measure the phase timeline of one MoE layer point.

    Runs the layer EAGERLY (no jit) with ``profile_phases=True`` and a
    timeline armed: eager shard_map dispatches per primitive with
    concrete per-device values, so the in-body fences
    (:func:`flashmoe_tpu.profiler.spans.fence`) genuinely block and
    every trace_span's duration is device-complete wall time.  Stats
    collection is forced on so the imbalance counter track has data.

    ``overlapped=True`` additionally times the SAME computation jitted
    (XLA's latency-hiding schedule) and stores the median per-step ms
    on ``timeline.overlapped_ms`` — the denominator of the ledger's
    measured overlap fraction.  ``recorder``: a FlightRecorder to
    land per-step phase records in (the flight-ring integration)."""
    import jax

    from flashmoe_tpu.models.reference import init_moe_params
    from flashmoe_tpu.profiler import spans

    pcfg = cfg.replace(profile_phases=True, collect_stats=True)
    key = jax.random.PRNGKey(seed)
    params = init_moe_params(key, pcfg)
    params = jax.tree_util.tree_map(
        lambda p: p.astype(pcfg.dtype)
        if hasattr(p, "astype") else p, params)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (pcfg.tokens, pcfg.hidden_size), pcfg.dtype)

    if path == "ragged":
        from flashmoe_tpu.parallel.ragged_ep import ragged_ep_moe_layer

        def run(p, xx, c):
            return ragged_ep_moe_layer(p, xx, c, mesh)
    else:
        from flashmoe_tpu.parallel.ep import ep_moe_layer

        def run(p, xx, c):
            return ep_moe_layer(p, xx, c, mesh,
                                dcn_inner=(dcn_inner or 0))

    tl = PhaseTimeline(label=label or f"{path} d={mesh.shape['ep']}")
    tl.meta = {
        "path": path, "d": int(mesh.shape["ep"]),
        "chunks": cfg.a2a_chunks or 1, "dcn_inner": dcn_inner,
        "wire": cfg.wire_dtype or "off",
        "wire_combine": cfg.wire_dtype_combine or "off",
    }
    with spans.profiling(tl):
        for i in range(max(steps, 1)):
            tl.begin_step(i)
            out = run(params, x, pcfg)
            jax.block_until_ready(out.out)
            tl.end_step()
            if out.stats is not None:
                tl.counter("moe.load_imbalance",
                           float(out.stats.imbalance), step=i)
            if recorder is not None:
                recorder.record(**tl.step_records()[-1], **tl.meta)
                tl.counter("flight.queue_depth", len(recorder), step=i)
    if overlapped:
        # the jitted (overlap-scheduled) step: profile_phases stays on
        # — the knob is graph-neutral, so this times the IDENTICAL
        # graph the planner prices, with XLA free to overlap
        jf = jax.jit(lambda p, xx: run(p, xx, pcfg).out)
        jax.block_until_ready(jf(params, x))  # compile + warm
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(jf(params, x))
            times.append(time.perf_counter() - t0)
        tl.overlapped_ms = sorted(times)[len(times) // 2] * 1e3
    return tl


def phase_ledger(tl: PhaseTimeline, cfg: MoEConfig, *, d: int, gen: str,
                 path: str, slices: int = 1, links: int = 4,
                 mxu_fraction: float = 1.0, warn: bool = False
                 ) -> tuple[list[dict], dict | None]:
    """Join a measured timeline against the per-phase predictions.

    Returns ``(rows, overlap)``: one row per joined phase (each also
    recorded as a ``planner.phase_drift`` decision), and — when the
    timeline carries an overlapped (jitted) step time at d > 1 — the
    measured-vs-bound overlap fraction, recorded through the existing
    ``planner.overlap_drift`` monitor so the chunk picks' validation
    loop (PR 6) sees profiler data too."""
    from flashmoe_tpu.ops import wire as wr
    from flashmoe_tpu.planner.drift import (
        record_overlap_drift, record_phase_drift,
    )

    measured = tl.phase_means()
    pred = predicted_phase_ms(cfg, d, gen, path=path, slices=slices,
                              links=links, mxu_fraction=mxu_fraction)
    rows = []
    for ph in PHASES:
        if ph not in measured or ph not in pred:
            continue
        rec = record_phase_drift(cfg, path, ph, measured[ph],
                                 predicted_ms=pred[ph], d=d, gen=gen,
                                 warn=warn)
        rows.append({
            "phase": ph, "path": path, "gen": gen, "d": int(d),
            "chunks": rec.chunks, "wire": rec.wire,
            "measured_ms": round(measured[ph], 6),
            "predicted_ms": round(pred[ph], 6),
            "rel_error": round(rec.rel_error, 4),
            "exceeded": rec.exceeded,
        })

    overlap = None
    if tl.overlapped_ms and d > 1:
        from flashmoe_tpu.parallel.overlap import chunked_overlap_bound

        n = cfg.a2a_chunks or 1
        serial_ms = sum(measured.values())  # fenced = fully serialized
        frac = serial_ms / tl.overlapped_ms
        bound = chunked_overlap_bound(
            cfg, d, gen, n, links=links, mxu_fraction=mxu_fraction,
            path="ragged" if path == "ragged" else "collective",
        )["overlap_efficiency_bound"]
        odr = record_overlap_drift(path, frac,
                                   predicted_fraction=bound, gen=gen,
                                   d=d, chunks=n, warn=warn)
        overlap = {
            "path": path, "gen": gen, "d": int(d), "chunks": n,
            "wire": (f"{wr.canonical_name(cfg.wire_dtype)}/"
                     f"{wr.canonical_name(cfg.wire_dtype_combine)}"),
            "serial_phase_sum_ms": round(serial_ms, 6),
            "overlapped_ms": round(tl.overlapped_ms, 6),
            "measured_fraction": round(frac, 4),
            "predicted_fraction": round(bound, 4),
            "exceeded": odr.exceeded,
        }
    return rows, overlap


# ----------------------------------------------------------------------
# The acceptance matrix
# ----------------------------------------------------------------------

#: (name, ep width, dcn_inner, profiler path, planner slices)
MATRIX_PATHS = (
    ("flat", 2, None, "collective", 1),
    ("hierarchical", 4, 2, "collective", 2),
    ("ragged", 2, None, "ragged", 1),
)
MATRIX_CHUNKS = (None, 2)
MATRIX_WIRES = (None, "e4m3")


def ledger_config(ep: int) -> MoEConfig:
    """The matrix's measurement point: the invariant engine's
    small-config shape (drills every feature, costs kilobytes)."""
    import jax.numpy as jnp

    return MoEConfig(num_experts=8, expert_top_k=2, hidden_size=64,
                     intermediate_size=128, sequence_len=64 * ep,
                     drop_tokens=False, ep=ep, dtype=jnp.float32,
                     param_dtype=jnp.float32)


def run_ledger_matrix(obs_dir: str | None = None, *, quick: bool = False,
                      steps: int = 1, gen: str | None = None,
                      devices=None, overlapped: bool = True,
                      warn: bool = False) -> list[dict]:
    """Profile and ledger every matrix point; write artifacts.

    ``quick`` restricts to the first point (flat x serial x wire off) —
    the fast-lane CI smoke; the full matrix is slow-test / CLI
    material (eager per-primitive dispatch costs seconds per point on
    the virtual CPU mesh).  Artifacts into ``obs_dir``:
    ``ledger.jsonl`` (one line per joined phase + one ``overlap``
    line per point) and ``trace.json`` (all points merged, one
    Perfetto process per point).  Returns the per-point summary
    records."""
    import json

    import jax

    from flashmoe_tpu.ops import wire as wr
    from flashmoe_tpu.parallel.mesh import make_mesh
    from flashmoe_tpu.profiler.export import write_trace
    from flashmoe_tpu.utils.telemetry import FlightRecorder

    gen = gen or os.environ.get("FLASHMOE_TPU_GEN") or "v5e"
    devices = list(devices if devices is not None else jax.devices())
    records: list[dict] = []
    timelines: list[PhaseTimeline] = []
    labels: list[str] = []
    ledger_rows: list[dict] = []
    recorder = FlightRecorder()

    for pname, ep, dcn_inner, ppath, slices in MATRIX_PATHS:
        if len(devices) < ep:
            # no silent caps: a reduced matrix must be visible, or a
            # 2-chip run reads as "covered everything"
            import warnings

            warnings.warn(
                f"profile matrix: skipping the {pname!r} path — needs "
                f"{ep} devices, have {len(devices)}", RuntimeWarning,
                stacklevel=2)
            continue
        base = ledger_config(ep)
        mesh = make_mesh(base, dp=1, devices=devices[:ep])
        for chunks in MATRIX_CHUNKS:
            for wire in MATRIX_WIRES:
                cfg = base.replace(a2a_chunks=chunks, wire_dtype=wire)
                label = (f"{pname} chunks={chunks or 1} "
                         f"wire={wr.canonical_name(wire)}")
                tl = profile_moe_phases(
                    cfg, mesh, path=ppath, steps=steps,
                    dcn_inner=dcn_inner, overlapped=overlapped,
                    recorder=recorder, label=label)
                rows, overlap = phase_ledger(
                    tl, cfg, d=ep, gen=gen,
                    path=pname if pname == "hierarchical" else ppath,
                    slices=slices, warn=warn)
                # rows carry BOTH names: "path" is the planner's path
                # (the planner.phase_drift join key; "collective" IS
                # the flat transport) and "point" is the matrix point
                # the docs speak (flat/hierarchical/
                # ragged), so either vocabulary filters ledger.jsonl
                rows = [dict(r, point=pname) for r in rows]
                ledger_rows.extend(rows)
                if overlap is not None:
                    ledger_rows.append(dict(overlap, record="overlap",
                                            point=pname))
                timelines.append(tl)
                labels.append(label)
                records.append({
                    "metric": f"phase_ledger[{pname},"
                              f"chunks={chunks or 1},"
                              f"wire={wr.canonical_name(wire)}]",
                    "value": round(sum(r["measured_ms"]
                                       for r in rows), 3),
                    "unit": "ms", "path": pname, "gen": gen, "d": ep,
                    "a2a_chunks": chunks or 1,
                    "wire_dtype": wr.canonical_name(wire),
                    "step_ms": round(tl.step_wall_means() or 0.0, 3),
                    "overlapped_ms": (round(tl.overlapped_ms, 3)
                                      if tl.overlapped_ms else None),
                    "phases": {r["phase"]: r["measured_ms"]
                               for r in rows},
                    "phase_drift": {r["phase"]: r["rel_error"]
                                    for r in rows},
                    "overlap": overlap,
                })
                if quick:
                    break
            if quick:
                break
        if quick:
            break

    if obs_dir:
        os.makedirs(obs_dir, exist_ok=True)
        with open(os.path.join(obs_dir, "ledger.jsonl"), "w") as f:
            for row in ledger_rows:
                f.write(json.dumps(row) + "\n")
        write_trace(timelines, os.path.join(obs_dir, "trace.json"),
                    labels=labels)
        recorder.export_jsonl(os.path.join(obs_dir, "flight.jsonl"))
    return records
