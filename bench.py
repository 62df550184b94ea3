"""Benchmark: MoE-layer forward latency on the local chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

``vs_baseline`` is ``null`` (and the record carries a ``partial`` field,
with exit code 3) when the xla comparison leg never completed — partial
records are machine-distinguishable from genuine no-speedup results.

The headline config mirrors the reference's benchmark setting
(``csrc/flashmoe_config.json``: E=64, top-k=2, H=2048, I=2048, S=8192) run
through the fused Pallas path.  ``vs_baseline`` is the speedup of the fused
path over the naive XLA dense-dispatch implementation measured in the same
run on the same chip — the analogue of the reference's comparisons against
Megatron-style baselines (``README.md:27``).

Usage:
  python bench.py              # headline number (one JSON line)
  python bench.py --config token_scaling --trials 50
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time

import jax
import jax.numpy as jnp

from flashmoe_tpu.config import BENCH_CONFIGS, MoEConfig
from flashmoe_tpu.models.reference import init_moe_params
from flashmoe_tpu.ops.moe import moe_layer


def _chained(cfg: MoEConfig, use_pallas: bool, iters: int):
    """Jit `iters` dependent MoE-layer applications ending in a scalar
    readback.  The per-iteration time comes from differencing two chain
    lengths, which takes the dispatch and readback cost out of it (the
    benchmark PR, ROADMAP S1, replaces this with a host clock around
    ``block_until_ready``)."""

    def run(p, x):
        def body(x, _):
            o = moe_layer(p, x, cfg, use_pallas=use_pallas)
            return o.out.astype(x.dtype), None
        x, _ = jax.lax.scan(body, x, None, length=iters)
        return x.astype(jnp.float32).sum()

    return jax.jit(run)


def _time_chain(fn, p, x, trials):
    float(fn(p, x))  # compile + warm
    times = []
    for _ in range(trials):
        t0 = time.perf_counter()
        float(fn(p, x))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


# Progressive results: filled in as each path finishes so the deadline
# handler can emit a partial (but real) record instead of value: -1.
# Keyed by the measurement's own config/name so a sweep can never mix
# timings from different points into one record.
_PARTIAL: dict = {}


def bench_moe_layer(cfg: MoEConfig, trials: int, chain: int = 16,
                    name: str = ""):
    # clear before any slow work so a failure during setup can never
    # re-emit the previous sweep point's (already-printed) timings
    _PARTIAL.clear()
    _PARTIAL.update(cfg=cfg, name=name)
    key = jax.random.PRNGKey(0)
    params = init_moe_params(key, cfg)
    params = jax.tree_util.tree_map(lambda p: p.astype(cfg.dtype), params)
    x = jax.random.normal(
        jax.random.PRNGKey(1), (cfg.tokens, cfg.hidden_size), cfg.dtype
    )
    def per_iter(c, use_pallas):
        """Per-iteration time via two chain lengths (single definition —
        all legs must share the same differencing arithmetic)."""
        t1 = _time_chain(_chained(c, use_pallas, 1), params, x, trials)
        tn = _time_chain(_chained(c, use_pallas, chain), params, x, trials)
        return max(tn - t1, 1e-9) / (chain - 1)

    out = {}
    for pname, use_pallas in (("fused", True), ("xla", False)):
        out[pname] = per_iter(cfg, use_pallas)
        _PARTIAL[pname] = out[pname]
    return out["fused"], out["xla"]


def _layer_flops(cfg: MoEConfig) -> float:
    """Model FLOPs of one MoE layer forward: gate GEMM + routed expert
    FFN (2 or 3 GEMMs per token-slot)."""
    gate = 2.0 * cfg.tokens * cfg.hidden_size * cfg.num_experts
    rows = cfg.tokens * cfg.expert_top_k
    gemms = 3 if cfg.gated_ffn else 2
    ffn = gemms * 2.0 * rows * cfg.hidden_size * cfg.intermediate_size
    return gate + ffn


def _mxu_util(cfg: MoEConfig, seconds: float) -> float | None:
    """Achieved fraction of peak MXU throughput — the TPU analogue of the
    reference's headline SM-utilization metric (``README.md:43-44``,
    ``plots/sm_util.png``), computed from model FLOPs over wall time."""
    from flashmoe_tpu.parallel.topology import _PEAK_TFLOPS, tpu_generation

    peak = _PEAK_TFLOPS.get(tpu_generation(jax.devices()[0]))
    if peak is None or seconds <= 0:
        return None
    return _layer_flops(cfg) / seconds / (peak * 1e12)


def _planner_fields(cfg, t_fused, t_xla) -> dict:
    """Predicted-vs-measured fields for this record: the analytical
    planner's prediction of the measured path, the signed relative
    error, and the planner's predicted winner at this config — every
    bench run doubles as a calibration point for the cost model
    (``docs/PLANNER.md``).  Empty off known generations (the virtual
    CPU backend has no roofline to predict against; pin
    ``FLASHMOE_TPU_GEN`` to force one)."""
    from flashmoe_tpu.parallel.topology import _PEAK_TFLOPS, tpu_generation
    from flashmoe_tpu.planner.model import predict_paths

    gen = tpu_generation(jax.devices()[0])
    if gen not in _PEAK_TFLOPS:
        gen = os.environ.get("FLASHMOE_TPU_GEN", "")
        if gen not in _PEAK_TFLOPS:
            return {}
    preds = {p.path: p for p in predict_paths(cfg, 1, gen)}
    measured_path = "explicit"
    out = {"planner_gen": gen}
    winner = next((p for p in preds.values() if p.feasible), None)
    if winner is not None:
        out["predicted_winner"] = winner.path
    p = preds.get(measured_path)
    if p is not None:
        out["predicted_path"] = measured_path
        out["predicted_ms"] = round(p.total_ms, 3)
        out["prediction_error"] = round(
            t_fused * 1e3 / p.total_ms - 1.0, 3)
    px = preds.get("xla")
    if t_xla and px is not None:
        out["xla_predicted_ms"] = round(px.total_ms, 3)
        out["xla_prediction_error"] = round(
            t_xla * 1e3 / px.total_ms - 1.0, 3)
    return out


def _wire_fields(cfg: MoEConfig) -> dict:
    """Wire-dtype identity + modeled bytes saved for one bench record.

    ``wire_modeled_comm_mb`` is the byte model's EP-exchange traffic at
    this config's nominal ep width (0 at ep=1 — the single-chip headline
    has no a2a); ``wire_modeled_comm_saved_mb`` is the drop vs the same
    config with the wire off."""
    from flashmoe_tpu.analysis import path_costs
    from flashmoe_tpu.ops import wire as wr

    out = {"wire_dtype": wr.canonical_name(cfg.wire_dtype),
           "wire_dtype_combine": wr.canonical_name(cfg.wire_dtype_combine)}
    if cfg.wire_dtype is None and cfg.wire_dtype_combine is None:
        return out
    d = max(cfg.ep, 1)
    path = "ragged" if cfg.moe_backend == "ragged" else "explicit"
    comm = path_costs(cfg, path, d_world=d).comm_bytes
    raw = path_costs(
        cfg.replace(wire_dtype=None, wire_dtype_combine=None),
        path, d_world=d).comm_bytes
    out["wire_modeled_comm_mb"] = round(comm / 2**20, 3)
    out["wire_modeled_comm_saved_mb"] = round((raw - comm) / 2**20, 3)
    return out


def _quant_fields(cfg: MoEConfig) -> dict:
    """Quantized-expert-store identity + modeled weight bytes saved for
    one bench record.  ``quant_modeled_weight_mb`` is one full stream
    of this rank's expert weights at the store width (scale sidecars
    included); ``quant_modeled_weight_saved_mb`` the drop vs the same
    stream at full precision — the term the fused rowwin race and every
    HBM-bound path move by."""
    from flashmoe_tpu.analysis import expert_weight_stream_bytes
    from flashmoe_tpu.quant import core as qcore

    out = {"expert_quant": qcore.canonical_name(cfg.expert_quant)}
    if cfg.expert_quant is None:
        return out
    nlx = cfg.num_experts // max(cfg.ep, 1)
    on = expert_weight_stream_bytes(cfg, nlx)
    off = expert_weight_stream_bytes(
        cfg.replace(expert_quant=None), nlx)
    out["quant_modeled_weight_mb"] = round(on / 2**20, 3)
    out["quant_modeled_weight_saved_mb"] = round((off - on) / 2**20, 3)
    return out


def _emit(cfg, name, t_fused, t_xla, note: str | None = None):
    """One JSON record.  ``t_xla=None`` marks a partial measurement (the
    xla leg never completed): vs_baseline is ``null`` — not a number a
    driver could mistake for a genuine no-speedup result — and the record
    carries an explicit ``partial`` field (advisor round-3 #4)."""
    util = _mxu_util(cfg, t_fused)  # an unknown TPU kind raises, by name
    rec = {
        "metric": f"moe_layer_fwd_ms[{name}:E={cfg.num_experts},"
                  f"k={cfg.expert_top_k},H={cfg.hidden_size},"
                  f"I={cfg.intermediate_size},S={cfg.tokens},"
                  f"{jnp.dtype(cfg.dtype).name}]",
        "value": round(t_fused * 1e3, 3),
        "unit": "ms",
        "vs_baseline": round(t_xla / t_fused, 3) if t_xla else None,
        "tokens_per_sec_per_chip": round(cfg.tokens / t_fused),
        "xla_path_ms": round(t_xla * 1e3, 3) if t_xla else None,
        "mxu_util": round(util, 4) if util is not None else None,
        "backend": jax.default_backend(),
    }
    # main() times a preset whose ep exceeds the devices present at ep=1:
    # the record says so instead of passing for the preset
    preset = BENCH_CONFIGS.get(name.split("/")[0])
    if preset is not None and preset.ep > cfg.ep:
        rec["ep_folded_from"] = preset.ep
    # path/d identify this measurement for the planner's measured-winner
    # override (planner/select.py:_bench_record_latencies): the headline
    # bench times the single-chip (d=1) kernels.  a2a_chunks rides the
    # identity like the wire knobs: a chunk-pipelined timing never
    # overrides a serial selection (and vice versa)
    rec["path"] = "explicit"
    rec["d"] = 1
    rec["a2a_chunks"] = cfg.a2a_chunks or 1
    # wire-dtype knobs are part of the measurement identity (a
    # compressed timing never overrides an uncompressed selection), and
    # the modeled EP comm bytes at the config's nominal ep width show
    # what the wire saves — drift monitoring then covers the
    # compressed paths with their own keys
    try:
        rec.update(_wire_fields(cfg))
    except Exception as e:  # noqa: BLE001 — never lose the record
        rec["wire_error"] = f"{type(e).__name__}: {str(e)[:120]}"
    try:
        # quantized-store identity rides every record like the wire
        # knobs: an int8-weights timing never overrides a
        # full-precision selection (planner/select.py)
        rec.update(_quant_fields(cfg))
    except Exception as e:  # noqa: BLE001 — never lose the record
        rec["quant_error_field"] = f"{type(e).__name__}: {str(e)[:120]}"
    try:
        rec.update(_planner_fields(cfg, t_fused, t_xla))
    except Exception as e:  # noqa: BLE001 — never lose the record
        rec["planner_error"] = f"{type(e).__name__}: {str(e)[:120]}"
    # drift monitor: every bench measurement is a calibration point —
    # the planner.drift decision (and its warning past the threshold)
    # closes the predict -> measure -> correct loop (docs/OBSERVABILITY.md)
    if rec.get("predicted_ms"):
        try:
            from flashmoe_tpu.planner.drift import record_drift

            dr = record_drift(cfg, rec["path"], t_fused * 1e3,
                              d=rec["d"], gen=rec.get("planner_gen"),
                              predicted_ms=rec["predicted_ms"])
            rec["drift_exceeded"] = dr.exceeded
            if t_xla and rec.get("xla_predicted_ms"):
                record_drift(cfg, "xla", t_xla * 1e3, d=rec["d"],
                             gen=rec.get("planner_gen"),
                             predicted_ms=rec["xla_predicted_ms"],
                             warn=False)
        except Exception as e:  # noqa: BLE001 — never lose the record
            rec["drift_error"] = f"{type(e).__name__}: {str(e)[:120]}"
    if note:
        rec["partial"] = note
    print(json.dumps(rec), flush=True)
    _flush_observability(rec)
    # consumed: a late SIGALRM must not re-emit this record as "partial"
    _PARTIAL.clear()


# Observability artifact dir (--obs-dir / FLASHMOE_OBS_DIR): every
# emitted record appends to bench_records.jsonl and new telemetry
# decisions (planner.path_select, planner.drift) drain into
# decisions.jsonl — both are inputs `python -m flashmoe_tpu.observe`
# summarizes.  [dir, decisions-already-written] so sweep points never
# duplicate decisions.
_OBS: list = [None, 0]

# Perf-sentry collection (--regression): [history path or None, the
# run's emitted records].  Armed in main(); _finish_regression()
# appends ONE run entry to obs/history.jsonl when the mode completes —
# skipped/partial/error records never enter the baseline
# (telemetry_plane/regression.py filters them).
_REG: list = [None, []]


def _finish_regression():
    if not _REG[0] or not _REG[1]:
        return
    try:
        from flashmoe_tpu.telemetry_plane import regression as reg

        points = reg.collect_points(_REG[1])
        entry = reg.append_run(_REG[0], points,
                               meta={"argv": sys.argv[1:]})
        if entry:
            print(f"# perf sentry: appended {len(points)} metric "
                  f"point(s) to {_REG[0]}", file=sys.stderr, flush=True)
    except Exception as e:  # noqa: BLE001 — history is best-effort
        print(f"# regression history write failed: "
              f"{type(e).__name__}: {str(e)[:120]}",
              file=sys.stderr, flush=True)


def _flush_observability(rec: dict):
    if _REG[0] is not None:
        _REG[1].append(rec)
    if not _OBS[0]:
        return
    try:
        from flashmoe_tpu.utils.telemetry import metrics

        os.makedirs(_OBS[0], exist_ok=True)
        with open(os.path.join(_OBS[0], "bench_records.jsonl"), "a") as f:
            f.write(json.dumps(rec) + "\n")
        _OBS[1] = metrics.dump_decisions_jsonl(
            os.path.join(_OBS[0], "decisions.jsonl"), start=_OBS[1])
    except Exception as e:  # noqa: BLE001 — artifacts are best-effort
        print(f"# obs-dir write failed: {type(e).__name__}: "
              f"{str(e)[:120]}", file=sys.stderr, flush=True)


def _bench_checkpoint(trials: int):
    """Step-loop checkpoint overhead: blocking time of a sync save
    (serialize+fsync+rename on the loop) vs an async save (host snapshot
    only; the writer thread pays the rest).  One JSON record whose
    ``vs_baseline`` is the sync/async blocking-time ratio — the
    speedup the drain-safe async path buys the step loop
    (docs/RESILIENCE.md, preemption section)."""
    import shutil
    import tempfile

    from flashmoe_tpu.runtime import checkpoint as ckpt
    from flashmoe_tpu.runtime.trainer import TrainState

    state = TrainState(
        params={"w": jnp.zeros((512, 512), jnp.float32),
                "b": jnp.zeros((512,), jnp.float32)},
        opt_state={"m": jnp.zeros((512, 512), jnp.float32),
                   "v": jnp.zeros((512, 512), jnp.float32)},
        step=jnp.zeros((), jnp.int32))
    tmp = tempfile.mkdtemp(prefix="flashmoe_ckpt_bench_")
    sync_s, async_s = [], []
    try:
        d_sync = os.path.join(tmp, "sync")
        d_async = os.path.join(tmp, "async")
        # one throwaway save per directory: manager construction and
        # tracemetadata warmup must not be billed to either side
        ckpt.save(d_sync, state, step=0)
        ckpt.save(d_async, state, step=0)
        step = 0
        for _ in range(trials):
            step += 1
            t0 = time.perf_counter()
            ckpt.save(d_sync, state, step=step)
            sync_s.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            ckpt.save(d_async, state, step=step, blocking=False)
            async_s.append(time.perf_counter() - t0)
            ckpt.wait_for_saves()  # drain between points: measure the
            # enqueue cost, not queue-full newest-wins replacement
        errors = ckpt.wait_for_saves()
        sync_ms = sorted(sync_s)[len(sync_s) // 2] * 1e3
        async_ms = sorted(async_s)[len(async_s) // 2] * 1e3
        rec = {
            "metric": f"ckpt_step_block_ms[async,trials={trials}]",
            "value": round(async_ms, 3),
            "unit": "ms",
            "vs_baseline": round(sync_ms / async_ms, 3) if async_ms
            else None,
            "sync_block_ms": round(sync_ms, 3),
            "async_verified": all(
                ckpt.verify(d_async, s) for s in range(1, step + 1)
                if os.path.isdir(ckpt.step_dir(d_async, s))),
            "async_errors": len(errors),
            "backend": jax.default_backend(),
        }
        print(json.dumps(rec), flush=True)
        _flush_observability(rec)
    finally:
        ckpt.close_manager(os.path.join(tmp, "sync"))
        ckpt.close_manager(os.path.join(tmp, "async"))
        shutil.rmtree(tmp, ignore_errors=True)


def _bench_profile(obs_dir: str | None, *, steps: int = 1,
                   quick: bool = False):
    """Phase-level profile + cost ledger (``--profile``): run the
    flat/hierarchical/ragged x {serial, chunked} x {wire off, e4m3}
    matrix with ``profile_phases=True`` on the virtual CPU mesh (or real
    chips when FLASHMOE_OVERLAP_TPU=1), joining every measured phase
    against the planner's per-phase prediction.  One JSON record per
    matrix point; with ``--obs-dir`` the artifacts land there —
    ``ledger.jsonl`` + ``trace.json`` (open in ui.perfetto.dev) +
    ``flight.jsonl`` — and ``python -m flashmoe_tpu.observe --ledger``
    renders the drift table."""
    from flashmoe_tpu.profiler.ledger import run_ledger_matrix

    on_tpu = os.environ.get("FLASHMOE_OVERLAP_TPU") == "1"
    if not on_tpu:
        from __graft_entry__ import _force_cpu_devices
        _force_cpu_devices(8)
        devices = jax.devices("cpu")[:8]
    else:
        devices = jax.devices()
    records = run_ledger_matrix(obs_dir, quick=quick, steps=steps,
                                devices=devices)
    for rec in records:
        print(json.dumps(rec), flush=True)
        _flush_observability(rec)


def _bench_serve(loads, *, requests: int, max_batch: int,
                 telemetry_port: int | None = None,
                 speculate: int | None = None):
    """Offered-load serving sweep (``--serve``): the continuous-
    batching engine (flashmoe_tpu/serving/) driven by a seeded arrival
    trace at each offered-load point, one JSON record per point with
    throughput (tokens/sec), TTFT/TPOT percentiles, queue depth, cache
    occupancy, and evictions — the latency/throughput curve.  CPU-
    sized model; identical procedure on real chips.
    ``telemetry_port`` arms the live scrape plane for the sweep's
    duration; each record then carries a mid-sweep ``/metrics``
    self-scrape (``telemetry_scrape``).  ``speculate`` (``--serve
    --speculate K``) arms speculative decoding at ``draft_tokens=K``:
    each record gains a ``spec=kK`` identity tag, the realized
    ``accept_rate`` / ``spec_tokens_per_step``, an equal-SLO TPOT
    comparison against a per-point non-speculative baseline, and the
    asserted ``bit_equal_to_baseline`` exactness bit."""
    from flashmoe_tpu.serving.loadgen import serve_load_sweep

    for rec in serve_load_sweep(loads, n_requests=requests,
                                max_batch=max_batch,
                                telemetry_port=telemetry_port,
                                speculate=speculate):
        print(json.dumps(rec), flush=True)
        _flush_observability(rec)


def _bench_fabric(loads, *, requests: int, max_batch: int,
                  telemetry_port: int | None = None,
                  vclock: bool = False, wire: str = "inproc"):
    """Disaggregated-fabric offered-load sweep (``--fabric``): the
    :class:`~flashmoe_tpu.fabric.engine.ServingFabric` driven over
    mocked 1/2/4-replica worlds (``FLASHMOE_MOCK_FABRIC``, set per
    point and restored), one JSON record per (replica count, load
    point) with throughput, TTFT/TPOT percentiles, KV-handoff count and
    modeled DCN cost, and the router's placement histogram.  Host+CPU
    like ``--serve``; identical procedure on real multi-host serving.

    ``vclock`` (``--vclock``): step each point on the fabric's virtual
    clock behind the front door — TTFT/TPOT are measured UNDER the
    modeled DCN delay and each record adds the measured-vs-priced
    handoff fields plus the per-request attribution rollup
    (docs/OBSERVABILITY.md 'Virtual clock').

    ``wire`` (``--wire tcp``): every KV handoff crosses a real
    localhost socket; the record identity gains a ``wire=tcp`` tag so
    the sentry baselines socket and in-process throughput apart."""
    from flashmoe_tpu.serving.loadgen import fabric_load_sweep

    for rec in fabric_load_sweep(loads, n_requests=requests,
                                 max_batch=max_batch,
                                 telemetry_port=telemetry_port,
                                 vclock=vclock, wire=wire):
        print(json.dumps(rec), flush=True)
        _flush_observability(rec)


def _bench_fabric_faults():
    """Serving fault-tolerance sweep (``--fabric --faults``): every
    fault on the serving recovery ladder drilled end to end against a
    mocked 2-replica fabric, one JSON record per fault with recovery
    latency, migrated-request count, handoff retry/corrupt totals and
    the trace-contiguity verdict, plus one brownout record whose
    headline value is the shed fraction.  Host+CPU like ``--fabric``;
    identical drills on real multi-host serving."""
    from flashmoe_tpu.serving.loadgen import fabric_fault_sweep

    for rec in fabric_fault_sweep():
        print(json.dumps(rec), flush=True)
        _flush_observability(rec)


def _bench_overlap(ep: int, trials: int, *, path: str | None = None,
                   wire_dtype: str | None = None,
                   wire_combine: str | None = None,
                   a2a_chunks: int | None = None):
    """Overlap efficiency on an ep-way mesh (BASELINE.json metric 3),
    per chunk count: one record for the serial schedule and one per
    chunked-pipeline depth (``MoEConfig.a2a_chunks``), each reporting
    the measured efficiency next to its analytic bound
    (``overlap.chunked_overlap_bound`` for the chunked XLA schedules,
    ``overlap.overlap_bound`` for the fused kernel) with the
    predicted-vs-measured overlap fraction validated through the drift
    monitor (``planner.overlap_drift``).

    Multi-chip hardware is absent in this container, so the mesh is the
    virtual 8-device CPU backend (interpret-mode kernels) unless
    ``FLASHMOE_OVERLAP_TPU=1`` — the procedure is identical on real chips.
    See parallel/overlap.py for the metric definition.
    """
    import os

    from flashmoe_tpu.parallel.mesh import make_mesh
    from flashmoe_tpu.parallel.overlap import measure_overlap

    on_tpu = os.environ.get("FLASHMOE_OVERLAP_TPU") == "1"
    if not on_tpu:
        from __graft_entry__ import _force_cpu_devices
        _force_cpu_devices(ep)
        devices = jax.devices("cpu")[:ep]
    else:
        devices = jax.devices()[:ep]
    cfg = MoEConfig(
        num_experts=2 * ep, expert_top_k=2, hidden_size=256,
        intermediate_size=512, sequence_len=256 * ep, capacity_factor=1.0,
        drop_tokens=True, ep=ep,
        dtype=jnp.float32 if not on_tpu else jnp.bfloat16,
        wire_dtype=wire_dtype, wire_dtype_combine=wire_combine,
    )
    mesh = make_mesh(cfg, dp=1, devices=devices)
    # off-hardware, interpret-mode Pallas is ~100x slower than compiled XLA,
    # which would poison the ratio — the virtual mesh measures the collective
    # path (compiled end to end); real chips measure the fused kernel,
    # UNLESS wire/chunk knobs are set: those are XLA-transport features
    # (the fused kernel rejects wire dtypes and ignores a2a_chunks), so
    # the measurement they ask for is the collective schedule
    if path is None:
        path = "fused" if on_tpu else "collective"
        if path == "fused" and (wire_dtype or wire_combine or a2a_chunks):
            print("# wire/a2a-chunks knobs are XLA-transport only: "
                  "measuring the collective path instead of the fused "
                  "kernel", file=sys.stderr, flush=True)
            path = "collective"
    nlx = cfg.num_experts // ep
    if path == "fused":
        chunk_list = [1]  # the kernel overlaps in-kernel; no chunk knob
    elif a2a_chunks:
        chunk_list = sorted({1} | {n for n in (a2a_chunks,)
                                   if nlx % n == 0})
        if a2a_chunks > 1 and nlx % a2a_chunks:
            print(f"# a2a_chunks={a2a_chunks} does not divide "
                  f"nLx={nlx}; measuring serial only",
                  file=sys.stderr, flush=True)
    else:
        chunk_list = [1] + [n for n in (2, 4) if nlx % n == 0]

    from flashmoe_tpu.parallel.topology import tpu_generation

    gen = tpu_generation(devices[0])
    for n in chunk_list:
        m = measure_overlap(cfg, mesh, path=path, trials=trials,
                            interpret=False,
                            a2a_chunks=n if path != "fused" else None)
        rec = {
            "metric": f"overlap_efficiency[{path},ep={ep},"
                      f"E={cfg.num_experts},chunks={n},"
                      f"{'tpu' if on_tpu else 'virtual_cpu'}]",
            "value": round(m["overlap_efficiency"], 3),
            "unit": "ratio_vs_serialized",
            "vs_baseline": round(m["overlap_efficiency"], 3),
            "t_overlapped_ms": round(m["t_overlapped_ms"], 3),
            "t_compute_ms": round(m["t_compute_ms"], 3),
            "t_comm_ms": round(m["t_comm_ms"], 3),
            # what one pipeline stage occupies (the moe.a2a_dispatch.k /
            # moe.expert.k trace spans, averaged) — the observe phase
            # breakdown then shows per-chunk pipeline occupancy
            "per_chunk_a2a_ms": round(m["t_comm_ms"] / n, 3),
            "per_chunk_expert_ms": round(m["t_compute_ms"] / n, 3),
            "a2a_chunks": n,
            "path": path,
        }
        rec.update(_wire_fields(cfg))
        if n == 1:
            try:
                rec.update(_skew_metrics(cfg, ep, m))
            except Exception as e:  # noqa: BLE001 — stands alone
                rec["skew_error"] = f"{type(e).__name__}: {str(e)[:120]}"
        try:
            if gen in ("v4", "v5e", "v5p", "v6e"):
                if path == "fused":
                    from flashmoe_tpu.parallel.overlap import overlap_bound

                    b = overlap_bound(
                        cfg, ep, gen,
                        fuse_combine=os.environ.get(
                            "FLASHMOE_FUSED_COMBINE") == "1")
                    # the number this measurement is judged against
                    # (BASELINE.md round-5 note) — reported side by
                    # side, never in isolation; resolved for the FFN
                    # schedule that will actually run
                    rec["expected_bound"] = round(
                        b["overlap_efficiency_bound"], 3)
                    rec["expected_bound_schedule"] = b["schedule"]
                else:
                    from flashmoe_tpu.parallel.overlap import (
                        chunked_overlap_bound,
                    )

                    b = chunked_overlap_bound(cfg, ep, gen, n, path=path)
                    rec["expected_bound"] = round(
                        b["overlap_efficiency_bound"], 3)
                # measured-vs-analytic overlap fraction through the
                # drift monitor: the loop that tells us when the
                # pipeline model (and the chunk picks it drives) has
                # drifted from what the hardware delivers
                from flashmoe_tpu.planner.drift import record_overlap_drift

                dr = record_overlap_drift(
                    path, m["overlap_efficiency"],
                    predicted_fraction=rec["expected_bound"],
                    gen=gen, d=ep, chunks=n)
                rec["overlap_drift_exceeded"] = dr.exceeded
        except Exception as e:  # noqa: BLE001 — but record the breakage
            rec["bound_error"] = f"{type(e).__name__}: {str(e)[:120]}"
        print(json.dumps(rec), flush=True)
        _flush_observability(rec)


def _skew_metrics(cfg: MoEConfig, ep: int, m: dict) -> dict:
    """Ring-vs-predicted-order stall of the fused kernel's static slab
    schedule AT THIS BENCH'S CONFIG — the skew_sim discrete-event model
    (scripts/skew_sim.py) keyed to the measured per-slab compute time
    and this config's slab size, reported alongside the overlap number
    instead of living only in a standalone simulation (VERDICT r4 #6).
    Scenario: one source behind an 8x-slow link (the payload-skew case
    of BASELINE config #5)."""
    import sys as _sys

    # insert only if absent: an unconditional insert accumulated one
    # duplicate entry per overlap run and kept scripts/ ahead of every
    # other import root (module-shadowing risk; ADVICE round 5)
    _scripts = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts")
    if _scripts not in _sys.path:
        _sys.path.insert(0, _scripts)
    import skew_sim

    from flashmoe_tpu.parallel.ep import local_capacity

    nlx = cfg.num_experts // ep
    s_loc = max(cfg.tokens // ep, 1)
    slab_mb = (nlx * local_capacity(cfg, s_loc) * cfg.hidden_size
               * jnp.dtype(cfg.dtype).itemsize) / 1e6
    t_c = m["t_compute_ms"] / ep  # per-slab compute share
    adj = skew_sim.torus_adj(ep)
    adj.alpha[0, :] *= 8.0
    adj.beta[0, :] *= 8.0
    adj.alpha[0, 0] = adj.beta[0, 0] = 0.0
    r = skew_sim.simulate(adj, adj, slab_mb, t_c)
    return {
        "skew8_ring_stall_ms": round(r["ring"] - r["oracle"], 4),
        "skew8_pred_stall_ms": round(r["pred"] - r["oracle"], 4),
        "skew8_arrival_spread_ms": round(r["spread"], 4),
        "skew_slab_mb": round(slab_mb, 3),
    }


def _sweep_ep(trials: int, wire_dtype: str | None = None,
              wire_combine: str | None = None,
              a2a_chunks: int | None = None):
    """Weak-scaling sweep over the ep axis: per-rank tokens held constant
    while the mesh grows (the reference's ``scaling_gpus_8`` axis).
    Virtual CPU mesh when multi-chip hardware is absent; identical
    procedure on real chips (FLASHMOE_OVERLAP_TPU=1).  ``wire_dtype`` /
    ``wire_combine`` compress the EP exchange payload (ops/wire.py) and
    ``a2a_chunks`` runs the chunked double-buffered pipeline — the
    workloads those knobs exist for, so the sweep honors them."""
    import os

    from flashmoe_tpu.parallel.mesh import make_mesh
    from flashmoe_tpu.parallel.overlap import _time_chained
    from flashmoe_tpu.parallel.ep import ep_moe_layer
    from flashmoe_tpu.models.reference import init_moe_params

    on_tpu = os.environ.get("FLASHMOE_OVERLAP_TPU") == "1"
    if not on_tpu:
        from __graft_entry__ import _force_cpu_devices
        _force_cpu_devices(8)
        devs = jax.devices("cpu")
    else:
        devs = jax.devices()
    base_t = None
    for ep in (2, 4, 8):
        if len(devs) < ep:
            break
        chunks = (a2a_chunks if a2a_chunks and a2a_chunks > 1
                  and (16 // ep) % a2a_chunks == 0 else None)
        if a2a_chunks and chunks is None and a2a_chunks > 1:
            print(f"# ep={ep}: a2a_chunks={a2a_chunks} does not divide "
                  f"nLx={16 // ep}; measuring serial", file=sys.stderr,
                  flush=True)
        cfg = MoEConfig(
            num_experts=16, expert_top_k=2, hidden_size=256,
            intermediate_size=512, sequence_len=256 * ep,
            capacity_factor=1.0, drop_tokens=True, ep=ep,
            dtype=jnp.bfloat16 if on_tpu else jnp.float32,
            wire_dtype=wire_dtype, wire_dtype_combine=wire_combine,
            a2a_chunks=chunks,
        )
        mesh = make_mesh(cfg, dp=1, devices=devs[:ep])
        params = init_moe_params(jax.random.PRNGKey(0), cfg)
        params = jax.tree_util.tree_map(
            lambda p: p.astype(cfg.dtype), params)
        x = jax.random.normal(
            jax.random.PRNGKey(1), (cfg.tokens, cfg.hidden_size), cfg.dtype)
        fn = lambda c: ep_moe_layer(params, c, cfg, mesh,
                                    use_pallas=on_tpu).out
        t = _time_chained(fn, x, trials=trials, chain=8)
        base_t = base_t or t
        rec = {
            "metric": f"weak_scaling_ms[collective,ep={ep},"
                      f"tokens_per_rank=256,"
                      f"{'tpu' if on_tpu else 'virtual_cpu'}]",
            "value": round(t * 1e3, 3),
            "unit": "ms",
            "vs_baseline": round(base_t / t, 3),  # weak-scaling efficiency
            "a2a_chunks": cfg.a2a_chunks or 1,
        }
        rec.update(_wire_fields(cfg))
        print(json.dumps(rec), flush=True)


def _bench_scaling(trials: int, *, wire_dtype=None, wire_combine=None,
                   wire_dcn=None, a2a_chunks=None):
    """Weak-scaling sweep over mocked 1/2/4/8-slice meshes (ISSUE 13).

    The 8-rank mesh (virtual CPU, or real chips under
    FLASHMOE_OVERLAP_TPU=1) is partitioned into n "slices" per point
    via ``FLASHMOE_MOCK_SLICES`` — the same detection path a real
    multislice bootstrap runs (``topology.slice_structure``) — and the
    collective layer runs the two-stage hierarchical exchange at
    ``dcn_inner = 8 // n`` (flat at n=1, and at n=8 where one rank per
    slice degenerates to flat).  Per point one JSON record carries the
    measured per-step latency, the planner's slices=n prediction
    through the drift monitor (generation pinned by the backend or
    FLASHMOE_TPU_GEN; prediction fields absent otherwise, like the
    headline bench), the modeled per-hop wire bytes (ICI vs DCN row
    sizes — ``wire_dtype_dcn`` shrinks the dcn hop only) and DCN
    message counts (flat vs hierarchical aggregation), and the
    weak-scaling efficiency vs the 1-slice point."""
    from flashmoe_tpu.analysis import a2a_transport_cost
    from flashmoe_tpu.models.reference import init_moe_params
    from flashmoe_tpu.parallel.ep import ep_moe_layer
    from flashmoe_tpu.parallel.mesh import make_mesh
    from flashmoe_tpu.parallel.overlap import _time_chained
    from flashmoe_tpu.parallel.topology import (
        _PEAK_TFLOPS, slice_structure, tpu_generation,
    )
    from flashmoe_tpu.planner.model import predict_paths, slab_bytes

    on_tpu = os.environ.get("FLASHMOE_OVERLAP_TPU") == "1"
    if not on_tpu:
        from __graft_entry__ import _force_cpu_devices
        _force_cpu_devices(8)
        devs = jax.devices("cpu")[:8]
    else:
        devs = jax.devices()[:8]
    d = len(devs)
    gen = tpu_generation(devs[0])
    if gen not in _PEAK_TFLOPS:
        gen = os.environ.get("FLASHMOE_TPU_GEN", "")
    chunks = (a2a_chunks if a2a_chunks and a2a_chunks > 1
              and (16 // d) % a2a_chunks == 0 else None)
    if a2a_chunks and a2a_chunks > 1 and chunks is None:
        # the _sweep_ep convention: a dropped knob is announced, never
        # silently measured serial
        print(f"# --scaling: a2a_chunks={a2a_chunks} does not divide "
              f"nLx={16 // d}; measuring serial", file=sys.stderr,
              flush=True)
    base_t = None
    saved_mock = os.environ.get("FLASHMOE_MOCK_SLICES")
    try:
        for n_slices in (1, 2, 4, 8):
            if d % n_slices:
                continue
            os.environ["FLASHMOE_MOCK_SLICES"] = str(n_slices)
            ss = slice_structure(devs)
            inner = ss[1] if ss else d
            hier = 1 < inner < d
            cfg = MoEConfig(
                num_experts=16, expert_top_k=2, hidden_size=256,
                intermediate_size=512, sequence_len=256 * d,
                capacity_factor=1.0, drop_tokens=True, ep=d,
                dtype=jnp.bfloat16 if on_tpu else jnp.float32,
                wire_dtype=wire_dtype, wire_dtype_combine=wire_combine,
                wire_dtype_dcn=wire_dcn, a2a_chunks=chunks,
            )
            mesh = make_mesh(cfg, dp=1, devices=devs)
            params = init_moe_params(jax.random.PRNGKey(0), cfg)
            params = jax.tree_util.tree_map(
                lambda p: p.astype(cfg.dtype), params)
            x = jax.random.normal(
                jax.random.PRNGKey(1), (cfg.tokens, cfg.hidden_size),
                cfg.dtype)
            fn = lambda c: ep_moe_layer(params, c, cfg, mesh,
                                        use_pallas=on_tpu,
                                        dcn_inner=inner if hier else 0).out
            t = _time_chained(fn, x, trials=trials, chain=8)
            base_t = base_t or t
            path = "hierarchical" if hier else "collective"
            tc = a2a_transport_cost(d, max(inner, 1),
                                    slab_bytes(cfg, d, leg="dispatch"),
                                    gen=gen if gen in _PEAK_TFLOPS
                                    else "v5e",
                                    dcn_slab_bytes=slab_bytes(
                                        cfg, d, leg="dispatch",
                                        hop="dcn"))
            rec = {
                "metric": f"scaling_ms[{path},slices={n_slices},ep={d},"
                          f"tokens_per_rank=256,"
                          f"{'tpu' if on_tpu else 'virtual_cpu'}]",
                "value": round(t * 1e3, 3),
                "unit": "ms",
                # weak-scaling efficiency over the slice axis: per-rank
                # work constant, only the transport topology changes
                "vs_baseline": round(base_t / t, 3),
                "slices": n_slices,
                "dcn_inner": inner if hier else None,
                "path": path,
                "d": d,
                "a2a_chunks": cfg.a2a_chunks or 1,
                # modeled per-hop wire bytes of one dispatch leg slab
                # (the dcn row shrinks under --wire-dcn) + the DCN
                # message aggregation the two-stage exchange buys
                "slab_ici_mb": round(
                    slab_bytes(cfg, d, leg="dispatch") / 2**20, 4),
                "slab_dcn_mb": round(
                    slab_bytes(cfg, d, leg="dispatch", hop="dcn")
                    / 2**20, 4),
                "dcn_messages_flat": tc["flat"]["dcn_messages"],
                "dcn_messages_hier": tc["hierarchical"]["dcn_messages"],
            }
            rec.update(_wire_fields(cfg))
            rec["wire_dtype_dcn"] = wire_dcn or "off"
            if gen in _PEAK_TFLOPS:
                try:
                    preds = {p.path: p for p in predict_paths(
                        cfg, d, gen, slices=n_slices)}
                    p = preds.get(path) or preds["collective"]
                    rec["planner_gen"] = gen
                    rec["predicted_ms"] = round(p.total_ms, 3)
                    rec["prediction_error"] = round(
                        t * 1e3 / p.total_ms - 1.0, 3)
                    rec["predicted_dcn_ms"] = round(p.dcn_ms, 4)
                    from flashmoe_tpu.planner.drift import record_drift

                    dr = record_drift(cfg, path, t * 1e3, d=d, gen=gen,
                                      predicted_ms=p.total_ms,
                                      warn=False)
                    rec["drift_exceeded"] = dr.exceeded
                except Exception as e:  # noqa: BLE001 — keep the record
                    rec["planner_error"] = (f"{type(e).__name__}: "
                                            f"{str(e)[:120]}")
            print(json.dumps(rec), flush=True)
            _flush_observability(rec)
    finally:
        if saved_mock is None:
            os.environ.pop("FLASHMOE_MOCK_SLICES", None)
        else:
            os.environ["FLASHMOE_MOCK_SLICES"] = saved_mock


def _bench_tiles(cfg: MoEConfig, name: str, trials: int, chain: int):
    """Per-tile-choice records of the row-windowed fused schedule
    (ISSUE 12): every feasible K-window of the IO-aware chooser's grid
    (``parallel/fused.py:rowwin_sweep_candidates`` — one point per kw,
    at its widest feasible row tile) is
    forced through a throwaway ``fused_tiles`` table, timed through the
    fused layer on a 1-rank mesh (the geometry being tuned is
    transfer-free), and emitted as its own JSON record through the
    planner drift monitor — each record carries the byte model's
    roofline prediction FOR THAT TILE PAIR, so a tiles sweep doubles as
    a calibration run for the IO model the chooser minimizes.  The
    fastest candidate is what ``tune_sweep.py --stage tiles`` would
    commit."""
    from flashmoe_tpu import tuning
    from flashmoe_tpu.analysis import path_costs
    from flashmoe_tpu.models.reference import init_moe_params as _init
    from flashmoe_tpu.parallel.fused import (
        fused_ep_moe_layer, rowwin_sweep_candidates,
    )
    from flashmoe_tpu.parallel.mesh import make_mesh
    from flashmoe_tpu.parallel.topology import (
        _PEAK_TFLOPS, chip_spec, tpu_generation,
    )

    cfg = cfg.replace(ep=1, tp=1, fused_schedule="rowwin",
                      moe_backend="fused")
    h, i = cfg.hidden_size, cfg.intermediate_size
    dt = jnp.dtype(cfg.dtype).itemsize
    cap_pad = -(-cfg.capacity_for(cfg.tokens) // 32) * 32
    # the kernel's own candidate grid, one point per feasible K-window
    # at its widest feasible row tile — the sweeps and the chooser can
    # never enumerate different pairs (code-review finding)
    cands = rowwin_sweep_candidates(cap_pad, h, i, dt, cfg.gated_ffn,
                                    False, cfg.expert_top_k)
    if len(cands) < 2:
        print(json.dumps({
            "metric": f"fused_tiles_ms[{name}]", "value": None,
            "unit": "ms", "skipped": True,
            "reason": f"{len(cands)} feasible (cm, kw) rowwin "
                      f"candidates at this shape",
        }), flush=True)
        return
    gen = tpu_generation(jax.devices()[0])
    if gen not in _PEAK_TFLOPS:
        gen = os.environ.get("FLASHMOE_TPU_GEN", "")
    peak_hbm = None
    if gen in _PEAK_TFLOPS:
        peak_tf, hbm_gb = chip_spec(gen)
        if dt >= 4:
            peak_tf /= 2.0
        peak_hbm = (peak_tf * 1e12, hbm_gb * 1e9)
    params = _init(jax.random.PRNGKey(0), cfg)
    params = jax.tree_util.tree_map(lambda p: p.astype(cfg.dtype), params)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (cfg.tokens, cfg.hidden_size), cfg.dtype)
    mesh = make_mesh(cfg, dp=1, devices=jax.devices()[:1])
    tmp = "/tmp/flashmoe_bench_tiles_candidate.json"
    best = None
    try:
        for cm, kw in cands:
            with open(tmp, "w") as f:
                json.dump({"entries": [{
                    "kernel": "fused_tiles",
                    "match": {"h": h, "i": i,
                              "dtype": jnp.dtype(cfg.dtype).name},
                    "set": {"cm": cm, "kw": kw},
                }]}, f)
            os.environ["FLASHMOE_TUNING_FILE"] = tmp
            tuning._load.cache_clear()

            def layer(c):
                return fused_ep_moe_layer(params, c, cfg, mesh).out

            def chained(n):
                def run(p_unused, xx):
                    def body(c, _):
                        return layer(c).astype(c.dtype), None
                    c, _ = jax.lax.scan(body, xx, None, length=n)
                    return c.astype(jnp.float32).sum()
                return jax.jit(run)

            t1 = _time_chain(chained(1), None, x, trials)
            tn = _time_chain(chained(chain), None, x, trials)
            t = max(tn - t1, 1e-9) / (chain - 1)
            rec = {
                "metric": f"fused_tiles_ms[{name}:cm={cm},kw={kw},"
                          f"{jnp.dtype(cfg.dtype).name}]",
                "value": round(t * 1e3, 3), "unit": "ms",
                "cm": cm, "kw": kw, "schedule": "rowwin", "d": 1,
                "backend": jax.default_backend(),
            }
            # byte-model roofline FOR THIS TILE PAIR (the forced table
            # is live, so path_costs prices this candidate's window
            # count), through the drift monitor like every other bench
            # calibration point
            if peak_hbm is not None:
                try:
                    cost = path_costs(cfg, "fused", d_world=1,
                                      schedule="rowwin")
                    pred = max(cost.flops / peak_hbm[0],
                               cost.total_bytes / peak_hbm[1]) * 1e3
                    rec["planner_gen"] = gen
                    rec["predicted_ms"] = round(pred, 3)
                    rec["prediction_error"] = round(
                        t * 1e3 / pred - 1.0, 3)
                    from flashmoe_tpu.planner.drift import record_drift

                    dr = record_drift(cfg, "fused", t * 1e3, d=1,
                                      gen=gen, predicted_ms=pred,
                                      warn=False)
                    rec["drift_exceeded"] = dr.exceeded
                except Exception as e:  # noqa: BLE001 — keep the record
                    rec["planner_error"] = (f"{type(e).__name__}: "
                                            f"{str(e)[:120]}")
            if best is None or t < best[0]:
                best = (t, cm, kw)
            rec["best_so_far"] = best[1:] == (cm, kw)
            print(json.dumps(rec), flush=True)
            _flush_observability(rec)
    finally:
        os.environ.pop("FLASHMOE_TUNING_FILE", None)
        tuning._load.cache_clear()


def _bench_quant(cfg: MoEConfig, name: str, trials: int, chain: int):
    """Per-(store x path) records of the quantized expert store
    (ISSUE 15): the MoE layer timed at full precision and at each
    quant store (int8 / e4m3) on the single-chip explicit path, each
    record carrying the modeled weight bytes saved
    (``analysis.expert_weight_stream_bytes``) and measured-vs-predicted
    drift through the planner drift monitor — a quant sweep doubles as
    a calibration run for the store-width byte model the golden quant
    dimension freezes."""
    from flashmoe_tpu import quant as qtpkg
    from flashmoe_tpu.models.reference import init_moe_params as _init
    from flashmoe_tpu.ops.moe import moe_layer
    from flashmoe_tpu.parallel.topology import (
        _PEAK_TFLOPS, tpu_generation,
    )
    from flashmoe_tpu.planner.model import predict_paths

    cfg = cfg.replace(ep=1, tp=1)
    params = _init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (cfg.tokens, cfg.hidden_size), cfg.dtype)
    use_pallas = jax.default_backend() == "tpu"
    gen = tpu_generation(jax.devices()[0])
    if gen not in _PEAK_TFLOPS:
        gen = os.environ.get("FLASHMOE_TPU_GEN", "")

    def timed(p, c):
        # params are TRACED arguments (the headline bench's
        # convention), not closure constants: baked-in weights would
        # let XLA hoist/constant-fold the dequantize out of the
        # scanned chain, and the sweep would time a plain
        # full-precision matmul (code-review finding)
        def chained(n):
            def run(pp, xx):
                def body(cu, _):
                    return moe_layer(pp, cu, c,
                                     use_pallas=use_pallas
                                     ).out.astype(cu.dtype), None
                cu, _ = jax.lax.scan(body, xx, None, length=n)
                return cu.astype(jnp.float32).sum()
            return jax.jit(run)

        t1 = _time_chain(chained(1), p, x, trials)
        tn = _time_chain(chained(chain), p, x, trials)
        return max(tn - t1, 1e-9) / (chain - 1)

    t_base = timed(params, cfg)
    base_rec = {
        "metric": f"quant_ms[{name}:off,explicit,"
                  f"{jnp.dtype(cfg.dtype).name}]",
        "value": round(t_base * 1e3, 3), "unit": "ms",
        "vs_baseline": 1.0, "path": "explicit", "d": 1,
        "expert_quant": "off", "backend": jax.default_backend(),
    }
    print(json.dumps(base_rec), flush=True)
    _flush_observability(base_rec)

    for qname in ("int8", "e4m3"):
        try:
            cq = cfg.replace(expert_quant=qname)
        except ValueError as e:  # e.g. e4m3 on a float8-less jax
            rec = {"metric": f"quant_ms[{name}:{qname},explicit,"
                             f"{jnp.dtype(cfg.dtype).name}]",
                   "value": None, "unit": "ms", "skipped": True,
                   "reason": f"{type(e).__name__}: {str(e)[:160]}"}
            print(json.dumps(rec), flush=True)
            _flush_observability(rec)
            continue
        qparams = qtpkg.quantize_state(params, qname).params
        t_q = timed(qparams, cq)
        rec = {
            "metric": f"quant_ms[{name}:{qname},explicit,"
                      f"{jnp.dtype(cfg.dtype).name}]",
            "value": round(t_q * 1e3, 3), "unit": "ms",
            "vs_baseline": round(t_base / t_q, 3),
            "path": "explicit", "d": 1,
            "backend": jax.default_backend(),
        }
        rec.update(_quant_fields(cq))
        if gen in _PEAK_TFLOPS:
            try:
                preds = {p.path: p for p in predict_paths(cq, 1, gen)}
                p = preds.get("explicit")
                if p is not None:
                    rec["planner_gen"] = gen
                    rec["predicted_ms"] = round(p.total_ms, 3)
                    rec["prediction_error"] = round(
                        t_q * 1e3 / p.total_ms - 1.0, 3)
                    from flashmoe_tpu.planner.drift import record_drift

                    dr = record_drift(cq, "explicit", t_q * 1e3, d=1,
                                      gen=gen,
                                      predicted_ms=rec["predicted_ms"],
                                      warn=False)
                    rec["drift_exceeded"] = dr.exceeded
            except Exception as e:  # noqa: BLE001 — keep the record
                rec["planner_error"] = (f"{type(e).__name__}: "
                                        f"{str(e)[:120]}")
        print(json.dumps(rec), flush=True)
        _flush_observability(rec)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="reference",
                    choices=sorted(BENCH_CONFIGS.keys()))
    ap.add_argument("--trials", type=int, default=7)
    ap.add_argument("--chain", type=int, default=16)
    ap.add_argument("--sweep", choices=["tokens", "experts", "ep"],
                    default=None,
                    help="emit one JSON line per point instead of the "
                         "single headline number (ep = weak scaling on "
                         "an ep-way mesh)")
    ap.add_argument("--overlap", type=int, default=0, metavar="EP",
                    help="measure overlap efficiency on an EP-way mesh "
                         "instead of the latency bench")
    ap.add_argument("--scaling", action="store_true",
                    help="weak-scaling sweep over mocked 1/2/4/8-slice "
                         "meshes (FLASHMOE_MOCK_SLICES + the two-stage "
                         "hierarchical a2a): one JSON record per slice "
                         "count with measured vs slices=n predicted "
                         "latency through the drift monitor and the "
                         "per-hop wire bytes (see docs/PERF.md "
                         "'Multi-slice scale-out')")
    ap.add_argument("--tiles", action="store_true",
                    help="sweep the row-windowed fused schedule's "
                         "(cm, kw) tile candidates at --config instead "
                         "of the latency bench — one JSON record per "
                         "tile choice through the planner drift "
                         "monitor (the measured counterpart of the "
                         "IO-aware chooser; see docs/PERF.md)")
    ap.add_argument("--quant", action="store_true",
                    help="sweep the quantized expert store "
                         "(MoEConfig.expert_quant int8/e4m3) at "
                         "--config instead of the latency bench — one "
                         "JSON record per (store, path) with modeled "
                         "weight bytes saved and measured-vs-predicted "
                         "drift (see docs/PERF.md 'Quantized expert "
                         "storage')")
    ap.add_argument("--ckpt", action="store_true",
                    help="measure step-loop checkpoint blocking time, "
                         "sync vs async save, instead of the latency "
                         "bench (host-side)")
    ap.add_argument("--profile", action="store_true",
                    help="phase-level profile + predicted-vs-actual "
                         "cost ledger over the path x chunks x wire "
                         "matrix (virtual CPU mesh; artifacts into "
                         "--obs-dir, summarized by "
                         "`observe --ledger`)")
    ap.add_argument("--profile-quick", action="store_true",
                    help="--profile restricted to the first matrix "
                         "point (CI smoke)")
    ap.add_argument("--profile-steps", type=int, default=1,
                    help="profiled steps per matrix point")
    ap.add_argument("--serve", action="store_true",
                    help="offered-load serving sweep through the "
                         "continuous-batching engine (one record per "
                         "load point with tokens/sec + TTFT/TPOT "
                         "percentiles; see docs/SERVING.md)")
    ap.add_argument("--fabric", action="store_true",
                    help="offered-load sweep over mocked 1/2/4-replica "
                         "disaggregated fabrics (FLASHMOE_MOCK_FABRIC "
                         "+ the replica router + DCN-priced KV "
                         "handoff): one record per (replicas, load) "
                         "point (see docs/SERVING.md 'Disaggregated "
                         "fabric')")
    ap.add_argument("--vclock", action="store_true",
                    help="with --fabric: step the sweep on the "
                         "fabric's deterministic virtual clock behind "
                         "the front door — TTFT/TPOT measured under "
                         "the modeled DCN delay, plus measured-vs-"
                         "priced handoff reconciliation and per-"
                         "request latency attribution on every record")
    ap.add_argument("--faults", action="store_true",
                    help="with --fabric: run the serving fault-"
                         "tolerance sweep instead of the load sweep — "
                         "one record per chaos fault (replica_crash / "
                         "handoff_corrupt / handoff_timeout / "
                         "frontdoor_loss / net_partition / "
                         "lease_split_brain / replica_stall / "
                         "lease_torn_write) with recovery latency, "
                         "migrated-request count, retry totals, "
                         "heartbeat detection latency and shed "
                         "fraction (docs/RESILIENCE.md "
                         "'Serving-side ladder')")
    ap.add_argument("--wire", default="inproc",
                    choices=("inproc", "tcp"),
                    help="with --fabric: the KV-handoff wire for the "
                         "load sweep — 'tcp' sends every transfer "
                         "through a real localhost socket (length-"
                         "prefixed frames + per-page CRC verify) and "
                         "tags each record's identity with wire=tcp; "
                         "'inproc' (default) is the byte-identical "
                         "in-process path")
    ap.add_argument("--serve-loads", default="4,2,1",
                    help="comma-separated arrival gaps in engine "
                         "steps, lightest first (smaller = higher "
                         "offered load)")
    ap.add_argument("--serve-requests", type=int, default=8,
                    help="requests per --serve load point")
    ap.add_argument("--serve-batch", type=int, default=4,
                    help="engine decode-batch width for --serve")
    ap.add_argument("--speculate", type=int, default=None, metavar="K",
                    help="with --serve: arm speculative decoding at "
                         "draft_tokens=K — per-record accept_rate / "
                         "spec_tokens_per_step, an equal-SLO TPOT "
                         "comparison against a per-point baseline, "
                         "and the spec=kK metric-identity tag")
    ap.add_argument("--telemetry-port", type=int, default=None,
                    metavar="PORT",
                    help="with --serve: arm the live scrape plane for "
                         "the sweep and self-scrape /metrics mid-sweep "
                         "into each record (0 = ephemeral port)")
    ap.add_argument("--regression", action="store_true",
                    help="append this run's metric points to "
                         "obs/history.jsonl for the perf sentry "
                         "(`observe --regression`); headline, --serve, "
                         "--profile and --scaling modes")
    ap.add_argument("--deadline", type=int, default=720,
                    help="wall-clock watchdog (s) for the measurement; "
                         "emits the best partial record instead of "
                         "running on past it")
    ap.add_argument("--wire-dtype", default=None,
                    help="EP payload wire dtype for the dispatch leg "
                         "(bf16 / e4m3 / e5m2; default off) — recorded "
                         "on every emitted measurement")
    ap.add_argument("--wire-combine", default=None,
                    help="EP payload wire dtype for the combine leg")
    ap.add_argument("--wire-dcn", default=None,
                    help="per-hop wire dtype for the CROSS-SLICE (DCN) "
                         "stage of the hierarchical a2a "
                         "(MoEConfig.wire_dtype_dcn; --scaling only — "
                         "the other modes have no DCN hop)")
    ap.add_argument("--a2a-chunks", type=int, default=None,
                    help="chunked double-buffered EP pipeline depth "
                         "(MoEConfig.a2a_chunks; default off = serial "
                         "schedule) — honored by the latency bench, "
                         "the ep sweep, and --overlap (which also "
                         "measures the serial baseline for comparison)")
    ap.add_argument("--obs-dir",
                    default=os.environ.get("FLASHMOE_OBS_DIR"),
                    help="directory for observability artifacts "
                         "(bench_records.jsonl + decisions.jsonl, "
                         "summarized by `python -m flashmoe_tpu.observe`)")
    args = ap.parse_args()
    _OBS[0] = args.obs_dir

    # live-plane flag contracts (the --profile/--ckpt fail-fast rule:
    # refuse flags a mode would silently ignore)
    if args.telemetry_port is not None and not (args.serve
                                                or args.fabric):
        ap.error("--telemetry-port applies with --serve/--fabric only "
                 "(the live scrape plane rides the serving sweeps; the "
                 "train CLIs take their own --telemetry-port)")
    if args.vclock and not args.fabric:
        ap.error("--vclock applies with --fabric only (the virtual "
                 "clock is the fabric's measured-latency plane; every "
                 "other mode times real work on the wall clock)")
    if args.faults and not args.fabric:
        ap.error("--faults applies with --fabric only (the fault "
                 "sweep drills the serving fabric's recovery ladder; "
                 "no other mode owns those faults)")
    if args.faults and args.vclock:
        ap.error("--faults already steps every drill on the virtual "
                 "clock; drop --vclock")
    if args.faults and args.telemetry_port is not None:
        ap.error("--faults drives self-contained chaos drills with "
                 "no live scrape window; drop --telemetry-port")
    if args.wire != "inproc" and not args.fabric:
        ap.error("--wire applies with --fabric only (the socket wire "
                 "carries KV handoffs between fabric pools; no other "
                 "mode moves KV pages)")
    if args.faults and args.wire != "inproc":
        ap.error("--faults picks each drill's wire itself "
                 "(net_partition runs tcp, the rest in-process); "
                 "drop --wire")
    if args.regression and (args.ckpt or args.overlap or args.sweep
                            or args.tiles or args.quant):
        ap.error("--regression appends measured runs from the "
                 "headline bench, --serve, --profile, or --scaling; "
                 "drop --ckpt/--overlap/--sweep/--tiles/--quant")
    _REG[0] = (os.path.join(args.obs_dir or "obs", "history.jsonl")
               if args.regression else None)
    _REG[1].clear()

    # the headline record's identity follows the mode, so a tiles-sweep
    # or scaling-sweep skip/error is machine-distinguishable from a
    # latency-bench one
    headline_metric = (f"fused_tiles_ms[{args.config}]" if args.tiles
                       else f"quant_ms[{args.config}]" if args.quant
                       else "scaling_ms[slices]" if args.scaling
                       else "fabric_fault[matrix]"
                       if (args.fabric and args.faults)
                       else "fabric_tokens_per_sec[replicas]"
                       if args.fabric
                       else f"moe_layer_fwd_ms[{args.config}]")

    def emit_error(msg, code=2):
        print(json.dumps({
            "metric": headline_metric,
            "value": -1, "unit": "ms", "vs_baseline": 0,
            "error": msg,
        }), flush=True)
        sys.exit(code)

    def require_tpu():
        """These modes time the chip.  Without one there is nothing to
        time: an error record and rc 2, never a CPU number under a
        device metric's name and never a ``skipped`` record."""
        try:
            dev = jax.devices()[0]
        except RuntimeError as e:  # no backend came up at all
            emit_error(f"no JAX backend: {str(e)[:300]}")
        if dev.platform != "tpu":
            emit_error(f"{headline_metric} needs a TPU; JAX found "
                       f"platform {dev.platform!r} ({dev.device_kind})")

    def emit_best_partial(reason):
        """Emit whatever full measurement exists for the in-flight config
        (sweeps included: _PARTIAL carries that point's own cfg/name).
        Exit codes are machine-distinguishable: 0 = headline fully
        measured, 1 = interrupted sweep (emitted rows are real), 3 =
        headline partial (xla leg missing; the record also carries
        vs_baseline null), 2 = nothing measured."""
        tf, tx = _PARTIAL.get("fused"), _PARTIAL.get("xla")
        pcfg, pname = _PARTIAL.get("cfg"), _PARTIAL.get("name")
        if tf is not None and pcfg is not None:
            _emit(pcfg, pname, tf, tx,
                  note=f"{reason}; xla path "
                       f"{'measured' if tx else 'missing'}")
            sys.exit(1 if args.sweep else (0 if tx is not None else 3))
        emit_error(reason)

    def on_deadline(signum, frame):
        emit_best_partial(f"deadline {args.deadline}s exceeded "
                          f"(backend hung or compile stalled)")

    if args.deadline > 0:
        signal.signal(signal.SIGALRM, on_deadline)

    if (args.wire_dtype or args.wire_combine or args.a2a_chunks) \
            and args.ckpt:
        # refuse rather than silently measure uncompressed: the ckpt
        # mode is host-side and exchanges no wire payloads.  --overlap
        # now HONORS both knobs: the chunked schedule encodes/decodes
        # per chunk inside the pipeline, so compressed chunked overlap
        # is exactly the workload the knobs exist for.
        ap.error("--wire-dtype/--wire-combine/--a2a-chunks apply to "
                 "the latency bench, --sweep and --overlap runs, "
                 "not --ckpt")
    if args.a2a_chunks is not None and args.a2a_chunks < 1:
        ap.error("--a2a-chunks must be >= 1")
    if args.wire_dcn and not args.scaling:
        # fail-fast contract: the DCN-hop wire only exists on the
        # two-stage multi-slice exchange the scaling sweep runs; every
        # other mode would silently ignore it
        ap.error("--wire-dcn applies to --scaling only (the other "
                 "modes run no cross-slice hop)")
    if args.speculate is not None and not args.serve:
        # checked BEFORE any mode dispatches (--fabric/--scaling
        # return early): a silently-dropped --speculate would report
        # a plain sweep as a speculative one
        ap.error("--speculate applies with --serve only (the "
                 "speculative drill rides the serving engine)")
    if args.speculate is not None and args.speculate < 1:
        ap.error("--speculate must be >= 1 draft token")
    if args.fabric:
        # the --profile/--ckpt fail-fast contract: the fabric sweep
        # drives its own CPU-sized drill model over its own mocked
        # replica matrix — refuse every mode/knob it would silently
        # ignore
        if args.ckpt or args.overlap or args.profile \
                or args.profile_quick or args.quant or args.serve \
                or args.sweep or args.tiles or args.scaling:
            ap.error("--fabric is its own mode; drop "
                     "--ckpt/--overlap/--profile/--quant/--serve/"
                     "--sweep/--tiles/--scaling")
        if args.wire_dtype or args.wire_combine or args.a2a_chunks:
            ap.error("--fabric drives the CPU-sized serving drill "
                     "model; --wire-dtype/--wire-combine/--a2a-chunks "
                     "do not apply")
    if args.quant:
        # the --profile/--ckpt fail-fast contract: the quant sweep pins
        # its own (store x path) matrix at ep=1 — refuse knobs/modes it
        # would silently ignore.  --ckpt and --overlap are the
        # shape-changing combinations the ISSUE names; the rest follow
        # the same rule.
        if args.wire_dtype or args.wire_combine or args.a2a_chunks:
            ap.error("--quant sweeps the expert weight store; "
                     "--wire-dtype/--wire-combine/--a2a-chunks do not "
                     "apply")
        if args.overlap or args.ckpt or args.sweep or args.serve \
                or args.profile or args.profile_quick or args.tiles \
                or args.scaling:
            ap.error("--quant is its own mode; drop "
                     "--overlap/--ckpt/--sweep/--serve/--profile/"
                     "--tiles/--scaling")
    if args.scaling:
        if args.overlap or args.ckpt or args.sweep or args.serve \
                or args.profile or args.profile_quick or args.tiles:
            ap.error("--scaling is its own mode; drop "
                     "--overlap/--ckpt/--sweep/--serve/--profile/"
                     "--tiles")
        if os.environ.get("FLASHMOE_OVERLAP_TPU") == "1":
            require_tpu()
        if args.deadline > 0:
            signal.alarm(args.deadline)
        _bench_scaling(args.trials, wire_dtype=args.wire_dtype,
                       wire_combine=args.wire_combine,
                       wire_dcn=args.wire_dcn,
                       a2a_chunks=args.a2a_chunks)
        _finish_regression()
        return
    if args.fabric:
        if os.environ.get("FLASHMOE_OVERLAP_TPU") == "1":
            require_tpu()
        if args.deadline > 0:
            signal.alarm(args.deadline)
        if args.faults:
            _bench_fabric_faults()
        else:
            _bench_fabric([4, 2, 1], requests=8, max_batch=4,
                          telemetry_port=args.telemetry_port,
                          vclock=args.vclock, wire=args.wire)
        _finish_regression()
        return
    if args.tiles:
        # the --profile/--ckpt fail-fast contract: refuse knobs/modes
        # this mode would silently ignore — the tiles sweep pins its
        # own (fused, rowwin, ep=1) execution and the RDMA transport
        # composes with neither wire compression nor chunking
        if args.wire_dtype or args.wire_combine or args.a2a_chunks:
            ap.error("--tiles sweeps the fused rowwin kernel; "
                     "--wire-dtype/--wire-combine/--a2a-chunks do not "
                     "apply")
        if args.overlap or args.ckpt or args.sweep or args.serve \
                or args.profile or args.profile_quick:
            ap.error("--tiles is its own mode; drop "
                     "--overlap/--ckpt/--sweep/--serve/--profile")
    if not args.serve and (args.serve_requests != 8
                           or args.serve_batch != 4
                           or args.serve_loads != "4,2,1"):
        # checked BEFORE any mode dispatches: --profile et al. return
        # early, and a silently-dropped --serve-requests would break
        # the fail-fast contract every other flag combination honors
        ap.error("--serve-loads/--serve-requests/--serve-batch only "
                 "apply with --serve")
    if args.profile or args.profile_quick:
        # --profile runs its own fixed path x chunks x wire matrix;
        # refuse knobs/modes it would silently ignore rather than let
        # the user believe they profiled a shape they named (the same
        # fail-fast contract --ckpt applies to the wire knobs)
        if args.wire_dtype or args.wire_combine or args.a2a_chunks:
            ap.error("--profile ledgers its own path x chunks x wire "
                     "matrix; --wire-dtype/--wire-combine/--a2a-chunks "
                     "do not apply")
        if args.overlap or args.ckpt or args.sweep or args.serve:
            ap.error("--profile is its own mode; drop "
                     "--overlap/--ckpt/--sweep/--serve")
        if args.deadline > 0:
            signal.alarm(args.deadline)
        _bench_profile(args.obs_dir, steps=args.profile_steps,
                       quick=args.profile_quick)
        _finish_regression()
        return
    if args.profile_steps != 1:
        ap.error("--profile-steps only applies with "
                 "--profile/--profile-quick")
    if args.serve:
        # the --profile/--ckpt contract: refuse knobs/modes this mode
        # would silently ignore rather than let the user believe they
        # swept a shape they named
        if args.wire_dtype or args.wire_combine or args.a2a_chunks:
            ap.error("--serve drives the CPU-sized serving drill "
                     "model; --wire-dtype/--wire-combine/--a2a-chunks "
                     "do not apply")
        if args.overlap or args.ckpt or args.sweep:
            ap.error("--serve is its own mode; drop "
                     "--overlap/--ckpt/--sweep")
        try:
            loads = [int(v) for v in
                     str(args.serve_loads).split(",") if v.strip()]
        except ValueError:
            ap.error(f"--serve-loads must be comma-separated ints, "
                     f"got {args.serve_loads!r}")
        if not loads or any(v < 1 for v in loads):
            ap.error("--serve-loads gaps must be >= 1 engine step")
        if args.deadline > 0:
            signal.alarm(args.deadline)
        _bench_serve(loads, requests=args.serve_requests,
                     max_batch=args.serve_batch,
                     telemetry_port=args.telemetry_port,
                     speculate=args.speculate)
        _finish_regression()
        return
    if args.ckpt:
        if args.deadline > 0:
            signal.alarm(args.deadline)
        _bench_checkpoint(args.trials)
        return
    if args.overlap:
        if args.deadline > 0:
            signal.alarm(args.deadline)
        _bench_overlap(args.overlap, args.trials,
                       wire_dtype=args.wire_dtype,
                       wire_combine=args.wire_combine,
                       a2a_chunks=args.a2a_chunks)
        return
    if args.sweep == "ep":
        if args.deadline > 0:
            signal.alarm(args.deadline)
        _sweep_ep(args.trials, wire_dtype=args.wire_dtype,
                  wire_combine=args.wire_combine,
                  a2a_chunks=args.a2a_chunks)
        return

    require_tpu()
    if args.deadline > 0:
        signal.alarm(args.deadline)

    cfg = BENCH_CONFIGS[args.config]
    if cfg.ep > 1 and len(jax.devices()) < cfg.ep:
        # the layer-latency modes time ONE chip's layer; a preset whose
        # ep exceeds the devices present runs at ep=1, and _emit says so
        # in the record (`ep_folded_from`)
        print(f"# {args.config}: ep={cfg.ep} folded to 1 "
              f"({len(jax.devices())} device(s) present)",
              file=sys.stderr, flush=True)
        cfg = cfg.replace(ep=1)
    if args.wire_dtype or args.wire_combine:
        cfg = cfg.replace(wire_dtype=args.wire_dtype,
                          wire_dtype_combine=args.wire_combine)
    if args.a2a_chunks and args.a2a_chunks > 1:
        cfg = cfg.replace(a2a_chunks=args.a2a_chunks)  # ValueError if
        # the count cannot divide this config's local-expert axis

    if args.tiles:
        try:
            _bench_tiles(cfg, args.config, args.trials, args.chain)
        except Exception as e:  # noqa: BLE001 — always leave a record
            emit_error(f"{type(e).__name__}: {str(e)[:300]}")
        return

    if args.quant:
        try:
            _bench_quant(cfg, args.config, args.trials, args.chain)
        except Exception as e:  # noqa: BLE001 — always leave a record
            emit_error(f"{type(e).__name__}: {str(e)[:300]}")
        return

    try:
        if args.sweep == "tokens":
            for s in (1024, 2048, 4096, 8192, 16384):
                c = cfg.replace(sequence_len=s)
                n = f"{args.config}/S={s}"
                tf, tx = bench_moe_layer(c, args.trials, args.chain,
                                         name=n)
                _emit(c, n, tf, tx)
            return
        if args.sweep == "experts":
            for e in (8, 16, 32, 64, 128):
                c = cfg.replace(num_experts=e,
                                expert_top_k=min(cfg.expert_top_k, e))
                n = f"{args.config}/E={e}"
                tf, tx = bench_moe_layer(c, args.trials, args.chain,
                                         name=n)
                _emit(c, n, tf, tx)
            return
        t_fused, t_xla = bench_moe_layer(cfg, args.trials, args.chain,
                                         name=args.config)
    except Exception as e:  # noqa: BLE001 — always leave a JSON record
        emit_best_partial(f"{type(e).__name__}: {str(e)[:300]}")
        return
    _emit(cfg, args.config, t_fused, t_xla)
    _finish_regression()


if __name__ == "__main__":
    from flashmoe_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
