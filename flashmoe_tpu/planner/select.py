"""Path selection policy: predicted winner, measured winner when
measurements exist.

The policy (VERDICT r3 #4 "measured-winner", applied framework-wide):

  1. :func:`flashmoe_tpu.planner.model.predict_paths` prices every
     candidate path; the fastest *feasible* prediction is the
     **predicted winner**.
  2. If measured end-to-end latencies exist for this shape — committed
     ``path_latency`` tuning entries
     (:func:`flashmoe_tpu.tuning.measured_path_latencies`) or an
     explicit ``measured=`` dict — the fastest
     *measured* path overrides the prediction (**measured winner**).
     Measurements only override for paths the predictor considers
     runnable; a stale measurement of an infeasible path is ignored.
  3. The decision and its full latency breakdown go through
     :mod:`flashmoe_tpu.utils.telemetry` (``metrics.decision``), so a
     postmortem can always answer "why did this run take this path".

Measurements are keyed at path-family granularity ('fused', not
'fused[batched]' / 'fused[rowwin]') because that is what a wall-clock
measurement of the kernel observes — the kernel resolves its own
schedule (``MoEConfig.fused_schedule`` pins it when a measurement must
target one schedule; the per-TILE geometry inside the rowwin schedule
is measured separately, as ``fused_tiles`` tuning entries).
"""

from __future__ import annotations

import dataclasses
import functools

import jax.numpy as jnp

from flashmoe_tpu.config import MoEConfig
from flashmoe_tpu.planner.model import PathPrediction, predict_paths
from flashmoe_tpu.utils.telemetry import metrics


class PathFailure(RuntimeError):
    """A selected execution path failed at trace/compile/run time.

    Carries the backend so recovery layers (``auto_ep_moe_layer``,
    :func:`flashmoe_tpu.runtime.resilient.resilient_train`) can report
    it via :func:`report_path_failure` and re-resolve onto the next-best
    path instead of dying on a path the planner merely *predicted* would
    work."""

    def __init__(self, backend: str, reason: str = ""):
        super().__init__(reason or f"execution path {backend!r} failed")
        self.backend = backend
        self.reason = reason


# Backends observed failing this process — consulted (and demoted away
# from) by every subsequent 'auto' resolution.  'collective' is never
# blacklisted: it is the robust baseline every config can run.
_FAILED_BACKENDS: set[str] = set()


def failed_backends() -> frozenset[str]:
    return frozenset(_FAILED_BACKENDS)


def report_path_failure(backend: str, reason: str = "") -> None:
    """Record a path failure and demote the backend for the rest of the
    process: future ``moe_backend='auto'`` resolutions skip it (runtime
    path polymorphism, docs/RESILIENCE.md — demote to a healthy path,
    don't die).  Logged as a ``planner.fallback`` decision so
    postmortems see WHY the path changed mid-run."""
    metrics.decision("planner.fallback", failed=backend,
                     reason=reason or None, phase="report")
    if backend not in ("collective", "local", None):
        _FAILED_BACKENDS.add(backend)
        _cached_backend.cache_clear()


def reset_path_failures() -> None:
    """Forget reported failures (tests / chaos drills)."""
    if _FAILED_BACKENDS:
        _FAILED_BACKENDS.clear()
        _cached_backend.cache_clear()


@dataclasses.dataclass(frozen=True)
class Selection:
    """The planner's verdict for one (cfg, d, gen) point."""

    winner: str                 # winning path (family name if measured)
    backend: str                # moe_backend that runs it
    mode: str                   # 'predicted' | 'measured'
    predicted_winner: str       # what the model alone would pick
    predicted_ms: float         # the winner's predicted latency
    measured_ms: float | None   # the winner's measured latency (if any)
    predictions: tuple[PathPrediction, ...]
    measured: dict              # family -> measured ms consulted
    a2a_chunks: int = 1         # the winner's chunked-pipeline depth
                                # (1 = serial; >1 only for XLA
                                # transports when the sweep wins)
    chunk_sweep: tuple = ()     # ((n, best feasible predicted ms), ...)
                                # across the candidate chunk counts


def _shape_key(cfg: MoEConfig, d: int, spec: str = "off") -> dict:
    # wire/wire_combine/chunks/quant/spec ride the key so a latency
    # measured with payload compression, a chunked pipeline, a
    # quantized expert store, or a speculative verify span on is never
    # applied to a run without it (and vice versa) —
    # tuning.measured_path_latencies matches them STRICTLY, with
    # "off" / 1 as the implicit defaults for legacy entries.  spec is
    # "v<k>" when the decode step scores a verify_tokens=k span
    from flashmoe_tpu.ops import wire as wr
    from flashmoe_tpu.quant import core as qcore

    return dict(h=cfg.hidden_size, i=cfg.intermediate_size,
                e=cfg.num_experts, k=cfg.expert_top_k, s=cfg.tokens,
                d=d, dtype=jnp.dtype(cfg.dtype).name,
                wire=wr.canonical_name(cfg.wire_dtype),
                wire_combine=wr.canonical_name(cfg.wire_dtype_combine),
                wire_dcn=wr.canonical_name(cfg.wire_dtype_dcn),
                chunks=cfg.a2a_chunks or 1,
                quant=qcore.canonical_name(cfg.expert_quant),
                spec=spec)


def spec_tag(verify_tokens: int | None) -> str:
    """The measurement-identity tag of a speculative verify span:
    ``"off"`` for the plain one-token step, ``"v<k>"`` for a
    ``verify_tokens=k`` span (rides tuning/select shape keys
    like ``wire`` / ``chunks``)."""
    k = int(verify_tokens or 0)
    return f"v{k}" if k else "off"


#: chunk counts the auto sweep considers (filtered per shape by
#: local-expert divisibility; 1 = the serial schedule, always present)
CHUNK_CANDIDATES = (1, 2, 4, 8)


def _chunk_candidates(cfg: MoEConfig, d: int) -> list[int]:
    """Valid ``a2a_chunks`` candidates at (cfg, d): divisors of the
    local-expert axis at BOTH the queried rank count and the config's
    own ep (so ``cfg.replace(a2a_chunks=n)`` always constructs)."""
    if d <= 1 or cfg.num_experts % d:
        return [1]
    nlx_d = cfg.num_experts // d
    nlx_cfg = cfg.num_experts // max(cfg.ep, 1)
    return [n for n in CHUNK_CANDIDATES
            if n == 1 or (nlx_d % n == 0 and nlx_cfg % n == 0)]


def select_path(cfg: MoEConfig, d: int = 1, gen: str | None = None, *,
                slices: int = 1, links: int = 4,
                mxu_fraction: float = 1.0,
                measured: dict | None = None,
                record: bool = True,
                sweep_chunks: bool = False,
                mode: str = "training",
                decode_tokens: int | None = None,
                verify_tokens: int | None = None,
                dp: int = 1, dp_over_dcn: bool = False) -> Selection:
    """Pick the execution path for (cfg, d ranks, gen).

    ``measured``: explicit {path_family: ms} overrides (highest
    precedence); the tuning table is consulted automatically.
    ``record=False`` suppresses the telemetry decision record (pure
    queries, e.g. the CLI's golden writer).

    ``sweep_chunks``: additionally sweep the chunked-pipeline depth
    (``MoEConfig.a2a_chunks``) over :data:`CHUNK_CANDIDATES` and pick
    the fastest (path, chunk count) — the ``moe_backend='auto'``
    resolution uses this; an explicit ``cfg.a2a_chunks`` pins the
    sweep to that value.  Measurements keep their chunk identity: a
    timing recorded at chunks=4 only competes inside the chunks=4
    candidate (the tuning table's ``chunks`` key).

    ``mode``: the pricing regime (``planner.model.predict_paths``) —
    ``'decode'`` re-shapes the config to the per-step decode batch
    (``decode_tokens``, default ``DECODE_TOKENS_DEFAULT``) FIRST, so
    every downstream consumer (chunk candidates, measurement shape
    keys, predictions, the decision record) sees the decode-shaped
    problem; a decode measurement therefore keys at decode token
    counts and can never override a training-shape selection.
    ``verify_tokens`` additionally prices a speculative verify span
    (``decode_tokens x (k+1)`` rows) and stamps the ``spec="v<k>"``
    measurement-identity tag on every shape key, so a verify-span
    timing never crosses with a plain one-token decode timing.

    ``dp`` / ``dp_over_dcn``: price the DP gradient allreduce into
    every prediction (``planner.model.dp_allreduce_ms``) — constant
    across paths, so it never changes which path wins here, but it
    makes selections comparable across slice MAPPINGS; that comparison
    is :func:`scaleout_plan`."""
    from flashmoe_tpu import tuning
    from flashmoe_tpu.planner.model import decode_shape

    if mode not in ("training", "prefill", "decode"):
        raise ValueError(
            f"mode {mode!r} not in ('training', 'prefill', 'decode')")
    if verify_tokens and mode != "decode":
        raise ValueError("verify_tokens prices the speculative verify "
                         "span — decode mode only")
    spec = spec_tag(verify_tokens)
    if mode == "decode":
        cfg = decode_shape(cfg, d, decode_tokens, verify_tokens)
    elif mode == "prefill" and cfg.is_training:
        cfg = cfg.replace(is_training=False)

    gen = gen or tuning.generation()
    if sweep_chunks and cfg.a2a_chunks is None:
        cands = _chunk_candidates(cfg, d)
    else:
        cands = [cfg.a2a_chunks or 1]

    # price every candidate chunk count; measurements are keyed per
    # candidate (the chunks field rides the shape key)
    by_n = []
    for n in cands:
        cfg_n = (cfg if n == (cfg.a2a_chunks or 1)
                 else cfg.replace(a2a_chunks=None if n == 1 else n))
        preds = predict_paths(cfg_n, d, gen, slices=slices, links=links,
                              mxu_fraction=mxu_fraction, dp=dp,
                              dp_over_dcn=dp_over_dcn)
        feasible = [p for p in preds if p.feasible]
        if not feasible:
            continue
        pw = min(feasible, key=lambda p: p.total_ms)
        meas: dict[str, float] = {}
        meas.update(tuning.measured_path_latencies(
            gen, **_shape_key(cfg_n, d, spec)))
        if measured:
            meas.update(measured)
        runnable = {p.family for p in feasible}
        usable = {f: ms for f, ms in meas.items() if f in runnable}
        by_n.append((n, preds, feasible, pw, usable))
    if not by_n:
        raise ValueError(f"no feasible path at d={d} for this config")
    chunk_sweep = tuple((n, round(pw.total_ms, 6))
                        for n, _, _, pw, _ in by_n)
    # the predicted winner across candidates (ties -> fewer chunks:
    # the serial schedule needs no justification)
    n_win, preds, feasible, pred_win, usable = min(
        by_n, key=lambda t: (t[3].total_ms, t[0]))

    best_meas = None  # (ms, n, family, candidate predictions)
    for n, preds_n, feasible_n, _, usable_n in by_n:
        for f, ms in usable_n.items():
            if best_meas is None or (ms, n) < (best_meas[0],
                                               best_meas[1]):
                best_meas = (ms, n, f, preds_n, feasible_n, usable_n)

    if best_meas is not None:
        ms, n_m, win_family, preds_m, feasible_m, usable_m = best_meas
        win_pred = min((p for p in feasible_m
                        if p.family == win_family),
                       key=lambda p: p.total_ms)
        sel = Selection(
            winner=win_family, backend=win_pred.backend, mode="measured",
            predicted_winner=pred_win.path, predicted_ms=win_pred.total_ms,
            measured_ms=ms, predictions=tuple(preds_m),
            measured=dict(usable_m), a2a_chunks=win_pred.a2a_chunks,
            chunk_sweep=chunk_sweep)
    else:
        sel = Selection(
            winner=pred_win.path, backend=pred_win.backend,
            mode="predicted", predicted_winner=pred_win.path,
            predicted_ms=pred_win.total_ms, measured_ms=None,
            predictions=tuple(preds), measured={},
            a2a_chunks=pred_win.a2a_chunks, chunk_sweep=chunk_sweep)

    if record:
        metrics.decision(
            "planner.path_select",
            serving_mode=mode,
            winner=sel.winner, backend=sel.backend, mode=sel.mode,
            predicted_winner=sel.predicted_winner,
            predicted_ms=round(sel.predicted_ms, 4),
            measured_ms=(round(sel.measured_ms, 4)
                         if sel.measured_ms is not None else None),
            gen=gen, d=d, slices=slices,
            a2a_chunks=sel.a2a_chunks,
            chunk_sweep=[list(t) for t in chunk_sweep],
            config=_shape_key(cfg, d, spec),
            breakdown=[{
                "path": p.path, "feasible": p.feasible,
                "compute_ms": round(p.compute_ms, 4),
                "hbm_ms": round(p.hbm_ms, 4),
                "ici_ms": round(p.ici_ms, 4),
                "dcn_ms": round(p.dcn_ms, 4),
                "total_ms": round(p.total_ms, 4),
                "a2a_chunks": p.a2a_chunks,
            } for p in sel.predictions])
    return sel


@functools.lru_cache(maxsize=64)
def _cached_backend(cfg: MoEConfig, d: int, gen: str, slices: int,
                    mode: str = "training", decode_tokens: int = 0
                    ) -> tuple[str, int | None]:
    """(backend, a2a_chunks) plan for one (cfg, d, gen, slices, mode)
    point — the chunk count is the planner's sweep pick for the XLA
    transports (``None`` = serial), kept alongside the backend so
    ``moe_backend='auto'`` resolves both in one cached decision.
    ``mode``/``decode_tokens`` select the pricing regime (the serving
    engine resolves its decode path with ``mode='decode'``; 0 =
    default decode batch)."""
    # constraint filter first: combinations config.py rejects outright
    # never reach the latency comparison
    if cfg.tp > 1:
        return "collective", cfg.a2a_chunks
    sel = select_path(cfg, d, gen, slices=slices, sweep_chunks=True,
                      mode=mode, decode_tokens=decode_tokens or None)
    backend = sel.backend
    chunks = sel.a2a_chunks if sel.a2a_chunks > 1 else None
    if backend in _FAILED_BACKENDS:
        # path fallback: the predicted winner already failed in this
        # process; demote to the fastest feasible prediction on a
        # still-healthy backend, bottoming out at the collective layer
        ranked = sorted((p for p in sel.predictions if p.feasible),
                        key=lambda p: p.total_ms)
        alt = next((p for p in ranked
                    if p.backend not in _FAILED_BACKENDS), None)
        new_backend = alt.backend if alt is not None else "collective"
        metrics.decision(
            "planner.fallback", failed=backend, backend=new_backend,
            winner=(alt.path if alt is not None else "collective"),
            phase="resolve", d=d, gen=gen)
        backend = new_backend
        chunks = (alt.a2a_chunks if alt is not None
                  and alt.a2a_chunks > 1 else None)
    if backend == "ragged" and cfg.num_shared_experts:
        # the ragged layer cannot host shared experts; the demotion is
        # its own telemetry record so the path_select breakdown never
        # silently disagrees with what actually ran
        backend = "collective"
        metrics.decision(
            "planner.backend_constraint", winner=sel.winner,
            requested="ragged", backend=backend,
            reason="shared experts need the collective layer")
    if backend == "local":
        backend = "collective"
    if backend == "fused":
        chunks = None  # the in-kernel transport ignores the knob
    return backend, chunks


def resolve_moe_plan(cfg: MoEConfig, mesh=None, *,
                     mode: str | None = None,
                     decode_tokens: int | None = None
                     ) -> tuple[str, int | None]:
    """(moe_backend, a2a_chunks) an ``moe_backend='auto'`` config
    should run.

    Non-auto configs pass through untouched (their own
    ``cfg.a2a_chunks`` stands).  Auto consults the planner at this
    mesh's ep width, the trace-time generation pin
    (:func:`flashmoe_tpu.tuning.generation` — never touches a possibly
    wedged backend), and the detected slice structure; the chunked-
    pipeline depth is swept alongside the path.  Results are cached per
    (cfg, d, gen, slices, mode); the decision itself is recorded in
    telemetry once per cache fill.

    ``mode``: the pricing regime (None reads ``cfg.serving_mode``, so a
    decode-phase config resolves a decode-priced plan without every
    call site learning the axis); ``decode_tokens``: the per-step
    decode batch the decode regime prices (the serving engine passes
    its batch width; default ``planner.model.DECODE_TOKENS_DEFAULT``).
    """
    if cfg.moe_backend != "auto":
        return cfg.moe_backend, cfg.a2a_chunks
    from flashmoe_tpu import tuning

    mode = mode or cfg.serving_mode or "training"
    d = int(mesh.shape.get("ep", cfg.ep)) if mesh is not None else cfg.ep
    if d <= 1:
        return "collective", None
    slices = 1
    try:
        from flashmoe_tpu.parallel.topology import slice_structure

        ss = slice_structure()
        if ss and d % ss[0] == 0:
            slices = ss[0]
    except Exception:  # noqa: BLE001 — detection must never block trace
        slices = 1
    return _cached_backend(cfg, d, tuning.generation(), slices, mode,
                           int(decode_tokens or 0))


def resolve_moe_backend(cfg: MoEConfig, mesh=None) -> str:
    """The moe_backend an ``moe_backend='auto'`` config should run —
    :func:`resolve_moe_plan` without the chunk component."""
    return resolve_moe_plan(cfg, mesh)[0]


@dataclasses.dataclass(frozen=True)
class ScaleoutPlan:
    """The planner's verdict on how a multi-slice job should map its
    DP x EP axes onto the slice topology (:func:`scaleout_plan`)."""

    mapping: str                # 'ep_across_dcn' | 'dp_across_dcn'
    ep: int                     # expert-parallel width
    dp: int                     # data-parallel replica count
    a2a_slices: int             # slices the ep a2a spans (1 = in-slice)
    dp_over_dcn: bool           # the gradient ring rides DCN
    predicted_ms: float         # winning mapping's per-step prediction
    alternative_ms: float | None  # the losing mapping's (None when the
                                # other mapping is infeasible)
    selection: Selection        # the winner's full path selection
    reason: str


def scaleout_plan(cfg: MoEConfig, n_devices: int, n_slices: int,
                  gen: str | None = None, *, links: int = 4,
                  record: bool = True) -> ScaleoutPlan:
    """Trade **EP-across-DCN** against **DP-across-DCN** for a job of
    ``n_devices`` chips on ``n_slices`` DCN-connected slices — the
    planner-side counterpart of the bootstrap Decider's group formation
    (:func:`flashmoe_tpu.runtime.bootstrap.form_groups`), the tradeoff
    the reference's Decider objective makes with its inter-group
    allreduce term (``decider.cuh:60-158``).

    Two candidate mappings of the same ``dp x ep`` factorization:

    * ``ep_across_dcn`` — the ep axis spans every slice, so the expert
      all-to-all pays the DCN hop (hierarchical two-stage exchange,
      ``wire_dtype_dcn`` applies) while the DP gradient ring rides ICI
      inside each slice;
    * ``dp_across_dcn`` — the ep axis packs inside one slice (needs
      ``ep <= n_devices // n_slices``), the a2a never leaves ICI, and
      the gradient ring pays DCN instead
      (``planner.model.dp_allreduce_ms`` with ``over_dcn=True``).

    Whichever axis moves fewer bytes per step should own the slow hop;
    each candidate is priced end to end through :func:`select_path`
    (chunk sweep included) and the faster total wins.  Inference jobs
    have no allreduce, so ``dp_across_dcn`` wins whenever it is
    feasible.  Recorded as a ``planner.scaleout`` decision."""
    if n_slices < 1 or n_devices % n_slices:
        raise ValueError(
            f"n_devices={n_devices} not divisible into "
            f"{n_slices} slices")
    inner = n_devices // n_slices
    ep = min(cfg.ep if cfg.ep > 1 else n_devices, n_devices)
    while cfg.num_experts % ep:
        ep -= 1
    dp = n_devices // ep

    cands = []
    if n_slices == 1 or ep % n_slices == 0:
        # ep spans the slices evenly; dp replicas live inside slices
        cands.append(("ep_across_dcn", n_slices, False))
    if ep <= inner:
        # ep packs in one slice; the dp ring crosses slices (when any)
        cands.append(("dp_across_dcn", 1, n_slices > 1))
    if not cands:
        raise ValueError(
            f"ep={ep} neither spans {n_slices} slices evenly nor fits "
            f"one slice of {inner} ranks — no regular DP x EP mapping")

    priced = []
    for mapping, a2a_slices, over_dcn in cands:
        sel = select_path(cfg, ep, gen, slices=a2a_slices, links=links,
                          record=False, sweep_chunks=True, dp=dp,
                          dp_over_dcn=over_dcn)
        priced.append((sel.predicted_ms, mapping, a2a_slices, over_dcn,
                       sel))
    priced.sort(key=lambda t: t[0])
    win_ms, mapping, a2a_slices, over_dcn, sel = priced[0]
    alt_ms = priced[1][0] if len(priced) > 1 else None
    reason = (f"{mapping} predicts {win_ms:.3f} ms"
              + (f" vs {alt_ms:.3f} ms" if alt_ms is not None
                 else " (only regular mapping)"))
    plan = ScaleoutPlan(mapping=mapping, ep=ep, dp=dp,
                        a2a_slices=a2a_slices, dp_over_dcn=over_dcn,
                        predicted_ms=win_ms, alternative_ms=alt_ms,
                        selection=sel, reason=reason)
    if record:
        metrics.decision(
            "planner.scaleout", mapping=mapping, ep=ep, dp=dp,
            n_devices=n_devices, n_slices=n_slices,
            a2a_slices=a2a_slices, dp_over_dcn=over_dcn,
            winner=sel.winner, backend=sel.backend,
            a2a_chunks=sel.a2a_chunks,
            predicted_ms=round(win_ms, 4),
            alternative_ms=(round(alt_ms, 4) if alt_ms is not None
                            else None),
            reason=reason)
    return plan
