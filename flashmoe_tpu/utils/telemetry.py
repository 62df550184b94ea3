"""Tracing / profiling / metrics.

The reference instruments with NVTX scoped ranges in a "Flashmoe" domain
around every host phase (``csrc/include/flashmoe/telemetry.cuh:16-21``,
used throughout ``bootstrap.cuh``/``moe.cuh``), inline ``%globaltimer``
reads inside kernels, and cudaEvent kernel timing.  TPU equivalents:

  * :func:`trace_span` — ``jax.profiler.TraceAnnotation`` +
    ``jax.named_scope``: shows up both in host traces and as HLO op-name
    prefixes in xprof; the ep and fused MoE layers wrap their gate /
    dispatch / a2a / expert / combine phases so traces read like the
    reference's NVTX domain;
  * :func:`program_scopes` — from a compiled program's HLO text to the
    registered scope, pass and kernel of every instruction a device trace
    can show: what ``python -m flashmoe_tpu.observe --device`` joins a
    ``jax.profiler`` trace's events with (the SM-utilization analogue:
    device time by stage comes from the captured trace);
  * :class:`Metrics` — lightweight host-side counters/gauges/timers/
    histograms with JSONL export and Prometheus text exposition (the
    reference's per-rank ``fmt::println`` timings, structured);
  * :class:`FlightRecorder` — a bounded per-step ring buffer of
    structured records (the in-graph MoE stats of
    :mod:`flashmoe_tpu.ops.stats`, losses, step timings) with JSONL
    export, summarized offline by ``python -m flashmoe_tpu.observe``.
"""

from __future__ import annotations

import bisect
import contextlib
import gc
import json
import math
import os
import re
import time
import warnings
from collections import defaultdict, deque

import jax

#: Central decision-name registry.  Every ``Metrics.decision("x.y",
#: ...)`` literal in the codebase must be declared here with a one-line
#: meaning — a typo'd name used to vanish silently into the JSONL
#: stream.  Enforced two ways: :meth:`Metrics.decision` warns (and
#: counts ``decision.unregistered``) at runtime, and the static lint
#: pass (``python -m flashmoe_tpu.staticcheck --lint``) fails CI on any
#: unregistered literal.  The table in docs/OBSERVABILITY.md is
#: generated from this dict (:func:`decision_table_markdown`) and the
#: lint's doc-sync rule keeps the two aligned.
DECISION_NAMES: dict[str, str] = {
    "bootstrap.groups":
        "the Decider formed DP x EP groups from the measured/mocked "
        "slice topology at bootstrap",
    "checkpoint.async_error":
        "a background async save failed (surfaced, not raised)",
    "checkpoint.emergency_save":
        "last good state persisted on an abort path",
    "checkpoint.fallback":
        "restore demoted a corrupt step to an older intact one",
    "controller.cooldown":
        "a trigger fired during cooldown (or planned a noop) and was "
        "suppressed",
    "controller.demotion_reset":
        "a restart cleared path demotions earned on the dead topology",
    "controller.morph":
        "the self-healing controller re-selected the MoE path mid-job",
    "controller.probe_error":
        "the slow-trigger throughput re-probe failed; re-placement "
        "degraded to uniform rates",
    "controller.replace":
        "the self-healing controller re-placed/replicated experts "
        "mid-job",
    "controller.replica_morph":
        "the controller drained (sustained-idle fabric) or returned "
        "(sustained queue pressure) a decode replica in the fabric "
        "router's rotation",
    "controller.spec_morph":
        "the controller switched speculative decoding off after the "
        "fleet acceptance EMA ran below the planner's break-even "
        "acceptance for the debounce window (token streams unchanged "
        "by construction — the morph costs zero tokens)",
    "controller.wire_morph":
        "the controller flipped the DCN-hop wire dtype after sustained "
        "a2a-leg dominance on a multi-slice job",
    "fabric.handoff":
        "a prefill KV run crossed to a decode replica as wire-coded "
        "pages: payload size, modeled DCN cost, and whether it hides "
        "under the decode pool's per-step objective",
    "fabric.handoff_drift":
        "measured-vs-priced reconciliation for one KV handoff on the "
        "virtual clock: measured DCN (modeled + chaos), hidden/exposed "
        "split against the decode tick, and whether the measured "
        "overlap verdict agrees with the priced one",
    "fabric.handoff_corrupt":
        "a KV-handoff transfer failed its per-page CRC32 verify at the "
        "receiver: which pages were corrupted, on which attempt — the "
        "bytes never reach the paged cache",
    "fabric.handoff_retry":
        "the handoff transport retransmitted a failed transfer "
        "(corrupt or timed out): attempt number, wasted wire ms, "
        "capped-exponential backoff, remaining retry budget",
    "fabric.heartbeat_miss":
        "a decode replica with pending work advanced no heartbeat seq "
        "across one fabric-step observation: consecutive miss count "
        "and remaining deadline budget before a stall is declared",
    "fabric.heartbeat_stall":
        "the heartbeat watchdog declared a replica stalled MID-STEP "
        "(its probe still answers — only the sub-step heartbeat "
        "deadline catches a hang): last published phase/seq and the "
        "detection latency in virtual decode ms",
    "fabric.migrate":
        "a crashed replica's request moved to a survivor: the resumed "
        "prompt carries every delivered token, so the deterministic "
        "re-prefill replays the token stream bit-equal",
    "fabric.replica_crash":
        "the fabric's health probes detected a dead decode replica: "
        "in-flight and queued victim counts, surviving rotation",
    "fabric.partition":
        "the KV wire dropped a transfer mid-stream (injected "
        "net_partition, or a real kernel-socket reset on the tcp "
        "wire): bytes that never crossed, attempt number — the "
        "receiver discarded the partial transfer at the short read",
    "fabric.route":
        "the replica router placed a request (session affinity or "
        "join-shortest-queue over live /healthz depths)",
    "frontdoor.brownout":
        "the front door's hysteretic overload detector changed state "
        "(enter/exit): queue pressure vs thresholds, debounce/cooldown "
        "/budget bookkeeping (PR 9 controller discipline)",
    "frontdoor.failover":
        "a dead front-door peer's namespace lease moved to a survivor: "
        "shard, old/new owner, bumped epoch",
    "frontdoor.fence":
        "the external lease store REFUSED a stale-epoch lease write: "
        "the claimant's fencing token is not newer than the stored "
        "epoch — the split-brain guard (a zombie door cannot take a "
        "shard back)",
    "frontdoor.lease_repair":
        "the lease store found a torn tail (a writer died mid-append) "
        "and rolled the log back to the last intact CRC-framed "
        "record: torn bytes dropped, restored epoch",
    "frontdoor.shed":
        "a brownout admission verdict: the arriving request was shed "
        "(rejected) or degraded (token budget capped) instead of "
        "joining an overloaded fleet",
    "frontdoor.submit":
        "the fabric front door accepted a request into the fleet-wide "
        "trace namespace and recorded the router's placement",
    "planner.backend_constraint":
        "auto pick demoted to a backend the config can actually run",
    "planner.drift":
        "measured latency compared against the analytical prediction",
    "planner.fallback":
        "a failed execution path was demoted for the process",
    "planner.overlap_drift":
        "measured overlap fraction compared against the chunked bound",
    "planner.path_select":
        "moe_backend='auto' resolved a path (full latency breakdown)",
    "planner.scaleout":
        "the planner traded EP-across-DCN against DP-across-DCN for a "
        "multi-slice job",
    "preempt.drain":
        "graceful drain completed: final step, remaining grace",
    "preempt.notice":
        "a preemption notice arrived (signal source, grace budget)",
    "planner.phase_drift":
        "one MoE phase's measured time compared against its prediction",
    "postmortem.saved":
        "a crash postmortem bundle was written (dir, error, step)",
    "serve.admit":
        "the serving engine admitted a request into the decode batch",
    "serve.attribution":
        "one retired request's measured latency decomposed into "
        "critical-path components (queue wait, router spill, prefill, "
        "handoff DCN, decode, eviction gaps) with the dominant "
        "contributor named; components sum to the span within 1%",
    "serve.evict":
        "page pressure preempted the youngest request back to the "
        "queue (its pages freed, delivered tokens stand)",
    "serve.plan":
        "the engine resolved its prefill- and decode-priced execution "
        "plans (decode priced at per-step token counts)",
    "serve.pools":
        "prefill/decode pool split over the inference-mode Decider "
        "(heterogeneous groups, no allreduce term)",
    "serve.quant":
        "the serving engine loaded a quantized expert state: store "
        "dtype, freed HBM, and the extra KV-cache pages that headroom "
        "buys (flashmoe_tpu/quant/)",
    "serve.retire":
        "a request completed (stop token or max length) with its "
        "TTFT/TPOT (plus per-request draft-acceptance stats when "
        "speculation is configured)",
    "serve.spec":
        "speculative decoding lifecycle: armed at engine build, "
        "morph_on/morph_off at a controller (or operator) toggle — "
        "with the SpecConfig knobs or the morph reason",
    "serve.trace":
        "a request's trace closed at retirement: trace_id, span count, "
        "evictions, end-to-end duration (telemetry_plane/tracing.py)",
    "slo.breach":
        "a step/phase time exceeded its SLO budget",
    "slo.recovered":
        "a breached SLO target returned under budget",
    "supervisor.resume":
        "a restart resumed: incarnation, step, world size, ep x dp",
    "telemetry.server_start":
        "the live telemetry scrape server came up (bound port)",
    "telemetry.server_stop":
        "the live telemetry scrape server shut down",
    "trainer.grad_skip":
        "tier 1 skipped an anomalous update in-graph",
}

#: Central span-name registry — the trace_span / profiler-section
#: analogue of :data:`DECISION_NAMES`.  Every literal handed to
#: :func:`trace_span` or to a profiler ``section(...)`` must be declared
#: here (chunked pipeline spans append a numeric suffix to a registered
#: base: ``moe.expert.3``); the staticcheck lint
#: (``python -m flashmoe_tpu.staticcheck --lint``) flags typo'd or
#: computed literals, because a misspelled span silently forks the phase
#: timeline the cost ledger joins on.  The docs/OBSERVABILITY.md span
#: table is generated from this dict (:func:`span_table_markdown`).
SPAN_NAMES: dict[str, str] = {
    "moe.gate": "router: logits, top-k selection, aux losses",
    "moe.dispatch": "scatter tokens into the exchange layout",
    "moe.a2a_dispatch":
        "dispatch all-to-all (``.k`` suffix = pipeline chunk k)",
    "moe.expert": "expert FFN on received rows (``.k`` = chunk k)",
    "moe.a2a_combine":
        "return all-to-all (``.k`` suffix = pipeline chunk k)",
    "moe.combine": "weighted gather back to token order",
    "moe.fused_kernel": "fused RDMA kernel (dispatch+FFN in one launch)",
    "moe.shared": "shared experts: the dense FFN every token takes",
    "attn.mla_prefill":
        "latent attention, first form: K and V of the whole context "
        "decompressed from the latent rows (prefill, chunked prefill, "
        "training forward)",
    "attn.mla_decode":
        "latent attention, absorbed form: scores and output taken over "
        "the latent rows themselves (decode and verify steps)",
    "attn.kda_prefill":
        "delta-rule linear attention, chunkwise form: matrix products "
        "over 64-token chunks and a scan over the chunks (prefill, "
        "chunked prefill, training forward)",
    "attn.kda_decode":
        "delta-rule linear attention, one recurrence step over the "
        "slots' float32 state (decode)",
    "attn.conv_prefill":
        "gated short convolution over a span: the taps' shifted products "
        "from the carried inputs on (prefill, chunked prefill, training "
        "forward)",
    "attn.conv_decode":
        "gated short convolution, one step over the slots' carried "
        "inputs (decode)",
    "attn.ssm_prefill":
        "state-space mixer, chunked form: masked products with the "
        "cumulative decay inside chunks of ``ssm_chunk`` tokens and a scan "
        "that carries the state between them (prefill, chunked prefill)",
    "attn.ssm_decode":
        "state-space mixer, one recurrence step over every slot's float32 "
        "state, read once and written once (decode)",
    "attn.kv_prefill":
        "K/V attention, the gather arm: a span's rows stored, its context "
        "gathered (or the span itself, a whole prompt) and scored, "
        "blockwise (``fm_flash_span``) or in plain XLA (prefill, chunked "
        "prefill; the CPU's decode steps)",
    "attn.kv_decode":
        "K/V attention, the kernel's arm: a short span over each slot's "
        "own pages, read and written in place (``fm_paged_decode``: decode, "
        "verify and denoise steps on a TPU)",
    "attn.gate":
        "the output gate of a K/V attention layer (``MoEConfig.attn_gate``): "
        "the heads' outputs times the sigmoid of ``u Wg``, before the "
        "output product (every program of a model that gates; inside "
        "``attn.kv``)",
    "attn.kv":
        "a K/V attention layer's part (MHA / GQA, with or without a "
        "window): what of it stands under "
        "neither arm: its norm, the q / k / v projections with their norms "
        "and RoPE, the output product, the residual join; training's "
        "attention whole",
    "attn.mla":
        "a latent-attention layer's part: what of it stands under neither "
        "form: its norm, the projections down and up, the output product, "
        "the pool's store and gather, the residual join",
    "attn.kda":
        "a delta-rule layer's part: what of it stands under neither form: "
        "its norm, projections, short convolutions, gates, the output "
        "product, the state's read and write, the residual join",
    "attn.conv":
        "a gated short convolution layer's part: what of it stands under "
        "neither form: its norm, the in- and out-projections, the gates, "
        "the residual join",
    "attn.ssm":
        "a state-space layer's part: what of it stands under neither form: "
        "its norm, the in-projection, the convolution, the gated norm, the "
        "output product, the state's read and write, the residual join",
    "ffn.dense":
        "a dense feed-forward part (one expert of the dense width, no "
        "router): a layer's own, or a mixture model's leading layers'; "
        "its norm and its residual join with it",
    "ffn.moe":
        "a mixture feed-forward part: what of it stands under none of its "
        "stages (``moe.*``, which open inside it): its norm, its residual "
        "join, what the layer counts",
    "lm.embed":
        "the embedding's rows of a span's tokens (and their multiplier)",
    "lm.sample":
        "the serving engine's sampler program: the rows' keys, the arms "
        "by what the knobs ask (an argmax, a draw, a sort), the tokens",
    "lm.head":
        "the final norm and the head's product over the vocabulary (a "
        "tied head over the embedding's own rows), the logits' divisor",
    "moe.route_groups":
        "group-limited routing: the groups' scores and the mask of the "
        "experts outside the kept groups",
    "moe.zero":
        "zero-compute (identity) experts: the layer's input times the "
        "weights of a token's identity choices, added beside the combine",
    "moe.shortcut_join":
        "a mixture branch read at an earlier layer joins the residual "
        "stream after this layer's feed-forward part",
    "serve.prefill":
        "serving engine: single-pass prompt prefill into cache pages",
    "serve.prefill_feed":
        "serving engine: a whole prompt's upload and its pad to the "
        "bucket, eager, before ``serve.prefill`` (inside ``serve.admit``)",
    "serve.prefill_chunk":
        "serving engine: one fixed-budget chunk of an admitted "
        "prompt's incremental prefill (chunked admission)",
    "serve.chunk_feed":
        "serving engine: a chunk's tokens, block table, page ids and four "
        "scalars uploaded, eager, before ``serve.prefill_chunk`` (inside "
        "``serve.prefill_advance``)",
    "serve.logits_put":
        "serving engine: a finished prefill's logits written into the "
        "slot's row of the pending logits (an eager scatter, inside "
        "``serve.admit`` or ``serve.prefill_advance``)",
    "serve.retire":
        "serving engine: one request retired: pages freed, TTFT/TPOT, the "
        "``serve.retire`` decision (inside ``serve.deliver``)",
    "serve.handoff":
        "fabric: a prefill KV run's page codec round-trip on its way "
        "to the decode replica",
    "serve.decode":
        "serving engine: one continuous-batching decode step",
    "serve.draft":
        "serving engine: host-side n-gram drafting over the per-slot "
        "suffix-match tables (speculative decode's propose phase)",
    "serve.verify":
        "serving engine: one speculative verify forward scoring "
        "draft_tokens+1 positions per slot (replaces serve.decode on "
        "steps where anything was drafted)",
    "serve.queued":
        "request trace: queue wait from arrival (or eviction — "
        "``resumed``) to admission; the visible eviction gap",
    "serve.request":
        "request trace: the parent span of one request's whole "
        "lifecycle (trace_id minted at serve.admit)",
    "serve.step":
        "one whole engine step, the parent of the step's phases (the "
        "profiler's event carries the stat ``step``); on a "
        "request's trace, the step window the request rode (it opens "
        "where the request's last window closed, so the time between "
        "two steps is on the track too)",
    "serve.admit":
        "step phase: arrivals stamped and queued requests admitted "
        "(``serve.prefill`` runs inside it)",
    "serve.prefill_advance":
        "step phase: one chunk of every prompt mid chunked prefill",
    "serve.sample_keys":
        "step phase: per-slot temperature, top-k/top-p, seed and token "
        "index filled into numpy arrays on the host (the sampler "
        "program derives the keys from them; nothing is read back)",
    "serve.sample":
        "step phase: the sampler program dispatched and, when the step "
        "reads them, the wait for its tokens on the host, the step's one "
        "read-back (it waits for the decode step dispatched the step "
        "before, then for the sampler).  On a step that decodes ahead "
        "(``readback: after_dispatch``) the phase opens twice, the "
        "dispatch before ``serve.grow`` and the wait after "
        "``serve.decode``, and its two times add up",
    "serve.deliver":
        "step phase: tokens appended, first-token stamps, retirements "
        "(after ``serve.decode`` on a step that decodes ahead)",
    "serve.grow":
        "step phase: next KV page for every slot at a page edge "
        "(evictions happen here, on a step that read its tokens first)",
    "serve.decode_feed":
        "step phase: the decode step's positions and block tables built "
        "on the host (its feed is the sampler's array on the device); "
        "the verify step's feed too",
    "serve.denoise":
        "step phase: the denoise program of a model that generates by "
        "blocks dispatched, one forward of every decoding slot's open "
        "block (where ``serve.decode`` stands for the other models)",
    "serve.reveal":
        "step phase: the read-back of the slots' blocks as the launch "
        "before left them (the step's one read-back: after "
        "``serve.denoise`` on a step that dispatches ahead) and the "
        "delivery of the blocks it made whole, retirements included",
    "serve.account":
        "step phase: gauges, sketches and the step's flight record",
    "train.data_pull": "host wait on the data iterator",
    "train.step": "one train step: dispatch + device execution",
    "train.forward_backward":
        "in the compiled step: loss and gradients (jax marks the "
        "backward's operations ``transpose(...)`` inside it)",
    "train.optimizer":
        "in the compiled step: gradient norm, optimizer update, new "
        "parameters",
    "train.checkpoint": "checkpoint save on the step loop",
    "train.drain": "graceful preemption drain (final save + cursor)",
}


def register_span(name: str, meaning: str) -> None:
    """Declare a span name at runtime (plugins / experiments).  Repo
    code should add to :data:`SPAN_NAMES` directly so the static lint
    and the docs table see it."""
    SPAN_NAMES[name] = meaning


def span_table_markdown() -> str:
    """The docs/OBSERVABILITY.md span table, generated from the
    registry (the staticcheck doc-sync rule keeps the doc aligned)."""
    lines = ["| span | meaning |", "|------|---------|"]
    for name in sorted(SPAN_NAMES):
        lines.append(f"| `{name}` | {SPAN_NAMES[name]} |")
    return "\n".join(lines)


def register_decision(name: str, meaning: str) -> None:
    """Declare a decision name at runtime (plugins / experiments).
    Repo code should add to :data:`DECISION_NAMES` directly so the
    static lint and the docs table see it."""
    DECISION_NAMES[name] = meaning


def decision_table_markdown() -> str:
    """The docs/OBSERVABILITY.md decision table, generated from the
    registry (single source of truth; the staticcheck doc-sync rule
    verifies the doc carries every name)."""
    lines = ["| decision | meaning |", "|----------|---------|"]
    for name in sorted(DECISION_NAMES):
        lines.append(f"| `{name}` | {DECISION_NAMES[name]} |")
    return "\n".join(lines)


#: Active span listener (one slot): an object with ``span_enter(name)
#: -> token`` / ``span_exit(name, token)``, installed by the phase
#: profiler (:mod:`flashmoe_tpu.profiler.spans`) while a timeline is
#: armed.  ``None`` (default) keeps :func:`trace_span` exactly the
#: metadata-only context manager it always was.
_SPAN_LISTENER: list = [None]


def set_span_listener(listener) -> None:
    """Install (or, with ``None``, remove) the span listener the phase
    profiler uses to turn trace_span sites into a host-side timeline."""
    _SPAN_LISTENER[0] = listener


def get_span_listener():
    """The currently armed listener (None when nothing is armed) — the
    request tracer (telemetry_plane/tracing.py) chains to it so phase
    profiling and request tracing compose."""
    return _SPAN_LISTENER[0]


@contextlib.contextmanager
def trace_span(name: str, **stats):
    """Named scope visible in xprof traces and HLO metadata.  When a
    phase-profiler timeline is armed (:func:`set_span_listener`), the
    span's host enter/exit instants are additionally recorded — the
    xprof-free phase timeline of :mod:`flashmoe_tpu.profiler`.
    ``stats`` ride the profiler's event as its stats (the event keeps
    ``name``)."""
    lst = _SPAN_LISTENER[0]
    tok = lst.span_enter(name) if lst is not None else None
    try:
        with jax.profiler.TraceAnnotation(name, **stats):
            with jax.named_scope(name):
                yield
    finally:
        if lst is not None:
            lst.span_exit(name, tok)


#: jax.monitoring's name for one backend compile request (a load from
#: the persistent cache counts: it is a program the call did not have)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_listening: list = [False]


def watch_compiles() -> None:
    """Count every backend compile of the process into the global
    :data:`metrics` (``compile.count``, ``compile.seconds``).  Installs
    ONE ``jax.monitoring`` listener, however often it is called; the
    serving engine and the train loops call it when they are built and
    report each step's share (:func:`compile_totals` before and after)
    as ``compiles`` / ``compile_ms`` in their step records."""
    if _compile_listening[0]:
        return
    _compile_listening[0] = True

    def on_duration(event, duration_secs, **_):
        if event == _COMPILE_EVENT:
            metrics.counters["compile.count"] += 1
            metrics.counters["compile.seconds"] += duration_secs

    jax.monitoring.register_event_duration_secs_listener(on_duration)


def compile_totals() -> tuple[float, float]:
    """(compiles, seconds spent in them) since :func:`watch_compiles`."""
    c = metrics.counters
    return c.get("compile.count", 0.0), c.get("compile.seconds", 0.0)


_gc_listening: list = [False]


def watch_gc() -> None:
    """Count every collection of CPython's collector into the global
    :data:`metrics` (``gc.count``, ``gc.seconds``: those that ENDED, and
    the time from their start).  Installs ONE ``gc.callbacks`` listener,
    however often it is called; the serving engine calls it when it is
    built and reports each step's share (:func:`gc_totals` before and
    after) as ``gc_n`` / ``gc_ms`` in its step records."""
    if _gc_listening[0]:
        return
    _gc_listening[0] = True
    started = [None]

    def on_gc(phase, info):
        if phase == "start":
            started[0] = time.perf_counter()
        elif started[0] is not None:
            metrics.counters["gc.count"] += 1
            metrics.counters["gc.seconds"] += time.perf_counter() - started[0]
            started[0] = None

    gc.callbacks.append(on_gc)


def gc_totals() -> tuple[float, float]:
    """(collections, seconds spent in them) since :func:`watch_gc`."""
    c = metrics.counters
    return c.get("gc.count", 0.0), c.get("gc.seconds", 0.0)


def span_of_path(path: str) -> str | None:
    """The INNERMOST name of :data:`SPAN_NAMES` on an operation's
    ``op_name`` path (``jit(f)/attn.ssm_prefill/while/body/mul``; jax
    wraps a differentiated scope, ``transpose(jvp(moe.expert))``), a
    chunk's suffix folded (``moe.expert.3``); None where it holds none."""
    from flashmoe_tpu.profiler.spans import merged_phase

    for word in reversed(re.findall(r"[A-Za-z_][\w.]*", path)):
        if merged_phase(word) in SPAN_NAMES:
            return merged_phase(word)
    return None


_HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s+=\s+(.*)$")
_HLO_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_HLO_CALLS = re.compile(
    r"\b(calls|body|condition|to_apply|true_computation|false_computation"
    r"|branch_computations)=(?:%?([\w.\-]+)|\{([^}]*)\})")
#: the instructions whose computations the chip runs instruction by
#: instruction (their events enclose their bodies' on the ``XLA Ops`` line)
_HLO_CONTROL = ("while", "conditional", "call")


def _closing(text: str, start: int) -> int:
    """Index of the bracket that closes the one at ``text[start]``."""
    depth = 0
    for i in range(start, len(text)):
        depth += (text[i] == "(") - (text[i] == ")")
        if not depth:
            return i
    return len(text) - 1


def hlo_result(text: str) -> tuple[str, str, str]:
    """(result shape, opcode, operands) of an instruction's text after its
    ``=``: ``f32[8,128]{1,0} fusion(%a, %b), kind=...`` or a tuple
    ``(s32[], f32[2]{0}) while(%t), ...``; the shape without layouts."""
    if text.startswith("("):
        end = _closing(text, 0) + 1
        shape, rest = text[:end], text[end:].lstrip()
    else:
        shape, _, rest = text.partition(" ")
    opcode, paren, _ = rest.partition("(")
    operands = (rest[len(opcode) + 1:_closing(rest, len(opcode))]
                if paren else "")
    return re.sub(r"\{[^{}]*\}", "", shape), opcode, operands


def program_scopes(hlo_text: str) -> dict:
    """From a compiled program's optimized HLO text
    (``compiled.as_text()``, or what a trace keeps of it:
    ``observe.trace_programs``) to ``{instruction name: (scope, pass,
    kernel)}`` for every instruction the chip's ``XLA Ops`` line can show:
    those of the entry computation and of the computations its ``while``
    / ``conditional`` / ``call`` instructions run, a fusion being ONE
    instruction.  ``scope``: :func:`span_of_path` of the instruction's
    ``op_name``, None where there is none; a fusion whose own names none
    takes the one most of its fused instructions carry; an instruction the
    compiler made, whose ``op_name`` is no path of the program (none at
    all, or a parameter's name: the prefetch of a weight, a copy into
    another layout), takes the one most of the instructions that READ it
    carry.  ``pass``: ``"bwd"`` where the path holds ``transpose(`` (jax's
    mark on a backward's operations), else ``"fwd"``.  ``kernel``: the
    family of a ``tpu_custom_call`` (``fm_ffn_fwd.13`` -> ``fm_ffn_fwd``),
    else None.  The chip's trace names an event by its instruction's text
    and gives it no ``op_name``: this is the join."""
    comps, entry, cur = {}, None, None
    for line in hlo_text.splitlines():
        if not line or line[0] in " }" or line.startswith("HloModule"):
            m = cur is not None and _HLO_INSTRUCTION.match(line)
            if m:
                cur.append((m.group(1), m.group(2)))
        elif line.rstrip().endswith("{"):
            head = line.split("(", 1)[0].split()
            cur = comps[head[-1].lstrip("%")] = []
            if head[0] == "ENTRY":
                entry = cur

    def called(text):
        for m in _HLO_CALLS.finditer(text):
            for name in (m.group(2) or m.group(3)).split(","):
                yield name.strip().lstrip("%")

    def own(text):
        """(the ``op_name`` path or None, its scope, its pass)."""
        m = _HLO_OP_NAME.search(text)
        path = m.group(1) if m else ""
        return (path if "/" in path else None, span_of_path(path),
                "bwd" if "transpose(" in path else "fwd")

    most = lambda votes: max(votes.items(), key=lambda kv: kv[1])[0]
    fused = {}

    def tally(comp):
        """(scope, pass) -> instructions under it, over a fused
        computation and those it calls in turn."""
        if comp not in fused:
            fused[comp] = acc = defaultdict(int)
            for _, text in comps.get(comp, ()):
                _, scope, way = own(text)
                if scope:
                    acc[scope, way] += 1
                for sub in called(text):
                    for key, n in tally(sub).items():
                        acc[key] += n
        return fused[comp]

    out, seen, todo = {}, set(), [entry]
    while todo:
        comp = todo.pop()
        if comp is None or id(comp) in seen:
            continue
        seen.add(id(comp))
        readers = defaultdict(lambda: defaultdict(int))
        for name, text in reversed(comp):       # a reader before its operand
            _, opcode, operands = hlo_result(text)
            path, scope, way = own(text)
            subs = list(called(text))
            if opcode in _HLO_CONTROL:
                todo += [comps.get(sub) for sub in subs]
            elif scope is None and subs:
                votes = defaultdict(int)
                for sub in subs:
                    for key, n in tally(sub).items():
                        votes[key] += n
                if votes:
                    scope, way = most(votes)
            if scope is None and path is None and readers[name]:
                scope, way = most(readers[name])
            if scope is not None:
                for operand in re.findall(r"%([\w.\-]+)", operands):
                    readers[operand][scope, way] += 1
            kernel = None
            if 'custom_call_target="tpu_custom_call"' in text:
                kernel = re.sub(r"(\.\d+)+$", "", name)
            out[name] = (scope, way, kernel)
    return out


class Histogram:
    """Fixed-bucket histogram with percentile estimates and
    Prometheus-compatible cumulative buckets.

    Default bounds span 1 µs – 1000 ms style magnitudes (1-2.5-5 decades)
    — wide enough for both per-step seconds and per-phase milliseconds
    without configuration; pass explicit ``buckets`` when the quantity
    has a known range."""

    DEFAULT_BUCKETS = tuple(
        m * 10.0 ** e for e in range(-3, 4) for m in (1.0, 2.5, 5.0)
    )

    def __init__(self, buckets=None):
        self.buckets = tuple(sorted(buckets)) if buckets \
            else self.DEFAULT_BUCKETS
        # counts[i] = observations <= buckets[i] (exclusive of earlier
        # buckets); counts[-1] = overflow (> buckets[-1])
        self.counts = [0] * (len(self.buckets) + 1)
        self.n = 0
        self.total = 0.0
        self.min = math.inf
        self.max = -math.inf

    def observe(self, value: float):
        v = float(value)
        self.counts[bisect.bisect_left(self.buckets, v)] += 1
        self.n += 1
        self.total += v
        self.min = min(self.min, v)
        self.max = max(self.max, v)

    def percentile(self, q: float) -> float:
        """Approximate q-quantile (0..1) from the bucket boundaries."""
        if not self.n:
            return 0.0
        target = q * self.n
        seen = 0
        for i, c in enumerate(self.counts):
            seen += c
            if seen >= target and c:
                hi = self.buckets[i] if i < len(self.buckets) else self.max
                return min(hi, self.max)
        return self.max

    def summary(self) -> dict:
        if not self.n:
            return {"count": 0}
        return {
            "count": self.n, "sum": self.total,
            "min": self.min, "max": self.max,
            "mean": self.total / self.n,
            "p50": self.percentile(0.5), "p99": self.percentile(0.99),
        }


class FlightRecorder:
    """Bounded ring buffer of per-step structured records — the
    postmortem black box.  Old steps fall off the back, so a recorder
    left attached to a long run costs O(capacity) memory forever; export
    dumps whatever the window still holds.

    Capacity: explicit argument, else ``FLASHMOE_FLIGHT_CAPACITY``,
    else 1024 steps."""

    def __init__(self, capacity: int | None = None):
        if capacity is None:
            try:
                capacity = int(os.environ.get(
                    "FLASHMOE_FLIGHT_CAPACITY", "1024"))
            except ValueError:
                capacity = 1024
        self._buf: deque = deque(maxlen=max(1, int(capacity)))
        self._total = 0  # records ever recorded (ring wraps don't reset)

    @property
    def capacity(self) -> int:
        return self._buf.maxlen

    @property
    def records(self) -> list[dict]:
        return list(self._buf)

    @property
    def total_recorded(self) -> int:
        """Records ever recorded, including ones the ring has dropped —
        the absolute-index space the offset-aware export speaks."""
        return self._total

    def __len__(self) -> int:
        return len(self._buf)

    def record(self, **fields) -> dict:
        rec = dict(fields)
        self._buf.append(rec)
        self._total += 1
        return rec

    def export_jsonl(self, path: str, start: int | None = None,
                     metrics_obj: "Metrics | None" = None) -> int:
        """Export records as JSONL.

        ``start=None`` (legacy): snapshot — truncate ``path`` and write
        every record the ring still holds; returns the count written.

        ``start=<int>``: offset-aware export (the
        :meth:`Metrics.dump_decisions_jsonl` convention): write every
        record with absolute index >= ``start`` that the ring still
        holds, and return the total record count — the next call's
        ``start``.  ``start == 0`` (the cursor's initial value) starts
        a FRESH file, so a stale artifact from an earlier run never
        contaminates this one; ``start > 0`` appends.  A periodic
        flusher passing the previous return value therefore writes each
        record exactly once, and records that rotate out of the bounded
        ring BETWEEN flushes are already on disk instead of silently
        discarded (the mode-"w" data-loss bug this closes).  Records
        that rotated out before ever being flushed are unrecoverable;
        the gap is counted as ``flight.export_lost`` in ``metrics_obj``
        (the global stream by default) so the loss is visible."""
        if start is None:
            with open(path, "w") as f:
                for rec in self._buf:
                    f.write(json.dumps(rec) + "\n")
            return len(self._buf)
        oldest = self._total - len(self._buf)  # abs index of buf[0]
        lost = max(0, oldest - max(start, 0))
        if lost:
            sink = metrics_obj if metrics_obj is not None else metrics
            sink.count("flight.export_lost", lost)
        first = max(start - oldest, 0)
        with open(path, "w" if start <= 0 else "a") as f:
            for i, rec in enumerate(self._buf):
                if i >= first:
                    f.write(json.dumps(rec) + "\n")
        return self._total


#: the content type every Prometheus text-exposition response must
#: carry (the 0.0.4 text format) — the scrape server
#: (telemetry_plane/server.py) sends exactly this on ``/metrics``
PROM_CONTENT_TYPE = "text/plain; version=0.0.4"


def _prom_name(name: str) -> str:
    """Sanitize to the Prometheus metric-name grammar
    ``[a-zA-Z_:][a-zA-Z0-9_:]*``."""
    n = re.sub(r"[^a-zA-Z0-9_:]", "_", name)
    if not n or n[0].isdigit():
        n = "_" + n
    return n


def escape_label_value(value) -> str:
    """Exposition-spec escaping for a label VALUE: backslash, newline,
    and double-quote must be escaped (in that order — escaping the
    backslash first keeps ``\\n`` from double-encoding), or a hostile
    value (a path with quotes, a reason string with newlines) breaks
    every parser downstream of ``/metrics``."""
    return (str(value).replace("\\", r"\\").replace("\n", r"\n")
            .replace('"', r'\"'))


def _snapshot(obj, copy=dict):
    """Copy a registry container that another thread may be growing.

    Even a plain ``dict(d)`` / ``list(d.items())`` can raise
    "dictionary changed size during iteration" when the job thread
    inserts a new key mid-copy (observed under a scrape-hammer on
    CPython 3.10) — retry until a consistent copy lands; under the GIL
    a handful of attempts always suffices."""
    for _ in range(64):
        try:
            return copy(obj)
        except RuntimeError:
            continue
    return copy(obj)    # last try: surface the error if truly stuck


def _prom_labels(labels: dict) -> str:
    if not labels:
        return ""
    inner = ",".join(
        f'{_prom_name(str(k))}="{escape_label_value(v)}"'
        for k, v in sorted(labels.items()))
    return "{" + inner + "}"


class Metrics:
    """Host-side metrics registry: counters, gauges, wall timers,
    histograms, and structured decision records (planner path
    selections, schedule choices — anything a postmortem needs the full
    breakdown of, not just a scalar)."""

    def __init__(self):
        self.counters: dict[str, float] = defaultdict(float)
        self.gauges: dict[str, float] = {}
        self.times: dict[str, list[float]] = defaultdict(list)
        self.histograms: dict[str, Histogram] = {}
        self.sketches: dict = {}          # name -> QuantileSketch
        # name -> {sorted (label, value) tuple -> gauge value}
        self.labeled_gauges: dict[str, dict[tuple, float]] = {}
        self.decisions: list[dict] = []

    def count(self, name: str, inc: float = 1.0):
        self.counters[name] += inc

    def gauge(self, name: str, value: float):
        self.gauges[name] = float(value)

    def labeled_gauge(self, name: str, value: float, **labels):
        """A gauge with label dimensions (one value per label set) —
        e.g. ``labeled_gauge("serve.rate", 120.0, kind="tokens")``.
        Label VALUES are exposition-escaped at render time, so hostile
        strings (quotes, newlines, backslashes) cannot corrupt
        ``/metrics``."""
        key = tuple(sorted((str(k), str(v)) for k, v in labels.items()))
        self.labeled_gauges.setdefault(name, {})[key] = float(value)

    def histogram(self, name: str, value: float, buckets=None):
        h = self.histograms.get(name)
        if h is None:
            h = self.histograms[name] = Histogram(buckets)
        h.observe(value)
        return h

    def sketch(self, name: str, value: float, quantiles=None):
        """Observe ``value`` on the named streaming quantile sketch
        (telemetry_plane/sketch.py): O(1)-memory rolling p50/p90/p99
        instead of a full-history percentile list — the live plane's
        replacement for unbounded TTFT/TPOT retention.  Rendered as a
        Prometheus summary by :meth:`prometheus_text`."""
        s = self.sketches.get(name)
        if s is None:
            from flashmoe_tpu.telemetry_plane.sketch import QuantileSketch

            s = self.sketches[name] = QuantileSketch(
                quantiles or QuantileSketch.DEFAULT_QS)
        s.observe(value)
        return s

    def decision(self, name: str, **fields) -> dict:
        """Record a structured decision (e.g. the planner's path choice
        with its full latency breakdown).  Kept as a list so repeated
        decisions (one per layer/config) are all visible; ``summary()``
        reports the count per decision name.

        Unregistered names (not in :data:`DECISION_NAMES`) are recorded
        anyway — losing the record would be worse — but warn and count
        ``decision.unregistered``, so a typo is visible instead of
        silently forking the JSONL stream."""
        if name not in DECISION_NAMES:
            self.counters["decision.unregistered"] += 1
            warnings.warn(
                f"unregistered decision name {name!r}: declare it in "
                f"flashmoe_tpu/utils/telemetry.py:DECISION_NAMES (the "
                f"staticcheck lint gates literals in-repo)",
                RuntimeWarning, stacklevel=2)
        rec = {"decision": name, **fields}
        self.decisions.append(rec)
        self.counters[f"decision.{name}"] += 1
        return rec

    def last_decision(self, name: str) -> dict | None:
        for rec in reversed(self.decisions):
            if rec["decision"] == name:
                return rec
        return None

    @contextlib.contextmanager
    def timer(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.times[name].append(time.perf_counter() - t0)

    def summary(self) -> dict:
        out: dict[str, float] = dict(self.counters)
        out.update(self.gauges)
        for k, v in self.times.items():
            if v:
                s = sorted(v)
                out[f"{k}_ms_p50"] = s[len(s) // 2] * 1e3
                out[f"{k}_ms_sum"] = sum(v) * 1e3
                out[f"{k}_calls"] = len(v)
        for k, h in self.histograms.items():
            for stat, val in h.summary().items():
                out[f"{k}_{stat}"] = val
        for k, s in self.sketches.items():
            for stat, val in s.summary().items():
                if val is not None:
                    out[f"{k}_{stat}"] = val
        return out

    def prometheus_text(self, prefix: str = "flashmoe") -> str:
        """Prometheus text-exposition (format 0.0.4) rendering of the
        registry: counters as ``*_total``, gauges (labeled included),
        timers and quantile sketches as summaries, histograms with
        cumulative ``le`` buckets.  Every family carries its ``# HELP``
        and ``# TYPE`` lines and every label value is spec-escaped
        (:func:`escape_label_value`); serve it with
        :data:`PROM_CONTENT_TYPE` (the scrape server does).

        Renders from SHALLOW SNAPSHOTS of the registry dicts: the
        scrape server calls this from its own thread while the job
        thread registers new metrics, and iterating the live dicts
        would intermittently raise "dictionary changed size during
        iteration" (an HTTP 500 on the first scrape that races a
        first-time counter/sketch)."""
        lines: list[str] = []
        counters = _snapshot(self.counters)
        gauges = _snapshot(self.gauges)
        labeled = {k: _snapshot(v)
                   for k, v in _snapshot(self.labeled_gauges).items()}
        times = {k: _snapshot(v, list)
                 for k, v in _snapshot(self.times).items()}
        sketches = _snapshot(self.sketches)
        histograms = _snapshot(self.histograms)

        def fmt(v: float) -> str:
            return repr(float(v))

        def family(n: str, kind: str, desc: str):
            lines.append(f"# HELP {n} {escape_label_value(desc)}")
            lines.append(f"# TYPE {n} {kind}")

        for name in sorted(counters):
            n = f"{prefix}_{_prom_name(name)}_total"
            family(n, "counter", f"flashmoe counter {name}")
            lines.append(f"{n} {fmt(counters[name])}")
        for name in sorted(gauges):
            n = f"{prefix}_{_prom_name(name)}"
            family(n, "gauge", f"flashmoe gauge {name}")
            lines.append(f"{n} {fmt(gauges[name])}")
        for name in sorted(labeled):
            series = labeled[name]
            n = f"{prefix}_{_prom_name(name)}"
            family(n, "gauge", f"flashmoe gauge {name}")
            for key in sorted(series):
                lines.append(f"{n}{_prom_labels(dict(key))} "
                             f"{fmt(series[key])}")
        for name in sorted(times):
            v = times[name]
            if not v:
                continue
            n = f"{prefix}_{_prom_name(name)}_seconds"
            s = sorted(v)
            family(n, "summary", f"flashmoe timer {name} (seconds)")
            lines += [
                f'{n}{{quantile="0.5"}} {fmt(s[len(s) // 2])}',
                f"{n}_sum {fmt(sum(v))}",
                f"{n}_count {len(v)}",
            ]
        for name in sorted(sketches):
            sk = sketches[name]
            if not sk.n:
                continue
            n = f"{prefix}_{_prom_name(name)}"
            family(n, "summary",
                   f"flashmoe streaming quantile sketch {name}")
            for q in sk.quantiles:
                val = sk.quantile(q)
                if val is not None:
                    lines.append(f'{n}{{quantile="{q:g}"}} {fmt(val)}')
            lines.append(f"{n}_sum {fmt(sk.total)}")
            lines.append(f"{n}_count {sk.n}")
        for name in sorted(histograms):
            h = histograms[name]
            n = f"{prefix}_{_prom_name(name)}"
            family(n, "histogram", f"flashmoe histogram {name}")
            cum = 0
            for bound, c in zip(h.buckets, h.counts):
                cum += c
                lines.append(f'{n}_bucket{{le="{bound:g}"}} {cum}')
            lines.append(f'{n}_bucket{{le="+Inf"}} {h.n}')
            lines.append(f"{n}_sum {fmt(h.total)}")
            lines.append(f"{n}_count {h.n}")
        return "\n".join(lines) + "\n"

    def dump_jsonl(self, path: str, **extra):
        rec = dict(self.summary(), **extra)
        with open(path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        return rec

    def dump_decisions_jsonl(self, path: str, start: int = 0) -> int:
        """Append recorded decisions (full breakdowns) as JSONL from
        index ``start`` on — callers that flush repeatedly
        pass the previous return value so no decision is written twice.
        Returns the total decision count (the next call's ``start``)."""
        with open(path, "a") as f:
            for rec in self.decisions[start:]:
                f.write(json.dumps(rec) + "\n")
        return len(self.decisions)


metrics = Metrics()
