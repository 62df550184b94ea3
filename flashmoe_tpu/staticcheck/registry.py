"""Declarative registry: which knobs exist, what each guarantees, and
which backends the invariant engine traces them on.

Adding a knob to :class:`~flashmoe_tpu.config.MoEConfig` REQUIRES adding
a row here (or classifying the field as structural) — the matrix-
coverage check (:func:`check_knob_coverage`, CI-gated by
``tests/test_staticcheck.py``) fails otherwise, so a PR 8+ knob (serving
paths, row-windowed fused, ...) gets invariant coverage by adding one
table row, not by writing another one-off jaxpr assertion.
"""

from __future__ import annotations

import dataclasses
from typing import Any


@dataclasses.dataclass(frozen=True)
class Violation:
    """One static-analysis finding.  ``engine`` is the subsystem that
    found it (invariants / census / lint), ``rule`` the check that
    fired, ``subject`` what it fired on (a knob, a config point, a
    file:line), ``detail`` the human-readable explanation."""

    engine: str
    rule: str
    subject: str
    detail: str

    def __str__(self) -> str:
        return f"[{self.engine}:{self.rule}] {self.subject}: {self.detail}"


# ----------------------------------------------------------------------
# Backends the invariant engine traces
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """One traceable MoE execution path.

    ``ep``: mesh width the trace needs (1 = single-chip layer);
    ``dcn_inner``: two-stage exchange blocking (hierarchical only);
    ``stages``: all_to_all hops per exchange leg (flat 1, hierarchical
    2 — each stage moves the full local buffer, the staging cost
    ``analysis.comm_census`` documents); ``meta_a2a_serial`` /
    ``meta_a2a_chunked``: metadata all_to_alls beyond the payload legs
    (the ragged layer's count-matrix exchange); ``meta_gather_*``: the
    same for all_gather."""

    name: str
    ep: int = 2
    dcn_inner: int | None = None
    stages: int = 1
    meta_a2a_serial: int = 0
    meta_a2a_chunked: int = 0
    meta_gather_serial: int = 0
    meta_gather_chunked: int = 0


BACKENDS: tuple[BackendSpec, ...] = (
    # single-chip dispatch (ops/moe.py) — no exchange, XLA oracle path
    BackendSpec("local", ep=1),
    # flat XLA all-to-all EP (parallel/ep.py)
    BackendSpec("collective", ep=2),
    # two-stage ICI+DCN exchange (parallel/ep.py _hierarchical_a2a)
    BackendSpec("hierarchical", ep=4, dcn_inner=2, stages=2),
    # dropless ragged EP, dense fallback arm (parallel/ragged_ep.py):
    # serial trades one [D,D] size gather + one count-matrix a2a;
    # chunked derives everything from one [D, D, nLx] gather
    BackendSpec("ragged", ep=2, meta_a2a_serial=1, meta_gather_serial=1,
                meta_gather_chunked=1),
)

BACKENDS_BY_NAME = {b.name: b for b in BACKENDS}


# ----------------------------------------------------------------------
# Knobs and their invariants
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KnobSpec:
    """One behavior knob of :class:`MoEConfig` and its guarantees.

    ``off_values``: every value equivalent to "off" — the first must be
    the dataclass default (config-identity check: ``replace`` with it is
    an EQUAL frozen dataclass, one jit cache entry, bit-identical by
    construction); every further value must trace to the IDENTICAL
    jaxpr (e.g. ``a2a_chunks=1`` is the serial schedule, ``gather_fused
    =False`` the env-off default).  ``on``: the canonical enabled point
    the on-trace uses.  ``off_rules`` / ``on_rules``: named predicates
    (:mod:`flashmoe_tpu.staticcheck.invariants`) run on the baseline
    trace / the on-trace vs baseline.  ``changes_graph``: whether the
    on point must alter the traced graph at all (``gather_fused`` is a
    kernel-entry selector that leaves the XLA oracle path untouched)."""

    name: str
    off_values: tuple
    on: Any  # mapping of config overrides for the canonical on point
    backends: tuple = ("local", "collective", "hierarchical", "ragged")
    changes_graph: bool = True
    off_rules: tuple = ()
    on_rules: tuple = ()
    doc: str = ""


KNOBS: tuple[KnobSpec, ...] = (
    KnobSpec(
        "wire_dtype", off_values=(None,), on={"wire_dtype": "e4m3"},
        backends=("collective", "hierarchical", "ragged"),
        off_rules=("fp8_free",), on_rules=("fp8_present",),
        doc="EP dispatch-leg payload compression (ops/wire.py); off = "
            "bit-identical, fp8-free graph"),
    KnobSpec(
        "wire_dtype_combine", off_values=(None,),
        on={"wire_dtype_combine": "e5m2"},
        backends=("collective", "hierarchical", "ragged"),
        off_rules=("fp8_free",), on_rules=("fp8_present",),
        doc="EP combine-leg payload compression; off = bit-identical, "
            "fp8-free graph"),
    KnobSpec(
        "wire_dtype_dcn", off_values=(None,),
        on={"wire_dtype_dcn": "e4m3"},
        backends=("hierarchical",),
        off_rules=("fp8_free",), on_rules=("fp8_present",),
        doc="per-hop wire for the CROSS-SLICE (DCN) stage of the "
            "two-stage exchange (parallel/ep.py _wired_exchange): set, "
            "both legs re-encode their DCN hop at this dtype while the "
            "ICI hop keeps the leg wire; None inherits the leg wire — "
            "graph-identical to the single-dtype build.  Hierarchical "
            "backend only: the flat transports have no DCN hop, so the "
            "knob is inert (= off graph) there, which the census's "
            "flat rows double-check"),
    KnobSpec(
        "a2a_chunks", off_values=(None, 1), on={"a2a_chunks": 2},
        backends=("collective", "hierarchical", "ragged"),
        on_rules=("chunked_a2a_count",),
        doc="chunked double-buffered EP pipeline; None and 1 are both "
            "the serial schedule (identical jaxpr)"),
    KnobSpec(
        "collect_stats", off_values=(False,), on={"collect_stats": True},
        on_rules=("no_extra_exchange",),
        doc="in-graph MoEStats; off = bit-identical, on adds reductions "
            "but never an exchange"),
    KnobSpec(
        "degrade_unhealthy_experts", off_values=(False,),
        on={"degrade_unhealthy_experts": True},
        on_rules=("health_ops_added", "no_extra_exchange"),
        doc="tier-0 expert-health masking; off = bit-identical (no "
            "extra is_finite beyond the router's logsumexp), on is "
            "jnp.where-only — no collectives"),
    KnobSpec(
        "expert_replicas", off_values=((),),
        on={"expert_replicas": ((0, 1),)},
        on_rules=("no_extra_exchange",),
        doc="hot-expert replica routing map ((hot, slot), ...) written "
            "by the self-healing controller's re-placement action "
            "(runtime/controller.py): tokens routed to `hot` split "
            "across the two value-identical physical slots after top-k "
            "(ops/gate.py apply_replicas) — jnp.where-only, no "
            "collectives; off = bit-identical, replica-free graph"),
    KnobSpec(
        "expert_quant", off_values=(None,), on={"expert_quant": "int8"},
        on_rules=("quant_ops_present", "no_extra_exchange"),
        doc="quantized expert weight storage & compute "
            "(flashmoe_tpu/quant/): int8/e4m3 FFN weights with "
            "per-output-channel f32 scales, dequantized in compute "
            "(f32 accumulation untouched).  Off = no quant code runs "
            "= bit-identical graph on every backend; on adds the "
            "quantize/dequantize arithmetic (int8 dtypes appear in "
            "the graph — the teeth check) but NEVER an exchange: "
            "weights are rank-local, so compression of their storage "
            "cannot touch a collective"),
    KnobSpec(
        "kv_wire_dtype", off_values=(None,),
        on={"kv_wire_dtype": "e4m3"}, changes_graph=False,
        doc="KV-page handoff wire for the disaggregated fabric "
            "(fabric/handoff.py): the prefill->decode page stream is "
            "encoded/decoded HOST-SIDE between the prefill jit and the "
            "cache store, so BOTH values trace the byte-identical "
            "graph on every backend — off is bit-identical by "
            "construction (the 'off' codec arm returns the arrays "
            "untouched, no astype), and on never adds a collective "
            "(the handoff is a host boundary, not an exchange; the "
            "census's kv-wire rows double-check)"),
    KnobSpec(
        "gather_fused", off_values=(None, False), on={"gather_fused": True},
        backends=("local",), changes_graph=False,
        doc="inference kernel-entry selector; on the XLA oracle path "
            "(use_pallas=False) every value traces to the identical "
            "graph — the knob only swaps Pallas kernel entries"),
    KnobSpec(
        "profile_phases", off_values=(False,),
        on={"profile_phases": True}, changes_graph=False,
        doc="host-side phase-fence clock (flashmoe_tpu/profiler/): the "
            "fences block on concrete eager values only and no-op on "
            "tracers, so BOTH values trace the byte-identical graph on "
            "every backend — off is bit-identical by construction and "
            "on costs nothing under jit"),
)

KNOBS_BY_NAME = {k.name: k for k in KNOBS}

#: serving-plane knobs that live OUTSIDE MoEConfig (constructor seams
#: on the fabric/engine, not dataclass fields) — documented with the
#: same KnobSpec vocabulary so docs/OBSERVABILITY.md can cite one
#: registry, but excluded from :func:`check_knob_coverage`'s
#: MoEConfig-bidirectional matrix (registering them THERE would flag a
#: stale row).  Their off-identity story is drilled where they plug in
#: (tests/test_frontdoor.py's byte-identity gate), not by the jaxpr
#: invariant engine: a clock never appears in a traced graph.
SERVING_KNOBS: tuple[KnobSpec, ...] = (
    KnobSpec(
        "vclock", off_values=(None,), on={"vclock": "VirtualClock()"},
        backends=(), changes_graph=False,
        doc="the fabric's deterministic virtual clock (fabric/"
            "vclock.py): ServingFabric(vclock=...) steps every replica "
            "on per-lane virtual time, the KV handoff advances it by "
            "the measured DCN cost (modeled + chaos), and TTFT/TPOT "
            "become measured-under-delay numbers reconciled against "
            "the priced verdicts (fabric.handoff_drift).  Off (None, "
            "the default) is the wall clock: byte-identical graphs and "
            "token-bit-equal outputs to the unclocked fabric — the "
            "clock is a host-side seam that never enters a jit"),
    KnobSpec(
        "transport", off_values=(None,),
        on={"transport": "HandoffTransport()"},
        backends=(), changes_graph=False,
        doc="the failable KV-handoff wire (fabric/transport.py): "
            "ServingFabric(transport=...) routes every prefill->decode "
            "page stream through a serialize/verify/deserialize hop "
            "with per-page CRC32 checksums, capped-exponential-backoff "
            "retries on corruption or timeout (fabric.handoff_retry / "
            "fabric.handoff_corrupt), and the wasted wire time priced "
            "into the virtual clock (handoff_drift retry_ms).  Off "
            "(None, the default) hands the payload object across "
            "in-process untouched — byte-identical to the PR 15 path; "
            "on with a clean wire is token-bit-equal because the "
            "decode side caches the RECEIVED bytes"),
    KnobSpec(
        "brownout", off_values=(None,),
        on={"brownout": "BrownoutConfig()"},
        backends=(), changes_graph=False,
        doc="hysteretic brownout load-shedding at the front door "
            "(runtime/controller.py BrownoutConfig + frontdoor.py): "
            "FrontDoor(brownout=...) stages admissions and sheds "
            "(mode='shed') or truncates (mode='degrade') NEW arrivals "
            "while fleet queue depth or handoff-retry pressure holds "
            "above the enter threshold, with the controller's debounce"
            "/cooldown/episode-budget discipline (frontdoor.brownout / "
            "frontdoor.shed).  Off (None, the default) admits "
            "everything up front — the PR 15/17 path unchanged; "
            "already-admitted requests are never touched either way"),
    KnobSpec(
        "fault_plan", off_values=(None,),
        on={"fault_plan": "FaultPlan('replica_crash', ...)"},
        backends=(), changes_graph=False,
        doc="deterministic replica-crash injection (fabric/engine.py): "
            "ServingFabric(fault_plan=...) silently kills the planned "
            "replica at the planned step; the next step's health "
            "probes detect it, the router fences it (mark_failed), and "
            "its in-flight requests re-queue at the FRONT of surviving "
            "replicas via the eviction-resume path — token-bit-equal "
            "recovery (fabric.replica_crash / fabric.migrate).  Off "
            "(None, the default) injects nothing; detection and "
            "migration still guard real probe failures"),
    KnobSpec(
        "wire", off_values=("inproc",), on={"wire": "'tcp'"},
        backends=(), changes_graph=False,
        doc="the transport's socket wire (fabric/transport.py): "
            "HandoffTransport(wire='tcp') sends every KV transfer "
            "through a REAL localhost TCP socket — length-prefixed "
            "frames, per-page CRC32 verify on receive — so connection "
            "reset, partial read and recv timeout are genuine kernel "
            "failure modes feeding the same capped-backoff retry "
            "ladder (fabric.partition / fabric.handoff_retry "
            "reason='reset'), with wasted wire time priced into the "
            "virtual clock as retry_ms.  Off ('inproc', the default) "
            "hands the serialized frames across in-process: no "
            "sockets, no threads, byte-identical payloads — the wire "
            "is a byte codec either way, so tcp is token-bit-equal "
            "too (tests/test_transport.py)"),
    KnobSpec(
        "heartbeat", off_values=(None,),
        on={"heartbeat": "HeartbeatConfig()"},
        backends=(), changes_graph=False,
        doc="sub-step heartbeat crash detection (fabric/leasestore.py "
            "+ fabric/engine.py): ServingFabric(heartbeat=...) makes "
            "every decode replica publish monotonic per-phase "
            "heartbeats (admit/prefill/sample/decode/end, vclock-"
            "stamped) into the fcntl-locked external lease store, and "
            "a watchdog with misses_to_stall hysteresis declares a "
            "replica that stops beating WITH pending work stalled "
            "mid-step (fabric.heartbeat_miss / fabric.heartbeat_stall) "
            "— triggering the same fence+evacuate+adopt migration as "
            "a probed crash, detection latency priced in virtual ms.  "
            "Off (None, the default) installs no heartbeat_fn: zero "
            "engine callbacks, no store file, byte-identical to the "
            "probe-only PR 18 path"),
    KnobSpec(
        "speculate", off_values=(None,),
        on={"speculate": "SpecConfig(draft_tokens=3)"},
        backends=(), changes_graph=False,
        doc="speculative multi-token decoding (serving/speculate.py + "
            "engine.py): ServeConfig(speculate=SpecConfig(...)) drafts "
            "up to draft_tokens continuation tokens per slot from an "
            "n-gram/prompt-lookup index over each request's history "
            "and scores them in ONE k+1-position paged verify forward "
            "(serve.draft / serve.verify spans).  Only CANONICAL "
            "samples are emitted — each draft column is re-sampled "
            "with the per-request fold_in key stream the plain decode "
            "step would have used, so accepted prefixes are token-"
            "bit-equal to non-speculative decode at every temperature/"
            "top-k/top-p arm; KV pages for rejected suffixes roll "
            "back before the causal mask ever exposes them.  Off "
            "(None, the default) never builds the verify jit and "
            "traces the byte-identical decode graph; on is priced by "
            "the planner's verify_tokens axis and morphed off fleet-"
            "wide by the controller under sustained low acceptance "
            "(controller.spec_morph) with zero lost tokens"),
)

SERVING_KNOBS_BY_NAME = {k.name: k for k in SERVING_KNOBS}

#: fields that select among registered execution paths rather than
#: toggling graph content; their safety story is config-time validation
#: (config.py __post_init__) + planner selection tests
SELECTOR_FIELDS = {
    "moe_backend": "execution-path selector (collective / fused / "
                   "ragged / auto); invalid combinations rejected at "
                   "config time, auto resolution covered by "
                   "tests/test_planner.py",
    "serving_mode": "planner pricing-regime selector (None = training "
                    "shape / 'prefill' / 'decode'); only changes which "
                    "path moe_backend='auto' resolves to — the traced "
                    "graph is identical for every value; invalid names "
                    "rejected at config time, decode-mode selection "
                    "covered by tests/test_serving.py",
    "fused_schedule": "fused-kernel FFN-schedule selector (None = auto "
                      "/ 'batched' / 'resident' / 'stream' / 'rowwin'); "
                      "every value computes the same function on a "
                      "different execution geometry — invalid names "
                      "rejected at config time, VMEM-infeasible forced "
                      "schedules raise at launch, cross-schedule "
                      "bit-identity asserted by tests/test_fused.py and "
                      "the planner's per-schedule rows by "
                      "tests/test_planner.py",
}

#: model/job *shape* fields: changing one changes the problem, not a
#: default-off code path, so no identity invariant applies
STRUCTURAL_FIELDS = frozenset({
    "num_experts", "expert_top_k", "hidden_size", "intermediate_size",
    "sequence_len", "mini_batch", "global_batch", "capacity_factor",
    "drop_tokens", "is_training", "hidden_act",
    "num_layers", "moe_frequency", "vocab_size",
    "num_shared_experts", "num_heads", "num_kv_heads", "head_dim",
    "gated_ffn", "router_jitter", "aux_loss_coef", "router_z_loss_coef",
    "rope_theta",
    # the published architecture's own keys: which attention and router
    # the model IS (softmax / mha defaults are every earlier preset's)
    "attention_kind", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim",
    "router_score", "router_bias", "norm_topk_prob",
    "routed_scaling_factor", "first_k_dense", "dense_intermediate_size",
    "n_group", "topk_group", "layer_mixers", "layer_ffns",
    "kda_heads", "kda_head_dim", "kda_conv", "kda_lower_bound",
    "conv_taps", "qk_norm", "use_rope", "norm_eps",
    "ssm_heads", "ssm_head_dim", "ssm_groups", "ssm_state", "ssm_conv",
    "ssm_chunk",
    "expert_first", "experts_held", "intermediate_pad",
    "zero_experts", "mla_rank_scale", "block_length", "mask_token_id",
    "embedding_multiplier", "residual_multiplier", "attention_multiplier",
    "logits_scaling", "tie_embeddings",
    "attn_window", "attn_gate", "part_out_norm",
    "dtype", "param_dtype", "accum_dtype",
    "dp", "ep", "tp", "sp", "pp",
})


def check_knob_coverage(field_names=None) -> list[Violation]:
    """Every MoEConfig field must be classified: structural, selector,
    or a registered knob.  ``field_names`` defaults to the live
    dataclass — tests pass a synthetic list to prove an unclassified
    knob fails the matrix."""
    if field_names is None:
        from flashmoe_tpu.config import MoEConfig

        field_names = [f.name for f in dataclasses.fields(MoEConfig)]
    known = STRUCTURAL_FIELDS | set(SELECTOR_FIELDS) | set(KNOBS_BY_NAME)
    out = []
    for name in field_names:
        if name not in known:
            out.append(Violation(
                "invariants", "knob-coverage", name,
                "MoEConfig field has no registered invariant: add a "
                "KnobSpec row (or classify it in STRUCTURAL_FIELDS / "
                "SELECTOR_FIELDS) in staticcheck/registry.py"))
    for name in sorted((set(KNOBS_BY_NAME) | set(SELECTOR_FIELDS))
                       - set(field_names)):
        out.append(Violation(
            "invariants", "knob-coverage", name,
            "registered knob is not a MoEConfig field (stale registry "
            "row?)"))
    return out
