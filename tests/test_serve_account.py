"""The engine's own account of a step and of a request, and the stage
scopes of the one-chip layer — all on the ``clock=`` seam with a stepped
fake clock: nothing here sleeps or reads the wall clock."""

import gc
import json
import logging

import jax
import jax.numpy as jnp
import pytest

from flashmoe_tpu.config import MoEConfig
from flashmoe_tpu.models.transformer import init_params
from flashmoe_tpu.serving import engine as eng
from flashmoe_tpu.serving.engine import (
    Request, ServeConfig, ServingEngine,
)
from flashmoe_tpu.serving.loadgen import tiny_config
from flashmoe_tpu.utils import telemetry
from flashmoe_tpu.utils.telemetry import (
    SPAN_NAMES, FlightRecorder, Metrics,
)

CFG = tiny_config()
SERVE = ServeConfig(max_batch=4, page_size=8, num_pages=32,
                    max_pages_per_slot=4, ctx_bucket_pages=1,
                    prompt_bucket=8)


#: spans that are no phase: each opens inside the phase named
CHILD_SPANS = {"serve.prefill_feed": "serve.admit",
               "serve.prefill": "serve.admit",
               "serve.logits_put": "serve.admit",
               "serve.retire": "serve.deliver"}


class Ticking:
    """Every read is ``tick`` seconds after the one before."""

    def __init__(self, tick=0.001):
        self.t, self.tick = 0.0, tick

    def __call__(self):
        self.t += self.tick
        return self.t


class Stepped:
    """Stands still until the test moves it."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _prompt(rid, n=8):
    return tuple(int(t) for t in jax.random.randint(
        jax.random.PRNGKey(100 + rid), (n,), 0, CFG.vocab_size))


def _req(rid, n=8, max_new=4, **kw):
    return Request(rid=rid, prompt=_prompt(rid, n), max_new_tokens=max_new,
                   **kw)


def _drive(engine, clock=None, dt=1.0, before_step=None):
    """Step to completion, moving a :class:`Stepped` clock by ``dt``
    after every step; returns the step records."""
    recs = []
    while engine.pending():
        if before_step is not None:
            before_step(engine.step_idx)
        recs.append(engine.step())
        if clock is not None:
            clock.t += dt
    return recs


class _SpanLog:
    """A span listener that writes down enters and exits in order."""

    def __init__(self):
        self.events = []

    def span_enter(self, name):
        self.events.append(("enter", name))
        return name

    def span_exit(self, name, tok):
        self.events.append(("exit", name))


def test_phases_are_registered_sum_to_step_and_nest(params):
    mx, log = Metrics(), _SpanLog()
    engine = ServingEngine(params, CFG, SERVE, metrics_obj=mx,
                           clock=Ticking(), recorder=FlightRecorder())
    telemetry.set_span_listener(log)
    try:
        for rid in range(3):
            engine.submit(_req(rid))
        recs = _drive(engine)
    finally:
        telemetry.set_span_listener(None)
    assert len(recs) >= 4
    seen = set()
    for rec in recs:
        phases = rec["phase_ms"]
        assert set(phases) <= set(SPAN_NAMES), set(phases) - set(SPAN_NAMES)
        assert all(k.startswith("serve.") for k in phases)
        assert rec["t1_s"] > rec["t0_s"]
        assert rec["step_ms"] == pytest.approx(
            (rec["t1_s"] - rec["t0_s"]) * 1e3, abs=2e-3)
        assert sum(phases.values()) == pytest.approx(rec["step_ms"],
                                                     rel=0.01)
        seen |= set(phases)
    # a step that decodes walks every phase of the plain path
    assert seen == {"serve.admit", "serve.prefill_advance",
                    "serve.sample_keys", "serve.sample", "serve.deliver",
                    "serve.grow", "serve.decode_feed", "serve.decode",
                    "serve.account"}
    # each phase feeds the scrape's sketch, once a step it ran in
    for name in seen:
        sk = mx.sketches[f"serve.phase.{name[6:]}_ms"]
        assert sk.n == sum(name in r["phase_ms"] for r in recs)
    assert "flashmoe_serve_phase_sample_keys_ms" in mx.prometheus_text()

    # nesting: one serve.step around everything, the phases one after
    # another inside it, an admission's spans inside serve.admit and a
    # retirement's inside serve.deliver
    # (the layer's own scopes show up too, while a program is traced
    # for its first call: not the engine's, left out here)
    open_names, steps, children = [], 0, []
    for kind, name in log.events:
        if not name.startswith("serve."):
            continue
        if kind == "enter":
            if name == "serve.step":
                assert not open_names
                steps += 1
            elif name in CHILD_SPANS:
                assert open_names == ["serve.step", CHILD_SPANS[name]]
                children.append(name)
            else:
                assert open_names == ["serve.step"], (name, open_names)
            open_names.append(name)
        else:
            assert open_names.pop() == name
    assert not open_names and steps == len(recs)
    # three admissions: feed, program, put, in that order; three retired
    assert children == ["serve.prefill_feed", "serve.prefill",
                        "serve.logits_put"] * 3 + ["serve.retire"] * 3


def test_phase_names_under_speculation(params):
    from flashmoe_tpu.serving.speculate import SpecConfig

    serve = ServeConfig(max_batch=2, page_size=8, num_pages=32,
                        max_pages_per_slot=4, ctx_bucket_pages=1,
                        prompt_bucket=8,
                        speculate=SpecConfig(draft_tokens=2))
    engine = ServingEngine(params, CFG, serve, metrics_obj=Metrics(),
                           clock=Ticking(), recorder=FlightRecorder())
    motif = (5, 9)
    engine.submit(Request(rid=0, prompt=motif * 4, max_new_tokens=8))
    recs = _drive(engine)
    seen = set().union(*(r["phase_ms"] for r in recs))
    assert seen <= set(SPAN_NAMES)
    assert "serve.draft" in seen
    for rec in recs:
        assert sum(rec["phase_ms"].values()) == pytest.approx(
            rec["step_ms"], rel=0.01)
        if "serve.verify" in rec["phase_ms"]:
            assert rec["ctx_pages"] >= 1
    total = sum(n for r in recs for _, n in r["delivered"])
    assert total == len(engine.outputs[0]) - 8


def test_queue_wait_prefill_and_widest_gap_follow_the_clock(params):
    """One slot: the second request waits until the first has retired.
    The clock moves 1 s after every step, and 5 s more before step 2."""
    clock, mx, rec = Stepped(), Metrics(), FlightRecorder()
    serve = ServeConfig(max_batch=1, page_size=8, num_pages=32,
                        max_pages_per_slot=4, ctx_bucket_pages=1,
                        prompt_bucket=8)
    engine = ServingEngine(params, CFG, serve, metrics_obj=mx,
                           recorder=rec, clock=clock)
    engine.submit(_req(0, max_new=4))
    engine.submit(_req(1, max_new=3))

    def hold(step):
        if step == 2:
            clock.t += 5.0

    _drive(engine, clock, before_step=hold)
    reqs = {r["rid"]: r for r in rec.records
            if r.get("kind") == "serve_request"}
    # request 0: admitted in step 0 at t=0, tokens at t = 0, 1, 7, 8
    assert reqs[0]["queue_wait_ms"] == 0.0
    assert reqs[0]["prefill_ms"] == 0.0
    assert reqs[0]["gap_max_ms"] == 6000.0
    # request 1 arrived at t=0 and was admitted in step 4 (t = 9), the
    # step after request 0's last token; its tokens came 1 s apart
    assert reqs[1]["queue_wait_ms"] == 9000.0
    assert reqs[1]["gap_max_ms"] == 1000.0
    retire = {d["rid"]: d for d in mx.decisions
              if d["decision"] == "serve.retire"}
    for rid in (0, 1):
        for k in ("queue_wait_ms", "prefill_ms", "gap_max_ms"):
            assert retire[rid][k] == reqs[rid][k]
    # the sketch is fed once per admission
    assert mx.sketches["serve.queue_wait_ms"].n == 2
    assert mx.sketches["serve.queue_wait_ms"].max == 9000.0


def test_evicted_request_reports_the_sum_of_its_waits(params):
    """Page pressure evicts; the evictee's queue wait is its first wait
    plus every wait between an eviction and the admission after it, as
    the decision stream and the clock (1 s a step) dictate."""
    clock, mx, rec = Stepped(), Metrics(), FlightRecorder()
    serve = ServeConfig(max_batch=8, page_size=8, num_pages=20,
                        max_pages_per_slot=4, ctx_bucket_pages=1,
                        prompt_bucket=8)
    engine = ServingEngine(params, CFG, serve, metrics_obj=mx,
                           recorder=rec, clock=clock)
    arrivals = [0, 0, 0, 0, 1, 1, 2, 3]
    for rid, arr in enumerate(arrivals):
        engine.submit(_req(rid, max_new=10), arr)
    _drive(engine, clock)
    assert engine.stats["evictions"] > 0
    want = {}
    queued_at = {rid: float(arr) for rid, arr in enumerate(arrivals)}
    for d in mx.decisions:
        if d["decision"] == "serve.evict":
            queued_at[d["rid"]] = float(d["step"])
        elif d["decision"] == "serve.admit":
            want[d["rid"]] = want.get(d["rid"], 0.0) + 1e3 * (
                d["step"] - queued_at.pop(d["rid"]))
    reqs = {r["rid"]: r for r in rec.records
            if r.get("kind") == "serve_request"}
    assert len(reqs) == 8
    evicted = {d["rid"] for d in mx.decisions
               if d["decision"] == "serve.evict"}
    for rid, r in reqs.items():
        assert r["queue_wait_ms"] == want[rid], rid
        # an evictee's tokens stand: the gap across its eviction is on
        # its account, so it is wider than one step
        assert (r["gap_max_ms"] > 1000.0) == (rid in evicted), rid
    assert any(reqs[rid]["queue_wait_ms"] > 0 for rid in evicted)


def test_delivered_alone_rebuilds_counts_and_first_token_order(params):
    rec = FlightRecorder()
    engine = ServingEngine(params, CFG, SERVE, metrics_obj=Metrics(),
                           recorder=rec, clock=Ticking())
    for rid, (arr, new) in enumerate([(0, 5), (0, 2), (1, 4), (3, 3),
                                      (3, 6), (4, 2)]):
        engine.submit(_req(rid, max_new=new), arr)
    # the old way, for comparison: read the engine's slots after a step
    first_seen = []
    while engine.pending():
        engine.step()
        for s in engine.slots:
            if s is not None and s.emitted \
                    and s.orig.rid not in first_seen:
                first_seen.append(s.orig.rid)
        for rid in engine.outputs:
            if rid not in first_seen:
                first_seen.append(rid)
    counts, order, t_prev = {}, [], 0.0
    for r in rec.records:
        if r.get("kind") != "serve_step":
            continue
        assert r["t1_s"] > t_prev          # every token has a time
        t_prev = r["t1_s"]
        assert sum(n for _, n in r["delivered"]) == r["tokens"]
        for rid, n in r["delivered"]:
            counts[rid] = counts.get(rid, 0) + n
            if rid not in order:
                order.append(rid)
    assert counts == {rid: len(out) - 8
                      for rid, out in engine.outputs.items()}
    assert sorted(order) == sorted(first_seen)
    # same step, same slot order: the two views agree on who came first
    assert [sorted(g) for g in _by_step(order, rec)] == \
        [sorted(g) for g in _by_step(first_seen, rec)]


def _by_step(order, rec):
    """Group rids by the step their first token came in."""
    first_step = {}
    for r in rec.records:
        if r.get("kind") == "serve_step":
            for rid, _ in r["delivered"]:
                first_step.setdefault(rid, r["step"])
    groups = {}
    for rid in order:
        groups.setdefault(first_step[rid], []).append(rid)
    return [groups[k] for k in sorted(groups)]


def test_step_that_meets_a_new_shape_reports_its_compiles(params):
    """max_batch 3 and a 5-token prompt are shapes no other test uses:
    the first request compiles (pad, prefill, sampler, decode), a repeat
    of it compiles nothing."""
    serve = ServeConfig(max_batch=3, page_size=8, num_pages=16,
                        max_pages_per_slot=2, ctx_bucket_pages=1,
                        prompt_bucket=8)
    engine = ServingEngine(params, CFG, serve, metrics_obj=Metrics(),
                           clock=Ticking())
    engine.submit(_req(0, n=5, max_new=3))
    first = _drive(engine)
    assert first[0]["compiles"] >= 1 and first[0]["compile_ms"] > 0.0
    engine.submit(_req(1, n=5, max_new=3))
    again = _drive(engine)
    assert [r["compiles"] for r in again] == [0] * len(again)
    assert all(r["compile_ms"] == 0.0 for r in again)
    count, seconds = telemetry.compile_totals()
    assert count >= first[0]["compiles"] and seconds > 0.0


def test_token_streams_do_not_depend_on_the_recorder(params):
    outs = []
    for recorder in (None, FlightRecorder()):
        engine = ServingEngine(params, CFG, SERVE, metrics_obj=Metrics(),
                               recorder=recorder, clock=Ticking())
        outs.append(engine.run(
            [_req(rid, max_new=6, temperature=0.7 * (rid % 2), seed=rid)
             for rid in range(6)], [0, 0, 1, 1, 2, 5]))
    assert outs[0] == outs[1]


#: what PR 35 put on EVERY ``serve_step`` record (a reader that means over
#: records of a kind indexes the field)
ACCOUNT = ("t0_trace_ns", "wait_ms", "host_ms", "between_ms", "cpu_ms",
           "between_cpu_ms", "ctx_switches", "page_faults", "gc_n", "gc_ms",
           "starved", "starved_at", "held_slots", "prefill_programs",
           "prefill_tokens", "prefill_rows", "admitted", "retired",
           "compiles", "compile_ms")


@pytest.mark.parametrize("chunk", [None, 16], ids=["whole", "chunked"])
def test_every_step_record_carries_the_host_account(params, chunk):
    """Prompts of 5 to 40 tokens, whole (padded to buckets of 8) or in
    chunks of 16: every record has every field, the wait and the host's
    time add up to the step, the phases still do, and the prefill
    programs' records add up to the prompts."""
    serve = ServeConfig(max_batch=3, page_size=8, num_pages=40,
                        max_pages_per_slot=8, ctx_bucket_pages=2,
                        prompt_bucket=8, prefill_chunk=chunk)
    mx, rec = Metrics(), FlightRecorder()
    engine = ServingEngine(params, CFG, serve, metrics_obj=mx,
                           clock=Ticking(), recorder=rec)
    lengths = [5, 40, 21, 33, 8, 17]
    for rid, n in enumerate(lengths):
        engine.submit(_req(rid, n=n, max_new=3 + rid), rid // 2)
    recs = _drive(engine)
    steps = [r for r in rec.records if r["kind"] == "serve_step"]
    assert steps == recs
    t1_before = None
    for r in recs:
        assert all(k in r for k in ACCOUNT), set(ACCOUNT) - set(r)
        assert r["host_ms"] + r["wait_ms"] == pytest.approx(
            r["step_ms"], abs=2e-3)
        assert 0.0 <= r["wait_ms"] <= r["phase_ms"].get("serve.sample", 0.0)
        assert sum(r["phase_ms"].values()) == pytest.approx(
            r["step_ms"], rel=0.01)
        assert r["between_ms"] == pytest.approx(
            0.0 if t1_before is None else (r["t0_s"] - t1_before) * 1e3,
            abs=2e-3)
        t1_before = r["t1_s"]
        assert r["t0_trace_ns"] > 1_500_000_000 * 10**9    # a wall clock
        assert (r["starved_at"] is None) == (r["starved"] == 0)
        assert r["held_slots"] in (0, r["sample_rows"])
    # one serve_held record a step, the reader's: the same numbers
    held = [r for r in rec.records if r["kind"] == "serve_held"]
    assert [(h["step"], h["held_slots"], h["starved"]) for h in held] == [
        (r["step"], r["held_slots"], r["starved"]) for r in recs]
    # one serve_prefill record a program
    pre = [r for r in rec.records if r["kind"] == "serve_prefill"]
    by_rid = {}
    for p in pre:
        by_rid.setdefault(p["rid"], []).append(p)
        assert p["pad_rows"] == p["rows"] - p["tokens"] >= 0
        assert p["host_ms"] > 0.0 and isinstance(p["starved"], bool)
        if p["form"] == "chunk":
            assert p["rows"] == 16 and p["pos"] % 16 == 0
        else:
            assert p["rows"] % 8 == 0 and p["pos"] == 0 \
                and p["pad_rows"] < 8
    assert {rid: sum(p["tokens"] for p in ps)
            for rid, ps in by_rid.items()} == dict(enumerate(lengths))
    forms = {p["form"] for p in pre}
    assert forms == ({"whole", "chunk"} if chunk else {"whole"})
    by_step = {r["step"]: r for r in recs}
    for step, r in by_step.items():
        mine = [p for p in pre if p["step"] == step]
        assert (r["prefill_programs"], r["prefill_tokens"],
                r["prefill_rows"]) == (
            len(mine), sum(p["tokens"] for p in mine),
            sum(p["rows"] for p in mine))
    assert mx.counters["serve.prefill_programs"] == len(pre)
    # the CPU keeps the plain XLA attention in every prefill program
    assert {p["attn_arm"] for p in pre} == {"xla"}
    assert mx.counters.get("serve.prefill_flash_programs", 0) == 0
    assert mx.counters["serve.prefill_tokens"] == sum(lengths)
    assert mx.counters["serve.prefill_rows"] == sum(p["rows"] for p in pre)
    assert mx.sketches["serve.host_ms"].n == len(recs)
    assert mx.sketches["serve.between_ms"].n == len(recs) - 1
    assert mx.counters.get("serve.held_steps", 0) == sum(
        r["starved"] > 0 for r in recs)
    assert mx.counters.get("serve.starved_dispatches", 0) == sum(
        r["starved"] for r in recs)


@pytest.mark.parametrize("model", ["kv", "latent_beside_state"])
def test_prefill_records_say_which_arm_the_attention_took(monkeypatch,
                                                          model):
    """``attn_arm`` on every ``serve_prefill`` record is what
    ``ops/attention.span_attention_arm`` answers for the program's rows
    and context (the rule the traced program asked), and the counter
    ``serve.prefill_flash_programs`` counts once a program that took the
    kernel: 0 on the CPU; with the arm forced (the kernel in
    ``interpret``), whole prompts and chunks of a K/V toy and of one with
    a latent layer beside recurrent state serve the plain arm's tokens,
    and every prefill program is counted."""
    from flashmoe_tpu.models.presets import PRESETS
    from flashmoe_tpu.ops import attention

    if model == "kv":
        cfg = tiny_config(vocab=247)
    else:
        cfg = PRESETS["ling-3.0-flash"](
            num_layers=3, layer_mixers=("kda", "kda", "mla"), first_k_dense=1,
            num_experts=16, expert_top_k=3, n_group=4, topk_group=2,
            kda_heads=3, kda_head_dim=16, hidden_size=64,
            intermediate_size=64, dense_intermediate_size=128,
            vocab_size=247, num_heads=3, kv_lora_rank=20,
            qk_nope_head_dim=10, qk_rope_head_dim=6, v_head_dim=14,
            dtype=jnp.float32, param_dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    reqs = [Request(rid=i, prompt=tuple(int(t) for t in jax.random.randint(
        jax.random.PRNGKey(50 + i), (n,), 1, 247)), max_new_tokens=5)
        for i, n in enumerate((5, 37, 9))]
    serve = ServeConfig(max_batch=4, page_size=8, num_pages=40,
                        max_pages_per_slot=6, ctx_bucket_pages=2,
                        prompt_bucket=8, prefill_chunk=16)
    programs = [eng._prefill_padded, *eng._INPLACE.values()]
    asked = []

    def run():
        for program in programs:
            program.clear_cache()       # trace with the arm of the moment
        recorder, metrics = FlightRecorder(), Metrics()
        engine = ServingEngine(params, cfg, serve, recorder=recorder,
                               metrics_obj=metrics)
        out = engine.run(reqs, arrivals=[0, 0, 1])
        pre = [r for r in recorder.records if r["kind"] == "serve_prefill"]
        return out, pre, metrics.counters.get(
            "serve.prefill_flash_programs", 0)

    want, pre, counted = run()
    assert {p["attn_arm"] for p in pre} == {"xla"} and counted == 0

    def forced(t, s, heads, k_widths, v_width, dtype):
        assert heads == cfg.num_heads
        asked.append((t, s, k_widths, v_width))
        return "flash"

    try:
        monkeypatch.setattr(attention, "span_attention_arm", forced)
        got, pre, counted = run()
    finally:
        monkeypatch.undo()
        for program in programs:
            program.clear_cache()
    assert got == want
    assert {p["attn_arm"] for p in pre} == {"flash"}
    assert {p["form"] for p in pre} == {"whole", "chunk"}
    assert counted == len(pre) >= 5             # a prompt in three chunks
    # the records asked what the traced programs asked: a whole prompt
    # over itself, a chunk over its bucket of pages, this model's widths
    widths = attention.attention_widths(cfg)
    assert widths == (((32,), 32) if model == "kv" else ((10, 6), 14))
    assert {a[2:] for a in asked} == {widths}
    assert {(8, 8), (16, 16), (16, 32), (16, 48)} <= {a[:2] for a in asked}


@pytest.mark.parametrize("chunks", [1, 4], ids=["one_chunk", "four_chunks"])
def test_records_say_in_how_many_chunks_the_experts_walk(monkeypatch, chunks):
    """``expert_chunks`` on every ``serve_decode`` and ``serve_prefill``
    record is what ``ops/moe.expert_chunks`` answers for the program's
    rows (``ops/expert._ffn_chunks`` on the shapes the launch is handed),
    and ``serve.expert_chunked_programs`` counts a program that walks more
    than one: ``None`` and 0 on the plain arms (the CPU's); with the
    grouped kernel forced (in ``interpret``) 1 under the kernel's own
    VMEM ceiling and 4 under one that holds a quarter of this toy's
    expert, where whole prompts, chunks and decode steps, dead tiles
    behind the live ones in every launch, serve the plain arm's tokens
    (ISSUE 48)."""
    import os

    from flashmoe_tpu.ops import expert as exp
    from flashmoe_tpu.ops import moe

    cfg = tiny_config(vocab=247)
    params = init_params(jax.random.PRNGKey(0), cfg)
    reqs = [Request(rid=i, prompt=tuple(int(t) for t in jax.random.randint(
        jax.random.PRNGKey(60 + i), (n,), 1, 247)), max_new_tokens=5)
        for i, n in enumerate((5, 21, 9))]
    serve = ServeConfig(max_batch=4, page_size=8, num_pages=32,
                        max_pages_per_slot=4, ctx_bucket_pages=4,
                        prompt_bucket=8, prefill_chunk=16)
    programs = [eng._prefill_padded, *eng._INPLACE.values(),
                moe._rows_kernel_ffn, moe._rows_kernel_waves,
                exp.grouped_ffn]

    def run():
        for program in programs:
            program.clear_cache()       # trace under the patches of now
        recorder, metrics = FlightRecorder(), Metrics()
        engine = ServingEngine(params, cfg, serve, recorder=recorder,
                               metrics_obj=metrics)
        out = engine.run(reqs, arrivals=[0, 0, 1])
        seen = {kind: [r["expert_chunks"] for r in recorder.records
                       if r["kind"] == kind]
                for kind in ("serve_decode", "serve_prefill")}
        return out, seen, metrics.counters

    want, seen, counters = run()
    assert set(seen["serve_decode"]) == set(seen["serve_prefill"]) == {None}
    assert "serve.expert_chunked_programs" not in counters
    try:
        monkeypatch.setattr(moe, "routed_rows_form",
                            lambda c: "routed_kernel")
        if chunks > 1:
            # a 16-row float32 tile and a QUARTER of an ungated expert of
            # 64 x 128: the walk is four chunks of 32 columns
            monkeypatch.setattr(exp, "_VMEM_CEILING",
                                exp._ffn_vmem(16, 64, 32, False, 4, 4))
        got, seen, counters = run()
    finally:
        monkeypatch.undo()
        for program in programs:
            program.clear_cache()
    assert got == want
    assert set(seen["serve_decode"]) == set(seen["serve_prefill"]) == {chunks}
    assert len(seen["serve_prefill"]) >= 4       # a prompt in two chunks
    launched = len(seen["serve_decode"]) + len(seen["serve_prefill"])
    assert counters["serve.expert_kernel_programs"] == launched
    assert counters.get("serve.expert_chunked_programs", 0) == (
        launched if chunks > 1 else 0)
    docs = os.path.join(os.path.dirname(__file__), "..", "docs")
    for doc in ("OBSERVABILITY.md", "SERVING.md"):
        text = open(os.path.join(docs, doc)).read()
        assert "`expert_chunks`" in text
        assert "`serve.expert_chunked_programs`" in text


def _long_run(params, clock, mx, rec, new=40):
    serve = ServeConfig(max_batch=1, page_size=8, num_pages=32,
                        max_pages_per_slot=8, ctx_bucket_pages=8,
                        prompt_bucket=8)
    engine = ServingEngine(params, CFG, serve, metrics_obj=mx,
                           recorder=rec, clock=clock)
    engine.submit(_req(0, max_new=new))
    return engine


def test_a_step_forty_times_its_median_leaves_one_stall(params, caplog):
    """The clock stands still inside a step and moves 1 s between steps;
    before step 20 it moves 40 s: ONE ``serve_stall`` record, one count,
    one entry of ``stats["stalls"]`` (the worst too), one WARNING, and
    the record says where the time went: not in the step."""
    clock, mx, rec = Stepped(), Metrics(), FlightRecorder()
    engine = _long_run(params, clock, mx, rec)

    def hold(step):
        if step == 20:
            clock.t += 39.0

    with caplog.at_level(logging.WARNING, logger="flashmoe_tpu.serving"):
        recs = _drive(engine, clock, before_step=hold)
    assert len(recs) == 40
    stalls = [r for r in rec.records if r["kind"] == "serve_stall"]
    assert len(stalls) == 1
    st = stalls[0]
    assert st["step"] == 20 and st["between_ms"] == 40000.0
    assert st["host_ms"] == 0.0 and st["median_ms"] == 1000.0
    assert st["phase_ms"] == recs[20]["phase_ms"] and st["phase"] in st[
        "phase_ms"]
    for k in ("wait_ms", "cpu_ms", "between_cpu_ms", "ctx_switches",
              "page_faults", "gc_n", "gc_ms", "compiles", "compile_ms",
              "prefill_programs", "admitted", "retired", "t0_trace_ns"):
        assert st[k] == recs[20][k], k
    assert (st["admitted"], st["retired"]) == (0, 0)
    assert [(r["admitted"], r["retired"]) for r in recs] == [
        (1, 0)] + [(0, 0)] * 38 + [(0, 1)]
    assert mx.counters["serve.stall_steps"] == 1
    assert mx.counters["serve.stall_ms"] == 39000.0
    assert list(engine.stats["stalls"]) == [st]
    assert engine.stats["worst_stall"] == st
    assert engine.summary()["stalls"] == [st]
    lines = [r for r in caplog.records if r.name == "flashmoe_tpu.serving"]
    assert len(lines) == 1 and lines[0].levelno == logging.WARNING
    said = lines[0].getMessage()
    assert said.startswith("serve_stall {")
    assert json.loads(said[len("serve_stall "):]) == {
        k: v for k, v in st.items() if k not in ("kind", "phase_ms")}


def test_a_long_step_under_five_milliseconds_is_no_stall(params):
    """0.1 ms between steps and 4.1 ms before step 20: forty times the
    median, under the floor.  6 ms: a stall, and stalls a second apart on
    the engine's clock are each logged, closer ones are not."""
    clock, mx, rec = Stepped(), Metrics(), FlightRecorder()
    engine = _long_run(params, clock, mx, rec)

    def hold(step):
        if step == 20:
            clock.t += 0.004
        elif step in (25, 26):
            clock.t += 0.006

    _drive(engine, clock, dt=0.0001, before_step=hold)
    stalls = [r["step"] for r in rec.records if r["kind"] == "serve_stall"]
    assert stalls == [25, 26]
    assert mx.counters["serve.stall_steps"] == 2
    assert engine.stats["worst_stall"]["step"] == 25
    assert eng._STALL_FLOOR_MS == 5.0 and eng._STALL_FACTOR == 4.0


def test_watch_gc_installs_one_listener_and_a_step_counts_a_collection(
        params):
    def listeners():
        return sum("watch_gc" in getattr(cb, "__qualname__", "")
                   for cb in gc.callbacks)

    telemetry.watch_gc()
    telemetry.watch_gc()
    assert listeners() == 1
    n0, s0 = telemetry.gc_totals()
    gc.collect()
    n1, s1 = telemetry.gc_totals()
    assert n1 == n0 + 1 and s1 > s0
    engine = ServingEngine(params, CFG, SERVE, metrics_obj=Metrics(),
                           clock=Ticking())
    assert listeners() == 1
    engine.submit(_req(0, max_new=6))
    engine.step()
    forced = engine._deliver

    def deliver(*a):
        gc.collect()
        return forced(*a)

    engine._deliver = deliver
    rec = engine.step()
    assert rec["gc_n"] >= 1 and rec["gc_ms"] > 0.0
    del engine._deliver
    gc.disable()
    try:
        rec = engine.step()
    finally:
        gc.enable()
    assert (rec["gc_n"], rec["gc_ms"]) == (0, 0.0)


class _Output:
    """Stands for the output of the program issued last."""

    def __init__(self, ready):
        self.ready = ready

    def is_ready(self):
        return self.ready


@pytest.mark.parametrize("ready", [False, True], ids=["busy", "empty"])
def test_held_slots_are_the_decoding_slots_of_a_step_that_starved(
        params, ready):
    """Every dispatch asks the output of the program issued last whether it
    is ready.  A busy queue: nothing starved, no slot held.  An empty one:
    every dispatch starved, the first of them the step's first program, and
    the held slots are the slots the sampler served."""

    class Engine(ServingEngine):
        _last_out = property(lambda self: _Output(ready),
                             lambda self, out: None)

    mx = Metrics()
    engine = Engine(params, CFG, SERVE, metrics_obj=mx, clock=Ticking())
    for rid in range(3):
        engine.submit(_req(rid, max_new=5), rid)
    recs = _drive(engine)
    assert engine.outputs == ServingEngine(
        params, CFG, SERVE, metrics_obj=Metrics()).run(
        [_req(rid, max_new=5) for rid in range(3)], [0, 1, 2])
    if not ready:
        assert all((r["starved"], r["starved_at"], r["held_slots"])
                   == (0, None, 0) for r in recs)
        assert "serve.held_steps" not in mx.counters
        return
    for r in recs:
        # a prefill, the sampler, the decode step where the step ran them
        want = r["prefill_programs"] + bool(r["sample_rows"]) \
            + bool(r["ctx_pages"])
        assert r["starved"] == want >= 1
        assert r["starved_at"] == ("serve.prefill" if r["prefill_programs"]
                                   else "serve.sample")
        assert r["held_slots"] == r["sample_rows"]
    assert mx.counters["serve.held_steps"] == len(recs)


def test_device_gaps_find_their_step_span_and_prefill_by_hand():
    """``observe.gaps_report`` on a hand-made trace, times in ms on one
    clock: two steps (10-14, 15-19), device busy 10-11, 11.5-14.2, 14.5-15.2
    and 18-19: gaps of 0.5 (inside step 0, the sampler's phase), 0.3
    (after step 0 ended: BEFORE step 1, in the caller's span), 2.8 (in
    step 1, under a chunk's feed, the prefill record beside it) and none
    of the 0.05 ms one."""
    from flashmoe_tpu import observe

    ms = 1_000_000
    base = 5 * ms
    steps = [
        {"kind": "serve_step", "step": 0, "t0_trace_ns": 10 * ms,
         "step_ms": 4.0, "host_ms": 1.0, "between_ms": 0.0, "cpu_ms": 1.0,
         "gc_ms": 0.0, "ctx_switches": 0},
        {"kind": "serve_step", "step": 1, "t0_trace_ns": 15 * ms,
         "step_ms": 4.0, "host_ms": 3.5, "between_ms": 1.0, "cpu_ms": 3.0,
         "gc_ms": 0.25, "ctx_switches": 2},
        {"kind": "serve_prefill", "step": 1, "rid": 7, "slot": 1,
         "form": "chunk", "pos": 16, "tokens": 9, "rows": 16,
         "host_ms": 2.5, "t0_trace_ns": int(15.1 * ms)},
        {"kind": "serve_step", "step": 2},        # an older program's
    ]
    spans = sorted([
        (10 * ms, 4 * ms, "serve.step", 0),
        (10 * ms + 40_000, 2 * ms, "serve.sample", None),
        (14 * ms, 1 * ms, "bench.observe", None),
        (15 * ms - 30_000, 4 * ms, "serve.step", 1),
        (15 * ms, 3 * ms, "serve.prefill_advance", None),
        (int(15.1 * ms), 2 * ms, "serve.chunk_feed", None),
    ])
    trace = {"file": "by hand", "base": base, "spans": spans, "busy": [
        (10 * ms, 1 * ms), (int(11.5 * ms), int(2.7 * ms)),
        (int(14.5 * ms), int(0.7 * ms)), (18 * ms, int(0.95 * ms)),
        (19 * ms, 1 * ms)]}
    rep = observe.gaps_report(trace, steps)
    rows = rep["gaps"]
    assert [round(r["gap_ms"], 3) for r in rows] == [0.5, 0.3, 2.8]
    assert [(r["step"], r["where"]) for r in rows] == [
        (0, "in"), (1, "before"), (1, "in")]
    assert [r["spans"] for r in rows] == [
        ["serve.step", "serve.sample"], ["bench.observe"],
        ["serve.step", "serve.prefill_advance", "serve.chunk_feed"]]
    assert [r["prefill"] and r["prefill"]["rid"] for r in rows] == [
        None, None, 7]
    assert rows[2]["between_ms"] == 1.0 and rows[2]["ctx_switches"] == 2
    assert rows[0]["at_ms"] == 6.0
    assert rep["idle_ms"] == pytest.approx(3.65)
    assert rep["gaps_ms"] == rep["named_ms"] == pytest.approx(3.6)
    assert rep["clock_skew_ms"] == pytest.approx(0.03) and rep["steps"] == 2
    # the idle time under the INNERMOST span open when each gap began,
    # largest first: every gap with a span has one, so it names at least
    # what ``named_ms`` does
    assert list(rep["by_span"].items()) == [
        ("serve.chunk_feed", pytest.approx(2.8)),
        ("serve.sample", pytest.approx(0.5)),
        ("bench.observe", pytest.approx(0.3))]
    assert sum(rep["by_span"].values()) == pytest.approx(rep["named_ms"])
    text = observe.render_gaps_text(rep)
    assert "serve.chunk_feed 2.800, serve.sample 0.500" in text
    assert "98.6 % of the idle time" in text
    assert "serve.step > serve.prefill_advance > serve.chunk_feed" in text
    assert "[chunk rid 7 pos 16: 9 tokens in 16 rows" in text


def test_a_profiled_run_puts_records_and_trace_on_one_clock(
        params, tmp_path, capsys):
    """The engine under ``jax.profiler`` on the CPU (no device plane: no
    gaps to list): every ``serve.step`` event carries its step as a stat
    under the name it had, and starts where its record's ``t0_trace_ns``
    says, on the same clock; the command line finds the trace and the
    records."""
    from flashmoe_tpu import observe

    rec = FlightRecorder()
    engine = ServingEngine(params, CFG, SERVE, metrics_obj=Metrics(),
                           recorder=rec)
    engine.submit(_req(0, max_new=3))
    engine.run()                         # compiled before the trace
    for rid in (1, 2):
        engine.submit(_req(rid, max_new=4))
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        recs = _drive(engine)
    finally:
        jax.profiler.stop_trace()
    trace = observe.read_gaps_trace(str(tmp_path / "trace"))
    stepped = [s for s in trace["spans"] if s[2] == "serve.step"]
    assert [s[3] for s in stepped] == [r["step"] for r in recs]
    names = {s[2] for s in trace["spans"]}
    assert {"serve.prefill_feed", "serve.logits_put", "serve.retire",
            "serve.sample"} <= names
    rep = observe.gaps_report(trace, rec.records)
    assert rep["steps"] == sum(r["kind"] == "serve_step"
                               for r in rec.records) > len(recs)
    # ONE clock, not a tight one: ``t0_trace_ns`` is ``time.time_ns()`` read
    # a few Python statements before the ``serve.step`` annotation opens,
    # 0.10-0.14 ms apart on the chip's host (PERF.md section 6, PR 35) and
    # under a millisecond here when the worker has a core to itself.  The
    # driver runs six workers beside the profiler's own threads on eight
    # cores, and a worker descheduled between the two reads waits a
    # scheduler quantum or several (it read 1-3 ms there and failed a 1 ms
    # bound).  Two clocks that are NOT one (the epoch's against a monotonic
    # one) lie some 1.7e12 ms apart, so 250 ms of room still proves the
    # point the test makes
    assert 0.0 < rep["clock_skew_ms"] < 250.0
    path = tmp_path / "flight.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in rec.records))
    assert observe.main(["--gaps", str(tmp_path / "trace"), str(path)]) == 2
    assert "device gaps over the threshold: 0" in capsys.readouterr().out
    assert observe.main(["--gaps", str(tmp_path / "none"), str(path)]) == 2
    # no TPU's plane: nothing for ``--device`` to split, and it says so
    assert trace["device"] is None and trace["modules"] == []
    assert observe.device_report(trace, rec.records) is None
    assert observe.main(["--device", str(tmp_path / "trace"), str(path)]) == 2
    assert "no .xplane.pb with a TPU's plane" in capsys.readouterr().err
    # but the trace holds the HLO of every program that ran under it, in
    # the plane ``ProfileData`` does not list (``/host:metadata``), under
    # the name the ``XLA Modules`` line would give its executions: the
    # decode step's instructions find their scopes from THAT text
    progs = observe.read_gaps_trace(str(tmp_path / "trace"),
                                    programs=True)["programs"]
    # (every program the process holds compiled when the trace stops:
    # other tests' engines may have left decode steps of other shapes)
    decode = [name for name in progs
              if name.startswith("jit__paged_decode_step(")]
    assert decode and all(name.endswith(")") for name in decode)
    assert any({"attn.kv", "lm.head", "lm.embed"}
               <= {scope for scope, _, _ in progs[name].values()}
               for name in decode)
    sampler = next(v for k, v in progs.items()
                   if k.startswith("jit__sample_dynamic("))
    assert {scope for scope, _, _ in sampler.values()} >= {"lm.sample"}


def test_ctx_pages_by_hand_on_a_two_slot_batch(params):
    """Pages of 4 tokens, buckets of 2 pages.  Prompts of 4 and 12
    tokens: the first decode writes positions 4 and 12, so the longest
    context is 13 tokens = 4 pages, a whole bucket count already; the
    slots' own contexts fill 2 and 4 pages, mean 3: one page a slot is
    gathered and masked."""
    rec = FlightRecorder()
    serve = ServeConfig(max_batch=2, page_size=4, num_pages=32,
                        max_pages_per_slot=8, ctx_bucket_pages=2,
                        prompt_bucket=4)
    engine = ServingEngine(params, CFG, serve, metrics_obj=Metrics(),
                           recorder=rec, clock=Ticking())
    engine.submit(_req(0, n=4, max_new=6))
    engine.submit(_req(1, n=12, max_new=2))
    recs = _drive(engine)
    assert (recs[0]["ctx_pages"], recs[0]["ctx_pages_idle"]) == (4, 1.0)
    # step 1: request 1 retires with its second token before the decode;
    # request 0 alone writes position 5: 2 pages, none idle
    assert (recs[1]["ctx_pages"], recs[1]["ctx_pages_idle"]) == (2, 0.0)
    # step 4: request 0 writes position 8, its third page: bucket of 4
    assert (recs[4]["ctx_pages"], recs[4]["ctx_pages_idle"]) == (4, 1.0)
    # the last step samples the last token and runs no decode
    assert (recs[-1]["ctx_pages"], recs[-1]["ctx_pages_idle"]) == (0, 0.0)
    assert all("ctx_pages" in r and "ctx_pages_idle" in r for r in recs)
    decodes = [r for r in rec.records if r.get("kind") == "serve_decode"]
    assert [(d["step"], d["slots"], d["ctx_pages"], d["ctx_pages_idle"])
            for d in decodes] == [
        (r["step"], 2 if r["step"] == 0 else 1, r["ctx_pages"],
         r["ctx_pages_idle"]) for r in recs if r["ctx_pages"]]


def test_one_chip_layer_carries_the_stage_scopes():
    """The lowered one-chip ``moe_layer`` (XLA path) names the paper's
    four stages, and the shared experts, in its operations' op_name."""
    p, x, layer = _one_chip_layer()
    text = jax.jit(layer).lower(p, x).compile().as_text()
    for stage in ("moe.gate", "moe.dispatch", "moe.expert", "moe.combine",
                  "moe.shared"):
        assert f"/{stage}/" in text, stage


def _one_chip_layer():
    from flashmoe_tpu.models.reference import init_moe_params
    from flashmoe_tpu.ops.moe import moe_layer

    cfg = MoEConfig(num_experts=4, expert_top_k=2, hidden_size=64,
                    intermediate_size=128, sequence_len=32,
                    num_shared_experts=1, drop_tokens=False,
                    dtype=jnp.float32, param_dtype=jnp.float32)
    p = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jnp.ones((32, 64), jnp.float32)
    return p, x, lambda p, x: moe_layer(p, x, cfg, use_pallas=False).out


def test_program_scopes_of_the_one_chip_layer_forward_and_backward():
    """``telemetry.program_scopes`` on what the compiler built of the
    one-chip ``moe_layer``: every stage is some instruction's scope, all
    ``fwd``; under ``jax.grad`` the stages come back as ``bwd`` too (jax
    marks a backward's operations ``transpose(...)``)."""
    p, x, layer = _one_chip_layer()
    stages = {"moe.gate", "moe.dispatch", "moe.expert", "moe.combine",
              "moe.shared"}
    fwd = telemetry.program_scopes(
        jax.jit(layer).lower(p, x).compile().as_text())
    assert {scope for scope, _, _ in fwd.values()} >= stages
    assert {way for _, way, _ in fwd.values()} == {"fwd"}
    assert {kernel for _, _, kernel in fwd.values()} == {None}
    grad = telemetry.program_scopes(jax.jit(jax.grad(
        lambda p, x: jnp.sum(layer(p, x)))).lower(p, x).compile().as_text())
    both = {(scope, way) for scope, way, _ in grad.values()}
    assert ("moe.expert", "bwd") in both and ("moe.expert", "fwd") in both
    assert ("moe.gate", "bwd") in both


_HLO_BY_HAND = """HloModule jit_f, is_scheduled=true, entry_computation_layout={(f32[8,128]{1,0})->f32[8,128]{1,0}}

%fused_computation.1 (p0: f32[8,128]) -> f32[8,128] {
  %p0 = f32[8,128]{1,0} parameter(0)
  %a.1 = f32[8,128]{1,0} add(%p0, %p0), metadata={op_name="jit(f)/moe.gate/add"}
  %a.2 = f32[8,128]{1,0} multiply(%a.1, %a.1), metadata={op_name="jit(f)/moe.gate/mul"}
  ROOT %a.3 = f32[8,128]{1,0} negate(%a.2), metadata={op_name="jit(f)/moe.expert/neg"}
}

%fused_computation.2 (p1: f32[8,128]) -> f32[8,128] {
  %p1 = f32[8,128]{1,0} parameter(0)
  ROOT %b.1 = f32[8,128]{1,0} exponential(%p1), metadata={op_name="jit(f)/exp"}
}

%body.5 (t: (s32[], f32[8,128])) -> (s32[], f32[8,128]) {
  %t = (s32[], f32[8,128]{1,0}) parameter(0)
  %g.1 = f32[8,128]{1,0} get-tuple-element(%t), index=1
  %fm_ffn_fwd.13 = f32[8,128]{1,0} custom-call(%g.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/moe.expert.3/while/body/fm_ffn_fwd/pallas_call"}
  %g.0 = s32[] get-tuple-element(%t), index=0
  ROOT %tuple.7 = (s32[], f32[8,128]{1,0}) tuple(%g.0, %fm_ffn_fwd.13)
}

%cond.6 (t.1: (s32[], f32[8,128])) -> pred[] {
  %t.1 = (s32[], f32[8,128]{1,0}) parameter(0)
  %g.2 = s32[] get-tuple-element(%t.1), index=0
  %c.9 = s32[] constant(4)
  ROOT %lt.1 = pred[] compare(%g.2, %c.9), direction=LT, metadata={op_name="jit(f)/moe.expert.3/while/cond/lt"}
}

ENTRY %main.9 (x: f32[8,128]) -> f32[8,128] {
  %x = f32[8,128]{1,0:T(8,128)} parameter(0), metadata={op_name="x"}
  %copy-start.1 = (f32[8,128]{1,0:T(8,128)S(1)}, f32[8,128]{1,0:T(8,128)}, u32[]{:S(2)}) copy-start(%x)
  %copy-done.1 = f32[8,128]{1,0:T(8,128)S(1)} copy-done(%copy-start.1)
  %fusion.1 = f32[8,128]{1,0} fusion(%copy-done.1), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = f32[8,128]{1,0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(f)/exp"}
  %c.0 = s32[] constant(0)
  %tuple.1 = (s32[], f32[8,128]{1,0}) tuple(%c.0, %fusion.2)
  %while.4 = (s32[], f32[8,128]{1,0}) while(%tuple.1), condition=%cond.6, body=%body.5, metadata={op_name="jit(f)/moe.expert.3/while"}
  %g.9 = f32[8,128]{1,0} get-tuple-element(%while.4), index=1
  %dot.3 = f32[8,128]{1,0} multiply(%g.9, %g.9), metadata={op_name="jit(f)/train.forward_backward/transpose(jvp(moe.combine))/mul"}
  ROOT %add.8 = f32[8,128]{1,0} add(%dot.3, %x), metadata={op_name="jit(f)/jit(helper)/add"}
}
"""


@pytest.mark.parametrize("instruction, want", [
    # no metadata of its own: two of its three fused instructions
    ("fusion.1", ("moe.gate", "fwd", None)),
    # its own path names no registered span, nor do its fused ones
    ("fusion.2", (None, "fwd", None)),
    # the compiler's own (no ``op_name``: a weight's prefetch): what
    # READS it says whose time it is, through the pair's other half
    ("copy-done.1", ("moe.gate", "fwd", None)),
    ("copy-start.1", ("moe.gate", "fwd", None)),
    # a chunk's suffix folds onto the registered base
    ("while.4", ("moe.expert", "fwd", None)),
    # a ``while``'s body and condition are on the ``XLA Ops`` line
    ("fm_ffn_fwd.13", ("moe.expert", "fwd", "fm_ffn_fwd")),
    ("lt.1", ("moe.expert", "fwd", None)),
    # the INNERMOST registered name, and jax's mark of a backward
    ("dot.3", ("moe.combine", "bwd", None)),
    # a path of the program with no registered span stays unscoped,
    # whoever reads it
    ("add.8", (None, "fwd", None)),
    # a fused computation's instructions are NOT on the line
    ("a.1", None),
])
def test_program_scopes_by_hand(instruction, want):
    assert telemetry.program_scopes(_HLO_BY_HAND).get(instruction) == want


def _device_trace_by_hand():
    """Times in units of 10 us.  ``jit_f`` ran at two shapes (two
    fingerprints): (11) three times, the first clipped by the trace's
    start (one operation of its four), (22) once; ``jit_g(33)`` once, with
    an instruction its program does not hold.  Both ``jit_f``s and
    ``jit_g`` own a ``%fusion.1``.  A ``while`` of 600 us encloses a
    kernel of 400: 200 its own."""
    u = 10_000
    f11 = {"fusion.1": ("moe.gate", "fwd", None),
           "while.2": ("moe.expert", "fwd", None),
           "fm_ffn_fwd.3": ("moe.expert", "fwd", "fm_ffn_fwd"),
           "copy.4": (None, "fwd", None)}
    f22 = {"fusion.1": ("moe.combine", "fwd", None),
           "fm_ffn_fwd.3": ("moe.expert", "fwd", "fm_ffn_fwd")}
    g33 = {"fusion.1": ("lm.head", "fwd", None)}
    fusion = "%fusion.1 = f32[8,128]{1,0:T(8,128)} fusion(f32[8,128] %p)"
    loop = ("%while.2 = (s32[]{:T(128)}, f32[8,128]{1,0}) while((s32[], "
            "f32[8,128]) %tuple.1), condition=%c, body=%b")
    kernel = "%fm_ffn_fwd.3 = f32[8,128]{1,0} custom-call(f32[8,128] %g)"
    copy = "%copy.4 = f32[8]{0:T(128)} copy(f32[8]{0} %q)"
    modules = [(0, 30, "jit_f(11)"), (100, 100, "jit_f(11)"),
               (200, 120, "jit_f(11)"), (330, 50, "jit_f(22)"),
               (400, 50, "jit_g(33)")]
    ops = [(10, 20, copy),
           (100, 30, fusion), (130, 60, loop), (140, 40, kernel),
           (190, 10, copy),
           (200, 40, fusion), (240, 60, loop), (250, 40, kernel),
           (300, 20, copy),
           (330, 20, fusion), (350, 30, kernel),
           (400, 20, fusion),
           (420, 30, "%mystery.9 = f32[4]{0} add(f32[4] %a, f32[4] %b)")]
    spans = [(20 * u, 100 * u, "serve.step", 0),
             (25 * u, 60 * u, "serve.chunk_feed", None),
             (315 * u, 10 * u, "bench.observe", None)]
    scale = lambda evs: sorted((t * u, d * u, n) for t, d, n in evs)
    ops = scale(ops)
    return {"file": "by hand", "base": 0, "device": "/device:TPU:0",
            "ops": ops, "busy": [op[:2] for op in ops],
            "modules": scale(modules), "spans": spans,
            "programs": {"jit_f(11)": f11, "jit_f(22)": f22,
                         "jit_g(33)": g33}}


def test_device_report_by_hand():
    """``observe.device_report`` on a hand-made trace, every number
    worked by hand (ms): busy 0.2 + 1.0 + 1.2 + 0.5 + 0.5 = 3.4 of a
    window of 4.4; the instruction no program holds 0.3 (matched 3.1 of
    3.4); ``copy.4`` unscoped in three executions 0.2 + 0.1 + 0.2 (under a
    scope 2.6 of 3.4).  ``jit_f(11)``: the clipped execution apart; of the
    two whole ones ``moe.gate`` 0.3 / 0.4, ``moe.expert`` 0.2 of the
    ``while`` itself + 0.4 of the kernel in it = 0.6 / 0.6, unscoped 0.1 /
    0.2: medians 0.35 + 0.6 + 0.15 = 1.1 = the median execution."""
    from flashmoe_tpu import observe

    rep = observe.device_report(_device_trace_by_hand())
    assert rep["busy_ms"] == pytest.approx(3.4)
    assert rep["window_ms"] == pytest.approx(4.4)
    assert rep["idle_ms"] == pytest.approx(1.0)
    assert rep["matched_share"] == pytest.approx(3.1 / 3.4)
    assert rep["scoped_share"] == pytest.approx(2.6 / 3.4)
    assert rep["outside_programs_ms"] == 0.0
    by = {p["program"]: p for p in rep["programs"]}
    assert list(by) == ["jit_f(11)", "jit_f(22)", "jit_g(33)"]  # by time
    f11 = by["jit_f(11)"]
    assert (f11["executions"], f11["clipped"]) == (2, 1)
    assert f11["clipped_ms"] == pytest.approx(0.2)
    assert f11["ms"] == pytest.approx([1.0, 1.1, 1.2])
    assert f11["busy_ms"] == pytest.approx(2.4)
    assert f11["share"] == pytest.approx(2.4 / 3.4) and f11["ops"] == 4
    assert [(r["scope"], r["pass"], pytest.approx(r["ms"]))
            for r in f11["scopes"]] == [
        ("moe.expert", "fwd", 0.6), ("moe.gate", "fwd", 0.35),
        ("(unscoped)", "", 0.15)]
    # the parts sum to the execution
    assert f11["rows_ms"] == pytest.approx(f11["ms"][1])
    assert f11["kernels"] == [{"kernel": "fm_ffn_fwd",
                               "ms": pytest.approx(0.4), "calls": 1}]
    assert f11["unscoped"] == [{"op": "copy", "shape": "f32[8]",
                                "ms": pytest.approx(0.15), "n": 1.0}]
    assert f11["unmatched"] == []
    # the other shape of the same function is a program of its own, and
    # its ``fusion.1`` is not the first's
    f22 = by["jit_f(22)"]
    assert [(r["scope"], pytest.approx(r["ms"])) for r in f22["scopes"]] \
        == [("moe.expert", 0.3), ("moe.combine", 0.2)]
    g33 = by["jit_g(33)"]
    assert (g33["executions"], g33["clipped"]) == (1, 0)   # last, but whole
    assert [(r["scope"], pytest.approx(r["ms"])) for r in g33["scopes"]] \
        == [("(unmatched)", 0.3), ("lm.head", 0.2)]
    assert g33["unmatched"] == [{"op": "mystery", "shape": "f32[4]",
                                 "ms": pytest.approx(0.3), "n": 1.0}]
    # every program's rows sum to the busy time, the clipped one apart
    assert sum(p["rows_ms"] * p["executions"] for p in rep["programs"]) \
        + f11["clipped_ms"] == pytest.approx(rep["busy_ms"])
    assert rep["kernels"] == {"fm_ffn_fwd": {"ms": pytest.approx(1.1),
                                             "calls": 3}}
    # the idle side: gaps of 0.7 (under a chunk's feed), 0.1 (the caller's)
    # and 0.2 ms (no span); no ``serve_step`` record: none has a step
    idle = rep["idle"]
    assert idle["idle_ms"] == pytest.approx(1.0) and "gaps" not in idle
    assert list(idle["by_span"].items()) == [
        ("serve.chunk_feed", pytest.approx(0.7)),
        ("(no span)", pytest.approx(0.2)),
        ("bench.observe", pytest.approx(0.1))]
    assert idle["named_ms"] == 0.0
    text = observe.render_device_text(rep)
    assert "91.18 % of the busy time matched" in text
    assert "76.47 % under a registered scope" in text
    assert "jit_f(11): 2 whole executions (1 clipped, 0.200 ms)" in text
    assert "rows sum to 1.100 ms" in text
    assert "(unmatched)    0.300 ms in 1: mystery -> f32[4]" in text
    assert "fm_ffn_fwd 1.100 ms in 3 calls" in text


def test_device_report_without_a_program_line_or_a_program():
    """A trace whose programs' HLO is not there (``programs`` not read)
    still splits by program and says every operation is unmatched; one
    with no ``XLA Modules`` line is one nameless program."""
    from flashmoe_tpu import observe

    trace = _device_trace_by_hand()
    del trace["programs"]
    rep = observe.device_report(trace)
    assert rep["matched_share"] == 0.0 and rep["scoped_share"] == 0.0
    assert rep["busy_ms"] == pytest.approx(3.4)
    assert {r["scope"] for p in rep["programs"] for r in p["scopes"]} \
        == {"(unmatched)"}
    trace["modules"] = []
    rep = observe.device_report(trace)
    assert [p["program"] for p in rep["programs"]] == ["(no program)"]
    assert rep["programs"][0]["busy_ms"] == pytest.approx(3.4)


def test_every_mixer_has_a_registered_scope_and_its_two_forms():
    from flashmoe_tpu.config import STATE_MIXERS, WINDOW_MIXER
    from flashmoe_tpu.ops.attention import MIXER_SPANS

    assert set(MIXER_SPANS) == {"mha", WINDOW_MIXER, "mla", *STATE_MIXERS}
    assert MIXER_SPANS[WINDOW_MIXER] == MIXER_SPANS["mha"]
    for part in MIXER_SPANS.values():
        assert {part, part + "_prefill", part + "_decode"} <= set(SPAN_NAMES)


@pytest.mark.parametrize("program", ["decode", "prefill", "chunk"])
def test_the_engines_programs_stand_under_their_parts_scopes(params,
                                                             program):
    """What ``observe --device`` will find in a trace of the toy engine's
    programs (compiled here for the CPU, where every span takes the
    gather arm): the mixer's part under ``attn.kv`` with the arm's own
    scope inside it, the mixture's under ``ffn.moe`` and its stages, the
    embedding and the head under theirs; the matrix products all under a
    scope."""
    from flashmoe_tpu.serving.kvcache import init_paged_cache

    cache = init_paged_cache(CFG, SERVE.num_pages, SERVE.page_size,
                             SERVE.max_batch)
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    lowered = {
        "decode": lambda: eng._paged_decode_step.lower(
            params, CFG, cache, i32(4), i32(4, 2), i32(4)),
        "prefill": lambda: eng._prefill_padded.lower(
            params, CFG, i32(1, 16), jnp.int32(9)),
        "chunk": lambda: eng._prefill_chunk.lower(
            params, CFG, cache, i32(1, 8), i32(2), i32(1), jnp.int32(0),
            jnp.int32(7), jnp.int32(0)),
    }[program]()
    text = lowered.compile().as_text()
    found = telemetry.program_scopes(text)
    scopes = {scope for scope, _, _ in found.values()}
    assert {"attn.kv", "attn.kv_prefill", "lm.embed", "lm.head", "ffn.moe",
            "moe.gate", "moe.expert"} <= scopes
    assert "attn.kv_decode" not in scopes       # the kernel's arm: a TPU's
    products = [name for name in found if name.startswith(("dot", "conv"))]
    assert products and all(found[name][0] for name in products)


def test_train_step_scopes_and_records_say_which_step_compiled():
    from flashmoe_tpu.parallel.mesh import make_mesh
    from flashmoe_tpu.runtime.trainer import (
        init_state, make_optimizer, make_train_step, train,
    )

    cfg = MoEConfig(num_experts=4, expert_top_k=2, hidden_size=64,
                    intermediate_size=128, sequence_len=24, num_layers=1,
                    moe_frequency=1, vocab_size=256, num_heads=2,
                    drop_tokens=False, is_training=True,
                    dtype=jnp.float32, param_dtype=jnp.float32)
    mesh = make_mesh(cfg, dp=1, devices=jax.devices()[:1])

    def batches():
        i = 0
        while True:
            yield {"tokens": jax.random.randint(
                jax.random.PRNGKey(i), (1, 25), 0, 256)}
            i += 1

    rec = FlightRecorder()
    train(cfg, mesh, batches(), num_steps=3, recorder=rec)
    steps = rec.records
    assert steps[0]["compiles"] >= 1 and steps[0]["compile_ms"] > 0.0
    assert [s["compiles"] for s in steps[1:]] == [0, 0]

    opt = make_optimizer(cfg, total_steps=3)
    state = init_state(jax.random.PRNGKey(0), cfg, opt)
    text = make_train_step(cfg, mesh, opt, use_pallas=False).lower(
        state, next(batches())).compile().as_text()
    assert "train.forward_backward" in text
    assert "train.optimizer" in text
    # the layer's stages sit inside the trainer's scope, forward and
    # backward (jax marks the backward transpose(...))
    assert "train.forward_backward/jvp(" in text
    assert "train.forward_backward/transpose(" in text
    assert "moe.expert" in text
