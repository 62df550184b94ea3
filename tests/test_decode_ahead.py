"""The engine dispatches a step's decode program BEFORE it reads the
step's tokens (ISSUE 34): the sampler's array is the decode step's feed
where it lies on the device, and the one read-back of the step follows
the dispatch.  It reads first, as it always did, on a step where what
stands between the sampler and the decode program needs the tokens on the
host: speculation armed (the drafts are built from them) or a pool that
cannot cover the step's growth (an eviction rebuilds its victim's prompt
from them).

Held on a K/V toy (this file), on a short-convolution + K/V toy (LFM2's
pattern) and on a delta-rule + latent toy (Ling's; a file each, the same
cases imported): the served tokens are
``generate()``'s one request at a time through joins, retirements, chunked
prefill and an eviction cycle; which steps take which order, and that the
record and the counter say so; a request that ends on a stop token AFTER
its next row was dispatched (the one wasted row): its tokens, its pages,
and the slot's next tenant.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashmoe_tpu.models.generate import generate
from flashmoe_tpu.models.presets import PRESETS
from flashmoe_tpu.models.transformer import init_params
from flashmoe_tpu.serving import engine as eng
from flashmoe_tpu.serving.engine import Request, ServeConfig, ServingEngine
from flashmoe_tpu.serving.kvcache import SCRATCH_PAGE, init_paged_cache
from flashmoe_tpu.serving.loadgen import tiny_config
from flashmoe_tpu.serving.speculate import SpecConfig
from flashmoe_tpu.utils.telemetry import FlightRecorder, Metrics

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)
TOYS = {
    # the serving drill's model: MHA, a dense and a mixture layer
    "kv": tiny_config(),
    # tests/test_lfm2.py's sizes: gated short convolutions beside GQA
    # layers whose two K/V heads of 64 share a row of the pool
    "conv_kv": PRESETS["lfm2-24b-a2b"](
        num_layers=5, layer_mixers=("conv", "mha", "conv", "conv", "mha"),
        first_k_dense=1, hidden_size=256, intermediate_size=64,
        dense_intermediate_size=128, num_experts=8, expert_top_k=2,
        vocab_size=256, num_heads=4, num_kv_heads=2, **F32),
    # tests/test_ling3.py's sizes: delta-rule layers beside a latent one
    "kda_mla": PRESETS["ling-3.0-flash"](
        num_layers=3, layer_mixers=("kda", "kda", "mla"), first_k_dense=1,
        hidden_size=64, intermediate_size=64, dense_intermediate_size=128,
        num_experts=16, expert_top_k=3, n_group=4, topk_group=2,
        expert_first=4, experts_held=4, vocab_size=256, num_heads=3,
        kda_heads=3, kda_head_dim=16, kv_lora_rank=20, qk_nope_head_dim=10,
        qk_rope_head_dim=6, v_head_dim=14, **F32),
}
TOKENS = np.random.default_rng(34).integers(1, 256, 400)
SERVE = dict(max_batch=3, page_size=8, num_pages=40, max_pages_per_slot=8,
             ctx_bucket_pages=2, prompt_bucket=8)
#: (prompt length, new tokens): whole prompts and, at ``prefill_chunk``
#: 16, prompts of two and three chunks; six requests over three slots
MIXED = [(9, 7), (40, 9), (21, 5), (33, 12), (8, 10), (17, 4)]
ARRIVALS = [0, 0, 1, 2, 4, 5]


def _toy(name):
    cfg = TOYS[name]
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


#: the K/V toy here; the cases that take ``toy`` run on the other two in
#: ``test_decode_ahead_conv_kv.py`` and ``test_decode_ahead_kda_mla.py``,
#: which import them (a toy's first case compiles its oracle's programs, a
#: minute and a half under the gate's load: one file a toy keeps each
#: under the two minutes a file of few cases may hold a worker)
@pytest.fixture(scope="module", params=["kv"])
def toy(request):
    return _toy(request.param)


def _prompt(r, t0):
    return tuple(int(t) for t in TOKENS[7 * r:7 * r + t0])


_ORACLE: dict = {}


def _oracle(cfg, params, prompt, n, most=12):
    """``generate()``'s answer to ``prompt`` alone, its first ``n`` tokens
    (greedy: a prefix of a longer answer), one program a prompt length."""
    key = (cfg, prompt)
    if key not in _ORACLE:
        _ORACLE[key] = [int(t) for t in np.asarray(generate(
            params, jnp.asarray([prompt], jnp.int32), cfg,
            max_new_tokens=most))[0]]
    return _ORACLE[key][:len(prompt) + n]


def _drive(engine, reqs, arrivals=None):
    """Step ``engine`` through ``reqs``; the records ``step()`` returned,
    each with what :meth:`_growth_fits` answered in it (``None``: not
    asked)."""
    asked = []
    real = engine._growth_fits

    def spy(rows):
        asked.append(real(rows))
        return asked[-1]

    engine._growth_fits = spy
    for j, req in enumerate(reqs):
        engine.submit(req, arrivals[j] if arrivals else 0)
    recs = []
    while engine.pending():
        del asked[:]
        rec = engine.step()
        recs.append(dict(rec, fits=asked[0] if asked else None))
        assert len(asked) <= 1
    return recs


def _order_holds(recs, mx):
    """Every record says when its tokens were read, its phases lie in
    that order, and the counter counts the steps that ran ahead."""
    ahead = 0
    for rec in recs:
        phases = list(rec["phase_ms"])
        if not rec["sample_rows"]:
            assert "readback" not in rec and "serve.deliver" not in phases
            continue
        if rec["readback"] == "after_dispatch":
            ahead += 1
            assert "serve.verify" not in phases
            assert phases.index("serve.decode") \
                < phases.index("serve.deliver"), phases
        else:
            assert rec["readback"] == "before_dispatch"
            later = [p for p in ("serve.decode", "serve.verify")
                     if p in phases]
            assert all(phases.index("serve.deliver") < phases.index(p)
                       for p in later), phases
    assert mx.counters.get("serve.decode_ahead_steps", 0) == ahead
    return ahead


def _mixed(cfg, params, serve, mixed=MIXED, arrivals=ARRIVALS):
    mx = Metrics()
    engine = ServingEngine(params, cfg, serve, metrics_obj=mx,
                           recorder=FlightRecorder())
    reqs = [Request(rid=r, prompt=_prompt(r, t0), max_new_tokens=n)
            for r, (t0, n) in enumerate(mixed)]
    recs = _drive(engine, reqs, arrivals)
    assert engine.stats["completed"] == len(mixed)
    for r, (t0, n) in enumerate(mixed):
        assert engine.outputs[r] == _oracle(cfg, params, reqs[r].prompt, n), r
    assert engine.pool.used_pages == 0
    assert [r for r in engine.recorder.records
            if r["kind"] == "serve_step"] == [
        {k: v for k, v in rec.items() if k != "fits"} for rec in recs]
    _account_holds(engine, recs, serve, [t0 for t0, _ in mixed])
    return engine, recs, mx


def _account_holds(engine, recs, serve, prompt_lengths):
    """The host's account (ISSUE 35) through the same joins, chunks and
    evictions, on the wall clock: the wait and the host's time are the
    step, the wait lies where the tokens are read, and the prefill
    programs' records carry every prompt token of every admission."""
    for rec in recs:
        assert rec["host_ms"] + rec["wait_ms"] == pytest.approx(
            rec["step_ms"], abs=2e-3)
        assert rec["wait_ms"] <= rec["phase_ms"].get("serve.sample", 0.0) \
            + 1e-3
        assert (rec["wait_ms"] > 0.0) == ("readback" in rec)
        assert rec["held_slots"] == (rec["sample_rows"]
                                     if rec["starved"] else 0)
        assert rec["cpu_ms"] >= 0.0 and rec["between_ms"] >= 0.0
    pre = [r for r in engine.recorder.records if r["kind"] == "serve_prefill"]
    assert sum(rec["prefill_programs"] for rec in recs) == len(pre)
    # an evicted request is admitted again with what it was served on its
    # prompt: the admissions' own count of prompt tokens is the yardstick
    admitted = [d for d in engine.metrics.decisions
                if d["decision"] == "serve.admit"]
    assert sum(p["tokens"] for p in pre) == sum(
        d["prompt_tokens"] for d in admitted) >= sum(prompt_lengths)
    for p in pre:
        assert p["rows"] == (serve.prefill_chunk if p["form"] == "chunk"
                             else -(-p["tokens"] // serve.prompt_bucket)
                             * serve.prompt_bucket)
    if engine.stats["evictions"] == 0:
        assert len(admitted) == len(prompt_lengths)


@pytest.mark.parametrize("chunk", [None, 16], ids=["whole", "chunked"])
def test_a_roomy_pool_decodes_ahead_on_every_decode_step(toy, chunk):
    """Joins, retirements, slot reuse, whole and chunked prefill over a
    pool that always covers a step's growth: ``generate()``'s tokens, and
    EVERY step that ran the decode program read its tokens after the
    dispatch; the steps that read first fed nobody (their sampled slots all
    ended by count)."""
    cfg, params = toy
    engine, recs, mx = _mixed(cfg, params,
                              ServeConfig(**SERVE, prefill_chunk=chunk))
    assert engine.stats["evictions"] == 0
    assert engine.stats["max_active"] == SERVE["max_batch"]
    ahead = _order_holds(recs, mx)
    decoded = [rec for rec in recs if "serve.decode" in rec["phase_ms"]]
    assert ahead == len(decoded) >= 12
    assert all(rec["readback"] == "after_dispatch" and rec["fits"]
               for rec in decoded)
    for rec in recs:
        if rec.get("readback") == "before_dispatch":
            assert rec["fits"] is None and rec["ctx_pages"] == 0


def test_a_pool_too_small_for_a_steps_growth_reads_first(toy):
    """Three answers that outgrow a pool of nine pages, and a prompt of
    three chunks behind them: the steps whose growth the pool cannot cover
    read their tokens FIRST (an eviction needs the victim's tokens on the
    host), the others run ahead, one engine takes both orders step by
    step, and the tokens are ``generate()``'s."""
    cfg, params = toy
    engine, recs, mx = _mixed(
        cfg, params, ServeConfig(**dict(SERVE, num_pages=10),
                                 prefill_chunk=16),
        mixed=[(14, 12), (14, 12), (14, 12), (33, 6)],
        arrivals=[0, 0, 0, 3])
    assert engine.stats["evictions"] >= 1
    ahead = _order_holds(recs, mx)
    first = [rec for rec in recs if rec["fits"] is False]
    assert first and ahead
    assert all(rec["readback"] == "before_dispatch" for rec in first)
    assert all(rec["readback"] == "after_dispatch"
               for rec in recs if rec["fits"])
    # an eviction of the growth phase happens in a step that read first
    grew = {d["step"] for d in mx.decisions if d["decision"] == "serve.evict"}
    by_step = {rec["step"]: rec for rec in recs}
    assert all(by_step[s].get("readback") != "after_dispatch" for s in grew)


def test_speculation_armed_reads_first_and_morphed_off_runs_ahead():
    """With speculation armed every step reads first (drafted or not);
    morphed off at a step boundary, the same engine runs ahead; the
    tokens are the plain engine's either way."""
    cfg = TOYS["kv"]
    params = init_params(jax.random.PRNGKey(0), cfg)
    reqs = [Request(rid=r, prompt=tuple(int(TOKENS[2 * r + j % 2])
                                        for j in range(8)),
                    max_new_tokens=20) for r in range(3)]
    want = ServingEngine(params, cfg, ServeConfig(**SERVE)).run(reqs)
    mx = Metrics()
    engine = ServingEngine(
        params, cfg, ServeConfig(**SERVE, speculate=SpecConfig(
            draft_tokens=3)), metrics_obj=mx)
    for req in reqs:
        engine.submit(req)
    recs = []
    while not any("serve.verify" in rec["phase_ms"] for rec in recs):
        recs.append(dict(engine.step(), fits=None))     # drafted or not
        assert len(recs) < 10, "never drafted"
    armed = len(recs)
    assert all(rec["readback"] == "before_dispatch" for rec in recs)
    assert "serve.decode_ahead_steps" not in mx.counters
    engine.set_speculate(False, reason="test")
    while engine.pending():
        recs.append(dict(engine.step(), fits=None))
    assert _order_holds(recs, mx) >= 1
    assert recs[armed]["readback"] == "after_dispatch"
    assert engine.outputs == want


def _stop_after(answer, least=2):
    """Index and value of a generated token that none before it equals,
    at least ``least`` tokens in: stopping on it ends the request there,
    on a token that is not its last by count."""
    for j in range(least, len(answer) - 1):
        if answer[j] not in answer[:j]:
            return j, answer[j]
    raise AssertionError("no token of the answer is new")


def test_a_stop_token_wastes_one_row_and_nothing_else(toy):
    """A request that ends on a stop token is found out after its next
    row was dispatched: its tokens are ``generate()``'s up to the stop
    token, its pages are back in the pool when the step returns, and the
    request admitted to its slot the step after (over the pages and the
    per-slot state the wasted row wrote) is served ``generate()``'s tokens
    too."""
    cfg, params = toy
    one = ServeConfig(**dict(SERVE, max_batch=1, num_pages=8))
    first, second = _prompt(0, 14), _prompt(3, 11)
    answer = _oracle(cfg, params, first, 12)[14:]
    j, stop = _stop_after(answer)
    mx = Metrics()
    engine = ServingEngine(params, cfg, one, metrics_obj=mx)
    recs = _drive(engine, [
        Request(rid=0, prompt=first, max_new_tokens=12, stop_tokens=(stop,)),
        Request(rid=1, prompt=second, max_new_tokens=6)])
    assert engine.outputs[0] == list(first) + answer[:j + 1]
    assert engine.outputs[1] == _oracle(cfg, params, second, 6)
    # the step that delivered the stop token had fed it: one row, wasted
    stopped = recs[j]
    assert stopped["readback"] == "after_dispatch" and stopped["ctx_pages"]
    assert stopped["completed"] == 1 and stopped["pages_used"] == 0
    admit = [d for d in mx.decisions if d["decision"] == "serve.admit"]
    assert [d["step"] for d in admit] == [0, j + 1]
    assert [d["slot"] for d in admit] == [0, 0]
    assert engine.pool.used_pages == 0
    _order_holds(recs, mx)
    # and beside a neighbour that keeps decoding: the slot's row of the
    # wasted step and its next tenant leave the neighbour's tokens alone
    two = ServeConfig(**dict(SERVE, max_batch=2, num_pages=16))
    engine = ServingEngine(params, cfg, two)
    third = _prompt(5, 9)
    engine.run([
        Request(rid=0, prompt=first, max_new_tokens=12, stop_tokens=(stop,)),
        Request(rid=1, prompt=third, max_new_tokens=12),
        Request(rid=2, prompt=second, max_new_tokens=6)])
    assert engine.outputs[0] == list(first) + answer[:j + 1]
    assert engine.outputs[1] == _oracle(cfg, params, third, 12)
    assert engine.outputs[2] == _oracle(cfg, params, second, 6)


def test_the_decode_program_feeds_an_idle_row_the_pad_token(toy):
    """What lets the sampler's array be the feed: with ``pad_token`` the
    decode program feeds a row whose table is all scratch the pad token
    whatever ``toks`` holds, so logits and pools are those of a host-built
    feed TO THE BIT; without it the program is the parent's."""
    cfg, params = toy
    pools = init_paged_cache(cfg, 12, 8, 4)
    tables = np.full((4, 2), SCRATCH_PAGE, np.int32)
    tables[0], tables[2] = (1, 2), (3, 4)
    pos = jnp.asarray([3, 0, 9, 0], jnp.int32)
    sampled = jnp.asarray([17, 99, 23, 201], jnp.int32)    # rows 1, 3 idle
    masked = jnp.asarray([17, 5, 23, 5], jnp.int32)
    step = eng._paged_decode_step
    got = step(params, cfg, pools, sampled, jnp.asarray(tables), pos,
               pad_token=5)
    want = step(params, cfg, pools, masked, jnp.asarray(tables), pos)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    plain = step(params, cfg, pools, sampled, jnp.asarray(tables), pos)
    assert not np.array_equal(np.asarray(plain[0][1]), np.asarray(want[0][1]))
    np.testing.assert_array_equal(np.asarray(plain[0][0]),
                                  np.asarray(want[0][0]))


def test_the_ep_sharded_engine_decodes_ahead_too():
    """``ep_shards=2`` on two virtual devices shares the feed code and
    takes the same order: the sampler's array goes into the sharded
    program as it lies, idle rows masked by their shard-local tables."""
    cfg = TOYS["kv"]
    params = init_params(jax.random.PRNGKey(0), cfg)
    serve = dict(SERVE, max_batch=4, num_pages=40)
    reqs = [Request(rid=r, prompt=_prompt(r, 9 + r), max_new_tokens=5 + r)
            for r in range(3)]
    want = ServingEngine(params, cfg, ServeConfig(**serve)).run(reqs)
    mx = Metrics()
    engine = ServingEngine(params, cfg, ServeConfig(**serve, ep_shards=2),
                           metrics_obj=mx)
    recs = _drive(engine, reqs, [0, 0, 1])
    assert engine.outputs == want
    assert _order_holds(recs, mx) >= 5
    assert all(rec["readback"] == "after_dispatch" for rec in recs
               if "serve.decode" in rec["phase_ms"])
