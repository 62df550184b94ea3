"""Generation by diffusion over blocks (ISSUE 46: SDAR-30B-A3B-Chat's
architecture at toy sizes, float32, seeded weights): ``generate_blocks``
against the plain reference ``benchmark/lib/reference_sdar.py``, the
serving engine against ``generate_blocks``, across an eviction, the
kernels' arms under the block mask against the XLA arm, the two wrong
programs the cell's controls name, the refusals, and the programs of a
config WITHOUT a block length as the parent commit traced them."""

import hashlib
import importlib.util
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashmoe_tpu.config import Activation, MoEConfig
from flashmoe_tpu.models.generate import (
    REVEAL_RULES, generate, generate_blocks,
)
from flashmoe_tpu.models.transformer import init_params
from flashmoe_tpu.ops import attention
from flashmoe_tpu.serving import engine as eng
from flashmoe_tpu.serving.engine import Request, ServeConfig, ServingEngine
from flashmoe_tpu.utils.telemetry import Metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lib(name):
    full = f"benchlib_{name}"
    if full not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            full, os.path.join(ROOT, "benchmark", "lib", f"{name}.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[full] = mod
        spec.loader.exec_module(mod)
    return sys.modules[full]


ref = _lib("reference_sdar")

TINY = dict(
    num_experts=8, expert_top_k=2, hidden_size=64, intermediate_size=64,
    num_layers=2, vocab_size=300, num_heads=4, num_kv_heads=2, head_dim=16,
    qk_norm=True, gated_ffn=True, hidden_act=Activation.SILU,
    drop_tokens=False, dtype=jnp.float32, param_dtype=jnp.float32,
    rope_theta=1e6, sequence_len=128)
CFG = MoEConfig(**TINY, block_length=4, mask_token_id=299)
#: the same sizes as the reference reads them
DIMS = {"hidden": 64, "layers": 2, "heads": 4, "kv_heads": 2, "head_dim": 16,
        "vocab": 300, "experts": 8, "top_k": 2, "inter": 64,
        "norm_topk": True, "rope_theta": 1e6, "eps": 1e-6, "block": 4,
        "mask_id": 299, "param_dtype": "float32"}
#: float32 on both sides, the products in another order: logits of some
#: +-3 agree to 2e-4
TOL = 2e-4


@pytest.fixture(scope="module")
def params():
    return ref.make_params(2**31 + 46, DIMS)


def _prompt(n, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(int(t) for t in rng.integers(1, 290, n))


def _blocks(params, prompt, n, **kw):
    out = generate_blocks(params, jnp.asarray(prompt, jnp.int32)[None], CFG,
                          max_new_tokens=n, **kw)
    return [np.asarray(a)[0] if a.ndim < 5 else np.asarray(a)[:, :, 0]
            for a in out]


# ---- (a) generate() against the plain reference --------------------------

@pytest.mark.parametrize("steps", [1, 2, 4])
def test_generate_blocks_is_the_plain_references_generation(params, steps):
    prompt = _prompt(10)            # two whole blocks and a tail of two
    toks, at, logits = _blocks(params, prompt, 13, denoise_steps=steps,
                               with_logits=True)
    want_toks, want_at, want_logits = ref.generate(params, DIMS, prompt, 13,
                                                   steps)
    assert list(toks) == want_toks
    assert list(at) == want_at
    assert logits.shape == want_logits.shape == (4, steps, 4, 300)
    assert np.abs(logits - want_logits).max() < TOL
    # generate() is the same loop, tokens alone
    assert list(np.asarray(generate(
        params, jnp.asarray(prompt, jnp.int32)[None], CFG,
        max_new_tokens=13, denoise_steps=steps))[0]) == want_toks


def test_a_stop_token_ends_a_row_and_pads_what_follows(params):
    prompt = _prompt(9, 3)
    toks, _ = _blocks(params, prompt, 12, denoise_steps=2)
    stop = int(toks[9 + 5])
    first = list(toks[9:]).index(stop)
    cut, _ = _blocks(params, prompt, 12, denoise_steps=2,
                     stop_tokens=(stop,), pad_token=7)
    assert list(cut[9:9 + first + 1]) == list(toks[9:9 + first + 1])
    assert set(cut[9 + first + 1:]) == {7}


# ---- (b) the engine against generate() -----------------------------------

#: four prompts of 18 tokens (four whole blocks and a tail of two; two
#: prefill buckets of 8, so a chunk of 8 splits them) and ten new tokens
#: each: ONE compiled oracle a case
PROMPTS = [_prompt(18, 30 + i) for i in range(4)]
NEW = 10
ARRIVALS = [0, 0, 1, 3]             # slots join at different steps


def _serve(**kw):
    base = dict(max_batch=3, page_size=8, num_pages=64,
                max_pages_per_slot=8, ctx_bucket_pages=2, prompt_bucket=8)
    return ServeConfig(**dict(base, **kw))


_ORACLE = {}


def _oracle(params, steps, rule, thr):
    key = (steps, rule, thr)
    if key not in _ORACLE:
        toks, at = generate_blocks(
            params, jnp.asarray(PROMPTS, jnp.int32), CFG,
            max_new_tokens=NEW, denoise_steps=steps, reveal_rule=rule,
            reveal_threshold=thr)
        _ORACLE[key] = (np.asarray(toks).tolist(), np.asarray(at).tolist())
    return _ORACLE[key]


@pytest.mark.parametrize("chunk", [None, 8], ids=["whole", "chunked"])
@pytest.mark.parametrize("rule", REVEAL_RULES)
@pytest.mark.parametrize("steps", [1, 2, 4])
def test_engine_is_generate_blocks(params, steps, rule, chunk):
    thr = 0.0155        # between the toy's confidences: both arms of it
    recs = []
    recorder = type("R", (), {"record": lambda self, **r: recs.append(r)})()
    engine = ServingEngine(params, CFG, _serve(
        page_size=4, prefill_chunk=chunk, denoise_steps=steps,
        reveal_rule=rule, reveal_threshold=thr), recorder=recorder)
    out = engine.run([Request(rid=i, prompt=p, max_new_tokens=NEW)
                      for i, p in enumerate(PROMPTS)], arrivals=ARRIVALS)
    toks, at = _oracle(params, steps, rule, thr)
    for i in range(len(PROMPTS)):
        assert out[i] == toks[i], (i, out[i][18:])
        assert engine.reveal_steps[i] == at[i]
    assert engine.stats["tokens"] == NEW * len(PROMPTS)
    assert len(engine.stats["decode_buckets"]) >= 2   # two context buckets
    launches = [r for r in recs if r["kind"] == "serve_decode"]
    # one launch held a slot denoising alone AND one whose commit rode
    # beside the block it opened; no forward revealed nothing
    assert any(0 < r["fused_rows"] < r["slots"] for r in launches)
    assert all(r["commit_rows"] == 0 for r in launches)
    assert all(r["span_rows"] == 4 * (r["slots"] + r["fused_rows"])
               for r in launches)
    assert all(r["masked_rows"] <= r["span_rows"] for r in launches)
    counters = engine.metrics.counters
    assert counters["serve.denoise_steps"] >= len(launches)
    assert counters["serve.blocks_delivered"] >= 3 * len(PROMPTS)
    steps_rec = [r for r in recs if r["kind"] == "serve_step"]
    assert sum(r["tokens"] for r in steps_rec) == NEW * len(PROMPTS)
    ahead = {r["readback"] for r in steps_rec if "readback" in r}
    if rule == "low_confidence_dynamic":
        assert ahead == {"before_dispatch"}
    else:
        assert "after_dispatch" in ahead
        assert sum(r["revealed"] for r in launches) >= NEW * len(PROMPTS)
    if chunk:
        assert sum(r["form"] == "chunk" for r in recs
                   if r["kind"] == "serve_prefill") == 2 * len(PROMPTS)


def test_short_prompts_whole_blocks_and_a_requests_own_steps(params):
    """A prompt shorter than a block (nothing to prefill), one of whole
    blocks (no tail), and a request that overrides the steps a block."""
    engine = ServingEngine(params, CFG, _serve(denoise_steps=4))
    short, whole = _prompt(3, 3), _prompt(8, 8)
    out = engine.run([
        Request(rid=0, prompt=short, max_new_tokens=9),
        Request(rid=1, prompt=whole, max_new_tokens=9, denoise_steps=1)])
    assert out[0] == list(_blocks(params, short, 9, denoise_steps=4)[0])
    assert out[1] == list(_blocks(params, whole, 9, denoise_steps=1)[0])


def test_a_stop_token_in_a_delivered_block_retires_the_request(params):
    p = PROMPTS[0]
    toks, _ = _oracle(params, 2, "low_confidence_static", 0.0155)
    new = toks[0][18:]
    stop = new[6]
    first = new.index(stop)
    engine = ServingEngine(params, CFG, _serve(denoise_steps=2))
    out = engine.run([Request(rid=0, prompt=p, max_new_tokens=NEW,
                              stop_tokens=(stop,))])
    assert out[0] == toks[0][:18 + first + 1]


# ---- (b2) the schedule: a commit rides in the next block's first step ------

@pytest.mark.parametrize("rule", ["low_confidence_static",
                                  "low_confidence_dynamic"])
@pytest.mark.parametrize("steps", [1, 2, 4])
def test_a_block_costs_its_denoising_steps_and_no_commit_of_its_own(
        params, steps, rule):
    """A prompt of whole blocks and an answer of three: ``steps`` launches
    a block (with a launch a commit, ISSUE 46's schedule, 3 * (steps + 1)
    - 1), the first two blocks' commits each in the launch that opens the
    next, the last block not committed."""
    thr, n, prompt = 0.0155, 3, _prompt(8, 8)
    m = Metrics()
    engine = ServingEngine(params, CFG, _serve(
        denoise_steps=steps, reveal_rule=rule, reveal_threshold=thr),
        metrics_obj=m)
    out = engine.run([Request(rid=0, prompt=prompt, max_new_tokens=4 * n)])
    toks, at = _blocks(params, prompt, 4 * n, denoise_steps=steps,
                       reveal_rule=rule, reveal_threshold=thr)
    assert out[0] == list(toks)
    assert engine.reveal_steps[0] == list(at)
    c = m.counters
    assert c["serve.fused_commits"] + c["serve.commit_rows"] == n - 1
    if rule == "low_confidence_static":
        assert c["serve.denoise_steps"] == n * steps
        assert c["serve.revealed_tokens"] == 4 * n
        assert c["serve.commit_rows"] == 0
    else:       # a block is whole when its confidences say so
        assert n <= c["serve.denoise_steps"] <= n * steps


def test_the_span_is_two_blocks_where_that_keeps_the_arm(params,
                                                         monkeypatch):
    """The engine asks the arm rule at a span of one block and of two,
    ONCE: where two blocks would leave the kernel's arm (not under a
    page, on a TPU: the rule patched as
    ``test_engine_tokens_on_the_kernels_arm`` patches it) a launch spans
    one block and a commit is a launch of its own, ISSUE 46's schedule."""
    two = lambda **kw: ServingEngine(params, CFG, _serve(**kw))._halves
    assert two() == two(page_size=16, prompt_bucket=16) == 2    # the CPU
    m = Metrics()
    with monkeypatch.context() as mp:
        mp.setattr(
            attention, "kv_attention_arm",
            lambda t, page, *a, **k: "paged_kernel" if t < page
            else "gather")
        assert two(page_size=16, prompt_bucket=16) == 2
        engine = ServingEngine(params, CFG, _serve(denoise_steps=2),
                               metrics_obj=m)       # a page of 8 rows
    assert engine._halves == 1
    prompt = _prompt(8, 8)
    out = engine.run([Request(rid=0, prompt=prompt, max_new_tokens=12)])
    assert out[0] == list(_blocks(params, prompt, 12, denoise_steps=2)[0])
    assert m.counters["serve.denoise_steps"] == 3 * (2 + 1) - 1
    assert m.counters["serve.commit_rows"] == 2
    assert m.counters["serve.fused_commits"] == 0


# ---- (c) across an eviction ---------------------------------------------

def test_an_evicted_request_resumes_to_the_same_tokens(params):
    reqs = [Request(rid=i, prompt=p, max_new_tokens=24)
            for i, p in enumerate(PROMPTS[:3])]
    roomy = ServingEngine(params, CFG, _serve(denoise_steps=2))
    want = roomy.run(reqs)
    assert roomy.stats["evictions"] == 0
    tight = ServingEngine(params, CFG, _serve(denoise_steps=2,
                                              num_pages=12))
    got = tight.run(reqs)
    assert tight.stats["evictions"] > 0
    assert got == want
    assert tight.reveal_steps == roomy.reveal_steps


# ---- (d) the kernels' arms under the block mask ---------------------------

def test_paged_decode_kernel_under_the_block_mask_is_the_gather_arm():
    rng = np.random.default_rng(5)
    b, t, nh, nkv, d, page, n_pages, n_tab = 3, 4, 4, 2, 128, 8, 12, 3
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    pools = (f(1, n_pages, nkv, page, d), f(1, n_pages, nkv, page, d))
    q, k, v = f(b, t, nh, d), f(b, t, nkv, d), f(b, t, nkv, d)
    tables = jnp.asarray([[1, 2, 3], [4, 5, 0], [6, 0, 0]], jnp.int32)
    pos = jnp.asarray([16, 12, 4], jnp.int32)      # whole blocks
    at = pos[:, None] + jnp.arange(t)[None, :]
    write = (jnp.take_along_axis(tables, at // page, axis=1), at % page)
    out, new = attention.paged_decode_attention(
        q, (k, v), pools, 0, tables, pos, write, block=4, interpret=True)
    want_pools = tuple(attention.store_kv(p, 0, x, *write)
                       for p, x in zip(pools, (k, v)))
    ctx = [attention.gather_ctx(p, 0, tables) for p in want_pools]
    want = attention.kv_attend({"wo": jnp.eye(nh * d)}, q, *ctx, at, 4)
    assert np.abs(np.asarray(out) - np.asarray(want)).max() < 2e-5
    for got, exp in zip(new, want_pools):
        assert np.array_equal(np.asarray(got)[0, 1:7], np.asarray(exp)[0, 1:7])
    # and the causal kernel differs: the mask is read
    causal, _ = attention.paged_decode_attention(
        q, (k, v), pools, 0, tables, pos, write, interpret=True)
    assert np.abs(np.asarray(causal) - np.asarray(want)).max() > 1e-2
    with pytest.raises(NotImplementedError, match="ONE block"):
        attention.paged_decode_attention(
            q[:, :3], (k[:, :3], v[:, :3]), pools, 0, tables, pos,
            (write[0][:, :3], write[1][:, :3]), block=4, interpret=True)


@pytest.mark.parametrize("live", [False, True], ids=["dead", "live"])
def test_paged_decode_kernel_under_a_span_of_two_blocks_is_the_gather_arm(
        live):
    """A span of TWO blocks of 4 that starts at rows 0, 4, 8 and 12 of a
    16-row page (the last crosses into the next page; the first has no
    context), its second half live (the next block, beside the commit of
    the first) or dead (written to the scratch page, as
    ``engine._span_step`` routes it: the slot's rows there stay)."""
    rng = np.random.default_rng(7)
    b, t, nh, nkv, d, page, n_pages = 4, 8, 4, 2, 128, 16, 13
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    pools = (f(1, n_pages, nkv, page, d), f(1, n_pages, nkv, page, d))
    q, k, v = f(b, t, nh, d), f(b, t, nkv, d), f(b, t, nkv, d)
    tables = jnp.arange(1, n_pages, dtype=jnp.int32).reshape(b, 3)
    pos = jnp.asarray([0, 20, 40, 28], jnp.int32)
    at = pos[:, None] + jnp.arange(t)[None, :]
    own = (jnp.take_along_axis(tables, at // page, axis=1), at % page)
    writes = (jnp.arange(t) < 4)[None, :] | live
    write = (jnp.where(writes, own[0], 0), jnp.where(writes, own[1], 0))
    out, new = attention.paged_decode_attention(
        q, (k, v), pools, 0, tables, pos, write, block=4, interpret=True)
    want_pools = tuple(attention.store_kv(p, 0, x, *write)
                       for p, x in zip(pools, (k, v)))
    ctx = [attention.gather_ctx(p, 0, tables) for p in want_pools]
    want = attention.kv_attend({"wo": jnp.eye(nh * d)}, q, *ctx, at, 4)
    rows = t if live else 4         # a dead half's outputs nobody reads
    assert np.abs(np.asarray(out)[:, :rows]
                  - np.asarray(want)[:, :rows]).max() < 2e-5
    half_1 = lambda pool: np.asarray(pool)[
        0, np.asarray(own[0])[:, 4:], :, np.asarray(own[1])[:, 4:]]
    for got, exp, old in zip(new, want_pools, pools):
        assert np.array_equal(np.asarray(got)[0, 1:], np.asarray(exp)[0, 1:])
        assert np.array_equal(half_1(got), half_1(old)) != live
    # the first half never sees the second: its rows are the rows of a
    # span of ONE block
    one, _ = attention.paged_decode_attention(
        q[:, :4], (k[:, :4], v[:, :4]), pools, 0, tables, pos,
        (write[0][:, :4], write[1][:, :4]), block=4, interpret=True)
    assert np.abs(np.asarray(out)[:, :4] - np.asarray(one)).max() < 2e-5


@pytest.mark.parametrize("pos0", [0, 128])
def test_flash_span_with_the_block_diagonal_is_the_xla_arm(pos0):
    rng = np.random.default_rng(6)
    b, t, s, nh, nkv, d = 1, 128, 256, 4, 2, 128
    f = lambda *sh: jnp.asarray(rng.standard_normal(sh), jnp.float32)
    q, k_ctx, v_ctx = f(b, t, nh, d), f(b, nkv, s, d), f(b, nkv, s, d)
    q_pos = pos0 + jnp.arange(t, dtype=jnp.int32)[None, :]
    layer = {"wo": jnp.eye(nh * d)}
    want = attention.kv_attend(layer, q, k_ctx, v_ctx, q_pos, 4)
    got = attention._flash_span_ctx((q,), (k_ctx,), v_ctx, q_pos,
                                    d ** -0.5, 4)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5
    causal = attention.kv_attend(layer, q, k_ctx, v_ctx, q_pos)
    assert np.abs(np.asarray(causal) - np.asarray(want)).max() > 1e-2


def test_a_span_of_several_blocks_keeps_the_gather_arm(params, monkeypatch):
    """Where the rule would hand the kernel a span of more blocks than
    the two its mask knows (``generate_blocks``' prefill over a short
    dense cache on a TPU), the attention keeps the gather arm: found by
    ``chip_smoke.py``."""
    cfg = CFG.replace(head_dim=128, num_heads=2, num_kv_heads=1)
    layer = ref.make_params(2**31 + 47, dict(DIMS, head_dim=128, heads=2,
                                             kv_heads=1))["layers"][0]
    x = jnp.asarray(np.random.default_rng(1).standard_normal((1, 12, 64)),
                    jnp.float32)
    pools = tuple(jnp.zeros((1, 4, 1, 16, 128), jnp.float32)
                  for _ in range(2))
    pos = jnp.arange(12, dtype=jnp.int32)[None, :]
    write = (jnp.ones((1, 12), jnp.int32), pos % 16)
    table = jnp.asarray([[1]], jnp.int32)
    want = attention.kv_paged_attention(layer, x, cfg, pools, 0, pos, write,
                                        table)[0]
    monkeypatch.setattr(attention, "kv_attention_arm",
                        lambda *a, **k: "paged_kernel")
    got = attention.kv_paged_attention(layer, x, cfg, pools, 0, pos, write,
                                       table)[0]
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_engine_tokens_on_the_kernels_arm(params, monkeypatch):
    """The toy engine with the paged kernel forced (interpret mode): the
    tokens of the gather arm.  A page of 16 rows, as the cell's: a span
    of two blocks is under it, and every launch takes the kernel."""
    cfg = CFG.replace(head_dim=128, num_heads=2, num_kv_heads=1)
    wide = ref.make_params(2**31 + 47, dict(DIMS, head_dim=128, heads=2,
                                            kv_heads=1))
    reqs = [Request(rid=i, prompt=PROMPTS[i], max_new_tokens=6)
            for i in range(2)]
    serve = _serve(denoise_steps=2, page_size=16, prompt_bucket=16)
    want = ServingEngine(wide, cfg, serve).run(reqs)
    monkeypatch.setattr(
        attention, "kv_attention_arm",
        lambda t, page, *a, **k: "paged_kernel" if t < page else "gather")
    jax.clear_caches()
    m = Metrics()
    try:
        engine = ServingEngine(wide, cfg, serve, metrics_obj=m)
        got = engine.run(reqs)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert got == want
    assert engine._halves == 2 and m.counters["serve.fused_commits"] == 2
    assert (m.counters["serve.decode_kernel_steps"]
            == m.counters["serve.denoise_steps"] > 0)


# ---- (e) the two wrong programs the cell's controls name ------------------

@pytest.fixture(scope="module")
def served(params):
    toks, at = _oracle(params, 2, "low_confidence_static", 0.0155)
    return [(PROMPTS[i], tuple(toks[i][18:]), tuple(at[i]))
            for i in range(len(PROMPTS))]


def test_the_sound_program_reads_no_gap(params, served):
    got = ref.block_gaps(params, DIMS, served, 2, 64, 24)
    assert got["tokens"] >= 24 and got["steps"] >= 8
    assert got["served"]["widest"] < TOL and got["reveal"]["widest"] < TOL


@pytest.mark.parametrize("control", ["causal_block", "no_commit", "fp8"])
def test_a_wrong_program_differs_by_more_than_the_tolerance(params, served,
                                                            control):
    got = ref.block_gaps(params, DIMS, served, 2, 64, 24,
                         controls=(control,))["controls"][control]
    assert got["served"]["mean"] > 10 * TOL
    assert got["served"]["widest"] > 0.02


def test_no_commit_is_told_by_later_blocks_alone(params, served):
    """A program that keeps the last denoising step's K/V serves the FIRST
    block of an answer as the sound one does: nothing has been committed
    yet."""
    prompt, toks, at = served[0]
    first = [(prompt, toks[:2], at[:2])]        # the tail's block alone
    got = ref.block_gaps(params, DIMS, first, 2, 64, 24,
                         controls=("no_commit",))
    assert got["controls"]["no_commit"]["served"]["widest"] < TOL


# ---- (f) the refusals, by name --------------------------------------------

@pytest.mark.parametrize("over,match", [
    (dict(layer_mixers=("conv", "mha")), r"\['conv'\] layers"),
    (dict(attention_kind="mla", num_kv_heads=0, head_dim=0, q_lora_rank=0,
          kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
          v_head_dim=16, qk_norm=False), r"\['mla'\] layers"),
])
def test_config_refuses_a_state_mixer_or_mla_beside_a_block_length(over,
                                                                   match):
    with pytest.raises(NotImplementedError, match=match):
        MoEConfig(**dict(TINY, block_length=4, mask_token_id=299, **over))


@pytest.mark.parametrize("over,match", [
    (dict(block_length=3), "power of two"),
    (dict(block_length=4, mask_token_id=300), "outside the vocabulary"),
])
def test_config_refuses_a_block_it_cannot_mask(over, match):
    with pytest.raises(ValueError, match=match):
        MoEConfig(**dict(TINY, mask_token_id=299) | over)


@pytest.mark.parametrize("kw,match", [
    (dict(speculate=eng.SpecConfig()), "speculate"),
    (dict(ep_shards=2, max_batch=4), "ep_shards > 1"),
])
def test_engine_refuses_by_name(params, kw, match):
    with pytest.raises(NotImplementedError,
                       match=f"generation by blocks.*{match}"):
        ServingEngine(params, CFG, _serve(**kw))


def test_engine_refuses_a_handoff_and_sizes_that_split_a_block(params):
    with pytest.raises(NotImplementedError, match="prefill_fn"):
        ServingEngine(params, CFG, _serve(), prefill_fn=lambda *a, **k: 0)
    with pytest.raises(ValueError, match="page_size=6 must be whole blocks"):
        ServingEngine(params, CFG, _serve(page_size=6, prompt_bucket=12))
    with pytest.raises(ValueError, match="denoise_steps=3 must divide"):
        ServingEngine(params, CFG, _serve(denoise_steps=3))
    with pytest.raises(ValueError, match="reveal_rule"):
        _serve(reveal_rule="random")


def test_submit_refuses_a_temperature_and_steps_that_do_not_divide(params):
    engine = ServingEngine(params, CFG, _serve())
    with pytest.raises(NotImplementedError, match="temperature=0.7"):
        engine.submit(Request(rid=0, prompt=(1, 2), temperature=0.7))
    with pytest.raises(ValueError, match="denoise_steps=3"):
        engine.submit(Request(rid=1, prompt=(1, 2), denoise_steps=3))
    with pytest.raises(NotImplementedError, match="greedy"):
        generate(params, jnp.ones((1, 4), jnp.int32), CFG, temperature=0.5,
                 key=jax.random.PRNGKey(0))


# ---- (g) a config without a block length: the parent's programs -----------

#: sha256 of the jaxpr (source locations struck) of each program of a toy
#: K/V config WITHOUT a block length, traced as on a TPU (the kernels'
#: arms), as the PARENT commit (87e5287) traces it: the block mask is
#: carried as a static 1 that writes nothing into these programs
#: (``_prefill_chunk`` re-pinned by ISSUE 50: its context gathers index
#: the whole pool by (layer, page), no ``slice`` + ``squeeze`` of a layer)
PARENT_JAXPRS = {
    "_paged_decode_step":
        "307bbcdc5e757d2f29efa30611c8d02aa718c914397933d1e2043320fe657bd1",
    "_paged_verify_step":
        "ba25ecb7fd89b8348099afdf440e761f54b0d26eb03e01eab90c0e84d56de712",
    "_prefill_chunk":
        "a9de06838d90a26b8bba7fdbffcfaaf0daa582421c90f30d6ab8ea5e1a74f138",
    "flash_attention":
        "a92144ac8726bb2a89d75cde589d7acadb613f928e404d4e124763867e1d790e",
    "flash_span":
        "a0090eda96b751b3e195ae02f4a32c8863ca520aa0ef0f70f2d9b5af6b7b07d1",
}


def _jaxpr_digest(fn, *args, **kw):
    text = str(jax.make_jaxpr(fn, **kw)(*args))
    text = re.sub(r" at [^\s]+\.py:\d+", "", text)
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    return hashlib.sha256(text.encode()).hexdigest()


def block_free_digests():
    """The programs' digests on this tree (also run from a copy of
    the parent commit to make the pins: ``python tests/test_sdar.py``)."""
    from flashmoe_tpu.serving.kvcache import init_paged_cache

    cfg = MoEConfig(**dict(TINY, head_dim=128, num_heads=2, num_kv_heads=1,
                           dtype=jnp.bfloat16, param_dtype=jnp.bfloat16))
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: init_paged_cache(cfg, 32, 16, 4))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    qkv = jax.ShapeDtypeStruct((1, 2, 256, 128), jnp.bfloat16)
    with pytest.MonkeyPatch.context() as mp:        # traced as on a TPU
        mp.setattr(jax, "default_backend", lambda: "tpu")
        jax.clear_caches()
        try:
            return {
                "_paged_decode_step": _jaxpr_digest(
                    lambda p, c, *a: eng._paged_decode_step(
                        p, cfg, c, *a, pad_token=0),
                    params, cache, i32(4), i32(4, 8), i32(4)),
                "_paged_verify_step": _jaxpr_digest(
                    lambda p, c, *a: eng._paged_verify_step(p, cfg, c, *a),
                    params, cache, i32(4, 3), i32(4, 8), i32(4)),
                "_prefill_chunk": _jaxpr_digest(
                    lambda p, c, *a: eng._prefill_chunk(p, cfg, c, *a),
                    params, cache, i32(1, 128), i32(8), i32(8), i32(),
                    i32(), i32()),
                "flash_span": _jaxpr_digest(
                    lambda q, k, v, at: attention._flash_span_ctx(
                        (q,), (k,), v, at, 128 ** -0.5),
                    jax.ShapeDtypeStruct((1, 128, 2, 128), jnp.bfloat16),
                    jax.ShapeDtypeStruct((1, 1, 256, 128), jnp.bfloat16),
                    jax.ShapeDtypeStruct((1, 1, 256, 128), jnp.bfloat16),
                    i32(1, 128)),
                "flash_attention": _jaxpr_digest(
                    lambda q, k, v: jax.grad(
                        lambda q, k, v: attention.flash_attention(
                            q, k, v).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v), qkv, qkv, qkv),
            }
        finally:
            jax.clear_caches()


@pytest.fixture(scope="module")
def digests():
    return block_free_digests()


@pytest.mark.parametrize("program", sorted(PARENT_JAXPRS))
def test_block_length_1_traces_the_parents_program(digests, program):
    assert digests[program] == PARENT_JAXPRS[program]


if __name__ == "__main__":
    print(block_free_digests())
