"""LFM2-24B-A2B's block against the plain reference
(``benchmark/lib/reference_lfm2.py``), at tiny sizes on the CPU, float32,
seeded random weights: the gated short convolution in its two forms, its
per-slot inputs under the serving engine beside K/V pages (whole and
chunked prefill, decode, slot reuse, eviction, idle rows), q/k norm,
heads packed two to a lane row, the expert arm, the refusals; and the case
the cache manager refused before: delta-rule layers beside K/V layers.

Tolerances.  As ``tests/test_ling3.py``: the program and the reference
compute the same float32 products in different orders; ``TIGHT`` (2e-5 of
the compared values' scale) has a factor of ten over the largest reading
seen (1.6e-6), and anything left out of the mathematics (a tap, a gate,
the carried inputs, a head's norm, the selection bias) moves a logit by
1e-2 or more.
"""

from __future__ import annotations

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashmoe_tpu.models.generate import generate
from flashmoe_tpu.models.presets import PRESETS
from flashmoe_tpu.models.transformer import forward, init_params
from flashmoe_tpu.ops import attention, conv
from flashmoe_tpu.ops.moe import expert_arm
from flashmoe_tpu.serving import engine as eng
from flashmoe_tpu.serving.engine import Request, ServeConfig, ServingEngine
from flashmoe_tpu.serving.kvcache import (
    HybridCache, init_paged_cache, slot_state_fields,
)
from flashmoe_tpu.serving.speculate import SpecConfig
from flashmoe_tpu.utils.telemetry import SPAN_NAMES, FlightRecorder, Metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TIGHT = 2e-5


def _load(path, name):
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


ref = _load(os.path.join(ROOT, "benchmark", "lib", "reference_lfm2.py"),
            "benchlib_reference_lfm2")

# the cut's pattern in small: a dense conv layer, then attention + conv
# mixture layers; 4 query heads over 2 K/V heads of width 64 (so the pool
# packs the two heads into one row of 128 lanes, as the cell's 8 into 4)
KINDS = ("conv", "mha", "conv", "conv", "mha")
TINY = dict(num_layers=5, layer_mixers=KINDS, first_k_dense=1,
            hidden_size=256, intermediate_size=64,
            dense_intermediate_size=128, num_experts=8, expert_top_k=2,
            vocab_size=256, num_heads=4, num_kv_heads=2,
            dtype=jnp.float32, param_dtype=jnp.float32)
CFG = PRESETS["lfm2-24b-a2b"](**TINY)
MODEL = {  # the same sizes under the published key names
    "hidden_size": 256, "num_hidden_layers": 5, "num_attention_heads": 4,
    "num_key_value_heads": 2, "conv_L_cache": 3, "conv_bias": False,
    "vocab_size": 256, "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 64, "intermediate_size": 128,
    "num_dense_layers": 1, "routed_scaling_factor": 1,
    "norm_topk_prob": True, "use_expert_bias": True, "norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}}
FILE = {"model": MODEL,
        "layer_kinds": ["conv" if k == "conv" else "full_attention"
                        for k in KINDS],
        "served": {"param_dtype": "float32"}}
DIMS = ref.model_dims(FILE)
SERVE = dict(max_batch=3, page_size=8, num_pages=40, max_pages_per_slot=12,
             ctx_bucket_pages=3, prompt_bucket=8)
TOKENS = np.random.default_rng(5).integers(1, 256, 200)


@pytest.fixture(scope="module")
def params():
    """The reference's weights (its tree layout IS the program's), norms
    moved off one so that a norm left out shows."""
    p = ref.make_params(1234567891011, DIMS)
    key = jax.random.PRNGKey(3)
    for li, layer in enumerate(p["layers"]):
        for j, name in enumerate(("attn_norm", "ffn_norm", "q_norm",
                                  "k_norm")):
            if name in layer:
                k = jax.random.fold_in(key, 10 * li + j)
                layer[name] = 1.0 + 0.1 * jax.random.normal(
                    k, layer[name].shape, jnp.float32)
    return p


def _close(got, want, tol=TIGHT):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= tol * max(np.abs(want).max(), 1e-30)


def _x(t, seed=1, b=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, t, 256),
                             jnp.float32)


def test_params_have_the_programs_tree(params):
    mine = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), CFG))
    assert (jax.tree.map(lambda a: (a.shape, a.dtype), mine)
            == jax.tree.map(lambda a: (a.shape, a.dtype), params))
    assert CFG.mixers == KINDS and CFG.cache_layers == (1, 4)
    assert CFG.state_layers == (0, 2, 3)
    assert CFG.slot_state == (("conv", (2 * 256,), jnp.float32),)
    assert CFG.state_slot_bytes == 3 * 2 * 256 * 4
    # the published layer_types: full attention at 2, 6, ..., 38
    full = PRESETS["lfm2-24b-a2b"]()
    assert [li for li, m in enumerate(full.mixers) if m == "mha"] == list(
        range(2, 40, 4))
    assert full.mixers.count("conv") == 30
    assert len(full.moe_layer_indices) == 38 and full.qk_norm
    assert full.resolved_head_dim == 64 and full.norm_eps == 1e-5
    # 2 caching layers x K and V x 8 heads x 64 x 2 B, stored 4 rows of 128
    cut = PRESETS["lfm2-24b-a2b"](num_layers=9, first_k_dense=1,
                                  layer_mixers=("conv", "mha") + ("conv",) * 3
                                  + ("mha",) + ("conv",) * 3)
    assert cut.kv_pool_rows == (2, 4, 128)
    assert cut.kv_token_bytes == cut.kv_pool_token_bytes == 4096
    assert cut.state_slot_bytes == 7 * 2 * 2048 * 2
    # the tied head
    np.testing.assert_array_equal(np.asarray(params["lm_head"]),
                                  np.asarray(params["embed"]).T)


# ------------------------------------------ (a) the convolution's two forms

_conv = jax.jit(conv.conv_attention, static_argnums=(2, 4))


def _inputs(b=1, layers=3):
    return jnp.zeros((layers, b, 2 * 256), jnp.float32)


@pytest.mark.parametrize("t", [1, 2, 37])
def test_a_span_equals_its_steps_and_the_reference(params, t):
    """A span at once against the same tokens one step at a time through
    the same function, and both against the reference's shifted
    products."""
    layer, x = params["layers"][2], _x(t, b=2)
    at_once, c1, _ = _conv(layer, x, CFG, _inputs(2), 1)
    carried, outs = _inputs(2), []
    for i in range(t):
        o, carried, _ = _conv(layer, x[:, i:i + 1], CFG, carried, 1)
        outs.append(o)
    _close(at_once, jnp.concatenate(outs, axis=1))
    _close(c1, carried)
    assert not np.asarray(c1[0]).any() and not np.asarray(c1[2]).any()
    for row in range(2):
        _close(at_once[row], jax.jit(
            lambda x: ref.short_conv(layer, x, DIMS))(x[row]))


def test_a_chunk_edge_carries_the_inputs(params):
    """Two spans, the second from what the first left, are the one span;
    without the carried inputs the second span's first outputs differ."""
    layer, x = params["layers"][0], _x(40, 9)
    whole, c_whole, _ = _conv(layer, x, CFG, _inputs(), 0)
    first, c_mid, _ = _conv(layer, x[:, :17], CFG, _inputs(), 0)
    second, c_end, _ = _conv(layer, x[:, 17:], CFG, c_mid, 0)
    _close(whole, jnp.concatenate([first, second], 1))
    _close(c_whole, c_end)
    cold, _, _ = _conv(layer, x[:, 17:], CFG, _inputs(), 0)
    assert np.abs(np.asarray(cold - second))[0, :2].max() > 1e-3
    np.testing.assert_array_equal(np.asarray(cold[0, 2:]),
                                  np.asarray(second[0, 2:]))


def test_pads_idle_rows_slots_and_a_fresh_start(params):
    """Whatever stands past a row's valid prefix the carried inputs are
    the same to the bit; a row with nothing valid (an idle slot of the
    decode step) keeps what it had to the bit; a chunk addresses its slot
    among many; a prompt's first chunk starts from nothing whatever the
    slot holds."""
    layer = params["layers"][0]
    _, held, _ = _conv(layer, _x(9, 2, b=2), CFG, _inputs(2), 0)
    x, junk = _x(40, 3, b=2), _x(40, 4, b=2)
    valid = jnp.arange(40)[None, :] < jnp.asarray([[23], [0]])
    mixed = jnp.where(valid[:, :, None], x, junk)
    o1, c1, _ = _conv(layer, x, CFG, held, 0, valid)
    o2, c2, _ = _conv(layer, mixed, CFG, held, 0, valid)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    np.testing.assert_array_equal(np.asarray(o1[0, :23]),
                                  np.asarray(o2[0, :23]))
    np.testing.assert_array_equal(np.asarray(c1[:, 1]), np.asarray(held[:, 1]))
    _, c3, _ = _conv(layer, x[:1, :23], CFG, held[:, :1], 0)
    _close(c1[0, 0], c3[0, 0])
    # one step: the idle row of a decode batch
    _, c4, _ = _conv(layer, x[:, :1], CFG, c1, 0,
                     jnp.asarray([[True], [False]]))
    np.testing.assert_array_equal(np.asarray(c4[:, 1]), np.asarray(c1[:, 1]))
    assert np.asarray(c4[0, 0] != c1[0, 0]).any()
    # slots and a fresh start
    many = _inputs(3) + 1.0
    slots = jnp.asarray([2])
    o, c5, _ = _conv(layer, x[:1], CFG, many, 0, slots=slots,
                     fresh=jnp.bool_(True))
    want, c0, _ = _conv(layer, x[:1], CFG, _inputs(), 0)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(c5[0, 2]), np.asarray(c0[0, 0]))
    np.testing.assert_array_equal(np.asarray(c5[:, :2]),
                                  np.asarray(many[:, :2]))
    carried, _, _ = _conv(layer, x[:1], CFG, many, 0, slots=slots,
                          fresh=jnp.bool_(False))
    assert np.abs(np.asarray(carried - want)).max() > 1e-3


# --------------------------------------------- (b) q/k norm, and packed heads

def test_qk_norm_is_the_references_and_leaving_it_out_shows(params):
    layer, x = params["layers"][1], _x(24, 5)
    pos = jnp.arange(24)[None, :]
    got = jax.jit(lambda x: attention.kv_paged_attention(
        layer, x, CFG, None, 0, pos, None, None)[0])(x)
    _close(got[0], jax.jit(lambda x: ref.attention(layer, x, DIMS))(x[0]))
    bare = jax.jit(lambda x: attention.kv_paged_attention(
        layer, x, CFG.replace(qk_norm=False), None, 0, pos, None, None)[0])(x)
    assert np.abs(np.asarray(bare - got)).max() > 1e-2
    with pytest.raises(ValueError, match="qk_norm"):
        PRESETS["joyai-llm-flash"](qk_norm=True)


def test_narrow_heads_lie_two_to_a_lane_row():
    """The pool of 64-wide heads stores heads 2j and 2j + 1 side by side;
    what a span stores comes back as the heads apart; a whole prompt's
    run lands in the same rows; and the decode kernel over the packed
    pool (interpreted) gives the gather arm's output and pools."""
    assert CFG.kv_pool_rows == (2, 1, 128)
    cache = init_paged_cache(CFG, 6, 8, 3)
    assert cache.k_pages.shape == (2, 6, 1, 8, 128) and cache.page_size == 8
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 2, 64))
    ids = jnp.asarray([[1, 1, 1, 2, 2], [3, 3, 3, 3, 3]])
    rows = jnp.asarray([[5, 6, 7, 0, 1], [0, 1, 2, 3, 4]])
    pages = attention.store_kv(cache.k_pages, 1, k, ids, rows)
    ctx = attention.gather_ctx(pages, 1, jnp.asarray([[1, 2], [3, 0]]), 64)
    assert ctx.shape == (2, 2, 16, 64)
    np.testing.assert_array_equal(np.asarray(ctx[0, :, 5:10]),
                                  np.asarray(k[0].transpose(1, 0, 2)))
    np.testing.assert_array_equal(np.asarray(ctx[1, :, :5]),
                                  np.asarray(k[1].transpose(1, 0, 2)))
    from flashmoe_tpu.serving.kvcache import store_prefill
    run = jax.random.normal(jax.random.PRNGKey(2), (2, 2, 16, 64))
    pages = store_prefill(cache.k_pages, run, jnp.asarray([4, 2]))
    ctx = attention.gather_ctx(pages, 0, jnp.asarray([[4, 2]]), 64)
    np.testing.assert_array_equal(np.asarray(ctx[0]), np.asarray(run[0]))
    # the kernel's arm, handed rows of whole lanes
    key = jax.random.PRNGKey(4)
    pools = tuple(jax.random.normal(jax.random.fold_in(key, i),
                                    (2, 6, 1, 8, 128)) for i in range(2))
    q = jax.random.normal(jax.random.fold_in(key, 2), (2, 1, 4, 64))
    kv = [jax.random.normal(jax.random.fold_in(key, 3 + i), (2, 1, 2, 64))
          for i in range(2)]
    tables = jnp.asarray([[1, 2, 0], [3, 4, 5]])
    pos = jnp.asarray([11, 20])
    write = (jnp.asarray([[2], [5]]), jnp.asarray([[3], [4]]))
    out, new = attention.paged_decode_attention(
        q, kv, pools, 1, tables, pos, write, block_pages=1, interpret=True)
    want_pools = tuple(attention.store_kv(p, 1, x, *write)
                       for p, x in zip(pools, kv))
    layer = {"wo": jnp.eye(256)}
    want = attention.kv_attend(
        layer, q, attention.gather_ctx(want_pools[0], 1, tables, 64),
        attention.gather_ctx(want_pools[1], 1, tables, 64), pos[:, None])
    _close(out, want, 1e-5)
    for a, b in zip(new, want_pools):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -------------------------------------------------------- (c) the expert arm

@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_the_expert_arm_is_one_rule_over_what_the_arms_hold(monkeypatch,
                                                            backend):
    """Where the grouped kernel runs (a TPU, whole-lane widths) every
    dropless span takes the routed rows through it: it won every span of
    ISSUE 36's race.  Elsewhere the capacity arm while its
    [E, capacity, H] dispatch buffer is at most 128 MiB (a decode step of
    128 slots and a prompt of up to 512 tokens at 64 experts) and its rows
    are at most 24 times the routed rows (E / K: 16 and 10.7 at 64 experts
    top-4 and top-6; 32 at 256 experts top-8, which keeps the routed rows
    for every span), the routed rows as ``ragged_dot`` beyond either and
    for a share of the experts; on either backend the capacity arm for
    one expert and for a rule that drops.  No attention kind, no name."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    routed = "routed_kernel" if backend == "tpu" else "routed_rows"
    arms = lambda cfg, sizes: [expert_arm(cfg, s) for s in sizes]
    short = "capacity" if backend == "cpu" else routed
    sizes = (32, 128, 512, 768, 1024, 2048)
    lfm = PRESETS["lfm2-24b-a2b"]()
    ds = PRESETS["deepseek-moe-16b"]()
    assert arms(lfm, sizes) == arms(ds, sizes) == [short] * 3 + [routed] * 3
    joyai = PRESETS["joyai-llm-flash"]()
    assert arms(joyai, (32, 128, 160, 256, 1024)) == [routed] * 5
    assert arms(joyai.replace(expert_top_k=12), (32, 128, 160)) == [
        short, short, routed]            # 21 rows a routed row; 160 MiB
    assert arms(PRESETS["ling-3.0-flash"](experts_held=128),
                sizes) == [routed] * 6
    assert expert_arm(lfm.ffn_config(0), 4096) == "capacity"      # dense
    assert expert_arm(PRESETS["switch-base"](), 4096) == "capacity"  # drops
    assert expert_arm(ds.replace(degrade_unhealthy_experts=True),
                      4096) == "capacity"
    # float32 activations: the same bytes at half the rows
    assert arms(lfm.replace(dtype=jnp.float32), (256, 384)) == [short,
                                                                routed]
    # widths that are no whole lanes keep the plain forms on a TPU too,
    # unless the experts are STORED at whole lanes (ISSUE 39)
    odd = ds.replace(intermediate_size=1472)
    assert arms(odd, (32, 2048)) == ["capacity", "routed_rows"]
    assert arms(odd.replace(intermediate_pad=64), (32, 2048)) == [short,
                                                                  routed]


def test_both_expert_arms_compute_the_references_layer(params):
    """Whichever arm the rule picks, the mixture layer is the
    reference's: the toy's spans all fit the capacity arm's buffer, so the
    routed rows are forced here."""
    from flashmoe_tpu.ops.moe import moe_layer

    layer, x = params["layers"][2]["moe"], _x(40, 13)[0]
    want = jax.jit(lambda x: ref.ffn(layer, x, DIMS))(x)
    assert expert_arm(CFG, 40) == "capacity"
    for routed in (False, True):
        got = jax.jit(lambda x: moe_layer(layer, x, CFG, use_pallas=False,
                                          routed_rows=routed).out)(x)
        _close(got, want)


# ------------------------------------- (d) the engine against the reference

def _serve_logits(monkeypatch, params, serve, requests, cfg=CFG, **kw):
    """Run requests and keep the logits the sampler was given at every
    step, by slot: ``rows[rid]`` row j is what output token j of the
    request was sampled from, the prefill's row first."""
    rows, sampler = {}, eng._sample_dynamic
    holder = {}

    def watching(logits, *knobs):
        got = np.asarray(logits)
        for i in holder["engine"]._decoding():
            rows.setdefault(holder["engine"].slots[i].orig.rid,
                            []).append(got[i])
        return sampler(logits, *knobs)

    monkeypatch.setattr(eng, "_sample_dynamic", watching)
    holder["engine"] = engine = ServingEngine(params, cfg, serve, **kw)
    out = engine.run(requests)
    return out, {r: np.stack(v) for r, v in rows.items()}, engine


def _reference_rows(params, out, t0, n, dims=DIMS, module=ref):
    toks = jnp.asarray(out[:t0 + n - 1])
    return module.forward_logits(params, dims, toks,
                                 jnp.arange(t0 - 1, t0 + n - 1))


@pytest.mark.parametrize("chunk,t0", [(None, 21), (16, 21), (16, 70)])
def test_engine_logits_equal_the_references_full_forward(
        monkeypatch, params, chunk, t0):
    """Whole-prompt prefill, and chunked prefill with the convolution's
    inputs carried over two and over five chunks (the last one ragged),
    then 20 decode steps over the per-slot inputs and the K/V pages."""
    serve = ServeConfig(**SERVE, prefill_chunk=chunk)
    prompt = [int(t) for t in TOKENS[:t0]]
    mx = Metrics()
    out, got, engine = _serve_logits(
        monkeypatch, params, serve,
        [Request(rid=0, prompt=tuple(prompt), max_new_tokens=20)],
        metrics_obj=mx)
    assert isinstance(engine.cache, HybridCache)
    assert engine.cache._fields == ("k_pages", "v_pages", "conv")
    assert slot_state_fields(engine.cache) == (False, False, True)
    assert engine.cache.conv.shape == (3, 3, 512)
    assert len(out[0]) == t0 + 20 and out[0][:t0] == prompt
    want = _reference_rows(params, out[0], t0, 20)
    _close(got[0], want)
    assert out[0][t0:] == [int(t) for t in np.asarray(want).argmax(-1)]
    carries = -(-t0 // chunk) - 1 if chunk else 0
    assert mx.counters.get("serve.chunk_carries", 0) == carries
    assert mx.counters["serve.state_resets"] == 1


def test_generate_and_forward_equal_the_reference(params):
    """The dense cache of ``generate`` and the cacheless training forward
    run the same layers."""
    prompt = jnp.asarray(TOKENS[None, :19])
    out = np.asarray(generate(params, prompt, CFG, max_new_tokens=6))[0]
    want = _reference_rows(params, [int(t) for t in out], 19, 6)
    assert list(out[19:]) == [int(t) for t in np.asarray(want).argmax(-1)]
    logits, _ = jax.jit(lambda p, t: forward(p, t, CFG, use_pallas=False))(
        params, jnp.asarray(out[None, :24]))
    _close(logits[0], ref.forward_logits(params, DIMS, jnp.asarray(out[:24]),
                                         jnp.arange(24)), 1e-4)


# ----------------------------------- (e) a slot's state after another tenant

def _prefilled_state(engine, tokens):
    """The convolution inputs slot 0 of a one-slot ``engine`` holds after
    prefilling ``tokens``: a request of ONE new token retires in the step
    that admits it, before any decode step, and retiring touches
    nothing."""
    engine.run([Request(rid=10_000, prompt=tuple(tokens),
                        max_new_tokens=1)])
    return np.asarray(engine.cache.conv[:, 0])


def test_a_reused_slot_gives_a_fresh_engines_logits_and_state(
        monkeypatch, params):
    """Three slots, six requests of mixed lengths (whole and chunked
    prefill): every slot is reused after a finished request and each
    request's logits are those of the reference's full forward; and a
    slot's state after retire / re-admit, whole or in chunks, is a fresh
    engine's TO THE BIT."""
    serve = ServeConfig(**SERVE, prefill_chunk=16)
    lens = [(9, 5), (40, 7), (21, 4), (33, 6), (8, 9), (17, 3)]
    reqs = [Request(rid=r, prompt=tuple(int(t) for t in
                                        TOKENS[3 * r:3 * r + t0]),
                    max_new_tokens=n) for r, (t0, n) in enumerate(lens)]
    out, got, engine = _serve_logits(monkeypatch, params, serve, reqs)
    assert engine.stats["completed"] == 6 and engine.stats["max_active"] == 3
    for r, (t0, n) in enumerate(lens):
        _close(got[r], _reference_rows(params, out[r], t0, n))
    one = ServeConfig(**dict(SERVE, max_batch=1), prefill_chunk=16)
    for second in (reqs[4], reqs[1]):           # a whole prompt, 3 chunks
        used = ServingEngine(params, CFG, one)
        used.run([reqs[3]])
        assert np.asarray(used.cache.conv).any()
        np.testing.assert_array_equal(
            _prefilled_state(used, second.prompt),
            _prefilled_state(ServingEngine(params, CFG, one),
                             second.prompt))


def test_an_evicted_request_resumes_with_a_rebuilt_state(params):
    """A pool too small for three long answers: the youngest is evicted,
    requeued with what it has delivered and prefilled again, which rebuilds
    its state; the tokens are those of a pool that never evicts.  And the
    rebuilt state is a fresh run's to the bit: the step that re-admits the
    evictee prefills its resumed prompt and decodes one token, which
    shifts the newer of the two carried inputs into the older's place
    untouched, where a fresh engine's prefill of that prompt left it."""
    tight = ServeConfig(**dict(SERVE, num_pages=10))
    roomy = ServeConfig(**SERVE)
    reqs = [Request(rid=r, prompt=tuple(int(t) for t in
                                        TOKENS[5 * r:5 * r + 14]),
                    max_new_tokens=18) for r in range(3)]
    got = ServingEngine(params, CFG, tight)
    for r in reqs:
        got.submit(r)
    resumed = []
    while got.pending():
        got.step()
        resumed += [(tuple(s.req.prompt), np.asarray(got.cache.conv[:, i]))
                    for i, s in enumerate(got.slots)
                    if s is not None and s.req is not s.orig
                    and s.admit_step == got.step_idx - 1]
    out = got.outputs
    assert got.stats["evictions"] >= 1 and resumed
    assert out == ServingEngine(params, CFG, roomy).run(reqs)
    for r in range(3):
        want = _reference_rows(params, out[r], 14, 18)
        assert out[r][14:] == [int(t) for t in np.asarray(want).argmax(-1)]
    one = ServeConfig(**dict(SERVE, max_batch=1))
    for prompt, state in resumed:
        assert len(prompt) > 14                  # it carries its answer
        fresh = _prefilled_state(ServingEngine(params, CFG, one), prompt)
        np.testing.assert_array_equal(state[:, :256], fresh[:, 256:])


def test_idle_rows_leave_the_state_to_the_bit(params):
    """One request decoding among three slots: the other slots' inputs,
    set to a pattern, come through every decode step to the bit."""
    engine = ServingEngine(params, CFG, ServeConfig(**SERVE))
    mark = jnp.full((3, 2, 512), 0.25, jnp.float32)
    engine.submit(Request(rid=0, prompt=tuple(int(t) for t in TOKENS[:9]),
                          max_new_tokens=6))
    engine.step()
    slot = next(i for i, s in enumerate(engine.slots) if s is not None)
    idle = [i for i in range(3) if i != slot]
    engine.cache = engine.cache._replace(
        conv=engine.cache.conv.at[:, jnp.asarray(idle)].set(mark))
    while engine.pending():
        engine.step()
    np.testing.assert_array_equal(
        np.asarray(engine.cache.conv[:, jnp.asarray(idle)]),
        np.asarray(mark))


# ----------------------------------------------------------- (f) the refusals

def test_refusals_name_the_state_not_the_mixer(params):
    for kw, extra in ((dict(speculate=SpecConfig(draft_tokens=2)), {}),
                      (dict(ep_shards=3, num_pages=42), {}),
                      ({}, dict(prefill_fn=lambda *a, **k: None))):
        with pytest.raises(NotImplementedError, match="recurrent-state"):
            ServingEngine(params, CFG, ServeConfig(**dict(SERVE, **kw)),
                          **extra)
    from flashmoe_tpu.fabric.handoff import KVHandoff

    with pytest.raises(NotImplementedError, match="recurrent-state"):
        KVHandoff(params, CFG, 8)


@pytest.mark.parametrize("bad", [
    dict(layer_mixers=("conv", "mha")),                     # not every layer
    dict(layer_mixers=("conv", "mla", "conv", "conv", "mha")),
    dict(layer_mixers=("conv", "kda", "conv", "conv", "mha")),  # two kinds
    dict(layer_mixers=("conv", "window", "conv", "conv", "mha")),
    dict(conv_taps=1),
])
def test_config_validates_the_new_keys(bad):
    with pytest.raises(ValueError):
        CFG.replace(**bad)


# ------------------------------------------- the records, counters and names

def test_records_and_names(params):
    assert {"attn.conv_prefill", "attn.conv_decode"} <= set(SPAN_NAMES)
    rec, mx = FlightRecorder(), Metrics()
    engine = ServingEngine(params, CFG, ServeConfig(**SERVE,
                                                    prefill_chunk=16),
                           recorder=rec, metrics_obj=mx)
    engine.run([Request(rid=r, prompt=tuple(int(t) for t in TOKENS[:t0]),
                        max_new_tokens=4) for r, t0 in enumerate((9, 40))])
    slot = CFG.state_slot_bytes
    steps = [r for r in rec.records if r["kind"] == "serve_step"]
    decodes = [r for r in rec.records if r["kind"] == "serve_decode"]
    # a decode step of 3 rows x top-2 touches 1 to 6 of the 8 experts
    assert decodes and all(d["state_bytes"] == 2 * 3 * slot
                           and 1 <= d["experts_touched"] <= 6
                           and "held_rows" not in d
                           and d["attn_arm"] == "gather"
                           for d in decodes)
    # K and V of 2 heads of 64 in each of the 2 layers that cache
    assert steps[0]["kv_token_bytes"] == CFG.kv_token_bytes == 2 * 256 * 4
    assert steps[0]["state_bytes"] == slot + 2 * slot + 2 * 3 * slot
    assert mx.counters["serve.state_resets"] == 2
    assert mx.counters["serve.chunk_carries"] == 2
    lower = lambda fn, *a: fn.lower(params, CFG, init_paged_cache(
        CFG, 40, 8, 3), *a).as_text(debug_info=True)
    text = lower(eng._paged_decode_step, jnp.zeros((3,), jnp.int32),
                 jnp.zeros((3, 3), jnp.int32), jnp.zeros((3,), jnp.int32))
    assert "attn.conv_decode" in text and "attn.conv_prefill" not in text
    text = lower(eng._prefill_chunk, jnp.zeros((1, 16), jnp.int32),
                 jnp.zeros((3,), jnp.int32), jnp.zeros((2,), jnp.int32),
                 jnp.int32(0), jnp.int32(3))
    assert "attn.conv_prefill" in text


# ---------------- (g) the case the cache manager refused: 'kda' beside K/V

def test_delta_rule_layers_beside_kv_layers_are_built(monkeypatch):
    """A toy 'kda' + 'mha' config (the case ``cache_arrays`` raised
    NotImplementedError for) runs through the engine, whole and chunked
    prefill then decode, and agrees with its layers' references: the
    delta-rule layers of ``reference_ling3`` and the attention layer of
    this file's reference, composed here."""
    ling = _load(os.path.join(ROOT, "benchmark", "lib",
                              "reference_ling3.py"),
                 "benchlib_reference_ling3")
    kinds = ("kda", "mha", "kda")
    cfg = PRESETS["deepseek-moe-16b"](
        num_layers=3, layer_mixers=kinds, hidden_size=128,
        intermediate_size=64, num_experts=8, expert_top_k=2,
        num_shared_experts=0, vocab_size=256, num_heads=4, num_kv_heads=2,
        kda_heads=2, kda_head_dim=16, dtype=jnp.float32,
        param_dtype=jnp.float32)
    weights = init_params(jax.random.PRNGKey(11), cfg)
    cache = init_paged_cache(cfg, 40, 8, 3)
    assert cache._fields == ("k_pages", "v_pages", "state", "conv")
    assert slot_state_fields(cache) == (False, False, True, True)
    assert cache.k_pages.shape[0] == 1 and cache.state.shape[:2] == (2, 3)
    kd = {"heads": 2, "kda_dim": 16, "conv": 4, "lower": -5.0, "eps": 1e-6}

    def plain_attention(layer, x):
        """ref.attention without the heads' norms (this config has none):
        4 query heads over 2 K/V heads of 32, theta 1e4."""
        q = ref._mm(x, layer["wq"], None).reshape(-1, 4, 32)
        k = ref._mm(x, layer["wk"], None).reshape(-1, 2, 32)
        v = ref._mm(x, layer["wv"], None).reshape(-1, 2, 32)
        pos = jnp.arange(x.shape[0])
        q, k = (ref._rope(a, pos, 10000.0) for a in (q, k))
        k, v = (jnp.repeat(a, 2, axis=1) for a in (k, v))
        s = jnp.einsum("tnd,snd->nts", q, k, precision=ref.HIGHEST) / 32 ** .5
        s = jnp.where(pos[None, None, :] <= pos[None, :, None], s, -1e30)
        ctx = jnp.einsum("nts,snd->tnd", jax.nn.softmax(s, -1), v,
                         precision=ref.HIGHEST)
        return ref._mm(ctx.reshape(-1, 128), layer["wo"], None)

    def softmax_ffn(p, x):
        probs = jax.nn.softmax(jnp.dot(x, p["gate_w"],
                                       precision=ref.HIGHEST), -1)
        top, idx = jax.lax.top_k(probs, 2)
        cw = jnp.einsum("tk,tke->te", top / top.sum(-1, keepdims=True),
                        jax.nn.one_hot(idx, 8))
        return sum(cw[:, e][:, None] * ref._swiglu(
            x, p["w_gate"][e], p["w_up"][e], p["w_down"][e], None)
            for e in range(8))

    @jax.jit
    def want_logits(tokens):
        x = weights["embed"][tokens]
        for layer, kind in zip(weights["layers"], kinds):
            u = ref._rms(x, layer["attn_norm"], 1e-6)
            x = x + (ling.kda(layer, u, kd)[0] if kind == "kda"
                     else plain_attention(layer, u))
            x = x + softmax_ffn(layer["moe"],
                                ref._rms(x, layer["ffn_norm"], 1e-6))
        return jnp.dot(ref._rms(x, weights["final_norm"], 1e-6),
                       weights["lm_head"], precision=ref.HIGHEST)

    for chunk, t0 in ((None, 21), (16, 37)):
        prompt = [int(t) for t in TOKENS[:t0]]
        out, got, engine = _serve_logits(
            monkeypatch, weights, ServeConfig(**SERVE, prefill_chunk=chunk),
            [Request(rid=0, prompt=tuple(prompt), max_new_tokens=10)],
            cfg=cfg)
        assert isinstance(engine.cache, HybridCache)
        want = want_logits(jnp.asarray(out[0][:t0 + 9]))[t0 - 1:]
        _close(got[0], want)
