"""Ling-3.0-flash's block against the plain reference
(``benchmark/lib/reference_ling3.py``), at tiny sizes on the CPU, float32,
seeded random weights: the delta rule in its two forms, the per-slot state
under the serving engine beside the latent pages (whole and chunked
prefill, decode, slot reuse, eviction), group-limited routing, one chip's
share of the experts, the refusals, and that configurations without any of
it serve what they served.

Tolerances.  As ``tests/test_mla.py``: the program and the reference
compute the same float32 products in different orders; ``TIGHT`` (2e-5 of
the compared values' scale) has a factor of ten over the largest reading
seen, and anything left out of the mathematics (a decay, the convolution's
carried inputs, a group's mask, the 2.5) moves a logit by 1e-2 or more.
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
from flashmoe_tpu.ops import kda
from flashmoe_tpu.ops.gate import router_xla
from flashmoe_tpu.ops.moe import moe_layer
from flashmoe_tpu.serving import engine as eng
from flashmoe_tpu.serving.engine import Request, ServeConfig, ServingEngine
from flashmoe_tpu.serving.kvcache import HybridCache, init_paged_cache
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


ref = _load(os.path.join(ROOT, "benchmark", "lib", "reference_ling3.py"),
            "benchlib_reference_ling3")

# a dense 'kda' layer, a mixture 'kda' layer, a mixture 'mla' layer; 16
# experts in 4 groups of which 2 are kept, and the SECOND group held here
KINDS = ("kda", "kda", "mla")
TINY = dict(num_layers=3, layer_mixers=KINDS, first_k_dense=1,
            hidden_size=64, intermediate_size=64,
            dense_intermediate_size=128, num_experts=16, expert_top_k=3,
            n_group=4, topk_group=2, expert_first=4, experts_held=4,
            vocab_size=256, num_heads=3, kda_heads=3, kda_head_dim=16,
            kv_lora_rank=20, qk_nope_head_dim=10, qk_rope_head_dim=6,
            v_head_dim=14, dtype=jnp.float32, param_dtype=jnp.float32)
CFG = PRESETS["ling-3.0-flash"](**TINY)
MODEL = {  # the same sizes under the published key names
    "hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 3,
    "head_dim": 16, "q_lora_rank": None, "kv_lora_rank": 20,
    "qk_nope_head_dim": 10, "qk_rope_head_dim": 6, "v_head_dim": 14,
    "vocab_size": 256, "num_experts": 4, "num_experts_per_tok": 3,
    "n_group": 4, "topk_group": 2, "moe_intermediate_size": 64,
    "intermediate_size": 128, "first_k_dense_replace": 1,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "score_function": "sigmoid", "rope_theta": 6000000,
    "rms_norm_eps": 1e-6, "short_conv_kernel_size": 4,
    "kda_lower_bound": -5}
FILE = {"model": MODEL, "layer_kinds": list(KINDS),
        "published": {"num_experts": 16}, "held": {"expert_first": 4},
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
        for j, name in enumerate(("attn_norm", "ffn_norm", "kda_norm",
                                  "kv_a_norm")):
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
    return jax.random.normal(jax.random.PRNGKey(seed), (b, t, 64),
                             jnp.float32)


def test_params_have_the_programs_tree(params):
    mine = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), CFG))
    assert (jax.tree.map(lambda a: (a.shape, a.dtype), mine)
            == jax.tree.map(lambda a: (a.shape, a.dtype), params))
    assert CFG.mixers == KINDS and CFG.cache_layers == (2,)
    assert CFG.state_layers == (0, 1)
    # the published rule: the last layer of a group of 6 is the full one
    full = PRESETS["ling-3.0-flash"]()
    assert [li for li, m in enumerate(full.mixers) if m == "mla"] == [
        5, 11, 17, 23, 29, 35, 41]
    assert full.mixers.count("kda") == 35 and len(full.moe_layer_indices) == 40


# --------------------------------------------- (a) the delta rule's two forms

_attn = jax.jit(kda.kda_attention, static_argnums=(2, 5))


@jax.jit
def _token_by_token(layer, x, state, conv):
    """The same function one token at a time (its T = 1 form), layer 1."""
    def step(carry, x_t):
        o, s, c, _ = kda.kda_attention(layer, x_t[:, None], CFG, *carry, 1)
        return (s, c), o[:, 0]

    (state, conv), o = jax.lax.scan(step, (state, conv), x.swapaxes(0, 1))
    return o.swapaxes(0, 1), state, conv


def _state(b=1):
    return (jnp.zeros((2, b, 3, 16, 16), jnp.float32),
            jnp.zeros((2, b, 3 * 3 * 3 * 16), jnp.float32))


@pytest.mark.parametrize("t", [5, 64, 150])
def test_chunkwise_form_equals_the_recurrence_and_the_reference(params, t):
    """A span at once (the chunkwise form: whole chunks, a ragged last
    one, fewer tokens than a sub-chunk) against the same tokens one step
    at a time through the same function, and both against the
    reference's scan: output, state and the convolution's carried
    inputs."""
    layer, x = params["layers"][1], _x(t, b=2)
    state, conv = _state(2)
    at_once, s1, c1, _ = _attn(layer, x, CFG, state, conv, 1)
    steps, s2, c2 = _token_by_token(layer, x, state, conv)
    _close(at_once, steps)
    _close(s1[1], s2[1])
    _close(c1, c2)
    assert not np.asarray(s1[0]).any()          # the other layer's state
    for row in range(2):
        out, s_ref = jax.jit(lambda x: ref.kda(layer, x, DIMS))(x[row])
        _close(at_once[row], out)
        _close(s1[1, row], s_ref)
        # the reference's own pads leave its state alone
        _close(s_ref, jax.jit(lambda x: ref.kda(
            layer, x, DIMS, n_valid=t))(jnp.pad(x[row], ((0, 7), (0, 0))))[1])


def test_a_padded_position_leaves_the_state_untouched(params):
    """Whatever stands past a row's valid prefix, the state and the
    carried inputs are the same TO THE BIT; a row with nothing valid (an
    idle slot of the decode step, a slot between two chunks) keeps what it
    had to the bit; and the valid prefix alone gives the same numbers."""
    layer = params["layers"][0]
    state, conv = _state(2)
    _, state, conv, _ = _attn(layer, _x(9, 2, b=2), CFG, state,
                                          conv, 0)
    x, junk = _x(40, 3, b=2), _x(40, 4, b=2)
    valid = jnp.arange(40)[None, :] < jnp.asarray([[23], [0]])
    mixed = jnp.where(valid[:, :, None], x, junk)
    o1, s1, c1, _ = _attn(layer, x, CFG, state, conv, 0, valid)
    o2, s2, c2, _ = _attn(layer, mixed, CFG, state, conv, 0,
                                      valid)
    for a, b in ((s1, s2), (c1, c2), (o1[0, :23], o2[0, :23])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(s1[:, 1]),
                                  np.asarray(state[:, 1]))
    np.testing.assert_array_equal(np.asarray(c1[:, 1]),
                                  np.asarray(conv[:, 1]))
    _, s3, c3, _ = _attn(layer, x[:1, :23], CFG, state[:, :1],
                                     conv[:, :1], 0)
    _close(s1[0, 0], s3[0, 0])
    _close(c1[0, 0], c3[0, 0])
    # one step: the idle row of a decode batch
    step_valid = jnp.asarray([[True], [False]])
    _, s4, c4, _ = _attn(layer, x[:, :1], CFG, s1, c1, 0,
                                     step_valid)
    np.testing.assert_array_equal(np.asarray(s4[:, 1]), np.asarray(s1[:, 1]))
    np.testing.assert_array_equal(np.asarray(c4[:, 1]), np.asarray(c1[:, 1]))
    assert np.asarray(s4[0, 0] != s1[0, 0]).any()


def test_slots_and_a_fresh_start(params):
    """A chunk addresses its slot's state among many, and a prompt's first
    chunk starts from nothing whatever the slot holds."""
    layer = params["layers"][0]
    state, conv = _state(3)
    state, conv = state + 1.0, conv + 1.0
    x = _x(24, 7)
    slots = jnp.asarray([2])
    o, s1, c1, _ = _attn(layer, x, CFG, state, conv, 0,
                                     slots=slots, fresh=jnp.bool_(True))
    want, s0, c0, _ = _attn(layer, x, CFG, *_state(1), 0)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(s1[0, 2]), np.asarray(s0[0, 0]))
    np.testing.assert_array_equal(np.asarray(c1[0, 2]), np.asarray(c0[0, 0]))
    np.testing.assert_array_equal(np.asarray(s1[:, :2]),
                                  np.asarray(state[:, :2]))
    carried, _, _, _ = _attn(layer, x, CFG, state, conv, 0,
                                         slots=slots, fresh=jnp.bool_(False))
    assert np.abs(np.asarray(carried - want)).max() > 1e-3


# ------------------------------------------------- (d) the router, on groups

def _router_case(t=64):
    x = _x(t, 11)[0]
    w = jax.random.normal(jax.random.PRNGKey(12), (64, 16)) / 8.0
    b = 0.05 * jax.random.normal(jax.random.PRNGKey(13), (16,))
    return x, w, b


def test_router_equals_the_reference_on_groups():
    x, w, b = _router_case()
    r = router_xla(x, w, CFG, gate_bias=b)
    want_cw, want_idx = ref.router_weights(x, w, b, DIMS)
    assert (np.sort(np.asarray(r.expert_idx), -1)
            == np.sort(np.asarray(want_idx), -1)).all()
    got_cw = jnp.einsum("tk,tke->te", r.combine_weights,
                        jax.nn.one_hot(r.expert_idx, 16))
    _close(got_cw, want_cw)
    # the chosen experts of a token lie in at most two groups, and the
    # limit changes the choice of some tokens
    groups = np.asarray(r.expert_idx) // 4
    assert max(len(set(g)) for g in groups) <= 2
    free = router_xla(x, w, CFG.replace(n_group=1, topk_group=1),
                      gate_bias=b)
    assert (np.sort(np.asarray(free.expert_idx), -1)
            != np.sort(np.asarray(r.expert_idx), -1)).any()
    np.testing.assert_array_equal(
        np.asarray(r.expert_counts),
        np.bincount(np.asarray(r.expert_idx).ravel(), minlength=16))


@pytest.mark.parametrize("score,bias", [("sigmoid", True), ("sigmoid", False),
                                        ("softmax", False)])
def test_one_group_routes_as_before_to_the_bit(score, bias):
    """``n_group`` 1 is the rule the router had before groups: held
    against that rule written out here."""
    x, w, b = _router_case()
    cfg = CFG.replace(n_group=1, topk_group=1, router_score=score,
                      router_bias=bias)
    r = router_xla(x, w, cfg, gate_bias=b if bias else None)
    logits = jnp.dot(x, w, preferred_element_type=jnp.float32)
    if score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + b[None, :] if bias else scores, 3)
        top = jnp.take_along_axis(scores, idx, axis=-1)
    else:
        top, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), 3)
    want = top / jnp.maximum(jnp.sum(top, -1, keepdims=True), 1e-20) * 2.5
    np.testing.assert_array_equal(np.asarray(r.expert_idx), np.asarray(idx))
    np.testing.assert_array_equal(np.asarray(r.combine_weights),
                                  np.asarray(want))


# ------------------------------------------ (e) one chip's share of the experts

_routed = jax.jit(lambda p, x, cfg: moe_layer(p, x, cfg, use_pallas=False,
                                               routed_rows=True),
                  static_argnums=2)


def test_the_four_shares_add_up_to_the_uncut_layer(params):
    """Each of four chips holds one routing group of the 16 experts,
    routes over all of them and computes its own experts' rows; the shared
    expert is computed by every chip alike.  The four partial results,
    the shared expert counted once, are the uncut layer's result; and a
    share is what the reference gives for the same share."""
    key = jax.random.PRNGKey(21)
    whole_cfg = CFG.replace(expert_first=0, experts_held=0)
    whole = init_params(key, whole_cfg)["layers"][1]["moe"]
    x = _x(48, 22)[0]
    full = _routed(whole, x, whole_cfg).out
    parts, dims = [], dict(DIMS, router_experts=16)
    for chip in range(4):
        cfg = CFG.replace(expert_first=4 * chip, experts_held=4)
        mine = {k: (v[4 * chip:4 * chip + 4]
                    if k in ("w_up", "b_up", "w_down", "b_down", "w_gate")
                    else v) for k, v in whole.items()}
        out = _routed(mine, x, cfg)
        parts.append(out.out)
        _close(out.out, jax.jit(lambda p, x, first=4 * chip: ref.ffn(
            p, x, dict(dims, expert_first=first)))(mine, x))
        assert int(out.expert_counts.sum()) == 48 * 3   # routed over all
    once = ref._swiglu(x, whole["shared_w_gate"], whole["shared_w_up"],
                       whole["shared_w_down"], None)
    _close(sum(parts) - 3 * once, full)
    # every arm but the routed rows indexes all experts' weights
    with pytest.raises(NotImplementedError, match="routed_rows"):
        moe_layer(mine, x, cfg, use_pallas=False)


# ------------------------------------- (b) the engine against the reference

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


def _reference_rows(params, out, t0, n):
    toks = jnp.asarray(out[:t0 + n - 1])
    return ref.forward_logits(params, DIMS, toks,
                              jnp.arange(t0 - 1, t0 + n - 1))


@pytest.mark.parametrize("chunk,t0", [(None, 21), (16, 21), (16, 70)])
def test_engine_logits_equal_the_references_full_forward(
        monkeypatch, params, chunk, t0):
    """Whole-prompt prefill, and chunked prefill with the state carried
    over two and over five chunks (the last one ragged), then 20 decode
    steps over the per-slot state and the latent pages."""
    serve = ServeConfig(**SERVE, prefill_chunk=chunk)
    prompt = [int(t) for t in TOKENS[:t0]]
    mx = Metrics()
    out, got, engine = _serve_logits(
        monkeypatch, params, serve,
        [Request(rid=0, prompt=tuple(prompt), max_new_tokens=20)],
        metrics_obj=mx)
    assert isinstance(engine.cache, HybridCache)
    assert len(out[0]) == t0 + 20 and out[0][:t0] == prompt
    want = _reference_rows(params, out[0], t0, 20)
    _close(got[0], want)
    assert out[0][t0:] == [int(t) for t in np.asarray(want).argmax(-1)]
    carries = -(-t0 // chunk) - 1 if chunk else 0
    assert mx.counters.get("serve.chunk_carries", 0) == carries
    assert mx.counters["serve.state_resets"] == 1


@pytest.mark.parametrize("chunk", [None, 16])
def test_slots_in_flight_hold_the_references_state(params, chunk):
    """What the benchmark's state comparison reads
    (``benchmark/drivers/serve_state.py``): three requests stopped in
    flight, each slot's state of every 'kda' layer against the
    reference's recurrence over the tokens the slot has consumed; and a
    state rounded to bfloat16 every token reads a hundred times that."""
    driver = _load(os.path.join(ROOT, "benchmark", "drivers",
                                "serve_state.py"), "benchdriver_serve_state")
    engine = ServingEngine(params, CFG,
                           ServeConfig(**SERVE, prefill_chunk=chunk))
    for rid, t0 in enumerate((9, 21, 40)):
        engine.submit(Request(rid=rid, prompt=tuple(
            int(t) for t in TOKENS[rid:rid + t0]), max_new_tokens=40))
    for _ in range(12):
        engine.step()
    streams = driver._slot_states(engine, 3, True)
    assert len(streams) == 3 and len(streams[0][1]) == 2
    assert sorted(len(t) for t, _ in streams) == [
        s.length for s in sorted(engine.slots, key=lambda s: s.length)]
    # the first stream brings both state layers, the others the first
    got = ref.state_gaps(params, DIMS, streams[:1], 64, layers=2)
    assert max(got["per_stream"][0]) <= TIGHT
    got = ref.state_gaps(params, DIMS, streams, 64)
    assert got["widest"] <= TIGHT and len(got["per_stream"]) == 3
    low = ref.state_gaps(params, DIMS, streams, 64, control="bfloat16")
    assert low["widest"] > 100 * TIGHT


def test_generate_and_forward_equal_the_reference(params):
    """The dense cache of ``generate`` and the cacheless training forward
    run the same layers."""
    prompt = jnp.asarray(TOKENS[None, :19])
    out = np.asarray(generate(params, prompt, CFG, max_new_tokens=6))[0]
    want = _reference_rows(params, [int(t) for t in out], 19, 6)
    assert list(out[19:]) == [int(t) for t in np.asarray(want).argmax(-1)]
    # the training forward computes every expert: the uncut layer
    whole_cfg = CFG.replace(expert_first=0, experts_held=0)
    whole = init_params(jax.random.PRNGKey(2), whole_cfg)
    logits, _ = jax.jit(lambda p, t: forward(p, t, whole_cfg,
                                             use_pallas=False))(
        whole, jnp.asarray(out[None, :24]))
    dims = dict(DIMS, experts=16, expert_first=0)
    for layer in whole["layers"]:       # init_params leaves these at zero
        layer.get("moe", {}).setdefault("gate_bias", jnp.zeros((1,)))
    _close(logits[0], ref.forward_logits(whole, dims, jnp.asarray(out[:24]),
                                         jnp.arange(24)), 1e-4)


# ----------------------------------- (c) a slot's state after another tenant

def test_a_reused_slot_gives_a_fresh_engines_logits(monkeypatch, params):
    """Three slots, six requests of mixed lengths (whole and chunked
    prefill): every slot is reused after a finished request, and each
    request's logits are those of the reference's full forward, as a
    fresh engine's would be."""
    serve = ServeConfig(**SERVE, prefill_chunk=16)
    lens = [(9, 5), (40, 7), (21, 4), (33, 6), (8, 9), (17, 3)]
    reqs = [Request(rid=r, prompt=tuple(int(t) for t in
                                        TOKENS[3 * r:3 * r + t0]),
                    max_new_tokens=n) for r, (t0, n) in enumerate(lens)]
    out, got, engine = _serve_logits(monkeypatch, params, serve, reqs)
    assert engine.stats["completed"] == 6 and engine.stats["max_active"] == 3
    for r, (t0, n) in enumerate(lens):
        _close(got[r], _reference_rows(params, out[r], t0, n))


def test_an_evicted_request_resumes_with_a_rebuilt_state(monkeypatch,
                                                         params):
    """A pool too small for three long answers: the youngest is evicted,
    requeued with what it has delivered and prefilled again, which rebuilds
    its state; the tokens are those of a pool that never evicts."""
    tight = ServeConfig(**dict(SERVE, num_pages=10), prefill_chunk=16)
    roomy = ServeConfig(**SERVE, prefill_chunk=16)
    reqs = [Request(rid=r, prompt=tuple(int(t) for t in
                                        TOKENS[5 * r:5 * r + 14]),
                    max_new_tokens=18) for r in range(3)]
    got = ServingEngine(params, CFG, tight)
    out = got.run(reqs)
    assert got.stats["evictions"] >= 1
    assert out == ServingEngine(params, CFG, roomy).run(reqs)
    for r in range(3):
        want = _reference_rows(params, out[r], 14, 18)
        assert out[r][14:] == [int(t) for t in np.asarray(want).argmax(-1)]


# ----------------------------------------------------------- (f) the refusals

def test_refusals_name_the_recurrent_state(params):
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
    dict(layer_mixers=("kda", "mla")),                # not every layer
    dict(layer_mixers=("kda", "kda", "mha")),         # not attention_kind
    dict(kda_heads=0), dict(kda_lower_bound=-6.0), dict(kda_lower_bound=0.0),
    dict(n_group=3), dict(topk_group=5), dict(expert_top_k=9),
    dict(expert_first=14), dict(ep=2),
])
def test_config_validates_the_new_keys(bad):
    with pytest.raises(ValueError):
        CFG.replace(**bad)


# ------------------------------------------- the records, counters and names

def test_records_and_names(params):
    assert {"attn.kda_prefill", "attn.kda_decode",
            "moe.route_groups"} <= set(SPAN_NAMES)
    rec, mx = FlightRecorder(), Metrics()
    engine = ServingEngine(params, CFG, ServeConfig(**SERVE,
                                                    prefill_chunk=16),
                           recorder=rec, metrics_obj=mx)
    engine.run([Request(rid=r, prompt=tuple(int(t) for t in TOKENS[:t0]),
                        max_new_tokens=4) for r, t0 in enumerate((9, 40))])
    slot = CFG.state_slot_bytes
    assert slot == 2 * (3 * 16 * 16 * 4 + 3 * 144 * 4)
    steps = [r for r in rec.records if r["kind"] == "serve_step"]
    decodes = [r for r in rec.records if r["kind"] == "serve_decode"]
    assert decodes and all(d["state_bytes"] == 2 * 3 * slot
                           and 0 <= d["held_rows"] <= 3 * 3
                           for d in decodes)
    assert max(d["held_rows"] for d in decodes) > 0
    # one latent row a token, in the ONE layer that caches, as the pool
    # stores it (26 elements in 128 lanes)
    assert CFG.kv_token_bytes == 26 * 4
    assert steps[0]["kv_token_bytes"] == CFG.kv_pool_token_bytes == 128 * 4
    # the first step: a whole prefill writes a slot, a chunk reads and
    # writes one, the decode step all three
    assert steps[0]["state_bytes"] == slot + 2 * slot + 2 * 3 * slot
    assert mx.counters["serve.state_resets"] == 2
    assert mx.counters["serve.chunk_carries"] == 2
    text = eng._paged_decode_step.lower(
        params, CFG, init_paged_cache(CFG, 40, 8, 3), jnp.zeros((3,), jnp.int32),
        jnp.zeros((3, 3), jnp.int32), jnp.zeros((3,), jnp.int32)
    ).as_text(debug_info=True)
    for name in ("attn.kda_decode", "moe.route_groups", "attn.mla_decode"):
        assert name in text


# ------------------- (g) configurations without any of it serve what they did

GOLDEN = {  # the parent commit's tokens (59eb853), this traffic, this seed
    "mha": {0: [18, 85, 6, 41, 241], 1: [52, 205, 31, 197, 51, 57, 167],
            2: [191, 46, 191, 88], 3: [187, 57, 14, 28, 22, 84],
            4: [105, 191, 46, 158, 46, 216, 124, 191, 254]},
    "mla": {0: [243, 171, 151, 58, 72], 1: [52, 11, 151, 105, 242, 42, 22],
            2: [55, 169, 19, 218], 3: [16, 243, 202, 132, 22, 188],
            4: [72, 119, 6, 6, 6, 6, 149, 80, 75]},
}


@pytest.mark.parametrize("kind", ["mha", "mla"])
def test_one_kind_engines_serve_the_parents_tokens(kind):
    """A K/V-only and a latent-only engine, whole and chunked prefill,
    greedy and drawn rows, slots reused: token for token what the commit
    before the per-layer mixer served."""
    if kind == "mha":
        cfg = PRESETS["deepseek-moe-16b"](
            num_layers=2, hidden_size=64, intermediate_size=64,
            num_experts=8, expert_top_k=2, vocab_size=256, num_heads=4,
            dtype=jnp.float32)
    else:
        cfg = PRESETS["joyai-llm-flash"](
            num_layers=3, hidden_size=64, intermediate_size=64,
            dense_intermediate_size=128, num_experts=8, expert_top_k=2,
            vocab_size=256, num_heads=3, q_lora_rank=24, kv_lora_rank=20,
            qk_nope_head_dim=10, qk_rope_head_dim=6, v_head_dim=14,
            dtype=jnp.float32, param_dtype=jnp.float32)
    weights = init_params(jax.random.PRNGKey(7), cfg)
    serve = ServeConfig(**SERVE, prefill_chunk=16)
    lens = [(9, 5), (40, 7), (21, 4), (33, 6), (8, 9)]
    reqs = [Request(rid=r, prompt=tuple(int(t) for t in
                                        TOKENS[3 * r:3 * r + t0]),
                    max_new_tokens=n, temperature=0.0 if r % 2 == 0 else 0.8,
                    top_k=0 if r < 3 else 5, seed=r)
            for r, (t0, n) in enumerate(lens)]
    out = ServingEngine(weights, cfg, serve).run(reqs)
    assert {r: out[r][-n:] for r, (_, n) in enumerate(lens)} == GOLDEN[kind]
