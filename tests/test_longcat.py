"""LongCat-Flash-Omni's language layer against the plain reference
(``benchmark/lib/reference_longcat.py``), at tiny sizes on the CPU, float32,
seeded random weights, the odd sizes kept: more router outputs (12) than
FFN experts (8), a top-k (5) larger than the experts held (2), two latent
attention sublayers to one mixture, both rank scales other than 1 (2 and
sqrt 2).  The shortcut-connected mixture (read at one sublayer, joined
after the next one's dense FFN) under the one layer loop: the no-cache
forward, ``generate`` and the serving engine's paged cache, whole and in
chunks; zero-compute identity experts through both forms of the routed
rows; the counts; the shares; the refusals.

Tolerances.  As ``tests/test_nemotron3.py``: the program and the reference
compute the same float32 products in different orders (the sort's rows
against a dense weight matrix, the absorbed attention against the
decompressed one); ``TIGHT`` (2e-5 of the compared values' scale) has a
factor of ten over the largest reading seen (1.9e-6 on logits), and each
mistake the reference can be told to make (the mixture joined a sublayer
early, the identity term dropped, a rank scale left out) moves a logit by
0.6-1.1 of the scale at these sizes (the test asks for 150 x ``TIGHT``).
"""

from __future__ import annotations

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashmoe_tpu.config import FFN_PARTS, MoEConfig
from flashmoe_tpu.models.generate import generate, span_forward
from flashmoe_tpu.models.presets import PRESETS
from flashmoe_tpu.models.reference import init_moe_params
from flashmoe_tpu.models.transformer import forward, init_params
from flashmoe_tpu.ops import gate, moe
from flashmoe_tpu.ops.gate import RouterOutput
from flashmoe_tpu.parallel.mesh import transformer_param_specs
from flashmoe_tpu.serving import engine as eng
from flashmoe_tpu.serving.engine import Request, ServeConfig, ServingEngine
from flashmoe_tpu.serving.kvcache import LatentPagedCache
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


ref = _load(os.path.join(ROOT, "benchmark", "lib", "reference_longcat.py"),
            "benchlib_reference_longcat")

# two published layers = four sublayers; this "chip" holds FFN experts 2-3
# of 8 and every one of the 4 identity experts
TINY = dict(num_layers=4, hidden_size=64, intermediate_size=64,
            dense_intermediate_size=128, num_experts=8, zero_experts=4,
            expert_top_k=5, expert_first=2, experts_held=2, num_heads=4,
            q_lora_rank=16, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, vocab_size=256,
            dtype=jnp.float32, param_dtype=jnp.float32)
CFG = PRESETS["longcat-flash"](**TINY)
FILE = {  # the same sizes under the published key names
    "hidden_size": 64, "num_layers": 2, "num_attention_heads": 4,
    "q_lora_rank": 16, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "vocab_size": 256, "n_routed_experts": 2,
    "zero_expert_num": 4, "zero_expert_type": "identity", "moe_topk": 5,
    "expert_ffn_hidden_size": 64, "ffn_hidden_size": 128,
    "routed_scaling_factor": 6, "rope_theta": 10000000,
    "rms_norm_eps": 1e-5, "attention_method": "MLA",
    "published": {"n_routed_experts": 8}, "held": {"expert_first": 2},
    "served": {"param_dtype": "float32"}}
DIMS = ref.model_dims(FILE)
SERVE = dict(max_batch=3, page_size=8, num_pages=40, max_pages_per_slot=12,
             ctx_bucket_pages=3, prompt_bucket=8)
TOKENS = np.random.default_rng(5).integers(1, 256, 200)
MISTAKES = ("early_join", "no_zero", "no_scale_q", "no_scale_kv")


@pytest.fixture(scope="module")
def params():
    """The reference's weights (its tree layout IS the program's, the
    selection bias fitted), the norms moved off one so that one left out
    shows."""
    p = ref.make_params(1234567891011, DIMS)
    key = jax.random.PRNGKey(3)
    for li, layer in enumerate(p["layers"]):
        for j, name in enumerate(("attn_norm", "ffn_norm", "q_a_norm",
                                  "kv_a_norm")):
            k = jax.random.fold_in(key, 10 * li + j)
            layer[name] = 1.0 + 0.1 * jax.random.normal(
                k, layer[name].shape, jnp.float32)
    return p


def _close(got, want, tol=TIGHT):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= tol * max(np.abs(want).max(), 1e-30)


def _x(t, seed=1, h=64):
    return jax.random.normal(jax.random.PRNGKey(seed), (t, h), jnp.float32)


# ------------------------------------------------ (a) the layers' description

def test_a_branch_is_read_at_one_layer_and_joins_at_the_next(params):
    assert CFG.layers == (("mla", "dense+moe"), ("mla", "dense+join")) * 2
    assert CFG.moe_layer_indices == (0, 2)
    assert CFG.cache_layers == (0, 1, 2, 3)       # every sublayer caches
    assert CFG.router_width == 12 and CFG.kv_pool_rows == (1, 1, 128)
    assert CFG.ffn_config(0).num_experts == 1
    assert CFG.ffn_config(0).intermediate_size == 128
    assert CFG.ffn_config(0, branch=True) is CFG
    assert CFG.ffn_config(1).zero_experts == 0
    own = init_params(jax.random.PRNGKey(0), CFG)
    shapes = lambda tree: jax.tree_util.tree_map(lambda a: a.shape, tree)
    assert shapes(own) == shapes(params)          # the reference's tree
    reads, joins = own["layers"][0], own["layers"][1]
    assert "branch" in reads and "branch" not in joins
    assert reads["branch"]["gate_w"].shape == (64, 12)
    assert reads["branch"]["gate_bias"].shape == (12,)
    assert reads["branch"]["w_up"].shape == (2, 64, 64)   # the FFN experts held
    assert reads["moe"]["w_up"].shape == (1, 64, 128)
    specs = transformer_param_specs(CFG)["layers"]
    assert set(specs[0]["branch"]) == set(specs[0]["moe"]) - {
        "shared_w_up"} and "branch" not in specs[1]
    # two mixers and three feed-forward parts a published layer
    h, i, v = 64, 64, 256
    assert CFG.param_count == 2 * v * h + 4 * 4 * h * h + 6 * 2 * h * i


def test_earlier_configs_describe_their_layers_as_before():
    cfg = PRESETS["joyai-llm-flash"](num_layers=3)
    assert cfg.layers == (("mla", "dense"), ("mla", "moe"), ("mla", "moe"))
    assert cfg.router_width == cfg.num_experts == 256
    assert cfg.ffn_config(0).num_experts == 1 and cfg.ffn_config(1) is cfg
    assert set(FFN_PARTS) == {None, "moe", "dense", "dense+moe",
                              "dense+join"}


@pytest.mark.parametrize("bad,error", [
    (dict(layer_ffns=("dense+moe", "dense", "dense", "dense")), ValueError),
    (dict(layer_ffns=("dense+moe", "dense+join", "dense+join", "dense")),
     ValueError),
    (dict(layer_ffns=("dense+join", "dense+moe", "dense+join", "dense")),
     ValueError),
    (dict(layer_ffns=("dense+moe", "dense+moe", "dense+join",
                      "dense+join")), ValueError),
    (dict(layer_ffns=("dense+moe", "moe+join", "dense", "dense")),
     ValueError),
    (dict(expert_top_k=13), ValueError),
    (dict(zero_experts=-1), ValueError),
    (dict(drop_tokens=True), ValueError),
    (dict(n_group=2, topk_group=2), ValueError),
    (dict(collect_stats=True), ValueError),
    (dict(ep=2, experts_held=0, expert_first=0), NotImplementedError),
    (dict(is_training=True), NotImplementedError),
], ids=["never_joins", "joins_twice", "joins_before_read", "two_open",
        "unknown_name", "top_k_over_width", "negative", "drops",
        "groups", "stats", "ep", "training"])
def test_config_refuses_by_name(bad, error):
    with pytest.raises(error):
        PRESETS["longcat-flash"](**dict(TINY, **bad))


def test_the_arms_that_cannot_express_identity_experts_refuse(params):
    """The router for a caller that adds no identity term (the mesh
    layers, the capacity arm), the Pallas routers, the pipeline."""
    layer = params["layers"][0]["branch"]
    x = _x(8)
    with pytest.raises(NotImplementedError, match="zero-compute"):
        gate.router(x, layer["gate_w"], CFG, use_pallas=False,
                    gate_bias=layer["gate_bias"])
    with pytest.raises(NotImplementedError, match="zero-compute"):
        gate.router(x, layer["gate_w"], CFG, use_pallas=True,
                    gate_bias=layer["gate_bias"], zero_ok=True)
    whole = CFG.replace(experts_held=0, expert_first=0)
    wide = init_moe_params(jax.random.PRNGKey(0), whole)
    with pytest.raises(NotImplementedError, match="capacity"):
        moe.moe_layer(wide, x, whole, use_pallas=False)
    from flashmoe_tpu.parallel.pipeline import stack_stage_params
    with pytest.raises(ValueError, match="uniform"):
        stack_stage_params(params, CFG, 2)
    assert moe.expert_arm(whole, 8) == "routed_rows"     # never "capacity"


def test_tiles_follow_the_rows_an_ffn_expert_expects():
    """K x rows over the ROUTER's width, not over the FFN experts."""
    cfg = PRESETS["longcat-flash"](num_layers=2, experts_held=16)
    assert cfg.router_width == 768
    assert moe.rows_block_m(cfg, 64) == 16        # one row an expert
    assert moe.rows_block_m(cfg, 1024) == 32      # sixteen
    assert moe.rows_block_m(cfg.replace(zero_experts=0), 1024) == 64


# --------------------------------------- (b) the layer loop and the reference

def test_forward_and_generate_equal_the_reference(params):
    toks = jnp.asarray(TOKENS[None, :29], jnp.int32)
    rows = jnp.arange(29)
    want = ref.forward_logits(params, DIMS, toks[0], rows)
    logits, _ = jax.jit(lambda p, t: forward(p, t, CFG))(params, toks)
    _close(logits[0], want)
    out = np.asarray(generate(params, toks[:, :12], CFG, max_new_tokens=8))
    after = ref.forward_logits(params, DIMS, jnp.asarray(out[0, :19]),
                               jnp.arange(11, 19))
    assert list(out[0, 12:]) == [int(t) for t in np.asarray(after).argmax(-1)]
    # a program that made one of these mistakes fails the tolerance
    scale = float(np.abs(np.asarray(want)).max())
    for mistake in MISTAKES:
        off = ref.forward_logits(params, DIMS, toks[0], rows, quant=mistake)
        assert np.abs(np.asarray(off - want)).max() > 150 * TIGHT * scale, \
            mistake


def test_the_mixture_is_the_references(params):
    """The routed rows (``ragged_dot`` on the CPU) against the dense
    weight matrix; identity experts alone and FFN experts alone show."""
    layer = params["layers"][2]["branch"]
    u = _x(40, seed=8)
    want = jax.jit(lambda p, x: ref.mixture(p, x, DIMS))(layer, u)
    out = moe.moe_layer(layer, u, CFG, use_pallas=False, routed_rows=True)
    _close(out.out, want)
    assert out.expert_counts.shape == (12,)
    assert int(out.expert_counts.sum()) == 40 * 5
    bare = ref.mixture(layer, u, DIMS, quant="no_zero")
    assert np.abs(np.asarray(want - bare)).max() > 1e-2
    assert np.abs(np.asarray(bare)).max() > 1e-3


# -------------------------- (c) the routed rows in both forms, by hand-made r

def _route(idx, width, seed=0):
    rng = np.random.default_rng(seed)
    idx = np.asarray(idx)
    counts = np.bincount(idx.reshape(-1), minlength=width)
    zero = jnp.zeros((), jnp.float32)
    return RouterOutput(
        jnp.asarray(rng.uniform(0.1, 1.0, idx.shape), jnp.float32),
        jnp.asarray(idx, jnp.int32), jnp.asarray(counts, jnp.int32),
        jnp.zeros((width,), jnp.float32), zero, zero)


@pytest.mark.parametrize("held,plan", [(0, None), (2, None), (2, 1)],
                         ids=["all", "a_share", "a_share_two_windows"])
def test_both_forms_of_the_routed_rows_take_identity_experts(
        monkeypatch, held, plan):
    """8 FFN experts + 6 identity, top-5, with every expert and with a
    share of two: a token all of whose choices are identity experts (row
    0), one with none (row 1), one whose FFN choices are all held
    elsewhere (row 2), random rows behind.  The grouped kernel in
    interpret mode against ``ragged_dot``, and both against the sum
    written out.  With every expert the kernel's plan holds the 200 routed
    rows; with a share it holds the 116 the two experts could expect four
    times over and is walked in windows (one here; with a plan of ONE
    tile, the two experts' rows take two)."""
    cfg = MoEConfig(num_experts=8, zero_experts=6, expert_top_k=5,
                    hidden_size=128, intermediate_size=128,
                    drop_tokens=False, gated_ffn=True, hidden_act="silu",
                    dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                    experts_held=held, expert_first=3 if held else 0)
    p = init_moe_params(jax.random.PRNGKey(0), cfg)
    assert p["gate_w"].shape == (128, 14) and p["w_up"].shape[0] == (
        held or 8)
    rng = np.random.default_rng(1)
    idx = [[8, 9, 10, 11, 13], [0, 3, 4, 6, 7], [0, 1, 2, 12, 13]] + [
        list(rng.choice(14, 5, replace=False)) for _ in range(37)]
    r = _route(idx, 14)
    x = jax.random.normal(jax.random.PRNGKey(1), (40, 128), jnp.bfloat16)
    assert moe.routed_rows_form(cfg) == "routed_rows"      # the CPU's
    want = jax.jit(lambda x: moe.routed_rows_ffn(p, x, r, cfg))(x)
    assert moe.rows_plan(cfg, 40) == (116 if held else 200)
    monkeypatch.setattr(moe, "routed_rows_form", lambda c: "routed_kernel")
    if plan:
        monkeypatch.setattr(moe, "rows_plan", lambda c, s: plan)
        assert all(0 < int(c) <= 32 for c in r.expert_counts[3:5])
    got = jax.jit(lambda x: moe.routed_rows_ffn(p, x, r, cfg))(x)
    scale = float(np.abs(np.asarray(want)).max())
    assert scale > 0 and np.abs(np.asarray(got - want)).max() <= 2e-2 * scale
    # the sum written out, in float32 over the bf16 weights
    first, n = cfg.expert_first, held or 8
    f32 = lambda a: np.asarray(a, np.float32)
    xs, w = f32(x), f32(r.combine_weights)
    by_hand = np.zeros((40, 128), np.float32)
    for t, row in enumerate(idx):
        for j, e in enumerate(row):
            if e >= 8:
                by_hand[t] += w[t, j] * xs[t]
            elif first <= e < first + n:
                k = e - first
                up = xs[t] @ f32(p["w_up"][k])
                g = xs[t] @ f32(p["w_gate"][k])
                hid = f32(jnp.asarray(g / (1 + np.exp(-g)) * up,
                                      jnp.bfloat16))
                by_hand[t] += w[t, j] * f32(jnp.asarray(
                    hid @ f32(p["w_down"][k]), jnp.bfloat16))
    assert np.abs(f32(want) - by_hand).max() <= 2e-2 * scale
    # all identity: the input times the sum of its weights, to rounding
    assert np.abs(f32(want[0]) - w[0].sum() * xs[0]).max() <= 1e-5 * scale
    if held:                    # nothing of row 2's FFN choices is here
        assert np.abs(f32(want[2]) - w[2, 3:].sum() * xs[2]).max() <= (
            1e-5 * scale)


def test_the_shares_add_up_to_the_uncut_layer(params):
    """THE SHARE TEST.  Four chips hold FFN experts 0-1, 2-3, 4-5 and 6-7,
    each routes over all twelve outputs, computes its own experts' rows
    and the identity experts' term for ITS tokens (every chip has the same
    tokens here).  The four partial results, the identity term counted
    ONCE, are the uncut layer's result, and each share is what the
    reference gives for the same share."""
    layer = params["layers"][0]["branch"]            # experts 2-3's weights
    whole_cfg = CFG.replace(expert_first=0, experts_held=0)
    fresh = init_moe_params(jax.random.PRNGKey(7), whole_cfg)
    whole = dict(fresh, gate_w=layer["gate_w"], gate_bias=layer["gate_bias"])
    u = _x(48, seed=22)
    routed = jax.jit(lambda p, x, cfg: moe.moe_layer(
        p, x, cfg, use_pallas=False, routed_rows=True),
        static_argnames="cfg")
    uncut = dict(DIMS, experts=8, expert_first=0)
    full = routed(whole, u, whole_cfg).out
    _close(full, jax.jit(lambda p, x: ref.mixture(p, x, uncut))(whole, u))
    parts = []
    for chip in range(4):
        cfg = CFG.replace(expert_first=2 * chip, experts_held=2)
        mine = {k: (v[2 * chip:2 * chip + 2]
                    if k in ("w_up", "b_up", "w_down", "b_down", "w_gate")
                    else v) for k, v in whole.items()}
        out = routed(mine, u, cfg)
        parts.append(out.out)
        _close(out.out, jax.jit(lambda p, x, first=2 * chip: ref.mixture(
            p, x, dict(DIMS, experts=2, expert_first=first)))(mine, u))
        assert int(out.expert_counts.sum()) == 48 * 5   # routed over all
    cw, _ = ref.router_weights(u, whole["gate_w"], whole["gate_bias"], DIMS)
    identity = jnp.sum(cw[:, 8:], axis=-1)[:, None] * u
    assert np.abs(np.asarray(identity)).max() > 1e-2
    _close(sum(parts) - 3 * identity, full)


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


@pytest.mark.parametrize("chunk,t0", [(None, 21), (16, 21), (16, 70)])
def test_engine_logits_equal_the_references_full_forward(
        monkeypatch, params, chunk, t0):
    """Whole-prompt prefill, and chunked prefill over two and over five
    chunks (the last one ragged), then 20 decode steps in the absorbed
    form over the latent pages of FOUR sublayers: the logits the sampler
    saw against the reference's full forward (no cache, the mixture's
    output added where the equations add it), to ``TIGHT``."""
    serve = ServeConfig(**SERVE, prefill_chunk=chunk)
    prompt = [int(t) for t in TOKENS[:t0]]
    out, got, engine = _serve_logits(
        monkeypatch, params, serve,
        [Request(rid=0, prompt=tuple(prompt), max_new_tokens=20)])
    assert isinstance(engine.cache, LatentPagedCache)
    assert engine.cache.pages.shape == (4, 40, 8, 128)
    assert len(out[0]) == t0 + 20 and out[0][:t0] == prompt
    want = ref.forward_logits(params, DIMS, jnp.asarray(out[0][:t0 + 19]),
                              jnp.arange(t0 - 1, t0 + 19))
    _close(got[0], want)
    assert out[0][t0:] == [int(t) for t in np.asarray(want).argmax(-1)]


def test_three_slots_decode_side_by_side(monkeypatch, params):
    """Requests of different lengths share the decode program: each row's
    branch is its own."""
    serve = ServeConfig(**SERVE, prefill_chunk=16)
    reqs = [Request(rid=r, prompt=tuple(int(t) for t in TOKENS[r:r + t0]),
                    max_new_tokens=6) for r, t0 in enumerate((9, 40, 17))]
    out, got, _ = _serve_logits(monkeypatch, params, serve, reqs)
    for r, t0 in enumerate((9, 40, 17)):
        want = ref.forward_logits(
            params, DIMS, jnp.asarray(out[r][:t0 + 5]),
            jnp.arange(t0 - 1, t0 + 5))
        _close(got[r], want)


# ------------------------------------------- the counts, records and names

def test_the_counts_are_a_hand_count(params):
    """``experts_touched`` (outputs of the router with a routed row,
    identity ones included), ``held_rows`` (rows on FFN experts 2-3) and
    ``zero_rows`` (rows on identity experts), each a mean over the two
    mixtures, against the reference's own choices."""
    toks = jnp.asarray(TOKENS[:33], jnp.int32)
    x = params["embed"][toks][None]
    pos = jnp.arange(33, dtype=jnp.int32)[None]
    _, _, _, counted = jax.jit(lambda p, x: span_forward(
        p, CFG, x, None, pos, None, None, absorbed=False))(params, x)
    dkey = ref._dims_key(DIMS)
    h, want = x[0], {"experts_touched": [], "held_rows": [], "zero_rows": []}
    layers = params["layers"]
    for a, b in zip(layers[0::2], layers[1::2]):
        u = ref._mixture_input(a, h, dkey)
        idx = np.asarray(ref.chosen(
            ref.router_probs(u, a["branch"]["gate_w"]),
            a["branch"]["gate_bias"], DIMS))
        want["experts_touched"].append(len(set(idx.reshape(-1))))
        want["held_rows"].append(int(((idx >= 2) & (idx < 4)).sum()))
        want["zero_rows"].append(int((idx >= 8).sum()))
        h = ref._layer(a, b, h, dkey, None)
    assert set(counted) == set(want)
    for name, per_layer in want.items():
        assert float(counted[name]) == pytest.approx(np.mean(per_layer))
    # a balanced router: a third of the choices are identity experts
    assert 0.2 < np.mean(want["zero_rows"]) / (33 * 5) < 0.5


def test_records_and_names(params):
    assert {"moe.zero", "moe.shortcut_join"} <= set(SPAN_NAMES)
    rec, mx = FlightRecorder(), Metrics()
    engine = ServingEngine(params, CFG, ServeConfig(**SERVE,
                                                    prefill_chunk=16),
                           recorder=rec, metrics_obj=mx)
    engine.run([Request(rid=r, prompt=tuple(int(t) for t in TOKENS[:t0]),
                        max_new_tokens=4) for r, t0 in enumerate((9, 40))])
    decodes = [r for r in rec.records if r["kind"] == "serve_decode"]
    # the counts are the latest FINISHED decode program's: of 3 rows
    assert decodes and all(
        0 <= d["zero_rows"] <= 5 * 3 and 0 <= d["held_rows"] <= 2 * 3
        and d["zero_rows"] + d["held_rows"] <= 5 * 3
        and 1 <= d["experts_touched"] <= 12
        and d["expert_arm"] == "routed_rows" for d in decodes)
    steps = [r for r in rec.records if r["kind"] == "serve_step"]
    assert any("zero_rows" in s for s in steps)
    assert mx.counters["serve.zero_rows"] == pytest.approx(
        sum(d["zero_rows"] for d in decodes))
    from flashmoe_tpu.serving.kvcache import init_paged_cache

    lower = lambda fn, *a: fn.lower(params, CFG, init_paged_cache(
        CFG, 40, 8, 3), *a).as_text(debug_info=True)
    text = lower(eng._paged_decode_step, jnp.zeros((3,), jnp.int32),
                 jnp.zeros((3, 3), jnp.int32), jnp.zeros((3,), jnp.int32))
    assert "moe.shortcut_join" in text and "moe.zero" in text
    text = lower(eng._prefill_chunk, jnp.zeros((1, 16), jnp.int32),
                 jnp.zeros((3,), jnp.int32), jnp.zeros((2,), jnp.int32),
                 jnp.int32(0), jnp.int32(3))
    assert "moe.shortcut_join" in text and "moe.zero" in text
