"""granite-4.0-h-micro's block against the plain reference
(``benchmark/lib/reference_granite4.py``), at tiny sizes on the CPU,
float32, seeded random weights: a mixer AND a dense SwiGLU part in every
layer, the four scalar factors (embedding, residual, attention scores,
logits), the head tied to the embedding, a state-space mixer whose B and C
are shared by ALL heads (one group).  The layer loop, ``generate`` and the
serving engine (whole prompt, chunked prefill over five chunks, decode
through pool and state) against the reference's full forward pass; each
factor and the tie shown to matter; ONE leaf for embedding and head; the
programs of the presets the benchmark already had, to the jaxpr.

Tolerances.  ``TIGHT`` (2e-5 of the compared values' scale) is
``tests/test_nemotron3.py``'s, for its reason: the same float32 products in
another order.  The largest reading here is 1.5e-6 on logits, and a factor
left at its default moves a logit by 1e-2 of the scale or more.
"""

from __future__ import annotations

import hashlib
import importlib.util
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashmoe_tpu.config import MoEConfig
from flashmoe_tpu.models.generate import generate
from flashmoe_tpu.models.presets import PRESETS
from flashmoe_tpu.models.transformer import forward, init_params
from flashmoe_tpu.ops import ssm
from flashmoe_tpu.serving import engine as eng
from flashmoe_tpu.serving.engine import Request, ServeConfig, ServingEngine
from flashmoe_tpu.serving.kvcache import init_paged_cache
from flashmoe_tpu.utils.telemetry import SPAN_NAMES, Metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TIGHT = 2e-5
NAME = "granite-4.0-h-micro"


def _load(path, name):
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


# the published pattern in small: runs of state layers around two attention
# layers; 4 query heads over 2 K/V heads; 4 state heads that share ONE B / C
KINDS = ("ssm", "mha", "ssm", "ssm", "mha", "ssm")
TINY = dict(num_layers=6, layer_mixers=KINDS, hidden_size=64,
            intermediate_size=128, vocab_size=256, num_heads=4,
            num_kv_heads=2, head_dim=8, attention_multiplier=0.25,
            ssm_heads=4, ssm_head_dim=8, ssm_state=16, ssm_chunk=8,
            dtype=jnp.float32, param_dtype=jnp.float32)
FILE = {  # the same sizes under the published key names
    "hidden_size": 64, "num_hidden_layers": 6,
    "layer_types": ["attention" if k == "mha" else "mamba" for k in KINDS],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "shared_intermediate_size": 128, "vocab_size": 256,
    "mamba_n_heads": 4, "mamba_d_head": 8, "mamba_n_groups": 1,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_conv_bias": True,
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "attention_multiplier": 0.25, "logits_scaling": 8,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
    "position_embedding_type": "nope", "num_local_experts": 0,
    "served": {"param_dtype": "float32"}}
SERVE = dict(max_batch=3, page_size=8, num_pages=48, max_pages_per_slot=12,
             ctx_bucket_pages=3, prompt_bucket=8)
TOKENS = np.random.default_rng(11).integers(1, 256, 200)

# None when run from a copy of the parent commit, to print the pins of (g)
CFG = PRESETS[NAME](**TINY) if NAME in PRESETS else None
if CFG is not None:
    ref = _load(os.path.join(ROOT, "benchmark", "lib",
                             "reference_granite4.py"),
                "benchlib_reference_granite4")
    DIMS = ref.model_dims(FILE)


@pytest.fixture(scope="module")
def params():
    """The reference's weights (its tree layout IS the program's), norms
    and the skip moved off one so that one left out shows."""
    p = ref.make_params(1234567891011, DIMS)
    key = jax.random.PRNGKey(3)
    for li, layer in enumerate(p["layers"]):
        for j, name in enumerate(("attn_norm", "ffn_norm", "ssm_norm",
                                  "ssm_D")):
            if name in layer:
                k = jax.random.fold_in(key, 10 * li + j)
                layer[name] = 1.0 + 0.1 * jax.random.normal(
                    k, layer[name].shape, jnp.float32)
    p["final_norm"] = 1.0 + 0.1 * jax.random.normal(key, (64,), jnp.float32)
    return p


def _close(got, want, tol=TIGHT):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= tol * max(np.abs(want).max(), 1e-30)


def _apart(got, want, factor=100):
    """Further apart than ``factor`` x the tolerance ``_close`` allows."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.max(np.abs(got - want)) > factor * TIGHT * np.abs(want).max()


# ------------------------------------------------ (a) the description

def test_the_preset_is_the_published_model():
    full = PRESETS[NAME]()
    assert full.num_layers == 40 and full.vocab_size == 100352
    assert full.cache_layers == (5, 15, 25, 35)
    assert len(full.state_layers) == 36
    assert set(full.layers) == {("ssm", "dense"), ("mha", "dense")}
    assert (full.embedding_multiplier, full.residual_multiplier,
            full.attention_multiplier, full.logits_scaling) == (
        12.0, 0.22, 0.015625, 8.0)
    assert full.tie_embeddings and not full.use_rope and full.rescaled
    assert full.resolved_head_dim == 64 and full.kv_pool_rows == (2, 4, 128)
    assert full.ssm_groups == 1 and full.ssm_chunk == 256
    assert full.dense_config.intermediate_size == 8192
    # a slot's two kinds of memory: 75.50 MB of float32 state + 0.94 MB of
    # convolution inputs whatever its context, 8 kB of K/V a token
    assert full.state_slot_bytes == 36 * (64 * 64 * 128 * 4
                                          + 3 * 4352 * 2)
    assert full.kv_pool_token_bytes == full.kv_token_bytes == 8192
    # 3.191 B parameters, the embedding counted ONCE
    tree = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), full))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    n -= 40 * 2048                  # the dense parts' one-column ``gate_w``
    n -= 40 * (8192 + 2048)         # and their zero biases
    assert n == 36 * 76_182_976 + 4 * 60_821_504 + 205_522_944
    assert n == 3_191_396_096


def test_one_leaf_for_embedding_and_head(params):
    """The parameter tree has NO ``lm_head``: the program's tree IS the
    reference's, one leaf fewer than the same model untied."""
    mine = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), CFG))
    assert (jax.tree.map(lambda a: (a.shape, a.dtype), mine)
            == jax.tree.map(lambda a: (a.shape, a.dtype), params))
    assert "lm_head" not in mine and mine["embed"].shape == (256, 64)
    untied = jax.eval_shape(lambda: init_params(
        jax.random.PRNGKey(0), CFG.replace(tie_embeddings=False)))
    assert (len(jax.tree_util.tree_leaves(untied))
            == len(jax.tree_util.tree_leaves(mine)) + 1)
    wide = [a for a in jax.tree_util.tree_leaves(mine)
            if 256 in a.shape]
    assert len(wide) == 1           # the vocabulary's ONE array


def test_a_gradient_reaches_the_embedding_from_both_uses(params):
    """d loss / d embed of the tied model is the untied twin's gradient of
    its embedding plus its head's, transposed."""
    toks = jnp.asarray(TOKENS[:12])[None, :]
    twin_cfg = CFG.replace(tie_embeddings=False)
    twin = dict(params, lm_head=params["embed"].T)

    def loss(p, cfg):
        return jnp.sum(jnp.square(forward(p, toks, cfg)[0]))

    g = jax.jit(jax.grad(lambda p: loss(p, CFG)))(params)["embed"]
    g2 = jax.jit(jax.grad(lambda p: loss(p, twin_cfg)))(twin)
    assert float(jnp.abs(g2["lm_head"]).max()) > 0
    assert float(jnp.abs(g2["embed"]).max()) > 0
    _close(g, g2["embed"] + g2["lm_head"].T, 1e-4)


# ------------------------------------------------ (b) the layer loops

def _want(params, toks, rows, quant=None):
    return ref.forward_logits(params, DIMS, jnp.asarray(toks),
                              jnp.asarray(rows), quant=quant)


def test_forward_is_the_references(params):
    toks = TOKENS[:40]
    got = jax.jit(lambda p, t: forward(p, t, CFG)[0])(
        params, jnp.asarray(toks)[None, :])[0]
    _close(got, _want(params, toks, np.arange(40)))


@pytest.mark.parametrize("prefill", ["batched", "loop"])
def test_generate_follows_the_reference(params, prefill):
    prompt = jnp.asarray(TOKENS[:19])[None, :]
    out = np.asarray(generate(params, prompt, CFG, max_new_tokens=8,
                              prefill=prefill))[0]
    want = np.asarray(_want(params, out[:-1], np.arange(18, 26)))
    assert list(out[19:]) == [int(t) for t in want.argmax(-1)]


@pytest.mark.parametrize("control", ["residual_1", "attention_rsqrt",
                                     "untied_head", "fp8"])
def test_each_factor_and_the_tie_matter(params, control):
    """The controls of the cell's check, at toy size: the reference with
    the residual factor at 1, the scores' factor at D ** -0.5, a head of
    its own, or float8 operands is not the model ``forward`` computes."""
    toks = TOKENS[:40]
    got = jax.jit(lambda p, t: forward(p, t, CFG)[0])(
        params, jnp.asarray(toks)[None, :])[0]
    _apart(got, _want(params, toks, np.arange(40), control))


@pytest.mark.parametrize("field,value", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("attention_multiplier", None), ("logits_scaling", 1.0)])
def test_a_factor_left_at_its_default_shows(params, field, value):
    toks = TOKENS[:40]
    cfg = CFG.replace(**{field: value})
    got = jax.jit(lambda p, t: forward(p, t, cfg)[0])(
        params, jnp.asarray(toks)[None, :])[0]
    _apart(got, _want(params, toks, np.arange(40)))


# ------------------------------------------------ (c) the serving engine

def _serve_logits(monkeypatch, params, serve, requests, **kw):
    """Run requests and keep the logits the sampler was given at every
    step, by request (``tests/test_nemotron3_engine.py``'s)."""
    rows, sampler = {}, eng._sample_dynamic
    holder = {}

    def watching(logits, *knobs):
        got = np.asarray(logits)
        for i in holder["engine"]._decoding():
            rows.setdefault(holder["engine"].slots[i].orig.rid,
                            []).append(got[i])
        return sampler(logits, *knobs)

    monkeypatch.setattr(eng, "_sample_dynamic", watching)
    holder["engine"] = engine = ServingEngine(params, CFG, serve, **kw)
    out = engine.run(requests)
    return out, {r: np.stack(v) for r, v in rows.items()}, engine


class _Records:
    def __init__(self):
        self.records = []

    def record(self, **rec):
        self.records.append(rec)


@pytest.mark.parametrize("chunk,t0", [(None, 21), (16, 70)])
def test_engine_logits_equal_the_references_full_forward(
        monkeypatch, params, chunk, t0):
    """Whole-prompt prefill, and chunked prefill with the state carried
    over five chunks (the last one ragged), then 20 decode steps over the
    by-slot state and the K/V pages: the logits the sampler saw against
    the reference's full forward pass, and what the prefill programs
    gathered on their records."""
    serve = ServeConfig(**SERVE, prefill_chunk=chunk)
    prompt = [int(t) for t in TOKENS[:t0]]
    mx, rec = Metrics(), _Records()
    out, got, engine = _serve_logits(
        monkeypatch, params, serve,
        [Request(rid=0, prompt=tuple(prompt), max_new_tokens=20)],
        metrics_obj=mx, recorder=rec)
    assert engine.cache._fields == ("k_pages", "v_pages", "state", "conv")
    assert engine.cache.state.shape == (4, 3, 4, 8, 16)
    assert engine.cache.k_pages.shape == (2, 48, 2, 8, 8)
    want = _want(params, out[0][:-1], np.arange(t0 - 1, t0 + 19))
    _close(got[0], want)
    assert out[0][t0:] == [int(t) for t in np.asarray(want).argmax(-1)]
    pre = [r for r in rec.records if r["kind"] == "serve_prefill"]
    if chunk is None:
        assert [r["ctx_pages"] for r in pre] == [0]
        assert "serve.prefill_ctx_pages" not in mx.counters
    else:
        # five chunks, each gathering its context's bucket of pages
        assert [r["ctx_pages"] for r in pre] == [3, 6, 6, 9, 12]
        assert mx.counters["serve.prefill_ctx_pages"] == 36
        assert mx.counters["serve.chunk_carries"] == 4


def test_slots_share_a_step_and_idle_rows_keep_their_state(monkeypatch,
                                                           params):
    """Three requests of different lengths through three slots, chunked:
    every one's logits are the reference's."""
    serve = ServeConfig(**SERVE, prefill_chunk=16)
    lens = [(9, 5), (40, 7), (33, 6)]
    reqs = [Request(rid=r, prompt=tuple(int(t) for t in
                                        TOKENS[3 * r:3 * r + t0]),
                    max_new_tokens=n) for r, (t0, n) in enumerate(lens)]
    out, got, _ = _serve_logits(monkeypatch, params, serve, reqs)
    for r, (t0, n) in enumerate(lens):
        _close(got[r], _want(params, out[r][:-1],
                             np.arange(t0 - 1, t0 + n - 1)))


def test_one_group_through_the_step_kernel():
    """``fm_ssm_step`` in interpret mode at ONE group (B and C shared by
    every head) against the plain step."""
    rng = jax.random.split(jax.random.PRNGKey(2), 6)
    s, n, p, ns = 3, 4, 8, 128
    state = jax.random.normal(rng[0], (2, s, n, p, ns), jnp.float32)
    xs = jax.random.normal(rng[1], (s, n, p), jnp.float32)
    bm = jax.random.normal(rng[2], (s, 1, ns), jnp.float32)
    cm = jax.random.normal(rng[3], (s, 1, ns), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(rng[4], (s, n), jnp.float32))
    a = -jnp.exp(jax.random.normal(rng[5], (n,), jnp.float32))
    y, new = ssm.ssm_step_pallas(state, 1, xs, bm, cm, dt, a,
                                 interpret=True)
    y_want, s_want = ssm.ssm_step(xs, bm, cm, dt, a, jnp.zeros((n,)),
                                  state[1])
    _close(y, y_want)
    _close(new[1], s_want)
    assert bool(jnp.all(new[0] == state[0]))


# ------------------------------------------------ (d) refusals and names

def test_what_the_factors_do_not_reach_is_refused_by_name(params):
    for bad in (dict(is_training=True), dict(ep=2), dict(dp=2)):
        base = dict(num_layers=2, hidden_size=64, intermediate_size=64,
                    vocab_size=256, num_heads=4, num_experts=2,
                    residual_multiplier=0.5)
        with pytest.raises(NotImplementedError, match="residual_multiplier"):
            MoEConfig(**base, **bad)
    with pytest.raises(NotImplementedError, match="tie_embeddings"):
        MoEConfig(num_layers=2, hidden_size=64, tie_embeddings=True,
                  is_training=True)
    with pytest.raises(ValueError, match="'mha' layer's scores"):
        PRESETS["joyai-llm-flash"](attention_multiplier=0.1)
    kv = MoEConfig(num_layers=2, hidden_size=64, intermediate_size=64,
                   vocab_size=256, num_heads=4, num_experts=2,
                   drop_tokens=False, logits_scaling=2.0)
    with pytest.raises(NotImplementedError, match="ep_shards > 1"):
        ServingEngine(init_params(jax.random.PRNGKey(0), kv), kv,
                      ServeConfig(max_batch=2, page_size=8, num_pages=8,
                                  max_pages_per_slot=4, ep_shards=2))


def test_the_dense_part_and_the_head_have_scopes(params):
    assert {"ffn.dense", "lm.head"} <= set(SPAN_NAMES)
    cache = init_paged_cache(CFG, 48, 8, 3)
    text = eng._paged_decode_step.lower(
        params, CFG, cache, jnp.zeros((3,), jnp.int32),
        jnp.zeros((3, 3), jnp.int32),
        jnp.zeros((3,), jnp.int32)).as_text(debug_info=True)
    for name in ("ffn.dense", "lm.head", "attn.ssm_decode"):
        assert name in text
    with open(os.path.join(ROOT, "docs", "OBSERVABILITY.md")) as f:
        doc = f.read()
    for name in ("`ffn.dense`", "`lm.head`", "`serve.prefill_ctx_pages`"):
        assert name in doc


# ---- (g) the presets the benchmark had: the parent's programs -------------

#: tiny sizes of every preset a cell of the benchmark runs
OLDER = {
    "deepseek-moe-16b": dict(
        num_layers=2, hidden_size=64, intermediate_size=64, num_experts=8,
        expert_top_k=2, vocab_size=256, num_heads=4),
    "flashmoe-reference": dict(
        num_layers=2, hidden_size=64, intermediate_size=64, num_experts=8,
        vocab_size=256, num_heads=4, sequence_len=128, drop_tokens=False),
    "joyai-llm-flash": dict(
        num_layers=3, hidden_size=64, intermediate_size=64,
        dense_intermediate_size=128, num_experts=8, expert_top_k=2,
        vocab_size=256, num_heads=3, q_lora_rank=24, kv_lora_rank=20,
        qk_nope_head_dim=10, qk_rope_head_dim=6, v_head_dim=14),
    "ling-3.0-flash": dict(
        num_layers=3, layer_mixers=("kda", "kda", "mla"), first_k_dense=1,
        hidden_size=64, intermediate_size=64, dense_intermediate_size=128,
        num_experts=16, expert_top_k=3, n_group=4, topk_group=2,
        expert_first=4, experts_held=4, vocab_size=256, num_heads=3,
        kda_heads=3, kda_head_dim=16, kv_lora_rank=20, qk_nope_head_dim=10,
        qk_rope_head_dim=6, v_head_dim=14),
    "lfm2-24b-a2b": dict(
        num_layers=5, layer_mixers=("conv", "mha", "conv", "conv", "mha"),
        first_k_dense=1, hidden_size=256, intermediate_size=64,
        dense_intermediate_size=128, num_experts=8, expert_top_k=2,
        vocab_size=256, num_heads=4, num_kv_heads=2),
    "nemotron-3-nano-30b-a3b": dict(
        pattern="MEM*EM", hidden_size=64, intermediate_size=192,
        num_experts=8, expert_top_k=2, vocab_size=256, num_heads=16,
        num_kv_heads=1, head_dim=8, ssm_heads=4, ssm_head_dim=8,
        ssm_groups=2, ssm_state=16, ssm_chunk=8),
    "longcat-flash": dict(
        num_layers=4, hidden_size=64, intermediate_size=64,
        dense_intermediate_size=128, num_experts=8, zero_experts=4,
        expert_top_k=5, expert_first=2, experts_held=2, num_heads=4,
        q_lora_rank=16, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, vocab_size=256),
    "sdar-30b-a3b-chat": dict(
        num_layers=2, hidden_size=64, intermediate_size=64, num_experts=8,
        expert_top_k=2, vocab_size=300, num_heads=4, num_kv_heads=2,
        head_dim=16),
}

#: sha256 of the jaxprs (source locations struck) of each older preset's
#: decode (a denoise step where it generates by blocks), chunk and
#: whole-prompt programs and its ``forward``, traced as on the CPU and as
#: on a TPU, as the PARENT commit (1144894) traces them: every new field's
#: default writes nothing into them.  ISSUE 50 re-pinned the five K/V
#: presets (the three MLA presets' pins are PR 49's): their chunk programs,
#: and at these toy widths their gather-arm decode programs, read one
#: ``gather`` of the whole pool by (layer, page) where they read a ``slice``
#: + ``squeeze`` of the layer and a gather by page; the primitive counts of
#: parent and change differ by that and nothing else, the whole-prompt
#: programs and ``forward`` are the parent's letter for letter.  ISSUE 52
#: re-pinned ``sdar-30b-a3b-chat`` alone: its denoise program spans two
#: blocks a slot (``tests/test_sdar.py`` pins its block-free twins)
PARENT_JAXPRS = {
    "deepseek-moe-16b":
        "d275ffd6ce11ab2405f6a32bf4ab47ab99c4b9327ba669eeff68e8bb197a6269",
    "flashmoe-reference":
        "be6547708caaa9d98e25915bf87a1ca10e7b84e97075d5f9c156da9c20df6570",
    "joyai-llm-flash":
        "01cd895da1c70c00d329d0c475fc24fe0864a65b85ed8814760cdcb5464ee845",
    "lfm2-24b-a2b":
        "192c11b5a688fbc91c241f2804c9238a6fab8bad1848e83d9cf45b5994a313fa",
    "ling-3.0-flash":
        "1a86865cabe1fa61120497c340a552f1291119e663e222dd173026d16256933d",
    "longcat-flash":
        "e1196f6470ad4c464ca9285284574a031edbea9dfdf90e51a4f7d842d001813a",
    "nemotron-3-nano-30b-a3b":
        "503b68b5d6f979cb12c17e75b0d7c9ebce333dbd553d91f35f418164827b8e8e",
    "sdar-30b-a3b-chat":
        "ab6b9d0b530c712c442ccccfd9b59660973f673575d782ed5fb384699e1959e8",
}


def _jaxpr_text(fn, *args):
    text = str(jax.make_jaxpr(fn)(*args))
    text = re.sub(r" at [^\s]+\.py:\d+", "", text)
    return re.sub(r"0x[0-9a-f]+", "0x", text)


def older_digests():
    """The programs' digests on this tree (run from a copy of the parent
    commit to make the pins: ``PYTHONPATH=<copy> JAX_PLATFORMS=cpu python
    tests/test_granite4.py``)."""
    out = {}
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    for name, tiny in OLDER.items():
        cfg = PRESETS[name](**tiny, dtype=jnp.bfloat16,
                            param_dtype=jnp.bfloat16)
        params = jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), cfg))
        cache = jax.eval_shape(lambda: init_paged_cache(cfg, 32, 16, 4))
        bl = cfg.block_length
        texts = []
        for backend in ("cpu", "tpu"):
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(jax, "default_backend", lambda: backend)
                jax.clear_caches()
                try:
                    if bl:
                        texts.append(_jaxpr_text(
                            lambda p, c, *a: eng._paged_denoise_step(
                                p, cfg, c, *a, pad_token=0),
                            params, cache, i32(4, 3, bl), i32(4, 5 + bl),
                            i32(4, 8)))
                    else:
                        texts.append(_jaxpr_text(
                            lambda p, c, *a: eng._paged_decode_step(
                                p, cfg, c, *a, pad_token=0),
                            params, cache, i32(4), i32(4, 8), i32(4)))
                    texts.append(_jaxpr_text(
                        lambda p, c, *a: eng._prefill_chunk(p, cfg, c, *a),
                        params, cache, i32(1, 128), i32(8), i32(8), i32(),
                        i32(), i32()))
                    texts.append(_jaxpr_text(
                        lambda p, *a: eng._prefill_padded(p, cfg, *a),
                        params, i32(1, 128), i32()))
                    texts.append(_jaxpr_text(
                        lambda p, t: forward(p, t, cfg)[0], params,
                        i32(2, 128)))
                finally:
                    jax.clear_caches()
        out[name] = hashlib.sha256("\n".join(texts).encode()).hexdigest()
    return out


@pytest.fixture(scope="module")
def digests():
    return older_digests()


@pytest.mark.parametrize("preset", sorted(OLDER))
def test_an_older_presets_programs_are_the_parents(digests, preset):
    assert digests[preset] == PARENT_JAXPRS[preset]


if __name__ == "__main__":
    import pprint

    pprint.pprint(older_digests())
