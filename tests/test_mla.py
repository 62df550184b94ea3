"""JoyAI-LLM-Flash's block against the plain reference
(``benchmark/lib/reference_mla.py``), at tiny sizes on the CPU, float32,
seeded random weights, a non-zero selection bias: latent attention in
both forms, the adjacent-pair RoPE, the sigmoid router, the leading dense
layer, the model through ``transformer.forward`` and through the serving
engine's latent paged cache, the refusals, the preset's parameter counts
and the names the tracing holds.

Tolerances.  The program and the reference compute the same float32
products in different orders (and the reference at matmul precision
"highest", which on the CPU is the same arithmetic): what separates them is
float32 reassociation, a few ulps a product and some 1e-6 of the logits'
scale after three layers.  ``TIGHT`` (2e-5 of the compared values' scale)
has a factor of ten over the largest reading seen, and anything left out of
the mathematics (the bias, the 2.5, a rotation, a mask) moves a logit by
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

from flashmoe_tpu.config import MoEConfig
from flashmoe_tpu.models.presets import PRESETS
from flashmoe_tpu.models.transformer import forward, init_params, loss_fn
from flashmoe_tpu.ops import attention as att
from flashmoe_tpu.ops.gate import router, router_xla
from flashmoe_tpu.serving import engine as eng
from flashmoe_tpu.serving.engine import Request, ServeConfig, ServingEngine
from flashmoe_tpu.serving.kvcache import (
    LatentPagedCache, PagedKVCache, init_paged_cache, store_prefill,
)
from flashmoe_tpu.utils.telemetry import SPAN_NAMES, Metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TIGHT = 2e-5


def _load(path, name):
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


ref = _load(os.path.join(ROOT, "benchmark", "lib", "reference_mla.py"),
            "benchlib_reference_mla")

# heads, head sizes, the rank and the context length all differ, so that a
# shape names its axes (the decompressed-K/V search below relies on it)
TINY = dict(num_layers=3, hidden_size=64, intermediate_size=64,
            dense_intermediate_size=128, num_experts=8, expert_top_k=2,
            vocab_size=256, num_heads=3, q_lora_rank=24, kv_lora_rank=20,
            qk_nope_head_dim=10, qk_rope_head_dim=6, v_head_dim=14,
            dtype=jnp.float32, param_dtype=jnp.float32)
CFG = PRESETS["joyai-llm-flash"](**TINY)
MODEL = {  # the same sizes under the published key names
    "hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 3,
    "q_lora_rank": 24, "kv_lora_rank": 20, "qk_nope_head_dim": 10,
    "qk_rope_head_dim": 6, "v_head_dim": 14, "vocab_size": 256,
    "n_routed_experts": 8, "num_experts_per_tok": 2, "n_shared_experts": 1,
    "moe_intermediate_size": 64, "intermediate_size": 128,
    "first_k_dense_replace": 1, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "scoring_func": "sigmoid", "n_group": 1,
    "rope_theta": 32000000, "rms_norm_eps": 1e-6}
DIMS = ref.model_dims({"model": MODEL, "served": {"param_dtype": "float32"}})


@pytest.fixture(scope="module")
def params():
    """The reference's weights (its tree layout IS the program's), norms
    moved off one so that a norm left out shows."""
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


def test_params_have_the_programs_tree_and_a_bias(params):
    mine = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), CFG))
    assert (jax.tree_util.tree_structure(mine)
            == jax.tree_util.tree_structure(params))
    for a, b in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(params)):
        assert a.shape == b.shape and a.dtype == b.dtype
    assert CFG.moe_layer_indices == (1, 2)
    assert "gate_bias" not in params["layers"][0]["moe"]
    assert params["layers"][0]["moe"]["w_up"].shape == (1, 64, 128)
    for layer in params["layers"][1:]:
        assert float(jnp.abs(layer["moe"]["gate_bias"]).min()) > 0


# ------------------------------------------------------------- attention

def test_rope_rotates_adjacent_pairs():
    x = jnp.asarray([[[1.0, 0.0, 0.0, 2.0]]])          # [B=1, T=1, D=4]
    pos = jnp.asarray([[3]])
    got = np.asarray(att.rope_adjacent(x, pos, 100.0))[0, 0]
    a0, a1 = 3.0, 3.0 * 100.0 ** -0.5                    # the two angles
    want = [np.cos(a0), np.sin(a0), -2 * np.sin(a1), 2 * np.cos(a1)]
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    # the half-split rotation of the other presets pairs (0, 2) and (1, 3)
    from flashmoe_tpu.models.transformer import _rope
    half, _ = _rope(x[:, :, None, :], x[:, :, None, :], pos, 100.0)
    assert not np.allclose(np.asarray(half)[0, 0, 0], want, atol=1e-3)


@pytest.mark.parametrize("ndim", [3, 4])
def test_rope_equals_the_references(ndim):
    shape = (1, 37, 6) if ndim == 3 else (1, 37, 3, 6)
    x = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32)
    pos = jnp.arange(100, 137)[None, :]
    _close(att.rope_adjacent(x, pos, 3.2e7)[0],
           ref.rope_adjacent(x[0], pos[0], 3.2e7), 1e-6)


def _x(t, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (1, t, 64),
                             jnp.float32)


def test_prefill_form_equals_the_reference(params):
    layer, x = params["layers"][1], _x(37)
    pos = jnp.arange(37)[None, :]
    got, pool, latent = att.mla_paged_attention(
        layer, x, CFG, None, 0, pos, None, None, absorbed=False)
    assert pool is None and latent.shape == (1, 37, 26)
    _close(got[0], ref.attention(layer, x[0], DIMS))


@pytest.mark.parametrize("t_span", [1, 4])
def test_absorbed_form_equals_the_prefill_form_on_one_cache(params, t_span):
    """A span at the end of a 40-token context, over the SAME paged rows,
    in both orders of the products; and against the reference's rows."""
    layer, x = params["layers"][2], _x(40, seed=2)
    pos = jnp.arange(40)[None, :]
    page, n_pages = 8, 6
    pool = jnp.zeros((2, n_pages + 1, page, CFG.kv_row_elems), jnp.float32)
    table = jnp.asarray([[3, 1, 6, 2, 5, 4]])           # scattered pages
    ids = table[0][pos[0] // page][None, :]
    _, pool, _ = att.mla_paged_attention(
        layer, x, CFG, pool, 1, pos, (ids, pos % page), table,
        absorbed=False)
    assert not np.asarray(pool[0]).any()                # layer 1's rows only
    # the same rows written as whole pages (a prefill chunk's way)
    _, whole, _ = att.mla_paged_attention(
        layer, x, CFG, jnp.zeros_like(pool), 1, pos, (table[:, :5], None),
        table, absorbed=False)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(pool))
    lo = 40 - t_span
    args = (layer, x[:, lo:], CFG, pool, 1, pos[:, lo:],
            (ids[:, lo:], pos[:, lo:] % page), table)
    plain = att.mla_paged_attention(*args, absorbed=False)[0]
    absorbed = att.mla_paged_attention(*args, absorbed=True)[0]
    _close(absorbed, plain)
    _close(absorbed[0], ref.attention(layer, x[0], DIMS)[lo:])


# ---------------------------------------------------------------- router

def _router_case():
    x = jax.random.normal(jax.random.PRNGKey(5), (64, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(6), (64, 8), jnp.float32) / 8
    b = jnp.asarray([0.4, -0.4, 0.2, 0.0, -0.2, 0.3, -0.3, 0.1])
    return x, w, b


def test_router_equals_the_reference_and_the_bias_only_selects():
    x, w, b = _router_case()
    out = router(x, w, CFG, use_pallas=True, gate_bias=b)   # XLA arm anyway
    cw, top_i = ref.router_weights(x, w, b, DIMS)
    got = np.zeros((64, 8), np.float32)
    np.put_along_axis(got, np.asarray(out.expert_idx),
                      np.asarray(out.combine_weights), axis=1)
    np.testing.assert_array_equal(np.sort(np.asarray(out.expert_idx), 1),
                                  np.sort(np.asarray(top_i), 1))
    _close(got, cw, 1e-6)
    # the weights are the chosen experts' sigmoid scores, normalised, x 2.5
    s = np.asarray(jax.nn.sigmoid(x @ w))
    chosen = np.take_along_axis(s, np.asarray(out.expert_idx), 1)
    np.testing.assert_allclose(
        np.asarray(out.combine_weights),
        2.5 * chosen / chosen.sum(1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out.combine_weights).sum(1), 2.5,
                               rtol=1e-6)
    # without the bias other experts are chosen for some tokens, and where
    # the set is the same the weights are the same: b steers, never weighs
    plain = router_xla(x, w, CFG.replace(router_bias=False))
    same = (np.sort(np.asarray(plain.expert_idx), 1)
            == np.sort(np.asarray(out.expert_idx), 1)).all(1)
    assert 0 < same.sum() < 64
    np.testing.assert_allclose(
        np.sort(np.asarray(plain.combine_weights)[same], 1),
        np.sort(np.asarray(out.combine_weights)[same], 1), rtol=1e-6)


def test_router_switches():
    x, w, _ = _router_case()
    base = CFG.replace(router_bias=False)
    raw = router_xla(x, w, base.replace(norm_topk_prob=False,
                                        routed_scaling_factor=1.0))
    s = np.asarray(jax.nn.sigmoid(x @ w))
    np.testing.assert_allclose(
        np.asarray(raw.combine_weights),
        np.take_along_axis(s, np.asarray(raw.expert_idx), 1), rtol=1e-6)
    soft = router_xla(x, w, base.replace(router_score="softmax",
                                         norm_topk_prob=False,
                                         routed_scaling_factor=1.0))
    p = np.asarray(jax.nn.softmax(x @ w, -1))
    np.testing.assert_allclose(
        np.asarray(soft.combine_weights),
        np.take_along_axis(p, np.asarray(soft.expert_idx), 1), rtol=1e-6)


@pytest.mark.parametrize("preset", ["deepseek-moe-16b", "flashmoe-reference"])
def test_softmax_presets_route_as_before(preset):
    """Renormalising softmax top-k, bit for bit, and the same traced
    graph as the formula written out."""
    cfg = PRESETS[preset](hidden_size=64, intermediate_size=64,
                          num_experts=8, vocab_size=256, num_heads=4,
                          num_layers=2)
    assert (cfg.router_score, cfg.router_bias, cfg.norm_topk_prob,
            cfg.routed_scaling_factor, cfg.first_k_dense,
            cfg.attention_kind) == ("softmax", False, True, 1.0, 0, "mha")
    x, w, _ = _router_case()

    def before(x, w):
        probs = jax.nn.softmax(jnp.dot(
            x, w, preferred_element_type=jnp.float32), axis=-1)
        top_p, top_i = jax.lax.top_k(probs, cfg.expert_top_k)
        return (top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-20),
                top_i)

    out = router_xla(x, w, cfg)
    want_w, want_i = before(x, w)
    np.testing.assert_array_equal(np.asarray(out.combine_weights),
                                  np.asarray(want_w))
    np.testing.assert_array_equal(np.asarray(out.expert_idx),
                                  np.asarray(want_i))


def test_layer_output_keeps_the_scaling_through_the_capacity_combine(params):
    """``dispatch.combine`` renormalises what survives a drop to the sum
    the router gave (2.5 here, not one)."""
    from flashmoe_tpu.ops.moe import moe_layer

    p = params["layers"][1]["moe"]
    x = _x(24, seed=7)[0]
    got = moe_layer(p, x, CFG, use_pallas=False).out
    _close(got, ref.ffn(p, x, DIMS))


def _tiny_softmax_layer():
    cfg = PRESETS["deepseek-moe-16b"](
        hidden_size=64, intermediate_size=64, num_experts=8,
        vocab_size=256, num_heads=4, num_layers=2, dtype=jnp.float32)
    p = init_params(jax.random.PRNGKey(2), cfg)["layers"][1]["moe"]
    return cfg, p


@pytest.mark.parametrize("which", ["sigmoid-bias-2.5", "softmax"])
def test_routed_rows_arm_equals_the_capacity_arm(params, which):
    """The experts over the S x K routed rows (``jax.lax.ragged_dot``)
    give what the E x S capacity arm gives, for the new router and for a
    softmax preset; float32, so only the order of the sums differs."""
    from flashmoe_tpu.ops.moe import moe_layer

    if which == "softmax":
        cfg, p = _tiny_softmax_layer()
    else:
        cfg, p = CFG, params["layers"][2]["moe"]
    x = _x(40, seed=9)[0]
    dense = moe_layer(p, x, cfg, use_pallas=False)
    routed = moe_layer(p, x, cfg, use_pallas=False, routed_rows=True)
    _close(routed.out, dense.out)
    np.testing.assert_array_equal(np.asarray(routed.expert_counts),
                                  np.asarray(dense.expert_counts))
    if which != "softmax":
        _close(routed.out, ref.ffn(p, x, DIMS))
    g = jax.grad(lambda w: moe_layer(
        dict(p, w_down=w), x, cfg, use_pallas=False,
        routed_rows=True).out.sum())(p["w_down"])
    assert bool(jnp.isfinite(g).all()) and float(jnp.abs(g).max()) > 0


@pytest.mark.parametrize("bad", [
    dict(use_pallas=True), dict(use_pallas=False, capacity=16)])
def test_routed_rows_arm_refuses_what_it_is_not(params, bad):
    from flashmoe_tpu.ops.moe import moe_layer

    p, x = params["layers"][1]["moe"], _x(8)[0]
    with pytest.raises(ValueError, match="routed_rows"):
        moe_layer(p, x, CFG, routed_rows=True, **bad)
    with pytest.raises(ValueError, match="routed_rows"):
        moe_layer(p, x, CFG.replace(drop_tokens=True), use_pallas=False,
                  routed_rows=True)


# ------------------------------------------------------------- the model

TOKENS = np.random.default_rng(11).integers(1, 256, 60)


def test_forward_logits_equal_the_reference(params):
    toks = jnp.asarray(TOKENS[None, :41])
    logits, aux = forward(params, toks, CFG)
    want = ref.forward_logits(params, DIMS, toks[0], jnp.arange(41))
    _close(logits[0], want)
    assert np.isfinite(float(aux))


def test_gradient_is_finite(params):
    cfg = CFG.replace(is_training=True)
    grads = jax.jit(jax.grad(
        lambda p: loss_fn(p, {"tokens": jnp.asarray(TOKENS[None, :33])},
                          cfg)[0]))(params)
    leaves = jax.tree_util.tree_leaves(grads)
    assert all(bool(jnp.isfinite(g).all()) for g in leaves)
    # every matrix of the new block gets a gradient
    for name in ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "q_a_norm",
                 "kv_a_norm"):
        assert float(jnp.abs(grads["layers"][1][name]).max()) > 0
    assert float(jnp.abs(grads["layers"][0]["moe"]["w_up"]).max()) > 0


# ------------------------------------------------ through the serving engine

SERVE = dict(max_batch=2, page_size=8, num_pages=24, max_pages_per_slot=8,
             ctx_bucket_pages=3, prompt_bucket=8)


def _serve_logits(monkeypatch, params, serve, prompt, n_new):
    """Run one request alone (slot 0) and keep the logits the sampler was
    given at every step: row j is what output token j was sampled from,
    the prefill's row first."""
    rows, sampler = [], eng._sample_dynamic

    def watching(logits, *knobs):
        rows.append(np.asarray(logits[0]))
        return sampler(logits, *knobs)

    monkeypatch.setattr(eng, "_sample_dynamic", watching)
    engine = ServingEngine(params, CFG, serve)
    out = engine.run([Request(rid=0, prompt=tuple(prompt),
                              max_new_tokens=n_new)])[0]
    assert len(rows) == n_new
    return out, np.stack(rows), engine


@pytest.mark.parametrize("chunk", [None, 8])
def test_engine_logits_equal_the_references_full_forward(
        monkeypatch, params, chunk):
    """Prefill (whole, or in chunks of one page) then 20 absorbed decode
    steps over the latent pages: the prompt of 21 tokens crosses a page
    edge (8) and the context grows across a bucket edge (24 tokens)."""
    serve = ServeConfig(**SERVE, prefill_chunk=chunk)
    prompt = [int(t) for t in TOKENS[:21]]
    out, got, engine = _serve_logits(monkeypatch, params, serve, prompt, 20)
    assert len(out) == 41 and out[:21] == prompt
    assert len(engine.stats["decode_buckets"]) >= 2
    toks = jnp.asarray(out[:40])
    want = ref.forward_logits(params, DIMS, toks, jnp.arange(20, 40))
    _close(got, want)
    assert out[21:] == [int(t) for t in np.asarray(want).argmax(-1)]


def _filled_cache(params, t0=19):
    serve = ServeConfig(**SERVE)
    cache = init_paged_cache(CFG, serve.num_pages, serve.page_size)
    prompt = jnp.asarray(TOKENS[None, :24])
    _, latents = eng._prefill_padded(params, CFG, prompt, jnp.int32(t0))
    pages = jnp.asarray([5, 2, 9])
    cache = LatentPagedCache(store_prefill(cache.pages, latents, pages))
    tables = jnp.zeros((2, 3), jnp.int32).at[0].set(pages)
    return cache, tables


def test_verify_column_0_equals_the_decode_step(params):
    cache, tables = _filled_cache(params)
    toks = jnp.asarray([[7, 9, 11], [0, 0, 0]])
    pos = jnp.asarray([19, 0])
    dec, c1, _ = eng._paged_decode_step(params, CFG, cache, toks[:, 0], tables,
                                     pos)
    ver, c2 = eng._paged_verify_step(params, CFG, cache, toks, tables, pos)
    assert isinstance(c1, LatentPagedCache) and c1.pages.shape == \
        cache.pages.shape == c2.pages.shape
    _close(ver[0, 0], dec[0], 1e-6)
    # and both are the reference's row for the same 20 tokens
    seq = jnp.asarray(list(TOKENS[:19]) + [7, 9, 11])
    want = ref.forward_logits(params, DIMS, seq, jnp.arange(19, 22))
    _close(ver[0], want)


def _vars(jaxpr):
    for eqn in jaxpr.eqns:
        yield from eqn.outvars
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _vars(sub)


def test_decode_reads_latent_rows_and_never_decompresses_the_context(params):
    """The traced decode program holds no value with a context axis, a
    head axis and a per-head key or value size together: K and V of the
    context are never formed.  The prefill-form program does hold them."""
    cache, tables = _filled_cache(params)
    n_ctx, nh = 3 * 8, CFG.num_heads
    sizes = {CFG.qk_nope_head_dim, CFG.v_head_dim}

    def decompressed(fn, *args):
        jaxpr = jax.make_jaxpr(fn.__wrapped__, static_argnums=(1,))(*args)
        return [v.aval.shape for v in _vars(jaxpr.jaxpr)
                if hasattr(v.aval, "shape") and n_ctx in v.aval.shape
                and nh in v.aval.shape and sizes & set(v.aval.shape)]

    toks, pos = jnp.asarray([7, 0]), jnp.asarray([19, 0])
    assert decompressed(eng._paged_decode_step, params, CFG, cache, toks,
                        tables, pos) == []
    assert decompressed(eng._paged_verify_step, params, CFG, cache,
                        jnp.stack([toks, toks], 1), tables, pos) == []
    chunk = (params, CFG, cache, jnp.asarray(TOKENS[None, 16:24]),
             tables[0], jnp.asarray([9]), jnp.int32(16), jnp.int32(2))
    assert decompressed(eng._prefill_chunk, *chunk) != []


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


def test_mla_programs_compute_routed_rows_and_kv_programs_as_before(
        params, monkeypatch):
    """On the routed rows (since ISSUE 33 ONE rule over what the arms hold
    picks the arm, ``ops/moe.expert_arm``, whatever the attention kind; a
    toy's spans all fit the capacity arm's buffer, so it is given none
    here) the MLA serving programs hold three ragged products a mixture
    layer and no [E, S, .] capacity buffer, and so does a K/V model's
    decode program; with the buffer it has, a toy of either kind takes
    the capacity arm: no ragged product."""
    from flashmoe_tpu.ops import moe as moe_ops

    cache, tables = _filled_cache(params)
    toks, pos = jnp.asarray([7, 0]), jnp.asarray([19, 0])
    ds, p = _tiny_softmax_layer()
    del p
    model = init_params(jax.random.PRNGKey(0), ds)
    kv = init_paged_cache(ds, 24, 8)

    def decode_jaxpr(weights, cfg, pool):
        # a function of its own a call: a traced one is kept by identity
        step = lambda w, c, *a: eng._paged_decode_step.__wrapped__(w, c, *a)
        return jax.make_jaxpr(step, static_argnums=(1,))(
            weights, cfg, pool, toks, tables, pos).jaxpr

    for cfg, weights, pool in ((CFG, params, cache), (ds, model, kv)):
        assert not [n for n in _primitives(decode_jaxpr(weights, cfg, pool))
                    if "ragged" in n]
    monkeypatch.setattr(moe_ops, "_CAPACITY_BUFFER_BYTES", 0)
    jaxpr = decode_jaxpr(params, CFG, cache)
    prims = list(_primitives(jaxpr))
    assert prims.count("ragged_dot_general") + prims.count("ragged_dot") \
        == 3 * len(CFG.moe_layer_indices)
    e, inter = CFG.num_experts, CFG.intermediate_size
    assert not [v.aval.shape for v in _vars(jaxpr)
                if hasattr(v.aval, "shape") and len(v.aval.shape) == 3
                and v.aval.shape[0] == e and v.aval.shape[2] == inter
                and v.aval.shape[1] == 2]            # [E, S=2 slots, I]
    assert len([n for n in _primitives(decode_jaxpr(model, ds, kv))
                if "ragged" in n]) == 3 * len(ds.moe_layer_indices)


def test_pool_shape_and_bytes_a_token():
    cut = PRESETS["joyai-llm-flash"](num_layers=5)
    shape = jax.eval_shape(lambda: init_paged_cache(cut, 16384, 16))
    assert isinstance(shape, LatentPagedCache) and len(shape) == 1
    # a row is stored padded to whole lanes: a page is whole tiles
    assert shape.pages.shape == (5, 16384, 16, 640)
    assert shape.pages.dtype == jnp.bfloat16 and shape.num_pages == 16384
    assert cut.kv_token_elems == 576 and cut.kv_token_bytes == 5760
    assert cut.kv_row_elems == 640 and cut.kv_pool_token_bytes == 6400
    assert shape.pages.size * 2 == 16384 * 16 * 6400          # 1.68 GB
    ds = PRESETS["deepseek-moe-16b"](num_layers=6)
    assert ds.kv_token_elems == 2 * 16 * 128 and ds.kv_token_bytes == 49152
    assert ds.kv_row_elems == 4096 and ds.kv_pool_token_bytes == 49152
    pair = jax.eval_shape(lambda: init_paged_cache(ds, 2048, 16))
    assert isinstance(pair, PagedKVCache)
    assert pair.k_pages.shape == (6, 2048, 16, 16, 128)


@pytest.mark.parametrize("kind", ["mla", "mha"])
def test_engine_serves_either_cache_in_place(params, kind):
    """The engine runs the donated programs for either cache kind since
    ISSUE 30 (``engine._INPLACE``: the pool it handed in is gone after a
    step); the undonated ones keep their inputs for tests and
    ``lower()``."""
    assert set(eng._INPLACE) == {"_prefill_chunk", "_paged_decode_step",
                                 "_paged_verify_step", "_paged_denoise_step"}
    cfg, weights = CFG, params
    if kind == "mha":
        cfg = PRESETS["deepseek-moe-16b"](
            num_layers=2, hidden_size=64, intermediate_size=64,
            num_experts=8, expert_top_k=2, vocab_size=256, num_heads=4,
            dtype=jnp.float32)
        weights = init_params(jax.random.PRNGKey(0), cfg)
    engine = ServingEngine(weights, cfg, ServeConfig(**SERVE))
    engine.submit(Request(rid=0, prompt=tuple(int(t) for t in TOKENS[:9]),
                          max_new_tokens=3))
    engine.step()                   # admits and prefills, then decodes
    handed_in = jax.tree.leaves(engine.cache)
    engine.step()
    assert all(pool.is_deleted() for pool in handed_in)
    assert not any(pool.is_deleted()
                   for pool in jax.tree.leaves(engine.cache))


# -------------------------------------------------------------- refusals

def test_refusals_name_what_is_missing(params):
    with pytest.raises(NotImplementedError, match="_ep_decode_fn"):
        ServingEngine(params, CFG, ServeConfig(**dict(SERVE, ep_shards=2)))
    with pytest.raises(NotImplementedError, match="prefill_fn"):
        ServingEngine(params, CFG, ServeConfig(**SERVE),
                      prefill_fn=lambda *a, **k: None)
    with pytest.raises(NotImplementedError, match="kv_wire_dtype"):
        CFG.replace(kv_wire_dtype="e4m3")
    from flashmoe_tpu.fabric.handoff import KVHandoff

    with pytest.raises(NotImplementedError, match="latent-row payload"):
        KVHandoff(params, CFG, 8)
    x, w, _ = _router_case()
    with pytest.raises(NotImplementedError, match="gate_bias"):
        router(x, w, CFG)                 # what the mesh layers would call
    with pytest.raises(NotImplementedError, match="ring"):
        from flashmoe_tpu.models.transformer import attention

        attention(params["layers"][1], _x(8), CFG.replace(sp=2), mesh=object())


@pytest.mark.parametrize("bad", [
    dict(attention_kind="mla"),                       # sizes missing
    dict(q_lora_rank=8),                              # sizes without mla
    dict(attention_kind="gqa"), dict(router_score="tanh"),
    dict(routed_scaling_factor=0.0), dict(first_k_dense=9),
    dict(dense_intermediate_size=100),
])
def test_config_validates_the_new_keys(bad):
    with pytest.raises(ValueError):
        MoEConfig(**bad)


# ------------------------------------------------- the preset and the names

def test_preset_parameter_counts_are_the_published_ones():
    """The issue's table: MLA 26.3 M a layer, the dense layer 70.4 M, a
    mixture layer 1239.5 M, embedding + head 529.5 M, the cut 5558 M."""
    cfg = PRESETS["joyai-llm-flash"](num_layers=5)
    assert (cfg.num_experts, cfg.expert_top_k, cfg.num_shared_experts,
            cfg.hidden_size, cfg.intermediate_size,
            cfg.dense_intermediate_size, cfg.vocab_size, cfg.num_heads) \
        == (256, 8, 1, 2048, 768, 7168, 129280, 32)
    assert PRESETS["joyai-llm-flash"]().num_layers == 40
    tree = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    layer = tree["layers"][1]
    attn = sum(int(np.prod(layer[k].shape))
               for k in ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo"))
    assert attn == 26_345_472
    moe = lambda l: sum(int(np.prod(v.shape)) for k, v in l["moe"].items()
                        if k.startswith(("w_", "shared_w_", "gate_w")))
    dense = attn + moe(tree["layers"][0]) - 2048      # its unused gate_w
    assert dense == 70_385_664
    mixture = attn + moe(layer)
    assert mixture == 1_239_547_904
    ends = 2 * 129280 * 2048
    assert dense + 4 * mixture + ends == 5_558_108_160
    counts = _load(os.path.join(ROOT, "benchmark", "lib", "counts_mla.py"),
                   "benchlib_counts_mla")
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "joyai_flash.json")) as f:
        import json
        d = ref.model_dims(json.load(f))
    assert counts.model_params(d) == 5_558_108_160


def test_tracing_holds_the_new_names(params):
    assert {"attn.mla_prefill", "attn.mla_decode", "moe.gate"} \
        <= set(SPAN_NAMES)
    cache, tables = _filled_cache(params)
    toks, pos = jnp.asarray([7, 0]), jnp.asarray([19, 0])
    dec = eng._paged_decode_step.lower(params, CFG, cache, toks, tables,
                                       pos).as_text(debug_info=True)
    assert "attn.mla_decode" in dec and "moe.gate" in dec
    assert "attn.mla_prefill" not in dec
    pre = eng._prefill_padded.lower(
        params, CFG, jnp.asarray(TOKENS[None, :24]),
        jnp.int32(19)).as_text(debug_info=True)
    assert "attn.mla_prefill" in pre and "attn.mla_decode" not in pre


def test_kv_token_bytes_is_on_the_records_and_the_gauge(params):
    class Rec:
        def __init__(self):
            self.records = []

        def record(self, **rec):
            self.records.append(rec)

    rec, mx = Rec(), Metrics()
    engine = ServingEngine(params, CFG, ServeConfig(**SERVE), recorder=rec,
                           metrics_obj=mx)
    engine.run([Request(rid=0, prompt=(5, 6, 7), max_new_tokens=3)])
    steps = [r for r in rec.records if r["kind"] == "serve_step"]
    # layers x elements as the pool stores them (26 in 128 lanes) x float32
    want = 3 * 128 * 4
    assert CFG.kv_pool_token_bytes == want and CFG.kv_token_bytes == 3 * 26 * 4
    assert steps and all(r["kv_token_bytes"] == want for r in steps)
    assert [r for r in rec.records if r["kind"] == "serve_decode"]
    assert mx.gauges["serve.kv_token_bytes"] == want
