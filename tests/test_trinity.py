"""Trinity-Large-Preview's block against the plain reference
(``benchmark/lib/reference_trinity.py``), at tiny sizes on the CPU, float32,
seeded random weights: window layers beside full layers over TWO page pools
(the window pool gives back the pages behind the window while a slot lives),
RoPE on the window layers alone, the sigmoid gate on the heads' outputs,
four norms a layer, a share of the experts behind a router of all of them;
the two Pallas kernels under a window in interpret mode; the refusals.

The toy's window is 21 keys over pages of 8: smaller than every context
here and no whole pages, so a window's first key lies INSIDE a page.

Tolerances.  As ``tests/test_lfm2.py``: the program and the reference
compute the same float32 products in different orders; ``TIGHT`` (2e-5 of
the compared values' scale) has a factor of ten over the largest reading
seen, and each control (a mechanism left out or off by one) moves a logit
by a hundred times that or more (the fitted bias is small: 0.7 % of a
logit; the others 5 % and up).
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
from flashmoe_tpu.ops import attention
from flashmoe_tpu.ops.moe import moe_layer
from flashmoe_tpu.serving import engine as eng
from flashmoe_tpu.serving.engine import Request, ServeConfig, ServingEngine
from flashmoe_tpu.serving.kvcache import (
    SCRATCH_PAGE, HybridCache, init_paged_cache, slot_state_fields,
)
from flashmoe_tpu.serving.speculate import SpecConfig
from flashmoe_tpu.utils.telemetry import SPAN_NAMES, Metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TIGHT = 2e-5
WINDOW = 21


def _load(path, name):
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


ref = _load(os.path.join(ROOT, "benchmark", "lib", "reference_trinity.py"),
            "benchlib_reference_trinity")

# the cut's pattern in small: S S S F S, the first layer dense; 4 query
# heads over 2 K/V heads of 32; 4 of 16 experts held (a quarter of the
# router's outputs, as the cell's eighth), top 2
KINDS = ("swa", "swa", "swa", "mha", "swa")
TINY = dict(num_layers=5, layer_mixers=KINDS, first_k_dense=1,
            hidden_size=128, intermediate_size=64,
            dense_intermediate_size=128, num_experts=16, expert_top_k=2,
            experts_held=4, expert_first=4, vocab_size=256, num_heads=4,
            num_kv_heads=2, head_dim=32, attn_window=WINDOW,
            embedding_multiplier=128 ** 0.5, dtype=jnp.float32,
            param_dtype=jnp.float32)
CFG = PRESETS["trinity-large-preview"](**TINY)
FILE = {  # the same sizes under the published key names
    "hidden_size": 128, "num_hidden_layers": 5, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "sliding_window": WINDOW,
    "vocab_size": 256, "num_experts": 4, "num_experts_per_tok": 2,
    "moe_intermediate_size": 64, "intermediate_size": 128,
    "num_dense_layers": 1, "route_scale": 2.448, "route_norm": True,
    "score_func": "sigmoid", "n_group": 1, "mup_enabled": True,
    "tie_word_embeddings": False, "num_shared_experts": 1,
    "rope_scaling": None, "rope_theta": 10000, "rms_norm_eps": 1e-5,
    "published": {"num_experts": 16}, "held": {"expert_first": 4},
    "layer_kinds": ["sliding_attention" if k == "swa" else "full_attention"
                    for k in KINDS],
    "served": {"param_dtype": "float32"}}
DIMS = ref.model_dims(FILE)
SERVE = dict(max_batch=3, page_size=8, num_pages=48, max_pages_per_slot=16,
             ctx_bucket_pages=4, prompt_bucket=8)
TOKENS = np.random.default_rng(5).integers(1, 256, 200)


@pytest.fixture(scope="module")
def params():
    """The reference's weights: its tree layout IS the program's."""
    return ref.make_params(1234567891011, DIMS)


def _close(got, want, tol=TIGHT):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= tol * max(np.abs(want).max(), 1e-30)


def test_params_have_the_programs_tree_and_the_preset_is_the_published(
        params):
    mine = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), CFG))
    assert (jax.tree.map(lambda a: (a.shape, a.dtype), mine)
            == jax.tree.map(lambda a: (a.shape, a.dtype), params))
    assert CFG.cache_layers == (3,) and CFG.window_layers == (0, 1, 2, 4)
    assert not CFG.state_layers and CFG.windowed
    assert set(params["layers"][0]) >= {"attn_norm", "attn_out_norm",
                                        "ffn_norm", "ffn_out_norm", "wg"}
    full = PRESETS["trinity-large-preview"]()
    assert [li for li, m in enumerate(full.mixers) if m == "mha"] == list(
        range(3, 60, 4))
    assert full.mixers.count("swa") == 45 and full.attn_window == 4096
    assert len(full.moe_layer_indices) == 54 and full.qk_norm
    assert full.embedding_multiplier == pytest.approx(55.4256, abs=1e-4)
    # K and V of 8 heads of 128 in bfloat16: 4096 B a token a layer
    assert full.kv_token_bytes == full.kv_pool_token_bytes == 60 * 4096
    assert all(abs(float(jnp.mean(layer["moe"]["gate_bias"] ** 2))) > 0
               for layer in params["layers"][1:])


# --------------------------------------- the engine against the reference

def _serve_logits(monkeypatch, params, serve, requests, cfg=CFG, **kw):
    """Run requests and keep the logits the sampler was given at every
    step, by request: row j is what output token j was sampled from, the
    prefill's row first."""
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


def _reference_rows(params, out, t0, n, quant=None):
    toks = jnp.asarray(out[:t0 + n - 1])
    return ref.forward_logits(params, DIMS, toks,
                              jnp.arange(t0 - 1, t0 + n - 1), quant=quant)


@pytest.mark.parametrize("chunk,t0", [
    (None, 5),      # the window is crossed during decode
    (None, 50),     # during a whole prompt: pages behind it are never held
    (16, 18),       # during a prompt's second chunk
    (16, 70),       # five chunks, the last ragged, each wider than a page
])
def test_engine_logits_equal_the_references_full_forward(
        monkeypatch, params, chunk, t0):
    """Prefill (whole, or in chunks) then 30 decode steps THROUGH THE PAGED
    CACHE against the reference's forward over the whole sequence."""
    serve = ServeConfig(**SERVE, prefill_chunk=chunk)
    prompt = [int(t) for t in TOKENS[:t0]]
    out, got, engine = _serve_logits(
        monkeypatch, params, serve,
        [Request(rid=0, prompt=tuple(prompt), max_new_tokens=30)])
    assert isinstance(engine.cache, HybridCache)
    assert engine.cache._fields == ("k_pages", "v_pages", "wk_pages",
                                    "wv_pages")
    assert slot_state_fields(engine.cache) == (False,) * 4
    assert engine.cache.k_pages.shape[:2] == (1, 48)
    assert engine.cache.wk_pages.shape[:2] == (4, engine.wpool.num_pages)
    want = _reference_rows(params, out[0], t0, 30)
    _close(got[0], want)
    assert out[0][t0:] == [int(t) for t in np.asarray(want).argmax(-1)]
    assert engine.wpool.used_pages == engine.pool.used_pages == 0


def test_generate_and_forward_equal_the_reference(params):
    """The dense cache of ``generate`` (a window layer's rows all kept, the
    mask alone is the window) and the cacheless forward."""
    prompt = jnp.asarray(TOKENS[None, :30])
    out = np.asarray(generate(params, prompt, CFG, max_new_tokens=8))[0]
    want = _reference_rows(params, [int(t) for t in out], 30, 8)
    assert list(out[30:]) == [int(t) for t in np.asarray(want).argmax(-1)]
    logits, _ = jax.jit(lambda p, t: forward(p, t, CFG, use_pallas=False))(
        params, jnp.asarray(out[None, :36]))
    _close(logits[0], ref.forward_logits(params, DIMS, jnp.asarray(out[:36]),
                                         jnp.arange(36)), 1e-4)


@pytest.mark.parametrize("control", [c for c in ref.CONTROLS if c != "fp8"])
def test_a_mechanism_wrong_fails_the_tolerance(params, control):
    """The window one key short, RoPE on the full layer, the gate left
    out, the output norms left out, the bias added to the weights: each
    moves the reference's logits a hundred tolerances or more."""
    toks = [int(t) for t in TOKENS[:60]]
    want = np.asarray(_reference_rows(params, toks, 40, 20))
    got = np.asarray(_reference_rows(params, toks, 40, 20, quant=control))
    assert np.max(np.abs(got - want)) > 100 * TIGHT * np.abs(want).max()


# ------------------------------------------- the window pool gives pages back

def test_window_pages_return_while_a_slot_lives(params):
    """Over a decode of five windows the window pool's free pages are flat
    once the window has filled, the full pool's fall a page every eight
    tokens; the records and the registry say so."""
    mx = Metrics()

    class Recorder:
        records = []

        def record(self, **rec):
            self.records.append(rec)

    engine = ServingEngine(params, CFG, ServeConfig(**SERVE),
                           recorder=Recorder(), metrics_obj=mx)
    engine.submit(Request(rid=0, prompt=tuple(int(t) for t in TOKENS[:10]),
                          max_new_tokens=110))
    free, used = [], []
    while engine.pending():
        rec = engine.step()
        if engine.slots[0] is not None:
            free.append(engine.wpool.free_pages)
            used.append((rec["window_pages_used"], rec["pages_used"]))
    filled = [f for f, (_, full) in zip(free, used) if full * 8 > 2 * WINDOW]
    assert len(filled) > 60 and max(filled) - min(filled) <= 1
    # at most the window's pages and the one being written: 20 keys before
    # the token's own reach into four pages at the worst
    assert max(w for w, _ in used) == 4 and max(f for _, f in used) == 15
    steps = [r for r in engine.recorder.records if r["kind"] == "serve_step"]
    # the last query stands at position 118: pages 0 .. 11 lie behind it
    assert sum(r["window_pages_freed"] for r in steps) == 12
    assert mx.counters["serve.window_pages_freed"] == 12
    assert mx.counters["serve.window_programs"] == 1 + len(
        [r for r in engine.recorder.records if r["kind"] == "serve_decode"])
    assert mx.gauges["serve.window_pool_pages"] == 0       # all came back
    decodes = [r for r in engine.recorder.records
               if r["kind"] == "serve_decode"]
    # the gather arm (the CPU's) reads the table it is handed: the window's
    # four pages where the full layer reads its bucket
    assert {r["window_ctx_pages"] for r in decodes} == {4.0}
    assert max(r["ctx_pages"] for r in decodes) == 16
    assert engine.wpool.used_pages == engine.pool.used_pages == 0


def test_a_freed_page_serves_another_slot_and_changes_nothing(
        monkeypatch, params):
    """Request 0 decodes past its window while request 1 arrives later and
    is handed pages request 0 gave back: request 0's logits are what it
    gets alone, and both are the reference's."""
    serve = ServeConfig(**SERVE, prefill_chunk=16)
    first = Request(rid=0, prompt=tuple(int(t) for t in TOKENS[:40]),
                    max_new_tokens=40)
    second = Request(rid=1, prompt=tuple(int(t) for t in TOKENS[100:130]),
                     max_new_tokens=12)
    _, alone, _ = _serve_logits(monkeypatch, params, serve, [first])
    seen = {0: set(), 1: set()}
    holder = {}
    grow = ServingEngine._grow_pages

    def watching(self, rows, span=0):
        grow(self, rows, span)
        for i in rows:
            s = self.slots[i]
            if s is not None:
                seen[s.orig.rid].update(p for p in s.wpages
                                        if p != SCRATCH_PAGE)

    monkeypatch.setattr(ServingEngine, "_grow_pages", watching)
    holder["engine"] = engine = ServingEngine(params, CFG, serve)
    rows, sampler = {}, eng._sample_dynamic

    def keeping(logits, *knobs):
        got = np.asarray(logits)
        for i in engine._decoding():
            rows.setdefault(engine.slots[i].orig.rid, []).append(got[i])
        return sampler(logits, *knobs)

    monkeypatch.setattr(eng, "_sample_dynamic", keeping)
    engine.submit(first)
    engine.submit(second, arrival_step=20)
    out = engine.run()
    assert seen[0] & seen[1]            # a page of 0's became one of 1's
    _close(np.stack(rows[0]), alone[0], 1e-6)
    for rid, req in ((0, first), (1, second)):
        t0 = len(req.prompt)
        _close(np.stack(rows[rid]),
               _reference_rows(params, out[rid], t0, req.max_new_tokens))


def test_an_evicted_request_past_its_window_resumes(monkeypatch, params):
    """A window pool too small for three long slots: the youngest is
    evicted past its window and prefilled again (its prompt now longer than
    the window); every request's tokens are the reference's."""
    serve = ServeConfig(**dict(SERVE, num_pages=64), prefill_chunk=16,
                        window_pages=1 + 8)
    reqs = [Request(rid=i, prompt=tuple(int(t) for t in
                                        TOKENS[20 * i:20 * i + 26]),
                    max_new_tokens=40) for i in range(3)]
    out, got, engine = _serve_logits(monkeypatch, params, serve, reqs)
    assert engine.stats["evictions"] >= 1
    assert engine.window_slot_pages == 6
    for i, req in enumerate(reqs):
        want = _reference_rows(params, out[i], 26, 40)
        assert out[i][26:] == [int(t) for t in np.asarray(want).argmax(-1)]
    assert engine.wpool.used_pages == engine.pool.used_pages == 0


# -------------------------------------------------- the kernels, interpreted

@pytest.mark.parametrize("t,length", [(1, 70), (1, 21), (1, 5), (3, 46)])
def test_the_decode_kernel_under_a_window_equals_the_gather_arm(t, length):
    """``fm_paged_decode`` with a window against store + gather +
    ``kv_attend``'s mask, over a slot's own table (the walk starts at the
    block that holds the window's first key) and over the table the engine
    hands a window layer (from the window's first page, ``base`` on)."""
    window, page, nkv, rep, d = WINDOW, 8, 2, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(length), 6)
    pools = tuple(jax.random.normal(k, (2, 32, nkv, page, d), jnp.float32)
                  for k in ks[:2])
    tables = jnp.asarray(np.random.default_rng(length).permutation(
        np.arange(1, 32))[:2 * 14].reshape(2, 14), jnp.int32)
    pos = jnp.asarray([length, max(length - 3, 0)], jnp.int32)
    span_pos = pos[:, None] + jnp.arange(t)[None]
    q = jax.random.normal(ks[2], (2, t, nkv * rep, d), jnp.float32)
    k, v = (jax.random.normal(key, (2, t, nkv, d), jnp.float32)
            for key in ks[3:5])
    layer = {"wo": jnp.eye(nkv * rep * d, dtype=jnp.float32)}

    def gather(tables, pos, span_pos):
        write = (jnp.take_along_axis(tables, span_pos // page, axis=1),
                 span_pos % page)
        stored = tuple(attention.store_kv(p, 1, x, *write)
                       for p, x in zip(pools, (k, v)))
        ctx = [attention.gather_ctx(p, 1, tables) for p in stored]
        return (attention.kv_attend(layer, q, *ctx, span_pos,
                                    window=window), stored, write)

    want, stored, write = gather(tables, pos, span_pos)
    for bp in (None, 1):        # the rule's block, and a block of ONE page
        got, got_pools = attention.paged_decode_attention(
            q, (k, v), pools, 1, tables, pos, write, window=window,
            block_pages=bp, interpret=True)
        _close(got, want, 1e-5)
        for a, b in zip(got_pools, stored):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the engine's table: the pages from the window's first on
    first = np.maximum(np.asarray(pos) - window + 1, 0) // page
    sub = jnp.stack([tables[b, f:f + 5] for b, f in enumerate(first)])
    base = jnp.asarray(first * page, jnp.int32)
    rel = span_pos - base[:, None]
    write = (jnp.take_along_axis(sub, rel // page, axis=1), rel % page)
    got, _ = attention.paged_decode_attention(
        q, (k, v), pools, 1, sub, pos - base, write, window=window,
        interpret=True)
    _close(got, want, 1e-5)
    # and a window that is off by one key is another result
    other, _ = attention.paged_decode_attention(
        q, (k, v), pools, 1, tables, pos, write, window=window - 1,
        interpret=True)
    if length >= window:
        assert np.abs(np.asarray(other) - np.asarray(want)).max() > 1e-3


@pytest.mark.parametrize("window,tq,tk,pos0", [
    (300, 256, 1024, 700),      # tiles wholly behind, an edge inside a tile
    (128, 256, 512, 256),       # the edge ON a tile's edge
    (1000, 128, 256, 100),      # a window wider than the context
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_span_kernel_under_a_window_equals_the_xla_form(
        monkeypatch, window, tq, tk, pos0, dtype):
    """``kv_attend`` with the flash arm forced (``fm_flash_span`` in
    interpret, tiles of 128 so that the window's edge crosses tiles, skips
    tiles and coincides with a tile's edge) against its plain XLA mask."""
    n, n_kv = 4, 2
    ks = jax.random.split(jax.random.PRNGKey(window), 4)
    rnd = lambda k, *shape: jax.random.normal(
        k, shape, jnp.float32).astype(dtype)
    layer = {"wo": rnd(ks[3], n * 128, 64) * (n * 128) ** -0.5}
    q_pos = pos0 + jnp.broadcast_to(jnp.arange(tq, dtype=jnp.int32), (2, tq))
    args = (layer, rnd(ks[0], 2, tq, n, 128), rnd(ks[1], 2, n_kv, tk, 128),
            rnd(ks[2], 2, n_kv, tk, 128), q_pos)
    want = attention.kv_attend(*args, window=window)
    assert np.abs(np.asarray(want, np.float32) - np.asarray(
        attention.kv_attend(*args), np.float32)).max() > 1e-2 or window > tk
    monkeypatch.setattr(attention, "span_attention_arm", lambda *a: "flash")
    monkeypatch.setattr(attention, "_FLASH_TILE", 128)
    attention.flash_span_attention.clear_cache()
    try:
        got = attention.kv_attend(*args, window=window)
    finally:
        attention.flash_span_attention.clear_cache()
    tol = 1e-5 if dtype == jnp.float32 else 2 ** -6
    _close(np.asarray(got, np.float32), np.asarray(want, np.float32), tol)


def test_the_engine_on_the_kernels_arm_serves_the_same_tokens(monkeypatch):
    """A toy engine with 128-wide heads, the decode kernel's arm forced (in
    interpret) against the gather arm: the same tokens through whole and
    chunked prefill and a decode past two windows."""
    cfg = PRESETS["trinity-large-preview"](**dict(
        TINY, num_layers=3, layer_mixers=("swa", "mha", "swa"),
        num_heads=2, num_kv_heads=1, head_dim=128))
    p = init_params(jax.random.PRNGKey(2), cfg)
    serve = ServeConfig(**dict(SERVE, page_size=16, num_pages=24,
                               max_pages_per_slot=8, prompt_bucket=16),
                        prefill_chunk=16)
    reqs = [Request(rid=0, prompt=tuple(int(t) for t in TOKENS[:40]),
                    max_new_tokens=30),
            Request(rid=1, prompt=tuple(int(t) for t in TOKENS[50:59]),
                    max_new_tokens=45)]
    want = ServingEngine(p, cfg, serve).run(reqs)
    monkeypatch.setattr(
        attention, "kv_attention_arm",
        lambda t, page, n_kv, d, dtype, pools=2:
        "paged_kernel" if t < page else "gather")
    jax.clear_caches()
    try:
        mx = Metrics()
        engine = ServingEngine(p, cfg, serve, metrics_obj=mx)
        got = engine.run(reqs)
        assert mx.counters["serve.decode_kernel_steps"] > 40
    finally:
        jax.clear_caches()
    assert got == want


# ------------------------------------------------------------- the shares

_routed = jax.jit(lambda p, x, cfg: moe_layer(p, x, cfg, use_pallas=False,
                                              routed_rows=True),
                  static_argnums=2)


def test_the_shares_add_up_to_the_uncut_layer(params):
    """Each of four chips holds 4 of the 16 experts, routes over all of
    them and computes its own experts' rows; the shared expert is computed
    by every chip alike.  The four partial results, the shared expert
    counted once, are the uncut layer's; a share is what the reference
    gives for the same share."""
    whole_cfg = CFG.replace(expert_first=0, experts_held=0)
    whole = init_params(jax.random.PRNGKey(21), whole_cfg)["layers"][1]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(22), (48, 128), jnp.float32)
    full = _routed(whole, x, whole_cfg).out
    parts = []
    for chip in range(4):
        cfg = CFG.replace(expert_first=4 * chip, experts_held=4)
        mine = {k: (v[4 * chip:4 * chip + 4]
                    if k in ("w_up", "b_up", "w_down", "b_down", "w_gate")
                    else v) for k, v in whole.items()}
        out = _routed(mine, x, cfg)
        parts.append(out.out)
        _close(out.out, jax.jit(lambda p, x, first=4 * chip: ref.ffn(
            p, x, dict(DIMS, expert_first=first)))(mine, x))
        assert int(out.expert_counts.sum()) == 48 * 2   # routed over all
    once = ref._swiglu(x, whole["shared_w_gate"], whole["shared_w_up"],
                       whole["shared_w_down"], None)
    _close(sum(parts) - 3 * once, full)


# -------------------------------------------------- refusals, names, records

def test_what_is_not_built_is_refused_by_name(params):
    tiny = lambda **kw: PRESETS["trinity-large-preview"](**dict(TINY, **kw))
    for kw in (dict(is_training=True), dict(ep=2), dict(tp=2), dict(sp=2),
               dict(block_length=8, mask_token_id=1)):
        with pytest.raises(NotImplementedError, match="swa|block_length"):
            tiny(**kw)
    with pytest.raises(ValueError, match="attn_window"):
        tiny(attn_window=0)
    with pytest.raises(ValueError, match="attn_window"):
        tiny(layer_mixers=("mha",) * 5)
    with pytest.raises(ValueError, match="attn_gate"):
        PRESETS["joyai-llm-flash"](num_layers=2, attn_gate=True)
    for kw, what in ((dict(speculate=SpecConfig(draft_tokens=2)),
                      "speculate"), (dict(ep_shards=2), "ep_shards")):
        with pytest.raises(NotImplementedError, match=what):
            ServingEngine(params, CFG, ServeConfig(**dict(SERVE, max_batch=4,
                                                          **kw)))
    with pytest.raises(NotImplementedError, match="prefill_fn"):
        ServingEngine(params, CFG, ServeConfig(**SERVE),
                      prefill_fn=lambda *a, **k: None)
    with pytest.raises(ValueError, match="cannot hold one slot"):
        ServingEngine(params, CFG, ServeConfig(**SERVE, window_pages=4))


def test_scopes_and_the_cache_say_which_pool(params):
    assert "attn.gate" in SPAN_NAMES
    text = jax.jit(lambda p, t: forward(p, t, CFG, use_pallas=False)).lower(
        params, jnp.asarray(TOKENS[None, :16])).as_text(debug_info=True)
    assert "attn.gate" in text and "attn.kv" in text
    cache = init_paged_cache(CFG, 9, 8, window_pages=5)
    assert cache.k_pages.shape[1] == 9
    assert cache.wk_pages.shape[1] == 5 and cache.page_size == 8
    assert not hasattr(cache, "num_pages")
    with pytest.raises(ValueError, match="window_pages"):
        init_paged_cache(CFG, 9, 8)
