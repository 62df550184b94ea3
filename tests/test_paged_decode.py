"""The paged decode attention kernel (``ops/attention.fm_paged_decode``
over a K and a V pool, ``fm_latent_decode`` over an MLA model's one latent
pool: ONE body) held against the plain forms it replaces on a TPU:
``store_kv`` + ``gather_ctx`` + ``kv_attend``, and ``store_latent`` +
``gather_latent`` + the absorbed ``mla_attend``.  The kernel runs in
``interpret`` here, at small shapes; what the chip's compiler makes of it
at the benchmark cells' shapes is ``tests/test_tpu_compile.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashmoe_tpu.models.presets import PRESETS
from flashmoe_tpu.models.transformer import init_params
from flashmoe_tpu.ops import attention
from flashmoe_tpu.serving import engine as eng
from flashmoe_tpu.serving.engine import Request, ServeConfig, ServingEngine
from flashmoe_tpu.serving.kvcache import SCRATCH_PAGE
from flashmoe_tpu.serving.loadgen import tiny_config
from flashmoe_tpu.serving.speculate import SpecConfig
from flashmoe_tpu.utils.telemetry import FlightRecorder, Metrics

PAGE, N_TAB, N_PAGES, LAYERS, LI, D = 16, 6, 48, 2, 1, 32

#: (query heads, heads a pool keeps, pools) of a case: K/V pools of every
#: head, of a quarter of the heads, and ONE latent pool whose row is the
#: key of all four heads and, in its first RANK columns, the value
KINDS = {"mha": (4, 4, 2), "gqa4": (8, 2, 2), "latent": (4, 1, 1)}
RANK = 24

#: tiny models of each cache kind for the layer's and the engine's arms
MLA = dict(hidden_size=64, intermediate_size=64, dense_intermediate_size=128,
           vocab_size=250, num_heads=3, kv_lora_rank=20, qk_nope_head_dim=10,
           qk_rope_head_dim=6, v_head_dim=14, dtype=jnp.float32,
           param_dtype=jnp.float32)
MODELS = {
    "kv": lambda: tiny_config(vocab=250),       # no other test's programs
    "mla": lambda: PRESETS["joyai-llm-flash"](
        num_layers=3, num_experts=8, expert_top_k=2, q_lora_rank=24, **MLA),
    "hybrid": lambda: PRESETS["ling-3.0-flash"](
        num_layers=3, layer_mixers=("kda", "kda", "mla"), first_k_dense=1,
        num_experts=16, expert_top_k=3, n_group=4, topk_group=2,
        expert_first=4, experts_held=4, kda_heads=3, kda_head_dim=16,
        **MLA),
}


def _slots(t):
    """(pos, what the slot is) of the five slots of a case: positions
    [0, pos) are the slot's context, the span lands at pos .. pos+t-1."""
    return [(0, "length 0, all-scratch table"),
            (2 * PAGE, "context ends on a page edge"),
            (2 * PAGE + 5, "context ends mid-page"),
            (N_TAB * PAGE - t, "the span fills the table's last page"),
            (PAGE - 2, "a span over one row crosses a page edge")]


def _case(t, kind, dtype, seed=0, d=D):
    """Random pools, tables and a span; every pool row at or past a
    slot's length (and every page no slot owns) holds large finite
    garbage.  Returns (q [B, T, N, d], the span's rows and the pools, one
    of each a pool, the tables, the positions, the write targets, the
    span's positions)."""
    nh, nkv, n_pools = KINDS[kind]
    rng = np.random.default_rng(seed)
    pos = np.array([p for p, _ in _slots(t)], np.int32)
    b = len(pos)
    tables = rng.permutation(np.arange(1, N_PAGES))[:b * N_TAB].reshape(
        b, N_TAB).astype(np.int32)
    tables[0] = SCRATCH_PAGE
    shape = (LAYERS, N_PAGES, nkv, PAGE, d)
    pools = []
    for _ in range(n_pools):
        pool = rng.choice([-3e4, 3e4], size=shape)
        for i in range(1, b):
            live = rng.normal(size=(LAYERS, N_TAB, nkv, PAGE, d))
            live = live.transpose(0, 2, 1, 3, 4).reshape(
                LAYERS, nkv, N_TAB * PAGE, d)
            live[:, :, pos[i]:] = rng.choice([-3e4, 3e4],
                                             size=live[:, :, pos[i]:].shape)
            pool[:, tables[i]] = live.reshape(
                LAYERS, nkv, N_TAB, PAGE, d).transpose(0, 2, 1, 3, 4)
        pools.append(jnp.asarray(pool, dtype))
    q = jnp.asarray(rng.normal(size=(b, t, nh, d)), dtype)
    span = tuple(jnp.asarray(rng.normal(size=(b, t, nkv, d)), dtype)
                 for _ in range(n_pools))
    span_pos = pos[:, None] + np.arange(t)[None, :]
    write = (jnp.asarray(np.take_along_axis(tables, span_pos // PAGE, 1)),
             jnp.asarray(span_pos % PAGE, jnp.int32))
    return (q, span, tuple(pools), jnp.asarray(tables), jnp.asarray(pos),
            write, jnp.asarray(span_pos, jnp.int32))


def _plain(q, span, pools, tables, write, span_pos):
    """The gather arm without the projections around it: (the heads'
    outputs [B, T, N * Dv], the pools).  Two pools:
    ``kv_paged_attention``'s.  One: ``mla_paged_attention``'s, from the
    absorbed query to the latent sums, which is multi-query attention
    with a row as the key and its first RANK columns as the value."""
    b, t, nh, d = q.shape
    layer = {"wo": jnp.eye(nh * d, dtype=q.dtype)}
    if len(pools) == 1:
        pool = attention.store_latent(pools[0][:, :, 0], LI,
                                      span[0][:, :, 0], *write)
        ctx = attention.gather_latent(pool, LI, tables, d)[:, None]
        out = attention.kv_attend(layer, q, ctx, ctx, span_pos)
        return (out.reshape(b, t, nh, d)[..., :RANK].reshape(b, t, -1),
                (pool[:, :, None],))
    pools = tuple(attention.store_kv(pool, LI, rows, *write)
                  for pool, rows in zip(pools, span))
    out = attention.kv_attend(
        layer, q, attention.gather_ctx(pools[0], LI, tables),
        attention.gather_ctx(pools[1], LI, tables), span_pos)
    return out, pools


@pytest.mark.parametrize("d", [None, 64], ids=["plain", "packed"])
@pytest.mark.parametrize("li", [0, 1, 2])
def test_gather_ctx_is_the_layers_pages_laid_out_by_hand(li, d):
    """``gather_ctx(pool, li, tables, d)`` indexes layer and pages in ONE
    gather of the whole pool (no layer of it as a value: on the chip that
    is a copy of the layer's pool, ISSUE 50) and gives, bit for bit, what
    ``pool[li][tables]`` laid out by hand gives: a slot's pages in its
    table's order, row ``i * page + r`` of head h the row r of page
    ``tables[b, i]``; a head packed two to a row (``d`` 64 in a width of
    128) comes back as heads 2j and 2j + 1.  A page met twice and the
    scratch page read like any other."""
    rng = np.random.default_rng(50)
    pool = jnp.asarray(rng.normal(size=(3, 7, 2, 4, 128)), jnp.bfloat16)
    tables = np.array([[3, 5, 3], [6, SCRATCH_PAGE, 1]], np.int32)
    got = attention.gather_ctx(pool, li, jnp.asarray(tables), d)
    pages = np.asarray(pool.astype(jnp.float32))[li][tables]  # [B, n, 2, 4, W]
    w = d or 128                                   # a head's width
    p = 128 // w                                   # heads to a row
    want = np.empty((2, 2 * p, 3 * 4, w), np.float32)
    for b, i, j, r, h in np.ndindex(2, 3, 2, 4, p):
        want[b, j * p + h, i * 4 + r] = pages[b, i, j, r, h * w:(h + 1) * w]
    assert got.dtype == pool.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)), want)
    # the program holds the pool and the gathered pages, no layer of it
    text = str(jax.make_jaxpr(
        lambda pool, t: attention.gather_ctx(pool, li, t, d))(pool, tables))
    assert "[7,2,4,128]" not in text and text.count("gather") == 1


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("kind", list(KINDS))
@pytest.mark.parametrize("t", [1, 5])
def test_paged_decode_kernel_is_the_gather_arm(t, kind, dtype):
    """The kernel against the gather arm of its kind (``store_kv`` +
    ``gather_ctx`` + ``kv_attend``; ``store_latent`` + ``gather_latent``
    + the absorbed softmax and sums) on random pools, tables and
    lengths: a slot at length 0 on the scratch page, contexts that end on
    a page edge and mid-page, a slot at its table's last page, a span
    across a page edge, blocks of two pages (so contexts of one, two and
    three blocks), garbage past every length.  f32 to 1e-5, bf16 to
    bf16's rounding; the pools equal to the bit."""
    q, span, pools, tables, pos, write, span_pos = _case(t, kind, dtype)
    want, want_pools = _plain(q, span, pools, tables, write, span_pos)
    got, got_pools = attention.paged_decode_attention(
        q, span, pools, LI, tables, pos, write, block_pages=2,
        v_width=RANK if kind == "latent" else None, interpret=True)
    assert got.dtype == want.dtype and got.shape == want.shape
    tol = 1e-5 if dtype == jnp.float32 else 2 ** -7
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    assert np.abs(np.asarray(got, np.float32)).max() < 10   # no garbage
    assert len(got_pools) == len(want_pools)
    for got_pool, want_pool in zip(got_pools, want_pools):
        np.testing.assert_array_equal(np.asarray(got_pool, np.float32),
                                      np.asarray(want_pool, np.float32))


def _force_kernel(monkeypatch):
    """The kernel's arm for every short span, off the TPU (where the
    paged attentions run it in ``interpret``), for programs traced from
    here on."""
    monkeypatch.setattr(
        attention, "kv_attention_arm",
        lambda t, page, n_kv, d, dtype, pools=2:
        "paged_kernel" if t < page else "gather")


@pytest.mark.parametrize("t", [1, 3])
@pytest.mark.parametrize("model", ["kv", "mla"])
def test_the_kernels_arm_leaves_the_pool_as_the_store_would(monkeypatch,
                                                            model, t):
    """One layer's ``kv_paged_attention`` / ``mla_paged_attention`` on
    either arm: the pools hold the span's rows where ``store_kv`` /
    ``store_latent`` puts them (a latent row padded with zeros to the
    pool's lanes) and not a bit else changed; the attention outputs
    agree."""
    cfg = MODELS[model]()
    if model == "kv":
        cfg = cfg.replace(num_heads=4, num_kv_heads=2)
    layer = init_params(jax.random.PRNGKey(0), cfg)["layers"][LI]
    kind, d = (("gqa4", cfg.resolved_head_dim) if model == "kv"
               else ("latent", cfg.kv_row_elems))
    _, _, pools, tables, pos, write, span_pos = _case(
        t, kind, jnp.float32, seed=t, d=d)
    if model == "mla":
        pools = (pools[0][:, :, 0],)                # [L, P, page, R]
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (len(pos), t, cfg.hidden_size), jnp.float32)

    def arm():
        if model == "kv":
            return jax.jit(lambda pools: attention.kv_paged_attention(
                layer, x, cfg, pools, LI, span_pos, write, tables)[:2])(pools)
        out, pool, _ = jax.jit(lambda pool: attention.mla_paged_attention(
            layer, x, cfg, pool, LI, span_pos, write, tables,
            absorbed=True))(pools[0])
        return out, (pool,)

    want, want_pools = arm()
    _force_kernel(monkeypatch)
    got, got_pools = arm()
    for before, got_pool, want_pool in zip(pools, got_pools, want_pools):
        np.testing.assert_array_equal(got_pool, want_pool)
        changed = np.asarray(got_pool != before)
        changed = changed.any(axis=(2, 4) if model == "kv" else 3)
        rows = np.zeros_like(changed)
        rows[LI, np.asarray(write[0]), np.asarray(write[1])] = True
        np.testing.assert_array_equal(changed, rows)
    if model == "mla":          # the padding of a written row is zeros
        written = np.asarray(got_pools[0])[
            LI, np.asarray(write[0]), np.asarray(write[1])]
        assert not written[..., cfg.kv_token_elems:].any()
        assert written[..., :cfg.kv_token_elems].all()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("model, speculate", [
    ("kv", None), ("kv", 3), ("mla", None), ("mla", 3),
    ("hybrid", None),           # a recurrent-state config never speculates
], ids=["kv-decode", "kv-verify", "mla-decode", "mla-verify",
        "hybrid-decode"])
def test_engine_on_the_kernels_arm_serves_the_gather_arms_tokens(
        monkeypatch, model, speculate):
    """The engine's decode step (and, speculating, its verify step) with
    the kernel's arm forced through ``interpret``, over a K/V cache, a
    latent cache and a hybrid one (latent pages beside recurrent state):
    the token streams of the gather arm on the seeded toy model, and the
    records and the counter say which arm ran and what it read."""
    cfg = MODELS[model]()
    params = init_params(jax.random.PRNGKey(0), cfg)
    motifs = np.random.default_rng(7).integers(0, 250, (4, 2))
    reqs = [Request(rid=i, prompt=tuple(int(motifs[i][j % 2])
                                        for j in range(8 + 3 * i)),
                    max_new_tokens=8) for i in range(4)]
    serve = ServeConfig(
        max_batch=4, page_size=8, num_pages=32, max_pages_per_slot=4,
        ctx_bucket_pages=4, prompt_bucket=8,    # one decode program
        speculate=speculate and SpecConfig(draft_tokens=speculate))
    programs = [eng._INPLACE[name] for name in
                ("_paged_decode_step", "_paged_verify_step")]

    def run():
        for program in programs:
            program.clear_cache()       # trace with the arm of the moment
        recorder, metrics = FlightRecorder(), Metrics()
        engine = ServingEngine(params, cfg, serve, recorder=recorder,
                               metrics_obj=metrics)
        out = engine.run(reqs, arrivals=[0, 0, 1, 2])
        decodes = [r for r in recorder.records
                   if r["kind"] == "serve_decode"]
        return out, decodes, metrics.counters.get(
            "serve.decode_kernel_steps", 0), engine

    want, decodes, kernel_steps, _ = run()
    assert {r["attn_arm"] for r in decodes} == {"gather"}
    assert kernel_steps == 0
    assert {r["ctx_pages"] for r in decodes} == {4}         # the bucket
    try:
        _force_kernel(monkeypatch)
        got, decodes, kernel_steps, engine = run()
    finally:
        monkeypatch.undo()
        for program in programs:
            program.clear_cache()
    assert got == want
    assert {r["attn_arm"] for r in decodes} == {"paged_kernel"}
    assert kernel_steps == len(decodes) > 0
    if speculate:
        assert engine.spec_snapshot()["spec_drafted"] > 0, "never verified"
    # each slot's own pages in whole blocks (the table's four pages a
    # block here: one block wherever a slot has a context) and the page
    # or two the span is written into; idle is that less what the
    # contexts fill
    pools, heads, row = cfg.kv_pool_rows
    block = attention.paged_decode_block_pages(serve.page_size, 4, heads,
                                               row, cfg.dtype, pools)
    assert block == 4
    for r in decodes:
        assert 1 <= r["ctx_pages"] <= block + 2
        assert 0 <= r["ctx_pages_idle"] < r["ctx_pages"]


@pytest.mark.parametrize("backend, t, page, n_kv, d, dtype, pools, arm", [
    ("tpu", 1, 16, 16, 128, jnp.bfloat16, 2, "paged_kernel"),   # decode
    ("tpu", 5, 16, 16, 128, jnp.bfloat16, 2, "paged_kernel"),   # verify
    ("tpu", 1, 16, 4, 128, jnp.float32, 2, "paged_kernel"),
    ("tpu", 1024, 16, 16, 128, jnp.bfloat16, 2, "gather"),      # a chunk
    ("tpu", 16, 16, 16, 128, jnp.bfloat16, 2, "gather"),     # a whole page
    ("tpu", 1, 4096, 16, 128, jnp.bfloat16, 2, "gather"),    # dense cache
    ("tpu", 1, 8, 16, 128, jnp.bfloat16, 2, "gather"),       # half a tile
    ("tpu", 1, 16, 16, 64, jnp.bfloat16, 2, "gather"),    # half the lanes
    ("tpu", 1, 16, 64, 256, jnp.float32, 2, "gather"),    # 32 MB of VMEM
    ("cpu", 1, 16, 16, 128, jnp.bfloat16, 2, "gather"),
    # ONE latent pool, a page [1, page, row]: the two MLA cells' decode
    # and verify steps, their chunk, the dense cache, a row as the model
    # defines it (4.5 lanes: what the pool pads away), the CPU
    ("tpu", 1, 16, 1, 640, jnp.bfloat16, 1, "paged_kernel"),
    ("tpu", 5, 16, 1, 640, jnp.bfloat16, 1, "paged_kernel"),
    ("tpu", 1, 16, 1, 640, jnp.float32, 1, "paged_kernel"),
    ("tpu", 1024, 16, 1, 640, jnp.bfloat16, 1, "gather"),
    ("tpu", 1, 4096, 1, 640, jnp.bfloat16, 1, "gather"),
    ("tpu", 1, 8, 1, 640, jnp.bfloat16, 1, "gather"),
    ("tpu", 1, 16, 1, 576, jnp.bfloat16, 1, "gather"),
    ("cpu", 1, 16, 1, 640, jnp.bfloat16, 1, "gather"),
], ids=str)
def test_the_arm_follows_the_shapes_and_the_backend(monkeypatch, backend, t,
                                                    page, n_kv, d, dtype,
                                                    pools, arm):
    """No option picks the arm, and ONE rule picks it for both kinds of
    pool: a span shorter than a page over pages that tile the kernel's
    block, on a TPU; decode and verify alike."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert attention.kv_attention_arm(t, page, n_kv, d, dtype, pools) == arm


@pytest.mark.parametrize("n_kv, d, dtype, pools, pages", [
    (16, 128, jnp.bfloat16, 2, 8),      # the backlog cell: 128 positions
    (1, 640, jnp.bfloat16, 1, 32),      # the MLA cells: 512 latent rows
    (4, 128, jnp.float32, 2, 16),
], ids=str)
def test_a_block_holds_a_megabyte_of_context(n_kv, d, dtype, pools, pages):
    """The walk's block follows the bytes of a position: 128 positions at
    least, then the largest power of two within a megabyte over all
    pools, and never more than the tables hold."""
    assert attention.paged_decode_block_pages(
        16, 448, n_kv, d, dtype, pools) == pages
    assert attention.paged_decode_block_pages(
        16, 4, n_kv, d, dtype, pools) == 4
