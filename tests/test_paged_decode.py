"""The paged decode attention kernel (``ops/attention.fm_paged_decode``)
held against the plain form it replaces on a TPU: ``store_kv`` +
``gather_ctx`` + ``kv_attend``.  The kernel runs in ``interpret`` here, at
small shapes; what the chip's compiler makes of it at the benchmark cell's
shapes is ``tests/test_tpu_compile.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashmoe_tpu.models.transformer import init_params
from flashmoe_tpu.ops import attention
from flashmoe_tpu.serving import engine as eng
from flashmoe_tpu.serving.engine import Request, ServeConfig, ServingEngine
from flashmoe_tpu.serving.kvcache import SCRATCH_PAGE
from flashmoe_tpu.serving.loadgen import tiny_config
from flashmoe_tpu.serving.speculate import SpecConfig
from flashmoe_tpu.utils.telemetry import FlightRecorder, Metrics

PAGE, N_TAB, N_PAGES, LAYERS, LI, D = 16, 6, 48, 2, 1, 32


def _slots(t):
    """(pos, what the slot is) of the five slots of a case: positions
    [0, pos) are the slot's context, the span lands at pos .. pos+t-1."""
    return [(0, "length 0, all-scratch table"),
            (2 * PAGE, "context ends on a page edge"),
            (2 * PAGE + 5, "context ends mid-page"),
            (N_TAB * PAGE - t, "the span fills the table's last page"),
            (PAGE - 2, "a span over one row crosses a page edge")]


def _case(t, nh, nkv, dtype, seed=0):
    """Random pools, tables and a span; every pool row at or past a
    slot's length (and every page no slot owns) holds large finite
    garbage."""
    rng = np.random.default_rng(seed)
    pos = np.array([p for p, _ in _slots(t)], np.int32)
    b = len(pos)
    tables = rng.permutation(np.arange(1, N_PAGES))[:b * N_TAB].reshape(
        b, N_TAB).astype(np.int32)
    tables[0] = SCRATCH_PAGE
    shape = (LAYERS, N_PAGES, nkv, PAGE, D)
    pools = []
    for _ in range(2):
        pool = rng.choice([-3e4, 3e4], size=shape)
        for i in range(1, b):
            live = rng.normal(size=(LAYERS, N_TAB, nkv, PAGE, D))
            live = live.transpose(0, 2, 1, 3, 4).reshape(
                LAYERS, nkv, N_TAB * PAGE, D)
            live[:, :, pos[i]:] = rng.choice([-3e4, 3e4],
                                             size=live[:, :, pos[i]:].shape)
            pool[:, tables[i]] = live.reshape(
                LAYERS, nkv, N_TAB, PAGE, D).transpose(0, 2, 1, 3, 4)
        pools.append(jnp.asarray(pool, dtype))
    q = jnp.asarray(rng.normal(size=(b, t, nh, D)), dtype)
    k = jnp.asarray(rng.normal(size=(b, t, nkv, D)), dtype)
    v = jnp.asarray(rng.normal(size=(b, t, nkv, D)), dtype)
    span_pos = pos[:, None] + np.arange(t)[None, :]
    write = (jnp.asarray(np.take_along_axis(tables, span_pos // PAGE, 1)),
             jnp.asarray(span_pos % PAGE, jnp.int32))
    return (q, k, v, tuple(pools), jnp.asarray(tables), jnp.asarray(pos),
            write, jnp.asarray(span_pos, jnp.int32))


def _plain(q, k, v, pools, tables, write, span_pos):
    """The gather arm of ``kv_paged_attention``, without the output
    projection: (the heads' outputs [B, T, N * D], the pools)."""
    pools = tuple(attention.store_kv(pool, LI, rows, *write)
                  for pool, rows in zip(pools, (k, v)))
    nh, d = q.shape[2:]
    layer = {"wo": jnp.eye(nh * d, dtype=q.dtype)}
    out = attention.kv_attend(
        layer, q, attention.gather_ctx(pools[0][LI], tables),
        attention.gather_ctx(pools[1][LI], tables), span_pos)
    return out, pools


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", [(4, 4), (8, 2)], ids=["mha", "gqa4"])
@pytest.mark.parametrize("t", [1, 5])
def test_paged_decode_kernel_is_the_gather_arm(t, heads, dtype):
    """``fm_paged_decode`` against ``store_kv`` + ``gather_ctx`` +
    ``kv_attend`` on random pools, tables and lengths: a slot at length
    0 on the scratch page, contexts that end on a page edge and
    mid-page, a slot at its table's last page, a span across a page
    edge, blocks of two pages (so contexts of one, two and three
    blocks), garbage past every length.  f32 to 1e-5, bf16 to bf16's
    rounding; the pools equal to the bit."""
    q, k, v, pools, tables, pos, write, span_pos = _case(t, *heads, dtype)
    want, want_pools = _plain(q, k, v, pools, tables, write, span_pos)
    got, got_pools = attention.paged_decode_attention(
        q, k, v, pools, LI, tables, pos, write, block_pages=2,
        interpret=True)
    assert got.dtype == want.dtype and got.shape == want.shape
    tol = 1e-5 if dtype == jnp.float32 else 2 ** -7
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    assert np.abs(np.asarray(got, np.float32)).max() < 10   # no garbage
    for got_pool, want_pool in zip(got_pools, want_pools):
        np.testing.assert_array_equal(np.asarray(got_pool, np.float32),
                                      np.asarray(want_pool, np.float32))


def _force_kernel(monkeypatch):
    """The kernel's arm for every short span, off the TPU (where
    ``kv_paged_attention`` runs it in ``interpret``), for programs traced
    from here on."""
    monkeypatch.setattr(
        attention, "kv_attention_arm",
        lambda t, page, n_kv, d, dtype:
        "paged_kernel" if t < page else "gather")


@pytest.mark.parametrize("t", [1, 3])
def test_the_kernels_arm_leaves_the_pool_as_store_kv_would(monkeypatch, t):
    """One layer's ``kv_paged_attention`` on either arm: the pools hold
    the span's rows where ``store_kv`` puts them and not a bit else
    changed; the attention outputs agree."""
    cfg = tiny_config().replace(num_heads=4, num_kv_heads=2)
    layer = init_params(jax.random.PRNGKey(0), cfg)["layers"][LI]
    _, _, _, pools, tables, pos, write, span_pos = _case(
        t, 4, 2, jnp.float32, seed=t)
    pools = tuple(pool[..., :cfg.resolved_head_dim] for pool in pools)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (len(pos), t, cfg.hidden_size), jnp.float32)
    arm = lambda: jax.jit(lambda pools: attention.kv_paged_attention(
        layer, x, cfg, pools, LI, span_pos, write, tables))(pools)
    want, want_pools, _ = arm()
    _force_kernel(monkeypatch)
    got, got_pools, _ = arm()
    for before, got_pool, want_pool in zip(pools, got_pools, want_pools):
        np.testing.assert_array_equal(got_pool, want_pool)
        changed = np.asarray(got_pool != before).any(axis=(2, 4))
        rows = np.zeros_like(changed)
        rows[LI, np.asarray(write[0]), np.asarray(write[1])] = True
        np.testing.assert_array_equal(changed, rows)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("speculate", [None, 3], ids=["decode", "verify"])
def test_engine_on_the_kernels_arm_serves_the_gather_arms_tokens(
        monkeypatch, speculate):
    """The engine's decode step (and, speculating, its verify step) with
    the kernel's arm forced through ``interpret``: the token streams of
    the gather arm on the seeded toy model, and the records and the
    counter say which arm ran and what it read."""
    cfg = tiny_config(vocab=250)        # no other test's programs
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
    block = attention.paged_decode_block_pages(serve.page_size, 4)
    assert block == 4
    for r in decodes:
        assert 1 <= r["ctx_pages"] <= block + 2
        assert 0 <= r["ctx_pages_idle"] < r["ctx_pages"]


@pytest.mark.parametrize("backend, t, page, n_kv, d, dtype, arm", [
    ("tpu", 1, 16, 16, 128, jnp.bfloat16, "paged_kernel"),   # decode
    ("tpu", 5, 16, 16, 128, jnp.bfloat16, "paged_kernel"),   # verify: same
    ("tpu", 1, 16, 4, 128, jnp.float32, "paged_kernel"),
    ("tpu", 1024, 16, 16, 128, jnp.bfloat16, "gather"),      # a chunk
    ("tpu", 16, 16, 16, 128, jnp.bfloat16, "gather"),        # a whole page
    ("tpu", 1, 4096, 16, 128, jnp.bfloat16, "gather"),       # dense cache
    ("tpu", 1, 8, 16, 128, jnp.bfloat16, "gather"),          # half a tile
    ("tpu", 1, 16, 16, 64, jnp.bfloat16, "gather"),          # half the lanes
    ("tpu", 1, 16, 64, 256, jnp.float32, "gather"),          # 32 MB of VMEM
    ("cpu", 1, 16, 16, 128, jnp.bfloat16, "gather"),
], ids=str)
def test_the_arm_follows_the_shapes_and_the_backend(monkeypatch, backend, t,
                                                    page, n_kv, d, dtype,
                                                    arm):
    """No option picks the arm: a span shorter than a page over pages
    that tile the kernel's block, on a TPU; decode and verify alike."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert attention.kv_attention_arm(t, page, n_kv, d, dtype) == arm
