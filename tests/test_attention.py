"""Flash attention kernel + ring attention vs the XLA oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashmoe_tpu.ops import attention
from flashmoe_tpu.ops.attention import (
    NEG_INF, _flash_forward, attention_xla, flash_attention, flash_blocks,
    flash_span_attention, span_attention_arm,
)
from flashmoe_tpu.parallel.ringattn import ring_attention
from jax.sharding import Mesh


def _qkv(b=1, n=2, t=256, d=64, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, n, t, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, n, t, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, n, t, d), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_xla(causal):
    q, k, v = _qkv()
    want = attention_xla(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
    )


def test_flash_uneven_blocks():
    q, k, v = _qkv(t=384)
    want = attention_xla(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                          interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
    )


def _close(got, want, dtype):
    """float32 to the oracle's own rounding; bfloat16 to a few ulps of the
    output's scale (both sides round p and the output to 8 bits)."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    tol = 2e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("blocks", [None, (64, 128)], ids=["rule", "64x128"])
@pytest.mark.parametrize("t", [256, 384, 1024])
def test_flash_tiles_match_xla(t, blocks, causal, dtype):
    """The rule's tile (one block a side up to 1024) and an explicit small
    one, on the operands' own dtype."""
    q, k, v = (a.astype(dtype) for a in _qkv(t=t))
    bq, bk = blocks or (None, None)
    want = attention_xla(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                          interpret=True)
    assert got.dtype == dtype
    _close(got, want, dtype)


@pytest.mark.parametrize("blocks", [(128, 256), (256, 128)],
                         ids=["q_short", "k_short"])
def test_flash_diagonal_clamp_with_unequal_blocks(blocks):
    """block_q != block_k: the last K/V block a query block needs is no
    longer its own index, on either side of the diagonal."""
    q, k, v = _qkv(t=512)
    want = attention_xla(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, block_q=blocks[0],
                          block_k=blocks[1], interpret=True)
    _close(got, want, jnp.float32)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("blocks", [None, (128, 64)], ids=["rule", "128x64"])
def test_flash_lse_is_the_oracles_logsumexp(blocks, causal):
    q, k, v = _qkv(t=256)
    bq, bk = blocks or (None, None)
    _, lse = _flash_forward(q, k, v, causal=causal, scale=None, block_q=bq,
                            block_k=bk, interpret=True)
    s = jnp.einsum("bntd,bnsd->bnts", q, k) * q.shape[-1] ** -0.5
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((256, 256), bool)), s, NEG_INF)
    want = jax.nn.logsumexp(s, axis=-1).reshape(2, 1, 256)
    assert lse.shape == (2, 1, 256) and lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_flash_rule_tile_walks_several_blocks():
    """T 2048: the rule's 1024 x 1024, two blocks a side, one of them
    above the diagonal (never fetched, never computed)."""
    q, k, v = _qkv(n=1, t=2048)
    assert flash_blocks(2048, 2048, 64, q.dtype) == (1024, 1024)
    want = attention_xla(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, interpret=True)
    _close(got, want, jnp.float32)


def test_flash_blocks_rule():
    """The tile from what the code sees: 1024 a side where it divides the
    side, the largest whole-lane divisor under it otherwise, the side
    itself where it has none, the larger side halved while the kernels
    would ask for more VMEM than Mosaic's default scope."""
    assert flash_blocks(4096, 4096, 128, jnp.bfloat16) == (1024, 1024)
    assert flash_blocks(4096, 4096, 128, jnp.float32) == (1024, 512)
    assert flash_blocks(4096, 4096, 256, jnp.bfloat16) == (1024, 512)
    assert flash_blocks(4096, 4096, 512, jnp.float32) == (256, 256)
    assert flash_blocks(1024, 1024, 64, jnp.float32) == (1024, 1024)
    assert flash_blocks(384, 384, 64, jnp.float32) == (384, 384)
    assert flash_blocks(640, 1280, 64, jnp.float32) == (640, 640)
    assert flash_blocks(1280, 3072, 64, jnp.float32) == (640, 1024)
    assert flash_blocks(200, 200, 64, jnp.float32) == (200, 200)
    # the forward kernel alone (the span form): keys 256 lanes wide in
    # VMEM (MLA's 128 + 64) and values 128 keep 1024 a side in bfloat16
    bf16, f32 = jnp.bfloat16, jnp.float32
    assert flash_blocks(1024, 7168, 256, bf16, 128) == (1024, 1024)
    assert flash_blocks(1024, 2048, 256, f32, 128) == (1024, 512)
    assert flash_blocks(1024, 2560, 128, bf16, 128) == (1024, 640)
    assert flash_blocks(1024, 4608, 128, bf16, 128) == (1024, 768)
    assert flash_blocks(256, 256, 256, bf16, 128) == (256, 256)


# ----------------------------------------------------------------------
# The span form: the forward kernel with the first query's position an
# operand (serving's prefill programs), against the plain XLA forms
# ----------------------------------------------------------------------

def _mla_case(n, dt, seed=0):
    """A layer and config of latent attention at the published head
    widths (128 + a shared 64 for the keys, 128 for the values)."""
    from flashmoe_tpu.config import MoEConfig

    cfg = MoEConfig(num_experts=1, expert_top_k=1, hidden_size=64,
                    intermediate_size=64, num_heads=n, attention_kind="mla",
                    kv_lora_rank=32, qk_nope_head_dim=128,
                    qk_rope_head_dim=64, v_head_dim=128, dtype=dt,
                    param_dtype=dt)
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    layer = {"wkv_b": (jax.random.normal(ks[0], (32, n * 256), jnp.float32)
                       * 32 ** -0.5).astype(dt),
             "wo": (jax.random.normal(ks[1], (n * 128, 64), jnp.float32)
                    * (n * 128) ** -0.5).astype(dt)}
    return cfg, layer


#: form, batch, query heads, K/V heads, span rows, context rows, the
#: first query's position
_SPAN_CASES = {
    "offset_0": ("kv", 1, 2, 2, 256, 256, 0),
    "offset_one_block": ("kv", 1, 2, 2, 128, 384, 128),
    "offset_nine_pages": ("kv", 2, 2, 2, 128, 384, 144),
    "two_kv_heads_of_32": ("kv", 1, 32, 2, 128, 256, 48),
    "oracle_tq_not_tk": ("oracle", 1, 2, 1, 256, 512, 208),
    "mla_192_and_128": ("mla", 1, 2, 1, 128, 384, 144),
    "mla_whole_prompt": ("mla", 2, 3, 1, 256, 256, 0),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", _SPAN_CASES)
def test_flash_span_matches_the_xla_forms(monkeypatch, case, dtype):
    """``flash_span_attention`` (interpret) over query offsets (0, a
    block, nine 16-row pages), Tq != Tk, two K/V heads under 32 query
    heads, and MLA's 192-wide keys (128 + ONE shared 64-wide rotary key)
    with 128-wide values: ``kv_attend`` and ``mla_attend(absorbed=False)``
    with the arm forced against their plain XLA form, and the call itself
    against ``attention_xla``."""
    form, b, n, n_kv, tq, tk, pos0 = _SPAN_CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    rnd = lambda k, *shape: jax.random.normal(
        k, shape, jnp.float32).astype(dtype)
    q_pos = pos0 + jnp.broadcast_to(jnp.arange(tq, dtype=jnp.int32),
                                    (b, tq))
    flash = lambda: monkeypatch.setattr(
        attention, "span_attention_arm", lambda *a: "flash")
    assert span_attention_arm(tq, tk, n, (128,), 128, dtype) == "xla"  # CPU
    if form == "oracle":
        q, k, v = rnd(ks[0], b, n, tq, 128), rnd(ks[1], b, n_kv, tk, 128), \
            rnd(ks[2], b, n_kv, tk, 128)
        want = attention_xla(q, jnp.repeat(k, n // n_kv, 1),
                             jnp.repeat(v, n // n_kv, 1), q_offset=pos0)
        got = flash_span_attention((q,), (k,), v, q_pos[:, 0],
                                   scale=128 ** -0.5, interpret=True)
    elif form == "kv":
        layer = {"wo": rnd(ks[3], n * 128, 64) * (n * 128) ** -0.5}
        args = (layer, rnd(ks[0], b, tq, n, 128),
                rnd(ks[1], b, n_kv, tk, 128), rnd(ks[2], b, n_kv, tk, 128),
                q_pos)
        want = attention.kv_attend(*args)
        flash()
        got = attention.kv_attend(*args)
    else:
        cfg, layer = _mla_case(n, dtype)
        args = (layer, rnd(ks[0], b, tq, n, 128), rnd(ks[1], b, tq, n, 64),
                rnd(ks[2], b, tk, 32 + 64), cfg, q_pos)
        want = attention.mla_attend(*args, absorbed=False)
        flash()
        got = attention.mla_attend(*args, absorbed=False)
    assert got.shape == want.shape and got.dtype == dtype
    _close(got, want, dtype)


@pytest.mark.parametrize("form", ["kv", "mla"])
def test_flash_span_keeps_scratch_rows_out(monkeypatch, form):
    """Context rows past the last query's position are scratch: NaN in
    them (in a block the diagonal crosses and in one wholly past it, which
    is never fetched) does not reach the output, which is the XLA form's
    over zeros there."""
    tq, tk, pos0 = 128, 512, 80
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    rnd = lambda k, *shape: jax.random.normal(k, shape, jnp.float32)
    q_pos = pos0 + jnp.arange(tq, dtype=jnp.int32)[None]
    past = (jnp.arange(tk) >= pos0 + tq)[:, None]
    if form == "kv":
        layer = {"wo": rnd(ks[3], 2 * 128, 64) * 256 ** -0.5}
        q, k, v = rnd(ks[0], 1, tq, 2, 128), rnd(ks[1], 1, 2, tk, 128), \
            rnd(ks[2], 1, 2, tk, 128)
        call = lambda fill: attention.kv_attend(
            layer, q, jnp.where(past, fill, k), jnp.where(past, fill, v),
            q_pos)
    else:
        cfg, layer = _mla_case(2, jnp.float32)
        q_nope, q_rope = rnd(ks[0], 1, tq, 2, 128), rnd(ks[1], 1, tq, 2, 64)
        latent = rnd(ks[2], 1, tk, 32 + 64)
        call = lambda fill: attention.mla_attend(
            layer, q_nope, q_rope, jnp.where(past, fill, latent), cfg,
            q_pos, absorbed=False)
    want = call(0.0)
    assert bool(jnp.isnan(call(jnp.nan)).any())    # the XLA form: 0 x NaN
    monkeypatch.setattr(attention, "span_attention_arm", lambda *a: "flash")
    got = call(jnp.nan)
    assert not bool(jnp.isnan(got).any())
    _close(got, want, jnp.float32)


def test_span_attention_arm_is_one_rule_over_shapes_and_backend(monkeypatch):
    """``"flash"`` on a TPU for whole 128-row blocks of span and context
    whose float32 scores would take 64 MiB or more, parts of whole or half
    lane tiles, bfloat16 or float32; ``"xla"`` for a decode or verify
    span, a short prompt (XLA's fusions keep up under 64 MiB of scores:
    PERF.md section 6, PR 44), rows or widths off the blocks, another
    dtype, any other backend."""
    arm = lambda t, s, n=64, k=(128, 64), v=128, dt=jnp.bfloat16: \
        span_attention_arm(t, s, n, k, v, dt)
    assert arm(1024, 2048) == "xla"                 # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert arm(1024, 2048) == arm(512, 512) == arm(1024, 7168) == "flash"
    assert arm(1024, 1024, 16, (128,), 128) == "flash"      # 64 MiB
    assert arm(768, 768, 32) == arm(1024, 1024, 32, (64,), 64) == "flash"
    assert arm(128, 4608, 32, (128,), 128, jnp.float32) == "flash"
    # scores under 64 MiB: short whole prompts
    assert arm(256, 256) == arm(512, 512, 32) == "xla"
    assert arm(768, 768, 16, (128,), 128) == "xla"
    assert arm(1, 2048) == arm(5, 2048) == "xla"    # decode, verify
    assert arm(1000, 2048) == arm(1024, 2000) == arm(1024, 512) == "xla"
    assert arm(1024, 1024, 64, (10, 6), 14) == "xla"
    assert arm(1024, 1024, 64, (96,), 96) == "xla"
    assert arm(1024, 1024, dt=jnp.float16) == "xla"


def test_training_flash_call_is_the_span_calls_algorithm():
    """The training call (offset 0, equal widths, Tq == Tk) launches
    ``fm_flash_fwd`` over three blocked inputs, with the log-sum-exp
    beside the output; its output equals the span call's at
    offset 0 to the bit (one body, one tile rule), and its gradients are
    the oracle's."""
    q, k, v = _qkv(t=256)
    text = str(jax.make_jaxpr(
        lambda *a: flash_attention(*a, interpret=True))(q, k, v))
    assert text.count("pallas_call") == 1 and "fm_flash_fwd" in text
    assert text.count("BlockMapping(block_shape") == 5  # q, k, v; o, lse
    assert "fm_flash_span" not in text
    got = flash_attention(q, k, v, interpret=True)
    span = flash_span_attention((q,), (k,), v, jnp.zeros((1,), jnp.int32),
                                scale=64 ** -0.5, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(span))
    loss = lambda f: lambda q, k, v: (f(q, k, v) ** 2).sum()
    want = jax.grad(loss(attention_xla), argnums=(0, 1, 2))(q, k, v)
    grads = jax.jit(jax.grad(loss(lambda *a: flash_attention(
        *a, interpret=True)), argnums=(0, 1, 2)))(q, k, v)
    for g, w in zip(grads, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("sp,causal", [(4, True), (8, True), (4, False)])
def test_ring_attention_matches_full(sp, causal, devices):
    import numpy as onp
    q, k, v = _qkv(t=512)
    mesh = Mesh(onp.asarray(devices[:sp]), ("sp",))
    want = attention_xla(q, k, v, causal=causal)
    if causal:
        got = jax.jit(lambda q, k, v: ring_attention(
            q, k, v, mesh, causal=True))(q, k, v)
    else:
        # eager on purpose: a bare call works (ring_attention is a
        # public function); the causal cases run it under jax.jit
        got = ring_attention(q, k, v, mesh, causal=False)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
    )


def test_ring_attention_long_context(devices):
    """8-way sharded 2048-token causal attention, bf16 inputs."""
    import numpy as onp
    q, k, v = _qkv(b=1, n=1, t=2048, d=64)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    mesh = Mesh(onp.asarray(devices[:8]), ("sp",))
    got = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, mesh, causal=True))(q, k, v)
    want = attention_xla(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=True,
    )
    rel = float(
        jnp.max(jnp.abs(got.astype(jnp.float32) - want))
        / jnp.max(jnp.abs(want))
    )
    assert rel < 0.05, rel
