"""Flash attention kernel + ring attention vs the XLA oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashmoe_tpu.ops.attention import (
    NEG_INF, _flash_forward, attention_xla, flash_attention, flash_blocks,
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


@pytest.mark.parametrize("sp,causal", [(4, True), (8, True), (4, False)])
def test_ring_attention_matches_full(sp, causal, devices):
    import numpy as onp
    q, k, v = _qkv(t=512)
    mesh = Mesh(onp.asarray(devices[:sp]), ("sp",))
    want = attention_xla(q, k, v, causal=causal)
    got = ring_attention(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
    )


def test_ring_attention_long_context(devices):
    """8-way sharded 2048-token causal attention, bf16 inputs."""
    import numpy as onp
    q, k, v = _qkv(b=1, n=1, t=2048, d=64)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    mesh = Mesh(onp.asarray(devices[:8]), ("sp",))
    got = ring_attention(q, k, v, mesh, causal=True)
    want = attention_xla(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=True,
    )
    rel = float(
        jnp.max(jnp.abs(got.astype(jnp.float32) - want))
        / jnp.max(jnp.abs(want))
    )
    assert rel < 0.05, rel
