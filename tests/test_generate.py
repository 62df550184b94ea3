"""KV-cache generation: consistency with the training-path forward,
the two prefill arms, sampling truncations, and stop tokens."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashmoe_tpu.config import MoEConfig
from flashmoe_tpu.models.generate import (
    _decode_step, generate, init_cache, prefill_batched, prefill_loop,
    sample_tokens,
)
from flashmoe_tpu.models.transformer import forward, init_params

CFG = MoEConfig(num_experts=4, expert_top_k=2, hidden_size=64,
                intermediate_size=128, sequence_len=64, num_layers=2,
                moe_frequency=2, vocab_size=256, num_heads=2,
                drop_tokens=False, dtype=jnp.float32,
                param_dtype=jnp.float32)


def test_greedy_matches_full_forward():
    """Greedy decode must reproduce argmax of the full (non-cached)
    forward at every step."""
    params = init_params(jax.random.PRNGKey(0), CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 256)
    out = generate(params, prompt, CFG, max_new_tokens=4)
    assert out.shape == (2, 12)

    # oracle: re-run the full forward on the growing sequence
    fwd = jax.jit(lambda p, s: forward(p, s, CFG))  # one program a length
    seq = prompt
    for _ in range(4):
        logits, _ = fwd(params, seq)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(seq))


def test_sampled_decode_shape_and_range():
    params = init_params(jax.random.PRNGKey(0), CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 4), 0, 256)
    out = generate(params, prompt, CFG, max_new_tokens=8, temperature=1.0,
                   key=jax.random.PRNGKey(3))
    assert out.shape == (1, 12)
    toks = np.asarray(out)
    assert (toks >= 0).all() and (toks < 256).all()


def test_batched_prefill_logits_equal_loop():
    """Satellite: the single-pass prefill and the one-token-at-a-time
    loop are logits-equal (and cache-equal) on dropless configs."""
    params = init_params(jax.random.PRNGKey(0), CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 256)
    lb, cb = prefill_batched(params, CFG, prompt, init_cache(CFG, 2, 8))
    ll, cl = prefill_loop(params, CFG, prompt, init_cache(CFG, 2, 8))
    np.testing.assert_allclose(np.asarray(lb), np.asarray(ll),
                               rtol=0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cb.k), np.asarray(cl.k),
                               rtol=0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cb.v), np.asarray(cl.v),
                               rtol=0, atol=1e-5)
    # and the full decode agrees token-for-token across the two arms
    out_b = generate(params, prompt, CFG, max_new_tokens=4,
                     prefill="batched")
    out_l = generate(params, prompt, CFG, max_new_tokens=4,
                     prefill="loop")
    np.testing.assert_array_equal(np.asarray(out_b), np.asarray(out_l))


def test_prefill_auto_and_validation():
    params = init_params(jax.random.PRNGKey(0), CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 4), 0, 256)
    with pytest.raises(ValueError, match="prefill"):
        generate(params, prompt, CFG, max_new_tokens=2,
                 prefill="bogus")


def test_teacher_forcing_decode_matches_forward():
    """Satellite: step-wise decode logits pin against the full-sequence
    training forward on the SAME tokens — the equivalence nothing
    previously asserted between ``_decode_step`` and
    ``transformer.forward``."""
    params = init_params(jax.random.PRNGKey(0), CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 10), 0, 256)
    full, _ = forward(params, tokens, CFG)          # [B, T, V]

    cache = init_cache(CFG, 2, 10)
    step_logits = []
    for i in range(10):
        x = params["embed"].astype(CFG.dtype)[tokens[:, i]][:, None, :]
        lg, cache = _decode_step(params, CFG, x, cache, jnp.int32(i))
        step_logits.append(lg)
    stepwise = jnp.stack(step_logits, axis=1)       # [B, T, V]
    np.testing.assert_allclose(np.asarray(stepwise), np.asarray(full),
                               rtol=0, atol=2e-5)


def test_sample_tokens_truncations():
    """top-k=1 is argmax at any temperature; top-p -> 0 keeps only the
    head; truncations never emit a masked token."""
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (4, 32), jnp.float32) * 3.0
    greedy = np.asarray(jnp.argmax(logits, axis=-1))
    np.testing.assert_array_equal(
        np.asarray(sample_tokens(logits, key, temperature=0.0)), greedy)
    np.testing.assert_array_equal(
        np.asarray(sample_tokens(logits, key, temperature=1.3,
                                 top_k=1)), greedy)
    np.testing.assert_array_equal(
        np.asarray(sample_tokens(logits, key, temperature=0.9,
                                 top_p=1e-6)), greedy)
    # top-k=3: every draw must come from the 3 highest logits
    top3 = np.argsort(np.asarray(logits), axis=-1)[:, -3:]
    for s in range(5):
        draw = np.asarray(sample_tokens(
            logits, jax.random.PRNGKey(s), temperature=1.0, top_k=3))
        for b in range(4):
            assert draw[b] in top3[b]
    with pytest.raises(ValueError, match="top_p"):
        sample_tokens(logits, key, temperature=1.0, top_p=0.0)
    with pytest.raises(ValueError, match="top_k"):
        sample_tokens(logits, key, temperature=1.0, top_k=-1)


def test_stop_tokens_freeze_rows():
    """A row that emits a stop token pads the rest of its output while
    other rows keep decoding (per-request retirement semantics)."""
    params = init_params(jax.random.PRNGKey(0), CFG)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0, 256)
    base = np.asarray(generate(params, prompt, CFG, max_new_tokens=6))
    stop = int(base[0, 4])                          # row 0's 1st token
    out = np.asarray(generate(params, prompt, CFG, max_new_tokens=6,
                              stop_tokens=(stop,), pad_token=0))
    assert out[0, 4] == stop
    assert (out[0, 5:] == 0).all()                  # frozen after stop
    if stop not in base[1, 4:]:
        np.testing.assert_array_equal(out[1], base[1])  # unaffected
