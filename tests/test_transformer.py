"""Flagship transformer: forward, loss, sharded training on the mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashmoe_tpu.config import MoEConfig
from flashmoe_tpu.models.transformer import (
    forward, init_params, loss_fn, sgd_train_step,
)
from flashmoe_tpu.parallel.mesh import make_mesh
from flashmoe_tpu.runtime.trainer import (
    init_state, make_optimizer, make_train_step, state_shardings, train,
)

CFG = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                intermediate_size=256, sequence_len=64, num_layers=2,
                moe_frequency=2, vocab_size=512, num_heads=4,
                drop_tokens=False, is_training=True, ep=4,
                dtype=jnp.float32, param_dtype=jnp.float32)


def _batch(cfg, b=2, seed=0):
    return {
        "tokens": jax.random.randint(
            jax.random.PRNGKey(seed), (b, cfg.sequence_len + 1), 0,
            cfg.vocab_size
        )
    }


def test_forward_shapes():
    cfg = CFG.replace(ep=1)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = _batch(cfg)["tokens"][:, :-1]
    logits, aux = forward(params, tokens, cfg)
    assert logits.shape == (2, cfg.sequence_len, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    assert float(aux) > 0  # MoE layer contributes aux loss


def test_dense_layers_interleave():
    """moe_frequency=2 -> layer 0 dense (1 expert), layer 1 MoE."""
    cfg = CFG.replace(ep=1)
    params = init_params(jax.random.PRNGKey(0), cfg)
    assert params["layers"][0]["moe"]["w_up"].shape[0] == 1
    assert params["layers"][1]["moe"]["w_up"].shape[0] == cfg.num_experts


def test_train_step_decreases_loss(devices):
    mesh = make_mesh(CFG)
    params = init_params(jax.random.PRNGKey(0), CFG)
    batch = _batch(CFG)
    step = jax.jit(lambda p, b: sgd_train_step(p, b, CFG, lr=1e-2,
                                               mesh=mesh))
    p1, l1, m1 = step(params, batch)
    p2, l2, m2 = step(p1, batch)
    assert float(l2) < float(l1)
    assert np.isfinite(float(m2["ce"]))


def test_optax_trainer_with_shardings(devices):
    mesh = make_mesh(CFG)
    opt = make_optimizer(CFG, total_steps=4)
    state = init_state(jax.random.PRNGKey(0), CFG, opt)
    state = jax.device_put(state, state_shardings(state, CFG, mesh))
    step = make_train_step(CFG, mesh, opt)
    batch = _batch(CFG)
    losses = []
    for _ in range(3):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert int(state.step) == 3
    assert losses[-1] < losses[0]
    # expert weights actually sharded over ep
    moe_w = state.params["layers"][1]["moe"]["w_up"]
    assert "ep" in str(moe_w.sharding.spec) or moe_w.sharding.is_fully_replicated is False


@pytest.mark.parametrize("backend", ["fused", "ragged"])
def test_moe_backend_selection(backend, devices):
    """The flagship model can route its distributed MoE through the fused
    RDMA kernel or the dropless ragged layer and still match the default
    collective path (forward AND gradients)."""
    cfg = CFG.replace(ep=2, moe_backend=backend, moe_frequency=1,
                      num_layers=1)
    mesh = make_mesh(cfg, devices=devices[:2], dp=1)
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)

    def loss_with(backend_name):
        c = cfg.replace(moe_backend=backend_name)
        return float(jax.jit(
            lambda p, b: loss_fn(p, b, c, mesh, False)[0]
        )(params, batch))

    lb = loss_with(backend)
    lc = loss_with("collective")
    np.testing.assert_allclose(lb, lc, rtol=2e-4)

    def grads_with(backend_name):
        c = cfg.replace(moe_backend=backend_name)
        return jax.jit(jax.grad(
            lambda p: loss_fn(p, batch, c, mesh, False)[0]
        ))(params)

    gb = grads_with(backend)
    gc = grads_with("collective")
    fb, _ = jax.tree_util.tree_flatten_with_path(gb)
    fc, _ = jax.tree_util.tree_flatten_with_path(gc)
    for (path, a), (_, b) in zip(fb, fc):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-3,
            err_msg=jax.tree_util.keystr(path),
        )


def test_sequence_parallel_forward(devices):
    """sp=2: ring attention + EP MoE with tokens sharded over (ep, sp)."""
    cfg = CFG.replace(ep=2, sp=2, sequence_len=128)
    mesh = make_mesh(cfg)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = _batch(cfg)["tokens"][:, :-1]
    logits, aux = jax.jit(
        lambda p, t: forward(p, t, cfg, mesh))(params, tokens)
    # oracle: same params, no mesh (single-device dense path)
    want, _ = jax.jit(lambda p, t: forward(
        p, t, cfg.replace(ep=1, sp=1), None))(params, tokens)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(want), rtol=2e-3, atol=2e-3
    )


def test_train_loop_helper(devices):
    mesh = make_mesh(CFG)
    it = iter([_batch(CFG, seed=i) for i in range(3)])
    state, hist = train(CFG, mesh, it, num_steps=3, log_every=1)
    assert len(hist) == 3
    assert all(np.isfinite(h["loss"]) for h in hist)
