"""MoE layer correctness vs the dense-math oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashmoe_tpu.config import Activation, MoEConfig
from flashmoe_tpu.models.reference import init_moe_params, reference_moe
from flashmoe_tpu.ops.moe import moe_layer


def _setup(cfg, seed=0):
    key = jax.random.PRNGKey(seed)
    pk, xk = jax.random.split(key)
    params = init_moe_params(pk, cfg)
    x = jax.random.normal(xk, (cfg.tokens, cfg.hidden_size), cfg.dtype)
    return params, x


# float32 configs with no token dropping -> optimized path must match oracle
NODROP = dict(drop_tokens=False, dtype=jnp.float32, param_dtype=jnp.float32)


@pytest.mark.parametrize("cfg", [
    MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
              intermediate_size=256, sequence_len=128, **NODROP),
    MoEConfig(num_experts=4, expert_top_k=1, hidden_size=64,
              intermediate_size=128, sequence_len=256, **NODROP),
    MoEConfig(num_experts=16, expert_top_k=4, hidden_size=128,
              intermediate_size=128, sequence_len=128,
              hidden_act=Activation.RELU, **NODROP),
], ids=["top2", "top1", "top4_relu"])
def test_matches_oracle_nodrop(cfg):
    params, x = _setup(cfg)
    want, aux_want = reference_moe(params, x, cfg)
    got = moe_layer(params, x, cfg, use_pallas=False)
    np.testing.assert_allclose(
        np.asarray(got.out), np.asarray(want), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(
        float(got.aux_loss), float(aux_want) * cfg.aux_loss_coef, rtol=1e-4
    )


def test_gated_ffn_with_shared_experts():
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                    intermediate_size=128, sequence_len=128, gated_ffn=True,
                    hidden_act=Activation.SILU, num_shared_experts=2, **NODROP)
    params, x = _setup(cfg)
    want, _ = reference_moe(params, x, cfg)
    got = moe_layer(params, x, cfg, use_pallas=False)
    np.testing.assert_allclose(
        np.asarray(got.out), np.asarray(want), rtol=2e-4, atol=2e-4
    )


def test_drop_tokens_capacity():
    """With tight capacity, dropped tokens fall back to (renormalized)
    surviving experts; output stays finite and counts are exact."""
    cfg = MoEConfig(num_experts=4, expert_top_k=2, hidden_size=64,
                    intermediate_size=64, sequence_len=128,
                    capacity_factor=0.5, drop_tokens=True,
                    dtype=jnp.float32, param_dtype=jnp.float32)
    params, x = _setup(cfg)
    got = moe_layer(params, x, cfg, use_pallas=False)
    assert np.isfinite(np.asarray(got.out)).all()
    assert int(jnp.sum(got.expert_counts)) == cfg.tokens * cfg.expert_top_k


def test_dense_fallback_e1():
    """E==1 routes through the dense fffn-equivalent path."""
    cfg = MoEConfig(num_experts=1, expert_top_k=1, hidden_size=64,
                    intermediate_size=128, sequence_len=64, **NODROP)
    params, x = _setup(cfg)
    got = moe_layer(params, x, cfg, use_pallas=False)
    want, _ = reference_moe(params, x, cfg)
    np.testing.assert_allclose(
        np.asarray(got.out), np.asarray(want), rtol=2e-4, atol=2e-4
    )


def test_integrated_pallas_path_interpret():
    """The fused Pallas gate + grouped-FFN layer end-to-end (interpreter)."""
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                    intermediate_size=256, sequence_len=128, **NODROP)
    params, x = _setup(cfg)
    want, _ = reference_moe(params, x, cfg)
    got = moe_layer(params, x, cfg, use_pallas=True, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got.out), np.asarray(want), rtol=2e-4, atol=2e-4
    )


@pytest.mark.parametrize("gated,cf", [(False, 1.0), (True, 1.25),
                                      (False, 2.0)],
                         ids=["cf1", "gated_cf1.25", "cf2"])
def test_gather_fused_inference_matches_oracle(gated, cf):
    """The gather-fused capacity path (dispatch built inside the kernel,
    no [E, C, H] HBM buffer) matches the explicit-dispatch XLA oracle."""
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=256,
                    intermediate_size=512, sequence_len=256,
                    drop_tokens=True, capacity_factor=cf, gated_ffn=gated,
                    dtype=jnp.float32, param_dtype=jnp.float32,
                    is_training=False, gather_fused=True)
    params, x = _setup(cfg)
    got = moe_layer(params, x, cfg, use_pallas=True, interpret=True)
    want = moe_layer(params, x, cfg, use_pallas=False)
    np.testing.assert_allclose(
        np.asarray(got.out), np.asarray(want.out), rtol=2e-4, atol=2e-4
    )


@pytest.mark.parametrize("gated", [False, True], ids=["plain", "gated"])
def test_dropless_gather_fused_inference(gated):
    """Dropless inference routes through the gather-fused kernel (inverse
    map from the ragged plan); output and re-gather-VJP grads match XLA."""
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=256,
                    intermediate_size=512, sequence_len=256,
                    gated_ffn=gated, gather_fused=True, **NODROP)
    params, x = _setup(cfg)
    got = moe_layer(params, x, cfg, use_pallas=True, interpret=True)
    want, _ = reference_moe(params, x, cfg)
    np.testing.assert_allclose(
        np.asarray(got.out), np.asarray(want), rtol=2e-4, atol=2e-4
    )
    # jitted, as a train step takes them (an eager grad through an
    # interpret-mode kernel is dispatch, op by op)
    g = jax.jit(jax.grad(lambda xx: moe_layer(
        params, xx, cfg, use_pallas=True, interpret=True).out.sum()))(x)
    gx = jax.jit(jax.grad(lambda xx: moe_layer(
        params, xx, cfg, use_pallas=False).out.sum()))(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gx),
                               rtol=5e-3, atol=5e-3)


def test_fused_path_grad_matches_xla_grad():
    """The fused path's custom VJP (pallas fwd, XLA-recompute bwd) must
    produce the same gradients as differentiating the XLA path."""
    cfg = MoEConfig(num_experts=4, expert_top_k=2, hidden_size=64,
                    intermediate_size=128, sequence_len=64, **NODROP)
    params, x = _setup(cfg)

    def loss(p, use_pallas, interpret):
        o = moe_layer(p, x, cfg, use_pallas=use_pallas, interpret=interpret)
        return jnp.sum(o.out ** 2) + o.aux_loss

    gp = jax.jit(jax.grad(lambda p: loss(p, True, True)))(params)
    gx = jax.jit(jax.grad(lambda p: loss(p, False, False)))(params)
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gx)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4
        )


def test_jit_and_grad():
    """The layer must be jittable and differentiable (training path)."""
    cfg = MoEConfig(num_experts=4, expert_top_k=2, hidden_size=64,
                    intermediate_size=64, sequence_len=64, is_training=True,
                    **NODROP)
    params, x = _setup(cfg)

    @jax.jit
    def loss_fn(p, x):
        o = moe_layer(p, x, cfg, use_pallas=False)
        return jnp.sum(o.out ** 2) + o.aux_loss

    g = jax.grad(loss_fn)(params, x)
    for leaf in jax.tree_util.tree_leaves(g):
        assert np.isfinite(np.asarray(leaf)).all()
