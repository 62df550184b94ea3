"""Expert-parallel MoE layer on the virtual 8-device mesh vs the oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashmoe_tpu.config import Activation, MoEConfig
from flashmoe_tpu.models.reference import init_moe_params, reference_moe
from flashmoe_tpu.ops.moe import moe_layer
from flashmoe_tpu.parallel.ep import ep_moe_layer, local_capacity
from flashmoe_tpu.parallel.mesh import make_mesh

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)


def _setup(cfg, seed=0):
    pk, xk = jax.random.split(jax.random.PRNGKey(seed))
    params = init_moe_params(pk, cfg)
    x = jax.random.normal(xk, (cfg.tokens, cfg.hidden_size), jnp.float32)
    return params, x


@pytest.mark.parametrize("ep", [2, 4, 8])
def test_ep_matches_oracle_nodrop(ep, devices, jitted):
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=64,
                    intermediate_size=128, sequence_len=256,
                    drop_tokens=False, ep=ep, **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg, dp=1, devices=devices[:ep])
    if ep == 2:
        # eager on purpose: a bare call works (the layer is a public
        # function); every other execution of it here is under jax.jit
        out = ep_moe_layer(params, x, cfg, mesh)
    else:
        out = jitted(ep_moe_layer, cfg, mesh)(params, x)
    want, _ = reference_moe(params, x, cfg)
    np.testing.assert_allclose(
        np.asarray(out.out), np.asarray(want), rtol=2e-4, atol=2e-4
    )
    assert int(jnp.sum(out.expert_counts)) == cfg.tokens * cfg.expert_top_k


def test_ep_gated_shared(devices, jitted):
    cfg = MoEConfig(num_experts=16, expert_top_k=2, hidden_size=64,
                    intermediate_size=128, sequence_len=256,
                    drop_tokens=False, ep=8, gated_ffn=True,
                    hidden_act=Activation.SILU, num_shared_experts=1, **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg, dp=1)
    out = jitted(ep_moe_layer, cfg, mesh)(params, x)
    want, _ = reference_moe(params, x, cfg)
    np.testing.assert_allclose(
        np.asarray(out.out), np.asarray(want), rtol=2e-4, atol=2e-4
    )


def test_ep_matches_single_device_with_drops(devices, jitted):
    """With per-shard capacity limits, EP must equal the single-device layer
    run shard-by-shard (same drops, same renormalization)."""
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=64,
                    intermediate_size=128, sequence_len=512,
                    capacity_factor=1.0, drop_tokens=True, ep=8, **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg, dp=1)
    out = jitted(ep_moe_layer, cfg, mesh)(params, x)

    d = 8
    s_loc = cfg.tokens // d
    cap = local_capacity(cfg, s_loc)
    chunks = []
    for r in range(d):
        shard = x[r * s_loc:(r + 1) * s_loc]
        o = moe_layer(params, shard, cfg, use_pallas=False, capacity=cap)
        chunks.append(o.out)
    want = jnp.concatenate(chunks, axis=0)
    np.testing.assert_allclose(
        np.asarray(out.out), np.asarray(want), rtol=2e-4, atol=2e-4
    )


def test_ep_with_tensor_parallel_experts(devices, jitted):
    """EP x TP: experts over ep, each expert's intermediate dim Megatron-
    split over tp (one psum per FFN)."""
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=64,
                    intermediate_size=128, sequence_len=256,
                    drop_tokens=False, ep=2, tp=2, gated_ffn=True,
                    hidden_act=Activation.SILU, **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg)  # dp=2, ep=2, tp=2 on 8 devices
    out = jitted(ep_moe_layer, cfg, mesh,
                 token_axes=("dp", "ep"))(params, x)
    want, _ = reference_moe(params, x, cfg)
    np.testing.assert_allclose(
        np.asarray(out.out), np.asarray(want), rtol=2e-4, atol=2e-4
    )


@pytest.mark.parametrize("inner", [2, 4])
def test_hierarchical_dcn_a2a_matches_flat(inner, devices, jitted):
    """Two-stage (intra-slice, inter-slice) all-to-all must be
    bit-identical to the flat exchange."""
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=64,
                    intermediate_size=128, sequence_len=256,
                    drop_tokens=False, ep=8, **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg, dp=1, devices=devices[:8])
    flat = jitted(ep_moe_layer, cfg, mesh)(params, x)
    hier = jitted(ep_moe_layer, cfg, mesh, dcn_inner=inner)(params, x)
    np.testing.assert_array_equal(
        np.asarray(flat.out), np.asarray(hier.out)
    )


def test_ep_pallas_path_and_grad(devices, jitted):
    """EP with pallas experts (interpreter): forward matches oracle and
    the custom-VJP backward produces finite grads."""
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=64,
                    intermediate_size=128, sequence_len=256,
                    drop_tokens=False, ep=4, is_training=True, **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg, dp=1, devices=devices[:4])
    out = jitted(ep_moe_layer, cfg, mesh, use_pallas=True,
                 interpret=True)(params, x)
    want, _ = reference_moe(params, x, cfg)
    np.testing.assert_allclose(
        np.asarray(out.out), np.asarray(want), rtol=2e-4, atol=2e-4
    )

    def loss(p):
        o = ep_moe_layer(p, x, cfg, mesh, use_pallas=True, interpret=True)
        return jnp.sum(o.out ** 2) + o.aux_loss

    g = jax.jit(jax.grad(loss))(params)
    for leaf in jax.tree_util.tree_leaves(g):
        assert np.isfinite(np.asarray(leaf)).all()


def test_ep_grad(devices):
    """EP layer must be differentiable end-to-end (training path)."""
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=64,
                    intermediate_size=64, sequence_len=128,
                    drop_tokens=False, ep=8, is_training=True, **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg, dp=1)

    def loss(p):
        o = ep_moe_layer(p, x, cfg, mesh)
        return jnp.sum(o.out ** 2) + o.aux_loss

    g = jax.jit(jax.grad(loss))(params)
    for leaf in jax.tree_util.tree_leaves(g):
        assert np.isfinite(np.asarray(leaf)).all()
