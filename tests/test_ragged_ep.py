"""Distributed dropless (ragged all-to-all) EP layer vs the oracle.

XLA:CPU lacks the ragged-all-to-all op, so these tests run the dense-padded
exchange fallback — the layout/permutation logic (the hard part) is shared
between both exchange backends.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashmoe_tpu.config import MoEConfig
from flashmoe_tpu.models.reference import init_moe_params, reference_moe
from flashmoe_tpu.parallel.mesh import make_mesh
from flashmoe_tpu.parallel.ragged_ep import ragged_ep_moe_layer

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32, drop_tokens=False)


def _setup(cfg, seed=0):
    pk, xk = jax.random.split(jax.random.PRNGKey(seed))
    params = init_moe_params(pk, cfg)
    x = jax.random.normal(xk, (cfg.tokens, cfg.hidden_size), jnp.float32)
    return params, x


@pytest.mark.parametrize("ep", [2, 4, 8])
def test_matches_oracle(ep, devices, jitted):
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=64,
                    intermediate_size=128, sequence_len=256, ep=ep, **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg, dp=1, devices=devices[:ep])
    out = jitted(ragged_ep_moe_layer, cfg, mesh,
                 exchange="dense")(params, x)
    want, _ = reference_moe(params, x, cfg)
    np.testing.assert_allclose(
        np.asarray(out.out), np.asarray(want), rtol=2e-4, atol=2e-4
    )
    assert int(jnp.sum(out.expert_counts)) == cfg.tokens * cfg.expert_top_k


def test_skewed_all_to_one_expert(devices, jitted):
    """Extreme imbalance: all tokens to one expert on one rank — the exact
    case capacity-based EP drops and dropless must not."""
    cfg = MoEConfig(num_experts=8, expert_top_k=1, hidden_size=64,
                    intermediate_size=128, sequence_len=256, ep=4, **F32)
    params, x = _setup(cfg)
    params["gate_w"] = jnp.zeros_like(params["gate_w"]).at[:, 5].set(1.0)
    x = jnp.abs(x) + 0.1
    mesh = make_mesh(cfg, dp=1, devices=devices[:4])
    out = jitted(ragged_ep_moe_layer, cfg, mesh,
                 exchange="dense")(params, x)
    want, _ = reference_moe(params, x, cfg)
    np.testing.assert_allclose(
        np.asarray(out.out), np.asarray(want), rtol=2e-4, atol=2e-4
    )
    assert int(out.expert_counts[5]) == cfg.tokens


def test_gated_ffn(devices, jitted):
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=64,
                    intermediate_size=128, sequence_len=128, ep=4,
                    gated_ffn=True, hidden_act="silu", **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg, dp=1, devices=devices[:4])
    out = jitted(ragged_ep_moe_layer, cfg, mesh,
                 exchange="dense")(params, x)
    want, _ = reference_moe(params, x, cfg)
    np.testing.assert_allclose(
        np.asarray(out.out), np.asarray(want), rtol=2e-4, atol=2e-4
    )


def test_sentinel_no_collision_with_padded_targets(devices, jitted):
    """Regression: tile padding can push a real row's target to exactly
    recv_bound; the dropped-row sentinel must be out of range, not
    recv_bound, or the scatter zeroes a real token."""
    cfg = MoEConfig(num_experts=4, expert_top_k=1, hidden_size=64,
                    intermediate_size=128, sequence_len=128, ep=2, **F32)
    params, x = _setup(cfg, seed=3)
    mesh = make_mesh(cfg, dp=1, devices=devices[:2])
    # try several routings; with block_m=16 the padded segments force the
    # collision case the review repro found
    layer = jitted(ragged_ep_moe_layer, cfg, mesh, exchange="dense",
                   block_m=16)
    for seed in range(3):
        xs = jax.random.normal(
            jax.random.PRNGKey(100 + seed), (cfg.tokens, 64), jnp.float32
        )
        out = layer(params, xs)
        want, _ = reference_moe(params, xs, cfg)
        np.testing.assert_allclose(
            np.asarray(out.out), np.asarray(want), rtol=2e-4, atol=2e-4
        )


def test_token_count_not_multiple_of_block(devices):
    """Regression: recv_bound not divisible by block_m must not crash."""
    cfg = MoEConfig(num_experts=4, expert_top_k=1, hidden_size=64,
                    intermediate_size=128, sequence_len=72, ep=2, **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg, dp=1, devices=devices[:2])
    # eager on purpose: a bare call works (the cheapest case of this
    # file); every other execution of the layer here is under jax.jit
    out = ragged_ep_moe_layer(params, x, cfg, mesh, exchange="dense",
                              block_m=16)
    want, _ = reference_moe(params, x, cfg)
    np.testing.assert_allclose(
        np.asarray(out.out), np.asarray(want), rtol=2e-4, atol=2e-4
    )


def test_pallas_path_gradients_match_xla_path(devices, jitted):
    """The dropless pallas path must differentiate (grouped_ffn_ad) and
    agree with the XLA-fallback path's gradients."""
    cfg = MoEConfig(num_experts=4, expert_top_k=2, hidden_size=64,
                    intermediate_size=128, sequence_len=256, ep=2, **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg, dp=1, devices=devices[:2])

    def loss(p, use_pallas):
        o = ragged_ep_moe_layer(p, x, cfg, mesh, use_pallas=use_pallas,
                                interpret=use_pallas, exchange="dense")
        return (o.out.astype(jnp.float32) ** 2).sum()

    gp = jax.jit(jax.grad(lambda p: loss(p, True)))(params)
    gx = jax.jit(jax.grad(lambda p: loss(p, False)))(params)
    for k in gx:
        np.testing.assert_allclose(
            np.asarray(gp[k]), np.asarray(gx[k]),
            rtol=5e-3, atol=5e-3, err_msg=k,
        )


def test_pallas_grouped_ffn_path(devices, jitted):
    """The grouped Pallas kernel runs on the regrouped ragged buffer."""
    cfg = MoEConfig(num_experts=4, expert_top_k=2, hidden_size=128,
                    intermediate_size=256, sequence_len=128, ep=2, **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg, dp=1, devices=devices[:2])
    out = jitted(ragged_ep_moe_layer, cfg, mesh, exchange="dense",
                 use_pallas=True, interpret=True, block_m=16)(params, x)
    want, _ = reference_moe(params, x, cfg)
    np.testing.assert_allclose(
        np.asarray(out.out), np.asarray(want), rtol=2e-4, atol=2e-4
    )
