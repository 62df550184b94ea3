"""Model presets build, shrink, and run through the layer stack."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashmoe_tpu.models.presets import PRESETS
from flashmoe_tpu.models.reference import init_moe_params, reference_moe
from flashmoe_tpu.ops.moe import expert_arm, moe_layer


def test_all_presets_valid():
    for name, fn in PRESETS.items():
        cfg = fn()
        assert cfg.num_experts >= 1, name
        assert cfg.expert_capacity > 0, name


@pytest.mark.parametrize("name", sorted(PRESETS))
def test_preset_layer_runs_small(name):
    """Each family's layer structure runs end-to-end at toy size."""
    cfg = PRESETS[name](
        hidden_size=128, intermediate_size=128, sequence_len=64,
        num_layers=2, vocab_size=512, num_heads=4, num_kv_heads=0,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    if cfg.num_experts > 16:
        cfg = cfg.replace(num_experts=16,
                          expert_top_k=min(cfg.expert_top_k, 16),
                          zero_experts=min(cfg.zero_experts, 8))
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (cfg.tokens, 128),
                          jnp.float32)
    # the arm the serving span would take: the capacity arm, or the routed
    # rows where the capacity arm cannot express the layer (a router wider
    # than its experts)
    out = moe_layer(params, x, cfg, use_pallas=False,
                    routed_rows=expert_arm(cfg, cfg.tokens) != "capacity")
    assert np.isfinite(np.asarray(out.out)).all()
    if not cfg.drop_tokens:
        want, _ = reference_moe(params, x, cfg)
        np.testing.assert_allclose(
            np.asarray(out.out), np.asarray(want), rtol=2e-4, atol=2e-4
        )


def test_weak_scaling_256_bench_config(devices, jitted):
    """BASELINE config #5 (256-expert weak-scaling / payload-skew) must be
    there by name (``BENCH_CONFIGS["weak_scaling_256"]``) and correct:
    the full 256-expert routing runs through the collective EP layer on
    the virtual 8-device mesh at shrunken H/I/S, matching the dense
    oracle."""
    from flashmoe_tpu.config import BENCH_CONFIGS
    from flashmoe_tpu.parallel.ep import ep_moe_layer
    from flashmoe_tpu.parallel.mesh import make_mesh

    cfg = BENCH_CONFIGS["weak_scaling_256"].replace(
        hidden_size=128, intermediate_size=128, sequence_len=1024,
        ep=8, drop_tokens=False, capacity_factor=1.0,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    assert cfg.num_experts == 256
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (cfg.tokens, cfg.hidden_size), jnp.float32)
    mesh = make_mesh(cfg, dp=1, devices=devices[:8])
    out = jitted(ep_moe_layer, cfg, mesh)(params, x)
    want, _ = reference_moe(params, x, cfg)
    np.testing.assert_allclose(
        np.asarray(out.out), np.asarray(want), rtol=3e-4, atol=3e-4
    )
