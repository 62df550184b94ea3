"""Runtime layer: bootstrap, worker CLI, API facade."""

import json
import subprocess
import sys

import jax
import pytest
import jax.numpy as jnp

import flashmoe_tpu as fm
from flashmoe_tpu.config import MoEConfig
from flashmoe_tpu.runtime import bootstrap


def setup_function(_):
    bootstrap.finalize()


def test_initialize_builds_runtime(devices):
    rt = bootstrap.initialize(MoEConfig(
        num_experts=8, hidden_size=128, intermediate_size=256,
        sequence_len=128,
    ))
    assert rt.cfg.ep == 8  # folded to available devices
    assert dict(rt.mesh.shape)["ep"] == 8
    assert rt.num_local_experts == 1
    assert bootstrap.get_runtime() is rt
    # idempotent
    assert bootstrap.initialize() is rt
    bootstrap.finalize()


def test_initialize_from_reference_json(devices, tmp_path):
    p = tmp_path / "cfg.json"
    p.write_text(json.dumps({
        "num_experts": 4, "expert_top_k": 2, "hidden_size": 128,
        "intermediate_size": 256, "sequence_len": 128, "torch_dtype": 0,
        "hidden_act": 1,
    }))
    rt = bootstrap.initialize(str(p))
    assert rt.cfg.num_experts == 4
    assert rt.cfg.ep == 4
    bootstrap.finalize()


def test_api_facade(devices):
    cc = fm.get_compiled_config()
    assert "num_experts" in cc and "hidden_size" in cc
    bootstrap.initialize(MoEConfig(num_experts=8, hidden_size=128,
                                   intermediate_size=256))
    assert fm.get_num_local_experts() >= 1
    bootstrap.finalize()


def test_bookkeeping_and_topo_export(devices, tmp_path):
    import flashmoe_tpu as fm
    from flashmoe_tpu.parallel.topology import ici_adjacency

    bootstrap.initialize(MoEConfig(num_experts=8, hidden_size=128,
                                   intermediate_size=256))
    bk = fm.get_bookkeeping()
    assert bk["mesh"]["ep"] == 8
    assert sorted(e for v in bk["local_experts"].values() for e in v) == \
        list(range(8))
    adj = ici_adjacency()
    p = tmp_path / "adj.txt"
    adj.export(str(p))
    text = p.read_text()
    assert "alpha" in text and "beta" in text
    bootstrap.finalize()


@pytest.mark.slow
def test_multiprocess_launcher(devices, tmp_path):
    """Two real processes form a jax.distributed cluster through the
    launcher + bootstrap env protocol (the nvshmrun-equivalent path) and
    run the MoE worker end-to-end."""
    import os
    from flashmoe_tpu.runtime.launcher import run_workers

    cfg = tmp_path / "cfg.json"
    cfg.write_text(json.dumps({
        "num_experts": 2, "expert_top_k": 1, "hidden_size": 128,
        "intermediate_size": 256, "sequence_len": 128,
    }))
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "",  # 1 CPU device per process -> 2 global
    }
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        rc = run_workers(2, config_path=str(cfg),
                         coordinator="127.0.0.1:9917")
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    assert rc == 0


def test_slow_worker_shifts_placement(devices, tmp_path):
    """Measured placement end to end: two real processes run the full
    bootstrap (throughput probe + pairwise DCN probe + Decider); rank 1's
    measured rate is scaled down 8x and per-device memory is capped so the
    two workers must form one EP group — the Decider's rate-proportional
    assignment must then give the slow worker visibly fewer experts
    (reference: ``mT`` -> ``WorkerAttribute`` -> ``assign``,
    ``throughput.cuh:99-170``, ``decider.cuh:273-329``)."""
    import os
    from flashmoe_tpu.runtime.launcher import run_workers

    out = tmp_path / "placement"
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "",  # 1 CPU device per process -> 2 global
        # each worker holds 3MB; 8 experts x 0.52MB need ~4.2MB -> a single
        # worker is infeasible, the pair must merge into one EP group
        "FLASHMOE_MEMORY_GB": "0.003",
        "FLASHMOE_PLACEMENT_OUT": str(out),
    }
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        rc = run_workers(
            2, coordinator="127.0.0.1:9919",
            per_rank_env={1: {"FLASHMOE_THROUGHPUT_SCALE": "0.125"}},
            worker_module="tests._placement_worker",
        )
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    assert rc == 0
    rec = json.loads((tmp_path / "placement.rank0.json").read_text())
    counts = {int(k): v for k, v in rec["counts"].items()}
    assert rec["groups"] == [[0, 1]], rec  # memory forced one EP group
    assert counts[0] + counts[1] == 8
    assert counts[0] > counts[1], (
        f"slow worker should hold fewer experts: {counts}"
    )


def test_worker_cli(devices):
    """The worker runs end-to-end as a subprocess (reference worker.py)."""
    import os
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    out = subprocess.run(
        [sys.executable, "-m", "flashmoe_tpu.runtime.worker"],
        capture_output=True, text=True, env=env, timeout=300,
        cwd=__import__("pathlib").Path(__file__).parent.parent,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["finite"] is True
    assert rec["rank"] == 0


def test_heterogeneous_src_order_published():
    """bootstrap computes the fused kernel's arrival-order schedule from
    the adjacency: homogeneous -> None (ring default); a DCN-slowed rank
    -> an own-first order that sinks the slow source to the back."""
    import numpy as np

    from flashmoe_tpu.config import MoEConfig
    from flashmoe_tpu.parallel.topology import Adjacency
    from flashmoe_tpu.runtime.bootstrap import _heterogeneous_src_order

    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                    intermediate_size=256, sequence_len=256, ep=4,
                    dtype=jnp.float32, param_dtype=jnp.float32)
    alpha = np.full((4, 4), 0.001); np.fill_diagonal(alpha, 0.0)
    beta = np.full((4, 4), 0.02); np.fill_diagonal(beta, 0.0)
    assert _heterogeneous_src_order(Adjacency(alpha, beta), cfg, 4) is None

    a2, b2 = alpha.copy(), beta.copy()
    a2[3, :3] *= 20.0; b2[3, :3] *= 20.0
    order = _heterogeneous_src_order(Adjacency(a2, b2), cfg, 4)
    assert order is not None
    for r in range(3):
        assert order[r, 0] == r and order[r, -1] == 3  # slow source last
        assert sorted(order[r]) == [0, 1, 2, 3]

    # ep != n (e.g. dp x ep job): no table, ring default
    assert _heterogeneous_src_order(Adjacency(a2, b2),
                                    cfg.replace(ep=2), 4) is None


def test_fused_layer_picks_up_runtime_src_order(monkeypatch, devices, jitted):
    """fused_ep_moe_layer adopts the bootstrapped table only when the
    mesh's device ordering matches its rank indexing.  Proof of
    consumption: a deliberately INVALID published table must surface as
    the launcher's own-first validation error — which can only happen if
    the pickup path actually read it."""
    import numpy as np
    import pytest as _pytest

    from flashmoe_tpu.config import MoEConfig
    from flashmoe_tpu.models.reference import init_moe_params, reference_moe
    from flashmoe_tpu.parallel.fused import fused_ep_moe_layer
    from flashmoe_tpu.parallel.mesh import make_mesh
    from flashmoe_tpu.runtime import bootstrap as bs

    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                    intermediate_size=256, sequence_len=128, ep=4,
                    drop_tokens=False, dtype=jnp.float32,
                    param_dtype=jnp.float32)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (cfg.tokens, cfg.hidden_size), jnp.float32)
    mesh = make_mesh(cfg, dp=1, devices=devices[:4])
    want, _ = reference_moe(params, x, cfg)

    class FakeRT:
        src_order = None

    monkeypatch.setattr(bs, "_runtime", FakeRT)

    # invalid published table -> ValueError proves the pickup read it
    FakeRT.src_order = np.array(
        [[1, 0, 2, 3]] * 4, np.int32)  # not own-first
    with _pytest.raises(ValueError, match="starting with"):
        # bare: the refusal comes before any program is built
        fused_ep_moe_layer(params, x, cfg, mesh, interpret=True)

    # valid reverse-ring table -> consumed, numerics still match oracle
    FakeRT.src_order = np.stack([
        np.array([r] + [(r - s) % 4 for s in range(1, 4)], np.int32)
        for r in range(4)
    ])
    out = jitted(fused_ep_moe_layer, cfg, mesh, interpret=True)(params, x)
    np.testing.assert_allclose(np.asarray(out.out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)

    # mesh whose ep devices are NOT jax.devices() order: table refused,
    # ring default used (runs fine even though the table is garbage for
    # this mesh)
    perm = [devices[2], devices[0], devices[3], devices[1]]
    mesh_p = make_mesh(cfg, dp=1, devices=perm)
    FakeRT.src_order = np.array([[1, 0, 2, 3]] * 4, np.int32)  # invalid
    out_p = jitted(fused_ep_moe_layer, cfg, mesh_p, interpret=True)(params, x)
    assert bool(jnp.isfinite(out_p.out).all())
