"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh (the "fake backend" the reference
never built — SURVEY.md §4): ``xla_force_host_platform_device_count=8``
gives real multi-device semantics (shard_map, collectives, all_to_all)
without TPU hardware.  Pallas kernels run in interpreter mode on CPU.
"""

import os

# Must be set before jax initializes its backends.  The tests always run
# on the 8-device virtual CPU backend; the chip is reached through
# ``chip_smoke.py``, never through this suite.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    d = jax.devices()
    assert len(d) >= 8, f"expected >=8 virtual devices, got {len(d)}"
    return d


@pytest.fixture(scope="session")
def jitted():
    """``jitted(layer, cfg, mesh, **kw)(params, x)``: ``layer``
    (``ep_moe_layer``, ``ragged_ep_moe_layer``, ``fused_ep_moe_layer``)
    the way every program of the repo runs it, ONE ``jax.jit`` program
    with ``cfg``, ``mesh`` and the keyword arguments closed over
    (``runtime/worker.py:51``).  A bare call of a ``shard_map`` dispatches
    each primitive of its body as a program of its own and caches none
    (about a thousand small compiles a call), so the gate keeps ONE bare
    call per transport, marked "a bare call works" where it stands.  A
    fixture and not an import: ``benchmark/tests`` has a ``conftest``
    module too, and a run over both directories imports the wrong one."""
    def wrap(layer, cfg, mesh, **kw):
        return jax.jit(lambda params, x: layer(params, x, cfg, mesh, **kw))
    return wrap
