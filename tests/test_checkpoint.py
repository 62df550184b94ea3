"""Checkpoint save/restore round trip with shardings."""

import jax
import pytest
import jax.numpy as jnp
import numpy as np

from flashmoe_tpu.config import MoEConfig
from flashmoe_tpu.parallel.mesh import make_mesh
from flashmoe_tpu.runtime import checkpoint as ckpt
from flashmoe_tpu.runtime.trainer import (
    init_state, make_optimizer, make_train_step, state_shardings,
)

CFG = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                intermediate_size=256, sequence_len=64, num_layers=2,
                moe_frequency=2, vocab_size=512, num_heads=4,
                drop_tokens=False, is_training=True, ep=4,
                dtype=jnp.float32, param_dtype=jnp.float32)


def test_save_restore_roundtrip(devices, tmp_path):
    mesh = make_mesh(CFG)
    opt = make_optimizer(CFG, total_steps=4)
    state = init_state(jax.random.PRNGKey(0), CFG, opt)
    state = jax.device_put(state, state_shardings(state, CFG, mesh))
    step = make_train_step(CFG, mesh, opt)
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (2, 65), 0, 512)}
    state, _ = step(state, batch)

    d = str(tmp_path / "ckpt")
    saved_step = ckpt.save(d, state)
    assert saved_step == 1
    assert ckpt.latest_step(d) == 1

    # fresh template, different values
    fresh = init_state(jax.random.PRNGKey(42), CFG, opt)
    fresh = jax.device_put(fresh, state_shardings(fresh, CFG, mesh))
    restored = ckpt.restore(d, fresh)
    assert int(restored.step) == 1
    for a, b in zip(jax.tree_util.tree_leaves(restored.params),
                    jax.tree_util.tree_leaves(state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # restored arrays keep the template's shardings
    w = restored.params["layers"][1]["moe"]["w_up"]
    assert w.sharding.is_equivalent_to(
        state.params["layers"][1]["moe"]["w_up"].sharding, w.ndim
    )

    # training continues from the restored state
    state2, metrics = step(restored, batch)
    assert int(state2.step) == 2
    assert np.isfinite(float(metrics["loss"]))


def test_latest_step_empty(tmp_path):
    assert ckpt.latest_step(str(tmp_path / "none")) is None


# ----------------------------------------------------------------------
# Async atomic saves (preemption-safe checkpointing, docs/RESILIENCE.md)
# ----------------------------------------------------------------------

def _tiny_state(step: int):
    from flashmoe_tpu.runtime.trainer import TrainState

    k = jax.random.PRNGKey(step)
    return TrainState(
        params={"w": jax.random.normal(k, (16, 16), jnp.float32)},
        opt_state={"m": jnp.zeros((16, 16), jnp.float32)},
        step=jnp.asarray(step, jnp.int32))


def test_async_save_verifies_and_restores(tmp_path):
    d = str(tmp_path / "ck")
    state = _tiny_state(1)
    ckpt.save(d, state, blocking=False,
              loader_state={"epoch": 0, "cursor": 2, "seed": 7,
                            "shuffle": True})
    assert ckpt.wait_for_saves() == []
    assert ckpt.latest_step(d) == 1
    assert ckpt.verify(d, 1)  # CRC manifest semantics preserved
    assert ckpt.load_loader_state(d, 1) == {
        "epoch": 0, "cursor": 2, "seed": 7, "shuffle": True}
    restored = ckpt.restore(d, _tiny_state(9))
    np.testing.assert_array_equal(np.asarray(restored.params["w"]),
                                  np.asarray(state.params["w"]))


def test_async_queue_is_newest_wins(tmp_path, monkeypatch):
    """Depth-1 queue: while one save is in flight, the QUEUED (not yet
    started) snapshot is replaced by a newer one — the writer never
    falls behind by more than one checkpoint."""
    import threading

    import flashmoe_tpu.runtime.checkpoint as ckpt_mod

    d = str(tmp_path / "ck")
    gate = threading.Event()
    real = ckpt_mod._write_sync
    stalled = {"n": 0}

    def slow_write(directory, state, step, loader_state,
               controller_state=None):
        stalled["n"] += 1
        if stalled["n"] == 1:
            gate.wait(timeout=30)
        real(directory, state, step, loader_state)

    monkeypatch.setattr(ckpt_mod, "_write_sync", slow_write)
    before = ckpt.async_save_stats()
    ckpt.save(d, _tiny_state(1), blocking=False)  # in flight, stalled
    for _ in range(500):  # wait until the writer picked job 1 up
        if stalled["n"]:
            break
        import time
        time.sleep(0.01)
    assert stalled["n"] == 1
    for s in (2, 3, 4):  # queue depth 1: 2 and 3 are replaced by 4
        ckpt.save(d, _tiny_state(s), blocking=False)
    gate.set()
    assert ckpt.wait_for_saves() == []
    after = ckpt.async_save_stats()
    assert after["dropped"] - before["dropped"] == 2
    assert after["completed"] - before["completed"] == 2  # 1 and 4
    assert ckpt.latest_step(d) == 4
    assert ckpt.verify(d, 4)


def test_async_queue_never_drops_across_directories(tmp_path):
    """Newest-wins is PER DIRECTORY: two runs sharing the process must
    not cancel each other's pending checkpoints."""
    d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")
    before = ckpt.async_save_stats()
    ckpt.save(d1, _tiny_state(1), blocking=False)
    ckpt.save(d2, _tiny_state(1), blocking=False)
    assert ckpt.wait_for_saves() == []
    after = ckpt.async_save_stats()
    assert after["dropped"] == before["dropped"]  # nothing replaced
    assert ckpt.latest_step(d1) == 1 and ckpt.latest_step(d2) == 1
    assert ckpt.verify(d1, 1) and ckpt.verify(d2, 1)


def test_async_writer_error_is_surfaced_not_raised(tmp_path, monkeypatch):
    import flashmoe_tpu.runtime.checkpoint as ckpt_mod

    def boom(directory, state, step, loader_state,
         controller_state=None):
        raise OSError("disk on fire")

    monkeypatch.setattr(ckpt_mod, "_write_sync", boom)
    ckpt.save(str(tmp_path / "ck"), _tiny_state(1), blocking=False)
    errors = ckpt.wait_for_saves()
    assert len(errors) == 1 and "disk on fire" in str(errors[0])
    assert ckpt.wait_for_saves() == []  # errors drained once


def test_kill_between_payload_and_manifest_keeps_previous_step(tmp_path):
    """Durability ordering: the manifest lands only after the payload
    commit.  A kill mid-payload leaves an uncommitted tmp dir orbax
    ignores; a kill between payload and manifest leaves a legacy-style
    manifest-less (but complete) checkpoint — either way the previous
    step restores intact."""
    import os
    import shutil

    d = str(tmp_path / "ck")
    ckpt.save(d, _tiny_state(1))
    ckpt.save(d, _tiny_state(2))

    # kill mid-payload: the step dir never committed (tmp name), no
    # manifest was written — invisible to the manager, step 2 restores
    src = ckpt.step_dir(d, 2)
    shutil.copytree(src, os.path.join(
        str(tmp_path / "ck"), "3.orbax-checkpoint-tmp-999"))
    assert ckpt.latest_step(d) == 2
    restored = ckpt.restore(d, _tiny_state(9))
    assert int(restored.step) == 2

    # kill between payload commit and manifest write: a complete but
    # manifest-less checkpoint — restorable as legacy, previous steps
    # (and their manifests) untouched
    os.remove(os.path.join(d, "manifest-2.json"))
    assert ckpt.verify(d, 2)  # manifest-less: no integrity claim
    assert ckpt.verify(d, 1)  # previous step's manifest still verifies
    assert int(ckpt.restore(d, _tiny_state(9)).step) == 2
    assert ckpt.load_loader_state(d, 2) is None  # cursor died with it


def test_manifest_loader_state_roundtrip_and_legacy(tmp_path):
    d = str(tmp_path / "ck")
    ckpt.save(d, _tiny_state(1))  # no loader attached
    assert ckpt.load_loader_state(d, 1) is None  # legacy/absent: None
    ckpt.save(d, _tiny_state(2), loader_state={"epoch": 1, "cursor": 3,
                                               "seed": 0,
                                               "shuffle": False})
    assert ckpt.load_loader_state(d, 2)["cursor"] == 3
    assert ckpt.verify(d, 2)  # the extra manifest field breaks nothing


def _quant_state(step: int):
    from flashmoe_tpu import quant as qt
    from flashmoe_tpu.models.reference import init_moe_params
    from flashmoe_tpu.runtime.trainer import TrainState

    cfg = MoEConfig(num_experts=4, expert_top_k=2, hidden_size=64,
                    intermediate_size=128, dtype=jnp.float32,
                    param_dtype=jnp.float32)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    qs = qt.quantize_state(params, "int8")
    return TrainState(params={"moe": dict(qs.params)},
                      opt_state={}, step=jnp.asarray(step, jnp.int32))


def test_quant_manifest_block_and_backcompat(tmp_path):
    """ISSUE 15 satellite: a pre-quant manifest (no `quant` block)
    restores unchanged; a quantized save -> restore -> dequantize round
    trip is bit-stable across the ASYNC save path; a tampered quant
    block trips the CRC instead of silently mis-decoding payloads."""
    import os

    from flashmoe_tpu import quant as qt

    d = str(tmp_path / "ck")
    # pre-quant checkpoint: no quant block, restore untouched
    ckpt.save(d, _tiny_state(1))
    assert ckpt.load_quant_metadata(d, 1) is None
    assert ckpt.verify(d, 1)

    # quantized save through the ASYNC path: the manifest gains the
    # CRC'd quant block automatically (derived from state.params)
    state = _quant_state(2)
    ckpt.save(d, state, step=2, blocking=False)
    assert ckpt.wait_for_saves() == []
    meta = ckpt.load_quant_metadata(d, 2)
    assert meta is not None and meta["dtype"] == "int8"
    assert qt.verify_quant_metadata(meta)
    assert ckpt.verify(d, 2)

    # restore -> dequantize bit-stable (int8 payloads + f32 scales ride
    # orbax unchanged, so decode(restore(x)) == decode(x) exactly)
    restored = ckpt.restore(d, _quant_state(9), step=2)
    want = qt.dequantize_state(state.params["moe"])
    got = qt.dequantize_state(restored.params["moe"])
    for k in ("w_up", "w_down"):
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]))
    assert np.asarray(restored.params["moe"]["w_up"]).dtype == np.int8

    # tamper the quant block: the content CRC must trip
    import json as _json

    mpath = os.path.join(d, "manifest-2.json")
    with open(mpath) as f:
        manifest = _json.load(f)
    manifest["quant"]["dtype"] = "e4m3"
    with open(mpath, "w") as f:
        _json.dump(manifest, f)
    with pytest.raises(ckpt.CheckpointCorruptionError,
                       match="quant metadata"):
        ckpt.load_quant_metadata(d, 2)


def test_has_guard_probe(tmp_path):
    from flashmoe_tpu.runtime.trainer import init_guard_state

    d = str(tmp_path / "ck")
    ckpt.save(d, _tiny_state(1))
    assert ckpt.has_guard(d, 1) is False
    guarded = _tiny_state(2)._replace(guard=init_guard_state())
    ckpt.save(d, guarded, step=2)
    assert ckpt.has_guard(d, 2) is True
