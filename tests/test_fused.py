"""Fused in-kernel all-to-all MoE (remote-DMA interpret emulation)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashmoe_tpu.config import MoEConfig
from flashmoe_tpu.models.reference import init_moe_params, reference_moe
from flashmoe_tpu.parallel.ep import ep_moe_layer
from flashmoe_tpu.parallel.fused import fused_ep_moe_layer
from flashmoe_tpu.parallel.mesh import make_mesh

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)


def _setup(cfg, seed=0):
    pk, xk = jax.random.split(jax.random.PRNGKey(seed))
    params = init_moe_params(pk, cfg)
    x = jax.random.normal(xk, (cfg.tokens, cfg.hidden_size), jnp.float32)
    return params, x


@pytest.mark.parametrize("ep", [2, 4])
def test_fused_matches_oracle(ep, devices, jitted):
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                    intermediate_size=256, sequence_len=256,
                    drop_tokens=False, ep=ep, **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg, dp=1, devices=devices[:ep])
    if ep == 2:
        # eager on purpose: a bare call works, and it is the one case
        # that reaches the layer's own wait on its interpret-mode
        # output (fused.py: "a no-op under jit"); every other execution
        # of the layer in this file is under jax.jit
        out = fused_ep_moe_layer(params, x, cfg, mesh, interpret=True)
    else:
        out = jitted(fused_ep_moe_layer, cfg, mesh,
                     interpret=True)(params, x)
    want, _ = reference_moe(params, x, cfg)
    np.testing.assert_allclose(
        np.asarray(out.out), np.asarray(want), rtol=2e-4, atol=2e-4
    )


def test_fused_matches_ep_layer_with_drops(devices, jitted):
    """Same drops/renormalization as the collective EP path."""
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                    intermediate_size=256, sequence_len=512,
                    capacity_factor=1.0, drop_tokens=True, ep=2, **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg, dp=1, devices=devices[:2])
    got = jitted(fused_ep_moe_layer, cfg, mesh, interpret=True)(params, x)
    want = jitted(ep_moe_layer, cfg, mesh, use_pallas=False)(params, x)
    np.testing.assert_allclose(
        np.asarray(got.out), np.asarray(want.out), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_array_equal(
        np.asarray(got.expert_counts), np.asarray(want.expert_counts)
    )


def test_fused_race_detector_clean(devices, jitted):
    """The interpreter's vector-clock race detector over the fused kernel's
    RDMA/semaphore protocol — the sanitizer the reference never had."""
    cfg = MoEConfig(num_experts=4, expert_top_k=2, hidden_size=128,
                    intermediate_size=256, sequence_len=256,
                    drop_tokens=False, ep=2, **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg, dp=1, devices=devices[:2])
    out = jitted(fused_ep_moe_layer, cfg, mesh, interpret=True,
                 detect_races=True)(params, x)
    want, _ = reference_moe(params, x, cfg)
    np.testing.assert_allclose(
        np.asarray(out.out), np.asarray(want), rtol=2e-4, atol=2e-4
    )


def test_fused_skewed_tile_skipping(devices, jitted):
    """All tokens to one remote expert: most slabs/tiles are empty and
    must be skipped on both send and wait sides without deadlock, while
    the loaded expert's tiles all arrive."""
    cfg = MoEConfig(num_experts=8, expert_top_k=1, hidden_size=128,
                    intermediate_size=256, sequence_len=256,
                    drop_tokens=False, ep=4, **F32)
    params, x = _setup(cfg)
    params["gate_w"] = jnp.zeros_like(params["gate_w"]).at[:, 5].set(1.0)
    x = jnp.abs(x) + 0.1
    mesh = make_mesh(cfg, dp=1, devices=devices[:4])
    out = jitted(fused_ep_moe_layer, cfg, mesh, interpret=True,
                 detect_races=True)(params, x)
    want, _ = reference_moe(params, x, cfg)
    np.testing.assert_allclose(
        np.asarray(out.out), np.asarray(want), rtol=2e-4, atol=2e-4
    )
    assert int(out.expert_counts[5]) == cfg.tokens


@pytest.mark.parametrize("variant", ["plain", "gated", "drops"])
def test_fused_gradients_match_collective_path(variant, devices):
    """The fused RDMA layer's custom VJP (XLA re-exchange + Pallas GEMM
    backward) must produce the same gradients as autodiff through the
    collective EP path — including the gated (SwiGLU) branch (g recompute,
    d_gate, d_wg) and the count-skewed drop path (zero cotangents on
    skipped tiles vs the full-slab backward)."""
    extra = {}
    if variant == "gated":
        extra = dict(gated_ffn=True, hidden_act="silu")
    if variant == "drops":
        extra = dict(capacity_factor=1.0, drop_tokens=True)
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                    intermediate_size=256, sequence_len=256,
                    drop_tokens=extra.pop("drop_tokens", False), ep=2,
                    **extra, **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg, dp=1, devices=devices[:2])
    _assert_fused_grads_match_collective(params, x, cfg, mesh)


def test_fused_non_tile_multiple_capacity(devices, jitted):
    """capacity_factor=1.25 at S=512/ep=2 gives cap=80 per (rank,
    expert) — padded to 96, not a multiple of 256.  The kernel must
    degrade its row tile (cm=32) / pad rather than raise (advisor
    finding, round 1), and still match the collective EP path."""
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                    intermediate_size=256, sequence_len=512,
                    capacity_factor=1.25, drop_tokens=True, ep=2, **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg, dp=1, devices=devices[:2])
    got = jitted(fused_ep_moe_layer, cfg, mesh, interpret=True)(params, x)
    want = jitted(ep_moe_layer, cfg, mesh, use_pallas=False)(params, x)
    np.testing.assert_allclose(
        np.asarray(got.out), np.asarray(want.out), rtol=2e-4, atol=2e-4
    )


@pytest.mark.parametrize("mode", ["1", "0"], ids=["in_kernel", "xla"])
def test_fused_combine_modes_match_oracle(mode, monkeypatch, devices, jitted):
    """FLASHMOE_FUSED_COMBINE forces each combine implementation; both
    must match the dense oracle (and hence each other) — incl. drops,
    where empty slots hold unwritten slab memory the in-kernel combine
    must never read."""
    monkeypatch.setenv("FLASHMOE_FUSED_COMBINE", mode)
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                    intermediate_size=256, sequence_len=256,
                    capacity_factor=1.0, drop_tokens=True, ep=2, **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg, dp=1, devices=devices[:2])
    got = jitted(fused_ep_moe_layer, cfg, mesh, interpret=True,
                 detect_races=(mode == "1"))(params, x)
    want = jitted(ep_moe_layer, cfg, mesh, use_pallas=False)(params, x)
    np.testing.assert_allclose(
        np.asarray(got.out), np.asarray(want.out), rtol=2e-4, atol=2e-4
    )


def test_fused_gated_with_shared_experts(devices, jitted):
    """SwiGLU experts stream through the kernel; shared experts add in."""
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                    intermediate_size=256, sequence_len=256,
                    drop_tokens=False, ep=4, gated_ffn=True,
                    hidden_act="silu", num_shared_experts=1, **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg, dp=1, devices=devices[:4])
    out = jitted(fused_ep_moe_layer, cfg, mesh, interpret=True)(params, x)
    want, _ = reference_moe(params, x, cfg)
    np.testing.assert_allclose(
        np.asarray(out.out), np.asarray(want), rtol=2e-4, atol=2e-4
    )


def test_fuse_combine_gate_is_opt_in(monkeypatch):
    """The in-kernel combine is opt-in until a measurement on chips
    justifies a default (advisor r3 #1/#2): env unset -> XLA combine;
    env=1 -> enabled only within the SMEM/VMEM budget, with a warning
    (not a Mosaic compile failure) when the combine maps are too large.
    Since the round-5 sorted-return restructure it also requires a
    multi-rank ep world — at world 1 there is no communication to
    overlap and the per-row return copies are pure overhead."""
    from flashmoe_tpu.parallel.fused import _fuse_combine_enabled

    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                    intermediate_size=256, sequence_len=256,
                    drop_tokens=False, ep=2, **F32)
    monkeypatch.delenv("FLASHMOE_FUSED_COMBINE", raising=False)
    assert not _fuse_combine_enabled(cfg, 256, 128, 256, 64)

    monkeypatch.setenv("FLASHMOE_FUSED_COMBINE", "1")
    assert _fuse_combine_enabled(cfg, 256, 128, 256, 64)

    # single-rank world: nothing to overlap -> XLA combine even when asked
    assert not _fuse_combine_enabled(cfg, 256, 128, 256, 64, d_world=1)
    assert not _fuse_combine_enabled(cfg.replace(ep=1), 256, 128, 256, 64)

    # 4096 experts x 4096-slot capacity: the sorted-row map alone is
    # 64 MiB of SMEM — must fall back (with a warning), never Mosaic-fail
    big = cfg.replace(num_experts=4096)
    with pytest.warns(UserWarning, match="SMEM/VMEM budget"):
        assert not _fuse_combine_enabled(big, 256, 128, 256, 4096)

    monkeypatch.setenv("FLASHMOE_FUSED_COMBINE", "0")
    assert not _fuse_combine_enabled(cfg, 256, 128, 256, 64)


@pytest.mark.parametrize("resident", [True, False], ids=["resident",
                                                         "streaming"])
def test_fused_weights_resident_matches_oracle(resident, monkeypatch,
                                               tmp_path, devices, jitted):
    """The weights-resident two-pass schedule (weights stream HBM->VMEM
    once per expert, x re-streams per chunk) must be numerically
    identical to the per-row-tile streaming schedule — forced each way
    through the tuning table's ``weights_resident`` knob on a
    multi-row-tile shape (cap 128 / cm tuned to 32 -> 4 row tiles)."""
    import json

    from flashmoe_tpu import tuning

    table = {"generation": "test", "entries": [{
        "kernel": "fused_ep", "match": {"h": 128},
        "set": {"cm": 32, "weights_resident": resident},
    }]}
    p = tmp_path / "tuning.json"
    p.write_text(json.dumps(table))
    monkeypatch.setenv("FLASHMOE_TUNING_FILE", str(p))
    tuning._load.cache_clear()
    try:
        cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                        intermediate_size=256, sequence_len=512,
                        drop_tokens=False, ep=2, **F32)
        params, x = _setup(cfg)
        mesh = make_mesh(cfg, dp=1, devices=devices[:2])
        out = jitted(fused_ep_moe_layer, cfg, mesh, interpret=True)(params, x)
        want, _ = reference_moe(params, x, cfg)
        np.testing.assert_allclose(
            np.asarray(out.out), np.asarray(want), rtol=2e-4, atol=2e-4
        )
    finally:
        tuning._load.cache_clear()


def test_fused_batched_schedule_matches_per_source(monkeypatch, devices,
                                                   jitted):
    """The arrival-batched schedule (default at ep >= 3: own slab at
    step 0, remote slabs expert-major at the final step with weights
    streamed once — the fix for the d x weight re-streaming the round-5
    cost model exposed) must be numerically identical to the per-source
    schedule and the oracle, drops included."""
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                    intermediate_size=256, sequence_len=512,
                    capacity_factor=1.0, drop_tokens=True, ep=4, **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg, dp=1, devices=devices[:4])
    monkeypatch.delenv("FLASHMOE_FUSED_BATCHED", raising=False)
    batched = jitted(fused_ep_moe_layer, cfg, mesh, interpret=True,
                     detect_races=True)(params, x)
    monkeypatch.setenv("FLASHMOE_FUSED_BATCHED", "0")
    per_src = jitted(fused_ep_moe_layer, cfg, mesh, interpret=True)(params, x)
    monkeypatch.delenv("FLASHMOE_FUSED_BATCHED")
    np.testing.assert_allclose(np.asarray(batched.out),
                               np.asarray(per_src.out),
                               rtol=1e-5, atol=1e-5)
    want = jitted(ep_moe_layer, cfg, mesh, use_pallas=False)(params, x)
    np.testing.assert_allclose(np.asarray(batched.out),
                               np.asarray(want.out),
                               rtol=2e-4, atol=2e-4)


def test_fused_batched_with_in_kernel_combine(monkeypatch, devices, jitted):
    """The two round-5 features compose: arrival-batched FFN (ep=4
    default) + sorted-return combine.  All remote returns issue at the
    final grid step, immediately before the drain's row waits and the
    segment-sum — the tightest schedule the combine's semaphore
    accounting has to survive.  Race detector on."""
    monkeypatch.setenv("FLASHMOE_FUSED_COMBINE", "1")
    monkeypatch.delenv("FLASHMOE_FUSED_BATCHED", raising=False)
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                    intermediate_size=256, sequence_len=512,
                    capacity_factor=1.0, drop_tokens=True, ep=4, **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg, dp=1, devices=devices[:4])
    got = jitted(fused_ep_moe_layer, cfg, mesh, interpret=True,
                 detect_races=True)(params, x)
    want = jitted(ep_moe_layer, cfg, mesh, use_pallas=False)(params, x)
    np.testing.assert_allclose(
        np.asarray(got.out), np.asarray(want.out), rtol=2e-4, atol=2e-4
    )


def _assert_fused_grads_match_collective(params, x, cfg, mesh):
    """Shared gradient contract: jitted grads (un-jitted grad through
    the fused kernels can deadlock the interpreter — see the note on
    the combine gradient test) compared param-by-param."""
    def loss_fused(p, xx):
        o = fused_ep_moe_layer(p, xx, cfg, mesh, interpret=True)
        return (o.out.astype(jnp.float32) ** 2).sum()

    def loss_coll(p, xx):
        o = ep_moe_layer(p, xx, cfg, mesh, use_pallas=False)
        return (o.out.astype(jnp.float32) ** 2).sum()

    gf = jax.jit(jax.grad(loss_fused, argnums=(0, 1)))(params, x)
    gc = jax.jit(jax.grad(loss_coll, argnums=(0, 1)))(params, x)
    np.testing.assert_allclose(np.asarray(gf[1]), np.asarray(gc[1]),
                               rtol=5e-3, atol=5e-3)
    for k in gc[0]:
        np.testing.assert_allclose(
            np.asarray(gf[0][k]), np.asarray(gc[0][k]),
            rtol=5e-3, atol=5e-3, err_msg=k,
        )


def test_fused_batched_gradients(monkeypatch, devices):
    """Autodiff through the batched-schedule forward (the custom VJP's
    backward is schedule-independent, but the fwd kernel under
    linearize is not)."""
    monkeypatch.delenv("FLASHMOE_FUSED_BATCHED", raising=False)
    monkeypatch.delenv("FLASHMOE_FUSED_COMBINE", raising=False)
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                    intermediate_size=256, sequence_len=256,
                    drop_tokens=False, ep=4, **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg, dp=1, devices=devices[:4])
    _assert_fused_grads_match_collective(params, x, cfg, mesh)


def test_fused_batched_forced_at_two_ranks(monkeypatch, tmp_path,
                                           devices, jitted):
    """ep=2 sits below the batched default (the schedules tie on weight
    bytes there) but a measured `batched: true` tuning entry must force
    it — the single-remote-source edge of the generalized two-pass
    (first_q=1, n_srcs=1)."""
    import json

    from flashmoe_tpu import tuning

    p = tmp_path / "t.json"
    p.write_text(json.dumps({"generation": "x", "entries": [{
        "kernel": "fused_ep", "match": {"h": 128},
        "set": {"batched": True}}]}))
    monkeypatch.setenv("FLASHMOE_TUNING_FILE", str(p))
    monkeypatch.delenv("FLASHMOE_FUSED_BATCHED", raising=False)
    tuning._load.cache_clear()
    try:
        cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                        intermediate_size=256, sequence_len=256,
                        drop_tokens=False, ep=2, **F32)
        params, x = _setup(cfg)
        mesh = make_mesh(cfg, dp=1, devices=devices[:2])
        out = jitted(fused_ep_moe_layer, cfg, mesh, interpret=True)(params, x)
        want, _ = reference_moe(params, x, cfg)
        np.testing.assert_allclose(np.asarray(out.out), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
    finally:
        tuning._load.cache_clear()


def test_fused_combine_gradients_match_collective_path(monkeypatch,
                                                       devices):
    """Router + FFN + input gradients must flow correctly through the
    in-kernel combine's custom VJP (w_sorted scatter-transpose + sorted
    dy reconstruction), matching autodiff through the collective path —
    including drops, where unoccupied sorted rows hold garbage that must
    not leak into any cotangent.

    The grads are jitted: un-jitted ``jax.grad`` (eager
    direct_linearize) deadlocks the Pallas interpreter's vector-clock
    device barrier when executing this kernel's forward — a jax
    interpreter issue (a jax.Array leaks into the numpy clock store and
    np.maximum defers back into a blocked dispatch); ``jit(grad(...))``
    compiles the same program and runs clean."""
    monkeypatch.setenv("FLASHMOE_FUSED_COMBINE", "1")
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                    intermediate_size=256, sequence_len=256,
                    capacity_factor=1.0, drop_tokens=True, ep=2, **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg, dp=1, devices=devices[:2])
    _assert_fused_grads_match_collective(params, x, cfg, mesh)


def test_fused_custom_src_order_any_permutation(devices, jitted):
    """Correctness must never depend on the source-processing schedule:
    an adversarial src_order (own slab first, then reverse ring — the
    WORST static prediction) must still match the oracle, with the
    race detector on (the waits, not the order, enforce the protocol)."""
    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                    intermediate_size=256, sequence_len=256,
                    drop_tokens=False, ep=4, **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg, dp=1, devices=devices[:4])
    d = 4
    order = np.stack([
        np.array([r] + [(r - s) % d for s in range(1, d)], np.int32)
        for r in range(d)
    ])
    out = jitted(fused_ep_moe_layer, cfg, mesh, interpret=True,
                 detect_races=True, src_order=order)(params, x)
    want, _ = reference_moe(params, x, cfg)
    np.testing.assert_allclose(
        np.asarray(out.out), np.asarray(want), rtol=2e-4, atol=2e-4
    )


def _force_tiles(monkeypatch, tmp_path, cm, kw, h=128):
    """Pin the rowwin (cm, kw) pair through a throwaway fused_tiles
    table."""
    import json

    from flashmoe_tpu import tuning

    p = tmp_path / "tiles.json"
    p.write_text(json.dumps({"generation": "test", "entries": [{
        "kernel": "fused_tiles", "match": {"h": h},
        "set": {"cm": cm, "kw": kw}}]}))
    monkeypatch.setenv("FLASHMOE_TUNING_FILE", str(p))
    tuning._load.cache_clear()


# The interpret-mode DMA/semaphore emulation this file's kernel tests
# need is absent in some jax versions (the suite's documented 8
# pre-existing environment failures).  NEW kernel-launch tests skip on
# that gap instead of adding to it; the schedule algebra stays gated by
# the emulation test below, which needs no kernel.
from jax.experimental.pallas import tpu as _pltpu  # noqa: E402

requires_interpret = pytest.mark.skipif(
    not hasattr(_pltpu, "InterpretParams"),
    reason="TPU interpret mode unavailable in this jax (pre-existing "
           "environment gap; see ROADMAP.md suite trajectory)")


@requires_interpret
@pytest.mark.parametrize("ep", [1, 2, 4])
def test_rowwin_matches_oracle(ep, monkeypatch, tmp_path, devices, jitted):
    """The row-windowed schedule (ISSUE 12) across world sizes — forced
    multi-window (kw=64 -> 4 K-windows, cm=32 -> multiple row tiles) so
    the HBM partial-sum accumulator path is really exercised — must
    match the dense oracle, with the race detector on."""
    from flashmoe_tpu import tuning

    _force_tiles(monkeypatch, tmp_path, cm=32, kw=64)
    try:
        cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                        intermediate_size=256, sequence_len=256,
                        drop_tokens=False, ep=ep,
                        fused_schedule="rowwin", **F32)
        params, x = _setup(cfg)
        mesh = make_mesh(cfg, dp=1, devices=devices[:ep])
        out = jitted(fused_ep_moe_layer, cfg, mesh, interpret=True,
                     detect_races=True)(params, x)
        want, _ = reference_moe(params, x, cfg)
        np.testing.assert_allclose(
            np.asarray(out.out), np.asarray(want), rtol=2e-4, atol=2e-4
        )
    finally:
        tuning._load.cache_clear()


@requires_interpret
@pytest.mark.parametrize("other", ["stream", "batched", "collective"])
def test_rowwin_identity_across_schedules(other, monkeypatch, tmp_path,
                                          devices, jitted):
    """ISSUE 12 acceptance: rowwin output vs every mutually-feasible
    alternative on the same shape — BIT-identical against the stream
    schedule when the tile/window geometry matches (identical f32
    partial-sum order: acc = sum_j act(x @ Wup_j) @ Wdn_j, the HBM
    round-trip preserves f32 exactly), allclose against the batched
    schedule and the collective path (different accumulation
    geometry reassociates float adds).  Drops included."""
    from flashmoe_tpu import tuning

    cfg = MoEConfig(num_experts=8, expert_top_k=2, hidden_size=128,
                    intermediate_size=256, sequence_len=512,
                    capacity_factor=1.0, drop_tokens=True, ep=4, **F32)
    params, x = _setup(cfg)
    mesh = make_mesh(cfg, dp=1, devices=devices[:4])
    # rowwin at (cm=32, kw=64): 4 windows x multiple row tiles
    _force_tiles(monkeypatch, tmp_path, cm=32, kw=64)
    try:
        rw = jitted(fused_ep_moe_layer, cfg.replace(fused_schedule="rowwin"),
                    mesh, interpret=True, detect_races=True)(params, x)
        if other == "collective":
            want = jitted(ep_moe_layer, cfg, mesh, use_pallas=False)(params, x)
            np.testing.assert_allclose(
                np.asarray(rw.out), np.asarray(want.out),
                rtol=2e-4, atol=2e-4)
            np.testing.assert_array_equal(
                np.asarray(rw.expert_counts),
                np.asarray(want.expert_counts))
        elif other == "batched":
            got = jitted(fused_ep_moe_layer,
                         cfg.replace(fused_schedule="batched"), mesh,
                         interpret=True)(params, x)
            np.testing.assert_allclose(np.asarray(rw.out),
                                       np.asarray(got.out),
                                       rtol=1e-5, atol=1e-5)
        else:
            # stream at the SAME (cm, bi=kw) tiles: identical chunked
            # f32 accumulation order -> bit-identical
            import json

            p = tmp_path / "stream.json"
            p.write_text(json.dumps({"generation": "test", "entries": [{
                "kernel": "fused_ep", "match": {"h": 128},
                "set": {"cm": 32, "bi_cap": 64}}]}))
            monkeypatch.setenv("FLASHMOE_TUNING_FILE", str(p))
            tuning._load.cache_clear()
            got = jitted(fused_ep_moe_layer,
                         cfg.replace(fused_schedule="stream"), mesh,
                         interpret=True)(params, x)
            np.testing.assert_array_equal(np.asarray(rw.out),
                                          np.asarray(got.out))
    finally:
        tuning._load.cache_clear()


def test_rowwin_window_major_emulation():
    """Schedule-math gate that needs no kernel execution (the interpret
    gap of this environment's jax must not leave the rowwin algebra
    unasserted): emulate the window-major loop — per K-window compute
    hidden_j = act(x @ Wup_j), fold acc += hidden_j @ Wdn_j through an
    f32 round-trip buffer (the HBM accumulator) — and assert BIT
    equality with the stream schedule's chunked accumulation and exact
    closeness to the unchunked einsum."""
    import numpy as np

    rng = np.random.RandomState(0)
    cm, h, i, kw = 32, 64, 256, 64
    x = rng.randn(cm, h).astype(np.float32)
    wu = rng.randn(h, i).astype(np.float32)
    wd = rng.randn(i, h).astype(np.float32)

    def relu(v):
        return np.maximum(v, 0.0)

    # stream schedule: VMEM-resident f32 acc over K-chunks
    acc_stream = np.zeros((cm, h), np.float32)
    for j in range(i // kw):
        hid = relu(x @ wu[:, j * kw:(j + 1) * kw])
        acc_stream += hid @ wd[j * kw:(j + 1) * kw, :]

    # rowwin schedule: the SAME per-window algebra, but the partial sum
    # round-trips through an f32 "HBM" buffer between windows
    hbm = None
    for j in range(i // kw):
        acc = np.zeros((cm, h), np.float32) if j == 0 else hbm.copy()
        hid = relu(x @ wu[:, j * kw:(j + 1) * kw])
        acc += hid @ wd[j * kw:(j + 1) * kw, :]
        hbm = acc.astype(np.float32)  # f32 -> f32: exact
    np.testing.assert_array_equal(hbm, acc_stream)
    # and both are the chunked form of the plain GEMM chain
    dense = relu(x @ wu) @ wd
    np.testing.assert_allclose(hbm, dense, rtol=1e-5, atol=1e-4)


def test_forced_infeasible_schedule_raises():
    """MoEConfig.fused_schedule pins a schedule past the heuristics but
    never past the VMEM gate: forcing a weights-once schedule onto a
    mixtral-width expert (or rowwin onto an absurd hidden size) must
    raise a clear ValueError at resolution — the planner marks the
    matching row infeasible instead (tests/test_planner.py)."""
    from flashmoe_tpu.config import BENCH_CONFIGS
    from flashmoe_tpu.parallel.fused import schedule_table

    mix = BENCH_CONFIGS["mixtral"]
    with pytest.raises(ValueError, match="VMEM-infeasible"):
        from flashmoe_tpu.parallel.fused import (
            _fused_schedule, _resolve_tiles,
        )

        cm, bi = _resolve_tiles(160, 4096, 14336, "bfloat16", False)
        _fused_schedule(160, 4096, 14336, 2, True, cm, bi, False, 2, 8,
                        {}, dtype_name="bfloat16", forced="batched")
    # schedule_table never raises for planner consumers: the forced
    # infeasibility surfaces as a reason + auto fallback
    t = schedule_table(mix.replace(fused_schedule="batched"), 8)
    assert t["forced_infeasible"] and "VMEM" in t["forced_infeasible"]
    assert t["schedule"] == "rowwin"  # the auto choice stands in
    # an absurd hidden size starves even the minimal rowwin window pair
    from flashmoe_tpu.parallel.fused import _rowwin_tiles

    assert _rowwin_tiles(32, 2 ** 17, 2 ** 17, 4, None, False, False,
                         2) == (None, None)


def test_rowwin_respects_batched_kill_switches(monkeypatch):
    """rowwin is a batched-pass schedule: FLASHMOE_FUSED_BATCHED=0 (a
    request for per-source arrival processing) must suppress the AUTO
    rowwin choice too, while FLASHMOE_FUSED_ROWWIN=0 targets it
    individually and an explicit fused_schedule='rowwin' forces past
    both."""
    from flashmoe_tpu.config import BENCH_CONFIGS
    from flashmoe_tpu.parallel.fused import schedule_table

    mix = BENCH_CONFIGS["mixtral"]
    monkeypatch.delenv("FLASHMOE_FUSED_BATCHED", raising=False)
    monkeypatch.delenv("FLASHMOE_FUSED_ROWWIN", raising=False)
    assert schedule_table(mix, 8)["schedule"] == "rowwin"
    monkeypatch.setenv("FLASHMOE_FUSED_ROWWIN", "0")
    assert schedule_table(mix, 8)["schedule"] == "stream"
    monkeypatch.delenv("FLASHMOE_FUSED_ROWWIN")
    monkeypatch.setenv("FLASHMOE_FUSED_BATCHED", "0")
    assert schedule_table(mix, 8)["schedule"] == "stream"
    assert schedule_table(mix.replace(fused_schedule="rowwin"),
                          8)["schedule"] == "rowwin"


def test_arrival_order_and_skew_bounds():
    """The static arrival-order schedule (VERDICT r3 missing #2): on a
    homogeneous torus it reduces to ring order; rows are always own-first
    permutations; and across the committed skew experiment the predicted
    order recovers the oracle makespan while ring order's stall stays
    bounded by the arrival spread."""
    import importlib.util as ilu
    import os
    from flashmoe_tpu.parallel.topology import arrival_order
    spec = ilu.spec_from_file_location(
        "skew_sim", os.path.join(os.path.dirname(__file__), "..",
                                 "scripts", "skew_sim.py"))
    sim = ilu.module_from_spec(spec)
    spec.loader.exec_module(sim)
    run, torus_adj = sim.run, sim.torus_adj

    adj = torus_adj(8)
    order = arrival_order(adj, 4.0)
    for r in range(8):
        assert order[r, 0] == r
        assert sorted(order[r]) == list(range(8))
    ring = np.array([[(r + s) % 8 for s in range(8)] for r in range(8)])
    np.testing.assert_array_equal(order, ring)

    for row in run(8, slab_mb=4.0, t_c=0.3):
        # perfect estimate -> predicted order is arrival order
        assert row["pred_stall_ms"] <= 1e-9, row
        # one slow link stalls ring order at most one arrival spread
        assert row["ring_stall_ms"] <= row["arrival_spread_ms"] + 1e-9, row
