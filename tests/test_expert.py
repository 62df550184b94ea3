"""Grouped Pallas FFN kernel vs the batched XLA path (interpreter mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashmoe_tpu.config import Activation, MoEConfig
from flashmoe_tpu.models.reference import init_moe_params
from flashmoe_tpu.ops.expert import (
    capacity_buffer_ffn_ad,
    capacity_buffer_ffn_pallas,
    expert_ffn_dense,
    grouped_ffn,
    grouped_matmul,
    tgmm,
)

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)


def _params_x(cfg, c, seed=0):
    key = jax.random.PRNGKey(seed)
    params = init_moe_params(key, cfg)
    xs = jax.random.normal(
        jax.random.PRNGKey(seed + 1),
        (cfg.num_experts, c, cfg.hidden_size), jnp.float32,
    )
    return params, xs


@pytest.mark.parametrize("cfg,cap", [
    (MoEConfig(num_experts=4, hidden_size=128, intermediate_size=256, **F32),
     128),
    (MoEConfig(num_experts=4, hidden_size=128, intermediate_size=512,
               hidden_act=Activation.RELU, **F32), 64),
    (MoEConfig(num_experts=2, hidden_size=256, intermediate_size=1024,
               gated_ffn=True, hidden_act=Activation.SILU, **F32), 128),
], ids=["gelu", "relu_smallcap", "gated_silu"])
def test_capacity_buffer_matches_dense(cfg, cap):
    params, xs = _params_x(cfg, cap)
    want = expert_ffn_dense(xs, params, cfg)
    got = capacity_buffer_ffn_pallas(xs, params, cfg, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
    )


def test_grouped_matmul_and_tgmm_match_einsum():
    """The backward kernels against XLA oracles: grouped matmul (plain and
    transposed weights) and the transposed grouped GEMM (dW)."""
    e, t, k, n, bm = 3, 6 * 16, 128, 256, 16
    kx = jax.random.PRNGKey(0)
    x = jax.random.normal(kx, (t, k), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (e, k, n), jnp.float32)
    wt = jax.random.normal(jax.random.PRNGKey(2), (e, n, k), jnp.float32)
    gid = jnp.array([0, 0, 1, 2, 2, 2], jnp.int32)  # nondecreasing
    row_e = jnp.repeat(gid, bm)

    got = grouped_matmul(x, gid, w, block_m=bm, interpret=True)
    want = jnp.einsum("tk,tkn->tn", x, w[row_e])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)

    got_t = grouped_matmul(x, gid, wt, transpose_w=True, block_m=bm,
                           interpret=True)
    want_t = jnp.einsum("tk,tnk->tn", x, wt[row_e])
    np.testing.assert_allclose(np.asarray(got_t), np.asarray(want_t),
                               rtol=2e-4, atol=2e-4)

    dy = jax.random.normal(jax.random.PRNGKey(3), (t, n), jnp.float32)
    got_w = tgmm(x, dy, gid, e, block_m=bm, interpret=True)
    oh = jax.nn.one_hot(row_e, e, dtype=jnp.float32)
    want_w = jnp.einsum("tk,tn,te->ekn", x, dy, oh)
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(want_w),
                               rtol=2e-4, atol=2e-4)


def test_tgmm_zero_token_expert_gets_zero_grad():
    """An expert absent from tile_gid must get exactly-zero dW, not the
    uninitialized garbage of its never-visited output blocks."""
    e, bm = 3, 16
    gid = jnp.array([0, 0, 2], jnp.int32)  # expert 1 has no tiles
    x = jax.random.normal(jax.random.PRNGKey(0), (3 * bm, 64), jnp.float32)
    dy = jax.random.normal(jax.random.PRNGKey(1), (3 * bm, 128), jnp.float32)
    dw = tgmm(x, dy, gid, e, block_m=bm, interpret=True)
    assert np.isfinite(np.asarray(dw)).all()
    assert (np.asarray(dw[1]) == 0).all()


def test_backward_handles_non_512_multiple_dims():
    """H or I not a multiple of 512 (e.g. 768) must train, not crash: the
    backward kernels fall back to a dividing chunk size."""
    cfg = MoEConfig(num_experts=2, hidden_size=192, intermediate_size=320,
                    **F32)
    params, xs = _params_x(cfg, 64)

    def loss(xs, p):
        return (capacity_buffer_ffn_ad(xs, p, cfg, interpret=True)
                .astype(jnp.float32) ** 2).sum()

    g = jax.grad(loss, argnums=(0, 1))(xs, params)
    for leaf in jax.tree_util.tree_leaves(g):
        assert np.isfinite(np.asarray(leaf)).all()


@pytest.mark.parametrize("cfg,cap", [
    (MoEConfig(num_experts=4, hidden_size=128, intermediate_size=256, **F32),
     64),
    (MoEConfig(num_experts=2, hidden_size=128, intermediate_size=512,
               gated_ffn=True, hidden_act=Activation.SILU, **F32), 64),
], ids=["gelu", "gated_silu"])
def test_fused_backward_matches_xla_grads(cfg, cap):
    """The Pallas backward (grouped_matmul/tgmm with saved residuals) must
    produce the same gradients as autodiff through the dense XLA FFN."""
    params, xs = _params_x(cfg, cap)

    def loss_pallas(xs, p):
        y = capacity_buffer_ffn_ad(xs, p, cfg, interpret=True)
        return (y.astype(jnp.float32) ** 2).sum()

    def loss_dense(xs, p):
        y = expert_ffn_dense(xs, p, cfg)
        return (y.astype(jnp.float32) ** 2).sum()

    gp = jax.grad(loss_pallas, argnums=(0, 1))(xs, params)
    gd = jax.grad(loss_dense, argnums=(0, 1))(xs, params)
    np.testing.assert_allclose(np.asarray(gp[0]), np.asarray(gd[0]),
                               rtol=5e-3, atol=5e-3)
    for k in gd[1]:
        if k.startswith("shared"):
            continue
        np.testing.assert_allclose(
            np.asarray(gp[1][k]), np.asarray(gd[1][k]),
            rtol=5e-3, atol=5e-3, err_msg=k,
        )


def test_grouped_ffn_respects_tile_gid():
    """Row tiles must each use exactly their own expert's weights."""
    cfg = MoEConfig(num_experts=4, hidden_size=128, intermediate_size=256,
                    **F32)
    params, _ = _params_x(cfg, 8)
    bm = 8
    # tiles assigned to experts in scrambled order, incl. repeats
    tile_gid = jnp.array([2, 0, 3, 3, 1, 0], dtype=jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(7), (6 * bm, 128), jnp.float32)
    got = grouped_ffn(
        x, tile_gid, params["w_up"], params["b_up"], params["w_down"],
        params["b_down"], act_name=cfg.hidden_act, block_m=bm,
        block_i=128, interpret=True,
    )
    # oracle: per-tile dense FFN with that tile's expert
    for t in range(6):
        e = int(tile_gid[t])
        xt = x[t * bm:(t + 1) * bm]
        up = xt @ params["w_up"][e] + params["b_up"][e]
        want = jax.nn.gelu(up) @ params["w_down"][e] + params["b_down"][e]
        np.testing.assert_allclose(
            np.asarray(got[t * bm:(t + 1) * bm]), np.asarray(want),
            rtol=2e-4, atol=2e-4,
        )


# ----------------------------------------------------------------------
# What a launch FETCHES: the weight blocks' index maps, walked as the
# pipeline walks them (ISSUE 48)
# ----------------------------------------------------------------------

def _weight_fetches(monkeypatch, tile_gid, live, nj, *, gated=True, bm=16):
    """The grid steps of one ``grouped_ffn`` launch at which the pipeline
    fetches each weight operand: the launch is traced with ``pl.pallas_call``
    replaced by a recorder of the ``BlockSpec`` s it is handed, every block's
    index map is evaluated at every step of the ``(nt, nj)`` grid in the
    order the grid runs (``j`` fastest) with the prefetched scalars as the
    arrays they are, and a step counts where the block's index differs from
    the step before (the first step fetches).  Returns ``{operand: steps}``
    for the up, gate, up-bias and down blocks, and the sequence of the up
    block's indices."""
    from flashmoe_tpu.ops import expert as exp

    h, i, e = 128, 128 * nj, int(max(tile_gid)) + 1
    nt = len(tile_gid)
    # one chunk of 128 columns fits, two do not: the walk is ``nj`` chunks
    need = lambda b: exp._ffn_vmem(bm, h, b, gated, 2, 2)
    monkeypatch.setattr(exp, "_VMEM_CEILING", need(128))
    seen = {}

    def record(kernel, *, grid_spec, out_shape, **kw):
        seen["spec"] = grid_spec
        return lambda *operands: jnp.zeros(out_shape.shape, out_shape.dtype)

    monkeypatch.setattr(exp.pl, "pallas_call", record)
    bf = lambda *s: jnp.zeros(s, jnp.bfloat16)
    f32 = lambda *s: jnp.zeros(s, jnp.float32)
    gid = jnp.asarray(tile_gid, jnp.int32)
    lv = None if live is None else jnp.asarray([live], jnp.int32)
    # not the jitted function: a trace of it would outlive the patches
    exp.grouped_ffn.__wrapped__(
        bf(nt * bm, h), gid, bf(e, h, i), f32(e, i), bf(e, i, h), f32(e, h),
        bf(e, h, i) if gated else None, lv, act_name="silu", gated=gated,
        block_m=bm, block_i=i)
    spec = seen["spec"]
    assert spec.grid == (nt, nj)
    names = (["x", "up"] + (["gate"] if gated else [])
             + ["b_up", "down", "b_down"])
    assert len(spec.in_specs) == len(names)
    scalars = (np.asarray(tile_gid),) + (() if live is None
                                         else (np.asarray([live]),))
    fetches, last, walk = dict.fromkeys(names, 0), {}, []
    for ti in range(nt):
        for j in range(nj):
            for name, bs in zip(names, spec.in_specs):
                at = tuple(int(a) for a in bs.index_map(ti, j, *scalars))
                fetches[name] += at != last.get(name)
                last[name] = at
                if name == "up":
                    walk.append(at)
    return fetches, walk


#: the decode plan of ``longcat_flash_omni.serve.avturns``: 304 rows in 19
#: tiles of 16, ten experts of sixteen touched with a tile each, four chunks
DECODE_PLAN = dict(tile_gid=[0, 2, 3, 5, 6, 8, 9, 11, 12, 15] + [15] * 9,
                   live=10, nj=4)
#: its walk with the parent's maps ``(gid[ti], 0, j)`` / ``(gid[ti], j, 0)``
PARENT_WALK = lambda gid, nj: [(g, 0, j) for g in gid for j in range(nj)]
#: and with every other tile walked backward: consecutive tiles meet at a chunk
SERPENTINE = lambda gid, nj: [
    (g, 0, j if ti % 2 == 0 else nj - 1 - j)
    for ti, g in enumerate(gid) for j in range(nj)]


def test_dead_tiles_of_a_chunked_launch_fetch_no_weights(monkeypatch):
    """19 tiles, 10 live, 4 chunks: the weight blocks' indices change at
    ``live x nj`` = 40 steps (each touched expert's four chunks, once);
    on the parent's maps ``j`` ran 0..3 under every dead tile too and
    they changed at ``nt x nj`` = 76, the last live expert streamed nine
    times more."""
    fetches, walk = _weight_fetches(monkeypatch, **DECODE_PLAN)
    assert [fetches[k] for k in ("up", "gate", "b_up", "down")] == [40] * 4
    assert fetches["b_down"] == 10 and fetches["x"] == 19
    parent = PARENT_WALK(DECODE_PLAN["tile_gid"], 4)
    assert 1 + sum(a != b for a, b in zip(parent, parent[1:])) == 76
    # the live tiles: each its own expert's four chunks, odd tiles backward
    assert walk[:40] == SERPENTINE(DECODE_PLAN["tile_gid"][:10], 4)
    assert sorted(walk[:40]) == sorted(parent[:40])
    # the dead ones: where the last live tile (the tenth: odd) ended
    assert set(walk[40:]) == {walk[39]} == {(15, 0, 0)}


@pytest.mark.parametrize("plan,want", [
    # a group of two tiles meets at one chunk: 2 x 4 - 1 blocks, where the
    # parent's wrapping ``j`` fetched 8 (and a group of t: t x 3 + 1)
    (dict(tile_gid=[1, 4, 4, 7, 7, 7, 7], live=4, nj=4), 4 + 7 + 4),
    (dict(tile_gid=[1, 4, 4, 7, 7, 7, 7], live=4, nj=2), 2 + 3 + 2),
    (dict(tile_gid=[2, 2, 2, 2, 5, 5], live=5, nj=4), 13 + 4),
    (dict(tile_gid=[3, 3, 3, 3], live=1, nj=4), 4),
    # no live tile (a window past the populated ones): one block, once
    (dict(tile_gid=[0, 0, 0], live=0, nj=4), 1),
    # every tile live: nothing to hold
    (dict(tile_gid=[0, 1, 2], live=3, nj=4), 12),
    (dict(tile_gid=[0, 1, 1, 1], live=2, nj=4, gated=False), 8),
], ids=["pair", "pair_two_chunks", "four_tiles", "one_live", "none_live",
        "all_live", "ungated"])
def test_a_chunked_launch_fetches_a_group_once_and_a_bit(monkeypatch, plan,
                                                         want):
    """``live x nj`` blocks less one for every live tile that follows a
    tile of its own expert; every live tile still walks each of its
    expert's chunks exactly once; no step past the live ones fetches."""
    fetches, walk = _weight_fetches(monkeypatch, **plan)
    live, nj, gid = plan["live"], plan["nj"], plan["tile_gid"]
    pairs = sum(a == b for a, b in zip(gid[:live], gid[1:live]))
    assert want == max(live * nj - pairs, 1)
    assert fetches["up"] == fetches["down"] == fetches["b_up"] == want
    assert walk[:live * nj] == SERPENTINE(gid[:live], nj)
    for ti in range(live):
        assert sorted(walk[ti * nj:(ti + 1) * nj]) == [
            (gid[ti], 0, j) for j in range(nj)]
    assert len(set(walk[max(live * nj - 1, 0):])) == 1


@pytest.mark.parametrize("live,nj", [(None, 4), (None, 1), (10, 1)],
                         ids=["no_live_tiles", "no_live_one_chunk",
                              "live_one_chunk"])
def test_other_launches_keep_the_parents_index_maps(monkeypatch, live, nj):
    """Without ``live_tiles`` (training's launches) or with ONE chunk
    (every serving cell but LongCat's) the walk is the parent's, step for
    step; with one chunk a dead tile never fetched (its index is the last
    live tile's)."""
    gid = DECODE_PLAN["tile_gid"]
    fetches, walk = _weight_fetches(monkeypatch, gid, live, nj)
    assert walk == PARENT_WALK(gid, nj)
    assert fetches["up"] == (76 if nj == 4 else 10)
