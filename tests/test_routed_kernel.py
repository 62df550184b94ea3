"""The routed rows through the grouped Pallas FFN kernel (ISSUE 36), in
``interpret`` here, at small shapes; what the chip's compiler makes of it
at the cells' shapes is ``tests/test_tpu_compile.py``'s.

The kernel form of ``ops/moe.routed_rows_ffn`` is held against its plain
``jax.lax.ragged_dot`` form through the function's own contract: x and a
router's output in, ``[S, H]`` float32 back.  Serving: no gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashmoe_tpu.config import MoEConfig
from flashmoe_tpu.models.presets import PRESETS
from flashmoe_tpu.models.reference import init_moe_params
from flashmoe_tpu.ops import moe
from flashmoe_tpu.ops import ragged as rag
from flashmoe_tpu.ops.gate import RouterOutput


def _cfg(e=8, k=2, h=128, i=256, gated=True, **more):
    return MoEConfig(num_experts=e, expert_top_k=k, hidden_size=h,
                     intermediate_size=i, sequence_len=64, drop_tokens=False,
                     dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                     gated_ffn=gated, **more)


def _route(rows_of, cfg, seed=0):
    """A router's output that sends ``rows_of[e]`` (token, choice) rows
    to expert e (their sum a multiple of K), the rows shuffled."""
    k = cfg.expert_top_k
    flat = np.repeat(np.arange(len(rows_of)), rows_of)
    assert len(flat) % k == 0 and len(rows_of) == cfg.num_experts
    rng = np.random.default_rng(seed)
    idx = rng.permutation(flat).reshape(-1, k).astype(np.int32)
    w = rng.uniform(0.1, 1.0, idx.shape).astype(np.float32)
    zero = jnp.zeros((), jnp.float32)
    return RouterOutput(
        jnp.asarray(w), jnp.asarray(idx),
        jnp.asarray(np.asarray(rows_of, np.int32)),
        jnp.zeros((cfg.num_experts,), jnp.float32), zero, zero)


def _both_forms(monkeypatch, params, x, r, cfg):
    want = jax.jit(lambda x: moe.routed_rows_ffn(params, x, r, cfg))(x)
    monkeypatch.setattr(moe, "routed_rows_form", lambda c: "routed_kernel")
    got = jax.jit(lambda x: moe.routed_rows_ffn(params, x, r, cfg))(x)
    return np.asarray(got), np.asarray(want)


def _close(got, want, tol=2e-2):
    """To the rounding of bf16 products summed in another order."""
    assert got.shape == want.shape and got.dtype == want.dtype == np.float32
    scale = np.abs(want).max()
    assert np.isfinite(got).all() and scale > 0
    assert np.abs(got - want).max() <= tol * scale


#: rows an expert, eight experts top-2, 16-row tiles (bf16's smallest)
ROWS = {
    "none_or_one": [0, 1, 0, 1, 1, 0, 0, 1],
    "exactly_a_tile": [16, 0, 16, 0, 0, 0, 0, 0],
    "over_a_tile": [17, 1, 33, 0, 5, 0, 0, 0],
    "one_expert_takes_all": [0, 0, 0, 64, 0, 0, 0, 0],
    "every_expert": [3, 5, 2, 7, 4, 6, 1, 4],
    "last_expert_empty": [9, 0, 0, 0, 0, 0, 7, 0],
    "first_expert_empty": [0, 0, 0, 0, 0, 0, 7, 9],
}


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
@pytest.mark.parametrize("rows", list(ROWS))
def test_the_kernel_form_is_the_ragged_forms_layer(monkeypatch, rows, gated):
    """Experts with 0, 1, exactly a tile and more than a tile of rows,
    tail tiles past the populated ones: the kernel form returns what the
    ``ragged_dot`` form returns, to bf16's rounding."""
    cfg = _cfg(gated=gated)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    r = _route(ROWS[rows], cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (r.expert_idx.shape[0], 128),
                          jnp.bfloat16)
    assert moe.rows_block_m(cfg, x.shape[0]) == 16
    _close(*_both_forms(monkeypatch, params, x, r, cfg))


@pytest.mark.parametrize("block_m", [16, 32, 128])
def test_the_kernel_form_at_taller_tiles(monkeypatch, block_m):
    """The tile the rule would give a longer span, forced on a short one:
    one tile an expert, most of it padding."""
    cfg = _cfg()
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    r = _route(ROWS["over_a_tile"], cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (r.expert_idx.shape[0], 128),
                          jnp.bfloat16)
    monkeypatch.setattr(moe, "rows_block_m", lambda c, s: block_m)
    _close(*_both_forms(monkeypatch, params, x, r, cfg))


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
@pytest.mark.parametrize("here", [[5, 0, 19, 0], [0, 0, 0, 0], [1, 1, 1, 1]],
                         ids=["some", "no_row_here", "one_each"])
def test_a_share_of_the_experts_gets_tiles_for_its_own_rows(monkeypatch, here,
                                                            gated):
    """``experts_held``: four of twelve experts held (experts 4-7); the
    rows that fall elsewhere get no tile and count as zero, in both
    forms alike, also when NO row falls here."""
    cfg = _cfg(e=12, gated=gated, experts_held=4, expert_first=4)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    assert params["w_up"].shape[0] == 4
    elsewhere = [7, 3, 0, 9, 2, 0, 4, 11]
    pad = (-(sum(here) + sum(elsewhere))) % cfg.expert_top_k
    r = _route(elsewhere[:4] + here + elsewhere[4:-1]
               + [elsewhere[-1] + pad], cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (r.expert_idx.shape[0], 128),
                          jnp.bfloat16)
    got, want = _both_forms(monkeypatch, params, x, r, cfg)
    if not any(here):
        assert not got.any() and not want.any()
    else:
        _close(got, want)
        # a token none of whose choices is held gets exactly nothing
        idx = np.asarray(r.expert_idx)
        out = ~((idx >= 4) & (idx < 8)).any(axis=1)
        assert out.any() and not got[out].any()


#: the four serving configurations' widths (H, I, K, activation, biases as
#: the presets have them), sixteen experts, one layer
SERVED = {
    "dsmoe16b": ("deepseek-moe-16b", {}),
    "joyai_flash": ("joyai-llm-flash", {}),
    "ling3_flash": ("ling-3.0-flash", {"experts_held": 8}),
    "lfm2_24b": ("lfm2-24b-a2b", {}),
}


@pytest.mark.parametrize("s", [8, 96], ids=["decode", "span"])
@pytest.mark.parametrize("name", list(SERVED))
def test_the_kernel_form_at_the_served_widths(monkeypatch, name, s):
    """Each served configuration's H, I and K (its experts cut to
    sixteen, one mixture layer) through the config's own router: a decode
    step's rows and a span's."""
    preset, more = SERVED[name]
    full = PRESETS[preset]()
    cfg = full.ffn_config(full.moe_layer_indices[0]).replace(
        num_experts=16, n_group=1, topk_group=1, num_shared_experts=0,
        **more)
    params = init_moe_params(jax.random.PRNGKey(4), cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (s, cfg.hidden_size),
                          jnp.bfloat16)

    def layer():
        # a function of its own each time: jax.jit of the SAME function
        # hands back its first trace and never reads the patched form (so
        # these cases compared the plain form with itself until ISSUE 47);
        # and the weights as an argument: 300 MB of closed-over constants
        # took eight of a case's nine seconds to compile
        return jax.jit(lambda p, x: moe.moe_layer(
            p, x, cfg, use_pallas=False, routed_rows=True).out)

    want = np.asarray(layer()(params, x), np.float32)
    asked = []
    monkeypatch.setattr(moe, "routed_rows_form",
                        lambda c: asked.append(c) or "routed_kernel")
    got = np.asarray(layer()(params, x), np.float32)
    assert asked, "the second trace never asked for its form"
    assert np.abs(got - want).max() <= 2e-2 * np.abs(want).max()


@pytest.mark.parametrize("backend,h,i,form", [
    ("tpu", 2048, 768, "routed_kernel"), ("tpu", 2048, 1408, "routed_kernel"),
    ("tpu", 2048, 960, "routed_rows"), ("tpu", 192, 768, "routed_rows"),
    ("cpu", 2048, 768, "routed_rows"), ("gpu", 2048, 768, "routed_rows"),
])
def test_the_form_follows_the_widths_and_the_backend(monkeypatch, backend, h,
                                                     i, form):
    """The kernel on a TPU when H and I (as stored) are whole lanes,
    ``ragged_dot`` everywhere else; ``expert_arm`` names the form where it takes the
    routed rows and says ``capacity`` as before where it does not."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = _cfg(e=256, k=8, h=h, i=i)
    assert moe.routed_rows_form(cfg) == form
    assert moe.expert_arm(cfg, 1024) == form
    assert moe.expert_arm(cfg.replace(experts_held=64), 8) == form
    assert moe.expert_arm(cfg.replace(num_experts=1, expert_top_k=1),
                          1024) == "capacity"
    # stored with zero columns to whole lanes (ISSUE 39), the odd width
    # takes the kernel too
    if (backend, i) == ("tpu", 960):
        assert moe.routed_rows_form(
            cfg.replace(intermediate_pad=64)) == "routed_kernel"


@pytest.mark.parametrize("s,e,k,dtype,block", [
    (32, 256, 8, jnp.bfloat16, 16), (64, 512, 8, jnp.bfloat16, 16),
    (128, 64, 4, jnp.bfloat16, 16), (1024, 256, 8, jnp.bfloat16, 64),
    (1024, 512, 8, jnp.bfloat16, 32), (1024, 64, 4, jnp.bfloat16, 128),
    (2048, 64, 6, jnp.bfloat16, 256), (8192, 64, 6, jnp.bfloat16, 256),
    (32, 256, 8, jnp.float32, 8),
])
def test_a_tile_is_twice_the_rows_an_expert_expects(s, e, k, dtype, block):
    """The smallest packed tile for a decode step's 1-4 rows an expert,
    64-128 rows for a 1024-token chunk's 32-64, never over 256."""
    assert moe.rows_block_m(_cfg(e=e, k=k).replace(dtype=dtype), s) == block


@pytest.mark.parametrize("sizes,block_m", [
    ([0, 1, 0, 17, 16, 0], 16), ([0, 0, 0], 16), ([40, 0, 3], 8),
    ([5], 16), ([0, 0, 64], 32),
])
def test_the_plan_comes_from_the_one_sort(sizes, block_m):
    """``sorted_rows_plan``: every row of a group lies in the group's own
    tiles, in sorted order; pad rows and the tiles past the live ones
    read row 0; the tail repeats the last live tile's group."""
    sizes = np.asarray(sizes, np.int32)
    groups = len(sizes)
    rng = np.random.default_rng(1)
    outside = 3                                    # rows of no group
    flat = rng.permutation(np.concatenate(
        [np.repeat(np.arange(groups), sizes), np.full(outside, groups)]))
    order = np.argsort(flat, kind="stable")
    n_tiles = rag.sorted_rows_tiles(len(flat), groups, block_m)
    src, gid, live, starts, pad_starts = map(np.asarray, rag.sorted_rows_plan(
        jnp.asarray(order), jnp.asarray(sizes), block_m, n_tiles))
    tiles = -(-sizes // block_m)
    assert live.tolist() == [tiles.sum()] and tiles.sum() <= n_tiles
    assert gid[:live[0]].tolist() == np.repeat(np.arange(groups),
                                               tiles).tolist()
    last = gid[live[0] - 1] if live[0] else gid[0]
    assert (gid[live[0]:] == last).all()
    placed = np.zeros(n_tiles * block_m, bool)
    for g in range(groups):
        at = pad_starts[g] + np.arange(sizes[g])
        assert (gid[at // block_m] == g).all()
        assert src[at].tolist() == order[starts[g]:starts[g] + sizes[g]
                                         ].tolist()
        placed[at] = True
    assert not src[~placed].any()


@pytest.mark.parametrize("model", ["kv", "held"])
def test_engine_on_the_kernel_form_serves_the_plain_arms_tokens(monkeypatch,
                                                                model):
    """The engine with every mixture layer's experts on the kernel form
    (forced, in ``interpret``), whole prompts, chunks and decode steps,
    over a K/V toy and one that holds a share of its experts beside
    recurrent state: the token streams of the plain arms, ``expert_arm``
    on every ``serve_decode`` and ``serve_prefill`` record, and the
    counter counts the programs."""
    from flashmoe_tpu.models.transformer import init_params
    from flashmoe_tpu.serving import engine as eng
    from flashmoe_tpu.serving.engine import (
        Request, ServeConfig, ServingEngine)
    from flashmoe_tpu.serving.loadgen import tiny_config
    from flashmoe_tpu.utils.telemetry import FlightRecorder, Metrics

    if model == "kv":
        cfg = tiny_config(vocab=247)
    else:
        cfg = PRESETS["ling-3.0-flash"](
            num_layers=3, layer_mixers=("kda", "kda", "mla"), first_k_dense=1,
            num_experts=16, expert_top_k=3, n_group=4, topk_group=2,
            expert_first=4, experts_held=4, kda_heads=3, kda_head_dim=16,
            hidden_size=64, intermediate_size=64,
            dense_intermediate_size=128, vocab_size=247, num_heads=3,
            kv_lora_rank=20, qk_nope_head_dim=10, qk_rope_head_dim=6,
            v_head_dim=14, dtype=jnp.float32, param_dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=tuple(int(t) for t in rng.integers(
        1, 247, n)), max_new_tokens=6) for i, n in enumerate((5, 21, 9))]
    serve = ServeConfig(max_batch=4, page_size=8, num_pages=32,
                        max_pages_per_slot=4, ctx_bucket_pages=4,
                        prompt_bucket=8, prefill_chunk=16)
    programs = [eng._prefill_padded, *eng._INPLACE.values()]

    def run():
        for program in programs:
            program.clear_cache()       # trace with the form of the moment
        recorder, metrics = FlightRecorder(), Metrics()
        engine = ServingEngine(params, cfg, serve, recorder=recorder,
                               metrics_obj=metrics)
        out = engine.run(reqs, arrivals=[0, 0, 1])
        arms = {kind: [r["expert_arm"] for r in recorder.records
                       if r["kind"] == kind]
                for kind in ("serve_decode", "serve_prefill")}
        return out, arms, metrics.counters.get(
            "serve.expert_kernel_programs", 0)

    plain = "routed_rows" if model == "held" else "capacity"
    want, arms, counted = run()
    assert set(arms["serve_decode"]) == set(arms["serve_prefill"]) == {plain}
    assert counted == 0
    try:
        monkeypatch.setattr(moe, "routed_rows_form",
                            lambda c: "routed_kernel")
        got, arms, counted = run()
    finally:
        monkeypatch.undo()
        for program in programs:
            program.clear_cache()
    assert got == want
    assert set(arms["serve_decode"]) == set(arms["serve_prefill"]) == {
        "routed_kernel"}
    assert len(arms["serve_prefill"]) >= 4       # a prompt in two chunks
    assert counted == len(arms["serve_decode"]) + len(arms["serve_prefill"])
