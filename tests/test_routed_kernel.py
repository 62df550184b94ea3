"""The routed rows through the grouped Pallas FFN kernel (ISSUE 36), in
``interpret`` here, at small shapes; what the chip's compiler makes of it
at the cells' shapes is ``tests/test_tpu_compile.py``'s.

The kernel form of ``ops/moe.routed_rows_ffn`` is held against its plain
``jax.lax.ragged_dot`` form through the function's own contract: x and a
router's output in, ``[S, H]`` float32 back.  Serving: no gradients."""

import hashlib
import re

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


def _retrace_launches():
    """The three jitted functions between the layer and the kernel keep
    their traces by shape: one made under another ``_VMEM_CEILING`` or
    other index maps would be handed back."""
    from flashmoe_tpu.ops import expert as exp

    for fn in (moe._rows_kernel_ffn, moe._rows_kernel_waves,
               exp.grouped_ffn):
        fn.clear_cache()


@pytest.fixture
def four_chunks(monkeypatch):
    """``_VMEM_CEILING`` at what ONE 128-column chunk of a gated H 128
    expert needs under 16-row bf16 tiles: at I 512 the launch walks four
    chunks, as LongCat's does at H 6144 x I 2048 under the real ceiling."""
    from flashmoe_tpu.ops import expert as exp

    monkeypatch.setattr(exp, "_VMEM_CEILING",
                        exp._ffn_vmem(16, 128, 128, True, 2, 2))
    _retrace_launches()
    yield exp
    monkeypatch.undo()
    _retrace_launches()


#: rows on the four experts held (4-7 of 32; S x K = 128 routed rows, a
#: plan of 64 in windows of 7 tiles): dead tiles behind a lopsided group
#: of three tiles; and 8 live tiles, a second window with one live of 7
HELD_ROWS = {"one_window": [5, 0, 40, 3], "two_windows": [40, 40, 8, 1]}


@pytest.mark.parametrize("rows", list(HELD_ROWS))
def test_the_chunked_walk_through_the_layer(monkeypatch, four_chunks, rows):
    """ISSUE 48: the intermediate axis in four chunks under a plan with
    dead tiles (a share of the experts held: ``rows_plan`` < S x K) and a
    group of several tiles.  The layer is the ``ragged_dot`` form's to
    bf16's rounding, and the launch whose tiles walk the chunks as
    ``expert._chunk_under_live`` says returns what the parent's maps,
    ``j`` under every tile, return: a dead tile computes nothing under
    either, an even live tile sums the same chunks in the same order (its
    rows BIT FOR BIT), an odd one in the other order (float32 sums in
    another order, rounded to bf16 once: an ulp of bf16 at most)."""
    exp = four_chunks
    cfg = _cfg(e=32, i=512, experts_held=4, expert_first=4)
    here = HELD_ROWS[rows]
    rest = 128 - 24 - sum(here)                    # on the last sixteen
    r = _route([2] * 4 + here + [2] * 8
               + [rest // 16 + (e < rest % 16) for e in range(16)], cfg)
    s = r.expert_idx.shape[0]
    assert s * 2 == 128 and moe.rows_plan(cfg, s) == 64 < s * 2
    assert moe.rows_block_m(cfg, s) == 16 and moe.expert_chunks(cfg, s) == 4
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(6), (s, 128), jnp.bfloat16)
    got, want = _both_forms(monkeypatch, params, x, r, cfg)
    _close(got, want)
    # a token none of whose choices is held gets exactly nothing
    idx = np.asarray(r.expert_idx)
    out = ~((idx >= 4) & (idx < 8)).any(axis=1)
    assert out.any() and not got[out].any()
    held = []
    monkeypatch.setattr(exp, "_chunk_under_live",
                        lambda nj: held.append(nj) or exp._chunk_of_step)
    _retrace_launches()
    parents = jax.jit(lambda x: moe.routed_rows_ffn(params, x, r, cfg))(x)
    assert held == [4], "the launch never asked for its tiles' chunks"
    parents = np.asarray(parents)
    assert (parents == got).mean() > 0.5           # the even tiles' tokens
    _close(got, parents, tol=2 ** -7)


#: the eight configurations the benchmark runs, by their presets: H, the
#: intermediate width as stored, gated, compute dtype
CONFIGURATIONS = {
    "dsmoe16b": ("deepseek-moe-16b", (2048, 1408, True, "bfloat16")),
    "fmref": ("flashmoe-reference", (2048, 2048, False, "bfloat16")),
    "joyai_flash": ("joyai-llm-flash", (2048, 768, True, "bfloat16")),
    "ling3_flash": ("ling-3.0-flash", (2560, 768, True, "bfloat16")),
    "lfm2_24b": ("lfm2-24b-a2b", (2048, 1536, True, "bfloat16")),
    "nemotron3_nano": ("nemotron-3-nano-30b-a3b",
                       (2688, 1920, False, "bfloat16")),
    "longcat_flash_omni": ("longcat-flash", (6144, 2048, True, "bfloat16")),
    "sdar_30b_a3b": ("sdar-30b-a3b-chat", (2048, 768, True, "bfloat16")),
}


@pytest.mark.parametrize("name", list(CONFIGURATIONS))
def test_only_longcats_launch_walks_more_than_one_chunk(name):
    """``_ffn_chunks`` at each configuration's (H, I, gated, dtype), the
    tile of a decode step and of a 1024-token chunk: an expert's matrices
    double buffered are 18-41 MB against the kernel's 64 MiB of VMEM
    everywhere but at H 6144 x I 2048 (151 MB: chunks of 512 columns).
    So ``longcat_flash_omni`` alone launches the maps that read
    ``live_tiles``, and ``expert_chunks`` says so to the engine's
    records."""
    from flashmoe_tpu.ops import expert as exp

    preset, widths = CONFIGURATIONS[name]
    cfg = PRESETS[preset]()
    h, i, gated, dtype = widths
    assert (cfg.hidden_size, cfg.intermediate_size + cfg.intermediate_pad,
            cfg.gated_ffn, jnp.dtype(cfg.dtype).name) == widths
    w = jax.ShapeDtypeStruct((1, h, i), cfg.dtype)
    for s in (64, 1024):
        bm = moe.rows_block_m(cfg, s)
        bi, _ = exp._ffn_chunks(jax.ShapeDtypeStruct((bm, h), cfg.dtype), w,
                                w if gated else None, bm, i, gated)
        assert (bi < i) == (name == "longcat_flash_omni")
        assert moe.expert_chunks(cfg, s) == i // bi == (
            4 if name == "longcat_flash_omni" else 1)


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


# ----------------------------------------------------------------------
# The launches ISSUE 48 must not move are the PARENT's: their programs
# with every kernel's index maps, pinned from a copy of the parent commit
# ----------------------------------------------------------------------

def _program_text(jaxpr) -> str:
    """A jaxpr's text and, behind it, the index maps of every
    ``pallas_call`` under it in the order met (the printed jaxpr leaves
    them out: a changed map would change WHAT A LAUNCH FETCHES and no
    letter of the text)."""
    maps = []

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                maps.extend(str(bm.index_map_jaxpr) for bm in
                            eqn.params["grid_mapping"].block_mappings)
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(jaxpr.jaxpr)
    return "\n".join([str(jaxpr), *maps])


def _digest(fn, *args):
    text = _program_text(jax.make_jaxpr(fn)(*args))
    text = re.sub(r" at [^\s]+\.py:\d+", "", text)
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    return hashlib.sha256(text.encode()).hexdigest(), text


def _parents_maps(exp):
    """``grouped_ffn`` with every tile's chunk ``j``, the parent's walk
    (on the parent's tree: the function itself)."""
    def launch(*a, **kw):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(exp, "_chunk_under_live",
                       lambda nj: lambda ti, j, *scalars: j, raising=False)
            return exp.grouped_ffn.__wrapped__(*a, **kw)
    return launch


def single_chunk_digests():
    """Digests (and texts) of what every configuration but LongCat's
    runs through ``ops/expert.py``, on this tree (run from a copy of the
    parent commit to make the pins: ``PYTHONPATH=. python
    <this file>``): the decode step and a prefill chunk of a K/V toy
    whose mixtures take the routed-rows kernel (traced as on a TPU, H and
    I whole lanes: ONE chunk, ``live_tiles`` given), and the launches
    that share ``_up_specs`` and carry no ``live_tiles``: the training
    forward and its residual-saving twin in two chunks, the gather-fused
    kernel."""
    from flashmoe_tpu.models.transformer import init_params
    from flashmoe_tpu.ops import expert as exp
    from flashmoe_tpu.serving import engine as eng
    from flashmoe_tpu.serving.kvcache import init_paged_cache

    cfg = MoEConfig(
        num_experts=8, expert_top_k=2, hidden_size=128,
        intermediate_size=256, num_layers=2, vocab_size=300, num_heads=2,
        num_kv_heads=1, head_dim=128, gated_ffn=True, drop_tokens=False,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, sequence_len=128)
    shape = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: init_paged_cache(cfg, 32, 16, 4))
    i32 = lambda *s: shape(s, jnp.int32)
    bf = lambda *s: shape(s, jnp.bfloat16)
    f32 = lambda *s: shape(s, jnp.float32)
    ffn = (bf(4, 128, 512), f32(4, 512), bf(4, 512, 128), f32(4, 128),
           bf(4, 128, 512))
    kw = dict(act_name="silu", gated=True, block_m=16, block_i=256)
    with pytest.MonkeyPatch.context() as mp:        # traced as on a TPU
        mp.setattr(jax, "default_backend", lambda: "tpu")
        jax.clear_caches()
        try:
            assert moe.expert_arm(cfg, 4) == "routed_kernel"
            return {
                "_paged_decode_step": _digest(
                    lambda p, c, *a: eng._paged_decode_step(
                        p, cfg, c, *a, pad_token=0),
                    params, cache, i32(4), i32(4, 8), i32(4)),
                "_prefill_chunk": _digest(
                    lambda p, c, *a: eng._prefill_chunk(p, cfg, c, *a),
                    params, cache, i32(1, 128), i32(8), i32(8), i32(),
                    i32(), i32()),
                "grouped_ffn_two_chunks_no_live": _digest(
                    lambda x, gid, *w: exp.grouped_ffn(x, gid, *w, **kw),
                    bf(64, 128), i32(4), *ffn),
                # the one launch that did change, held to the parent's
                # maps: the digest reads the maps (the test below)
                "grouped_ffn_two_chunks_live_parents_maps": _digest(
                    lambda x, gid, lv, *w: _parents_maps(exp)(
                        x, gid, *w, lv, **kw),
                    bf(64, 128), i32(4), i32(1), *ffn),
                "grouped_ffn_ad_grad": _digest(
                    lambda x, gid, *w: jax.grad(
                        lambda x, *w: exp.grouped_ffn_ad(
                            x, gid, *w, "silu", True, 16, 256, False)
                        .astype(jnp.float32).sum(), argnums=(0, 1, 3, 5))(
                            x, *w),
                    bf(64, 128), i32(4), *ffn),
                "grouped_ffn_tokens": _digest(
                    lambda x, tok, gid, *w: exp.grouped_ffn_tokens(
                        x, tok, gid, *w, **kw),
                    bf(40, 128), i32(64), i32(4), *ffn),
            }
        finally:
            jax.clear_caches()


#: as the PARENT commit (2f68ddd) traces them (``_prefill_chunk`` re-pinned
#: by ISSUE 50: its context gathers index the whole K/V pool by (layer,
#: page); every launch and index map in it is the parent's)
PARENT_PROGRAMS = {
    "_paged_decode_step":
        "83e3655c7751517b313252a72b709553e737d2f14130ddcc9d119fa3f176ba15",
    "_prefill_chunk":
        "b57b2390baa5e100ab7b34345e67515df67a0de2175ecccb3253c402a60e6680",
    "grouped_ffn_two_chunks_no_live":
        "c46c604854d6641c06ef676b4cede487a4e20d9247a0fb8c7be416b44d5e9e55",
    "grouped_ffn_two_chunks_live_parents_maps":
        "e76dd1b2ad0c8b3eb25189ca10b5082a784ed28d2935ecf3b97a25bd06c7583b",
    "grouped_ffn_ad_grad":
        "3ba35053daca0033b3f447a9e22f7bd183b5d9debc49b64955cf8c36b1dfc2e7",
    "grouped_ffn_tokens":
        "d629091804b0592b5eae321d2d96433bb732045b6b9791d3df174f1b6a42f8d1",
}


@pytest.fixture(scope="module")
def programs():
    return single_chunk_digests()


@pytest.mark.parametrize("program", sorted(PARENT_PROGRAMS))
def test_single_chunk_launches_are_the_parents(programs, program):
    """ISSUE 48 changed what a launch with ``live_tiles`` AND more than
    one chunk fetches, and nothing else: these programs, index maps
    included, are the parent's letter for letter."""
    digest, text = programs[program]
    assert digest == PARENT_PROGRAMS[program]
    # (a jitted function met twice is printed once)
    kernels = set(re.findall(r"name=(fm_\w+)", text))
    assert kernels == {
        "_paged_decode_step": {"fm_paged_decode", "fm_ffn_fwd"},
        "_prefill_chunk": {"fm_ffn_fwd"},
        "grouped_ffn_two_chunks_no_live": {"fm_ffn_fwd"},
        "grouped_ffn_two_chunks_live_parents_maps": {"fm_ffn_fwd"},
        "grouped_ffn_ad_grad": {"fm_ffn_fwd_res", "fm_gmm", "fm_tgmm"},
        "grouped_ffn_tokens": {"fm_ffn_fwd_gather"}}[program]


def test_the_digest_reads_the_index_maps(programs):
    """The launch with ``live_tiles`` in two chunks, as this tree traces
    it, is NOT the parent's (whose digest the same launch gives with the
    dead tiles' chunk put back to ``j``): its printed jaxpr is, letter
    for letter, and its maps are not."""
    from flashmoe_tpu.ops import expert as exp

    shape = jax.ShapeDtypeStruct
    args = (shape((64, 128), jnp.bfloat16), shape((4,), jnp.int32),
            shape((1,), jnp.int32),
            shape((4, 128, 512), jnp.bfloat16), shape((4, 512), jnp.float32),
            shape((4, 512, 128), jnp.bfloat16), shape((4, 128), jnp.float32),
            shape((4, 128, 512), jnp.bfloat16))
    launch = lambda x, gid, lv, *w: exp.grouped_ffn.__wrapped__(
        x, gid, *w, lv, act_name="silu", gated=True, block_m=16, block_i=256)
    digest, text = _digest(launch, *args)
    parents, parents_text = programs[
        "grouped_ffn_two_chunks_live_parents_maps"]
    assert digest != parents
    jaxpr = lambda t: t.split("{ lambda ; a:i32[] b:i32[]")[0]
    assert jaxpr(text) == jaxpr(parents_text) != text


if __name__ == "__main__":
    for name, (digest, _) in single_chunk_digests().items():
        print(f'    "{name}":\n        "{digest}",')
