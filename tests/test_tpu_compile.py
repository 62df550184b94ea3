"""The chip's own compiler on the main path at real widths — no chip needed.

libtpu compiles for a v5e that is described, not attached (topology
``v5e:2x2``, device kind "TPU v5 lite"), and refuses what the chip would
refuse: a slice off the (8, 128) tiling, a kernel over its VMEM limit, a
program over 16 GB.  Interpret mode shows none of that.  Nothing runs, so
these say nothing about results or times — ``chip_smoke.py`` does, on the
chip.

All of these live in this ONE file, and the topology is described inside a
module-scoped fixture: only the worker that is handed this file loads the
TPU's library (one process at a time may hold it).  Shapes come from
``jax.eval_shape``; the persistent compile cache is off around them (an
entry written without a chip cannot be read back and would only warn).
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

import flashmoe_tpu as fm
from flashmoe_tpu.config import BENCH_CONFIGS
from flashmoe_tpu.models.reference import init_moe_params


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu here: nothing to ask
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _layer_shapes(cfg, params_sharding, x_sharding):
    """(params, x) of one MoE layer as shapes: ``params_sharding`` maps a
    parameter's name to its sharding."""
    p = jax.eval_shape(lambda: init_moe_params(jax.random.PRNGKey(0), cfg))
    p = {k: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                 sharding=params_sharding(k))
         for k, a in p.items()}
    x = jax.ShapeDtypeStruct((cfg.tokens, cfg.hidden_size), cfg.dtype,
                             sharding=x_sharding)
    return p, x


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, compiled.as_text()


@pytest.mark.parametrize("kernel", ["router_pallas", "router_pallas_tiled"])
@pytest.mark.parametrize("name", ["reference", "deepseek"])
def test_gate_kernels_compile(one_chip, name, kernel):
    from flashmoe_tpu.ops import gate

    cfg = BENCH_CONFIGS[name].replace(ep=1)
    x = jax.ShapeDtypeStruct((cfg.tokens, cfg.hidden_size), cfg.dtype,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((cfg.hidden_size, cfg.num_experts),
                             cfg.param_dtype, sharding=one_chip)
    _, text = _compile(
        lambda x, w: getattr(gate, kernel)(x, w, cfg).combine_weights, x, w)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "name", ["reference", "deepseek", "mixtral", "token_scaling"])
def test_moe_layer_forward_compiles(one_chip, name):
    """deepseek, mixtral and token_scaling are the widths whose grouped
    FFN asked for more than Mosaic's 16 MiB of scoped VMEM before PR 22."""
    cfg = BENCH_CONFIGS[name].replace(ep=1)
    p, x = _layer_shapes(cfg, lambda k: one_chip, one_chip)
    _, text = _compile(
        lambda p, x: fm.moe_layer(p, x, cfg, use_pallas=True).out, p, x)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("name", ["reference", "mixtral"])
def test_moe_layer_forward_and_grad_compiles(one_chip, name):
    """The Pallas backward kernels (``grouped_matmul`` / ``tgmm``);
    Mixtral's I=14336 is the width that forces ``grouped_matmul`` to chunk
    its N axis."""
    cfg = BENCH_CONFIGS[name].replace(ep=1, is_training=True)
    p, x = _layer_shapes(cfg, lambda k: one_chip, one_chip)

    def loss(p, x):
        o = fm.moe_layer(p, x, cfg, use_pallas=True)
        return (o.out.astype(jnp.float32) ** 2).mean() + o.aux_loss

    _, text = _compile(jax.grad(loss), p, x)
    assert text.count("tpu_custom_call") >= 4  # forward, dX, dW up and down


def test_flash_attention_compiles_forward_and_grad(one_chip):
    from flashmoe_tpu.ops.attention import flash_attention

    q = jax.ShapeDtypeStruct((1, 16, 4096, 128), jnp.bfloat16,
                             sharding=one_chip)
    _, text = _compile(lambda q, k, v: flash_attention(q, k, v), q, q, q)
    assert [n.split(".")[0] for n, _ in _custom_call_names(text)] == [
        "fm_flash_fwd"]
    # the trainer differentiates through it: the forward kernel (it
    # writes the log-sum-exp) and the two backward kernels, and no
    # [T, T] array of scores or probabilities anywhere, f32[1,16,4096,4096]
    # among them (before PR 43 the backward recomputed through
    # attention_xla; before PR 22 pallas_call's JVP rule raised an
    # AssertionError)
    _, text = _compile(jax.grad(
        lambda q, k, v: flash_attention(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)), q, q, q)
    assert sorted(n.split(".")[0] for n, _ in _custom_call_names(text)) == [
        "fm_flash_bwd_dkv", "fm_flash_bwd_dq", "fm_flash_fwd"]
    assert not re.search(r"\[(\d+,)*4096,4096\]", text)


@pytest.mark.parametrize("heads,kv_heads,widths,t,s", [
    (64, 64, (128, 64), 1024, 7168),     # a shortcut chunk, widest table
    (32, 32, (128, 64), 256, 256),       # a short latent prompt
    (32, 2, (128,), 1024, 4608),         # 16 query heads a K/V head
    (32, 8, (64,), 1024, 5120),          # heads half a lane tile wide
    (16, 16, (128,), 2048, 2048),        # the backlog's longest prompt
], ids=["mla64_chunk", "mla32_prompt", "gqa_2_of_32", "heads_of_64",
        "mha16_prompt"])
def test_flash_span_compiles_at_the_serving_shapes(one_chip, heads,
                                                   kv_heads, widths, t, s):
    """Mosaic takes ``fm_flash_span`` at the prefill programs' shapes with
    the first query's position a scalar operand the index maps read: MLA's
    keys in two parts, the 64-wide rotary one a single array for all
    heads (a block as wide as the array, half a lane tile); K/V heads
    fewer than query heads; 64-wide heads; within Mosaic's default scope
    at the rule's tile."""
    from flashmoe_tpu.ops.attention import flash_span_attention

    arr = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                              sharding=one_chip)
    mla = len(widths) == 2
    q = tuple(arr(1, heads, t, w) for w in widths)
    k = tuple(arr(1, 1 if mla and i else kv_heads, s, w)
              for i, w in enumerate(widths))
    v = arr(1, kv_heads, s, 128 if mla else widths[0])
    pos = jax.ShapeDtypeStruct((1,), np.int32, sharding=one_chip)
    compiled, text = _compile(
        lambda q, k, v, pos: flash_span_attention(
            q, k, v, pos, scale=sum(widths) ** -0.5), q, k, v, pos)
    assert [n.split(".")[0] for n, _ in _custom_call_names(text)] == [
        "fm_flash_span"]
    assert '"size":"16777216"' in text           # the default 16 MiB scope
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.fixture(scope="module")
def ep4(topo):
    """reference config over a Mesh of the four described chips."""
    from flashmoe_tpu.parallel.mesh import make_mesh

    cfg = BENCH_CONFIGS["reference"].replace(ep=4)
    mesh = make_mesh(cfg, dp=1, devices=topo.devices)
    p, x = _layer_shapes(
        cfg,
        lambda k: NamedSharding(mesh, P() if k == "gate_w" else P("ep")),
        NamedSharding(mesh, P("ep", None)))
    return cfg, mesh, p, x


def test_ep_moe_layer_compiles_on_four_chips(ep4):
    from flashmoe_tpu.parallel.ep import ep_moe_layer

    cfg, mesh, p, x = ep4
    compiled, text = _compile(
        lambda p, x: ep_moe_layer(p, x, cfg, mesh, use_pallas=True).out,
        p, x)
    assert "tpu_custom_call" in text
    assert text.count("all-to-all(") == 2  # dispatch and combine
    # each chip holds its 16 experts' weights, not all 64
    per_chip = compiled.memory_analysis().argument_size_in_bytes
    whole = sum(np.prod(a.shape) * a.dtype.itemsize for a in p.values())
    assert per_chip < whole / 3


def test_fused_ep_moe_layer_compiles_on_four_chips(ep4):
    """The in-kernel RDMA path (the paper's kernel).  Before PR 22 Mosaic
    refused its one-row bias DMA (``pl.ds(e, 1)`` of a [16, 2048] ref)."""
    from flashmoe_tpu.parallel.fused import fused_ep_moe_layer

    cfg, mesh, p, x = ep4
    _, text = _compile(
        lambda p, x: fused_ep_moe_layer(p, x, cfg, mesh, interpret=False,
                                        use_pallas_gate=True).out, p, x)
    assert "tpu_custom_call" in text


@pytest.mark.xfail(strict=True, raises=Exception, reason=(
    "Mosaic refuses the gather-fused FFN's one-row DMAs "
    "(ops/expert.py _ffn_gather_kernel, x_ref.at[pl.ds(tok, 1), :]): "
    "'Slice shape along dimension 0 must be aligned to tiling (8), but "
    "is 1.'  Both ends of the copy are tiled; a repair needs another "
    "layout for the token rows, not a one-line change (ROADMAP S6)."))
def test_gather_fused_ffn_is_still_refused(one_chip):
    cfg = BENCH_CONFIGS["tiny"].replace(gather_fused=True)
    p, x = _layer_shapes(cfg, lambda k: one_chip, one_chip)
    _compile(lambda p, x: fm.moe_layer(p, x, cfg, use_pallas=True).out, p, x)


@pytest.mark.xfail(strict=True, raises=ValueError, reason=(
    "fused_ep_moe_layer at deepseek widths: 'intermediate 1408 not "
    "divisible by 512' (parallel/fused.py _resolve_tiles takes "
    "min(bi_cap, I), not a divisor of I) — raised before any lowering; "
    "the planner's golden tables price that same geometry (ROADMAP S6)."))
def test_fused_ep_moe_layer_deepseek_is_still_refused(topo):
    from flashmoe_tpu.parallel.fused import fused_ep_moe_layer
    from flashmoe_tpu.parallel.mesh import make_mesh

    cfg = BENCH_CONFIGS["deepseek"].replace(ep=4)
    mesh = make_mesh(cfg, dp=1, devices=topo.devices)
    p, x = _layer_shapes(
        cfg,
        lambda k: NamedSharding(
            mesh, P() if k == "gate_w" or k.startswith("shared")
            else P("ep")),
        NamedSharding(mesh, P("ep", None)))
    _compile(lambda p, x: fused_ep_moe_layer(
        p, x, cfg, mesh, interpret=False, use_pallas_gate=True).out, p, x)


@pytest.fixture(scope="module")
def train_step_compiled(one_chip, topo):
    """The step ``chip_smoke.py``'s train phase runs, compiled ONCE for
    the tests below: flashmoe-reference widths, batch 2 x 4096, f32 state
    with Adam moments.  Steered to the chip's branches here in the test:
    ``jax.default_backend()`` still says "cpu" while compiling for a
    described device."""
    import chip_smoke
    from flashmoe_tpu.models.presets import PRESETS
    from flashmoe_tpu.parallel.mesh import make_mesh
    from flashmoe_tpu.runtime.trainer import (
        init_state, make_optimizer, make_train_step,
    )

    cfg = PRESETS["flashmoe-reference"](
        sequence_len=chip_smoke.TRAIN_SEQ, is_training=True)
    mesh = make_mesh(cfg, devices=[topo.devices[0]])
    opt = make_optimizer(cfg, total_steps=3)
    state = jax.eval_shape(
        lambda: init_state(jax.random.PRNGKey(0), cfg, opt))
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        state)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (chip_smoke.TRAIN_BATCH, cfg.sequence_len + 1), jnp.int32,
        sharding=NamedSharding(mesh, P("dp", None)))}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        return make_train_step(cfg, mesh, opt).lower(
            state, batch).compile()


def test_train_step_compiles_at_chip_smoke_size(train_step_compiled):
    """Inside the 16 GB the chip's compiler counts, with the Pallas
    kernels in it."""
    m = train_step_compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total < 15.75 * 2**30
    assert train_step_compiled.as_text().count("tpu_custom_call") >= 4


def _custom_call_names(text):
    """(instruction name, op_name) of every Pallas kernel in a compiled
    program: the instruction name is what the chip's trace shows on its
    ``XLA Ops`` line (``%fm_tgmm.3 = ...``)."""
    import re

    out = []
    for line in text.splitlines():
        if "tpu_custom_call" not in line:
            continue
        name = re.search(r"%([\w.\-]+) = ", line)
        op = re.search(r'op_name="([^"]*)"', line)
        out.append((name.group(1) if name else "",
                    op.group(1) if op else ""))
    return out


def test_train_step_names_its_kernels(train_step_compiled):
    """Every Pallas kernel of the train step runs under its own
    ``fm_<kernel>`` instruction name — under ``jvp``, ``remat`` and
    ``custom_vjp`` alike — and inside the stage scope of its layer, so a
    reader over the trace finds it by a pattern that survives refactors
    (``benchmark/layer_metrics/expert_*_roofline.train.json``)."""
    calls = _custom_call_names(train_step_compiled.as_text())
    assert calls
    stray = [c for c in calls if not c[0].startswith("fm_")]
    assert not stray, stray
    families = {n.split(".")[0] for n, _ in calls}
    assert families == {"fm_ffn_fwd_res", "fm_gmm", "fm_tgmm",
                        "fm_flash_fwd", "fm_flash_bwd_dkv",
                        "fm_flash_bwd_dq", "fm_router"}, families
    for name, op in calls:
        assert "train.forward_backward" in op, (name, op)
        stage = ("moe.gate" if name.startswith("fm_router") else
                 None if name.startswith("fm_flash") else "moe.expert")
        assert stage is None or stage in op, (name, op)


@pytest.mark.parametrize("name", ["reference"])
def test_bare_layer_grad_names_its_kernels(one_chip, name):
    """The same holds for the layer differentiated on its own, with no
    trainer scope around it: the stage scopes inside ``moe_layer`` are
    what keeps a transform's name (``jvp(...)``) off the kernel's."""
    cfg = BENCH_CONFIGS[name].replace(ep=1, is_training=True)
    p, x = _layer_shapes(cfg, lambda k: one_chip, one_chip)

    def loss(p, x):
        o = fm.moe_layer(p, x, cfg, use_pallas=True)
        return (o.out.astype(jnp.float32) ** 2).mean() + o.aux_loss

    _, text = _compile(jax.grad(loss), p, x)
    calls = _custom_call_names(text)
    assert {n.split(".")[0] for n, _ in calls} >= {
        "fm_ffn_fwd_res", "fm_gmm", "fm_tgmm"}, calls
    assert all(n.startswith("fm_") for n, _ in calls), calls


def _compile_sampler(one_chip, b, v):
    from flashmoe_tpu.serving import engine as eng

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return eng._sample_dynamic.lower(
        arg((b, v), jnp.float32), arg((b,), jnp.uint32),
        arg((b,), jnp.int32), arg((b,), jnp.float32),
        arg((b,), jnp.int32), arg((b,), jnp.float32)).compile()


def test_sampler_program_derives_its_keys_on_the_chip(one_chip):
    """The serving sampler at the backlog cell's size (32 slots, the
    deepseek vocabulary): the chip's compiler takes the key derivation
    (a ``vmap`` of ``PRNGKey`` + ``fold_in`` over uint32 seeds) in the
    sampler's own program and returns 32 tokens."""
    b, v = 32, 102400
    compiled = _compile_sampler(one_chip, b, v)
    (out,) = jax.tree.leaves(compiled.out_info)
    assert out.shape == (b,) and out.dtype == jnp.int32
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


@pytest.mark.parametrize("v", [102400, 129280],
                         ids=["dsmoe16b", "joyai_flash"])
def test_sampler_program_keeps_its_sort_behind_a_conditional(one_chip, v):
    """At both serving cells' sizes the chip's compiler leaves the
    sampler's branches as ``conditional``s (it does not flatten them
    into selects that would run every arm) and the program holds ONE
    sort of the vocabulary, inside a branch."""
    text = _compile_sampler(one_chip, 32, v).as_text()
    assert len(re.findall(r" conditional\(", text)) == 2, "flattened"
    sorts = re.findall(r"^.* sort\(.*$", text, re.M)
    assert len(sorts) == 1, sorts
    assert f"f32[32,{v}]" in sorts[0] and "/cond/branch_1_fun" in sorts[0]
    entry = text[text.index("\nENTRY "):]
    assert " sort(" not in entry and " conditional(" in entry


@pytest.fixture(scope="module")
def mla_programs(one_chip):
    """The benchmark cell's largest decode, verify and prefill-chunk
    programs (``joyai_flash``: the leading dense layer + 4 mixture layers
    in bf16, 32 slots, a 16384 x 16-token latent pool, tables at their 448
    pages, a span of 5, a 1024-token chunk), lowered as the engine runs
    them on the chip: the pool donated, and traced as on a TPU (the
    attention picks its arm from the backend, and nothing is attached
    here)."""
    from flashmoe_tpu.models.presets import PRESETS
    from flashmoe_tpu.models.transformer import init_params
    from flashmoe_tpu.serving import engine as eng
    from flashmoe_tpu.serving.kvcache import init_paged_cache

    cfg = PRESETS["joyai-llm-flash"](num_layers=5, param_dtype=jnp.bfloat16)
    on = lambda t: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    params = on(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    cache = on(jax.eval_shape(lambda: init_paged_cache(cfg, 16384, 16)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, np.int32, sharding=one_chip)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        return {
            "decode": eng._INPLACE["_paged_decode_step"].lower(
                params, cfg, cache, i32(32), i32(32, 448), i32(32)),
            "verify": eng._INPLACE["_paged_verify_step"].lower(
                params, cfg, cache, i32(32, 5), i32(32, 448), i32(32)),
            "chunk": eng._INPLACE["_prefill_chunk"].lower(
                params, cfg, cache, i32(1, 1024), i32(448), i32(64), i32(),
                i32())}


@pytest.fixture(scope="module")
def mla_decode_compiled(mla_programs):
    compiled = mla_programs["decode"].compile()
    return compiled, compiled.as_text()


#: a whole latent pool of either MLA cell, as the programs hold it (the
#: kernel's operand has a unit axis of heads) and in any layout
_LATENT_POOL = r"bf16\[(?:5,16384|1,40960),(?:1,)?16,640\]"


def _fm_kernels(text):
    """The repo's own kernels in a compiled program, by name (XLA's
    grouped matmul is a custom call too)."""
    return [n.split(".")[0] for n, _ in _custom_call_names(text)
            if n.startswith("fm_")]


def _score_arrays(text, heads, span, ctx):
    """Arrays of a compiled program shaped as the scores of a span over
    its context, ``[heads, span, ctx]`` in either float type (what the
    plain XLA attention of a long span wrote and read three times before
    ISSUE 44; ``fm_flash_span`` keeps a tile of them in VMEM)."""
    return (_arrays_of(text, heads, span, ctx)
            + _arrays_of(text, 1, heads, span, ctx))


def _no_stacked_gate_up(text, e, h, i):
    """No array of a layer's gate + up weights side by side ([E, H, 2I]:
    what the grouped kernel's gated form concatenated on every call before
    ISSUE 36) in a compiled program."""
    return _arrays_of(text, e, h, 2 * i) == []


def _latent_pool_copies(text):
    return re.findall(rf"^.*= {_LATENT_POOL}\S* copy\(.*$", text, re.M)


def test_mla_prefill_chunk_fits_and_computes_the_routed_rows(mla_programs):
    """A 1024-token chunk at the widest context: the experts are ONE
    launch of the grouped Pallas kernel a mixture layer over the 8192
    routed rows in 64-row tiles (``fm_ffn_fwd``: four in the program, no
    ``ragged_dot``, no [8192, 768] intermediate in HBM, no
    [256, 2048, 1536] gate | up array, no [256, 1024, .] capacity buffer),
    and under 14.5 GB (13.78 as compiled; 13.80 with XLA's grouped matmul;
    the E x S arm took 16.16; 12.94 since ISSUE 44).  A chunk is no short
    span: its attention keeps the gather arm (whole pages scattered, the
    slot's pages gathered) and copies no pool; since ISSUE 44 the
    gathered context is scored blockwise, one ``fm_flash_span`` call a
    latent layer (FIVE, the decompressed keys and values its operands),
    and no ``[32, 1024, 7168]`` array of scores exists."""
    compiled = mla_programs["chunk"].compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 12.6e9 < total < 13.2e9
    text = compiled.as_text()
    assert "ragged-dot" not in text
    assert "[8192,768]" not in text and "[256,1024," not in text
    assert _no_stacked_gate_up(text, 256, 2048, 768)
    assert _fm_kernels(text) == ["fm_flash_span"] + [
        "fm_flash_span", "fm_ffn_fwd"] * 4 and " scatter(" in text
    assert _score_arrays(text, 32, 1024, 7168) == []
    assert "moe.expert/" in text
    assert "bf16[5,16384,16,640]{3,2,1,0" in text
    assert _latent_pool_copies(text) == []
    assert "attn.mla_prefill" in text and "attn.mla_decode" not in text


def test_mla_decode_step_fits_the_chip_in_place(mla_decode_compiled):
    compiled, text = mla_decode_compiled
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    # 11.12 GB of weights + the 1.68 GB pool, once (donated: aliased to
    # the output), + 0.02 GB of temporaries: 12.83 GB as compiled (13.225
    # with a gathered context a layer)
    assert m.alias_size_in_bytes >= 5 * 16384 * 16 * 640 * 2
    assert 12.6e9 < total < 13.0e9
    # the pool arrives and leaves row-major as declared, a page one
    # contiguous block of tiles: no whole-pool copy
    assert "bf16[5,16384,16,640]{3,2,1,0" in text
    assert _latent_pool_copies(text) == []


@pytest.mark.parametrize("program", ["decode", "verify"])
def test_mla_decode_step_reads_latent_rows_only(mla_programs,
                                                mla_decode_compiled,
                                                program):
    """No K or V of the whole context ([.., 32 heads, 7168, 128] in any
    order) in the program the chip runs: the absorbed form.  And no
    context at all: the decode step (T = 1) and the verify step (T = 5)
    read each slot's latent pages in place, Mosaic compiles
    ``fm_latent_decode`` at the cell's shapes (a 57 kB table as scalars,
    blocks of 32 pages), the ONE pool goes through every layer's call,
    and no array has the gathered context's element count in either
    row width."""
    text = (mla_decode_compiled[1] if program == "decode"
            else mla_programs[program].compile().as_text())
    shapes = set(re.findall(r"(?:bf16|f32)\[([0-9,]+)\]", text))
    big = [s for s in shapes
           if {"7168", "128"} <= set(s.split(","))
           and s.split(",").count("32") >= 2]
    assert big == []
    # a latent layer's attention, then (the mixture layers) its experts:
    # ONE launch of the grouped FFN kernel where three ragged_dot stood
    assert _fm_kernels(text) == ["fm_latent_decode"] + [
        "fm_latent_decode", "fm_ffn_fwd"] * 4
    assert "ragged-dot" not in text
    assert _no_stacked_gate_up(text, 256, 2048, 768)
    for width in (576, 640):
        assert _arrays_of(text, 32, 7168, width) == []
        assert _arrays_of(text, 32, 448, 16, width) == []
        assert _arrays_of(text, 14336, 16 * width) == []
    assert " scatter(" not in text                  # the kernel stores
    assert _latent_pool_copies(text) == []
    assert "attn.mla_decode" in text and "moe.gate" in text


def _program_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.fixture(scope="module")
def backlog_programs(one_chip):
    """The backlog cell's widest decode and verify programs, its
    1024-token chunk and its largest whole-prompt prefill (``dsmoe16b``:
    6 layers in bf16, 32 slots, a 2048 x 16-token K/V pool, tables at
    their 160 pages, a span of 5, a 2048-token pad), lowered as the engine
    runs them on the chip: the pool donated, and traced as on a TPU (the
    attention picks its arm from the backend, and nothing is attached
    here)."""
    from flashmoe_tpu.models.presets import PRESETS
    from flashmoe_tpu.models.transformer import init_params
    from flashmoe_tpu.serving import engine as eng
    from flashmoe_tpu.serving.kvcache import init_paged_cache

    cfg = PRESETS["deepseek-moe-16b"](num_layers=6,
                                      param_dtype=jnp.bfloat16)
    on = lambda t: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    params = on(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    cache = on(jax.eval_shape(lambda: init_paged_cache(cfg, 2048, 16)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, np.int32, sharding=one_chip)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        return {
            "decode": eng._INPLACE["_paged_decode_step"].lower(
                params, cfg, cache, i32(32), i32(32, 160), i32(32)),
            "verify": eng._INPLACE["_paged_verify_step"].lower(
                params, cfg, cache, i32(32, 5), i32(32, 160), i32(32)),
            "chunk": eng._INPLACE["_prefill_chunk"].lower(
                params, cfg, cache, i32(1, 1024), i32(160), i32(64), i32(),
                i32()),
            "prefill": eng._prefill_padded.lower(
                params, cfg, i32(1, 2048), i32())}


def _arrays_of(text, *dims):
    """Shapes of the bf16 / f32 arrays of a compiled program that have
    exactly ``dims``, in any order."""
    want = sorted(dims)
    return [s for s in set(re.findall(r"(?:bf16|f32)\[([0-9,]+)\]", text))
            if sorted(int(n) for n in s.split(",")) == want]


@pytest.mark.parametrize("program", ["decode", "verify", "chunk"])
def test_backlog_decode_step_is_the_program_the_ledger_measured(
        backlog_programs, program):
    """What the K/V programs that take a pool compile to since ISSUE 30
    (it was 13.07 GB with the pool TWICE, the gathered contexts and FOUR
    copies of a whole pool in gather order): the pool once, aliased to
    the output, and NO copy of it.  The decode step (T = 1) and the
    verify step (T = 5) read each slot's pages in place: Mosaic compiles
    ``fm_paged_decode`` at the cell's shapes, a K and a V pool through
    every layer's call, and no array has the gathered context's element
    count; the 1024-token chunk keeps ``gather_ctx`` + ``kv_attend``
    over its one slot, the gathered context scored blockwise since ISSUE
    44 (``fm_flash_span``, one call a layer, no ``[16, 1024, 2560]``
    scores)."""
    compiled = backlog_programs[program].compile()
    text = compiled.as_text()
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 2 * 6 * 2048 * 16 * 16 * 128 * 2                  # 1.61 GB
    # 9.53 / 9.59 / 9.74 (the chunk 10.38 with E x S rows)
    assert 9.3e9 < _program_bytes(compiled) < 11e9
    assert not re.findall(
        r"^.*= bf16\[6,2048,16,16,128\]\S* copy\(.*$", text, re.M)
    assert "moe.gate" in text and "moe.expert" in text
    # ONE rule picks the experts' arm (``ops/moe.expert_arm``): since
    # ISSUE 36 the routed rows through the grouped Pallas kernel at every
    # span on a TPU (192, 960 and 6144 rows here): one ``fm_ffn_fwd`` a
    # layer, no ``ragged_dot``, no [64, capacity, .] dispatch buffer, no
    # [64, 2048, 2816] gate | up array
    assert "ragged-dot" not in text
    assert _no_stacked_gate_up(text, 64, 2048, 1408)
    for rows in (32, 160, 1024):                    # capacity(s) = s
        assert _arrays_of(text, 64, rows, 2048) == []
    kernels = _fm_kernels(text)
    assert [n for n in kernels if n == "fm_ffn_fwd"] == ["fm_ffn_fwd"] * 6
    kernels = [n for n in kernels if n != "fm_ffn_fwd"]
    if program == "chunk":
        assert kernels == ["fm_flash_span"] * 6
        assert _arrays_of(text, 16, 2560, 128)      # its gathered context
        assert _score_arrays(text, 16, 1024, 2560) == []
        return
    assert len(kernels) == 6, kernels
    assert all(n.split(".")[0] == "fm_paged_decode" for n in kernels)
    assert _arrays_of(text, 5120, 16, 16, 128) == []
    assert _arrays_of(text, 32, 16, 2560, 128) == []
    assert " scatter(" not in text                  # the kernel stores


def test_backlog_whole_prompt_prefill_fits_beside_the_pool(
        backlog_programs):
    """A 2048-token prompt at once: 8.33 GB as compiled (the weights,
    f32 scores of 16 heads over 2048 x 2048, the experts over the 12288
    routed rows in 256-row tiles; 9.76 GB with E x S rows before ISSUE
    33), which leaves
    the engine's pool its 1.61 GB; the program holds no pool and hands
    back one K and one V run for ``store_prefill``."""
    compiled = backlog_programs["prefill"].compile()
    assert abs(_program_bytes(compiled) / 8.3298e9 - 1) < 0.01
    text = compiled.as_text()
    assert [n for n in _fm_kernels(text)
            if n != "fm_ffn_fwd"] == ["fm_flash_span"] * 6
    assert _score_arrays(text, 16, 2048, 2048) == []
    logits, k_run, v_run = jax.tree.leaves(compiled.out_info)
    assert logits.shape == (102400,) and logits.dtype == jnp.float32
    assert k_run.shape == v_run.shape == (6, 16, 2048, 128)
    assert "[6,2048,16,16,128]" not in compiled.as_text()


@pytest.fixture(scope="module")
def hybrid_programs(one_chip):
    """The widest decode program and the 1024-token chunk of the cell
    ``ling3_flash.serve.longgen`` (the leading dense layer + one period,
    6 'kda' layers and 1 'mla', 128 of 512 experts held, a quarter of the
    vocabulary, bf16; 64 slots, a 40960 x 16-token latent pool of ONE
    layer, tables at their 640 pages), lowered as the engine runs them:
    the whole cache donated."""
    from flashmoe_tpu.models.presets import PRESETS
    from flashmoe_tpu.models.transformer import init_params
    from flashmoe_tpu.serving import engine as eng
    from flashmoe_tpu.serving.kvcache import init_paged_cache

    cfg = PRESETS["ling-3.0-flash"](
        num_layers=7, first_k_dense=1, layer_mixers=("kda",) * 6 + ("mla",),
        experts_held=128, vocab_size=39296, param_dtype=jnp.bfloat16)
    on = lambda t: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    params = on(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    cache = on(jax.eval_shape(lambda: init_paged_cache(cfg, 40960, 16, 64)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, np.int32, sharding=one_chip)
    with pytest.MonkeyPatch.context() as mp:        # traced as on a TPU
        mp.setattr(jax, "default_backend", lambda: "tpu")
        return {
            "decode": eng._INPLACE["_paged_decode_step"].lower(
                params, cfg, cache, i32(64), i32(64, 640), i32(64)),
            "chunk": eng._INPLACE["_prefill_chunk"].lower(
                params, cfg, cache, i32(1, 1024), i32(640), i32(64), i32(),
                i32(), i32())}


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_hybrid_programs_fit_the_chip_with_state_and_pool_in_place(
        hybrid_programs, program):
    """12.07 GB (decode; 13.57 with the one latent layer's gathered
    context) and 12.24 GB (chunk; 13.45 with float32 scores over the
    widest table) as compiled, under the cell's 15.0:
    10.34 GB of weights, and the latent pool (0.84 GB of 640-wide rows),
    the float32 state (0.805 GB) and the convolution's inputs once each,
    aliased to the outputs; no copy of the state or of the pool; the
    experts are the grouped Pallas kernel over the routed rows that fall
    on the 128 experts held; the decode program is one recurrence step a 'kda'
    layer, reads the latent layer's pages in place (ONE
    ``fm_latent_decode``, a 164 kB table as scalars, no gathered context)
    and hands back what it counted; the chunk keeps the gather arm, its
    one latent layer's context scored blockwise (ONE ``fm_flash_span``,
    no ``[32, 1024, 10240]`` scores: ISSUE 44)."""
    compiled = hybrid_programs[program].compile()
    text = compiled.as_text()
    cache_bytes = (40960 * 16 * 640 * 2 + 6 * 64 * 32 * 128 * 128 * 4
                   + 6 * 64 * 3 * 12288 * 2)
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes
    lo, hi = (11.8e9, 12.4e9) if program == "decode" else (12.0e9, 12.5e9)
    assert lo < _program_bytes(compiled) < hi
    for shape in (r"f32\[6,64,32,128,128\]", r"bf16\[1,40960,16,640\]",
                  r"bf16\[6,64,36864\]"):
        assert re.search(shape, text)
        assert not re.findall(rf"^.*= {shape}\S* copy\(.*$", text, re.M)
    assert _latent_pool_copies(text) == []
    assert "ragged-dot" not in text
    assert "[128,2560,768]" in text and "[512,2560,768]" not in text
    assert _no_stacked_gate_up(text, 128, 2560, 768)
    assert "moe.route_groups" in text
    kernels = _fm_kernels(text)
    # the six mixture layers' experts: ONE launch of the grouped FFN
    # kernel each, over the rows that fall on the 128 experts held
    assert [n for n in kernels if n == "fm_ffn_fwd"] == ["fm_ffn_fwd"] * 6
    kernels = [n for n in kernels if n != "fm_ffn_fwd"]
    if program == "decode":
        assert kernels == ["fm_latent_decode"]
        for width in (576, 640):
            assert _arrays_of(text, 64, 10240, width) == []
            assert _arrays_of(text, 64, 640, 16, width) == []
            assert _arrays_of(text, 40960, 16 * width) == []
        assert " scatter(" not in text
        assert "attn.kda_decode" in text and "attn.mla_decode" in text
        # logits, the cache's three arrays, experts_touched and held_rows
        assert len(jax.tree.leaves(compiled.out_info)) == 1 + 3 + 2
    else:
        assert kernels == ["fm_flash_span"]
        assert _score_arrays(text, 32, 1024, 10240) == []
        assert "attn.kda_prefill" in text and "attn.mla_prefill" in text


@pytest.fixture(scope="module")
def lfm2_programs(one_chip):
    """The widest decode program, the widest 1024-token chunk and the
    largest padded prefill of the cell ``lfm2_24b.serve.shortchat``
    (LFM2-24B-A2B: the leading dense layer + two periods, 7 'conv' layers
    and 2 attention layers of 8 K/V heads of 64, 64 experts and the whole
    vocabulary, bf16; 128 slots, a 20480 x 16-token K/V pool of TWO
    layers whose rows hold two heads, tables at their 320 pages), lowered
    as the engine runs them: the whole cache donated, traced as on a
    TPU."""
    from flashmoe_tpu.models.presets import PRESETS
    from flashmoe_tpu.models.transformer import init_params
    from flashmoe_tpu.serving import engine as eng
    from flashmoe_tpu.serving.kvcache import init_paged_cache

    cfg = PRESETS["lfm2-24b-a2b"](
        num_layers=9, first_k_dense=1, param_dtype=jnp.bfloat16,
        layer_mixers=("conv", "mha") + ("conv",) * 3 + ("mha",)
        + ("conv",) * 3)
    on = lambda t: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    params = on(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    cache = on(jax.eval_shape(lambda: init_paged_cache(cfg, 20480, 16, 128)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, np.int32, sharding=one_chip)
    with pytest.MonkeyPatch.context() as mp:        # traced as on a TPU
        mp.setattr(jax, "default_backend", lambda: "tpu")
        return {
            "decode": eng._INPLACE["_paged_decode_step"].lower(
                params, cfg, cache, i32(128), i32(128, 320), i32(128)),
            "chunk": eng._INPLACE["_prefill_chunk"].lower(
                params, cfg, cache, i32(1, 1024), i32(320), i32(64), i32(),
                i32(), i32()),
            "prefill": eng._prefill_padded.lower(
                params, cfg, i32(1, 1024), i32())}


@pytest.mark.parametrize("program", ["decode", "chunk", "prefill"])
def test_lfm2_programs_fit_the_chip_with_inputs_and_pool_in_place(
        lfm2_programs, program):
    """12.03 GB (decode), 12.35 GB (chunk; 13.38 with float32 scores over
    the widest table) and 10.83 GB (a 1024-token prompt at once) as
    compiled, under the cell's 15.0: 10.63 GB of
    weights (the tied head a second array), and the K/V pool (1.34 GB:
    4 096 B a token, the 8 heads of 64 stored as 4 rows of 128 lanes, no
    padding) and the convolutions' inputs (7 MB) once each, aliased to
    the outputs; no copy of either; the experts by ``ops/moe.expert_arm``
    (below); the decode program is
    one step a 'conv' layer and reads the two attention layers' pages in
    place: Mosaic takes ``fm_paged_decode`` handed the packed rows, TWO
    calls, no gathered context; the chunk keeps the gather arm, and it
    and the whole prompt score their context blockwise since ISSUE 44
    (``fm_flash_span`` over 64-wide heads, a query head reading its K/V
    head of 8: TWO calls, no ``[32, 1024, .]`` scores)."""
    compiled = lfm2_programs[program].compile()
    text = compiled.as_text()
    pool, inputs = r"bf16\[2,20480,4,16,128\]", r"bf16\[7,128,4096\]"
    lo, hi = {"decode": (11.8e9, 12.3e9), "chunk": (12.1e9, 12.6e9),
              "prefill": (10.6e9, 11.1e9)}[program]
    assert lo < _program_bytes(compiled) < hi
    # the experts by ``ops/moe.expert_arm``: since ISSUE 36 the routed
    # rows through the grouped Pallas kernel at every span on a TPU (512
    # rows of a decode step in 16-row tiles, 4096 of 1024 tokens in
    # 128-row tiles): one ``fm_ffn_fwd`` a mixture layer, no
    # ``ragged_dot``, no [64, 128, .] dispatch buffer
    assert "ragged-dot" not in text
    assert "[64,2048,1536]" in text
    assert _no_stacked_gate_up(text, 64, 2048, 1536)
    assert _arrays_of(text, 64, 128, 2048) == []
    kernels = _fm_kernels(text)
    assert [n for n in kernels if n == "fm_ffn_fwd"] == ["fm_ffn_fwd"] * 8
    kernels = [n for n in kernels if n != "fm_ffn_fwd"]
    if program == "prefill":
        assert kernels == ["fm_flash_span"] * 2
        assert _score_arrays(text, 32, 1024, 1024) == []
        assert "attn.conv_prefill" in text
        assert len(jax.tree.leaves(compiled.out_info)) == 1 + 3
        return
    cache_bytes = 2 * 2 * 20480 * 4 * 16 * 128 * 2 + 7 * 128 * 4096 * 2
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes
    for shape in (pool, inputs):
        assert re.search(shape, text)
        assert not re.findall(rf"^.*= {shape}\S* copy\(.*$", text, re.M)
    # a pool of unpacked 64-wide heads would be padded to twice the bytes
    assert "[2,20480,8,16,64]" not in text
    if program == "decode":
        assert kernels == ["fm_paged_decode"] * 2
        assert _arrays_of(text, 128, 8, 5120, 64) == []   # no context
        assert _arrays_of(text, 128, 4, 5120, 128) == []
        assert " scatter(" not in text
        assert "attn.conv_decode" in text
        # logits, K and V pool, the inputs, experts_touched
        assert len(jax.tree.leaves(compiled.out_info)) == 1 + 3 + 1
    else:
        assert kernels == ["fm_flash_span"] * 2
        assert _score_arrays(text, 32, 1024, 5120) == []
        assert "attn.conv_prefill" in text


@pytest.fixture(scope="module")
def ssm_programs(one_chip):
    """The decode program, the widest 1024-token chunk and the largest
    padded prefill of the cell ``nemotron3_nano.serve.manyslot``
    (NVIDIA-Nemotron-3-Nano-30B-A3B: ``MEMEM*EMEMEM*``, 6 state-space
    layers, 5 mixture layers with 64 of 128 experts of width 1856 held, 2
    attention layers of 2 K/V heads, half the vocabulary, bf16; 256 slots
    of a float32 state [64, 64, 128] a layer, a 32768 x 16-token K/V pool
    of TWO layers, tables at their 288 pages), lowered as the engine runs
    them: the whole cache donated, traced as on a TPU."""
    from flashmoe_tpu.models.presets import PRESETS
    from flashmoe_tpu.models.transformer import init_params
    from flashmoe_tpu.serving import engine as eng
    from flashmoe_tpu.serving.kvcache import init_paged_cache

    cfg = PRESETS["nemotron-3-nano-30b-a3b"](
        pattern="MEMEM*EMEMEM*", experts_held=64, vocab_size=65536,
        param_dtype=jnp.bfloat16)
    on = lambda t: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    params = on(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    cache = on(jax.eval_shape(lambda: init_paged_cache(cfg, 32768, 16, 256)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, np.int32, sharding=one_chip)
    with pytest.MonkeyPatch.context() as mp:        # traced as on a TPU
        mp.setattr(jax, "default_backend", lambda: "tpu")
        return {
            "decode": eng._INPLACE["_paged_decode_step"].lower(
                params, cfg, cache, i32(256), i32(256, 288), i32(256)),
            "chunk": eng._INPLACE["_prefill_chunk"].lower(
                params, cfg, cache, i32(1, 1024), i32(288), i32(64), i32(),
                i32(), i32()),
            "prefill": eng._prefill_padded.lower(
                params, cfg, i32(1, 1024), i32())}


@pytest.mark.parametrize("program", ["decode", "chunk", "prefill"])
def test_ssm_programs_fit_the_chip_with_state_and_pool_in_place(
        ssm_programs, program):
    """12.55 GB (decode), 12.78 GB (chunk; 13.76 with float32 scores over
    the widest table) and 8.36 GB (a 1024-token prompt at once) as
    compiled, under the cell's 14.5: 8.08 GB of weights as
    stored, and the by-slot state (3.22 GB: 256 slots x 6 layers x 2.10 MB
    float32), the K/V pool (1.07 GB: 2048 B a token) and the convolutions'
    inputs (57 MB) once each, aliased to the outputs.  NO copy of the
    state or of the pool in any program, and none of an expert matrix: the
    experts of width 1856 (14.5 lanes) are STORED 1920 wide
    (``intermediate_pad``) and go through ``fm_ffn_fwd``, one launch a
    mixture layer, no ``ragged_dot``.  The decode program reads the two
    attention layers' pages in place (16 query heads a K/V head through
    ``fm_paged_decode``, TWO calls) and steps the state through
    ``fm_ssm_step``, SIX calls, each slot's [64, 64, 128] block read once
    and written once where it lies (in plain XLA the slice was read twice,
    and at 256 slots the compiler rematerialised the first layer's
    in-place update: PERF.md section 6).  The decode program alone moves
    the convolutions' inputs into a slots-minor layout and back (2 copies
    of 57 MB: XLA lays the [256, 10304] projection out batch-minor at 256
    rows and carries that to the array it is sliced beside; 0.2 GB of a
    step's 15 GB).  The chunk and the whole prompt score the two
    attention layers' context blockwise since ISSUE 44 (``fm_flash_span``,
    TWO calls, 16 query heads reading one K/V head's blocks: nothing
    repeated, no ``[32, 1024, .]`` scores)."""
    compiled = ssm_programs[program].compile()
    text = compiled.as_text()
    state, pool, inputs = (r"f32\[6,256,64,64,128\]",
                           r"bf16\[2,32768,2,16,128\]",
                           r"bf16\[6,256,18432\]")
    lo, hi = {"decode": (12.3e9, 12.8e9), "chunk": (12.5e9, 13.0e9),
              "prefill": (8.1e9, 8.6e9)}[program]
    assert lo < _program_bytes(compiled) < hi < 14.5e9
    assert "ragged-dot" not in text
    # the experts STORED at whole lanes (``intermediate_pad``): as
    # [64, 2688, 1856] the chip kept the array H-minor and copied 0.64 GB
    # of it into row-major order before every launch
    assert "[64,2688,1920]" in text and "[64,2688,1856]" not in text
    assert not re.findall(r"^.*= bf16\[64,(2688,1920|1920,2688)\]\S* "
                          r"copy\(.*$", text, re.M)
    kernels = _fm_kernels(text)
    assert [n for n in kernels if n == "fm_ffn_fwd"] == ["fm_ffn_fwd"] * 5
    kernels = [n for n in kernels if n != "fm_ffn_fwd"]
    if program == "prefill":
        assert kernels == ["fm_flash_span"] * 2
        assert _score_arrays(text, 32, 1024, 1024) == []
        assert "attn.ssm_prefill" in text
        # logits, K and V rows, the state and the inputs after the prompt
        assert len(jax.tree.leaves(compiled.out_info)) == 1 + 4
        return
    cache_bytes = (2 * 2 * 32768 * 2 * 16 * 128 * 2
                   + 6 * 256 * (64 * 64 * 128 * 4 + 18432 * 2))
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes
    copies = lambda shape: re.findall(rf"^.*= {shape}\S* copy\(.*$", text,
                                      re.M)
    for shape in (state, pool, inputs):
        assert re.search(shape, text)
    assert copies(state) == [] and copies(pool) == []
    if program == "decode":
        assert sorted(kernels) == ["fm_paged_decode"] * 2 + [
            "fm_ssm_step"] * 6
        assert ".remat" not in "".join(
            line for line in text.splitlines() if "f32[6,256,64" in line)
        assert len(copies(inputs)) <= 2
        assert _arrays_of(text, 256, 2, 4608, 128) == []   # no context
        assert "attn.ssm_decode" in text and "attn.ssm_prefill" not in text
        # logits, the cache's four arrays, experts_touched and held_rows
        assert len(jax.tree.leaves(compiled.out_info)) == 1 + 4 + 2
    else:
        assert kernels == ["fm_flash_span"] * 2 and copies(inputs) == []
        assert _score_arrays(text, 32, 1024, 4608) == []
        assert "attn.ssm_prefill" in text


@pytest.fixture(scope="module")
def shortcut_programs(one_chip):
    """The decode program, the widest 1024-token chunk and the largest
    padded prefill of the cell ``longcat_flash_omni.serve.avturns``
    (LongCat-Flash-Omni's language model: 4 published layers = 8
    latent-attention sublayers of 64 heads at width 6144, 8 dense FFNs of
    12288, 4 shortcut-connected mixtures behind a 768-wide router with 16
    of 512 FFN experts held and 256 identity experts, an eighth of the
    vocabulary, bf16; 64 slots, a 12288 x 16-token latent pool of EIGHT
    layers, tables at their 448 pages), lowered as the engine runs them:
    the pool donated, traced as on a TPU."""
    from flashmoe_tpu.models.presets import PRESETS
    from flashmoe_tpu.models.transformer import init_params
    from flashmoe_tpu.serving import engine as eng
    from flashmoe_tpu.serving.kvcache import init_paged_cache

    cfg = PRESETS["longcat-flash"](num_layers=8, experts_held=16,
                                   vocab_size=16384,
                                   param_dtype=jnp.bfloat16)
    on = lambda t: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    params = on(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    cache = on(jax.eval_shape(lambda: init_paged_cache(cfg, 12288, 16, 64)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, np.int32, sharding=one_chip)
    with pytest.MonkeyPatch.context() as mp:        # traced as on a TPU
        mp.setattr(jax, "default_backend", lambda: "tpu")
        return {
            "decode": eng._INPLACE["_paged_decode_step"].lower(
                params, cfg, cache, i32(64), i32(64, 448), i32(64)),
            "chunk": eng._INPLACE["_prefill_chunk"].lower(
                params, cfg, cache, i32(1, 1024), i32(448), i32(64), i32(),
                i32(), i32()),
            "prefill": eng._prefill_padded.lower(
                params, cfg, i32(1, 1024), i32())}


@pytest.mark.parametrize("program", ["decode", "chunk", "prefill"])
def test_shortcut_programs_fit_the_chip_with_the_pool_in_place(
        shortcut_programs, program):
    """12.40 GB (decode), 12.93 GB (chunk; 14.40 before ISSUE 44, 1.9 GB
    of it the float32 scores of 64 heads x 1024 queries x 7168 gathered
    rows) and 10.93 GB (a 1024-token prompt at once) as compiled, under
    the cell's 14.5: 10.35
    GB of weights and the latent pool (2.01 GB: 8 sublayers x 1280 B a
    token) once, aliased to the output.  NO copy of the pool.  The decode
    program attends through ``fm_latent_decode`` at 64 heads, EIGHT calls
    (a [64, 640] query block a slot against 512-row blocks of the slot's
    own pages), and every program runs its FOUR mixtures through
    ``fm_ffn_fwd``, one launch each inside the loop over the plan's
    windows, no ``ragged_dot``; the plan is laid out for the rows the 16
    experts held could expect four times over (64 of a decode step's 768
    routed rows, 1024 of a chunk's 12288: ``ops/moe.rows_plan``), so the
    kernel's row buffer is 304 / 1504 rows where the whole S x K would be
    1008 / 12768, and no tile is spent on a row of an identity expert or
    of an expert held elsewhere.  The chunk and the whole prompt score
    their context blockwise, EIGHT ``fm_flash_span`` calls (the
    decompressed 128-wide keys and values a head, the 64-wide rotary key
    ONE array for all 64), and hold no ``[64, 1024, .]`` scores."""
    compiled = shortcut_programs[program].compile()
    text = compiled.as_text()
    lo, hi = {"decode": (12.2e9, 12.6e9), "chunk": (12.7e9, 13.0e9),
              "prefill": (10.7e9, 11.2e9)}[program]
    assert lo < _program_bytes(compiled) < hi <= 14.5e9
    assert "ragged-dot" not in text
    kernels = _fm_kernels(text)
    assert [n for n in kernels if n == "fm_ffn_fwd"] == ["fm_ffn_fwd"] * 4
    rows = {"decode": 304, "chunk": 1504, "prefill": 1504}[program]
    assert len(re.findall(rf"%fm_ffn_fwd[.\d]* = bf16\[{rows},6144\]",
                          text)) == 4
    assert _arrays_of(text, 16, 6144, 4096) == []   # no gate | up array
    assert "moe.zero" in text and "moe.shortcut_join" in text
    kernels = [n for n in kernels if n != "fm_ffn_fwd"]
    if program == "prefill":
        assert kernels == ["fm_flash_span"] * 8
        assert _score_arrays(text, 64, 1024, 1024) == []
        # logits and the eight sublayers' latent rows
        assert len(jax.tree.leaves(compiled.out_info)) == 1 + 1
        return
    pool = r"bf16\[8,12288,(?:1,)?16,640\]"
    assert re.search(pool, text)
    assert re.findall(rf"^.*= {pool}\S* copy\(.*$", text, re.M) == []
    assert (compiled.memory_analysis().alias_size_in_bytes
            >= 8 * 12288 * 16 * 640 * 2)
    if program == "decode":
        assert kernels == ["fm_latent_decode"] * 8
        assert _arrays_of(text, 64, 7168, 640) == []    # no context
        assert "attn.mla_decode" in text
        # logits, the pool, experts_touched, held_rows and zero_rows
        assert len(jax.tree.leaves(compiled.out_info)) == 1 + 1 + 3
    else:
        assert kernels == ["fm_flash_span"] * 8
        assert _score_arrays(text, 64, 1024, 7168) == []
        assert "attn.mla_prefill" in text


@pytest.fixture(scope="module")
def sdar_programs(one_chip):
    """The widest denoise program and the widest 1024-token chunk of the
    cell ``sdar_30b_a3b.serve.blockgen`` (its largest padded prefill,
    10.24 GB with seven ``fm_flash_span``, was compiled once by PR 46's
    builder and is left out of the gate for its time)
    (SDAR-30B-A3B-Chat: 7 of 48 layers alike, 32 heads over 4 K/V heads
    of 128, 128 experts top-8 of 768 and the whole vocabulary, bf16; 64
    slots, an 8192 x 16-token K/V pool, tables at their 160 pages, blocks
    of 4 positions), lowered as the engine runs them: the pool donated,
    traced as on a TPU."""
    from flashmoe_tpu.models.presets import PRESETS
    from flashmoe_tpu.models.transformer import init_params
    from flashmoe_tpu.serving import engine as eng
    from flashmoe_tpu.serving.kvcache import init_paged_cache

    cfg = PRESETS["sdar-30b-a3b-chat"](num_layers=7,
                                       param_dtype=jnp.bfloat16)
    on = lambda t: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    params = on(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    cache = on(jax.eval_shape(lambda: init_paged_cache(cfg, 8192, 16, 64)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, np.int32, sharding=one_chip)
    with pytest.MonkeyPatch.context() as mp:        # traced as on a TPU
        mp.setattr(jax, "default_backend", lambda: "tpu")
        return {
            "denoise": eng._INPLACE["_paged_denoise_step"].lower(
                params, cfg, cache, i32(64, 3, 4), i32(64, 8), i32(64, 160),
                pad_token=0),
            "chunk": eng._INPLACE["_prefill_chunk"].lower(
                params, cfg, cache, i32(1, 1024), i32(160), i32(64), i32(),
                i32(), i32())}


@pytest.mark.parametrize("program", ["denoise", "chunk"])
def test_sdar_programs_fit_the_chip_with_the_pool_in_place(
        sdar_programs, program):
    """The cell's programs as the chip's compiler builds them, under its
    14.5 GB: 9.97 GB of weights and the K/V pool (1.88 GB: 14 336 B a
    token over 7 layers) once, aliased to the output; the denoise program
    reads every slot's pages in place (``fm_paged_decode`` at T = 4 under
    the block mask, a call a layer, no gathered context) and runs the
    routed rows of its 256-row span through ``fm_ffn_fwd``, a launch a
    layer; the chunk scores its context blockwise (``fm_flash_span`` with
    the block-causal diagonal)."""
    compiled = sdar_programs[program].compile()
    text = compiled.as_text()
    total = _program_bytes(compiled)
    print(program, total / 1e9)
    assert total < 14.5e9
    assert "ragged-dot" not in text
    kernels = _fm_kernels(text)
    assert [n for n in kernels if n == "fm_ffn_fwd"] == ["fm_ffn_fwd"] * 7
    kernels = [n for n in kernels if n != "fm_ffn_fwd"]
    pool = r"bf16\[7,8192,4,16,128\]"
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 2 * 7 * 8192 * 4 * 16 * 128 * 2
    assert re.search(pool, text)
    assert not re.findall(rf"^.*= {pool}\S* copy\(.*$", text, re.M)
    if program == "denoise":
        assert kernels == ["fm_paged_decode"] * 7
        assert _arrays_of(text, 64, 4, 2560, 128) == []   # no context
        assert " scatter(" not in text
        # the blocks' state, K and V pool, experts_touched
        assert len(jax.tree.leaves(compiled.out_info)) == 1 + 2 + 1
    else:
        assert kernels == ["fm_flash_span"] * 7
        assert _score_arrays(text, 32, 1024, 2560) == []
