"""The chip's own compiler on the kernels, the layers, the four-chip
programs and the train step at real widths — no chip needed.

``tests/test_tpu_compile.py`` says what the described chip is and where
every configuration's programs are compiled; the ``topo`` and ``one_chip``
fixtures are ``tests/_compiled.py``'s.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

import flashmoe_tpu as fm
from flashmoe_tpu.config import BENCH_CONFIGS
from flashmoe_tpu.models.reference import init_moe_params

from _compiled import one_chip, topo  # noqa: F401


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
