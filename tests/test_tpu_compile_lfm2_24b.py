"""The chip's own compiler on the programs of the ``lfm2_24b`` cell, K/V
pools of packed 64-wide heads beside a short-convolution state — no chip
needed.

``tests/test_tpu_compile.py`` says what the described chip is and where
every configuration's programs are compiled; the ``topo`` and ``one_chip``
fixtures and the readers of a compiled program are ``tests/_compiled.py``'s.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _compiled import (  # noqa: F401
    Programs, arrays_of, fm_kernels, layer_of_pool, no_stacked_gate_up,
    one_chip, program_bytes, score_arrays, topo,
)


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
        return Programs({
            "decode": eng._INPLACE["_paged_decode_step"].lower(
                params, cfg, cache, i32(128), i32(128, 320), i32(128)),
            "chunk": eng._INPLACE["_prefill_chunk"].lower(
                params, cfg, cache, i32(1, 1024), i32(320), i32(64), i32(),
                i32(), i32()),
            "prefill": eng._prefill_padded.lower(
                params, cfg, i32(1, 1024), i32())})


@pytest.mark.parametrize("program", ["decode", "chunk", "prefill"])
def test_lfm2_programs_fit_the_chip_with_inputs_and_pool_in_place(
        lfm2_programs, program):
    """12.03 GB (decode), 12.10 GB (chunk; 12.35 with a K and a V layer
    of the pool copied out before ISSUE 50, 13.38 with float32 scores over
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
    compiled = lfm2_programs.compiled(program)
    text = compiled.as_text()
    pool, inputs = r"bf16\[2,20480,4,16,128\]", r"bf16\[7,128,4096\]"
    lo, hi = {"decode": (11.8e9, 12.3e9), "chunk": (11.85e9, 12.35e9),
              "prefill": (10.6e9, 11.1e9)}[program]
    assert lo < program_bytes(compiled) < hi
    # the experts by ``ops/moe.expert_arm``: since ISSUE 36 the routed
    # rows through the grouped Pallas kernel at every span on a TPU (512
    # rows of a decode step in 16-row tiles, 4096 of 1024 tokens in
    # 128-row tiles): one ``fm_ffn_fwd`` a mixture layer, no
    # ``ragged_dot``, no [64, 128, .] dispatch buffer
    assert "ragged-dot" not in text
    assert "[64,2048,1536]" in text
    assert no_stacked_gate_up(text, 64, 2048, 1536)
    assert arrays_of(text, 64, 128, 2048) == []
    kernels = fm_kernels(text)
    assert [n for n in kernels if n == "fm_ffn_fwd"] == ["fm_ffn_fwd"] * 8
    kernels = [n for n in kernels if n != "fm_ffn_fwd"]
    if program == "prefill":
        assert kernels == ["fm_flash_span"] * 2
        assert score_arrays(text, 32, 1024, 1024) == []
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
        assert arrays_of(text, 128, 8, 5120, 64) == []   # no context
        assert arrays_of(text, 128, 4, 5120, 128) == []
        assert " scatter(" not in text
        assert "attn.conv_decode" in text
        # logits, K and V pool, the inputs, experts_touched
        assert len(jax.tree.leaves(compiled.out_info)) == 1 + 3 + 1
    else:
        assert kernels == ["fm_flash_span"] * 2
        assert score_arrays(text, 32, 1024, 5120) == []
        assert "attn.conv_prefill" in text


def test_lfm2_chunk_gathers_its_context_from_the_pool_where_it_lies(
        lfm2_programs):
    """ISSUE 50: the chunk's four context gathers (K and V of two layers,
    320 pages) index layer AND pages of the 5-D pool.  NO array of one
    layer's pool (``bf16[20480,4,16,128]``, 335.5 MB) exists in the
    program: with ``gather_ctx(pools[.][li], ...)`` there were four, a
    ``slice_bitcast_fusion`` each."""
    compiled = lfm2_programs.compiled("chunk")
    assert layer_of_pool(compiled, 2, 20480, 4, 16, 128) == ([], [], 4)
