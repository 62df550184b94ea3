"""The chip's own compiler on the programs of the ``joyai_flash`` cell, an
MLA model over a latent paged cache — no chip needed.

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
    arrays_of, fm_kernels, latent_pool_copies, no_stacked_gate_up, one_chip,
    score_arrays, topo,
)


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
    assert no_stacked_gate_up(text, 256, 2048, 768)
    assert fm_kernels(text) == ["fm_flash_span"] + [
        "fm_flash_span", "fm_ffn_fwd"] * 4 and " scatter(" in text
    assert score_arrays(text, 32, 1024, 7168) == []
    assert "moe.expert/" in text
    assert "bf16[5,16384,16,640]{3,2,1,0" in text
    assert latent_pool_copies(text) == []
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
    assert latent_pool_copies(text) == []


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
    assert fm_kernels(text) == ["fm_latent_decode"] + [
        "fm_latent_decode", "fm_ffn_fwd"] * 4
    assert "ragged-dot" not in text
    assert no_stacked_gate_up(text, 256, 2048, 768)
    for width in (576, 640):
        assert arrays_of(text, 32, 7168, width) == []
        assert arrays_of(text, 32, 448, 16, width) == []
        assert arrays_of(text, 14336, 16 * width) == []
    assert " scatter(" not in text                  # the kernel stores
    assert latent_pool_copies(text) == []
    assert "attn.mla_decode" in text and "moe.gate" in text
