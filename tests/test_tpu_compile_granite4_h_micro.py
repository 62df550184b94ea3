"""The chip's own compiler on the programs of the ``granite4_h_micro``
cell, the WHOLE model: K/V pools of four layers beside a Mamba-2 state of
36, a tied head — no chip needed.

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
    Programs, arrays_of, fm_kernels, layer_of_pool, one_chip, program_bytes,
    score_arrays, topo,
)


@pytest.fixture(scope="module")
def granite_programs(one_chip):
    """The decode program at the widest table, the 1024-token chunk at the
    widest context bucket and a 1024-token whole-prompt prefill of the cell
    ``granite4_h_micro.serve.ragdocs`` (granite-4.0-h-micro, 40 of 40
    layers, the whole vocabulary, bf16; 32 slots of a float32 state
    [64, 64, 128] in 36 layers, a 24576 x 16-token K/V pool of FOUR layers
    with 8 heads of 64 packed two to a row, tables at their 1056 pages),
    lowered as the engine runs them: the whole cache donated, traced as on
    a TPU."""
    from flashmoe_tpu.models.presets import PRESETS
    from flashmoe_tpu.models.transformer import init_params
    from flashmoe_tpu.serving import engine as eng
    from flashmoe_tpu.serving.kvcache import init_paged_cache

    cfg = PRESETS["granite-4.0-h-micro"](param_dtype=jnp.bfloat16)
    on = lambda t: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    params = on(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    cache = on(jax.eval_shape(lambda: init_paged_cache(cfg, 24576, 16, 32)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, np.int32, sharding=one_chip)
    with pytest.MonkeyPatch.context() as mp:        # traced as on a TPU
        mp.setattr(jax, "default_backend", lambda: "tpu")
        return Programs({
            "decode": eng._INPLACE["_paged_decode_step"].lower(
                params, cfg, cache, i32(32), i32(32, 1056), i32(32),
                pad_token=0),
            "chunk": eng._INPLACE["_prefill_chunk"].lower(
                params, cfg, cache, i32(1, 1024), i32(1056), i32(64), i32(),
                i32(), i32()),
            "prefill": eng._prefill_padded.lower(
                params, cfg, i32(1, 1024), i32())})


@pytest.mark.parametrize("program", ["decode", "chunk", "prefill"])
def test_granite_programs_fit_the_chip_with_one_embedding(granite_programs,
                                                          program):
    """12.10 GB (decode), 12.46 GB (the chunk over 1056 gathered pages;
    12.80 with two layers' pools copied out, before ISSUE 50) and 6.90 GB
    (a 1024-token prompt at once) as compiled, under the cell's 14.5:
    6.38 GB of weights, and the by-slot state (2.42 GB: 32 slots x 36
    layers x 2.10 MB float32), the K/V pool (3.22 GB: 8 kB a token) and the
    convolutions' inputs (30 MB) once each, aliased to the outputs.  NO
    copy of the state, of the pool or of the EMBEDDING in any program: the
    tied head contracts over the ``[100352, 2048]`` array where it lies, no
    ``[2048, 100352]`` array exists.  The decode program steps the state
    through ``fm_ssm_step``, 36 calls at ONE group, and reads the four
    attention layers' pages in place (``fm_paged_decode``, FOUR calls, the
    8 heads of 64 as 4 rows of 128); the chunk and the whole prompt score
    their context blockwise (``fm_flash_span``, FOUR calls at D 64, no
    ``[32, 1024, .]`` scores)."""
    compiled = granite_programs.compiled(program)
    text = compiled.as_text()
    state, pool, inputs, embed = (r"f32\[36,32,64,64,128\]",
                                  r"bf16\[4,24576,4,16,128\]",
                                  r"bf16\[36,32,13056\]",
                                  r"bf16\[100352,2048\]")
    lo, hi = {"decode": (11.9e9, 12.3e9), "chunk": (12.25e9, 12.65e9),
              "prefill": (6.7e9, 7.1e9)}[program]
    assert lo < program_bytes(compiled) < hi < 14.5e9
    copies = lambda shape: re.findall(rf"^.*= {shape}\S* copy\(.*$", text,
                                      re.M)
    assert re.search(embed, text) and copies(embed) == []
    assert not re.search(r"bf16\[2048,100352\]", text)
    assert "ragdot" not in text and "fm_ffn_fwd" not in fm_kernels(text)
    assert "ffn.dense" in text and "lm.head" in text
    kernels = fm_kernels(text)
    if program == "prefill":
        assert kernels == ["fm_flash_span"] * 4
        assert score_arrays(text, 32, 1024, 1024) == []
        assert "attn.ssm_prefill" in text
        # logits, K and V rows, the state and the inputs after the prompt
        assert len(jax.tree.leaves(compiled.out_info)) == 1 + 4
        return
    cache_bytes = (2 * 4 * 24576 * 4 * 16 * 128 * 2
                   + 36 * 32 * (64 * 64 * 128 * 4 + 13056 * 2))
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes
    for shape in (state, pool, inputs):
        assert re.search(shape, text)
        assert copies(shape) == []
    if program == "decode":
        assert sorted(kernels) == ["fm_paged_decode"] * 4 + [
            "fm_ssm_step"] * 36
        assert ".remat" not in "".join(
            line for line in text.splitlines() if "f32[36,32,64" in line)
        assert arrays_of(text, 32, 8, 16896, 64) == []    # no context
        assert "attn.ssm_decode" in text and "attn.ssm_prefill" not in text
        # logits and the cache's four arrays: the layers count nothing
        assert len(jax.tree.leaves(compiled.out_info)) == 1 + 4
    else:
        assert kernels == ["fm_flash_span"] * 4
        assert score_arrays(text, 32, 1024, 16896) == []
        assert "attn.ssm_prefill" in text


def test_granite_chunk_gathers_its_context_from_the_pool_where_it_lies(
        granite_programs):
    """ISSUE 50: the chunk's eight context gathers (K and V of four
    layers, 1056 pages of 16 kB each) index layer AND pages of the 5-D
    pool, the donated parameter.  NO array of one layer's pool
    (``bf16[24576,4,16,128]``, 403 MB) exists in the program: with
    ``gather_ctx(pools[.][li], ...)`` there were eight, a
    ``slice_bitcast_fusion`` each, 1.23 ms of copying a layer's pool that
    a gather then read 17 MB of, and two of them live at once: the
    program's temporaries were 0.753 GB, and are 0.407."""
    compiled = granite_programs.compiled("chunk")
    assert layer_of_pool(compiled, 4, 24576, 4, 16, 128) == ([], [], 8)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert 0.35e9 < temp < 0.46e9, temp
