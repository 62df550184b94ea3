"""The chip's own compiler on the programs of the ``longcat_flash_omni``
cell, a mixture branch beside two MLA layers — no chip needed.

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
    arrays_of, fm_kernels, one_chip, program_bytes, score_arrays, topo,
)


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
    windows, no ``ragged_dot`` (each launch walks the intermediate axis in
    FOUR chunks of 512 columns, ``ops/moe.expert_chunks``: an expert's
    three matrices double buffered are 151 MB against the kernel's 64 MiB
    of VMEM; since ISSUE 48 its tiles past the live ones, about 11 of 19 in
    a decode step and 30 of 47 in a chunk, fetch no weights); the plan is laid out for the rows the 16
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
    size = {"decode": 12.40e9, "chunk": 12.93e9, "prefill": 10.93e9}[program]
    # ISSUE 48: the launches whose dead tiles hold their chunk compile to
    # the sizes the parent's did, to 0.05 GB
    assert abs(program_bytes(compiled) - size) < 0.05e9 and size < 14.5e9
    assert "ragged-dot" not in text
    kernels = fm_kernels(text)
    assert [n for n in kernels if n == "fm_ffn_fwd"] == ["fm_ffn_fwd"] * 4
    rows = {"decode": 304, "chunk": 1504, "prefill": 1504}[program]
    assert len(re.findall(rf"%fm_ffn_fwd[.\d]* = bf16\[{rows},6144\]",
                          text)) == 4
    assert arrays_of(text, 16, 6144, 4096) == []   # no gate | up array
    assert "moe.zero" in text and "moe.shortcut_join" in text
    kernels = [n for n in kernels if n != "fm_ffn_fwd"]
    if program == "prefill":
        assert kernels == ["fm_flash_span"] * 8
        assert score_arrays(text, 64, 1024, 1024) == []
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
        assert arrays_of(text, 64, 7168, 640) == []    # no context
        assert "attn.mla_decode" in text
        # logits, the pool, experts_touched, held_rows and zero_rows
        assert len(jax.tree.leaves(compiled.out_info)) == 1 + 1 + 3
    else:
        assert kernels == ["fm_flash_span"] * 8
        assert score_arrays(text, 64, 1024, 7168) == []
        assert "attn.mla_prefill" in text
