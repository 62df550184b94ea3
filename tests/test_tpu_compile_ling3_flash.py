"""The chip's own compiler on the programs of the ``ling3_flash`` cell,
latent pages beside a delta-rule state — no chip needed.

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
    program_bytes, score_arrays, topo,
)


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
    assert lo < program_bytes(compiled) < hi
    for shape in (r"f32\[6,64,32,128,128\]", r"bf16\[1,40960,16,640\]",
                  r"bf16\[6,64,36864\]"):
        assert re.search(shape, text)
        assert not re.findall(rf"^.*= {shape}\S* copy\(.*$", text, re.M)
    assert latent_pool_copies(text) == []
    assert "ragged-dot" not in text
    assert "[128,2560,768]" in text and "[512,2560,768]" not in text
    assert no_stacked_gate_up(text, 128, 2560, 768)
    assert "moe.route_groups" in text
    kernels = fm_kernels(text)
    # the six mixture layers' experts: ONE launch of the grouped FFN
    # kernel each, over the rows that fall on the 128 experts held
    assert [n for n in kernels if n == "fm_ffn_fwd"] == ["fm_ffn_fwd"] * 6
    kernels = [n for n in kernels if n != "fm_ffn_fwd"]
    if program == "decode":
        assert kernels == ["fm_latent_decode"]
        for width in (576, 640):
            assert arrays_of(text, 64, 10240, width) == []
            assert arrays_of(text, 64, 640, 16, width) == []
            assert arrays_of(text, 40960, 16 * width) == []
        assert " scatter(" not in text
        assert "attn.kda_decode" in text and "attn.mla_decode" in text
        # logits, the cache's three arrays, experts_touched and held_rows
        assert len(jax.tree.leaves(compiled.out_info)) == 1 + 3 + 2
    else:
        assert kernels == ["fm_flash_span"]
        assert score_arrays(text, 32, 1024, 10240) == []
        assert "attn.kda_prefill" in text and "attn.mla_prefill" in text
