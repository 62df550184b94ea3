"""The chip's own compiler on the programs of the ``trinity_large`` cell:
a full layer's K/V pool beside four window layers' pool with page ids of
its own, the two kernels under a window — no chip needed.

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
    Programs, arrays_of, fm_kernels, one_chip, program_bytes, score_arrays,
    topo,
)

SLOTS, PAGES, WINDOW_PAGES = 32, 43008, 10273


@pytest.fixture(scope="module")
def trinity_programs(one_chip):
    """The decode program at the widest table and the 1024-token chunk at
    the widest context bucket of the cell ``trinity_large.serve.longmix``
    (Trinity-Large-Preview: layers 0-4 of 60, S S S F S, 32 of 256 experts
    held, an eighth of the vocabulary, bf16; 32 slots, a 43008 x 16-token
    pool of the ONE full layer, a 10273-page pool of the FOUR window
    layers; the full layer's tables at their 1936 pages, a window layer's
    at the 257 a token's window reaches and the 320 a chunk's does),
    lowered as the engine runs them: the whole cache donated, traced as on
    a TPU."""
    from flashmoe_tpu.models.presets import PRESETS
    from flashmoe_tpu.models.transformer import init_params
    from flashmoe_tpu.serving import engine as eng
    from flashmoe_tpu.serving.kvcache import init_paged_cache

    cfg = PRESETS["trinity-large-preview"](
        num_layers=5, first_k_dense=1, experts_held=32, vocab_size=25024,
        layer_mixers=("swa", "swa", "swa", "mha", "swa"),
        param_dtype=jnp.bfloat16)
    on = lambda t: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    params = on(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    cache = on(jax.eval_shape(lambda: init_paged_cache(
        cfg, PAGES, 16, SLOTS, WINDOW_PAGES)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, np.int32, sharding=one_chip)
    with pytest.MonkeyPatch.context() as mp:        # traced as on a TPU
        mp.setattr(jax, "default_backend", lambda: "tpu")
        return Programs({
            "decode": eng._INPLACE["_paged_decode_step"].lower(
                params, cfg, cache, i32(SLOTS), i32(SLOTS, 1936), i32(SLOTS),
                pad_token=0, window=(i32(SLOTS, 257), i32(SLOTS))),
            "chunk": eng._INPLACE["_prefill_chunk"].lower(
                params, cfg, cache, i32(1, 1024), i32(1936), i32(64), i32(),
                i32(), i32(), (i32(320), i32(64), i32()))})


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_trinity_programs_fit_the_chip_with_both_pools_in_place(
        trinity_programs, program):
    """14.17 GB (decode) and 14.37 GB (the chunk over 1936 gathered pages
    of the full layer and 320 of each window layer) as compiled, under 14.6:
    8.65 GB of weights, the full layer's pool (2.82 GB: 4 kB a token) and
    the window layers' (2.69 GB: 16 kB a token over 10272 pages) once each,
    aliased to the outputs, NO copy of either.  The decode program reads
    every layer's pages in place (``fm_paged_decode``, FIVE calls: one a
    layer, the window layers' over their own tables); the chunk scores its
    context blockwise (``fm_flash_span``, FIVE calls, no ``[48, 1024, .]``
    scores: the full layer over 30976 gathered rows, a window layer over
    5120); the held experts' rows go through ``fm_ffn_fwd`` in the four
    mixture layers; the output gate stands under ``attn.gate``."""
    compiled = trinity_programs.compiled(program)
    text = compiled.as_text()
    full, window = (rf"bf16\[1,{PAGES},8,16,128\]",
                    rf"bf16\[4,{WINDOW_PAGES},8,16,128\]")
    lo, hi = {"decode": (14.0e9, 14.35e9),
              "chunk": (14.2e9, 14.55e9)}[program]
    assert lo < program_bytes(compiled) < hi < 14.6e9
    cache_bytes = 2 * (PAGES + 4 * WINDOW_PAGES) * 8 * 16 * 128 * 2
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes
    for shape in (full, window):
        assert re.search(shape, text)
        assert re.findall(rf"^.*= {shape}\S* copy\(.*$", text, re.M) == []
    # no array of ONE window layer's pool (a ``pool[li]`` written out)
    assert arrays_of(text, WINDOW_PAGES, 8, 16, 128) == []
    assert "attn.gate" in text and "ffn.moe" in text and "ragdot" not in text
    kernels = fm_kernels(text)
    assert kernels.count("fm_ffn_fwd") == 4
    if program == "decode":
        assert kernels.count("fm_paged_decode") == 5
        assert "fm_flash_span" not in kernels
        assert arrays_of(text, SLOTS, 8, 30976, 128) == []    # no context
        assert "attn.kv_decode" in text
    else:
        assert kernels.count("fm_flash_span") == 5
        assert score_arrays(text, 48, 1024, 30976) == []
        assert score_arrays(text, 48, 1024, 5120) == []
        # a window layer gathers the 320 pages its windows reach, the full
        # layer its 1936: K and V of each
        assert len(arrays_of(text, 8, 5120, 128)) == 1
        assert len(arrays_of(text, 8, 30976, 128)) == 1
        assert "attn.kv_prefill" in text
