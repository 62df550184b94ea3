"""The chip's own compiler on the main path at real widths — no chip needed.

libtpu compiles for a v5e that is described, not attached (topology
``v5e:2x2``, device kind "TPU v5 lite"), and refuses what the chip would
refuse: a slice off the (8, 128) tiling, a kernel over its VMEM limit, a
program over 16 GB.  Interpret mode shows none of that.  Nothing runs, so
these say nothing about results or times — ``chip_smoke.py`` does, on the
chip.

One file a configuration since ISSUE 47 (as ONE file these held a worker
of the tier-1 gate for eleven minutes, the longest hold there was; a file
of few cases starts last in the gate's queue and must be cheap):

- ``test_tpu_compile.py`` (this file): ``sdar_30b_a3b``
- ``test_tpu_compile_dsmoe16b.py``: ``dsmoe16b`` (``-k backlog``)
- ``test_tpu_compile_lfm2_24b.py``: ``lfm2_24b`` (``-k lfm2``)
- ``test_tpu_compile_joyai_flash.py``: ``joyai_flash`` (``-k mla``)
- ``test_tpu_compile_ling3_flash.py``: ``ling3_flash`` (``-k hybrid``)
- ``test_tpu_compile_nemotron3_nano.py``: ``nemotron3_nano`` (``-k ssm``)
- ``test_tpu_compile_longcat_flash_omni.py``: ``longcat_flash_omni``
  (``-k shortcut``)
- ``test_tpu_compile_granite4_h_micro.py``: ``granite4_h_micro``
  (``-k granite``)
- ``test_tpu_compile_trinity_large.py``: ``trinity_large``
  (``-k trinity``)
- ``test_tpu_compile_sampler.py``: the sampler at both vocabularies
- ``test_tpu_compile_layers.py``: kernels, layers, four chips, train step

``pytest tests/test_tpu_compile*.py -k <word>`` finds what ``pytest
tests/test_tpu_compile.py -k <word>`` found.  The topology is described
inside a module-scoped fixture (``tests/_compiled.py``, imported by name):
only a worker that is handed one of these files loads the TPU's library,
and several may at once (the fixture sets ``ALLOW_MULTIPLE_LIBTPU_LOAD``
around the load as the driver's command does: nothing attaches a chip
here).  Shapes come from ``jax.eval_shape``; the persistent compile
cache is off around them (an entry written without a chip cannot be read
back and would only warn).
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
        return Programs({
            "denoise": eng._INPLACE["_paged_denoise_step"].lower(
                params, cfg, cache, i32(64, 3, 4), i32(64, 9), i32(64, 160),
                pad_token=0),
            "chunk": eng._INPLACE["_prefill_chunk"].lower(
                params, cfg, cache, i32(1, 1024), i32(160), i32(64), i32(),
                i32(), i32())})


@pytest.mark.parametrize("program", ["denoise", "chunk"])
def test_sdar_programs_fit_the_chip_with_the_pool_in_place(
        sdar_programs, program):
    """The cell's programs as the chip's compiler builds them, under its
    14.5 GB: 9.97 GB of weights and the K/V pool (1.88 GB: 14 336 B a
    token over 7 layers) once, aliased to the output; the denoise program
    reads every slot's pages in place (``fm_paged_decode`` at T = 8, a
    span of two blocks under the block mask (ISSUE 52), a call a layer,
    no gathered context), runs the routed rows of its 512-row span through
    ``fm_ffn_fwd``, a launch a layer, and scores 4 rows a slot, never 8;
    the chunk scores its context blockwise (``fm_flash_span`` with the
    block-causal diagonal)."""
    compiled = sdar_programs.compiled(program)
    text = compiled.as_text()
    total = program_bytes(compiled)
    print(program, total / 1e9)
    assert total < 14.5e9
    assert "ragged-dot" not in text
    kernels = fm_kernels(text)
    assert [n for n in kernels if n == "fm_ffn_fwd"] == ["fm_ffn_fwd"] * 7
    kernels = [n for n in kernels if n != "fm_ffn_fwd"]
    pool = r"bf16\[7,8192,4,16,128\]"
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 2 * 7 * 8192 * 4 * 16 * 128 * 2
    assert re.search(pool, text)
    assert not re.findall(rf"^.*= {pool}\S* copy\(.*$", text, re.M)
    if program == "denoise":
        assert kernels == ["fm_paged_decode"] * 7
        assert arrays_of(text, 64, 4, 2560, 128) == []   # no context
        assert " scatter(" not in text
        assert arrays_of(text, 64, 4, 151936)            # the head: L rows
        assert arrays_of(text, 64, 8, 151936) == []
        assert arrays_of(text, 512, 151936) == []
        # the blocks' state, K and V pool, experts_touched
        assert len(jax.tree.leaves(compiled.out_info)) == 1 + 2 + 1
    else:
        assert kernels == ["fm_flash_span"] * 7
        assert score_arrays(text, 32, 1024, 2560) == []


def test_sdar_chunk_gathers_its_context_from_the_pool_where_it_lies(
        sdar_programs):
    """ISSUE 50: the chunk's fourteen context gathers (K and V of seven
    layers, 160 pages) index layer AND pages of the 5-D pool.  NO array
    of one layer's pool (``bf16[8192,4,16,128]``, 134 MB) exists in the
    program: with ``gather_ctx(pools[.][li], ...)`` there were fourteen,
    a ``slice_bitcast_fusion`` each."""
    compiled = sdar_programs.compiled("chunk")
    assert layer_of_pool(compiled, 7, 8192, 4, 16, 128) == ([], [], 14)
