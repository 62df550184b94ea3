"""The chip's own compiler on the programs of the ``nemotron3_nano`` cell,
K/V pools beside a Mamba-2 state — no chip needed.

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
        return Programs({
            "decode": eng._INPLACE["_paged_decode_step"].lower(
                params, cfg, cache, i32(256), i32(256, 288), i32(256)),
            "chunk": eng._INPLACE["_prefill_chunk"].lower(
                params, cfg, cache, i32(1, 1024), i32(288), i32(64), i32(),
                i32(), i32()),
            "prefill": eng._prefill_padded.lower(
                params, cfg, i32(1, 1024), i32())})


@pytest.mark.parametrize("program", ["decode", "chunk", "prefill"])
def test_ssm_programs_fit_the_chip_with_state_and_pool_in_place(
        ssm_programs, program):
    """12.55 GB (decode), 12.65 GB (chunk; 12.78 with a layer of the
    pool copied out before ISSUE 50, 13.76 with float32 scores over
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
    compiled = ssm_programs.compiled(program)
    text = compiled.as_text()
    state, pool, inputs = (r"f32\[6,256,64,64,128\]",
                           r"bf16\[2,32768,2,16,128\]",
                           r"bf16\[6,256,18432\]")
    lo, hi = {"decode": (12.3e9, 12.8e9), "chunk": (12.4e9, 12.9e9),
              "prefill": (8.1e9, 8.6e9)}[program]
    assert lo < program_bytes(compiled) < hi < 14.5e9
    assert "ragged-dot" not in text
    # the experts STORED at whole lanes (``intermediate_pad``): as
    # [64, 2688, 1856] the chip kept the array H-minor and copied 0.64 GB
    # of it into row-major order before every launch
    assert "[64,2688,1920]" in text and "[64,2688,1856]" not in text
    assert not re.findall(r"^.*= bf16\[64,(2688,1920|1920,2688)\]\S* "
                          r"copy\(.*$", text, re.M)
    kernels = fm_kernels(text)
    assert [n for n in kernels if n == "fm_ffn_fwd"] == ["fm_ffn_fwd"] * 5
    kernels = [n for n in kernels if n != "fm_ffn_fwd"]
    if program == "prefill":
        assert kernels == ["fm_flash_span"] * 2
        assert score_arrays(text, 32, 1024, 1024) == []
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
        assert arrays_of(text, 256, 2, 4608, 128) == []   # no context
        assert "attn.ssm_decode" in text and "attn.ssm_prefill" not in text
        # logits, the cache's four arrays, experts_touched and held_rows
        assert len(jax.tree.leaves(compiled.out_info)) == 1 + 4 + 2
    else:
        assert kernels == ["fm_flash_span"] * 2 and copies(inputs) == []
        assert score_arrays(text, 32, 1024, 4608) == []
        assert "attn.ssm_prefill" in text


def test_ssm_chunk_gathers_its_context_from_the_pool_where_it_lies(
        ssm_programs):
    """ISSUE 50: the chunk's four context gathers (K and V of two layers,
    288 pages) index layer AND pages of the 5-D pool.  NO array of one
    layer's pool (``bf16[32768,2,16,128]``, 268 MB) exists in the
    program: with ``gather_ctx(pools[.][li], ...)`` there were four, a
    ``slice_bitcast_fusion`` each."""
    compiled = ssm_programs.compiled("chunk")
    assert layer_of_pool(compiled, 2, 32768, 2, 16, 128) == ([], [], 4)
