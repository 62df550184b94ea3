"""The chip's own compiler on the programs of the ``dsmoe16b`` backlog cell
(K/V pools) — no chip needed.

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
        return Programs({
            "decode": eng._INPLACE["_paged_decode_step"].lower(
                params, cfg, cache, i32(32), i32(32, 160), i32(32)),
            "verify": eng._INPLACE["_paged_verify_step"].lower(
                params, cfg, cache, i32(32, 5), i32(32, 160), i32(32)),
            "chunk": eng._INPLACE["_prefill_chunk"].lower(
                params, cfg, cache, i32(1, 1024), i32(160), i32(64), i32(),
                i32()),
            "prefill": eng._prefill_padded.lower(
                params, cfg, i32(1, 2048), i32())})


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
    compiled = backlog_programs.compiled(program)
    text = compiled.as_text()
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 2 * 6 * 2048 * 16 * 16 * 128 * 2                  # 1.61 GB
    # 9.53 / 9.59 / 9.74 (the chunk 10.38 with E x S rows)
    assert 9.3e9 < program_bytes(compiled) < 11e9
    assert not re.findall(
        r"^.*= bf16\[6,2048,16,16,128\]\S* copy\(.*$", text, re.M)
    assert "moe.gate" in text and "moe.expert" in text
    # ONE rule picks the experts' arm (``ops/moe.expert_arm``): since
    # ISSUE 36 the routed rows through the grouped Pallas kernel at every
    # span on a TPU (192, 960 and 6144 rows here): one ``fm_ffn_fwd`` a
    # layer, no ``ragged_dot``, no [64, capacity, .] dispatch buffer, no
    # [64, 2048, 2816] gate | up array
    assert "ragged-dot" not in text
    assert no_stacked_gate_up(text, 64, 2048, 1408)
    for rows in (32, 160, 1024):                    # capacity(s) = s
        assert arrays_of(text, 64, rows, 2048) == []
    kernels = fm_kernels(text)
    assert [n for n in kernels if n == "fm_ffn_fwd"] == ["fm_ffn_fwd"] * 6
    kernels = [n for n in kernels if n != "fm_ffn_fwd"]
    if program == "chunk":
        assert kernels == ["fm_flash_span"] * 6
        assert arrays_of(text, 16, 2560, 128)      # its gathered context
        assert score_arrays(text, 16, 1024, 2560) == []
        return
    assert len(kernels) == 6, kernels
    assert all(n.split(".")[0] == "fm_paged_decode" for n in kernels)
    assert arrays_of(text, 5120, 16, 16, 128) == []
    assert arrays_of(text, 32, 16, 2560, 128) == []
    assert " scatter(" not in text                  # the kernel stores


def test_backlog_whole_prompt_prefill_fits_beside_the_pool(
        backlog_programs):
    """A 2048-token prompt at once: 8.33 GB as compiled (the weights,
    f32 scores of 16 heads over 2048 x 2048, the experts over the 12288
    routed rows in 256-row tiles; 9.76 GB with E x S rows before ISSUE
    33), which leaves
    the engine's pool its 1.61 GB; the program holds no pool and hands
    back one K and one V run for ``store_prefill``."""
    compiled = backlog_programs.compiled("prefill")
    assert abs(program_bytes(compiled) / 8.3298e9 - 1) < 0.01
    text = compiled.as_text()
    assert [n for n in fm_kernels(text)
            if n != "fm_ffn_fwd"] == ["fm_flash_span"] * 6
    assert score_arrays(text, 16, 2048, 2048) == []
    logits, k_run, v_run = jax.tree.leaves(compiled.out_info)
    assert logits.shape == (102400,) and logits.dtype == jnp.float32
    assert k_run.shape == v_run.shape == (6, 16, 2048, 128)
    assert "[6,2048,16,16,128]" not in compiled.as_text()


def test_backlog_chunk_gathers_its_context_from_the_pool_where_it_lies(
        backlog_programs):
    """ISSUE 50: the chunk's twelve context gathers (K and V of six
    layers, 160 pages) index layer AND pages of the 5-D pool.  NO array
    of one layer's pool (``bf16[2048,16,16,128]``, 134 MB) exists in the
    program: with ``gather_ctx(pools[.][li], ...)`` there were twelve.
    (The cell's engine sets no ``prefill_chunk``; a deployment that does
    runs this program.)"""
    compiled = backlog_programs.compiled("chunk")
    assert layer_of_pool(compiled, 6, 2048, 16, 16, 128) == ([], [], 12)
