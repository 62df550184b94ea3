"""The chip's own compiler on the serving sampler at both serving
vocabularies — no chip needed.

``tests/test_tpu_compile.py`` says what the described chip is and where
every configuration's programs are compiled; the ``topo`` and ``one_chip``
fixtures are ``tests/_compiled.py``'s.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from _compiled import one_chip, topo  # noqa: F401


def _compile_sampler(one_chip, b, v):
    from flashmoe_tpu.serving import engine as eng

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return eng._sample_dynamic.lower(
        arg((b, v), jnp.float32), arg((b,), jnp.uint32),
        arg((b,), jnp.int32), arg((b,), jnp.float32),
        arg((b,), jnp.int32), arg((b,), jnp.float32)).compile()


def test_sampler_program_derives_its_keys_on_the_chip(one_chip):
    """The serving sampler at the backlog cell's size (32 slots, the
    deepseek vocabulary): the chip's compiler takes the key derivation
    (a ``vmap`` of ``PRNGKey`` + ``fold_in`` over uint32 seeds) in the
    sampler's own program and returns 32 tokens."""
    b, v = 32, 102400
    compiled = _compile_sampler(one_chip, b, v)
    (out,) = jax.tree.leaves(compiled.out_info)
    assert out.shape == (b,) and out.dtype == jnp.int32
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


@pytest.mark.parametrize("v", [102400, 129280],
                         ids=["dsmoe16b", "joyai_flash"])
def test_sampler_program_keeps_its_sort_behind_a_conditional(one_chip, v):
    """At both serving cells' sizes the chip's compiler leaves the
    sampler's branches as ``conditional``s (it does not flatten them
    into selects that would run every arm) and the program holds ONE
    sort of the vocabulary, inside a branch."""
    text = _compile_sampler(one_chip, 32, v).as_text()
    assert len(re.findall(r" conditional\(", text)) == 2, "flattened"
    sorts = re.findall(r"^.* sort\(.*$", text, re.M)
    assert len(sorts) == 1, sorts
    assert f"f32[32,{v}]" in sorts[0] and "/cond/branch_1_fun" in sorts[0]
    entry = text[text.index("\nENTRY "):]
    assert " sort(" not in entry and " conditional(" in entry
