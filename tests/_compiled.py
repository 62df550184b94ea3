"""What the ``tests/test_tpu_compile*.py`` files share: the described chip
(the ``topo`` and ``one_chip`` fixtures, which each of those files imports
by name: module-scoped, so only a worker that is handed one of those files
loads the TPU's library, and not in ``conftest.py``, where
``tests/test_chip_contract.py`` allows no word of a compile cache), and
what they read in a program the chip's compiler built: the repo's kernels
by name, arrays by shape, the bytes it holds."""

import os
import re

import jax
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # Several of the files that ask for this run beside each other under
    # xdist, and a second process that loads the TPU's library aborts on
    # libtpu's lock file unless this is set: every case of the later file
    # would SKIP, in silence.  The driver's command sets it; this is for a
    # bare ``pytest tests/ -n 6``.  Set HERE and nowhere at import: only a
    # worker inside one of those files sees it, no chip is attached to a
    # test run (``conftest.py`` pins the CPU), and it goes once the library
    # is loaded.
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no libtpu: nothing to ask
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


class Programs(dict):
    """A configuration's lowered programs by name.  ``compiled(name)``
    hands the chip's compiler a program ONCE, for every case that reads
    it (``--dist loadfile`` keeps a file's cases on one worker)."""

    def __init__(self, lowered):
        super().__init__(lowered)
        self._compiled = {}

    def compiled(self, name):
        if name not in self._compiled:
            self._compiled[name] = self[name].compile()
        return self._compiled[name]


#: a whole latent pool of either MLA cell, as the programs hold it (the
#: kernel's operand has a unit axis of heads) and in any layout
LATENT_POOL = r"bf16\[(?:5,16384|1,40960),(?:1,)?16,640\]"


def fm_kernels(text):
    """The repo's own kernels in a compiled program, by the name the
    chip's trace shows on its ``XLA Ops`` line (``%fm_ffn_fwd.3 = ...``;
    XLA's grouped matmul is a custom call too)."""
    names = [re.search(r"%([\w.\-]+) = ", line)
             for line in text.splitlines() if "tpu_custom_call" in line]
    return [m.group(1).split(".")[0] for m in names
            if m and m.group(1).startswith("fm_")]


def arrays_of(text, *dims):
    """Shapes of the bf16 / f32 arrays of a compiled program that have
    exactly ``dims``, in any order."""
    want = sorted(dims)
    return [s for s in set(re.findall(r"(?:bf16|f32)\[([0-9,]+)\]", text))
            if sorted(int(n) for n in s.split(",")) == want]


def score_arrays(text, heads, span, ctx):
    """Arrays of a compiled program shaped as the scores of a span over
    its context, ``[heads, span, ctx]`` in either float type (what the
    plain XLA attention of a long span wrote and read three times before
    ISSUE 44; ``fm_flash_span`` keeps a tile of them in VMEM)."""
    return (arrays_of(text, heads, span, ctx)
            + arrays_of(text, 1, heads, span, ctx))


def no_stacked_gate_up(text, e, h, i):
    """No array of a layer's gate + up weights side by side ([E, H, 2I]:
    what the grouped kernel's gated form concatenated on every call before
    ISSUE 36) in a compiled program."""
    return arrays_of(text, e, h, 2 * i) == []


def latent_pool_copies(text):
    return re.findall(rf"^.*= {LATENT_POOL}\S* copy\(.*$", text, re.M)


def layer_of_pool(compiled, layers, pages, rows, page, width):
    """What a compiled K/V program holds of a pool ``[layers, pages, rows,
    page, width]`` besides the pool: (the arrays shaped as ONE layer of
    it, the copies of all of it, how many gathers take whole pages by
    layer AND page id).  ``pool[li]`` as a value is such an array on the
    chip, a ``slice_bitcast_fusion`` that writes a layer's pool out before
    a gather reads a few pages of it (ISSUE 50); ``pool[li, tables]`` is
    one gather of the aliased parameter."""
    text = compiled.as_text()
    pool = rf"bf16\[{layers},{pages},{rows},{page},{width}\]"
    assert re.search(pool, text)
    return (arrays_of(text, pages, rows, page, width),
            re.findall(rf"^.*= {pool}\S* copy\(.*$", text, re.M),
            len(re.findall(
                r" gather\(.*collapsed_slice_dims=\{0,1\}.*slice_sizes="
                rf"\{{1,1,{rows},{page},{width}\}}", text)))


def program_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
