"""Paged ragged KV cache: block-table indirection over a fixed page
pool.

``generate.py``'s dense monolith allocates ``[L, B, N_kv, T_max, D]``
up front — every request pays the longest request's context, and a
retiring request's memory cannot be reused without reshaping the whole
cache (a recompile).  This module replaces it with the serving-standard
paged layout (vLLM-style, built on the same row-major "static shapes,
dynamic indices" machinery as :mod:`flashmoe_tpu.ops.ragged`):

* the device holds one fixed pool ``[L, P, N_kv, page, D]`` of KV
  pages (:class:`PagedKVCache`) — its shape never changes, so joining
  and retiring requests never force a recompile;
* each request owns a list of page ids (the *block table*); position
  ``t`` of a request lives in page ``table[t // page]``, row
  ``t % page`` — pure integer indirection, gathered/scattered with
  static shapes and dynamic indices;
* a host-side free-list allocator (:class:`PagePool`) hands pages out
  and takes them back on retirement/eviction — LIFO, so page reuse is
  deterministic and a drill replays bit-identically;
* attention reads a *bucketed* number of pages
  (:func:`ctx_pages_bucket`): the gather length is rounded up to a
  page-bucket granularity, so the decode step jit-compiles once per
  bucket instead of once per context length.

Page 0 is the **scratch page** (:data:`SCRATCH_PAGE`): never allocated,
it absorbs the KV writes of inactive batch slots (their block tables
point every entry at it) and backs the out-of-range block-table entries
of active requests — which the per-request length mask guarantees are
read back with exactly-zero attention weight.
"""

from __future__ import annotations

import collections
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from flashmoe_tpu.config import MoEConfig

#: page id reserved as the write target of inactive slots and the
#: backing of unallocated block-table entries — never handed out by
#: :class:`PagePool`, never read back with non-zero attention weight.
SCRATCH_PAGE = 0


class PagedKVCache(NamedTuple):
    """The device-side page pool.  ``k_pages`` / ``v_pages``:
    ``[L, P, N_kv, page, D]``; heads narrower than a lane tile lie p to a
    row, ``[L, P, N_kv / p, page, p * D]`` (``MoEConfig.kv_pool_rows``).
    Block tables and lengths live on the
    host (the engine's slot state) and ride into each jitted step as
    ordinary array arguments — values change, shapes never do."""

    k_pages: jax.Array
    v_pages: jax.Array

    @property
    def num_pages(self) -> int:
        return self.k_pages.shape[1]

    @property
    def page_size(self) -> int:
        return self.k_pages.shape[3]


class LatentPagedCache(NamedTuple):
    """The page pool of an MLA model (``cfg.attention_kind == 'mla'``):
    ONE array ``[L, P, page, R]`` holding a token a layer ONE row, the
    normed latent beside the roped shared key (C = kv_lora_rank +
    qk_rope_head_dim elements, nothing per head), padded with zeros to
    R = ``cfg.kv_row_elems``: C rounded up to whole lanes (576 -> 640,
    11 % more pool).  The padding is what makes a page a thing a DMA can
    take: ``[page, R]`` is whole ``(8, 128)`` tiles, one contiguous block
    of HBM, and the chip keeps the array row-major as declared, so the
    decode kernel (``ops/attention.fm_latent_decode``) reads and writes a
    slot's pages in place and no program copies the pool.  The two
    layouts this replaces: ``[.., page, 576]`` (4.5 lanes: the chip kept
    the array pages-minor and every program copied the whole pool into
    gather order and back, PR 27) and ``[.., page * 576]`` (no copy, but
    the page index second-minor: sixteen pages interleave in one tile and
    no DMA can take one).  Pages, block tables, the scratch page and the
    allocators below are :class:`PagedKVCache`'s."""

    pages: jax.Array

    @property
    def num_pages(self) -> int:
        return self.pages.shape[1]

    @property
    def page_size(self) -> int:
        return self.pages.shape[2]


class HybridCache:
    """The cache of a model whose layers differ in kind: two kinds of
    state side by side, in one tuple of arrays.  First the paged pools of
    the layers that cache rows (``cfg.cache_layers``): a K/V pair
    (:class:`PagedKVCache`'s arrays) or one latent pool
    (:class:`LatentPagedCache`'s), in their layouts and for their reasons,
    addressed by PAGE through the block tables and read in place by the
    same decode kernel.  Then what the other layers (``cfg.state_layers``)
    keep of a request whatever its length, addressed by SLOT
    (``cfg.slot_state``: each array ``[L_s, slots, ...]``).  A 'kda'
    layer keeps ``state``, the float32 delta-rule state ``[N, D, D]``, and
    ``conv``, its convolution's last inputs ``[(K - 1) * 3 N D]`` (side
    by side, as a page's rows: no 3-row axis to pad); a 'conv' layer keeps
    ``conv`` alone, its last ``(K - 1) * H`` inputs.  The mixer says what
    it keeps; nothing here names one.  The concrete classes are named
    tuples made by :func:`hybrid_cache_class`, one a set of field names;
    ``by_slot`` names the fields addressed by slot.

    Who does what to a slot's state.  A whole-prompt prefill computes it
    from nothing and :func:`store_state` puts it in the slot; a prompt's
    first chunk starts from nothing whatever the slot holds and every
    later chunk carries it on; every decode step reads and writes it in
    place, and a row of the step that is not decoding (idle, or between
    two chunks) leaves it to the bit.  Retiring and evicting touch
    nothing: the next tenant's prefill overwrites it, and an evicted
    request's re-prefill rebuilds it.

    A model with WINDOW layers (``cfg.window_layers``) keeps a second K/V
    pair between the two, ``wk_pages`` / ``wv_pages``: the window layers'
    rows, in a pool with a page count and a page-id space of its own (a
    slot's pages behind the window go back to it while the slot lives).
    So a cache has no ONE page count, and no ``num_pages``: a pool's is its
    array's (``cache.wk_pages.shape[1]``); the page SIZE is every pool's."""

    __slots__ = ()
    by_slot: tuple = ()

    @property
    def page_size(self) -> int:
        return self[0].shape[-2]


@functools.lru_cache(maxsize=None)
def hybrid_cache_class(paged: tuple, by_slot: tuple) -> type:
    """The :class:`HybridCache` whose fields are the paged pools ``paged``
    then the per-slot arrays ``by_slot`` (names)."""
    return type("HybridCache", (HybridCache, collections.namedtuple(
        "HybridCache", paged + by_slot)),
        {"__slots__": (), "by_slot": by_slot})


#: the window layers' K/V pair in a cache's tuple of arrays, behind the
#: full layers' (``PagedKVCache._fields``)
WINDOW_FIELDS = ("wk_pages", "wv_pages")


def cache_arrays(cfg: MoEConfig, num_pages: int, page_size: int,
                 slots: int, window_pages: int = 0) -> tuple:
    """The cache's arrays, zeroed: the paged pools of the layers that
    cache rows (a K/V pair, or one latent pool), where the config has
    window layers THEIR K/V pair of ``window_pages`` pages (0: as many as
    the full pools', the dense cache of ``generate()``), and, where it has
    state layers, ``slots`` slots of what they keep (``cfg.slot_state``);
    with either of the last two a :class:`HybridCache`."""
    n_cache = len(cfg.cache_layers)
    _, rows, width = cfg.kv_pool_rows
    if cfg.attention_kind == "mla":
        names = ("pages",)
        paged = (jnp.zeros((n_cache, num_pages, page_size, width),
                           cfg.dtype),)
    else:
        names = PagedKVCache._fields
        shape = (n_cache, num_pages, rows, page_size, width)
        paged = (jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype))
    if cfg.window_layers:
        names += WINDOW_FIELDS
        shape = (len(cfg.window_layers), window_pages or num_pages, rows,
                 page_size, width)
        paged += (jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype))
    if not cfg.state_layers and not cfg.window_layers:
        return paged
    kept = cfg.slot_state
    return hybrid_cache_class(names, tuple(name for name, _, _ in kept))(
        *paged, *(jnp.zeros((len(cfg.state_layers), slots, *shape), dtype)
                  for _, shape, dtype in kept))


def init_paged_cache(cfg: MoEConfig, num_pages: int, page_size: int,
                     slots: int = 0, window_pages: int = 0):
    """Allocate the cache the config's layers read: a K/V pair
    (:class:`PagedKVCache`), one latent pool (:class:`LatentPagedCache`)
    or, with window or state layers, such pools beside the window layers'
    pair of ``window_pages`` pages and ``slots`` slots of what
    the state layers keep (:class:`HybridCache`).  ``num_pages`` and
    ``window_pages`` include their pool's scratch page."""
    if num_pages < 2 or (cfg.window_layers and window_pages < 2):
        raise ValueError(f"num_pages={num_pages} (and, with window "
                         f"layers, window_pages={window_pages}) must be "
                         f">= 2 (page 0 is the reserved scratch page)")
    if page_size < 1:
        raise ValueError(f"page_size={page_size} must be >= 1")
    arrays = cache_arrays(cfg, num_pages, page_size, slots, window_pages)
    if cfg.state_layers or cfg.window_layers:
        return arrays
    return (LatentPagedCache if cfg.attention_kind == "mla"
            else PagedKVCache)(*arrays)


def slot_state_fields(cache) -> tuple:
    """For each array of ``cache``: whether it is addressed by slot (a
    state layer's) and not by page."""
    return tuple(name in getattr(cache, "by_slot", ())
                 for name in cache._fields)


def store_state(state, final, slot):
    """Put a prefilled prompt's state into its slot, all layers at once.
    state: ``[L_s, slots, ...]``; final: ``[L_s, ...]``."""
    return state.at[:, slot].set(final.astype(state.dtype))


# ----------------------------------------------------------------------
# Whole-run page write (the engine's admission; the per-span store and the
# gather of the jitted steps are ops/attention.py's store_kv / gather_ctx,
# which index the whole pool by layer AND pages: no layer of it as a value)
# ----------------------------------------------------------------------

def store_prefill(pages, seq_kv, page_ids):
    """Scatter a prefilled dense K (or V) run into freshly-allocated
    pages, all layers at once.

    pages: ``[L, P, N_kv, page, D]``; seq_kv: ``[L, N_kv, T_pad, D]``
    with ``T_pad = len(page_ids) * page``; page_ids: ``[n]`` int32.
    Positions past the true prompt length write garbage rows the
    length mask never exposes."""
    n = page_ids.shape[0]
    if pages.ndim == 4:
        # a latent pool [L, P, page, R] takes rows [L, T_pad, C], padded
        # to the pool's row
        l, t_pad, c = seq_kv.shape
        page, r = pages.shape[2:]
        if t_pad != n * page:
            raise ValueError(f"prefill run of {t_pad} rows does not fill "
                             f"{n} pages of {page}")
        rows = jnp.pad(seq_kv, ((0, 0), (0, 0), (0, r - c)))
        return pages.at[:, page_ids].set(
            rows.reshape(l, n, page, r).astype(pages.dtype))
    l, nkv, t_pad, d = seq_kv.shape
    rows, page, width = pages.shape[2:]
    if t_pad != n * page:
        raise ValueError(f"prefill run of {t_pad} rows does not fill "
                         f"{n} pages of {page}")
    if width != d:
        # packed heads: [L, rows, p, T, D] -> [L, rows, T, p * D]
        seq_kv = seq_kv.reshape(l, rows, width // d, t_pad, d).transpose(
            0, 1, 3, 2, 4).reshape(l, rows, t_pad, width)
    # [L, rows, n, page, width] -> [L, n, rows, page, width]
    chunks = seq_kv.reshape(l, rows, n, page, width).transpose(
        0, 2, 1, 3, 4)
    return pages.at[:, page_ids].set(chunks)


# ----------------------------------------------------------------------
# Bucketed-length jit policy
# ----------------------------------------------------------------------

def ctx_pages_bucket(max_tokens: int, page_size: int, bucket_pages: int,
                     max_pages: int) -> int:
    """The (static) number of pages the decode step gathers for a batch
    whose longest request spans ``max_tokens`` written positions:
    rounded up to ``bucket_pages`` granularity so a request joining
    with a slightly longer context reuses the previous compilation —
    the bucketed-length jit policy.  Clamped to ``max_pages``."""
    if max_tokens < 1:
        max_tokens = 1
    pages = -(-max_tokens // page_size)
    pages = -(-pages // bucket_pages) * bucket_pages
    return min(max(pages, bucket_pages), max_pages)


def prompt_pad(t0: int, bucket: int) -> int:
    """Prompt length padded to the prefill bucket (one compilation per
    padded length, not per prompt length)."""
    return -(-max(t0, 1) // bucket) * bucket


# ----------------------------------------------------------------------
# Host-side page allocator
# ----------------------------------------------------------------------

class PagePool:
    """Deterministic LIFO free-list over pages ``1..num_pages-1``
    (page 0 is scratch).  All host-side Python: allocation order is a
    pure function of the alloc/free call sequence, which the engine
    derives from its seeded arrival trace — so a drill's page
    placement (and therefore its jitted gathers) replays exactly.

    Beside the list the pool keeps one byte a page id (``_is_free``: 1
    while the id is on the list), in step with it in :meth:`alloc` and
    :meth:`free`.  It decides nothing about WHICH page goes out — the
    list alone does — and exists for the double-free check, which asks
    it in O(1) where a scan of the list cost a retirement the POOL's
    size a page (``p in self._free``: 250 ms for 640 pages with
    33 000 free), so a free costs the host the request's own pages."""

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError(f"num_pages={num_pages} must be >= 2")
        self.num_pages = num_pages
        # LIFO: lowest ids on top first, and freed pages come back on
        # top — eviction's pages are the next admission's pages
        self._free = list(range(num_pages - 1, 0, -1))
        self._is_free = bytearray(b"\x01") * num_pages
        self._is_free[SCRATCH_PAGE] = 0

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return (self.num_pages - 1) - len(self._free)

    @property
    def occupancy(self) -> float:
        """Allocated fraction of the allocatable pool (scratch page
        excluded) — the cache-occupancy gauge the engine reports."""
        total = self.num_pages - 1
        return self.used_pages / total if total else 0.0

    def alloc(self, n: int) -> list[int] | None:
        """Pop ``n`` pages, or ``None`` (no partial allocation) when
        fewer remain — the caller then defers admission or evicts."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} pages")
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        for p in out:
            self._is_free[p] = 0
        return out

    def free(self, pages) -> None:
        """Return pages to the pool (reverse order, so re-allocating
        the same count yields the same ids the evictee held)."""
        for p in reversed(list(pages)):
            if not 0 < p < self.num_pages:
                raise ValueError(f"page id {p} out of range")
            if self._is_free[p]:
                raise ValueError(f"double free of page {p}")
            self._is_free[p] = 1
            self._free.append(p)


class ShardedPagePool:
    """The EP-sharded twin of :class:`PagePool`: the page slab is
    partitioned into ``shards`` equal contiguous blocks (matching the
    ``P(None, "ep")`` device partitioning of the cache arrays), each
    with its OWN deterministic LIFO free list over shard-LOCAL ids.

    Ids handed out are local — exactly what the EP decode step's
    per-shard block tables index; each shard's local page 0 is its own
    scratch (so every device's slab has a scratch at the same local
    offset).  :meth:`to_global` maps to slab-global ids for the eager
    whole-page writes (prefill store) that address the unpartitioned
    array view."""

    def __init__(self, num_pages: int, shards: int):
        if shards < 1:
            raise ValueError(f"shards={shards} must be >= 1")
        if num_pages % shards:
            raise ValueError(f"num_pages={num_pages} must divide "
                             f"evenly across {shards} shards")
        self.num_pages = num_pages
        self.shards = shards
        self.pages_per_shard = num_pages // shards
        if self.pages_per_shard < 2:
            raise ValueError(
                f"num_pages={num_pages} leaves fewer than 2 pages per "
                f"shard across {shards} shards (each shard reserves "
                f"its own scratch page)")
        self._pools = [PagePool(self.pages_per_shard)
                       for _ in range(shards)]

    @property
    def free_pages(self) -> int:
        return sum(p.free_pages for p in self._pools)

    @property
    def used_pages(self) -> int:
        return sum(p.used_pages for p in self._pools)

    @property
    def occupancy(self) -> float:
        total = self.num_pages - self.shards   # one scratch per shard
        return self.used_pages / total if total else 0.0

    def shard_free_pages(self, shard: int) -> int:
        return self._pools[shard].free_pages

    def alloc(self, n: int, shard: int) -> list[int] | None:
        """Pop ``n`` shard-LOCAL page ids from ``shard``'s free list
        (``None`` on shortfall — no partial allocation, no cross-shard
        spill: a slot's pages must live on its shard's device)."""
        return self._pools[shard].alloc(n)

    def free(self, pages, shard: int) -> None:
        self._pools[shard].free(pages)

    def to_global(self, pages, shard: int) -> list[int]:
        """Shard-local -> slab-global ids (the eager whole-page write
        sites address the global array view)."""
        base = shard * self.pages_per_shard
        return [base + int(p) for p in pages]
