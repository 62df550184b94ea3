"""Serving subsystem: paged KV cache, continuous-batching engine,
decode-shaped planner split, serving observability.

The headline drill is the ISSUE acceptance: a seeded multi-request CPU
run sustaining 8 concurrent requests with joins and retirements
mid-flight whose outputs are token-bit-equal to the same prompts
decoded one at a time through ``generate()``.
"""

import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashmoe_tpu.config import BENCH_CONFIGS, MoEConfig
from flashmoe_tpu.models.generate import generate
from flashmoe_tpu.models.transformer import init_params
from flashmoe_tpu.serving import engine as eng
from flashmoe_tpu.serving.engine import (
    Request, ServeConfig, ServingEngine,
)
from flashmoe_tpu.ops.attention import gather_ctx, store_kv
from flashmoe_tpu.serving.kvcache import (
    SCRATCH_PAGE, PagePool, ShardedPagePool, ctx_pages_bucket,
    init_paged_cache, prompt_pad, store_prefill,
)
from flashmoe_tpu.serving.loadgen import build_requests, tiny_config
from flashmoe_tpu.utils.telemetry import FlightRecorder, Metrics

CFG = tiny_config()


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def prompts():
    return jax.random.randint(jax.random.PRNGKey(1), (8, 8), 0,
                              CFG.vocab_size)


def _requests(prompts, n, max_new=6, **kw):
    return [Request(rid=i, prompt=tuple(int(t) for t in prompts[i]),
                    max_new_tokens=max_new, **kw) for i in range(n)]


def _oracle(params, prompts, i, max_new=6):
    return np.asarray(generate(params, prompts[i:i + 1], CFG,
                               max_new_tokens=max_new))[0]


# ----------------------------------------------------------------------
# Paged KV cache
# ----------------------------------------------------------------------

def test_page_pool_lifo_reuse_and_errors():
    pool = PagePool(8)                      # pages 1..7 allocatable
    assert pool.free_pages == 7
    a = pool.alloc(3)
    b = pool.alloc(2)
    assert a == [1, 2, 3] and b == [4, 5]
    assert pool.used_pages == 5
    assert pool.alloc(3) is None            # no partial allocation
    pool.free(a)
    # LIFO: the freed pages come back in the SAME order — an evictee's
    # pages are exactly the next admission's pages
    assert pool.alloc(3) == [1, 2, 3]
    with pytest.raises(ValueError, match="double free"):
        pool.free(b + b)
    with pytest.raises(ValueError, match="out of range"):
        pool.free([SCRATCH_PAGE])


class _ListPool:
    """The allocator as it stood before the state-by-id array: the LIFO
    list alone, the double-free check a scan of it.  The plain reference
    the pools are held to, call for call."""

    def __init__(self, num_pages):
        self.num_pages = num_pages
        self._free = list(range(num_pages - 1, 0, -1))

    def alloc(self, n):
        if n > len(self._free):
            return None
        return [self._free.pop() for _ in range(n)]

    def free(self, pages):
        for p in reversed(list(pages)):
            if not 0 < p < self.num_pages:
                raise ValueError(f"page id {p} out of range")
            if p in self._free:
                raise ValueError(f"double free of page {p}")
            self._free.append(p)


def _call(fn, *args):
    """What a call gave: its value, or the error it raised."""
    try:
        return fn(*args)
    except ValueError as e:
        return f"ValueError: {e}"


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("shards", [None, 2, 4])
def test_page_pools_equal_the_list_only_allocator(shards, seed):
    """Seeded random alloc / free sequences (whole requests, the tails a
    speculative roll-back frees, frees that fill the pool, every kind of
    bad free): the pool and the list-only reference hand out the same
    ids in the same order and raise the same errors on the same calls."""
    per = 48
    if shards is None:
        pool, refs = PagePool(per), [_ListPool(per)]
        on = lambda shard: ()
    else:
        pool = ShardedPagePool(per * shards, shards)
        refs = [_ListPool(per) for _ in range(shards)]
        on = lambda shard: (shard,)
    rng = np.random.default_rng(seed)
    held = []                                   # (shard, pages) in flight
    gone = []                                   # freed once already
    raised = set()

    def both(op, shard, arg):
        got = _call(getattr(pool, op), arg, *on(shard))
        want = _call(getattr(refs[shard], op), arg)
        assert got == want, (op, shard, arg)
        if isinstance(got, str):
            raised.add(got.split(" ")[1])       # "double" / "page"
        assert pool.free_pages == sum(len(r._free) for r in refs)
        assert pool.used_pages == len(refs) * (per - 1) - pool.free_pages
        return got

    for _ in range(600):
        shard = int(rng.integers(len(refs)))
        kind = rng.choice(["alloc", "alloc", "free", "tail", "drain",
                           "bad"], p=[0.3, 0.2, 0.2, 0.15, 0.05, 0.1])
        mine = [k for k, (sh, _) in enumerate(held) if sh == shard]
        if kind == "alloc":
            # now and then more than remain: None, and nothing changes
            n = int(rng.integers(0, 14)) if rng.random() < 0.9 else per
            short = n > len(refs[shard]._free)
            pages = both("alloc", shard, n)
            assert (pages is None) == short
            if pages:
                held.append((shard, pages))
        elif kind == "free" and mine:
            sh, pages = held.pop(mine[int(rng.integers(len(mine)))])
            assert both("free", sh, pages) is None
            gone.append((sh, pages))
        elif kind == "tail" and mine:
            sh, pages = held[mine[int(rng.integers(len(mine)))]]
            keep = int(rng.integers(1, len(pages) + 1))
            assert both("free", sh, pages[keep:]) is None
            del pages[keep:]
        elif kind == "drain":
            for k in reversed(mine):
                sh, pages = held.pop(k)
                assert both("free", sh, pages) is None
                gone.append((sh, pages))
            assert pool.occupancy == pytest.approx(
                sum(len(pg) for _, pg in held)
                / (len(refs) * (per - 1)))
        elif kind == "bad":
            live = [held[k][1] for k in mine]
            stale = [pg for sh, pg in gone if sh == shard
                     and not set(pg) & {p for l in live for p in l}
                     and set(pg) <= set(refs[shard]._free)]
            case = int(rng.integers(4))
            if case == 0 and stale:             # freed by an earlier call
                both("free", shard, stale[-1])
            elif case == 1 and live:            # an id twice in ONE call:
                sh, pages = held.pop(mine[0])   # all of it goes back, then
                both("free", shard, pages + pages[:1])      # the error
                gone.append((sh, pages))
            elif case == 2:                     # the scratch page
                both("free", shard, (live[0] if live else []) + [0])
            elif live:                          # one past the last id,
                sh, pages = held.pop(mine[0])   # after the good ones
                both("free", shard, [per] + pages)
                gone.append((sh, pages))
    assert raised == {"double", "page"}
    # what is left comes out in the same order, to the last page
    for shard, ref in enumerate(refs):
        n = len(ref._free)
        assert both("alloc", shard, n + 1) is None
        assert len(both("alloc", shard, n)) == n


class _CountingList(list):
    """A free list that counts what would scan it."""
    scans = 0

    def _scan(name):
        def method(self, *args):
            type(self).scans += 1
            return getattr(list, name)(self, *args)
        return method

    __contains__, index, count = (_scan("__contains__"), _scan("index"),
                                  _scan("count"))
    __iter__, remove = _scan("__iter__"), _scan("remove")


def test_free_costs_the_requests_pages_not_the_pools():
    """A retirement at the longgen cell's size (640 pages into a pool of
    40 960 with 33 000 free): no scan of the free list, and far under
    the 250 ms the list's own membership test took."""
    pool = PagePool(40_960)
    held = [pool.alloc(640) for _ in range(12)]
    rest = pool.alloc(pool.free_pages - 33_000 + 640)
    pool._free = _CountingList(pool._free)
    _CountingList.scans = 0
    best = float("inf")
    for pages in held:
        assert pool.free_pages == 33_000 - 640
        t0 = time.perf_counter()
        pool.free(pages)
        best = min(best, time.perf_counter() - t0)
        assert pool.alloc(640) == pages         # LIFO, as ever
    assert _CountingList.scans == 0
    assert best < 0.020
    with pytest.raises(ValueError, match="double free"):
        pool.free(rest[:1] + held[0][:1] + rest[:1])
    assert _CountingList.scans == 0


def test_ctx_bucketing():
    # 9 tokens at page 4, bucket 2 -> 3 pages rounds up to 4
    assert ctx_pages_bucket(9, 4, 2, 8) == 4
    assert ctx_pages_bucket(1, 4, 2, 8) == 2
    assert ctx_pages_bucket(10_000, 4, 2, 8) == 8   # clamped
    assert prompt_pad(5, 8) == 8
    assert prompt_pad(8, 8) == 8
    assert prompt_pad(9, 8) == 16


def test_paged_store_gather_roundtrip():
    """store_prefill + store_kv + gather_ctx reproduce a dense K/V
    run exactly (the block-table indirection is pure reindexing)."""
    cache = init_paged_cache(CFG, num_pages=8, page_size=4)
    nkv, dh = CFG.resolved_num_kv_heads, CFG.resolved_head_dim
    l = CFG.num_layers
    seq = jax.random.normal(jax.random.PRNGKey(2), (l, nkv, 8, dh),
                            CFG.dtype)
    page_ids = jnp.asarray([3, 5], jnp.int32)       # non-contiguous
    kp = store_prefill(cache.k_pages, seq, page_ids)
    # one decode token at position 8 goes into a third page
    tok = jax.random.normal(jax.random.PRNGKey(3), (1, nkv, dh),
                            CFG.dtype)
    kp = store_kv(kp, 0, tok[:, None], jnp.asarray([[6]]),
                  jnp.asarray([[0]]))
    bt = jnp.asarray([[3, 5, 6]], jnp.int32)        # this slot's table
    got = gather_ctx(kp, 0, bt)                     # [1, nkv, 12, dh]
    np.testing.assert_array_equal(np.asarray(got[0, :, :8]),
                                  np.asarray(seq[0]))
    np.testing.assert_array_equal(np.asarray(got[0, :, 8]),
                                  np.asarray(tok[0]))


def test_engine_rejects_capacity_configs(params):
    with pytest.raises(ValueError, match="dropless"):
        ServingEngine(params, CFG.replace(drop_tokens=True))


def test_serve_config_validation():
    with pytest.raises(ValueError, match="prompt_bucket"):
        ServeConfig(page_size=8, prompt_bucket=4)
    with pytest.raises(ValueError, match="ctx_bucket_pages"):
        ServeConfig(ctx_bucket_pages=99, max_pages_per_slot=4)
    with pytest.raises(ValueError, match="scratch"):
        ServeConfig(num_pages=1)
    with pytest.raises(ValueError, match="empty prompt"):
        Request(rid=0, prompt=())
    with pytest.raises(ValueError, match="top_p"):
        Request(rid=0, prompt=(1,), top_p=0.0)


def test_submit_rejects_requests_the_pool_can_never_serve(params):
    """A request whose lifetime exceeds the whole page pool must be
    rejected at submit — not spin the engine through max_steps with a
    permanently-starved queue head."""
    engine = ServingEngine(
        params, CFG,
        ServeConfig(max_batch=2, page_size=8, num_pages=4,
                    max_pages_per_slot=8, ctx_bucket_pages=1,
                    prompt_bucket=8))
    # slot context (64) admits it, but the pool holds only 3 pages
    with pytest.raises(ValueError, match="pool"):
        engine.submit(Request(rid=0, prompt=tuple(range(1, 25)),
                              max_new_tokens=8))


# ----------------------------------------------------------------------
# The acceptance drill
# ----------------------------------------------------------------------

def test_drill_8_concurrent_bit_equal_vs_generate(params, prompts):
    """Seeded drill: 8 concurrent requests joining (staggered
    arrivals) and retiring mid-flight; engine outputs token-bit-equal
    to one-at-a-time ``generate()``; TTFT/TPOT/queue-depth/occupancy
    flow through the flight recorder."""
    mx = Metrics()
    recorder = FlightRecorder()
    engine = ServingEngine(
        params, CFG,
        ServeConfig(max_batch=8, page_size=8, num_pages=32,
                    max_pages_per_slot=4, ctx_bucket_pages=1,
                    prompt_bucket=8),
        recorder=recorder, metrics_obj=mx)
    reqs = _requests(prompts, 8)
    out = engine.run(reqs, arrivals=[0, 0, 0, 0, 1, 1, 2, 3])

    s = engine.summary()
    assert s["completed"] == 8
    assert s["max_active"] == 8                 # sustains 8 concurrent
    admits = [d for d in mx.decisions
              if d["decision"] == "serve.admit"]
    retires = [d for d in mx.decisions
               if d["decision"] == "serve.retire"]
    assert len(admits) == 8 and len(retires) == 8
    # joins happen mid-flight (after step 0) and before the first
    # retirement completes the run
    assert max(d["step"] for d in admits) > 0
    assert min(d["step"] for d in retires) \
        > min(d["step"] for d in admits)
    # bit-equal token streams vs the single-request decoder
    for i in range(8):
        np.testing.assert_array_equal(
            np.asarray(out[i]), _oracle(params, prompts, i))
    # observability: TTFT/TPOT on retires + step records carry queue
    # depth and cache occupancy
    assert all(d["ttft_ms"] is not None for d in retires)
    assert all(d["tpot_ms"] is not None for d in retires)
    steps = [r for r in recorder.records
             if r.get("kind") == "serve_step"]
    req_recs = [r for r in recorder.records
                if r.get("kind") == "serve_request"]
    assert steps and len(req_recs) == 8
    assert all("queue_depth" in r and "cache_occupancy" in r
               for r in steps)
    assert s["ttft_ms_mean"] is not None


def test_eviction_under_page_pressure_bit_equal(params, prompts):
    """A starved pool forces preemption: the youngest request is
    evicted (serve.evict), its pages are reused, it re-prefills and
    completes — outputs still bit-equal."""
    mx = Metrics()
    engine = ServingEngine(
        params, CFG,
        ServeConfig(max_batch=4, page_size=8, num_pages=8,
                    max_pages_per_slot=4, ctx_bucket_pages=1,
                    prompt_bucket=8),
        metrics_obj=mx)
    out = engine.run(_requests(prompts, 4, max_new=10))
    s = engine.summary()
    assert s["evictions"] > 0 and s["completed"] == 4
    evicts = [d for d in mx.decisions
              if d["decision"] == "serve.evict"]
    resumed = [d for d in mx.decisions
               if d["decision"] == "serve.admit" and d["resumed"]]
    assert evicts and len(resumed) == len(evicts)
    for i in range(4):
        np.testing.assert_array_equal(
            np.asarray(out[i]), _oracle(params, prompts, i,
                                        max_new=10))


def test_bucketed_jit_policy(params, prompts):
    """Requests with different prompt lengths inside one bucket share
    one prefill compilation, and the decode gather length stays on
    bucket boundaries — the join-without-recompile policy."""
    engine = ServingEngine(
        params, CFG,
        ServeConfig(max_batch=4, page_size=8, num_pages=32,
                    max_pages_per_slot=4, ctx_bucket_pages=2,
                    prompt_bucket=8))
    reqs = [Request(rid=i, prompt=tuple(int(t) for t in
                                        prompts[i][:4 + i]),
                    max_new_tokens=4) for i in range(3)]
    engine.run(reqs, arrivals=[0, 1, 2])
    s = engine.summary()
    assert s["prefill_buckets"] == [8]     # 3 lengths, one bucket
    assert s["decode_buckets"] == [2]      # one ctx bucket


def test_sampled_requests_deterministic(params, prompts):
    """Per-request seeded sampling: identical traces produce identical
    outputs, and sampling params ride per request."""
    def run():
        engine = ServingEngine(
            params, CFG,
            ServeConfig(max_batch=4, page_size=8, num_pages=32,
                        max_pages_per_slot=4, ctx_bucket_pages=1,
                        prompt_bucket=8))
        reqs = _requests(prompts, 3, max_new=5, temperature=0.8,
                         top_k=20, seed=11)
        return engine.run(reqs)

    a, b = run(), run()
    for i in range(3):
        np.testing.assert_array_equal(np.asarray(a[i]),
                                      np.asarray(b[i]))
        toks = a[i][8:]
        assert all(0 <= t < CFG.vocab_size for t in toks)


# ----------------------------------------------------------------------
# The sampler derives its keys in its own program (ISSUE 26)
# ----------------------------------------------------------------------

KEY_SEEDS = (0, 1, 11, 2**31 - 1, 2**31, 2**32 - 1, 2**32 + 5, -1)
KEY_INDICES = (0, 1, 511, 100000)


def _host_key(seed, n):
    """The key formula as the host loop had it, one row at a time."""
    return np.asarray(jax.random.fold_in(jax.random.PRNGKey(seed), n))


@pytest.mark.parametrize("n", KEY_INDICES)
@pytest.mark.parametrize("seed", KEY_SEEDS)
def test_token_keys_bit_equal_to_host_formula(seed, n):
    """Every seed ``Request`` accepts: the traced derivation, fed what
    ``_sampler_rows`` uploads, gives the host statement's key data."""
    seeds, index, *_ = eng._sampler_rows(
        [(Request(rid=0, prompt=(1,), seed=seed), n)])
    assert seeds.dtype == np.uint32 and index.dtype == np.int32
    got = np.asarray(jax.jit(eng._token_keys)(seeds, index))
    assert got.dtype == np.uint32
    np.testing.assert_array_equal(got, _host_key(seed, n)[None])


@pytest.mark.parametrize("draw", [0, 1, 2])
def test_sampler_entry_equals_numerics_on_host_keys(draw):
    """Greedy, temperature, top-k and top-p rows mixed in one batch,
    with an idle row: the jitted entry returns the tokens the
    key-taking numerics return on host-derived keys."""
    rng = np.random.default_rng(draw)
    knobs = [dict(), dict(temperature=0.7), dict(temperature=1.3, top_k=5),
             dict(temperature=0.9, top_p=0.6), None,
             dict(temperature=1.0, top_k=9, top_p=0.8), dict(top_k=3),
             dict(temperature=2.0)]
    rows = [None if kw is None else
            (Request(rid=j, prompt=(1,), seed=KEY_SEEDS[j], **kw),
             int(rng.integers(0, 100001)))
            for j, kw in enumerate(knobs)]
    logits = jnp.asarray(rng.normal(size=(len(rows), 96)) * 3,
                         jnp.float32)
    seeds, index, temps, top_ks, top_ps = eng._sampler_rows(rows)
    keys = np.stack([_host_key(0, 0) if row is None
                     else _host_key(row[0].seed, row[1]) for row in rows])
    want = np.asarray(jax.jit(eng._sample_with_keys)(
        logits, keys, temps, top_ks, top_ps))
    got = np.asarray(eng._sample_dynamic(
        logits, seeds, index, temps, top_ks, top_ps))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        got[[0, 4, 6]], np.argmax(np.asarray(logits), -1)[[0, 4, 6]])
    assert (got != np.argmax(np.asarray(logits), -1)).any(), \
        "no sampled row left the argmax: the keys were not exercised"


# ----------------------------------------------------------------------
# The sampler does only what its rows ask for (ISSUE 28)
# ----------------------------------------------------------------------

def _two_sort_sampler(logits, keys, temps, top_ks, top_ps):
    """The parent's ``_sample_with_keys``, verbatim: two sorts of the
    whole vocabulary, a softmax, a running sum and a draw for every
    row, thrown away where ``temperature <= 0``.  The reference."""
    v = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    neg = jnp.asarray(-1e30, jnp.float32)
    scaled = logits.astype(jnp.float32) / jnp.maximum(
        temps, 1e-6)[:, None]
    sort_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(
        sort_desc, jnp.clip(top_ks - 1, 0, v - 1)[:, None], axis=1)
    use_k = (top_ks > 0) & (top_ks < v)
    scaled = jnp.where(use_k[:, None] & (scaled < kth), neg, scaled)
    sort_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sort_desc, axis=-1)
    csum = jnp.cumsum(probs, axis=-1)
    keep = (csum - probs) < top_ps[:, None]
    thresh = jnp.min(
        jnp.where(keep, sort_desc, jnp.inf), axis=-1, keepdims=True)
    scaled = jnp.where(scaled < thresh, neg, scaled)
    sampled = jax.vmap(
        lambda kk, ll: jax.random.categorical(kk, ll))(keys, scaled)
    return jnp.where(temps <= 0.0, greedy, sampled.astype(jnp.int32))


@jax.jit
def _two_sort_dynamic(logits, seeds, index, temps, top_ks, top_ps):
    return _two_sort_sampler(logits, eng._token_keys(seeds, index),
                             temps, top_ks, top_ps)


SAMPLER_V = 96
# what a batch's rows ask of the sampler: ``None`` is an idle row
SAMPLER_BATCHES = {
    "greedy": [dict(), dict(top_k=3), dict(top_p=0.5), dict(),
               dict(top_k=7, top_p=0.2), dict()],
    "greedy+idle": [dict(), None, dict(top_k=3), None],
    "drawn": [dict(temperature=0.7), dict(temperature=1.3),
              dict(temperature=2.0, top_k=SAMPLER_V),
              dict(temperature=0.2, top_k=SAMPLER_V + 9),
              dict(temperature=1.0, top_p=1.0)],
    "drawn+greedy+idle": [dict(temperature=0.7), dict(), None,
                          dict(temperature=1.1, top_k=0), dict(top_k=2)],
    "top_k": [dict(temperature=0.7, top_k=1),
              dict(temperature=1.3, top_k=5),
              dict(temperature=0.9, top_k=SAMPLER_V - 1),
              dict(temperature=2.0, top_k=40)],
    "top_p": [dict(temperature=0.9, top_p=0.6),
              dict(temperature=1.5, top_p=0.05),
              dict(temperature=0.6, top_p=0.99)],
    "top_k+top_p": [dict(temperature=1.0, top_k=9, top_p=0.8),
                    dict(temperature=0.8, top_k=20, top_p=0.9),
                    dict(temperature=1.7, top_k=3, top_p=0.3)],
    "one_truncates": [dict(temperature=0.7), dict(), None,
                      dict(temperature=0.9, top_p=0.6),
                      dict(temperature=1.3), dict(top_p=0.1)],
    "mixed": [dict(), dict(temperature=0.7),
              dict(temperature=1.3, top_k=5),
              dict(temperature=0.9, top_p=0.6), None,
              dict(temperature=1.0, top_k=9, top_p=0.8), dict(top_k=3),
              dict(temperature=2.0)],
}


def _sampler_batch(name, draw, ties=False):
    """``(logits, *knobs)`` for one of :data:`SAMPLER_BATCHES`; with
    ``ties`` the logits take few distinct values, so the k-th value and
    the nucleus threshold are shared by several entries."""
    rng = np.random.default_rng(draw)
    rows = [None if kw is None else
            (Request(rid=j, prompt=(1,), seed=KEY_SEEDS[j], **kw),
             int(rng.integers(0, 100001)))
            for j, kw in enumerate(SAMPLER_BATCHES[name])]
    logits = rng.normal(size=(len(rows), SAMPLER_V)) * 3
    if ties:
        logits = np.round(logits)
    return (jnp.asarray(logits, jnp.float32),) + eng._sampler_rows(rows)


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
@pytest.mark.parametrize("draw", [0, 1])
@pytest.mark.parametrize("batch", list(SAMPLER_BATCHES))
def test_sampler_tokens_equal_the_two_sort_sampler(batch, draw, ties):
    """Whatever the batch's rows ask for, the program that skips what
    they do not ask for returns the two-sort sampler's tokens, bit for
    bit: the greedy arm, the draw with no sort, and the one sort whose
    top-k mask is applied to the sorted row."""
    args = _sampler_batch(batch, draw, ties)
    got = np.asarray(eng._sample_dynamic(*args))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, np.asarray(_two_sort_dynamic(*args)))
    temps = args[3]
    np.testing.assert_array_equal(
        got[temps <= 0], np.argmax(np.asarray(args[0]), -1)[temps <= 0])


@pytest.mark.parametrize("batch", ["drawn", "top_k", "top_p",
                                   "top_k+top_p", "mixed"])
def test_sampler_rows_equal_sample_tokens(batch):
    """Row by row, the engine's sampler returns what ``generate()``'s
    ``sample_tokens`` returns for that row alone, given the row's key
    and its knobs as static values."""
    from flashmoe_tpu.models.generate import sample_tokens

    logits, seeds, index, temps, top_ks, top_ps = _sampler_batch(batch, 2)
    got = np.asarray(eng._sample_dynamic(
        logits, seeds, index, temps, top_ks, top_ps))
    keys = np.asarray(jax.jit(eng._token_keys)(seeds, index))
    for j in range(len(got)):
        want = sample_tokens(
            logits[j:j + 1], keys[j], temperature=float(temps[j]),
            top_k=int(top_ks[j]), top_p=float(top_ps[j]))
        assert got[j] == int(want[0]), (j, SAMPLER_BATCHES[batch][j])


def test_a_row_without_nucleus_keeps_its_whole_distribution():
    """``top_p == 1`` asks for no truncation and gets none, in either
    arm: beside a truncating row, every logit of a drawn row reaches
    its draw.  (The two-sort sampler let the rounding of the running
    sum drop a tail of such a row, which the no-sort arm never does:
    a row's tokens must not depend on what its neighbours ask for.)"""
    rng = np.random.default_rng(0)
    v = 4096
    logits = jnp.asarray(rng.normal(size=(2, v)) * 3, jnp.float32)
    args = (logits, np.zeros((2, 2), np.uint32),
            np.array([0.7, 0.7], np.float32), np.zeros((2,), np.int32),
            np.array([1.0, 0.5], np.float32))
    with pytest.MonkeyPatch.context() as mp:
        # the "draw" reports how many logits reached it
        mp.setattr(jax.random, "categorical",
                   lambda key, row: jnp.sum(row > -1e29))
        kept = np.asarray(jax.jit(
            lambda *a: eng._sample_with_keys(*a))(*args))
        was = np.asarray(jax.jit(
            lambda *a: _two_sort_sampler(*a))(*args))
    assert kept[0] == v and 0 < kept[1] < v // 2, kept
    assert was[0] < v and was[1] == kept[1], was


def _cond_eqns(jaxpr):
    """The ``cond`` equations of ``jaxpr``, looking through ``jit``
    calls but not into a ``cond``'s branches."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            yield eqn
        elif eqn.primitive.name in ("jit", "pjit"):
            yield from _cond_eqns(eqn.params["jaxpr"].jaxpr)


def _prims_outside_conds(jaxpr):
    """Primitive names of ``jaxpr`` that no ``cond`` guards."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("jit", "pjit"):
            yield from _prims_outside_conds(eqn.params["jaxpr"].jaxpr)
        elif eqn.primitive.name != "cond":
            yield eqn.primitive.name


@pytest.fixture(scope="module")
def sampler_jaxpr():
    return jax.make_jaxpr(eng._sample_dynamic)(
        *_sampler_batch("mixed", 0)).jaxpr


def test_sampler_sorts_only_inside_a_cond(sampler_jaxpr):
    from flashmoe_tpu.staticcheck.graph import prim_counts

    outside = set(_prims_outside_conds(sampler_jaxpr))
    assert not outside & {"sort", "cumsum", "random_bits", "argmax"}, outside
    assert prim_counts(sampler_jaxpr)["sort"] == 1


def test_sampler_greedy_arm_is_an_argmax(sampler_jaxpr):
    from flashmoe_tpu.staticcheck.graph import prim_counts

    (cond,) = _cond_eqns(sampler_jaxpr)
    greedy, drawn = (prim_counts(br.jaxpr) for br in cond.params["branches"])
    assert greedy["argmax"] == 1
    assert not [p for p in greedy
                if p in ("sort", "cumsum", "cond") or "random" in p
                or "threefry" in p], greedy
    assert drawn["random_bits"] == 1 and drawn["sort"] == 1


def test_sampler_draws_without_a_sort_where_no_row_truncates(
        sampler_jaxpr):
    """Inside the drawn arm a second ``cond`` guards the sort: its
    other arm passes the scaled logits on untouched."""
    from flashmoe_tpu.staticcheck.graph import prim_counts

    (outer,) = _cond_eqns(sampler_jaxpr)
    drawn = outer.params["branches"][1].jaxpr
    assert "sort" not in set(_prims_outside_conds(drawn))
    (inner,) = _cond_eqns(drawn)
    plain, truncated = (prim_counts(br.jaxpr)
                        for br in inner.params["branches"])
    assert not plain, plain
    assert truncated["sort"] == 1 and truncated["cumsum"] == 1


SAMPLED_KNOBS = {
    "greedy": dict(),
    "drawn": dict(temperature=0.8, seed=5),
    "top_p": dict(temperature=0.8, top_p=0.9, seed=5),
    "top_k": dict(temperature=0.8, top_k=20, seed=5),
    "top_k_of_all": dict(temperature=0.8, top_k=CFG.vocab_size, seed=5),
}


@pytest.mark.parametrize("third", list(SAMPLED_KNOBS))
def test_step_records_count_what_the_sampler_was_asked(params, prompts,
                                                       third):
    """Two greedy requests and a third with the knobs named: every
    ``serve_step`` record says how many rows decoded, how many were
    drawn and how many made the program sort, and the counter
    ``serve.sample_sort_steps`` is the number of steps that sorted
    (0, and present, on traffic that never truncates)."""
    mx, recorder = Metrics(), FlightRecorder()
    engine = ServingEngine(params, CFG, _spec_serve(), metrics_obj=mx,
                           recorder=recorder)
    reqs = _requests(prompts, 2, max_new=5) + [
        Request(rid=2, prompt=tuple(int(t) for t in prompts[2]),
                max_new_tokens=3, **SAMPLED_KNOBS[third])]
    engine.run(reqs, arrivals=[0, 0, 1])
    steps = [r for r in recorder.records if r.get("kind") == "serve_step"]
    sampling = [r for r in steps if r["tokens"]]
    assert [r["sample_rows"] for r in steps] == [r["tokens"] for r in steps]
    assert sum(r["sample_rows"] for r in steps) == 5 + 5 + 3
    drawn = 0 if third == "greedy" else 3
    sorted_ = 3 if third in ("top_p", "top_k") else 0
    assert [r["sample_drawn"] for r in steps].count(1) == drawn
    assert [r["sample_sorted"] for r in steps].count(1) == sorted_
    assert sum(r["sample_drawn"] for r in steps) == drawn
    assert sum(r["sample_sorted"] for r in steps) == sorted_
    assert all(r["sample_sorted"] <= r["sample_drawn"] <= r["sample_rows"]
               for r in steps)
    assert mx.counters["serve.sample_steps"] == len(sampling)
    assert "serve.sample_sort_steps" in mx.counters
    assert mx.counters["serve.sample_sort_steps"] == sorted_


def _watch_host_traffic(monkeypatch) -> list:
    """While the patches hold, an eager ``jax.random`` call raises, and
    every read-back of a device array is appended to the returned list
    as ``(shape, dtype)``: ``np.asarray`` / ``np.array`` (on the CPU
    they read a device array through the buffer protocol, so the numpy
    entry is where it shows) and ``ArrayImpl._value`` (``int()``,
    ``float()``, ``bool()``, ``.tolist()``, ``.item()``,
    ``jax.device_get``)."""
    from jax._src.array import ArrayImpl

    reads = []

    def traced_only(name):
        real = getattr(jax.random, name)

        def guarded(*args, **kw):
            if not any(isinstance(a, jax.core.Tracer)
                       for a in list(args) + list(kw.values())):
                raise AssertionError(
                    f"eager jax.random.{name} inside engine.step()")
            return real(*args, **kw)
        return guarded

    for name in ("PRNGKey", "key", "fold_in", "split", "key_data",
                 "wrap_key_data", "categorical"):
        monkeypatch.setattr(jax.random, name, traced_only(name))

    def noting(real):
        def spy(a, *args, **kw):
            if isinstance(a, jax.Array):
                reads.append((tuple(a.shape), str(a.dtype)))
            return real(a, *args, **kw)
        return spy

    monkeypatch.setattr(np, "asarray", noting(np.asarray))
    monkeypatch.setattr(np, "array", noting(np.array))
    real_value = ArrayImpl._value

    def value(arr):
        reads.append((tuple(arr.shape), str(arr.dtype)))
        return real_value.fget(arr)

    monkeypatch.setattr(ArrayImpl, "_value", property(value))
    return reads


@pytest.mark.parametrize("speculate", [None, 3],
                         ids=["plain", "speculative"])
def test_step_reads_back_only_the_sampled_tokens(params, prompts,
                                                 spec_prompts, speculate):
    """No ``jax.random`` call outside a jit and one device value back
    per sampler call — the tokens — on the plain and on the
    speculative arm, and the streams are those of an unwatched run."""
    serve = _spec_serve(speculate=speculate)
    src = spec_prompts if speculate else prompts
    # two greedy requests (on the repetitive prompts they draft, so the
    # verify program runs) beside two sampled ones (they use the keys)
    reqs = (_requests(src, 2, max_new=8)
            + _requests(src, 4, max_new=8, temperature=0.8, top_k=20,
                        top_p=0.9, seed=21)[2:])
    want = ServingEngine(params, CFG, serve).run(
        reqs, arrivals=[0, 0, 1, 2])

    engine = ServingEngine(params, CFG, serve)
    for req, at in zip(reqs, [0, 0, 1, 2]):
        engine.submit(req, at)
    token_reads = {((serve.max_batch,), "int32")}
    if speculate:
        token_reads.add(((serve.max_batch * speculate,), "int32"))
    with pytest.MonkeyPatch.context() as mp:
        reads = _watch_host_traffic(mp)
        with pytest.raises(AssertionError, match="eager jax.random"):
            jax.random.fold_in(jax.random.PRNGKey(0), 1)
        np.asarray(jnp.zeros((3,)))
        assert reads == [((3,), "float32")]   # the spies work
        sampled_steps = sorted_rows = 0
        while engine.pending():
            del reads[:]
            rec = engine.step()
            assert set(reads) <= token_reads, reads
            assert len(reads) <= (2 if speculate else 1)
            sampled_steps += bool(reads)
            assert bool(reads) == bool(rec["tokens"])
            # counted from the host's arrays: no read-back more
            assert bool(reads) == bool(rec["sample_rows"])
            sorted_rows += rec["sample_sorted"]
    assert sampled_steps >= 8 and sorted_rows >= 8
    if speculate:
        assert engine.spec_snapshot()["spec_drafted"] > 0, "never verified"
    for i in range(4):
        np.testing.assert_array_equal(np.asarray(engine.outputs[i]),
                                      np.asarray(want[i]))


def test_stop_token_retires_early(params, prompts):
    """A request whose stop set contains its first greedy token
    retires after exactly one emission."""
    first = int(_oracle(params, prompts, 0, max_new=1)[-1])
    mx = Metrics()
    engine = ServingEngine(
        params, CFG,
        ServeConfig(max_batch=4, page_size=8, num_pages=32,
                    max_pages_per_slot=4, ctx_bucket_pages=1,
                    prompt_bucket=8),
        metrics_obj=mx)
    out = engine.run(_requests(prompts, 1, max_new=8,
                               stop_tokens=(first,)))
    assert list(out[0][8:]) == [first]
    retire = [d for d in mx.decisions
              if d["decision"] == "serve.retire"][0]
    assert retire["tokens"] == 1


# ----------------------------------------------------------------------
# Serving SLOs through the watchdog
# ----------------------------------------------------------------------

def test_ttft_slo_breach_through_watchdog(params, prompts):
    from flashmoe_tpu.profiler.slo import SLOConfig, SLOWatchdog

    mx = Metrics()
    dog = SLOWatchdog(SLOConfig(ttft_ms=1e-6, tpot_ms=1e9),
                      metrics=mx)
    engine = ServingEngine(
        params, CFG,
        ServeConfig(max_batch=4, page_size=8, num_pages=32,
                    max_pages_per_slot=4, ctx_bucket_pages=1,
                    prompt_bucket=8),
        slo=dog, metrics_obj=mx)
    engine.run(_requests(prompts, 2, max_new=3))
    breaches = [d for d in mx.decisions
                if d["decision"] == "slo.breach"]
    assert breaches and all(b["target"] == "ttft" for b in breaches)
    assert {b["request"] for b in breaches} == {0, 1}
    assert mx.counters["slo.breaches"] >= 2


def test_slo_config_serving_budgets():
    from flashmoe_tpu.profiler.slo import SLOConfig, SLOWatchdog

    with pytest.raises(ValueError, match="ttft_ms"):
        SLOConfig(ttft_ms=-1)
    slo = SLOConfig.from_dict({"ttft_ms": 50, "tpot_ms": 5})
    assert slo.ttft_ms == 50 and slo.tpot_ms == 5
    mx = Metrics()
    dog = SLOWatchdog(slo, metrics=mx)
    assert dog.observe_request(3, 7, ttft_ms=10, tpot_ms=1) == []
    ev = dog.observe_request(4, 8, ttft_ms=80, tpot_ms=9)
    assert [e["target"] for e in ev] == ["ttft", "tpot"]
    assert all(e["request"] == 8 for e in ev)


# ----------------------------------------------------------------------
# Decode-shaped planner split
# ----------------------------------------------------------------------

def test_decode_mode_golden_gated():
    """The decode-vs-training plan split is CI-gated: recompute the
    golden decode section and require at least one config where decode
    resolves a DIFFERENT plan than training."""
    from flashmoe_tpu.planner.golden import (
        GOLDEN_PATH, golden_snapshot,
    )

    with open(GOLDEN_PATH) as f:
        frozen = json.load(f)
    live = golden_snapshot()
    assert live["decode"] == frozen["decode"], (
        "decode-mode golden plans moved; if intentional regenerate "
        "with python -m flashmoe_tpu.planner --regen-golden")
    assert any(g["differs"] for gens in frozen["decode"].values()
               for g in gens.values()), (
        "no golden config resolves a different decode-priced plan — "
        "the serving planner split lost its teeth")
    # the reference config flips PATH (not just chunks): collective in
    # training, ragged at decode token counts
    ref = frozen["decode"]["reference"]["v5e"]
    assert ref["training"]["winner"] != ref["decode"]["winner"]


def test_resolve_moe_plan_decode_mode(monkeypatch):
    from flashmoe_tpu.planner.select import (
        _cached_backend, resolve_moe_plan,
    )

    monkeypatch.setenv("FLASHMOE_TPU_GEN", "v5e")
    for var in ("FLASHMOE_TUNING_FILE", "FLASHMOE_MOCK_SLICES"):
        monkeypatch.delenv(var, raising=False)
    _cached_backend.cache_clear()
    cfg = BENCH_CONFIGS["reference"].replace(moe_backend="auto", ep=8)
    train = resolve_moe_plan(cfg)
    decode = resolve_moe_plan(cfg, mode="decode", decode_tokens=64)
    assert decode != train
    assert decode[0] == "ragged"
    # the serving_mode selector field routes the same regime without
    # the call-site axis (the transformer hook's path)
    via_field = resolve_moe_plan(cfg.replace(serving_mode="decode"))
    assert via_field[0] == decode[0]
    _cached_backend.cache_clear()


def test_decode_shape_and_mode_validation():
    from flashmoe_tpu.planner.model import (
        decode_shape, predict_paths,
    )

    cfg = BENCH_CONFIGS["reference"]
    d = decode_shape(cfg, 8, 100)
    assert d.tokens == 104 and not d.is_training  # rounded up to d
    assert decode_shape(cfg, 8, 0).tokens == 64    # 0 = default batch
    with pytest.raises(ValueError, match="decode_tokens"):
        decode_shape(cfg, 8, -4)
    with pytest.raises(ValueError, match="mode"):
        predict_paths(cfg, 8, "v5e", mode="inference")
    with pytest.raises(ValueError, match="serving_mode"):
        cfg.replace(serving_mode="train")


def test_serve_plan_decision_recorded(params):
    mx = Metrics()
    engine = ServingEngine(
        params, CFG.replace(serving_mode="decode"),
        ServeConfig(max_batch=4, page_size=8, num_pages=16,
                    max_pages_per_slot=4, ctx_bucket_pages=1,
                    prompt_bucket=8),
        metrics_obj=mx)
    plan = [d for d in mx.decisions if d["decision"] == "serve.plan"]
    assert len(plan) == 1
    assert plan[0]["decode_tokens"] == 4
    assert engine.decode_plan and engine.prefill_plan


# ----------------------------------------------------------------------
# Prefill/decode pools (inference-mode Decider)
# ----------------------------------------------------------------------

def test_serving_pools_split():
    from flashmoe_tpu.parallel.topology import Adjacency, WorkerAttr
    from flashmoe_tpu.serving.pools import plan_serving_pools

    n = 4
    alpha = np.full((n, n), 1e-3)
    beta = np.full((n, n), 1e-5)
    np.fill_diagonal(alpha, 0.0)
    np.fill_diagonal(beta, 0.0)
    adj = Adjacency(alpha=alpha, beta=beta)
    # device 2 is the fastest: decode (latency-critical) must take it
    rates = [1.0, 1.0, 4.0, 1.0]
    workers = [WorkerAttr(throughput=r, memory_gb=16.0) for r in rates]
    cfg = BENCH_CONFIGS["reference"]
    plan = plan_serving_pools(adj, workers, cfg, decode_share=0.5,
                              record=False)
    assert 2 in plan.decode_devices
    assert plan.prefill_devices and plan.decode_devices
    assert set(plan.prefill_devices) | set(plan.decode_devices) \
        == set(range(n))
    assert not set(plan.prefill_devices) & set(plan.decode_devices)
    assert plan.prefill_ms > 0 and plan.decode_ms > 0
    with pytest.raises(ValueError, match="decode_share"):
        plan_serving_pools(adj, workers, cfg, decode_share=1.5)


# ----------------------------------------------------------------------
# CLI + load sweep
# ----------------------------------------------------------------------

def test_serving_cli_summary_and_artifacts(tmp_path, capsys):
    from flashmoe_tpu.serving.__main__ import main

    obs = tmp_path / "obs"
    rc = main(["--requests", "2", "--max-batch", "2", "--max-new", "3",
               "--prompt-len", "8", "--obs-dir", str(obs),
               "--ttft-slo-ms", "0.000001"])
    assert rc == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["completed"] == 2
    assert rec["slo_breaches"] >= 2
    assert rec["tokens_per_sec"] is not None
    flight = (obs / "flight.jsonl").read_text().splitlines()
    assert any(json.loads(l).get("kind") == "serve_step"
               for l in flight)
    decisions = (obs / "decisions.jsonl").read_text()
    assert "serve.retire" in decisions and "slo.breach" in decisions


def test_build_requests_deterministic():
    a, ar = build_requests(4, vocab=256, prompt_len=8, max_new=4,
                           seed=3, arrival_every=2)
    b, br = build_requests(4, vocab=256, prompt_len=8, max_new=4,
                           seed=3, arrival_every=2)
    assert [r.prompt for r in a] == [r.prompt for r in b]
    assert ar == br == [0, 0, 2, 2]


# ----------------------------------------------------------------------
# observe --serving
# ----------------------------------------------------------------------

def test_observe_serving_report(params, prompts, tmp_path, capsys):
    from flashmoe_tpu import observe

    mx = Metrics()
    recorder = FlightRecorder()
    engine = ServingEngine(
        params, CFG,
        ServeConfig(max_batch=4, page_size=8, num_pages=32,
                    max_pages_per_slot=4, ctx_bucket_pages=1,
                    prompt_bucket=8),
        recorder=recorder, metrics_obj=mx)
    engine.run(_requests(prompts, 3, max_new=3))
    flight = tmp_path / "flight.jsonl"
    dec = tmp_path / "decisions.jsonl"
    recorder.export_jsonl(str(flight))
    mx.dump_decisions_jsonl(str(dec))

    rc = observe.main(["--serving", "--json", str(flight), str(dec)])
    assert rc == 0
    rep = json.loads(capsys.readouterr().out.strip())
    assert rep["requests_completed"] == 3
    assert rep["ttft_ms"]["p50"] is not None
    assert rep["tpot_ms"] is not None
    assert rep["queue_depth"]["max"] >= 0
    assert rep["cache_occupancy"]["peak"] > 0
    assert rep["plan"] is not None
    assert rep["admissions"] == 3

    # text rendering + the no-data exit code
    rc = observe.main(["--serving", str(flight), str(dec)])
    assert rc == 0
    assert "TTFT" in capsys.readouterr().out
    empty = tmp_path / "empty.jsonl"
    empty.write_text('{"step": 1}\n')
    assert observe.main(["--serving", str(empty)]) == 2


# ----------------------------------------------------------------------
# Live telemetry plane on the real engine (PR 13 acceptance)
# ----------------------------------------------------------------------

def test_live_plane_drill_8_concurrent_traced_bit_identical(params,
                                                            prompts):
    """The acceptance drill: 8 concurrent requests under page pressure
    (at least one evicted/re-prefilled), tracing + scrape server ON —
    outputs token-bit-equal to the plane-off engine, every request
    reconstructs to a contiguous per-request Perfetto track (no orphan
    spans, eviction gap visible) passing validate_trace, and a LIVE
    /metrics scrape mid-drill returns parseable exposition text with
    the TTFT/TPOT summary quantiles."""
    import urllib.request

    from flashmoe_tpu.profiler.export import (
        request_trace_document, validate_trace,
    )

    # pool sized so all 8 requests are concurrently resident (2 pages
    # each) and the THIRD page (length 16, ~8 decode steps in) starves
    # the pool: 8-concurrent first, eviction/re-prefill after
    serve = ServeConfig(max_batch=8, page_size=8, num_pages=20,
                        max_pages_per_slot=4, ctx_bucket_pages=1,
                        prompt_bucket=8)
    reqs = _requests(prompts, 8, max_new=10)
    arrivals = [0, 0, 0, 0, 1, 1, 2, 3]

    m_on = Metrics()
    on = ServingEngine(params, CFG, serve, metrics_obj=m_on,
                       tracer=True, telemetry_port=0)
    try:
        for req, arr in zip(reqs, arrivals):
            on.submit(req, arr)
        # drive until the first retirement seeds the sketches, then
        # scrape while work is still in flight
        while on.pending() and "serve.ttft_ms" not in m_on.sketches:
            on.step()
        assert on.pending()
        url = f"http://127.0.0.1:{on.telemetry.port}/metrics"
        with urllib.request.urlopen(url, timeout=5) as r:
            body = r.read().decode()
            assert r.headers.get("Content-Type") == \
                "text/plain; version=0.0.4"
        assert 'flashmoe_serve_ttft_ms{quantile="' in body
        assert 'flashmoe_serve_tpot_ms{quantile="' in body
        assert "flashmoe_serve_queue_depth" in body
        while on.pending():
            on.step()
        out_on = dict(on.outputs)
        s_on = on.summary()
    finally:
        on.close()

    assert s_on["completed"] == 8 and s_on["max_active"] == 8
    assert s_on["evictions"] > 0            # re-prefill cycle exercised

    # plane off: bit-identical token streams
    off = ServingEngine(params, CFG, serve, metrics_obj=Metrics())
    out_off = off.run(_requests(prompts, 8, max_new=10), arrivals)
    for i in range(8):
        np.testing.assert_array_equal(np.asarray(out_on[i]),
                                      np.asarray(out_off[i]))

    # every request: contiguous track, eviction gaps visible
    tr = on.tracer
    assert tr.validate() == []
    assert len(tr.requests) == 8
    evicted = [rid for rid, st in tr.requests.items() if st.evictions]
    assert evicted
    for rid in evicted:
        gaps = [s for s in tr.request_track(rid)
                if s["name"] == "serve.queued" and s.get("resumed")]
        assert len(gaps) == tr.requests[rid].evictions
    doc = request_trace_document(tr)
    assert validate_trace(doc) == []
    assert len({e["pid"] for e in doc["traceEvents"]}) == 8
    # every retirement closed its trace
    traces = [d for d in m_on.decisions
              if d["decision"] == "serve.trace"]
    assert len(traces) == 8
    assert {d["rid"] for d in traces} == set(range(8))


def test_engine_summary_uses_sketches_not_decision_scan(params,
                                                        prompts):
    """summary() reads the O(1)-memory retire sketches — a foreign
    decision stream (e.g. another engine on the same Metrics) cannot
    change this engine's numbers, and p99 is reported."""
    mx = Metrics()
    engine = ServingEngine(
        params, CFG,
        ServeConfig(max_batch=4, page_size=8, num_pages=32,
                    max_pages_per_slot=4, ctx_bucket_pages=1,
                    prompt_bucket=8),
        metrics_obj=mx)
    engine.run(_requests(prompts, 3, max_new=3))
    s = engine.summary()
    assert s["ttft_ms_mean"] is not None
    assert s["ttft_ms_p99"] >= s["ttft_ms_mean"] * 0.5
    assert mx.sketches["serve.ttft_ms"].n == 3
    assert mx.sketches["serve.step_ms"].n == s["steps"]
    # windowed rates ride the gauges
    assert "serve.tokens_per_s" in mx.gauges


# ----------------------------------------------------------------------
# Speculative multi-token decoding (ISSUE 20)
# ----------------------------------------------------------------------

def _spec_serve(speculate=None, **kw):
    from flashmoe_tpu.serving.speculate import SpecConfig

    base = dict(max_batch=4, page_size=8, num_pages=32,
                max_pages_per_slot=4, ctx_bucket_pages=1,
                prompt_bucket=8)
    base.update(kw)
    if speculate is not None:
        base["speculate"] = SpecConfig(draft_tokens=speculate)
    return ServeConfig(**base)


@pytest.fixture(scope="module")
def spec_prompts():
    """Repetitive prompts (tiled bigram motifs): the n-gram drafter
    has suffix matches to propose from, so the verify path actually
    exercises acceptance instead of the empty-draft fallthrough."""
    motifs = jax.random.randint(jax.random.PRNGKey(7), (8, 2), 0,
                                CFG.vocab_size)
    return jnp.asarray([[int(motifs[i][j % 2]) for j in range(8)]
                        for i in range(8)])


# what the commit before the state-by-id array served (d6ace1a)
PARENT_DRILL = {
    "plain": {
        "out": {0: [173, 29, 29, 29, 29, 29, 29, 29, 29],
                1: [25, 135, 26, 135, 5, 40, 135, 5, 61, 122, 122, 122, 216,
                    26],
                2: [135, 96, 61, 139],
                3: [4, 182, 103, 72, 87, 72, 56, 177, 163, 177, 163],
                4: [209, 63, 140, 135, 63, 209],
                5: [187, 26, 27, 27, 139, 206, 139, 206, 26, 26, 26, 119,
                    119],
                6: [134, 96, 221, 221, 221],
                7: [206, 110, 110, 110, 110, 110, 96, 110, 96, 110]},
        "used": [8, 8, 8, 6, 9, 9, 9, 10, 6, 9, 10, 11, 11, 7, 3, 7, 7, 8,
                 9, 7, 7, 8, 9, 9, 5, 5, 0],
        "tables": [[[1, 2, 7], [3, 4], [5, 6, 8]],
                   [[1, 2, 7], [3, 4], [5, 6, 8]],
                   [[1, 2, 7], [3, 4], [5, 6, 8]],
                   [[1, 2, 7], [3, 4, 9], None],
                   [[1, 2, 7, 8], [3, 4, 9], [5, 6]],
                   [[1, 2, 7, 8], [3, 4, 9], [5, 6]],
                   [[1, 2, 7, 8], [3, 4, 9], [5, 6]],
                   [[1, 2, 7, 8], [3, 4, 9, 10], [5, 6]],
                   [None, [3, 4, 9, 10], [5, 6]],
                   [[1, 2], [3, 4, 9, 10], [5, 6, 7]],
                   [[1, 2, 8], [3, 4, 9, 10], [5, 6, 7]],
                   [[1, 2, 8], [3, 4, 9, 10, 11], [5, 6, 7]],
                   [[1, 2, 8], [3, 4, 9, 10, 11], [5, 6, 7]],
                   [[1, 2, 8], None, [5, 6, 7, 12]],
                   [None, [3, 4, 9], None], [[5, 6], [3, 4, 9], [7, 12]],
                   [[5, 6], [3, 4, 9], [7, 12]],
                   [[5, 6], [3, 4, 9], [7, 12, 1]],
                   [[5, 6], [3, 4, 9, 2], [7, 12, 1]],
                   [None, [3, 4, 9, 2], [7, 12, 1]],
                   [None, [3, 4, 9, 2], [7, 12, 1]],
                   [None, [3, 4, 9, 2], [7, 12, 1, 5]],
                   [None, [3, 4, 9, 2, 6], [7, 12, 1, 5]],
                   [None, [3, 4, 9, 2, 6], [7, 12, 1, 5]],
                   [None, [3, 4, 9, 2, 6], None],
                   [None, [3, 4, 9, 2, 6], None], [None, None, None]],
    },
    "speculate": {
        "out": {0: [182, 253, 96, 182, 134, 96, 96, 182, 134],
                1: [206, 206, 206, 206, 206, 108, 104, 206, 110, 110, 110,
                    110, 110, 110],
                2: [154, 154, 154, 154],
                3: [177, 222, 212, 218, 212, 75, 75, 75, 203, 178, 203],
                4: [16, 3, 3, 3, 3, 3],
                5: [198, 198, 198, 198, 198, 132, 132, 132, 132, 132, 132,
                    163, 109],
                6: [89, 89, 89, 89, 89],
                7: [16, 3, 16, 3, 3, 16, 3, 3, 3, 3]},
        "used": [8, 8, 6, 7, 9, 9, 10, 6, 9, 11, 6, 9, 7, 5, 8, 8, 7, 8, 8,
                 9, 0],
        "tables": [[[1, 2, 7], [3, 4], [5, 6, 8]],
                   [[1, 2, 7], [3, 4], [5, 6, 8]],
                   [[1, 2, 7], [3, 4, 10], None],
                   [[1, 2, 7], [3, 4, 10], [5]],
                   [[1, 2, 7, 6], [3, 4, 10], [5, 11]],
                   [[1, 2, 7, 6], [3, 4, 10], [5, 11]],
                   [[1, 2, 7, 6], [3, 4, 10, 8], [5, 11]],
                   [None, [3, 4, 10, 8], [5, 11]],
                   [[13, 12], [3, 4, 10, 8], [5, 11, 1]],
                   [[13, 12, 2], [3, 4, 10, 8, 7], [5, 11, 1]],
                   [[13, 12, 2], None, [5, 11, 1]],
                   [[13, 12, 2], [14, 3, 4], [5, 11, 1]],
                   [None, [14, 3, 4], [5, 11, 1, 13]],
                   [[12, 2], [14, 3, 4], None],
                   [[12, 2], [14, 3, 4, 1], [5, 11]],
                   [[12, 2], [14, 3, 4, 1], [5, 11]],
                   [None, [14, 3, 4, 1], [5, 11, 12]],
                   [None, [14, 3, 4, 1, 2], [5, 11, 12]],
                   [None, [14, 3, 4, 1, 2], [5, 11, 12]],
                   [None, [14, 3, 4, 1, 2], [5, 11, 12, 10]],
                   [None, None, None]],
    },
}


@pytest.mark.parametrize("arm", ["plain", "speculate"])
def test_large_pool_drill_places_pages_as_the_parent_did(params, prompts,
                                                         spec_prompts, arm):
    """The allocator's order is observable and did not move: a seeded
    drill over a 4096-page pool (slots reused, requests of several
    pages, and with speculation armed a roll-back's tail freed every
    verify step) serves the parent's tokens from the parent's pages:
    every slot's block table after every step, and the ``pages_used``
    of every ``serve_step`` record."""
    want = PARENT_DRILL[arm]
    lens = [(8, 9), (5, 14), (8, 4), (3, 11), (7, 6), (8, 13), (4, 5),
            (6, 10)]
    toks = (spec_prompts if arm == "speculate" else prompts).tolist()
    recorder = FlightRecorder()
    engine = ServingEngine(
        params, CFG,
        _spec_serve(3 if arm == "speculate" else None, max_batch=3,
                    page_size=4, num_pages=4096, max_pages_per_slot=6),
        recorder=recorder)
    for r, ((t0, n), at) in enumerate(zip(lens, [0, 0, 0, 1, 1, 2, 4, 4])):
        engine.submit(Request(rid=r, prompt=tuple(toks[r][:t0]),
                              max_new_tokens=n), at)
    tables = []
    while engine.pending():
        engine.step()
        tables.append([None if s is None else list(s.pages)
                       for s in engine.slots])
    assert tables == want["tables"]
    assert [r["pages_used"] for r in recorder.records
            if r.get("kind") == "serve_step"] == want["used"]
    assert {r: engine.outputs[r][-n:]
            for r, (_, n) in enumerate(lens)} == want["out"]
    assert engine.pool.free_pages == 4095


def test_speculative_decode_bit_equal_greedy(params, spec_prompts):
    """The exactness acceptance: speculation on emits token-bit-equal
    streams to the non-speculative oracle, while actually accepting
    drafts (not vacuously passing through the no-draft path)."""
    engine = ServingEngine(params, CFG, _spec_serve(speculate=3))
    out = engine.run(_requests(spec_prompts, 4, max_new=8),
                     arrivals=[0, 0, 1, 2])
    snap = engine.spec_snapshot()
    assert snap["spec_drafted"] > 0, "drill never drafted — vacuous"
    assert snap["spec_accepted"] > 0
    assert snap["spec_tokens_per_step"] > 1.0
    for i in range(4):
        np.testing.assert_array_equal(
            np.asarray(out[i]),
            _oracle(params, spec_prompts, i, max_new=8))


def test_speculative_decode_bit_equal_sampled(params, spec_prompts):
    """Exact rejection sampling: the per-request fold_in key stream
    makes speculative output bit-equal at every sampling arm, and
    bit-equal across batch-composition changes (staggered arrivals vs
    all-at-once)."""
    def run(spec, arrivals=None):
        engine = ServingEngine(params, CFG, _spec_serve(
            speculate=3 if spec else None))
        reqs = _requests(spec_prompts, 4, max_new=6, temperature=0.8,
                         top_k=20, top_p=0.9, seed=21)
        return engine.run(reqs, arrivals=arrivals)

    base = run(False)
    spec = run(True)
    stagger = run(True, arrivals=[0, 1, 2, 3])
    for i in range(4):
        np.testing.assert_array_equal(np.asarray(base[i]),
                                      np.asarray(spec[i]))
        np.testing.assert_array_equal(np.asarray(base[i]),
                                      np.asarray(stagger[i]))


def test_speculative_eviction_bit_equal(params, spec_prompts):
    """A starved pool evicts mid-speculation; the DraftState rebuilds
    from the resumed prompt (prompt + delivered tokens), and the
    re-prefilled request completes bit-equal."""
    mx = Metrics()
    engine = ServingEngine(params, CFG,
                           _spec_serve(speculate=3, num_pages=8),
                           metrics_obj=mx)
    out = engine.run(_requests(spec_prompts, 4, max_new=10))
    s = engine.summary()
    assert s["evictions"] > 0 and s["completed"] == 4
    assert s["spec_drafted"] > 0
    for i in range(4):
        np.testing.assert_array_equal(
            np.asarray(out[i]),
            _oracle(params, spec_prompts, i, max_new=10))


def test_spec_stats_ride_retire_and_flight_records(params,
                                                   spec_prompts):
    """Per-request acceptance stats land on serve.retire decisions and
    serve_request flight records; per-step spec_tokens/spec_on ride
    serve_step records; summary() and the health snapshot carry the
    fleet numbers."""
    mx = Metrics()
    recorder = FlightRecorder()
    engine = ServingEngine(params, CFG, _spec_serve(speculate=3),
                           metrics_obj=mx, recorder=recorder)
    engine.run(_requests(spec_prompts, 4, max_new=8))
    retires = [d for d in mx.decisions
               if d["decision"] == "serve.retire"]
    assert retires and all("spec_drafted" in d and "accept_rate" in d
                           for d in retires)
    req_recs = [r for r in recorder.records
                if r.get("kind") == "serve_request"]
    assert req_recs and all("spec_accepted" in r for r in req_recs)
    steps = [r for r in recorder.records
             if r.get("kind") == "serve_step"]
    assert steps and all("spec_tokens" in r and "spec_on" in r
                         for r in steps)
    assert sum(r["spec_tokens"] for r in steps) \
        == engine.spec_snapshot()["spec_accepted"]
    s = engine.summary()
    assert s["spec_drafted"] == engine.spec_snapshot()["spec_drafted"]
    assert engine._health_snapshot()["spec"]["spec_on"] is True
    # the recorder dump reduces to the same numbers through the
    # host-side consumer twin
    from flashmoe_tpu.ops.stats import speculation_summary

    agg = speculation_summary(recorder.records)
    assert agg["spec_drafted"] == s["spec_drafted"]
    assert agg["spec_accepted"] == s["spec_accepted"]
    assert agg["spec_steps"] > 0


def test_spec_off_graph_and_config_identity(params, prompts):
    """speculate=None is the off value: the ServeConfig is EQUAL to
    one that never named the field (one jit cache entry), the engine
    builds no verify function, and the decode step's traced graph is
    byte-identical before vs after a speculative engine ran."""
    from flashmoe_tpu.serving.engine import _paged_decode_step
    from flashmoe_tpu.staticcheck.graph import jaxpr_text

    assert _spec_serve() == _spec_serve(speculate=None)

    def decode_jaxpr():
        sv = _spec_serve()
        cache = init_paged_cache(CFG, sv.num_pages, sv.page_size)
        toks = jnp.zeros((sv.max_batch,), jnp.int32)
        pos = jnp.zeros((sv.max_batch,), jnp.int32)
        tables = jnp.zeros((sv.max_batch, sv.ctx_bucket_pages),
                           jnp.int32)
        closed = jax.make_jaxpr(
            lambda *a: _paged_decode_step.__wrapped__(params, CFG, *a))
        return jaxpr_text(closed(cache, toks, tables, pos).jaxpr)

    before = decode_jaxpr()
    engine = ServingEngine(params, CFG, _spec_serve(speculate=2))
    engine.run(_requests(prompts, 1, max_new=3))
    assert decode_jaxpr() == before
    plain = ServingEngine(params, CFG, _spec_serve())
    assert plain._spec is None
    assert "spec_drafted" not in plain.summary()


def test_set_speculate_morphs_and_validates(params, spec_prompts):
    """set_speculate flips the live engine off/on with serve.spec
    decisions; enabling on an engine that never armed a SpecConfig is
    a config error."""
    mx = Metrics()
    engine = ServingEngine(params, CFG, _spec_serve(speculate=3),
                           metrics_obj=mx)
    engine.set_speculate(False, reason="drill")
    assert engine._spec is None
    out = engine.run(_requests(spec_prompts, 2, max_new=6))
    assert engine.spec_snapshot()["spec_drafted"] == 0
    for i in range(2):
        np.testing.assert_array_equal(
            np.asarray(out[i]),
            _oracle(params, spec_prompts, i, max_new=6))
    morphs = [d for d in mx.decisions
              if d["decision"] == "serve.spec"
              and d.get("event") == "morph_off"]
    assert len(morphs) == 1
    plain = ServingEngine(params, CFG, _spec_serve())
    with pytest.raises(ValueError, match="speculate"):
        plain.set_speculate(True)


def test_draft_state_ngram_index():
    """DraftState unit: suffix-match drafting, continuation fallback
    to the previous occurrence, sync after external token appends."""
    from flashmoe_tpu.serving.speculate import (
        DraftState, SpecConfig, spec_stats_fields,
    )

    spec = SpecConfig(draft_tokens=3, ngram=2)
    ds = DraftState(spec, [1, 2, 3, 1, 2])
    assert ds.draft(3) == [3, 1, 2]        # continue the seen bigram
    ds.extend([3])                         # now ...1 2 3; suffix [2,3]
    assert ds.draft(3) == [1, 2, 3]
    ds.sync([1, 2, 3, 1, 2, 3, 9, 9])      # external append resyncs
    assert ds.draft(2) == []               # suffix [9,9] never seen
    with pytest.raises(ValueError):
        SpecConfig(draft_tokens=0)
    with pytest.raises(ValueError):
        SpecConfig(ngram=0)
    with pytest.raises(ValueError):
        SpecConfig(source="magic")
    f = spec_stats_fields(4, 3, 2)
    assert f["accept_rate"] == 0.75
    assert f["spec_tokens_per_step"] == 2.5


# ----------------------------------------------------------------------
# One layer body for every cached path (ISSUE 29)
# ----------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg",))
def _parent_span_step(params, cfg, pools, toks, block_tables, positions,
                      row=None):
    """THE REFERENCE: the K/V arm of ``_paged_verify_step`` as it stood
    before the seven hand-copied bodies became ``generate.span_forward``
    (commit a402b38), verbatim, with the two page ops it called
    (``kvcache.store_tokens``, ``kvcache.gather_ctx``) written out.  A
    ``[B, T]`` span over pages is the most general of the seven: decode
    is T = 1, a prefill chunk one slot writing whole pages, a whole
    prompt one slot over fresh pages.  ``row`` is the one addition: the
    prefill programs' head (the lm head on ONE hidden row of slot 0, as
    they apply it: a product over one row and over T rows may round
    differently)."""
    from flashmoe_tpu.models.generate import lm_logits, lm_logits_span
    from flashmoe_tpu.models.transformer import _rope, rms_norm
    from flashmoe_tpu.ops.moe import expert_arm, moe_layer
    from flashmoe_tpu.serving.kvcache import PagedKVCache

    def store_tokens(pages, span_kv, page_ids, rows):
        return pages.at[page_ids, :, rows, :].set(span_kv)

    def gather_ctx(pages, block_tables):
        b, n = block_tables.shape
        g = pages[block_tables]                    # [B, n, N_kv, page, D]
        _, _, nkv, page, d = g.shape
        return g.transpose(0, 2, 1, 3, 4).reshape(b, nkv, n * page, d)

    b, t_span = toks.shape
    page = pools.page_size
    ntab = block_tables.shape[1]
    n_ctx = ntab * page
    x = params["embed"].astype(cfg.dtype)[toks]              # [B, T, H]
    pos = (positions[:, None]
           + jnp.arange(t_span, dtype=jnp.int32)[None, :])   # [B, T]
    valid = pos < n_ctx
    pidx = jnp.clip(pos // page, 0, ntab - 1)
    page_ids = jnp.where(
        valid, jnp.take_along_axis(block_tables, pidx, axis=1),
        jnp.int32(SCRATCH_PAGE))
    rows = jnp.where(valid, pos % page, 0)
    k_pages, v_pages = pools
    nh, nkv, dh = (cfg.num_heads, cfg.resolved_num_kv_heads,
                   cfg.resolved_head_dim)
    for li, layer in enumerate(params["layers"]):
        h_in = rms_norm(x, layer["attn_norm"])
        q = (h_in @ layer["wq"].astype(x.dtype)).reshape(b, t_span, nh,
                                                         dh)
        k = (h_in @ layer["wk"].astype(x.dtype)).reshape(b, t_span, nkv,
                                                         dh)
        v = (h_in @ layer["wv"].astype(x.dtype)).reshape(b, t_span, nkv,
                                                         dh)
        q, k = _rope(q, k, pos, cfg.rope_theta)

        k_pages = k_pages.at[li].set(
            store_tokens(k_pages[li], k, page_ids, rows))
        v_pages = v_pages.at[li].set(
            store_tokens(v_pages[li], v, page_ids, rows))

        kk = gather_ctx(k_pages[li], block_tables)  # [B, nkv, ctx, D]
        vv = gather_ctx(v_pages[li], block_tables)
        if nkv != nh:
            rep = nh // nkv
            kk = jnp.repeat(kk, rep, axis=1)
            vv = jnp.repeat(vv, rep, axis=1)
        qh = q.transpose(0, 2, 1, 3)                # [B, N, T, D]
        logits = jnp.einsum(
            "bntd,bnsd->bnts", qh, kk, preferred_element_type=jnp.float32
        ) * (dh ** -0.5)
        mask = (jnp.arange(n_ctx)[None, None, None, :]
                <= pos[:, None, :, None])
        logits = jnp.where(mask, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        ctx = jnp.einsum(
            "bnts,bnsd->bntd", probs, vv, preferred_element_type=jnp.float32
        ).transpose(0, 2, 1, 3).reshape(b, t_span, nh * dh).astype(
            x.dtype)
        x = x + ctx @ layer["wo"].astype(x.dtype)

        f_in = rms_norm(x, layer["ffn_norm"])
        layer_cfg = cfg if li in cfg.moe_layer_indices else cfg.replace(
            num_experts=1, expert_top_k=1, num_shared_experts=0)
        # the ONE line that is not the parent's: since ISSUE 33 every
        # serving span takes its experts by ``ops/moe.expert_arm`` (at
        # these sizes the capacity arm, the parent's); everything around
        # it is held
        o = moe_layer(layer["moe"], f_in.reshape(b * t_span, -1),
                      layer_cfg, use_pallas=False,
                      routed_rows=expert_arm(layer_cfg, b * t_span)
                      != "capacity")
        x = x + o.out.reshape(b, t_span, -1).astype(x.dtype)

    if row is not None:
        h = jax.lax.dynamic_slice(x, (0, row, 0), (1, 1, x.shape[-1]))
        return lm_logits(params, cfg, h)[0], PagedKVCache(k_pages, v_pages)
    return lm_logits_span(params, cfg, x), PagedKVCache(k_pages, v_pages)


#: MHA with an interleaved dense layer (the drill's model); GQA, every
#: layer a mixture with a shared expert; one kv head under four query
#: heads, three layers of which one is a mixture, in bf16
_BODY_CFGS = {
    "mha_dense": CFG,
    "gqa": CFG.replace(num_heads=4, num_kv_heads=2, moe_frequency=1,
                       num_shared_experts=1),
    "mqa_dense_bf16": CFG.replace(num_heads=4, num_kv_heads=1,
                                  num_layers=3, dtype=jnp.bfloat16),
}
_PAGE = 8


@pytest.fixture(scope="module", params=sorted(_BODY_CFGS))
def body_state(request, prompts):
    """A config, its weights and a 32-page pool holding the eight drill
    prompts (slot i in page 1 + i, its second page 9 + i), written by
    the REFERENCE body."""
    cfg = _BODY_CFGS[request.param]
    params = init_params(jax.random.PRNGKey(0), cfg)
    tables = np.stack([1 + np.arange(8), 9 + np.arange(8)],
                      axis=1).astype(np.int32)
    _, pools = _parent_span_step(
        params, cfg, init_paged_cache(cfg, 32, _PAGE), prompts,
        jnp.asarray(tables), jnp.zeros((8,), jnp.int32))
    tables[7] = SCRATCH_PAGE                       # an idle slot
    return cfg, params, pools, jnp.asarray(tables)


def _same_bits(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(got.view(np.uint8), want.view(np.uint8))


@pytest.mark.parametrize("program", ["decode", "verify", "chunk",
                                     "whole_prompt"])
def test_every_paged_program_is_the_parents_span_body(body_state, prompts,
                                                      program):
    """The four jitted programs, each now ``span_forward`` under an
    embed and a head, hold to the kept copy of the parent's body BIT
    for bit, logits and pools: any drift of arithmetic order fails."""
    cfg, params, pools, tables = body_state
    for li in set(range(cfg.num_layers)) - set(cfg.moe_layer_indices):
        # the dense layer's config is the one the parent built inline:
        # an EQUAL frozen config, so the same jit cache entry
        inline = cfg.replace(num_experts=1, expert_top_k=1,
                             num_shared_experts=0)
        assert cfg.ffn_config(li) == inline
        assert hash(cfg.ffn_config(li)) == hash(inline)
    if program == "decode":
        pos = jnp.asarray([8, 8, 7, 15, 8, 6, 8, 0], jnp.int32)
        toks = prompts[:, 0]
        got, got_pools, _ = eng._paged_decode_step(params, cfg, pools, toks,
                                                tables, pos)
        want, want_pools = _parent_span_step(params, cfg, pools,
                                             toks[:, None], tables, pos)
        want = want[:, 0]
    elif program == "verify":
        # slot 3 drafts into its context ceiling (2 pages = 16 rows,
        # span 14..17): columns 2 and 3 go to the scratch page
        pos = jnp.asarray([8, 9, 7, 14, 8, 6, 8, 0], jnp.int32)
        got, got_pools = eng._paged_verify_step(
            params, cfg, pools, prompts[:, :4], tables, pos)
        want, want_pools = _parent_span_step(
            params, cfg, pools, prompts[:, :4], tables, pos)
    elif program == "chunk":
        # 16 tokens at positions 8..23 of slot 2, into pages 20 and 21
        toks = prompts[4:6].reshape(1, 16)
        table = jnp.asarray([3, 20, 21], jnp.int32)
        got, got_pools = eng._prefill_chunk(
            params, cfg, pools, toks, table, table[1:], jnp.int32(8),
            jnp.int32(13))
        want, want_pools = _parent_span_step(
            params, cfg, pools, toks, table[None, :],
            jnp.asarray([8], jnp.int32), row=jnp.int32(13))
    else:
        # a 13-token prompt padded to 16, stored into pages 25 and 17
        toks = prompts[6:8].reshape(1, 16)
        page_ids = jnp.asarray([25, 17], jnp.int32)
        got, *runs = eng._prefill_padded(params, cfg, toks, jnp.int32(13))
        got_pools = type(pools)(*(store_prefill(pool, run, page_ids)
                                  for pool, run in zip(pools, runs)))
        want, want_pools = _parent_span_step(
            params, cfg, pools, toks, page_ids[None, :],
            jnp.zeros((1,), jnp.int32), row=jnp.int32(12))
    assert type(got_pools) is type(want_pools)
    if program == "whole_prompt" and cfg.resolved_num_kv_heads == cfg.num_heads:
        # the one case the CPU cannot hold to the bit: with no GQA repeat
        # between them, XLA's CPU backend reads the scores' K operand
        # straight out of the projection's transpose here and out of the
        # page gather in the reference, and rounds the two products
        # differently (8e-7 on the logits; so did the parent's program:
        # parent and change ARE bit-equal here, run side by side for
        # ISSUE 29).  The first layer's rows, written before any
        # attention, are held to the bit; the rest to float32's grain.
        for got_pool, want_pool in zip(got_pools, want_pools):
            _same_bits(got_pool[0], want_pool[0])
            np.testing.assert_allclose(got_pool, want_pool, rtol=2e-5,
                                       atol=2e-6)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
        return
    _same_bits(got, want)
    for got_pool, want_pool in zip(got_pools, want_pools):
        _same_bits(got_pool, want_pool)


@pytest.mark.parametrize("speculate", [None, 3], ids=["plain", "speculate"])
def test_ep_sharded_decode_token_equal_to_one_device(params, prompts,
                                                     spec_prompts,
                                                     speculate):
    """``ServeConfig(ep_shards=2)`` on two of the CPU's virtual devices
    (the slot grid, the page slab and the experts split over ``"ep"``,
    the mixture layers through ``ragged_ep.decode_moe_rows``): token
    streams equal to the one-device engine's, through the decode twin
    and, with speculation on, through the verify twin — the one
    ``_span_step`` body under ``shard_map``."""
    from flashmoe_tpu.serving.speculate import SpecConfig

    src = spec_prompts if speculate else prompts

    def run(ep_shards):
        engine = ServingEngine(params, CFG, ServeConfig(
            max_batch=4, page_size=8, num_pages=16, max_pages_per_slot=4,
            ctx_bucket_pages=1, prompt_bucket=8, ep_shards=ep_shards,
            speculate=SpecConfig(draft_tokens=speculate)
            if speculate else None))
        return engine, engine.run(_requests(src, 6, max_new=8),
                                  arrivals=[0, 0, 0, 1, 1, 2])

    _, want = run(1)
    engine, got = run(2)
    assert engine._ep_fn is not None and engine.summary()["completed"] == 6
    if speculate:
        assert engine._ep_verify is not None
        assert engine.spec_snapshot()["spec_accepted"] > 0
    for i in range(6):
        np.testing.assert_array_equal(np.asarray(got[i]),
                                      np.asarray(want[i]))
