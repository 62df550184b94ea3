"""Continuous-batching serving engine over the paged KV cache.

One fixed decode batch of ``max_batch`` slots; requests join and leave
per step (continuous batching) instead of padding a static batch to the
slowest member:

* **admission** — queued requests whose (seeded-trace) arrival step has
  passed take a free slot when the page pool can hold their prompt:
  single-pass batched prefill (:func:`_prefill_padded`) writes their
  pages in one shot, ``serve.admit``;
* **decode** — one jitted step advances every active slot: sample from
  each slot's pending logits (greedy / temperature / top-k / top-p,
  per-request), feed the sampled tokens, paged attention over each
  slot's block table, MoE FFN on the batch rows.  The sampled tokens
  stay on the device as the decode program's feed, and the step reads
  them AFTER it has dispatched that program (who decodes is known from
  the counts alone), so the host's work runs under the device's;
* **retirement** — a slot leaves when it emits a stop token or its
  ``max_new_tokens``-th token (``serve.retire`` with TTFT/TPOT); its
  pages return to the pool and the next admission reuses them.  A stop
  token is found out after the slot's next row was dispatched: that one
  row is wasted (it lands in a page the slot still owned);
* **eviction** — when decode needs a page and the pool is dry, the
  youngest active request is preempted back to the queue head
  (``serve.evict``): its pages free immediately, its already-delivered
  tokens stand, and it later re-prefills prompt+generated and
  continues.  A step whose growth would have to evict reads its tokens
  first (the victim's resumed prompt is built from them), as does every
  step with speculation armed (the drafts are).

A model that generates by diffusion over blocks (``cfg.block_length``)
takes the same path with another step: a slot's step is a BLOCK of
positions scored together (:func:`_paged_denoise_step`), revealed over
``ServeConfig.denoise_steps`` forwards and committed in the forward that
opens the next block, and its tokens are delivered a block at a time (see
:meth:`ServingEngine._denoise`).

Everything host-side is a pure function of the submitted requests and
their arrival steps, and the page allocator is LIFO — so a seeded drill
replays bit-identically on CPU, which is what makes the engine
CI-testable (tests/test_serving.py asserts engine outputs token-equal
to the same prompts decoded one at a time through ``generate()``).

Jit policy: the pool shape is fixed; prefill compiles once per padded
prompt bucket and decode once per bucketed context length
(:func:`flashmoe_tpu.serving.kvcache.ctx_pages_bucket`) — requests
joining mid-flight reuse existing compilations.

The planner runs in DECODE mode for the step path
(``resolve_moe_plan(mode='decode', decode_tokens=max_batch)``): decode
steps move ``max_batch`` tokens (x ``top_k`` exchange rows), not B x S,
so the training-shaped schedule sweep is the wrong question to ask —
the resolved (prefill, decode) plans land in one ``serve.plan``
decision (the reference's inference-mode Decider specialization,
``decider.cuh:177-268``, surfaces through the same call — see
:mod:`flashmoe_tpu.serving.pools` for the pool split).
"""

from __future__ import annotations

import dataclasses
import functools
import json
import logging
import time
from collections import Counter, deque

import jax
import jax.numpy as jnp
import numpy as np

from flashmoe_tpu.config import STATE_MIXERS, MoEConfig
from flashmoe_tpu.models.generate import (
    REVEAL_RULES, lm_logits, lm_logits_span, reveal_rows, span_forward,
)
from flashmoe_tpu.models.transformer import embed_tokens
from flashmoe_tpu.ops import attention
from flashmoe_tpu.ops.attention import ByKind
from flashmoe_tpu.ops.moe import expert_arm, expert_chunks
from flashmoe_tpu.serving.kvcache import (
    SCRATCH_PAGE, WINDOW_FIELDS, PagedKVCache, PagePool, ShardedPagePool,
    ctx_pages_bucket, init_paged_cache, prompt_pad,
    slot_state_fields, store_prefill, store_state,
)
from flashmoe_tpu.serving.speculate import (
    DraftState, SpecConfig, spec_stats_fields,
)
from flashmoe_tpu.utils.telemetry import metrics as _global_metrics
from flashmoe_tpu.utils.telemetry import (
    compile_totals, gc_totals, trace_span, watch_compiles, watch_gc,
)

try:                            # the thread's own rusage: Linux
    import resource
    _RUSAGE_THREAD = resource.RUSAGE_THREAD
except (ImportError, AttributeError):
    _RUSAGE_THREAD = None

#: a step is STALLED when its host time plus the caller's time before it
#: (``host_ms + between_ms``) is over ``_STALL_FACTOR`` times the running
#: median of that sum and over ``_STALL_FLOOR_MS``
_STALL_FACTOR = 4.0
_STALL_FLOOR_MS = 5.0

_log = logging.getLogger("flashmoe_tpu.serving")


def _thread_rusage() -> tuple[int, int]:
    """(involuntary context switches, minor + major page faults) of the
    calling thread so far; zeros where the platform has no
    ``RUSAGE_THREAD``."""
    if _RUSAGE_THREAD is None:
        return 0, 0
    ru = resource.getrusage(_RUSAGE_THREAD)
    return ru.ru_nivcsw, ru.ru_minflt + ru.ru_majflt


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request.  ``seed`` keys the per-request sampler
    (folded with the token index, so sampling is independent of batch
    composition); ``stop_tokens`` retire the request the step one is
    emitted (the stop token itself is delivered)."""

    rid: int
    prompt: tuple
    max_new_tokens: int = 16
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    stop_tokens: tuple = ()
    seed: int = 0
    #: denoising forwards a block (a model that generates by blocks; None:
    #: ``ServeConfig.denoise_steps``)
    denoise_steps: int | None = None

    def __post_init__(self):
        if not self.prompt:
            raise ValueError(f"request {self.rid}: empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError(f"request {self.rid}: max_new_tokens must "
                             f"be >= 1")
        if not 0 < self.top_p <= 1.0:
            raise ValueError(f"request {self.rid}: top_p must be in "
                             f"(0, 1]")


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine shape knobs (all static: they size the jitted steps).

    ``num_pages`` includes the reserved scratch page; ``prompt_bucket``
    must be a multiple of ``page_size`` (prefilled pages are written
    whole); ``ctx_bucket_pages`` is the decode-gather granularity —
    the bucketed-length jit policy's bucket.

    ``prefill_chunk`` (tokens, a multiple of ``page_size``) bounds the
    per-step prefill budget: a prompt longer than one chunk is admitted
    in fixed-size slices, one slice per engine step, so a 32k-token
    prompt cannot hole a decode step.  ``ep_shards`` > 1 runs the
    decode step EP-sharded under ``shard_map`` on an ``("ep",)`` mesh
    with the paged KV slab partitioned alongside the experts (the
    fabric's decode-pool execution path).

    ``speculate`` (a :class:`~flashmoe_tpu.serving.speculate.
    SpecConfig`, None = off) arms speculative multi-token decoding
    (ISSUE 20): each step drafts up to ``draft_tokens`` continuation
    tokens per slot and verifies them in ONE ``k+1``-position paged
    forward — output tokens stay bit-equal to non-speculative decode
    (only canonical samples are ever emitted), and because the config
    rides ``ServeConfig`` it reaches every fabric replica, so
    speculation survives pool handoff and replica migration for
    free.

    ``denoise_steps`` / ``reveal_rule`` / ``reveal_threshold`` are read by
    a model that generates by blocks alone (``MoEConfig.block_length``):
    the denoising forwards a block (a divisor of the block length; None:
    the block length, one token a forward), the rule that picks the rows
    a forward reveals (``models/generate.REVEAL_RULES``) and the
    confidence ``low_confidence_dynamic`` reveals every row over.

    ``window_pages`` is read by a model with window layers alone
    (``MoEConfig.window_layers``): the pages of THEIR pool, its scratch
    page included, as ``num_pages`` is the full layers' (0: what every
    slot can hold at once, ``ServingEngine.window_slot_pages`` a slot; a
    smaller pool is covered by eviction, as a small ``num_pages`` is)."""

    max_batch: int = 8
    page_size: int = 8
    num_pages: int = 64
    window_pages: int = 0
    max_pages_per_slot: int = 8
    ctx_bucket_pages: int = 2
    prompt_bucket: int = 8
    pad_token: int = 0
    max_steps: int = 10_000
    prefill_chunk: int | None = None
    ep_shards: int = 1
    speculate: SpecConfig | None = None
    denoise_steps: int | None = None
    reveal_rule: str = "low_confidence_static"
    reveal_threshold: float = 0.9

    def __post_init__(self):
        if self.reveal_rule not in REVEAL_RULES:
            raise ValueError(f"reveal_rule {self.reveal_rule!r} not in "
                             f"{REVEAL_RULES}")
        if self.denoise_steps is not None and self.denoise_steps < 1:
            raise ValueError("denoise_steps must be >= 1")
        if self.speculate is not None \
                and not isinstance(self.speculate, SpecConfig):
            raise ValueError(
                f"speculate must be a SpecConfig or None, got "
                f"{type(self.speculate).__name__}")
        if self.max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        if self.page_size < 1:
            raise ValueError("page_size must be >= 1")
        if self.num_pages < 2 or self.window_pages == 1 \
                or self.window_pages < 0:
            raise ValueError("num_pages (and window_pages, where given) "
                             "must be >= 2 (page 0 is the scratch page)")
        if not 1 <= self.ctx_bucket_pages <= self.max_pages_per_slot:
            raise ValueError("ctx_bucket_pages must be in "
                             "[1, max_pages_per_slot]")
        if self.prompt_bucket < self.page_size \
                or self.prompt_bucket % self.page_size:
            raise ValueError(
                f"prompt_bucket={self.prompt_bucket} must be a "
                f"positive multiple of page_size={self.page_size} "
                f"(prefill writes whole pages)")
        if self.prefill_chunk is not None and (
                self.prefill_chunk < self.page_size
                or self.prefill_chunk % self.page_size):
            raise ValueError(
                f"prefill_chunk={self.prefill_chunk} must be a "
                f"positive multiple of page_size={self.page_size} "
                f"(chunks write whole pages)")
        if self.ep_shards < 1:
            raise ValueError("ep_shards must be >= 1")
        if self.ep_shards > 1:
            if self.max_batch % self.ep_shards:
                raise ValueError(
                    f"ep_shards={self.ep_shards} must divide "
                    f"max_batch={self.max_batch} (the slot grid is "
                    f"row-partitioned across shards)")
            if self.num_pages % self.ep_shards:
                raise ValueError(
                    f"ep_shards={self.ep_shards} must divide "
                    f"num_pages={self.num_pages} (the page slab is "
                    f"partitioned across shards)")
            if self.num_pages // self.ep_shards < 2:
                raise ValueError(
                    f"num_pages={self.num_pages} leaves fewer than 2 "
                    f"pages per shard at ep_shards={self.ep_shards} "
                    f"(each shard reserves its own scratch page)")

    @property
    def max_context(self) -> int:
        return self.max_pages_per_slot * self.page_size


@dataclasses.dataclass
class _QueueEntry:
    """One queued (or evicted-and-requeued) request."""

    arrival_step: int
    req: Request                   # current incarnation (prompt grows
                                   # across evictions)
    orig: Request                  # pre-eviction identity (output key)
    arrival_s: float | None        # wall clock when the trace arrival
                                   # step was reached (TTFT base); None
                                   # until then — a future arrival must
                                   # not accrue synthetic queue wait
    first_token_s: float | None    # survives eviction: the client
                                   # already holds the first token
    # ---- the request's account, carried across evictions -------------
    queued_s: float | None = None  # when THIS wait began (None: the
                                   # first wait, which began at arrival)
    waited_ms: float = 0.0         # queue waits already served
    prefill_ms: float | None = None
    last_token_s: float | None = None
    gap_max_ms: float = 0.0


@dataclasses.dataclass
class _Slot:
    """Host-side state of one occupied batch slot."""

    req: Request
    orig: Request                  # pre-eviction identity (output key)
    pages: list
    length: int                    # cache positions written (prompt+fed)
    emitted: list                  # tokens delivered THIS incarnation
    admit_step: int
    arrival_s: float               # wall clock at trace arrival
    first_token_s: float | None
    prefill_pos: int | None = None  # next chunk start (chunked prefill
                                    # in flight); None = decoding
    prefill_toks: object = None     # padded np prompt for the chunks
    # a model with window layers: the slot's pages of the WINDOW pool,
    # indexed as ``pages`` is (entry j holds positions j * page ..), the
    # entries wholly behind the window given back and pointing at the
    # scratch page; ``wlive`` is the first that is not
    wpages: list = dataclasses.field(default_factory=list)
    wlive: int = 0
    draft: object = None            # DraftState (speculative decode):
                                    # the slot's suffix-match table,
                                    # rebuilt from prompt+emitted so it
                                    # survives eviction and migration
    spec_drafted: int = 0           # drafts proposed this incarnation
    spec_accepted: int = 0          # ... and accepted (= canonical)
    # ---- the request's account (engine clock; all but admit_s survive
    # eviction through the queue entry) --------------------------------
    admit_s: float = 0.0            # this incarnation's admission
    queue_wait_ms: float = 0.0      # arrival to admission, summed over
                                    # re-admissions
    prefill_ms: float | None = None  # admission to first token
    last_token_s: float | None = None
    gap_max_ms: float = 0.0         # widest gap between two tokens
    # ---- a model that generates by blocks (see ServingEngine._denoise) --
    tail: tuple = ()                # the prompt's tokens past its last
                                    # whole block: they open the first
                                    # block, already revealed
    block_masked: int | None = None  # rows of the open block still
                                     # masked, by count (None: no block
                                     # is open; -1: ask the device)
    block_step: int = 0             # denoising forwards it has had
    block_first: int = 0            # its rows the prompt's tail filled


# ----------------------------------------------------------------------
# Jitted kernels (module-level so every engine instance shares caches).
# Each is: embed, say where the span's rows go (``pos`` / ``write`` from
# the block tables), the one layer loop (``generate.span_forward``, for
# either cache class), the head.
# ----------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg",))
def _prefill_padded(params, cfg: MoEConfig, prompt_padded, true_len):
    """Prefill one padded prompt: [1, T_pad] int32 -> (logits [V] at
    the true last position, then one dense run for each pool of the
    cache, as ``store_prefill`` takes it: k_seq/v_seq
    [L, N_kv, T_pad, D], or an MLA config's latent rows [L, T_pad, C];
    then, with state layers, what they keep after the TRUE last token,
    one [L_s, ...] array for each of ``cfg.slot_state``, as
    ``store_state`` takes it).
    Pad positions compute garbage no causal query before them ever
    sees; their rows land in pages the length mask never exposes, and
    they leave a recurrent state alone."""
    t_pad = prompt_padded.shape[1]
    positions = jnp.arange(t_pad, dtype=jnp.int32)[None, :]
    x, _, runs, _ = span_forward(
        params, cfg, embed_tokens(params, cfg, prompt_padded),
        None, positions, None, None, absorbed=False,
        valid=positions < true_len if cfg.state_layers else None)
    h = jax.lax.dynamic_slice(
        x, (0, true_len - 1, 0), (1, 1, x.shape[-1]))
    return (lm_logits(params, cfg, h)[0], *(run[:, 0] for run in runs))


@functools.partial(jax.jit, static_argnames=("cfg",))
def _prefill_chunk(params, cfg: MoEConfig, pools, chunk_toks,
                   block_table, chunk_page_ids, start_pos, rel_last,
                   slot=0, window=None):
    """Prefill ONE fixed-size chunk of a long prompt directly into the
    paged cache.

    chunk_toks: [1, C] int32 (C = ``ServeConfig.prefill_chunk``);
    block_table: [n] page ids covering positions [0, start_pos + C)
    (bucketed, scratch-padded); chunk_page_ids: [C / page] the pages
    THIS chunk writes; start_pos: absolute position of the chunk's
    first token; rel_last: in-chunk index of the prompt's true last
    token (clipped — only the chunk containing it keeps the logits);
    slot: the batch slot the prompt was admitted to, whose recurrent
    state the state layers carry from chunk to chunk (the first chunk
    starts from nothing; positions past ``rel_last`` leave it alone).
    ``pools`` is the engine's cache (any class of ``serving/kvcache``).
    ``window`` (a model with window layers): ``(table [n_w], page_ids
    [C / page], base)``, the window pool's ids of the pages the chunk's
    windows reach, column 0 the page of position ``base``, and of the
    pages the chunk writes.  Returns (logits [V], pools).

    The chunk's rows land in their pages BEFORE the gather, so in-chunk
    causal attention sees them through the same paged read decode uses.
    Positions past the true prompt end write garbage rows that decode
    overwrites before any causal query exposes them — the whole-prefill
    invariant, per chunk."""
    c = chunk_toks.shape[1]
    positions = start_pos + jnp.arange(c, dtype=jnp.int32)   # [C]
    state = dict(
        valid=(jnp.arange(c, dtype=jnp.int32) <= rel_last)[None, :],
        slots=jnp.asarray(slot, jnp.int32)[None],
        fresh=start_pos == 0) if cfg.state_layers else {}
    span = (embed_tokens(params, cfg, chunk_toks), pools, positions[None, :],
            (chunk_page_ids[None, :], None),                # whole pages
            block_table[None, :])
    if window is not None:
        wtable, wpage_ids, wbase = window
        span = (*span[:3], ByKind(span[3], (wpage_ids[None, :], None)),
                ByKind(span[4], (wtable[None, :], wbase[None])))
    x, pools, _, _ = span_forward(params, cfg, *span, absorbed=False,
                                  **state)
    h = jax.lax.dynamic_slice(x, (0, rel_last, 0), (1, 1, x.shape[-1]))
    return lm_logits(params, cfg, h)[0], pools


def _span_step(params, cfg: MoEConfig, pools, toks, block_tables,
               positions, mixture=None, pad_token=None, writes=None,
               window=None):
    """A span of T tokens a slot through the layers, over the paged
    cache: the body of the decode (T = 1) and verify programs and of
    their EP-sharded twins (which pass ``mixture``).  toks: [B, T];
    column t lands at ``positions + t``.  Returns (x [B, T, H], pools,
    what the layers counted: ``span_forward``'s).  Row b is slot b: its
    recurrent state is read and written in place, and a row whose table
    is all scratch (idle, or between two chunks of its prompt) leaves its
    state alone.  With a ``pad_token`` such a row is fed it, whatever
    ``toks`` holds there: the engine hands the decode step the sampler's
    array as it lies on the device, and the sampler gives an idle row the
    ``argmax`` of stale logits.

    Span positions past the gathered context (a slot drafted into its
    context ceiling) route their writes to the scratch page and produce
    garbage columns the host never reads — the host truncates drafts to
    fit, this is the in-graph belt-and-suspenders.  So do the columns
    outside ``writes`` ([B, T] bool, the denoise program's dead half;
    None: every column writes): they leave a slot's pages alone.
    ``window`` (a model with window layers): ``(tables [B, n_w], base
    [B])``, each slot's pages of the WINDOW pool from the first its window
    reaches, column 0 the page of position ``base`` (a whole page)."""
    page = pools.page_size
    ntab = block_tables.shape[1]
    pos = (positions[:, None]
           + jnp.arange(toks.shape[1], dtype=jnp.int32)[None, :])  # [B, T]
    valid = pos < ntab * page
    if writes is not None:
        valid &= writes
    page_ids = jnp.where(
        valid, jnp.take_along_axis(
            block_tables, jnp.clip(pos // page, 0, ntab - 1), axis=1),
        jnp.int32(SCRATCH_PAGE))
    rows = jnp.where(valid, pos % page, 0)
    write, tables = (page_ids, rows), block_tables
    if window is not None:
        wtables, wbase = window
        at = (pos - wbase[:, None]) // page     # the page's column there
        inside = valid & (at >= 0) & (at < wtables.shape[1])
        wpage_ids = jnp.where(
            inside, jnp.take_along_axis(
                wtables, jnp.clip(at, 0, wtables.shape[1] - 1), axis=1),
            jnp.int32(SCRATCH_PAGE))
        write = ByKind(write, (wpage_ids, jnp.where(inside, rows, 0)))
        tables = ByKind(tables, window)
    owns = block_tables[:, :1] != SCRATCH_PAGE      # the row has a tenant
    if pad_token is not None:
        toks = jnp.where(owns, toks, jnp.int32(pad_token))
    live = (jnp.broadcast_to(owns, pos.shape) if cfg.state_layers else None)
    # a short span over a long context: MLA's absorbed form
    x, pools, _, counted = span_forward(
        params, cfg, embed_tokens(params, cfg, toks), pools, pos,
        write, tables, absorbed=True, mixture=mixture, valid=live)
    return x, pools, counted


@functools.partial(jax.jit, static_argnames=("cfg", "pad_token"))
def _paged_decode_step(params, cfg: MoEConfig, pools, toks,
                       block_tables, positions, pad_token=None,
                       window=None):
    """One decode step for the whole slot grid: the span path at T = 1.

    toks: [B] int32 tokens to feed; block_tables: [B, n] page ids
    (bucketed); positions: [B] write positions (= each slot's current
    length; inactive slots pass 0 with an all-scratch table, and with a
    ``pad_token`` are fed it whatever ``toks`` holds: see
    :func:`_span_step`; ``window``: its).  Returns
    (logits [B, V] f32, pools, what the layers counted: a dict of scalars,
    empty for a config whose layers count nothing)."""
    x, pools, counted = _span_step(params, cfg, pools, toks[:, None],
                                   block_tables, positions,
                                   pad_token=pad_token, window=window)
    return lm_logits(params, cfg, x), pools, counted


@functools.partial(jax.jit, static_argnames=("cfg",))
def _paged_verify_step(params, cfg: MoEConfig, pools, toks,
                       block_tables, positions):
    """Speculative verify: score a ``T = draft_tokens + 1`` position
    SPAN per slot in one forward (ISSUE 20).

    toks: [B, T] int32 — column 0 is the canonical last-sampled token,
    columns 1..k the drafted continuation (pad past the real drafts);
    positions: [B] base write positions (column t lands at
    ``positions + t``).  Returns (logits [B, T, V] f32, pools):
    logits[:, t] is the next-token distribution after feeding
    column t — column 0 is bit-equal to what :func:`_paged_decode_step`
    returns for the same token, columns 1..k are what it WOULD return
    after each draft, all for one weight pass (the planner's decode
    mode prices the step as wire/HBM-bound, so the extra columns ride
    nearly free).

    Rejected columns DO write rows: the host rolls back the
    block-table/length state, and the next step's span overwrites those
    exact rows before any causal mask exposes them (the prefill pad-row
    invariant)."""
    x, pools, _ = _span_step(params, cfg, pools, toks, block_tables,
                             positions)
    return lm_logits_span(params, cfg, x), pools


@functools.partial(jax.jit, static_argnames=("cfg", "pad_token", "rule",
                                             "threshold", "halves"))
def _paged_denoise_step(params, cfg: MoEConfig, pools, state, ctl,
                        block_tables, pad_token=None,
                        rule: str = "low_confidence_static",
                        threshold: float = 0.9, halves: int = 2):
    """One forward of every slot's OPEN BLOCK (a model that generates by
    diffusion over blocks, L = ``cfg.block_length``) and, in the same
    span, of the clean block BEFORE it: the span path at T = 2 L under
    the block mask, ``[half 0 | half 1]`` from the slot's length, the
    head on L rows a slot, then on the device the greedy choice, its
    confidence and the rows to reveal (``generate.reveal_rows``).
    Denoising and committing are this ONE program: every forward writes
    its live rows' K/V in place, and the commit is the forward of a block
    whose rows are all revealed, so slots at different steps of their
    blocks share a launch.  What the halves hold is the host's to say:

    - the open block in half 0 (a step of it, or the commit ALONE: it
      reveals none) and half 1 DEAD: fed ``pad_token``, written to the
      scratch page, seen by no live row (under the block mask half 0
      never sees half 1) and read by nobody;
    - the COMMIT of the state's block, whole, in half 0 and the NEXT
      block, all ``[MASK]``, at its step 0 in half 1 (``half`` 1): half
      0's rows see the cache and the clean block, as a commit alone does,
      half 1's the cache, the clean block's K/V beside them in the span,
      and themselves, as the step after a commit does.

    state: [B, 3, L] int32, what the last launch left of each slot's
    block: its tokens, which rows are masked, the step that revealed each
    row (-1: the prompt's tail, or masked still); the feed of this launch
    where it lies.  ctl: [B, 5 + L] int32 from the host, a row a slot:
    the position of half 0's first row, the rows to reveal (0: a commit
    alone, or a row that is not fed), the denoising step's index,
    ``first``: -1 keeps the slot's state; >= 0 OPENS a block whose first
    ``first`` rows are the tokens in columns 5.., the others masked, and
    ``half``: the half that block stands in and the head reads (1: half
    0 commits the state's block).  block_tables: [B, n].  A row whose
    table is all scratch is fed ``pad_token`` and keeps its state.
    ``halves`` 1 is the span of ONE block (``half`` is 0 throughout): the
    engine's where two blocks would cost the kernel's arm.  Returns
    (state: the block the head read, pools, what the layers counted)."""
    bl = cfg.block_length
    pos, n, step, first, half = (ctl[:, j] for j in range(5))
    opened = (first >= 0)[:, None]
    toks = jnp.where(opened, ctl[:, 5:], state[:, 0])
    masked = jnp.where(opened, jnp.arange(bl)[None, :] >= first[:, None],
                       state[:, 1] > 0)
    steps = jnp.where(opened, -1, state[:, 2])
    feed = jnp.where(masked, jnp.int32(cfg.mask_token_id), toks)
    writes = None
    if halves == 2:
        fused = (half == 1)[:, None]
        writes = (jnp.arange(2 * bl) < bl)[None, :] | fused
        feed = jnp.concatenate(
            [jnp.where(fused, state[:, 0], feed),
             jnp.where(fused, feed, jnp.int32(pad_token or 0))], axis=1)
    x, pools, counted = _span_step(params, cfg, pools, feed, block_tables,
                                   pos, pad_token=pad_token, writes=writes)
    if halves == 2:
        x = jnp.where(fused[:, :, None], x[:, bl:], x[:, :bl])
    x0, reveal = reveal_rows(
        lm_logits_span(params, cfg, x), masked, n, rule=rule,
        threshold=threshold, mask_token_id=cfg.mask_token_id)
    state = jnp.stack([jnp.where(reveal, x0, toks),
                       (masked & ~reveal).astype(jnp.int32),
                       jnp.where(reveal, step[:, None], steps)], axis=1)
    return state, pools, counted


# The same programs with the cache DONATED: the pool is updated in
# place and the caller's arrays die with the call.  These are the programs
# the engine runs, for either cache kind: beside the weights the chip has
# no room for the input pool, the output pool and the pool of a prefill
# chunk dispatched while the decode step still runs (an MLA model's 11 GB
# leave none at all; a K/V model's second pool was 1.61 GB of the backlog
# cell's 13.07 and the reason for two whole-pool copies a step).  The
# undonated programs above are what keeps its inputs: tests and
# ``lower()`` hold them against each other over one pool.
_INPLACE = {
    fn.__name__: jax.jit(fn.__wrapped__, static_argnames=("cfg", *static),
                         donate_argnames=("pools",))
    for fn, *static in ((_prefill_chunk,), (_paged_decode_step, "pad_token"),
                        (_paged_verify_step,),
                        (_paged_denoise_step, "pad_token", "rule",
                         "threshold", "halves"))}

#: ``store_prefill`` with the pool donated, as ONE program: an admission
#: writes a prompt's pages into the pool where it lies (called eagerly, the
#: scatter copied the whole pool first: 2 x 2.4 ms an admission on the
#: backlog cell, a twentieth of the device's time once the decode step
#: stopped copying it)
_store_prefill = jax.jit(store_prefill, donate_argnums=(0,))
#: the same for a slot's recurrent state
_store_state = jax.jit(store_state, donate_argnums=(0,))


# ----------------------------------------------------------------------
# EP-sharded decode (the fabric's decode-pool execution path)
# ----------------------------------------------------------------------

_EP_CACHE: dict = {}


def _ep_param_specs(params, cfg: MoEConfig):
    """Partition specs for the EP decode step: expert-axis leaves of
    every MoE layer shard along ``"ep"`` (the ``_qscale`` sidecars
    included — their leading axis is the expert axis too); everything
    else (attention, norms, embed/head, the replicated router
    ``gate_w``, dense layers' single-expert FFNs) replicates."""
    from jax.sharding import PartitionSpec as P
    from jax.tree_util import DictKey, tree_map_with_path

    def spec(path, leaf):
        names = [p.key for p in path if isinstance(p, DictKey)]
        if (("moe" in names or "branch" in names)
                and (not names or names[-1] != "gate_w")
                and getattr(leaf, "ndim", 0) >= 1
                and leaf.shape[0] == cfg.num_experts):
            return P("ep")
        return P()

    return tree_map_with_path(spec, params)


def _ep_decode_fn(mesh, cfg: MoEConfig, params, *, span: bool = False,
                  pad_token=None):
    """Build (and cache per (mesh, cfg, param-structure, span,
    pad_token: what :func:`_span_step` feeds an idle row)) the
    EP-sharded twin of :func:`_paged_decode_step` or, with ``span``, of
    :func:`_paged_verify_step`: one jitted ``shard_map`` whose body is
    the same :func:`_span_step` on the LOCAL slot rows and the LOCAL
    slab of the paged K/V cache, with the mixture layers' experts
    dispatched through the decode-priced ragged EP path
    (:func:`flashmoe_tpu.parallel.ragged_ep.decode_moe_rows`) — the
    plan ``serve.plan`` resolves in decode mode is what actually
    executes here.  Block tables carry per-SHARD-local page ids.
    Called as ``fn(params, pools, toks, block_tables, positions)``."""
    import jax.tree_util as jtu
    from jax.sharding import PartitionSpec as P

    key = (mesh, cfg, jtu.tree_structure(params), span, pad_token)
    cached = _EP_CACHE.get(key)
    if cached is not None:
        return cached

    from flashmoe_tpu.parallel import ragged_ep

    head = lm_logits_span if span else lm_logits

    def body(params, pools, toks, block_tables, positions):
        x, pools, _ = _span_step(params, cfg, pools,
                                 toks if span else toks[:, None],
                                 block_tables, positions,
                                 mixture=ragged_ep.decode_moe_rows,
                                 pad_token=pad_token)
        return head(params, cfg, x), pools

    slab = PagedKVCache(P(None, "ep"), P(None, "ep"))
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(_ep_param_specs(params, cfg), slab,
                  P("ep", None) if span else P("ep"), P("ep", None),
                  P("ep")),
        out_specs=(P("ep"), slab),
        check_vma=False))
    _EP_CACHE[key] = fn
    return fn


def _token_keys(seeds, index):
    """The sampler's key for each row: ``fold_in(PRNGKey(seed),
    token_index)`` as ``[B, 2]`` uint32 key data, traced.  ``seeds`` is
    ``Request.seed & 0xFFFFFFFF`` as uint32 (what the host's
    ``PRNGKey(seed)`` keeps of any Python int with x64 off), ``index``
    the token's position in the request's output."""
    return jax.vmap(lambda s, n: jax.random.fold_in(
        jax.random.PRNGKey(s), n))(seeds, index)


def _rows_ask(temps, top_ks, top_ps, v):
    """What each sampler row asks for, from its knobs: a draw, a top-k
    cut, a nucleus cut.  ONE rule for the program (traced) and for the
    host's count of what the program did (numpy).  ``top_p == 1`` asks
    for no nucleus and gets none, whatever the rounding of a running
    sum and whatever the batch's other rows ask for."""
    return temps > 0.0, (top_ks > 0) & (top_ks < v), top_ps < 1.0


def _sample_with_keys(logits, keys, temps, top_ks, top_ps):
    """Per-slot sampling with DYNAMIC per-request knobs (the engine's
    batch mixes requests): temperature <= 0 rows take the exact argmax
    (bit-equal to ``sample_tokens``' greedy arm); sampled rows apply
    top-k then nucleus truncation, keyed per request.  The vocabulary
    is sorted, once, only where a sampled row of this batch truncates
    (a ``lax.cond`` on the knob vectors: nothing is read back)."""
    v = logits.shape[-1]
    neg = jnp.asarray(-1e30, jnp.float32)
    scaled = logits.astype(jnp.float32) / jnp.maximum(
        temps, 1e-6)[:, None]
    drawn, use_k, use_p = _rows_ask(temps, top_ks, top_ps, v)

    def truncated():
        sort_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
        kth = jnp.take_along_axis(
            sort_desc, jnp.clip(top_ks - 1, 0, v - 1)[:, None], axis=1)
        below_k = lambda x: use_k[:, None] & (x < kth)
        # the top-k survivors in descending order, with no second sort:
        # what falls under the k-th value already lies at the tail
        sort_desc = jnp.where(below_k(sort_desc), neg, sort_desc)
        probs = jax.nn.softmax(sort_desc, axis=-1)
        csum = jnp.cumsum(probs, axis=-1)
        keep = ((csum - probs) < top_ps[:, None]) | ~use_p[:, None]
        thresh = jnp.min(
            jnp.where(keep, sort_desc, jnp.inf), axis=-1, keepdims=True)
        kept = jnp.where(below_k(scaled), neg, scaled)
        return jnp.where(kept < thresh, neg, kept)

    scaled = jax.lax.cond(
        jnp.any(drawn & (use_k | use_p)), truncated, lambda: scaled)
    sampled = jax.vmap(
        lambda kk, ll: jax.random.categorical(kk, ll))(keys, scaled)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jnp.where(drawn, sampled.astype(jnp.int32), greedy)


@jax.jit
def _sample_dynamic(logits, seeds, index, temps, top_ks, top_ps):
    """The step's tokens from its logits and the rows' knobs; the host
    uploads five small numpy arrays and reads back the tokens, nothing
    else.  The program does what the rows of this batch ask for: with
    no sampled row it is an ``argmax``; the keys (:func:`_token_keys`,
    derived here) and :func:`_sample_with_keys` run only in the other
    arm of a ``lax.cond`` on ``temps``."""
    with trace_span("lm.sample"):
        return jax.lax.cond(
            jnp.any(temps > 0.0),
            lambda: _sample_with_keys(logits, _token_keys(seeds, index),
                                      temps, top_ks, top_ps),
            lambda: jnp.argmax(logits, axis=-1).astype(jnp.int32))


def _sampler_rows(rows):
    """``(request, token_index)`` per row, ``None`` for an idle row ->
    the numpy arrays :func:`_sample_dynamic` takes after the logits."""
    n = len(rows)
    seeds = np.zeros((n,), np.uint32)
    index = np.zeros((n,), np.int32)
    temps = np.zeros((n,), np.float32)
    top_ks = np.zeros((n,), np.int32)
    top_ps = np.ones((n,), np.float32)
    for j, row in enumerate(rows):
        if row is None:
            continue
        r, index[j] = row
        seeds[j] = r.seed & 0xFFFFFFFF
        temps[j] = r.temperature
        top_ks[j] = r.top_k
        top_ps[j] = r.top_p
    return seeds, index, temps, top_ks, top_ps


def _as_watchdog(slo):
    if slo is None:
        return None
    from flashmoe_tpu.profiler.slo import SLOConfig, SLOWatchdog

    return SLOWatchdog(slo) if isinstance(slo, SLOConfig) else slo


class ServingEngine:
    """Multi-request continuous-batching driver (host loop + jitted
    steps).  See the module docstring for the lifecycle."""

    def __init__(self, params, cfg: MoEConfig,
                 serve: ServeConfig | None = None, *,
                 recorder=None, slo=None, mesh=None, metrics_obj=None,
                 tracer=None, telemetry_port=None, prefill_fn=None,
                 replica_tag=None, pools_info=None, clock=None,
                 heartbeat_fn=None):
        """``prefill_fn(prompt_padded, true_len, *, rid)`` replaces the
        local prefill when set — the fabric's KV-handoff seam: the
        callable must honor :func:`_prefill_padded`'s contract
        (logits [V], k_seq/v_seq [L, N_kv, T_pad, D]).  A handed-off
        prefill is always whole (``prefill_chunk`` applies to the LOCAL
        path only — in a disaggregated fabric long prompts cannot hole
        decode by construction).  ``replica_tag`` (e.g. ``"r0"``)
        additionally keys this engine's TTFT/TPOT sketches per replica;
        ``pools_info`` is surfaced verbatim in ``/vars``.  ``clock``: a
        zero-arg seconds source replacing ``time.monotonic`` for every
        latency measurement (arrival, TTFT, TPOT, step time) — a
        :class:`~flashmoe_tpu.fabric.vclock.VirtualClock` additionally
        gets its decode tick stepped at the end of every engine step;
        None (the default) is the wall clock, byte-identical to the
        pre-seam engine.  ``heartbeat_fn(phase)``: invoked at every
        sub-step phase boundary (``admit`` / ``prefill`` / ``sample`` /
        ``decode`` / ``end``) — the fabric's liveness seam (a
        :class:`~flashmoe_tpu.fabric.leasestore.HeartbeatPublisher`):
        a replica that hangs mid-step stops beating mid-step, so the
        watchdog catches it without waiting for the step boundary.
        None (the default) makes zero calls — byte-identical."""
        mla = cfg.attention_kind == "mla"
        sv = serve if serve is not None else ServeConfig()
        if cfg.rescaled and sv.ep_shards > 1:
            raise NotImplementedError(
                "a config with scalar factors or a tied head "
                "(MoEConfig.rescaled) with ep_shards > 1: no test holds "
                "_ep_decode_fn's sharded twin to them; one chip's engine "
                "runs them")
        if cfg.state_layers:
            # what cannot keep a slot's state correct, whatever the
            # mixer that keeps it
            missing = {
                "speculate": (sv.speculate is not None,
                              "a rejected draft has already moved the "
                              "state; a snapshot to roll back to"),
                "ep_shards > 1": (sv.ep_shards > 1,
                                  "_ep_decode_fn shards a K/V page pair; "
                                  "a per-slot state arm"),
                "a prefill_fn (the fabric's KV handoff)": (
                    prefill_fn is not None,
                    "fabric/handoff.py encodes K/V pages; a payload for "
                    "a slot's state"),
            }
            for what, (asked, lack) in missing.items():
                if asked:
                    raise NotImplementedError(
                        f"recurrent-state layers (a state a slot: "
                        f"{sorted(set(cfg.mixers) & set(STATE_MIXERS))}) "
                        f"with {what}: {lack} is missing")
        if cfg.window_layers:
            missing = {
                "speculate": (sv.speculate is not None,
                              "a verify span's rejected rows in a window "
                              "pool whose pages behind them are gone"),
                "ep_shards > 1": (sv.ep_shards > 1,
                                  "a window slab and tables in "
                                  "_ep_decode_fn"),
                "a prefill_fn (the fabric's KV handoff)": (
                    prefill_fn is not None,
                    "a payload for the window layers' pages"),
            }
            for what, (asked, lack) in missing.items():
                if asked:
                    raise NotImplementedError(
                        f"window layers (attn_window={cfg.attn_window}) "
                        f"with {what}: {lack} is missing")
        if cfg.block_length:
            bl = cfg.block_length
            missing = {
                "speculate": (sv.speculate is not None,
                              "a verify span that is no whole block under "
                              "the block mask"),
                "ep_shards > 1": (sv.ep_shards > 1,
                                  "an EP-sharded twin of "
                                  "_paged_denoise_step"),
                "a prefill_fn (the fabric's KV handoff)": (
                    prefill_fn is not None,
                    "a handoff of a prompt's whole blocks with its tail"),
            }
            for what, (asked, lack) in missing.items():
                if asked:
                    raise NotImplementedError(
                        f"generation by blocks (block_length={bl}) with "
                        f"{what}: {lack} is missing")
            for name, size in (("page_size", sv.page_size),
                               ("prompt_bucket", sv.prompt_bucket),
                               ("prefill_chunk", sv.prefill_chunk)):
                if size is not None and size % bl:
                    raise ValueError(
                        f"{name}={size} must be whole blocks of "
                        f"block_length={bl} (a block lies in one page, a "
                        f"prefill span starts and ends at a block's edge)")
            if bl % (sv.denoise_steps or bl):
                raise ValueError(
                    f"denoise_steps={sv.denoise_steps} must divide "
                    f"block_length={bl}")
        if mla and sv.ep_shards > 1:
            raise NotImplementedError(
                "attention_kind='mla' with ep_shards > 1: _ep_decode_fn "
                "shards a K/V page pair over the mesh, and the mesh's "
                "expert exchange has no selection bias; a latent slab "
                "and the bias through ragged_ep are missing (ROADMAP R4)")
        if mla and prefill_fn is not None:
            raise NotImplementedError(
                "attention_kind='mla' with a prefill_fn (the fabric's KV "
                "handoff): the seam's contract is (logits, k_seq, v_seq) "
                "and fabric/handoff.py encodes K/V pages; a latent-row "
                "payload is missing")
        if cfg.drop_tokens:
            raise ValueError(
                "the serving engine requires a dropless config "
                "(drop_tokens=False): inactive/retired batch slots "
                "must never compete with live requests for capacity "
                "slots, and decode batches are token-count-tiny anyway")
        self.params = params
        self.cfg = cfg
        self.serve = serve if serve is not None else ServeConfig()
        self.mesh = mesh
        self._prefill_fn = prefill_fn
        self.replica_tag = replica_tag
        self.pools_info = pools_info
        self.recorder = recorder
        self.metrics = metrics_obj if metrics_obj is not None \
            else _global_metrics
        self.watchdog = _as_watchdog(slo)
        # ---- measured-latency clock seam -----------------------------
        # every wall read below goes through self._clock; a VirtualClock
        # (duck-typed on complete_step) additionally advances its decode
        # tick at the end of each engine step
        self._clock = clock if clock is not None else time.monotonic
        self._vclock = (clock if hasattr(clock, "complete_step")
                        else None)
        self._heartbeat = heartbeat_fn
        # ---- the step's own account (see _phase) ---------------------
        self._phase_open = None     # (span, name, opened at)
        self._phase_ms: dict = {}   # this step's phases, by name
        self._delivered_now: dict = {}   # rid -> tokens this step
        # this step's decode program: pages read, idle, slots, the
        # attention's arm, the experts' arm and chunks
        self._ctx_pages = (0, 0.0, 0, None, None, None)
        # this step's sampler rows: not idle, drawn, truncating
        self._sampled = np.zeros((3,), np.int64)
        # this step's host account (see _step): the time blocked on the
        # device, the dispatches that found its queue empty and the phase
        # of the first, the prefill programs with their tokens and rows
        self._wait_ms = 0.0
        self._starved, self._starved_at = 0, None
        self._prefills = [0, 0, 0]
        # where the last step ended: its t1_s, the thread's CPU seconds,
        # context switches and page faults then
        self._step_end = None
        self._stall_logged_s = None
        watch_compiles()
        watch_gc()
        # ---- live telemetry plane (default off = zero threads, no
        # behavior change; outputs are bit-identical either way) ------
        self.tracer = None
        if tracer:
            from flashmoe_tpu.telemetry_plane.tracing import RequestTracer

            self.tracer = (tracer if isinstance(tracer, RequestTracer)
                           else RequestTracer(metrics_obj=self.metrics,
                                              clock=self._clock))
            self.tracer.install()
        self.telemetry = None
        if telemetry_port is not None:
            from flashmoe_tpu.telemetry_plane.server import maybe_server

            self.telemetry = maybe_server(
                telemetry_port, metrics_fn=lambda: self.metrics,
                health_fn=self._health_snapshot,
                vars_fn=self._vars_snapshot)
        from flashmoe_tpu.telemetry_plane.sketch import WindowedRate

        self._rates = {"tokens": WindowedRate(), "admits": WindowedRate(),
                       "evictions": WindowedRate()}

        # ---- quantized expert storage (flashmoe_tpu/quant/) ----------
        # the engine accepts a QuantizedExpertState (or a raw quantized
        # tree) whenever cfg.expert_quant is set; the HBM the narrow
        # store frees is reported as additional KV-cache page headroom
        # (`observe --serving`), since on a serving host weight bytes
        # and KV pages compete for the same memory.
        from flashmoe_tpu import quant as qt

        if isinstance(params, qt.QuantizedExpertState):
            self.params = params = params.params
        self.quant_info = None
        if cfg.expert_quant is not None:
            if not qt.is_quantized(params):
                # a full-precision checkpoint under the quant knob
                # would fake-quant ALL expert weights inside every
                # jitted step — strictly slower with zero memory
                # savings.  Quantize ONCE at load instead, so serving
                # always runs the dequant-in-compute store (code-review
                # finding).
                self.params = params = qt.quantize_state(
                    params, cfg.expert_quant).params
            self.quant_info = {
                "expert_quant": qt.canonical_name(cfg.expert_quant),
                "freed_bytes": qt.quant_bytes_saved(params,
                                                    cfg.param_dtype),
            }

        # ---- EP-sharded decode (fabric decode-pool path) -------------
        self._ep_fn = None
        d = self.serve.ep_shards
        if d > 1:
            if cfg.num_experts % d:
                raise ValueError(
                    f"ep_shards={d} must divide num_experts="
                    f"{cfg.num_experts} (every shard holds the same "
                    f"local expert count)")
            if cfg.num_shared_experts:
                raise ValueError(
                    "EP-sharded decode requires num_shared_experts=0 "
                    "(the ragged EP path has no shared-expert arm)")
            if self.mesh is None:
                devs = jax.devices()
                if len(devs) < d:
                    raise ValueError(
                        f"ep_shards={d} needs {d} devices, have "
                        f"{len(devs)}")
                self.mesh = jax.sharding.Mesh(
                    np.asarray(devs[:d]), ("ep",))
            elif ("ep" not in self.mesh.axis_names
                  or self.mesh.shape["ep"] != d):
                raise ValueError(
                    f"ep_shards={d} needs an 'ep' mesh axis of size "
                    f"{d}, got mesh axes {dict(self.mesh.shape)}")
            self._ep_fn = _ep_decode_fn(self.mesh, cfg, params,
                                        pad_token=self.serve.pad_token)

        # ---- speculative decoding (serving/speculate.py) -------------
        # off (None) keeps the engine byte-identical: no draft tables,
        # no verify jit, the plain one-token decode step below
        self._spec = self.serve.speculate
        self._ep_verify = None   # lazily built EP verify twin
        self._spec_steps = 0     # steps that ran a verify forward
        self._spec_drafted = 0
        self._spec_accepted = 0
        if self._spec is not None:
            self.metrics.decision(
                "serve.spec", event="armed",
                draft_tokens=self._spec.draft_tokens,
                ngram=self._spec.ngram, source=self._spec.source)

        # a model with window layers: the pages ONE slot holds of their
        # pool at most (a chunk's, or a whole prompt's padded end, and the
        # window before it), the pool, and an allocator of its own
        self.window_slot_pages = 0
        self.wpool = None
        if cfg.window_layers:
            page = self.serve.page_size
            span = self.serve.prefill_chunk or self.serve.prompt_bucket
            self.window_slot_pages = (
                -(-(cfg.attn_window - 1 + span) // page) + 1)
            n_window = (self.serve.window_pages or
                        self.serve.max_batch * self.window_slot_pages + 1)
            if n_window - 1 < self.window_slot_pages:
                raise ValueError(
                    f"window_pages={n_window} cannot hold one slot's "
                    f"{self.window_slot_pages} pages (attn_window="
                    f"{cfg.attn_window} and a span of {span} tokens)")
            self.wpool = PagePool(n_window)
        self.cache = init_paged_cache(
            cfg, self.serve.num_pages, self.serve.page_size,
            self.serve.max_batch,
            self.wpool.num_pages if self.wpool is not None else 0)
        # this step's account of the window pool: pages given back, the
        # pages a slot its programs read in a window layer
        self._window_freed = 0
        self._window_ctx = 0.0
        # this step's traffic with the slots' recurrent state, and what
        # the decode program of the step before counted (ready by now:
        # reading this step's would wait for it)
        self._state_bytes = 0
        self._counted = self._counted_prev = None
        self.pool = (ShardedPagePool(self.serve.num_pages, d) if d > 1
                     else PagePool(self.serve.num_pages))
        if self.quant_info is not None:
            page_bytes = (sum(p.nbytes for p in self.cache)
                          / self.serve.num_pages)
            extra = int(self.quant_info["freed_bytes"] // page_bytes)
            self.quant_info.update(
                page_bytes=int(page_bytes), extra_kv_pages=extra)
            self.metrics.decision(
                "serve.quant",
                expert_quant=self.quant_info["expert_quant"],
                freed_mb=round(self.quant_info["freed_bytes"] / 2**20,
                               3),
                extra_kv_pages=extra,
                num_pages=self.serve.num_pages)
            self.metrics.gauge("serve.quant_freed_mb",
                               self.quant_info["freed_bytes"] / 2**20)
        # bytes one cached token costs over all layers (a gauge, and on
        # every serve_step record): what the pool's pages are made of
        self.metrics.gauge("serve.kv_token_bytes", cfg.kv_pool_token_bytes)
        # bytes a slot's state costs over the state layers, whatever its
        # context (0: every layer caches rows a token)
        self.metrics.gauge("serve.state_slot_bytes", cfg.state_slot_bytes)
        # generation by blocks: every slot's open block as the last launch
        # left it (the denoise program's feed, where it lies), what this
        # step's launch did by the host's count (slots fed, rows revealed,
        # commits, rows fed masked), and per request the denoising step
        # that revealed each delivered token
        self._block_state = (
            jnp.zeros((self.serve.max_batch, 3, cfg.block_length),
                      jnp.int32) if cfg.block_length else None)
        self._block_counts = None
        # blocks a slot's launch spans: TWO (a whole block's commit rides
        # beside the next block's first step: _denoise) where the longer
        # span keeps the attention's arm, ONE where it would cost the
        # kernel's (two blocks not under a page, on a TPU)
        self._halves = 1
        if cfg.block_length:
            pools, heads, row = cfg.kv_pool_rows
            arm = lambda t: attention.kv_attention_arm(
                t, self.serve.page_size, heads, row, cfg.dtype, pools)
            self._halves += arm(2 * cfg.block_length) == arm(cfg.block_length)
        self.reveal_steps: dict[int, list] = {}
        self.queue: deque = deque()       # (arrival_step, _Slot-seed)
        self.slots: list[_Slot | None] = [None] * self.serve.max_batch
        self._logits = jnp.zeros(
            (self.serve.max_batch, cfg.vocab_size), jnp.float32)
        # an output, not donated, of the device program issued last: when
        # it is ready the device's queue is empty (see _queue_empty)
        self._last_out = self._logits
        self.step_idx = 0
        self.outputs: dict[int, list] = {}
        self.stats = {
            "submitted": 0, "admitted": 0, "completed": 0, "evictions": 0,
            "adopted": 0,
            "tokens": 0, "steps": 0, "max_queue_depth": 0,
            "max_active": 0, "decode_buckets": set(),
            "prefill_buckets": set(), "peak_occupancy": 0.0,
            # the last stalled steps' serve_stall records, and the worst
            "stalls": deque(maxlen=16), "worst_stall": None,
        }
        self._record_plan()

    # ---- planner wiring ----------------------------------------------

    def _record_plan(self) -> None:
        """Resolve the prefill- and decode-priced execution plans once
        and record them as one ``serve.plan`` decision — decode is
        priced at per-step token counts (= the slot-grid width), the
        regime where the training-shaped schedules are wrong."""
        from flashmoe_tpu.planner.select import resolve_moe_plan

        cfg = self.cfg
        pre_b, pre_c = resolve_moe_plan(cfg, self.mesh, mode="prefill")
        dec_b, dec_c = resolve_moe_plan(
            cfg, self.mesh, mode="decode",
            decode_tokens=self.serve.max_batch)
        self.decode_plan = (dec_b, dec_c)
        self.prefill_plan = (pre_b, pre_c)
        self.metrics.decision(
            "serve.plan",
            prefill_backend=pre_b, prefill_chunks=pre_c or 1,
            decode_backend=dec_b, decode_chunks=dec_c or 1,
            decode_tokens=self.serve.max_batch,
            heterogeneous=(pre_b, pre_c) != (dec_b, dec_c),
            ep=cfg.ep, moe_backend=cfg.moe_backend)

    # ---- live-plane snapshots ----------------------------------------

    def _health_snapshot(self) -> dict:
        """The ``/healthz`` document: liveness plus the engine's load
        story and the SLO watchdog's episode state."""
        doc = {
            "steps": self.step_idx,
            "queue_depth": len(self.queue),
            "active_requests": len(self._active()),
            "cache_occupancy": round(self.pool.occupancy, 4),
            "completed": self.stats["completed"],
            "evictions": self.stats["evictions"],
        }
        if self.replica_tag is not None:
            doc["replica"] = self.replica_tag
        if self.serve.speculate is not None:
            doc["spec"] = self.spec_snapshot()
        if self.watchdog is not None:
            doc["slo"] = self.watchdog.snapshot()
        return doc

    def _vars_snapshot(self) -> dict:
        """The ``/vars`` document: what this engine actually resolved
        to run (plans + shape knobs)."""
        cfg = self.cfg
        return {
            "prefill_plan": list(self.prefill_plan),
            "decode_plan": list(self.decode_plan),
            "serve": dataclasses.asdict(self.serve),
            "config": {
                "num_experts": cfg.num_experts,
                "expert_top_k": cfg.expert_top_k,
                "hidden_size": cfg.hidden_size,
                "intermediate_size": cfg.intermediate_size,
                "num_layers": cfg.num_layers,
                "moe_backend": cfg.moe_backend,
                "serving_mode": cfg.serving_mode,
                "wire_dtype": cfg.wire_dtype,
                "a2a_chunks": cfg.a2a_chunks,
                "expert_quant": cfg.expert_quant,
                "kv_wire_dtype": cfg.kv_wire_dtype,
                "ep": cfg.ep,
            },
            "quant": self.quant_info,
            "tracing": self.tracer is not None,
            "replica": self.replica_tag,
            "pools": self.pools_info,
        }

    def close(self) -> None:
        """Tear down the live plane (scrape server thread, tracer
        listener).  Idempotent; engines without one are no-ops."""
        if self.telemetry is not None:
            self.telemetry.stop()
            self.telemetry = None
        if self.tracer is not None:
            self.tracer.uninstall()

    # ---- submission --------------------------------------------------

    def submit(self, req: Request, arrival_step: int = 0) -> None:
        bl = self.cfg.block_length
        if bl and req.temperature > 0.0:
            raise NotImplementedError(
                f"request {req.rid}: temperature={req.temperature} with "
                f"generation by blocks: the reveal rules rank greedy "
                f"choices; a keyed draw a position is missing")
        if bl and bl % (req.denoise_steps or bl):
            raise ValueError(
                f"request {req.rid}: denoise_steps={req.denoise_steps} "
                f"must divide block_length={bl}")
        # the BUCKETED full lifetime must fit the slot context, so an
        # evicted request's resumed (longer, re-bucketed) prompt plus
        # its remaining budget is covered by the same bound
        need = prompt_pad(len(req.prompt) + req.max_new_tokens,
                          self.serve.prompt_bucket)
        if need > self.serve.max_context:
            raise ValueError(
                f"request {req.rid}: bucketed prompt + max_new_tokens "
                f"({need}) exceeds the slot context "
                f"{self.serve.max_context} "
                f"(max_pages_per_slot x page_size)")
        # ... and the whole POOL: a request the allocator can never
        # serve would otherwise park at the queue head and spin the
        # engine through max_steps empty iterations
        need_pages = need // self.serve.page_size
        allocatable = (self.serve.num_pages // self.serve.ep_shards) - 1
        if need_pages > allocatable:
            raise ValueError(
                f"request {req.rid}: lifetime needs {need_pages} pages "
                f"but the pool only holds {allocatable} "
                f"allocatable pages"
                + (f" per shard (ep_shards={self.serve.ep_shards})"
                   if self.serve.ep_shards > 1 else ""))
        self.queue.append(_QueueEntry(int(arrival_step), req, req,
                                      None, None))
        self.stats["submitted"] += 1

    # ---- crash migration (the fabric's recovery path) ----------------

    def evacuate(self) -> tuple:
        """Crash evacuation: preempt EVERY active slot back through the
        PR 10 eviction path — each in-flight request's resumed prompt
        carries its delivered tokens, its pages free, its trace step
        span closes (``on_evict`` reopens the queued clock, so the
        fleet trace stays orphan-free) — then hand the whole queue to
        the caller.  Returns ``(inflight, queued)``: the evicted
        in-flight entries in ADMISSION order, and the entries that were
        still queued.  The engine is empty afterwards; the fabric
        re-routes both lists onto surviving replicas
        (:meth:`adopt`), and the deterministic resume makes the
        migrated token streams bit-equal to an uninterrupted run."""
        queued = list(self.queue)
        while self._evict_youngest():
            pass
        # _evict_youngest requeues at the FRONT, youngest first — so
        # the front of the deque now reads oldest-admitted .. youngest,
        # followed by the entries that were already queued
        inflight = list(self.queue)[:len(self.queue) - len(queued)]
        self.queue.clear()
        return inflight, queued

    def adopt(self, entry: _QueueEntry, *, front: bool = False) -> None:
        """Adopt a migrated queue entry from a crashed replica: a RAW
        queue insertion that preserves the entry's arrival and
        first-token clocks (the client already holds its delivered
        tokens — TTFT/TPOT must not restart) and its resumed prompt.
        ``front=True`` resumes ahead of local work: migrated in-flight
        requests outrank never-admitted ones, matching the eviction
        path's own head-of-queue discipline."""
        if front:
            # immediately admittable: the local step counter may trail
            # the dead replica's, and a resumed request must not wait
            # for it to catch up
            entry.arrival_step = min(entry.arrival_step, self.step_idx)
            self.queue.appendleft(entry)
        else:
            self.queue.append(entry)
        self.stats["adopted"] += 1

    # ---- internals ---------------------------------------------------

    def _active(self) -> list:
        return [i for i, s in enumerate(self.slots) if s is not None]

    def _decoding(self) -> list:
        """Occupied slots whose prefill has completed (the rows the
        sampler and the decode step actually advance)."""
        return [i for i, s in enumerate(self.slots)
                if s is not None and s.prefill_pos is None]

    # ---- shard-aware page accounting (ep_shards == 1: pass-through,
    # slots hold GLOBAL page ids; sharded: each slot belongs to the
    # shard owning its row block and holds shard-LOCAL ids, converted
    # to global only at the eager whole-page write sites) -------------

    def _shard_of(self, slot: int) -> int:
        return slot // (self.serve.max_batch // self.serve.ep_shards)

    def _alloc_pages(self, slot: int, n: int):
        if self.serve.ep_shards > 1:
            return self.pool.alloc(n, self._shard_of(slot))
        return self.pool.alloc(n)

    def _free_slot_pages(self, slot: int, pages) -> None:
        if self.serve.ep_shards > 1:
            self.pool.free(pages, self._shard_of(slot))
        else:
            self.pool.free(pages)

    def _global_pages(self, slot: int, pages):
        if self.serve.ep_shards > 1:
            return self.pool.to_global(pages, self._shard_of(slot))
        return pages

    # ---- the window pool (a model with window layers: a page-id space
    # and an allocator of its own; every method is a no-op without) -----

    def _window_first(self, pos: int) -> int:
        """Index, in a slot's table, of the first page a query at
        position ``pos`` still sees in a window layer."""
        return (max(0, pos - self.cfg.attn_window + 1)
                // self.serve.page_size)

    def _window_reach(self, span: int, whole_pages: bool = False) -> int:
        """Pages the windows of a span of ``span`` rows reach at most, the
        span's own among them: of a span that starts anywhere (a decode
        step's) or at a whole page (a chunk's): the width of the table a
        window layer is handed."""
        page, back = self.serve.page_size, self.cfg.attn_window - 1
        if whole_pages:
            return span // page - (-back // page)
        return 1 - (-(back + span - 1) // page)

    def _trim_window(self, s: _Slot, pos: int) -> None:
        """Give back the window pages of ``s`` that lie wholly behind the
        window of a query at ``pos`` (its NEXT query: every later one sees
        less of them); their table entries point at the scratch page."""
        if self.wpool is None:
            return
        first = min(self._window_first(pos), len(s.wpages))
        if first > s.wlive:
            self.wpool.free(s.wpages[s.wlive:first])
            s.wpages[s.wlive:first] = [SCRATCH_PAGE] * (first - s.wlive)
            self._window_freed += first - s.wlive
            s.wlive = first

    def _free_window(self, s: _Slot) -> None:
        """All of the slot's window pages back (it retires or is evicted)."""
        if self.wpool is not None:
            self.wpool.free(s.wpages[s.wlive:])
            s.wpages, s.wlive = [], 0

    def _cover_window(self, i: int, need: int) -> bool:
        """Extend slot ``i``'s window pages to ``need`` entries, evicting
        the youngest request while the window pool runs dry (as the full
        pool's growth does); False: slot ``i`` itself was evicted."""
        s = self.slots[i]
        while self.wpool is not None and len(s.wpages) < need:
            got = self.wpool.alloc(need - len(s.wpages))
            if got is not None:
                s.wpages.extend(got)
            elif not self._evict_youngest():
                raise RuntimeError("window page pool exhausted with no "
                                   "evictable request")
            elif self.slots[i] is None:         # we evicted ourselves
                return False
        return True

    def _window_table(self, s: _Slot, pos: int, width: int):
        """(``width`` ids: the slot's window pages from the first a query
        at ``pos`` sees, the scratch page past its own; the position that
        page starts at)."""
        first = self._window_first(pos)
        ids = s.wpages[first:first + width]
        return (ids + [SCRATCH_PAGE] * (width - len(ids)),
                first * self.serve.page_size)

    def _arrived_head(self) -> bool:
        return bool(self.queue) \
            and self.queue[0].arrival_step <= self.step_idx

    def _mark_arrivals(self) -> None:
        """Stamp the wall clock on every queue entry whose trace
        arrival step has been reached — the TTFT base.  A future
        arrival accrues no synthetic queue wait."""
        now = self._clock()
        for entry in self.queue:
            if entry.arrival_s is None \
                    and entry.arrival_step <= self.step_idx:
                entry.arrival_s = now
                if self.tracer is not None:
                    self.tracer.on_arrival(entry.orig.rid)

    def _shard_free_pages(self, slot: int) -> int:
        if self.serve.ep_shards > 1:
            return self.pool.shard_free_pages(self._shard_of(slot))
        return self.pool.free_pages

    def _admit(self) -> None:
        sv = self.serve
        while self._arrived_head() and None in self.slots:
            entry = self.queue[0]
            req, orig = entry.req, entry.orig
            t0 = len(req.prompt)
            blocks = {}
            if self.cfg.block_length:
                # only the prompt's WHOLE blocks are prefilled (a pad row
                # in the last token's block would be seen under the block
                # mask); its tail opens the first block
                t0 -= t0 % self.cfg.block_length
                blocks = {"tail": tuple(req.prompt[t0:])}
            t_pad = prompt_pad(t0, sv.prompt_bucket) if t0 else 0
            chunk = sv.prefill_chunk
            # a handed-off prefill is always whole: the fabric's
            # prefill pool absorbs the long prompt, so chunking (the
            # single-engine mitigation) only applies to the local path
            chunked = (chunk is not None and t_pad > chunk
                       and self._prefill_fn is None)
            n_pages = (chunk if chunked else t_pad) // sv.page_size
            # the window pool holds a first chunk whole; of a whole prompt
            # the pages its NEXT query's window reaches (the others' rows
            # go to the scratch page)
            w_skip = 0 if chunked or self.wpool is None \
                else min(self._window_first(t0), n_pages)
            n_wpages = 0 if self.wpool is None else n_pages - w_skip
            # first free slot whose shard can hold the pages (LIFO
            # alloc never partially succeeds, so free_pages >= n is
            # exactly alloc-would-succeed — the unsharded order is the
            # pre-fabric alloc-then-first-free-slot order)
            slot = None
            for i, s in enumerate(self.slots):
                if s is None and self._shard_free_pages(i) >= n_pages and (
                        not n_wpages or self.wpool.free_pages >= n_wpages):
                    slot = i
                    break
            if slot is None:
                break                      # head-of-line: deterministic
            pages = self._alloc_pages(slot, n_pages)
            window = {}
            if self.wpool is not None:
                window = dict(wpages=[SCRATCH_PAGE] * w_skip
                              + self.wpool.alloc(n_wpages), wlive=w_skip)
            self.queue.popleft()
            # the request's account: this wait ends here (a later one in
            # the same step also waited through its neighbour's prefill)
            admit_s = self._clock()
            wait_ms = max(0.0, admit_s - (
                entry.queued_s if entry.queued_s is not None
                else entry.arrival_s)) * 1e3
            self.metrics.sketch("serve.queue_wait_ms", wait_ms)
            account = dict(
                admit_s=admit_s, queue_wait_ms=entry.waited_ms + wait_ms,
                prefill_ms=entry.prefill_ms,
                last_token_s=entry.last_token_s,
                gap_max_ms=entry.gap_max_ms)
            if self.tracer is not None:
                # closes the queued span and arms prefill attribution
                # for the trace_span below
                self.tracer.on_admit(orig.rid, self.step_idx,
                                     resumed=req is not orig)
            if chunked:
                # pad out to whole chunks; trailing all-pad chunks past
                # the true end are never run (_advance_prefill stops at
                # the chunk holding the prompt's last token)
                t_pad_c = ((t_pad + chunk - 1) // chunk) * chunk
                toks = np.full((t_pad_c,), sv.pad_token, np.int32)
                toks[:t0] = req.prompt[:t0]
                self.slots[slot] = _Slot(
                    req=req, orig=orig, pages=list(pages), length=0,
                    emitted=[], admit_step=self.step_idx,
                    arrival_s=entry.arrival_s,
                    first_token_s=entry.first_token_s,
                    prefill_pos=0, prefill_toks=toks, **account, **blocks,
                    **window)
                self.stats["prefill_buckets"].add(chunk)
            elif not t0:
                # a prompt shorter than a block: nothing to prefill
                self.slots[slot] = _Slot(
                    req=req, orig=orig, pages=[], length=0, emitted=[],
                    admit_step=self.step_idx, arrival_s=entry.arrival_s,
                    first_token_s=entry.first_token_s, **account, **blocks)
            else:
                fed = self._clock(), time.time_ns()
                with trace_span("serve.prefill_feed"):
                    # eager uploads and a pad program, one by one
                    prompt = jnp.asarray(req.prompt[:t0], jnp.int32)[None, :]
                    if t_pad > t0:
                        prompt = jnp.pad(
                            prompt, ((0, 0), (0, t_pad - t0)),
                            constant_values=sv.pad_token)
                    true_len = jnp.int32(t0)
                    page_ids = jnp.asarray(
                        self._global_pages(slot, pages), jnp.int32)
                    wpage_ids = (jnp.asarray(window["wpages"], jnp.int32)
                                 if window else None)
                starved = self._queue_empty("serve.prefill")
                with trace_span("serve.prefill"):
                    # (logits, one dense run per pool of the cache)
                    if self._prefill_fn is not None:
                        logits, *seqs = self._prefill_fn(
                            prompt, t0, rid=orig.rid)
                    else:
                        logits, *seqs = _prefill_padded(
                            self.params, self.cfg, prompt, true_len)
                    self.cache = type(self.cache)(*(
                        _store_state(pool, seq, slot) if by_slot
                        else _store_prefill(
                            pool, seq,
                            wpage_ids if name in WINDOW_FIELDS else page_ids)
                        for pool, seq, by_slot, name in zip(
                            self.cache, seqs,
                            slot_state_fields(self.cache),
                            self.cache._fields)))
                    self._state_bytes += self.cfg.state_slot_bytes
                if blocks:      # no next-token logits: nothing reads them
                    self._last_out = logits
                else:
                    self._put_logits(slot, logits)
                self._note_prefill(orig.rid, slot, "whole", 0, t0, t_pad,
                                   t_pad, fed, starved)
                self.slots[slot] = _Slot(
                    req=req, orig=orig, pages=list(pages), length=t0,
                    emitted=[], admit_step=self.step_idx,
                    arrival_s=entry.arrival_s,
                    first_token_s=entry.first_token_s, **account, **blocks,
                    **window)
                self.stats["prefill_buckets"].add(t_pad)
            self._rates["admits"].add()
            self.stats["admitted"] += 1
            if self.cfg.state_layers:
                # the slot's state starts from nothing: a whole prefill
                # overwrites it, a first chunk ignores what it holds
                self.metrics.count("serve.state_resets")
            self.metrics.decision(
                "serve.admit", rid=orig.rid, step=self.step_idx,
                slot=slot, prompt_tokens=t0, pages=n_pages,
                resumed=req is not orig, chunked=chunked,
                queue_depth=len(self.queue))

    def _advance_prefill(self) -> None:
        """Advance every mid-prefill slot by exactly ONE fixed-size
        chunk (slot order — deterministic): the per-step prefill budget
        is bounded by ``prefill_chunk`` tokens per prefilling slot, so
        a long prompt is amortized across steps instead of holing one
        decode step with a monolithic prefill.  The chunk containing
        the prompt's true last token finishes the prefill: its logits
        arm the sampler and the slot joins the decode grid next
        sampling pass (this same step)."""
        sv = self.serve
        chunk = sv.prefill_chunk
        for i, s in enumerate(self.slots):
            if s is None or s.prefill_pos is None:
                continue
            pos = s.prefill_pos
            t0 = len(s.req.prompt) - len(s.tail)
            # this chunk's pages (first chunk's were allocated at
            # admission); eviction fallback mirrors _grow_pages
            need_pages = (pos + chunk) // sv.page_size
            while len(s.pages) < need_pages:
                got = self._alloc_pages(i, need_pages - len(s.pages))
                if got is not None:
                    s.pages.extend(got)
                    continue
                shard = (self._shard_of(i) if sv.ep_shards > 1
                         else None)
                if not self._evict_youngest(shard):
                    raise RuntimeError("page pool exhausted with no "
                                       "evictable request")
                if self.slots[i] is None:   # we evicted ourselves
                    break
            if self.slots[i] is None or not self._cover_window(
                    i, need_pages):
                continue
            fed = self._clock(), time.time_ns()
            with trace_span("serve.chunk_feed"):
                n_ctx_pages = ctx_pages_bucket(
                    pos + chunk, sv.page_size, sv.ctx_bucket_pages,
                    sv.max_pages_per_slot)
                # the chunk jit addresses the GLOBAL page slab (it runs
                # outside the EP shard_map); scratch fill rows are masked,
                # any valid page id serves
                gpages = self._global_pages(i, s.pages)
                table = np.full((n_ctx_pages,), SCRATCH_PAGE, np.int32)
                table[:len(gpages)] = gpages
                first_pg = pos // sv.page_size
                rel_last = min(max(t0 - 1 - pos, 0), chunk - 1)
                # eager uploads, one by one
                operands = (
                    jnp.asarray(s.prefill_toks[pos:pos + chunk])[None, :],
                    jnp.asarray(table),
                    jnp.asarray(gpages[first_pg:need_pages], jnp.int32),
                    jnp.int32(pos), jnp.int32(rel_last), jnp.int32(i))
                n_wctx = 0
                if self.wpool is not None:
                    # the pages the chunk's windows reach: its own and
                    # those of the window before its first row
                    n_wctx = min(n_ctx_pages,
                                 self._window_reach(chunk, whole_pages=True))
                    wtable, wbase = self._window_table(s, pos, n_wctx)
                    operands += ((
                        jnp.asarray(wtable, jnp.int32),
                        jnp.asarray(s.wpages[first_pg:need_pages],
                                    jnp.int32),
                        jnp.int32(wbase)),)
            if self.tracer is not None:
                # chunks interleave across slots: re-arm attribution so
                # the span lands on THIS slot's request track
                self.tracer.on_prefill_chunk(s.orig.rid)
            starved = self._queue_empty("serve.prefill_chunk")
            with trace_span("serve.prefill_chunk"):
                logits, self.cache = _INPLACE["_prefill_chunk"](
                    self.params, self.cfg, self.cache, *operands)
            self._last_out = logits
            if self.cfg.state_layers:
                self._state_bytes += 2 * self.cfg.state_slot_bytes
                if pos:
                    self.metrics.count("serve.chunk_carries")
            s.prefill_pos = pos + chunk
            # the next query: the next chunk's first row, or the first
            # token decoded after the prompt's true end
            self._trim_window(s, min(pos + chunk, t0))
            if pos <= t0 - 1 < pos + chunk:
                # prefill complete — arm the sampler, join decode
                if not self.cfg.block_length:
                    self._put_logits(i, logits)
                s.prefill_pos = None
                s.prefill_toks = None
                s.length = t0
            self._note_prefill(s.orig.rid, i, "chunk", pos,
                               min(t0, pos + chunk) - pos, chunk,
                               n_ctx_pages * sv.page_size, fed, starved,
                               n_wctx)

    def _put_logits(self, slot: int, logits) -> None:
        """A finished prefill's logits into the slot's row of the pending
        logits: an eager scatter, the last program of an admission."""
        with trace_span("serve.logits_put"):
            self._logits = self._logits.at[slot].set(logits)
        self._last_out = self._logits

    def _queue_empty(self, phase: str) -> bool:
        """Asked before every dispatch of a prefill, chunk, sampler, decode
        or verify program: whether the device has finished the program the
        engine issued last, so that its queue is empty and it idles until
        this one arrives.  Counts incidents, not time (``starved`` on the
        step's record, ``phase`` of the first as ``starved_at``)."""
        if not self._last_out.is_ready():
            return False
        if not self._starved:
            self._starved_at = phase
        self._starved += 1
        return True

    def _note_prefill(self, rid: int, slot: int, form: str, pos: int,
                      tokens: int, rows: int, ctx_rows: int, fed,
                      starved: bool, window_pages: int = 0) -> None:
        """One prefill program's account: ``tokens`` of a prompt in
        ``rows`` computed rows over a context of ``ctx_rows``, fed from
        ``fed`` (engine's clock, profiler's clock) to now.  A chunk GATHERS
        its context from the pool (``ctx_pages``: its block table,
        bucketed); a whole prompt's context is the span itself (0).
        ``window_pages``: the pages it gathers for a WINDOW layer (a model
        with such layers; its record alone carries ``window_ctx_pages``)."""
        done = self._prefills
        done[0] += 1
        done[1] += tokens
        done[2] += rows
        ctx_pages = (ctx_rows // self.serve.page_size if form == "chunk"
                     else 0)
        if ctx_pages:
            # for a scrape of an engine that has no recorder (over
            # ``serve.prefill_programs``: does ``ctx_bucket_pages`` fit?)
            self.metrics.count("serve.prefill_ctx_pages", ctx_pages)
        arm, chunks = self._expert_arm(rows)
        # the arm the program's attention layers took over their context
        # (``ops/attention.span_attention_arm``: the rule the traced
        # program asked), counted where it is the flash kernel
        attn_arm = None
        more = {}
        if self.wpool is not None:
            more["window_ctx_pages"] = window_pages
            self.metrics.count("serve.window_programs")
        if self.cfg.cache_layers:
            attn_arm = attention.span_attention_arm(
                rows, ctx_rows, self.cfg.num_heads,
                *attention.attention_widths(self.cfg), self.cfg.dtype)
            if attn_arm == "flash":
                self.metrics.count("serve.prefill_flash_programs")
        if self.recorder is not None:
            self.recorder.record(
                kind="serve_prefill", step=self.step_idx, rid=rid,
                slot=slot, form=form, pos=pos, tokens=tokens, rows=rows,
                pad_rows=rows - tokens, ctx_pages=ctx_pages, expert_arm=arm,
                expert_chunks=chunks, attn_arm=attn_arm,
                host_ms=round((self._clock() - fed[0]) * 1e3, 3),
                starved=starved, t0_trace_ns=fed[1], **more)

    def _evict_youngest(self, shard: int | None = None) -> bool:
        """Preempt the most recently admitted request back to the
        queue head; its pages free immediately.  Returns False when no
        active slot remains to evict.  ``shard`` restricts the victim
        set to one page shard (EP-sharded decode: only a same-shard
        eviction can free the pages the caller needs).  A request
        evicted mid-chunked-prefill resumes from scratch — delivered
        tokens are carried in the resumed prompt either way, so the
        resume is bit-equal regardless of how far prefill got."""
        active = self._active()
        if shard is not None:
            active = [i for i in active if self._shard_of(i) == shard]
        if not active:
            return False
        victim = max(active, key=lambda i: (self.slots[i].admit_step,
                                            self.slots[i].req.rid))
        s = self.slots[victim]
        self._free_slot_pages(victim, s.pages)
        self._free_window(s)
        delivered = self._delivered(s)
        remaining = s.orig.max_new_tokens - delivered
        # the resumed prompt carries EVERY delivered token (across any
        # number of evictions): the previous resumed prompt plus this
        # incarnation's emissions
        resumed = dataclasses.replace(
            s.req,
            prompt=tuple(s.req.prompt) + tuple(s.emitted),
            max_new_tokens=max(remaining, 1))
        # re-queue at the FRONT: the evictee is the next admission;
        # arrival AND first-token clocks survive (the client already
        # holds the delivered tokens — TTFT/TPOT must not restart)
        self.queue.appendleft(_QueueEntry(
            self.step_idx, resumed, s.orig, s.arrival_s,
            s.first_token_s, queued_s=self._clock(),
            waited_ms=s.queue_wait_ms, prefill_ms=s.prefill_ms,
            last_token_s=s.last_token_s, gap_max_ms=s.gap_max_ms))
        self.slots[victim] = None
        self.stats["evictions"] += 1
        self._rates["evictions"].add()
        if self.tracer is not None:
            self.tracer.on_evict(s.orig.rid, self.step_idx)
        self.metrics.count("serve.evictions")
        self.metrics.decision(
            "serve.evict", rid=s.orig.rid, step=self.step_idx,
            slot=victim, freed_pages=len(s.pages),
            emitted=delivered)
        return True

    def _delivered(self, s: _Slot) -> int:
        """Tokens delivered across incarnations (an evicted request's
        resumed prompt carries its earlier output)."""
        return len(s.req.prompt) - len(s.orig.prompt) + len(s.emitted)

    def _next_page(self, s: _Slot, span=0) -> int:
        """Index, in its table, of the page ``s`` writes its next row (and
        ``span`` more: a number, or a function of the slot) into, clamped
        to the table's width."""
        if callable(span):
            span = span(s)
        return min((s.length + span) // self.serve.page_size,
                   self.serve.max_pages_per_slot - 1)

    def _growth_fits(self, rows, span=0) -> bool:
        """Whether the pool, as it is, holds the next page of every slot
        of ``rows`` that stands at a page edge (or comes to one within
        ``span`` more positions: :meth:`_next_page`'s): :meth:`_grow_pages`
        then evicts nobody."""
        need = Counter()            # by page shard
        need_window = 0
        for i in rows:
            s = self.slots[i]
            next_page = self._next_page(s, span)
            need[self._shard_of(i)] += max(0, next_page + 1 - len(s.pages))
            need_window += max(0, next_page + 1 - len(s.wpages))
        free = (self.pool.shard_free_pages if self.serve.ep_shards > 1
                else lambda shard: self.pool.free_pages)
        return all(n <= free(shard) for shard, n in need.items()) and (
            self.wpool is None or need_window <= self.wpool.free_pages)

    def _grow_pages(self, rows, span=0) -> None:
        """Allocate the next page for every slot of ``rows`` (decoding
        slots) whose write position crosses its allocated frontier,
        evicting the youngest request when the pool runs dry (the caller
        has read the step's tokens then: :meth:`_growth_fits`).  ``span``
        extra positions (the verify step's drafted span, the rows of a
        denoise launch: :meth:`_next_page`'s) are pre-covered; the target
        index clamps to the slot's table width — the host truncates
        drafts to fit the context ceiling, and the verify graph routes
        any residual over-the-edge write to the scratch page."""
        shard = (self._shard_of if self.serve.ep_shards > 1
                 else lambda i: None)
        for i in rows:
            s = self.slots[i]
            if s is None:                   # evicted for a row before it
                continue
            need_idx = self._next_page(s, span)
            while need_idx >= len(s.pages):
                got = self._alloc_pages(i, 1)
                if got is not None:
                    s.pages.extend(got)
                    continue
                if not self._evict_youngest(shard(i)):
                    raise RuntimeError("page pool exhausted with no "
                                       "evictable request")
                if self.slots[i] is None:   # we evicted ourselves
                    break
            if self.slots[i] is not None:
                self._cover_window(i, need_idx + 1)

    def _spec_decode(self, active) -> int | None:
        """Speculative decode step: draft, verify the span in one
        forward, emit the drafted prefix the engine's own sampler
        agrees with (ISSUE 20).

        Exactness: the sampler keys every token on
        ``fold_in(PRNGKey(seed), token_index)`` — a TOKEN POSITION, not
        a step — so the canonical sample for drafted position ``t`` is
        computable from the verify span's column ``t-1`` logits with
        that position's own key and the shared
        :func:`_sample_dynamic` numerics.  A draft is emitted iff it
        EQUALS its canonical sample; the emitted stream is therefore
        bit-equal to non-speculative decode for every temperature /
        top-k / top-p arm, and the next step's sample pass (from the
        pending logits column this method selects) produces exactly the
        token a rejected draft was compared against.

        Returns the number of EXTRA tokens emitted (accepted drafts;
        the canonical token was already emitted by the sample pass), or
        ``None`` when no slot drafted anything — the caller then runs
        the plain one-token decode step."""
        sv = self.serve
        spec = self._spec
        k = spec.draft_tokens
        # ---- draft (host-only: per-slot suffix-match tables) ---------
        drafts: dict[int, list] = {}
        self._phase("serve.draft")
        for i in active:
            s = self.slots[i]
            hist = list(s.req.prompt) + s.emitted
            if s.draft is None:
                # deterministic rebuild from prompt + emitted: the
                # same history the eviction / migration resume
                # carries, so speculation survives both for free
                s.draft = DraftState(spec, hist)
            else:
                s.draft.sync(hist)
            dr = s.draft.draft(k)
            # truncate to the remaining token budget and the
            # context ceiling: every ACCEPTED draft's KV row must
            # land in a real page
            dr = dr[:max(0, s.orig.max_new_tokens
                         - self._delivered(s))]
            dr = dr[:max(0, sv.max_context - 1 - s.length)]
            if dr:
                drafts[i] = [int(t) for t in dr]
        if not drafts:
            return None

        # pre-cover the span's write positions (may evict — re-fetch)
        self._phase("serve.grow")
        self._grow_pages(active, span=k)
        active = self._decoding()
        if not active:
            return 0

        # ---- verify: score k+1 positions per slot in one forward ----
        # (the drafted positions' sampler rows are built with the feed)
        self._phase("serve.decode_feed")
        t_span = k + 1
        feed = np.full((sv.max_batch, t_span), sv.pad_token, np.int32)
        positions = np.zeros((sv.max_batch,), np.int32)
        tables = np.full((sv.max_batch, sv.max_pages_per_slot),
                         SCRATCH_PAGE, np.int32)
        rows = [None] * (sv.max_batch * k)
        longest = 1
        for i in active:
            s = self.slots[i]
            feed[i, 0] = s.emitted[-1]
            dr = drafts.get(i, ())
            feed[i, 1:1 + len(dr)] = dr
            positions[i] = s.length
            tables[i, :len(s.pages)] = s.pages
            longest = max(longest, s.length + t_span)
            base = self._delivered(s)   # emitted already holds tok_0
            rows[i * k:(i + 1) * k] = [(s.req, base + t)
                                       for t in range(k)]
        n_ctx = ctx_pages_bucket(longest, sv.page_size,
                                 sv.ctx_bucket_pages,
                                 sv.max_pages_per_slot)
        self.stats["decode_buckets"].add(n_ctx)
        self._phase("serve.verify")
        self._queue_empty("serve.verify")
        if self._ep_fn is not None:
            if self._ep_verify is None:
                self._ep_verify = _ep_decode_fn(
                    self.mesh, self.cfg, self.params, span=True)
            span_logits, self.cache = self._ep_verify(
                self.params, self.cache, jnp.asarray(feed),
                jnp.asarray(tables[:, :n_ctx]),
                jnp.asarray(positions))
        else:
            span_logits, self.cache = _INPLACE["_paged_verify_step"](
                self.params, self.cfg, self.cache, jnp.asarray(feed),
                jnp.asarray(tables[:, :n_ctx]),
                jnp.asarray(positions))
        self._last_out = span_logits
        self._note_ctx(n_ctx, positions[active], t_span)
        self._spec_steps += 1

        since = self._phase("serve.sample")
        # canonical samples for every drafted position: column t-1
        # logits, position-(base+t-1) key, the same sampler numerics
        cand = np.asarray(self._sample(
            span_logits[:, :k, :].reshape(sv.max_batch * k, -1),
            _sampler_rows(rows), len(active) * k
        )).reshape(sv.max_batch, k)

        # ---- accept the agreeing prefix; roll back the rest ----------
        # (the wait for the verify step: the candidates' dispatch with it)
        self._wait_ms += (self._phase("serve.deliver") - since) * 1e3
        n_extra = 0
        accepted_cols = np.zeros((sv.max_batch,), np.int32)
        for i in active:
            s = self.slots[i]
            dr = drafts.get(i, [])
            self._spec_drafted += len(dr)
            s.spec_drafted += len(dr)
            a = 0
            done = False
            for t in range(len(dr)):
                if int(cand[i, t]) != dr[t]:
                    break
                tok = dr[t]
                s.emitted.append(tok)
                a += 1
                n_extra += 1
                done = (tok in s.req.stop_tokens
                        or self._delivered(s) >= s.orig.max_new_tokens)
                if done:
                    break
            self._spec_accepted += a
            s.spec_accepted += a
            accepted_cols[i] = a
            if a and self.recorder is not None:
                rid = s.orig.rid
                self._delivered_now[rid] = \
                    self._delivered_now.get(rid, 0) + a
            s.length += 1 + a
            # roll back the block table past the accepted frontier:
            # rejected-draft rows free their surplus pages (LIFO, so
            # the next growth re-draws the same ids) and the rows
            # inside kept pages are overwritten by the next span
            # before any causal mask exposes them
            keep = (s.length - 1) // sv.page_size + 1
            if keep < len(s.pages):
                surplus = s.pages[keep:]
                del s.pages[keep:]
                self._free_slot_pages(i, surplus)
            if done:
                with trace_span("serve.retire"):
                    self._retire(i, s)
        # pending logits = the column after each slot's last emitted
        # token — exactly what the plain decode step would have
        # returned after feeding that token
        self._logits = self._last_out = span_logits[
            jnp.arange(sv.max_batch), jnp.asarray(accepted_cols)]
        return n_extra

    def set_speculate(self, enabled: bool, *, reason=None) -> None:
        """Morph speculation on/off at a step boundary (the runtime
        controller's actuator).  Off tears down nothing the sampler
        sees: draft tables idle on the slots, the next step simply runs
        the plain decode path — token streams are unchanged by
        construction, so morphing mid-request loses zero tokens."""
        if enabled and self.serve.speculate is None:
            raise ValueError(
                "cannot enable speculation: ServeConfig.speculate was "
                "never configured on this engine")
        was = self._spec is not None
        self._spec = self.serve.speculate if enabled else None
        if (self._spec is not None) != was:
            self.metrics.decision(
                "serve.spec",
                event="morph_on" if enabled else "morph_off",
                step=self.step_idx, reason=reason)

    def spec_snapshot(self) -> dict:
        """Live acceptance stats (the controller's observation feed)."""
        return dict(
            spec_stats_fields(self._spec_drafted, self._spec_accepted,
                              self._spec_steps),
            spec_steps=self._spec_steps,
            spec_on=self._spec is not None)

    def _retire(self, slot: int, s: _Slot) -> None:
        now = self._clock()
        self._free_slot_pages(slot, s.pages)
        self._free_window(s)
        self.slots[slot] = None
        out = (list(s.orig.prompt)
               + list(s.req.prompt[len(s.orig.prompt):])
               + list(s.emitted))
        self.outputs[s.orig.rid] = out
        self.stats["completed"] += 1
        n_tok = self._delivered(s)
        ttft_ms = ((s.first_token_s - s.arrival_s) * 1e3
                   if s.first_token_s is not None else None)
        tpot_ms = None
        if s.first_token_s is not None and n_tok > 1:
            tpot_ms = (now - s.first_token_s) * 1e3 / (n_tok - 1)
        # O(1)-memory rolling percentiles for the live /metrics scrape
        # (and summary()) — no per-request list grows under load
        if ttft_ms is not None:
            self.metrics.sketch("serve.ttft_ms", ttft_ms)
        if tpot_ms is not None:
            self.metrics.sketch("serve.tpot_ms", tpot_ms)
        # replica-keyed twins: the fabric's mid-drill scrape reads
        # per-replica latency sketches off the SHARED metrics object
        if self.replica_tag is not None:
            if ttft_ms is not None:
                self.metrics.sketch(
                    f"serve.{self.replica_tag}.ttft_ms", ttft_ms)
            if tpot_ms is not None:
                self.metrics.sketch(
                    f"serve.{self.replica_tag}.tpot_ms", tpot_ms)
        if self.tracer is not None:
            self.tracer.on_retire(s.orig.rid, self.step_idx,
                                  tokens=n_tok, ttft_ms=ttft_ms,
                                  tpot_ms=tpot_ms)
        spec_kw = {}
        if self.serve.speculate is not None:
            spec_kw = {
                "spec_drafted": s.spec_drafted,
                "spec_accepted": s.spec_accepted,
                "accept_rate": (round(s.spec_accepted / s.spec_drafted,
                                      6) if s.spec_drafted else None),
            }
        account = {
            "queue_wait_ms": round(s.queue_wait_ms, 3),
            "prefill_ms": (round(s.prefill_ms, 3)
                           if s.prefill_ms is not None else None),
            "gap_max_ms": round(s.gap_max_ms, 3),
        }
        self.metrics.decision(
            "serve.retire", rid=s.orig.rid, step=self.step_idx,
            slot=slot, tokens=n_tok,
            ttft_ms=round(ttft_ms, 3) if ttft_ms is not None else None,
            tpot_ms=round(tpot_ms, 3) if tpot_ms is not None else None,
            **account, **spec_kw)
        if self.recorder is not None:
            self.recorder.record(
                kind="serve_request", step=self.step_idx,
                rid=s.orig.rid, tokens=n_tok, ttft_ms=ttft_ms,
                tpot_ms=tpot_ms, **account, **spec_kw)
        if self.watchdog is not None:
            dominant = None
            if self.tracer is not None:
                # name the critical-path culprit on any breach this
                # retirement raises (the track is one closing step-span
                # short mid-step — good enough to rank components)
                from flashmoe_tpu.telemetry_plane.attribution import (
                    attribute_track,
                )

                att = attribute_track(
                    self.tracer.request_track(s.orig.rid))
                dominant = att["dominant"]
            self.watchdog.observe_request(
                self.step_idx, s.orig.rid, ttft_ms=ttft_ms,
                tpot_ms=tpot_ms, dominant=dominant)

    # ---- the engine step ---------------------------------------------

    def _phase(self, name, beat=None) -> float:
        """A phase boundary of :meth:`step`.  ONE read of the engine's
        clock closes the running phase (its time is added to this
        step's ``phase_ms`` under its name) and opens ``name`` (``None``:
        nothing) as a :func:`trace_span`, so the phase is on the
        profiler's clock too; ``beat`` goes to the heartbeat seam.
        Returns the boundary's time."""
        now = self._clock()
        if self._phase_open is not None:
            span, was, opened = self._phase_open
            span.__exit__(None, None, None)
            self._phase_ms[was] = (self._phase_ms.get(was, 0.0)
                                   + (now - opened) * 1e3)
            self._phase_open = None
        if name is not None:
            span = trace_span(name)  # staticcheck: ok the lint checks the literal at every _phase(...) call
            span.__enter__()
            self._phase_open = (span, name, now)
        if beat is not None and self._heartbeat is not None:
            self._heartbeat(beat)
        return now

    def _judge_stall(self, account: dict) -> dict | None:
        """Whether the step that ``account`` describes STALLED: the host's
        own time in it plus the caller's time before it over
        ``_STALL_FACTOR`` times the running median of that sum (the two
        sketches, as they stood before this step) and over
        ``_STALL_FLOOR_MS``.  A stall is counted (``serve.stall_steps``;
        ``serve.stall_ms``: what it took over the median), kept
        (``stats["stalls"]``, the worst as ``stats["worst_stall"]``),
        logged at most once a second, and returned as its ``serve_stall``
        record; otherwise ``None``."""
        sk_host = self.metrics.sketches.get("serve.host_ms")
        sk_between = self.metrics.sketches.get("serve.between_ms")
        if sk_between is None or sk_between.n < 8:
            return None                 # no median to speak of yet
        lag_ms = account["host_ms"] + account["between_ms"]
        median_ms = sk_host.quantile(0.5) + sk_between.quantile(0.5)
        if lag_ms <= max(_STALL_FACTOR * median_ms, _STALL_FLOOR_MS):
            return None
        # the wait lies inside serve.sample (serve.reveal for a model that
        # generates by blocks): the rest is the host's
        host_phases = dict(self._phase_ms)
        waited_in = ("serve.reveal" if self.cfg.block_length
                     else "serve.sample")
        host_phases[waited_in] = (host_phases.get(waited_in, 0.0)
                                  - self._wait_ms)
        stall = {
            "kind": "serve_stall", "step": self.step_idx,
            "median_ms": round(median_ms, 3),
            "phase": max(host_phases, key=host_phases.get),
            "phase_ms": {k: round(v, 3) for k, v in self._phase_ms.items()},
            **account,
        }
        self.metrics.count("serve.stall_steps")
        self.metrics.count("serve.stall_ms", lag_ms - median_ms)
        self.stats["stalls"].append(stall)
        worst = self.stats["worst_stall"]
        if worst is None or lag_ms > worst["host_ms"] + worst["between_ms"]:
            self.stats["worst_stall"] = stall
        now = self._step_end[0]
        if self._stall_logged_s is None or now - self._stall_logged_s >= 1.0:
            self._stall_logged_s = now
            # (the record and stats["stalls"] keep the whole phase_ms)
            _log.warning("serve_stall %s", json.dumps(
                {k: v for k, v in stall.items()
                 if k not in ("kind", "phase_ms")}))
        return stall

    def _note_ctx(self, n_ctx: int, lengths, t_span: int) -> None:
        """What this step's decode or verify program reads of the cache:
        the arm its attention takes (``ops/attention.kv_attention_arm``:
        the rule the traced program asked), the pages a slot it reads
        (mean over the decoding slots, whose contexts are ``lengths``
        before this span of ``t_span`` rows) and how many of them lie
        past what the slots' own contexts fill.  The gather arm reads
        the bucket ``n_ctx`` for every slot; the kernel reads each
        slot's context in whole blocks and the page or two it writes the
        span into (a span drafted up to the context ceiling counts pages
        past it: never under 0 idle)."""
        page = self.serve.page_size
        lengths = np.asarray(lengths)
        span_pages = (lengths + t_span - 1) // page - lengths // page + 1
        own = lengths // page + span_pages
        pools, heads, row = self.cfg.kv_pool_rows
        pool = (heads, row, self.cfg.dtype, pools)
        arm = attention.kv_attention_arm(t_span, page, *pool)
        read = n_ctx
        if arm == "paged_kernel":
            block = attention.paged_decode_block_pages(page, n_ctx, *pool)
            read = round(float(np.mean(
                -(-lengths // (block * page)) * block + span_pages)), 3)
        if self.wpool is not None:
            # a window layer's read: the table it is handed on the gather
            # arm; on the kernel's the whole blocks from the one that holds
            # the window's first key (counted from the table's first page)
            # and the page or two the span is written into
            window = self.cfg.attn_window
            n_w = min(n_ctx, self._window_reach(t_span))
            self._window_ctx = float(n_w)
            if arm == "paged_kernel":
                rel = lengths - np.maximum(lengths - window + 1, 0) \
                    // page * page
                block = attention.paged_decode_block_pages(page, n_w, *pool)
                walked = (-(-rel // (block * page))
                          - np.maximum(rel - window + 1, 0) // (block * page))
                self._window_ctx = round(float(np.mean(
                    walked * block + span_pages)), 3)
        self._ctx_pages = (read, max(0.0, read - float(own.mean())),
                           len(lengths), arm,
                           *self._expert_arm(self.serve.max_batch * t_span))

    def _expert_arm(self, rows: int) -> tuple[str | None, int | None]:
        """The arm the mixture layers of a program of ``rows`` rows take
        through their experts (``ops/moe.expert_arm``: the rule the traced
        program asked), counted where it is the grouped kernel, and there
        the chunks its launches walk the intermediate axis in
        (``ops/moe.expert_chunks``; counted where more than one: a launch
        then streams an expert once a tile of the plan, not once).  No arm
        for a model with no mixture layer and for an EP-sharded step (its
        experts are the exchange's), no chunks off the kernel."""
        mixture = self.cfg.moe_layer_indices
        if not mixture or self._ep_fn is not None:
            return None, None
        arm = expert_arm(self.cfg, rows)    # a mixture's config is cfg
        if arm != "routed_kernel":
            return arm, None
        self.metrics.count("serve.expert_kernel_programs")
        chunks = expert_chunks(self.cfg, rows)
        if chunks > 1:
            self.metrics.count("serve.expert_chunked_programs")
        return arm, chunks

    def _sample(self, logits, knobs, n_rows: int):
        """Dispatch :func:`_sample_dynamic` on ``logits`` and ``knobs``
        (:func:`_sampler_rows`; ``n_rows`` of its rows are not idle) and
        return its tokens where they lie, on the device: the caller reads
        them when it needs them on the host.  What the rows ask of the
        program is counted from the host's arrays by the program's own
        rule (:func:`_rows_ask`): rows with a temperature are drawn, and
        those of them that truncate make it sort (0 of them: it sorted
        nothing)."""
        drawn, use_k, use_p = _rows_ask(*knobs[2:], logits.shape[-1])
        self._sampled += np.array(
            [n_rows, drawn.sum(), (drawn & (use_k | use_p)).sum()])
        self._queue_empty("serve.sample")
        toks = self._last_out = _sample_dynamic(logits, *knobs)
        return toks

    def _deliver(self, rows, toks, since: float) -> int:
        """Read the step's tokens (``toks``: the sampler's array, a row a
        slot) and hand each slot of ``rows`` its own: appended, the clocks
        stamped, the request retired on a stop token or on its last token
        by count.  THE STEP'S ONE READ-BACK: it returns when the sampler
        has finished, which waits for the decode program the step before
        dispatched and for no program dispatched after the sampler; the
        time from ``since`` (the engine's clock, just read) until it
        returns is the step's ``wait_ms``.  Returns the tokens
        delivered."""
        toks = np.asarray(toks)
        now = self._phase("serve.deliver")
        self._wait_ms += (now - since) * 1e3
        for i in rows:
            s = self.slots[i]
            tok = int(toks[i])
            s.emitted.append(tok)
            if s.first_token_s is None:
                s.first_token_s = now
                s.prefill_ms = (now - s.admit_s) * 1e3
            elif s.last_token_s is not None:
                gap_ms = (now - s.last_token_s) * 1e3
                if gap_ms > s.gap_max_ms:
                    s.gap_max_ms = gap_ms
            s.last_token_s = now
            if self.recorder is not None:
                self._delivered_now[s.orig.rid] = 1
            if (tok in s.req.stop_tokens
                    or self._delivered(s) >= s.orig.max_new_tokens):
                with trace_span("serve.retire"):
                    self._retire(i, s)
        return len(rows)

    def _decode_tokens(self):
        """A step's middle for a model that generates a token at a time:
        sample, grow, decode (or draft and verify), deliver.  Returns (the
        slots sampled, whether the decode program was dispatched ahead of
        the read-back, the tokens delivered, the speculation's extra
        tokens or None)."""
        # sample each decoding slot's next token from its pending
        # logits (slots mid-chunked-prefill have none yet): dispatched,
        # not read
        sv = self.serve
        self._phase("serve.sample_keys", beat="prefill")
        emitted_now = 0
        sampled = self._decoding()
        active, toks, ahead = [], None, False
        if sampled:
            rows = [None] * sv.max_batch
            for i in sampled:
                s = self.slots[i]
                rows[i] = (s.req, self._delivered(s))
            knobs = _sampler_rows(rows)
            self._phase("serve.sample")
            toks = self._sample(self._logits, knobs, len(sampled))
            # who decodes is known without the tokens, but for a stop
            # token: a slot whose token of this step is its last by count
            # is not fed.  The decode step is dispatched AHEAD of the
            # read-back unless what stands between them needs the tokens
            # on the host: the drafts of an armed speculation are built
            # from them, and an eviction rebuilds its victim's prompt from
            # them (growth the pool cannot cover)
            active = [i for i in sampled
                      if self._delivered(self.slots[i]) + 1
                      < self.slots[i].orig.max_new_tokens]
            ahead = bool(active and self._spec is None
                         and self._growth_fits(active))
            if not ahead:
                emitted_now += self._deliver(sampled, toks, self._clock())
                active = self._decoding()

        # feed the survivors one decode step — speculative (draft +
        # span verify, possibly emitting extra tokens) when armed and
        # anything drafted, else the plain one-token step
        self._phase("serve.grow", beat="sample")
        if active:
            self._grow_pages(active)
            active = [i for i in active if self.slots[i] is not None]
        n_extra = None
        if active and self._spec is not None:
            n_extra = self._spec_decode(active)
            if n_extra is not None:
                emitted_now += n_extra
        if active and n_extra is None:
            # positions and block tables from the host's state; the FEED
            # is the sampler's array where it lies: the program feeds
            # sv.pad_token to every row whose table is all scratch (idle,
            # mid prefill, retired or evicted in this step, at its last
            # token by count)
            self._phase("serve.decode_feed")
            positions = np.zeros((sv.max_batch,), np.int32)
            tables = np.full((sv.max_batch, sv.max_pages_per_slot),
                             SCRATCH_PAGE, np.int32)
            longest = 1
            for i in active:
                s = self.slots[i]
                positions[i] = s.length
                tables[i, :len(s.pages)] = s.pages
                longest = max(longest, s.length + 1)
            n_ctx = ctx_pages_bucket(longest, sv.page_size,
                                     sv.ctx_bucket_pages,
                                     sv.max_pages_per_slot)
            self.stats["decode_buckets"].add(n_ctx)
            window = {}
            if self.wpool is not None:
                # the pages a token's window reaches, and the one it
                # writes: each slot's from the first its window sees
                n_w = min(n_ctx, self._window_reach(1))
                wtables = np.full((sv.max_batch, n_w), SCRATCH_PAGE,
                                  np.int32)
                wbase = np.zeros((sv.max_batch,), np.int32)
                for i in active:
                    s = self.slots[i]
                    wtables[i], wbase[i] = self._window_table(
                        s, s.length, n_w)
                window = {"window": (jnp.asarray(wtables),
                                     jnp.asarray(wbase))}
                self.metrics.count("serve.window_programs")
            self._phase("serve.decode")
            self._queue_empty("serve.decode")
            if self._ep_fn is not None:
                logits, self.cache = self._ep_fn(
                    self.params, self.cache, toks,
                    jnp.asarray(tables[:, :n_ctx]),
                    jnp.asarray(positions))
            else:
                logits, self.cache, counted = _INPLACE[
                    "_paged_decode_step"](
                    self.params, self.cfg, self.cache, toks,
                    jnp.asarray(tables[:, :n_ctx]),
                    jnp.asarray(positions), pad_token=sv.pad_token,
                    **window)
                self._counted = counted or None
                # every slot's state goes through the step and back
                self._state_bytes += (2 * sv.max_batch
                                      * self.cfg.state_slot_bytes)
            # the step's account of it, while the device runs it
            self._note_ctx(n_ctx, positions[active], 1)
            self._logits = self._last_out = logits
            for i in active:
                s = self.slots[i]
                s.length += 1
                self._trim_window(s, s.length)
        if ahead:
            # the wait for the sampler is the sampler's phase; the decode
            # program runs under it and under all that follows
            emitted_now += self._deliver(sampled, toks,
                                         self._phase("serve.sample"))
            self.metrics.count("serve.decode_ahead_steps")
        return sampled, ahead, emitted_now, n_extra

    def _steps_a_block(self, s: _Slot) -> int:
        return (s.req.denoise_steps or self.serve.denoise_steps
                or self.cfg.block_length)

    def _denoise(self):
        """A step's middle for a model that generates by diffusion over
        blocks (L = ``cfg.block_length``): ONE launch of
        :func:`_paged_denoise_step` advances every decoding slot's open
        block by a forward, and the blocks the LAST launch made whole are
        read and delivered.

        A slot's launch spans TWO blocks from ``length`` (``_halves``;
        see :func:`_paged_denoise_step`).  By slot: no block open -> this
        launch opens one at ``length`` (the prompt's tail first, on the
        slot's first block) and is its denoising step 0; rows still
        masked -> the next denoising step, which reveals
        ``L / denoise_steps`` of them; none masked -> the block's COMMIT
        (the forward of the clean block, whose K/V later blocks read) in
        the span's first half and, beside it in the second, the NEXT
        block opened, all ``[MASK]``, at its step 0: ``length`` advances
        by L and the slot's block is the new one, so a block costs
        ``denoise_steps`` launches and no launch reveals nothing.  The
        last block of a request (by count) is not committed: nothing
        reads it.  Where a span of two blocks would cost the attention
        the kernel's arm (``_halves`` 1) a launch spans one, and the
        commit is a launch of its own after which the next block opens.
        Under ``low_confidence_static`` and ``sequential`` the host knows
        every count without the tokens, so the launch is dispatched AHEAD
        of the read-back of the state the launch before left (that state
        is this launch's feed where it lies, the whole block its first
        half commits included, and is not donated);
        ``low_confidence_dynamic`` reveals by a threshold, so the step
        reads the masked counts first, as does a step whose growth the
        pool cannot cover (the victim's prompt is rebuilt from what was
        delivered).  An evicted slot's open block starts again.  A block
        is delivered in the step whose launch commits it, before that
        launch when the growth may evict and straight after it otherwise,
        so no eviction falls between the two: a victim resumes from a
        prompt that holds every block it committed, and loses the one
        forward its open block had.  Returns (the slots decoding, whether
        the read-back followed the dispatch, the tokens delivered)."""
        sv, bl = self.serve, self.cfg.block_length
        self._phase("serve.decode_feed", beat="prefill")
        decoding = self._decoding()
        if not decoding:
            return decoding, False, 0
        dynamic = sv.reveal_rule == "low_confidence_dynamic"
        state, read = self._block_state, None
        revealed = 0
        if dynamic:
            read = self._read_blocks(state)
            for i in decoding:
                s = self.slots[i]
                if s.block_masked == -1:
                    left = int(read[i, 1].sum())
                    revealed += int((read[i, 2] == s.block_step - 1).sum())
                    s.block_masked = left
        # blocks the last launch made whole, and those of them that end
        # their request by count (delivered, not committed)
        whole = [i for i in decoding if self.slots[i].block_masked == 0]
        closing = {i for i in whole
                   if self._delivered(self.slots[i])
                   + bl - self.slots[i].block_first
                   >= self.slots[i].orig.max_new_tokens}
        active = [i for i in decoding if i not in closing]
        fuses = lambda s: self._halves == 2 and s.block_masked == 0
        reach = lambda s: (1 + fuses(s)) * bl - 1   # rows past ``length``
        ahead = bool(active and not dynamic
                     and self._growth_fits(active, reach))
        emitted_now = 0
        if not ahead and whole:
            emitted_now += self._deliver_blocks(whole, state, read)
            active = [i for i in active if self.slots[i] is not None]
        self._phase("serve.grow", beat="sample")
        if active:
            self._grow_pages(active, span=reach)
            active = [i for i in active if self.slots[i] is not None]
        if active:
            self._phase("serve.decode_feed")
            ctl = np.zeros((sv.max_batch, 5 + bl), np.int32)
            ctl[:, 3] = -1
            tables = np.full((sv.max_batch, sv.max_pages_per_slot),
                             SCRATCH_PAGE, np.int32)
            longest, commits, fused, fed_masked = 1, 0, 0, 0
            for i in active:
                s = self.slots[i]
                ctl[i, 0] = s.length
                tables[i, :len(s.pages)] = s.pages
                longest = max(longest, s.length + reach(s) + 1)
                if fuses(s):
                    # the whole block commits in the first half; the
                    # next opens beside it (``block_first`` is the
                    # committed block's until that is delivered)
                    ctl[i, 3:5] = 0, 1
                    fused += 1
                    s.length += bl
                    s.block_step, s.block_masked = 0, bl
                elif s.block_masked is None:        # open a block
                    first = len(s.tail)
                    ctl[i, 3] = first
                    ctl[i, 5:5 + first] = s.tail
                    s.tail = ()
                    s.block_first, s.block_step = first, 0
                    s.block_masked = bl - first
                if s.block_masked:
                    n = bl // self._steps_a_block(s)
                    ctl[i, 1], ctl[i, 2] = n, s.block_step
                    fed_masked += s.block_masked
                    s.block_step += 1
                    if dynamic:
                        s.block_masked = -1
                    else:
                        revealed += min(n, s.block_masked)
                        s.block_masked = max(0, s.block_masked - n)
                else:                               # the commit, alone
                    commits += 1
                    s.length += bl
                    s.block_masked = None
            n_ctx = ctx_pages_bucket(longest, sv.page_size,
                                     sv.ctx_bucket_pages,
                                     sv.max_pages_per_slot)
            self.stats["decode_buckets"].add(n_ctx)
            self._phase("serve.denoise")
            self._queue_empty("serve.denoise")
            self._block_state, self.cache, counted = _INPLACE[
                "_paged_denoise_step"](
                self.params, self.cfg, self.cache, state, jnp.asarray(ctl),
                jnp.asarray(tables[:, :n_ctx]), pad_token=sv.pad_token,
                rule=sv.reveal_rule, threshold=sv.reveal_threshold,
                halves=self._halves)
            self._counted = counted or None
            self._last_out = self._block_state
            # the step's account of it, while the device runs it
            self._note_ctx(n_ctx, ctl[active, 0], self._halves * bl)
            self._block_counts = (bl * (len(active) + fused), revealed,
                                  commits, fed_masked, fused)
            self.metrics.count("serve.denoise_steps")
            self.metrics.count("serve.commit_rows", commits)
            self.metrics.count("serve.fused_commits", fused)
            self.metrics.count("serve.revealed_tokens", revealed)
        if ahead:
            if whole:
                emitted_now += self._deliver_blocks(whole, state, read)
            self.metrics.count("serve.decode_ahead_steps")
        return decoding, ahead, emitted_now

    def _read_blocks(self, state):
        """The slots' blocks on the host, [B, 3, L]: THE STEP'S READ-BACK.
        It waits for the launch that left ``state`` and for none
        dispatched after it; the wait is the step's ``wait_ms``."""
        since = self._phase("serve.reveal")
        read = np.asarray(state)
        self._wait_ms += (self._clock() - since) * 1e3
        return read

    def _deliver_blocks(self, rows, state, read=None) -> int:
        """Hand each slot of ``rows`` the tokens of its whole block, as
        ``state`` (what the launch that revealed its last row left) has
        them: the rows past the prompt's tail, cut at the request's last
        token by count and after a stop token (tokens past either are
        dropped); the clocks stamped, the request retired on either.
        Beside the tokens, the denoising step that revealed each
        (``reveal_steps``).  Returns the tokens delivered."""
        if read is None:
            read = self._read_blocks(state)
        now = self._phase("serve.reveal")
        n_tokens = 0
        for i in rows:
            s = self.slots[i]
            toks = read[i, 0, s.block_first:].tolist()
            steps = read[i, 2, s.block_first:].tolist()
            left = s.orig.max_new_tokens - self._delivered(s)
            stop = next((j for j, t in enumerate(toks[:left])
                         if t in s.req.stop_tokens), None)
            done = stop is not None or len(toks) >= left
            toks = toks[:left if stop is None else stop + 1]
            s.emitted.extend(toks)
            self.reveal_steps.setdefault(s.orig.rid, []).extend(
                steps[:len(toks)])
            n_tokens += len(toks)
            if s.first_token_s is None:
                s.first_token_s = now
                s.prefill_ms = (now - s.admit_s) * 1e3
            elif s.last_token_s is not None:
                gap_ms = (now - s.last_token_s) * 1e3
                if gap_ms > s.gap_max_ms:
                    s.gap_max_ms = gap_ms
            s.last_token_s = now
            s.block_first = 0
            if self.recorder is not None:
                self._delivered_now[s.orig.rid] = len(toks)
            if done:
                with trace_span("serve.retire"):
                    self._retire(i, s)
        self.metrics.count("serve.blocks_delivered", len(rows))
        return n_tokens

    def step(self) -> dict:
        """One engine iteration: admit -> sample -> grow -> decode ->
        read the tokens, deliver, retire (the decode program is dispatched
        AHEAD of the step's one read-back); sample -> read, deliver,
        retire -> grow -> decode on a step where the host needs the tokens
        in between (speculation armed, growth that would evict).  Either
        way every token sampled in the step is delivered and every
        finished request retired when it returns.  A model that
        generates by blocks runs :meth:`_denoise` in the middle instead:
        a step may then deliver several tokens a slot or none.  Returns
        the step's flight record (also appended to the recorder when one
        is attached)."""
        with trace_span("serve.step", step=self.step_idx):
            try:
                return self._step()
            finally:
                if self._phase_open is not None:    # the step raised
                    self._phase(None)

    def _step(self) -> dict:
        sv = self.serve
        compiles0, compile_s0 = compile_totals()
        gc_n0, gc_s0 = gc_totals()
        self._phase_ms = {}
        self._delivered_now = {}
        self._ctx_pages = (0, 0.0, 0, None, None, None)
        self._sampled = np.zeros((3,), np.int64)
        self._state_bytes = 0
        self._wait_ms = 0.0
        self._starved, self._starved_at = 0, None
        self._prefills = [0, 0, 0]
        self._block_counts = None
        self._window_freed, self._window_ctx = 0, 0.0
        if self._counted is not None:
            self._counted_prev, self._counted = self._counted, None
        # the same instant on the profiler's clock and on the engine's
        t0_trace_ns, cpu0_s = time.time_ns(), time.thread_time()
        t0_s = self._phase("serve.admit")
        if self.tracer is not None:
            # open the step window BEFORE admissions: everything in
            # this step (a neighbour's prefill compile included) rides
            # a serve.step span on each active request's track
            self.tracer.begin_step(
                self.step_idx,
                [self.slots[i].orig.rid for i in self._active()])
        self._mark_arrivals()
        admitted0 = self.stats["admitted"]
        retired0 = self.stats["completed"]
        self._admit()
        self._phase("serve.prefill_advance", beat="admit")
        self._advance_prefill()

        if self.cfg.block_length:
            sampled, ahead, emitted_now = self._denoise()
            n_extra = None
        else:
            sampled, ahead, emitted_now, n_extra = self._decode_tokens()
        self.stats["tokens"] += emitted_now

        # telemetry
        self._phase("serve.account", beat="decode")
        if self._vclock is not None:
            # charge the decode tick INSIDE the step window (before
            # end_step closes it): virtual step duration becomes
            # max(tick, handoff time), so transfers overlap the tick
            # and request tracks stay contiguous in virtual time
            self._vclock.complete_step()
        if self.tracer is not None:
            self.tracer.end_step()
        n_active = len(self._active())
        qd = len(self.queue)
        occ = self.pool.occupancy
        self.stats["steps"] += 1
        self.stats["max_queue_depth"] = max(self.stats["max_queue_depth"],
                                            qd)
        self.stats["max_active"] = max(self.stats["max_active"], n_active)
        self.stats["peak_occupancy"] = max(self.stats["peak_occupancy"],
                                           occ)
        self.metrics.gauge("serve.queue_depth", qd)
        self.metrics.gauge("serve.active_requests", n_active)
        self.metrics.gauge("serve.cache_occupancy", occ)
        # rolling distributions + windowed rates for the live scrape
        self.metrics.sketch("serve.queue_depth_dist", qd)
        self.metrics.gauge("serve.tokens_per_s",
                           self._rates["tokens"].add(emitted_now))
        self.metrics.gauge("serve.admits_per_s",
                           self._rates["admits"].rate())
        self.metrics.gauge("serve.evictions_per_s",
                           self._rates["evictions"].rate())
        for name, ms in self._phase_ms.items():
            self.metrics.sketch(f"serve.phase.{name[6:]}_ms", ms)
        # the step ends HERE on the engine's clock: its phases add up to
        # step_ms, and what follows (two sketches, the record) is the
        # only part of step() that no phase holds
        t1_s = self._phase(None)
        step_ms = (t1_s - t0_s) * 1e3
        self.metrics.sketch("serve.step_ms", step_ms)
        self.metrics.sketch("serve.phase.account_ms",
                            self._phase_ms["serve.account"])
        compiles1, compile_s1 = compile_totals()
        # ---- the host's account of the step --------------------------
        # wall: blocked on the device / the host's own / the caller's
        # before the step; the thread's CPU over the step and over the
        # caller's time; its context switches and page faults since the
        # last step ended (one getrusage a step), collections that ended
        # inside the step
        cpu1_s = time.thread_time()
        switches, faults = _thread_rusage()
        gc_n1, gc_s1 = gc_totals()
        first = self._step_end is None
        last = (t0_s, cpu0_s, switches, faults) if first else self._step_end
        self._step_end = (t1_s, cpu1_s, switches, faults)
        host_ms = step_ms - self._wait_ms
        between_ms = (t0_s - last[0]) * 1e3
        n_prefills, prefill_tokens, prefill_rows = self._prefills
        held = len(sampled) if self._starved else 0
        account = {
            "t0_trace_ns": t0_trace_ns,
            "wait_ms": round(self._wait_ms, 3),
            "host_ms": round(host_ms, 3),
            "between_ms": round(between_ms, 3),
            "cpu_ms": round((cpu1_s - cpu0_s) * 1e3, 3),
            "between_cpu_ms": round((cpu0_s - last[1]) * 1e3, 3),
            "ctx_switches": switches - last[2],
            "page_faults": faults - last[3],
            "gc_n": int(gc_n1 - gc_n0),
            "gc_ms": round((gc_s1 - gc_s0) * 1e3, 3),
            "compiles": int(compiles1 - compiles0),
            "compile_ms": round((compile_s1 - compile_s0) * 1e3, 3),
            "admitted": self.stats["admitted"] - admitted0,
            "retired": self.stats["completed"] - retired0,
            "prefill_programs": n_prefills,
        }
        if self._starved:
            self.metrics.count("serve.starved_dispatches", self._starved)
            self.metrics.count("serve.held_steps")
        if n_prefills:
            self.metrics.count("serve.prefill_programs", n_prefills)
            self.metrics.count("serve.prefill_tokens", prefill_tokens)
            self.metrics.count("serve.prefill_rows", prefill_rows)
        stall = self._judge_stall(account)
        self.metrics.sketch("serve.host_ms", host_ms)
        if not first:
            self.metrics.sketch("serve.between_ms", between_ms)
        (ctx_pages, ctx_idle, n_decoding, attn_arm, ffn_arm,
         ffn_chunks) = self._ctx_pages
        sample_rows, sample_drawn, sample_sorted = map(int, self._sampled)
        if sample_rows:
            self.metrics.count("serve.sample_steps")
            self.metrics.count("serve.sample_sort_steps",
                               float(sample_sorted > 0))
        if attn_arm == "paged_kernel":
            self.metrics.count("serve.decode_kernel_steps")
        rec = {
            "kind": "serve_step", "step": self.step_idx,
            "active": n_active, "queue_depth": qd,
            "pages_used": self.pool.used_pages,
            "cache_occupancy": round(occ, 4),
            "tokens": emitted_now,
            "completed": self.stats["completed"],
            "step_ms": round(step_ms, 3),
            "t0_s": t0_s, "t1_s": t1_s,
            "phase_ms": {k: round(v, 3)
                         for k, v in self._phase_ms.items()},
            **account,
            "starved": self._starved, "starved_at": self._starved_at,
            "held_slots": held,
            "prefill_tokens": prefill_tokens, "prefill_rows": prefill_rows,
            "ctx_pages": ctx_pages,
            "ctx_pages_idle": round(ctx_idle, 3),
            "kv_token_bytes": self.cfg.kv_pool_token_bytes,
            "sample_rows": sample_rows, "sample_drawn": sample_drawn,
            "sample_sorted": sample_sorted,
        }
        if sampled:
            rec["readback"] = ("after_dispatch" if ahead
                               else "before_dispatch")
        if self.cfg.state_layers:
            rec["state_bytes"] = self._state_bytes
        windowed = {}
        if self.wpool is not None:
            # the window pool beside ``pages_used``: its pages in use, the
            # pages this step gave back to it, the pages a slot the decode
            # program read in a window layer (beside ``ctx_pages``)
            windowed = {"window_pages_used": self.wpool.used_pages,
                        "window_pages_freed": self._window_freed,
                        "window_ctx_pages": self._window_ctx}
            rec.update(windowed)
            self.metrics.gauge("serve.window_pool_pages",
                               self.wpool.used_pages)
            if self._window_freed:
                self.metrics.count("serve.window_pages_freed",
                                   self._window_freed)
        # the latest decode program that has finished (the very first
        # record waits for its own)
        counted = (self._counted_prev if self._counted_prev is not None
                   else self._counted)
        if counted is not None and self.recorder is not None:
            counted = {k: round(float(v), 3)
                       for k, v in jax.device_get(counted).items()}
            rec.update(counted)
        if self.serve.speculate is not None:
            rec["spec_tokens"] = int(n_extra or 0)
            rec["spec_on"] = self._spec is not None
        if self.recorder is not None:
            # every token of the step has the time t1_s: the per-token
            # gaps are a reduction of these records
            rec["delivered"] = [[rid, n] for rid, n
                                in self._delivered_now.items()]
            self.recorder.record(**rec)
            # the slot-steps the host held up, one record a step under a
            # kind of its own: a reader that means over records of a kind
            # INDEXES the field, and a program without it has none of
            # this kind where it has ``serve_step`` records without
            self.recorder.record(
                kind="serve_held", step=self.step_idx,
                decoding=len(sampled), starved=self._starved,
                starved_at=self._starved_at, held_slots=held)
            if stall is not None:
                self.recorder.record(**stall)
            if ctx_pages:
                # the decode program's shape, one record per step that
                # ran it: a mean over these is a mean over decode steps
                more = dict(windowed)
                if self.cfg.state_layers:
                    # the program streams EVERY row's state through the
                    # step, live or not: beside ``slots``, the rows that
                    # got a token
                    more["state_rows"] = sv.max_batch
                    more["state_bytes"] = (2 * sv.max_batch
                                           * self.cfg.state_slot_bytes)
                if counted is not None:
                    more.update(counted)
                    if "zero_rows" in counted:
                        self.metrics.count("serve.zero_rows",
                                           counted["zero_rows"])
                if self._block_counts is not None:
                    # the denoise program's launch: the live rows fed (L
                    # a slot; 2 L where a commit rode beside the block it
                    # opened), the tokens it revealed, the slots whose
                    # forward revealed nothing (a commit alone), the rows
                    # fed as [MASK], the slots that committed AND opened
                    (span_rows, revealed, commits, fed_masked,
                     fused) = self._block_counts
                    more.update(
                        span_rows=span_rows, revealed=revealed,
                        commit_rows=commits, masked_rows=fed_masked,
                        fused_rows=fused)
                self.recorder.record(
                    kind="serve_decode", step=self.step_idx,
                    slots=n_decoding, ctx_pages=ctx_pages,
                    ctx_pages_idle=rec["ctx_pages_idle"],
                    attn_arm=attn_arm, expert_arm=ffn_arm,
                    expert_chunks=ffn_chunks, **more)
        if self.watchdog is not None:
            self.watchdog.observe_step(self.step_idx, step_ms)
        self.step_idx += 1
        if self._heartbeat is not None:
            self._heartbeat("end")
        return rec

    # ---- drivers -----------------------------------------------------

    def pending(self) -> bool:
        return bool(self.queue) or bool(self._active())

    def run(self, requests=None, arrivals=None, *, until=None) -> dict:
        """Drive to completion.  ``requests``: iterable of
        :class:`Request`; ``arrivals``: matching arrival steps (default
        all 0 — the seeded arrival trace of a drill).  ``until``: an
        optional zero-arg predicate that PAUSES the drive early when it
        turns true (the live-plane mid-drill scrape; call ``run()``
        again to finish) — the max_steps wedge guard applies either
        way.  Returns {rid: full token list (prompt + generated)}."""
        for idx, req in enumerate(requests or ()):
            self.submit(req, int(arrivals[idx]) if arrivals else 0)
        while self.pending() and not (until is not None and until()):
            if self.step_idx >= self.serve.max_steps:
                raise RuntimeError(
                    f"engine exceeded max_steps={self.serve.max_steps} "
                    f"with work pending")
            self.step()
        return dict(self.outputs)

    def summary(self) -> dict:
        s = dict(self.stats)
        s["stalls"] = list(s["stalls"])
        s["decode_buckets"] = sorted(s["decode_buckets"])
        s["prefill_buckets"] = sorted(s["prefill_buckets"])
        # O(1)-memory: the retire-time sketches, not a decision scan
        # (the decision list grows without bound under sustained load)
        tt = self.metrics.sketches.get("serve.ttft_ms")
        if tt is not None and tt.n:
            s["ttft_ms_mean"] = round(tt.mean, 3)
            s["ttft_ms_max"] = round(tt.max, 3)
            s["ttft_ms_p99"] = round(tt.quantile(0.99), 3)
        tp = self.metrics.sketches.get("serve.tpot_ms")
        if tp is not None and tp.n:
            s["tpot_ms_mean"] = round(tp.mean, 3)
        if self.serve.speculate is not None:
            s.update(self.spec_snapshot())
        s["decode_plan"] = list(self.decode_plan)
        s["prefill_plan"] = list(self.prefill_plan)
        if self.quant_info is not None:
            s["expert_quant"] = self.quant_info["expert_quant"]
            s["quant_freed_mb"] = round(
                self.quant_info["freed_bytes"] / 2**20, 3)
            s["quant_extra_kv_pages"] = self.quant_info["extra_kv_pages"]
        return s
