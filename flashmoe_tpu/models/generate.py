"""Autoregressive generation with a KV cache.

Inference support for the flagship transformer (the reference is
forward-only over random tensors; a complete framework serves models).
Decode runs as a ``lax.scan`` over steps with a static-shape KV cache —
one token per step through the same parameter tree as training, MoE layers
included (top-k routing per decoded token).

Prefill has two arms: the original one-token-at-a-time ``fori_loop``
(the fallback — exact drop semantics for capacity configs) and a batched
single-pass prefill (full-sequence forward with a causal mask writing
the whole cache in one shot — one kernel launch chain instead of T0).
``prefill='auto'`` picks batched for dropless configs, where the two
arms are logits-equal (asserted by tests/test_generate.py), and the
loop for ``drop_tokens=True`` configs, whose capacity competition is
per-step by construction.

Sampling supports greedy, temperature, top-k and nucleus (top-p)
truncation, plus per-request stop tokens — the retirement primitive the
continuous-batching engine (:mod:`flashmoe_tpu.serving.engine`) builds
on.  That engine's batch mixes requests, so it has a sampler of its own
with the knobs as vectors; tests/test_serving.py holds its rows to
:func:`sample_tokens`' tokens.

A model that generates by diffusion over blocks (``cfg.block_length``)
has a loop of its own beside the token loop, :func:`generate_blocks`: a
block of positions starts masked, ``denoise_steps`` forwards reveal it by
one of :data:`REVEAL_RULES` (:func:`reveal_rows`, the engine's rule too)
and one more forward of the clean block commits its K/V.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from flashmoe_tpu.config import FFN_PARTS, MoEConfig
from flashmoe_tpu.models.transformer import (
    embed_tokens, head_logits, join_stream, part_out, rms_norm,
)
from flashmoe_tpu.ops.attention import MIXER_SPANS, paged_attention
from flashmoe_tpu.ops.moe import expert_arm, moe_layer
from flashmoe_tpu.utils.telemetry import trace_span


class KVCache(NamedTuple):
    """The dense cache of a K/V model, ``[L, B, N_kv, T_max, D]`` each:
    a K/V page pool (``serving/kvcache.PagedKVCache``'s layout) of one
    ``T_max``-row page a batch row, which is how :func:`span_forward`
    takes it."""

    k: jax.Array
    v: jax.Array


class LatentCache(NamedTuple):
    """The dense cache of an MLA model: one latent row a token a layer,
    ``[L, B, T_max, R]``, the row padded to whole lanes: a latent pool
    (``serving/kvcache.LatentPagedCache``'s layout) of one ``T_max``-row
    page a batch row."""

    c: jax.Array


def init_cache(cfg: MoEConfig, batch: int, max_len: int):
    """The dense cache: the paged cache of ``serving/kvcache`` with one
    ``max_len``-row page a batch row, one slot a batch row."""
    from flashmoe_tpu.serving.kvcache import cache_arrays

    arrays = cache_arrays(cfg, batch, max_len, batch)
    if cfg.state_layers or cfg.window_layers:
        return arrays
    return (LatentCache if cfg.attention_kind == "mla" else KVCache)(*arrays)


def span_forward(params, cfg: MoEConfig, x, cache, pos, write,
                 block_tables, *, absorbed: bool, mixture=None, valid=None,
                 slots=None, fresh=None):
    """The model's layers over a span of T tokens a slot: the ONE layer
    loop of every cached path of either attention kind (the serving
    engine's prefill, chunked prefill, decode and verify programs, their
    EP-sharded twins, and :func:`generate`).

    x: [B, T, H] embedded tokens; cache: the pools as a NamedTuple of
    arrays (a paged cache of ``serving/kvcache`` or a dense one of this
    module), or None (a whole prompt at once: nothing cached yet); pos:
    [B, T] absolute positions; write / block_tables / absorbed: see
    :func:`~flashmoe_tpu.ops.attention.kv_paged_attention` and
    ``mla_paged_attention``; valid / slots / fresh: which positions are
    real, which slot's state a row owns and whether it starts from
    nothing (read by the layers that keep a state a slot alone:
    ``ops/attention.paged_attention``).  ``mixture(moe_params, rows, cfg)`` runs the
    experts of the mixture layers in place of :func:`moe_layer` (the EP
    programs' exchange), called where a mixture is READ: a layer whose
    feed-forward name opens a branch (``MoEConfig.layer_ffns``) computes
    the mixture from the part's normed input there, and the output is
    carried to the layer that joins it, whose mixer and feed-forward part
    do not see it (nothing of the mixture depends on them either, so a
    compiler may run it under them).  Returns (x pre-final-norm
    [B, T, H], the cache, the span's rows, one array ``[L, B, ...]`` for each array of the
    cache over the layers that own it, and what the mixture layers
    counted, each a mean over them: ``experts_touched``, the experts that
    at least one routed row of the span reached, and, for a config that
    holds a share of the experts, ``held_rows``, the routed rows that
    fell on them, and, for one that routes over zero-compute experts,
    ``zero_rows``, those that fell on these)."""
    b, t, _ = x.shape
    pools = None if cache is None else tuple(cache)
    rows, held, touched, zero = [], [], [], []

    def feed_forward(onto, ffn_params, f_in, layer_cfg, routed: bool,
                     layer=None):
        """``onto`` + the part's output over ``f_in`` (None: the output
        alone), counting what its router chose.  ``layer``: the layer
        whose part it is (its output norm, ``cfg.part_out_norm``)."""
        if mixture is not None and routed:
            o = mixture(ffn_params, f_in, layer_cfg)
        else:
            o = moe_layer(ffn_params, f_in, layer_cfg, use_pallas=False,
                          routed_rows=expert_arm(layer_cfg, b * t)
                          != "capacity")
        out = o.out.reshape(b, t, -1).astype(x.dtype)
        if onto is not None:
            out = join_stream(cfg, onto, part_out(cfg, layer,
                                                  "ffn_out_norm", out))
        if layer_cfg.num_experts > 1:
            touched.append(jnp.sum(o.expert_counts > 0))
        if layer_cfg.experts_held:
            held.append(jnp.sum(o.expert_counts[
                cfg.expert_first:cfg.expert_first + cfg.experts_held]))
        if layer_cfg.zero_experts:
            zero.append(jnp.sum(o.expert_counts[cfg.num_experts:]))
        return out

    carried = None
    for li, (layer, (mixer, ffn)) in enumerate(zip(params["layers"],
                                                   cfg.layers)):
        # a layer is the parts ``cfg.layers`` names, each behind its norm
        if mixer is not None:
            scope = MIXER_SPANS[mixer]
            with trace_span(scope):  # staticcheck: ok a MIXER_SPANS name
                a, pools, span = paged_attention(
                    layer, rms_norm(x, layer["attn_norm"], cfg.norm_eps),
                    cfg, pools, li, pos, write, block_tables,
                    absorbed=absorbed, valid=valid, slots=slots,
                    fresh=fresh)
                rows.append(span)
                x = join_stream(cfg, x, part_out(cfg, layer,
                                                 "attn_out_norm", a))
        part, branch = FFN_PARTS[ffn]
        if part is None:
            continue
        with trace_span("ffn.moe") if part == "moe" \
                else trace_span("ffn.dense"):
            f_in = rms_norm(x, layer["ffn_norm"], cfg.norm_eps).reshape(
                b * t, -1)
            x = feed_forward(x, layer["moe"], f_in, cfg.ffn_config(li),
                             part == "moe", layer)
        if branch == "moe":
            with trace_span("ffn.moe"):
                carried = feed_forward(
                    None, layer["branch"], f_in,
                    cfg.ffn_config(li, branch=True), True)
        elif branch == "join":
            with trace_span("moe.shortcut_join"):
                x = join_stream(cfg, x, carried)
    if cache is not None:
        cache = type(cache)(*pools)
    counted = {name: jnp.mean(jnp.stack(per_layer).astype(jnp.float32))
               for name, per_layer in (("experts_touched", touched),
                                       ("held_rows", held),
                                       ("zero_rows", zero)) if per_layer}
    return x, cache, tuple(
        jnp.stack([r for r in of_pool if r is not None])
        for of_pool in zip(*rows)), counted


def _dense_span(params, cfg: MoEConfig, x, cache, pos, absorbed: bool):
    """:func:`span_forward` over the dense cache: batch row b owns page
    b, and a span starting at ``pos`` writes rows pos..pos+T-1."""
    b, t, _ = x.shape
    positions = jnp.broadcast_to(
        pos + jnp.arange(t, dtype=jnp.int32)[None, :], (b, t))
    rows_b = jnp.broadcast_to(jnp.arange(b, dtype=jnp.int32)[:, None],
                              (b, t))
    x, cache, _, _ = span_forward(
        params, cfg, x, cache, positions, (rows_b, positions),
        rows_b[:, :1], absorbed=absorbed)
    return x, cache


def _decode_step(params, cfg: MoEConfig, x, cache, pos):
    """One token through all layers. x: [B, 1, H]; pos: [] current index."""
    x, cache = _dense_span(params, cfg, x, cache, pos, absorbed=True)
    return lm_logits(params, cfg, x), cache


def prefill_forward(params, cfg: MoEConfig, prompt, cache):
    """Single-pass prefill core: the full prompt through every layer at
    once, causal-masked, writing the cache in one shot.

    prompt: [B, T0] int32.  Returns (x [B, T0, H] pre-final-norm hidden
    states, cache with positions [0, T0) filled).  The same span path as
    :func:`_decode_step` with T0 query positions, so the two prefill arms
    stay logits-equal on dropless configs (capacity configs compete for
    slots per call, so their drop pattern is step-count-dependent — use
    the loop arm there)."""
    x = embed_tokens(params, cfg, prompt)  # [B, T0, H]
    return _dense_span(params, cfg, x, cache, jnp.int32(0), absorbed=False)


def lm_logits(params, cfg: MoEConfig, h):
    """Final-norm + lm_head on [B, 1, H] hidden states -> [B, V] f32
    (the exact tail :func:`_decode_step` applies, shared so every
    consumer produces bit-identical logits from the same hidden)."""
    return head_logits(params, cfg, h)[:, 0]  # [B, V]


def lm_logits_span(params, cfg: MoEConfig, h):
    """The multi-position twin of :func:`lm_logits`: final-norm +
    lm_head over a [B, T, H] hidden SPAN -> [B, T, V] f32.  The serving
    engine's speculative verify step (ISSUE 20) scores ``k+1`` drafted
    positions per slot in one forward and needs the lm head at every
    one of them; sharing the tail here keeps each column bit-identical
    to what :func:`lm_logits` produces from the same hidden row."""
    return head_logits(params, cfg, h)  # [B, T, V]


def prefill_batched(params, cfg: MoEConfig, prompt, cache: KVCache):
    """Single-pass prefill: :func:`prefill_forward` + the lm head on
    the LAST prompt position.  Returns (logits [B, V], filled cache)."""
    x, cache = prefill_forward(params, cfg, prompt, cache)
    return lm_logits(params, cfg, x[:, -1:]), cache


def prefill_loop(params, cfg: MoEConfig, prompt, cache: KVCache):
    """One-token-at-a-time prefill (the original arm): exact per-step
    capacity semantics, T0 sequential launches."""
    b, t0 = prompt.shape

    def body(i, carry):
        cache, _ = carry
        x = embed_tokens(params, cfg, prompt[:, i])[:, None, :]
        logits, cache = _decode_step(params, cfg, x, cache, i)
        return cache, logits

    cache, logits = jax.lax.fori_loop(
        0, t0, body, (cache, jnp.zeros((b, cfg.vocab_size), jnp.float32))
    )
    return logits, cache


def sample_tokens(logits, key, *, temperature: float = 0.0,
                  top_k: int = 0, top_p: float = 1.0):
    """Sample next tokens from [B, V] f32 logits -> [B] int32.

    ``temperature=0`` is greedy (argmax; ``key`` unused).  ``top_k > 0``
    truncates to the k highest logits; ``top_p < 1`` applies nucleus
    truncation (smallest prefix of the sorted distribution whose mass
    reaches ``top_p`` — the top token always survives).  Truncations
    compose (top-k first, then top-p over the survivors).  The knobs
    are static here, one set for the batch.  The serving engine mixes
    requests, so its sampler takes them as vectors
    (``serving.engine._sample_dynamic``: the same arithmetic row by row,
    which ``tests/test_serving.py`` holds token-equal to this one)."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if not 0 < top_p <= 1.0:
        raise ValueError(f"top_p={top_p} must be in (0, 1]")
    if top_k < 0:
        raise ValueError(f"top_k={top_k} must be >= 0")
    logits = logits.astype(jnp.float32) / temperature
    neg = jnp.asarray(-1e30, logits.dtype)
    if top_k and top_k < logits.shape[-1]:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, neg, logits)
    if top_p < 1.0:
        sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        csum = jnp.cumsum(probs, axis=-1)
        # keep entries whose preceding mass is < top_p (the argmax has
        # preceding mass 0, so at least one entry always survives)
        keep = (csum - probs) < top_p
        thresh = jnp.min(
            jnp.where(keep, sorted_desc, jnp.inf), axis=-1, keepdims=True)
        logits = jnp.where(logits < thresh, neg, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


#: how a denoising step picks the masked rows of a block it reveals, n of
#: them a step (``block_length / denoise_steps``): the n of highest
#: confidence (ties to the lower index); the n leftmost; every row whose
#: confidence is over a threshold, and the n of highest confidence where
#: fewer are
REVEAL_RULES = ("low_confidence_static", "sequential",
                "low_confidence_dynamic")


def reveal_rows(logits, masked, n, *, rule: str, threshold: float,
                mask_token_id: int):
    """One denoising step's choice over a block a row.  logits:
    [B, L, V] float32, row i's logits position i's OWN token (no shift);
    masked: [B, L] bool; n: [B] int32, the rows a batch row reveals at
    least (0: none, a commit or an idle row).  Greedy: ``x0`` is the
    ``argmax`` with the ``[MASK]`` id left out, its confidence
    ``softmax(logits)[x0]``.  Returns (x0 [B, L] int32, reveal [B, L]
    bool, a subset of ``masked``).  ONE rule for :func:`generate_blocks`
    and the serving engine's denoise program."""
    if rule not in REVEAL_RULES:
        raise ValueError(f"reveal rule {rule!r} not in {REVEAL_RULES}")
    b, l, v = logits.shape
    logits = jnp.where(jnp.arange(v) == mask_token_id,
                       jnp.float32(-1e30), logits)
    top = jnp.max(logits, axis=-1)
    x0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    conf = 1.0 / jnp.sum(jnp.exp(logits - top[..., None]), axis=-1)
    idx = jnp.arange(l)
    key = (jnp.broadcast_to(-idx.astype(jnp.float32), (b, l))
           if rule == "sequential" else conf)
    key = jnp.where(masked, key, -jnp.inf)
    ahead = ((key[:, None, :] > key[:, :, None])
             | ((key[:, None, :] == key[:, :, None])
                & (idx[None, None, :] < idx[None, :, None])))
    reveal = masked & (jnp.sum(ahead, axis=-1) < n[:, None])
    if rule == "low_confidence_dynamic":
        over = masked & (conf > threshold) & (n > 0)[:, None]
        reveal = jnp.where((jnp.sum(over, axis=-1) >= n)[:, None], over,
                           reveal)
    return x0, reveal


@functools.partial(
    jax.jit, static_argnames=("cfg", "max_new_tokens", "denoise_steps",
                              "reveal_rule", "reveal_threshold",
                              "stop_tokens", "pad_token", "with_logits"))
def generate_blocks(params, prompt, cfg: MoEConfig, *,
                    max_new_tokens: int = 32,
                    denoise_steps: int | None = None,
                    reveal_rule: str = "low_confidence_static",
                    reveal_threshold: float = 0.9,
                    stop_tokens: tuple = (), pad_token: int = 0,
                    with_logits: bool = False):
    """Greedy generation by diffusion over blocks of ``cfg.block_length``
    (L) positions, over the dense cache: the oracle of the serving
    engine's denoise path.

    The first ``T0 // L * L`` prompt tokens are prefilled under the
    block-causal mask; the prompt's tail opens the first block already
    revealed and every other position starts as ``cfg.mask_token_id``.
    A block takes ``denoise_steps`` (S, a divisor of L; None: L) forwards
    of its L rows as they stand, each revealing ``L / S`` masked rows by
    ``reveal_rule`` (:func:`reveal_rows`; a step that finds none masked
    reveals none), and ONE more forward of the clean block that writes
    the K/V later blocks read (the commit).  A row's stop token ends it:
    the stop token is emitted, later positions are ``pad_token``.

    prompt: [B, T0] int32.  Returns (tokens [B, T0 + max_new_tokens],
    steps [B, max_new_tokens]: the denoising step that revealed each
    answer position; and with ``with_logits`` the float32 logits of every
    denoising forward, [blocks, S, B, L, V])."""
    bl = cfg.block_length
    if not bl:
        raise ValueError("generate_blocks needs a config with a "
                         "block_length")
    s_steps = denoise_steps or bl
    if bl % s_steps:
        raise ValueError(f"denoise_steps={s_steps} must divide "
                         f"block_length={bl}")
    b, t0 = prompt.shape
    t_pre = t0 // bl * bl
    tail = t0 - t_pre
    nb = -(-(tail + max_new_tokens) // bl)
    cache = init_cache(cfg, b, t_pre + nb * bl)
    if t_pre:
        _, cache = prefill_forward(params, cfg, prompt[:, :t_pre], cache)
    open_toks = jnp.full((b, nb * bl), cfg.mask_token_id, jnp.int32)
    open_toks = open_toks.at[:, :tail].set(prompt[:, t_pre:])
    n_reveal = jnp.full((b,), bl // s_steps, jnp.int32)

    def forward(cache, toks, masked, pos):
        feed = jnp.where(masked, jnp.int32(cfg.mask_token_id), toks)
        x, cache = _dense_span(params, cfg, embed_tokens(params, cfg, feed),
                               cache, pos, absorbed=True)
        return lm_logits_span(params, cfg, x), cache

    def block(cache, xs):
        toks, first, pos = xs
        masked = jnp.arange(bl)[None, :] >= first
        steps = jnp.full((b, bl), -1, jnp.int32)
        seen = []
        for s in range(s_steps):
            logits, cache = forward(cache, toks, masked, pos)
            x0, reveal = reveal_rows(
                logits, masked, n_reveal, rule=reveal_rule,
                threshold=reveal_threshold,
                mask_token_id=cfg.mask_token_id)
            toks = jnp.where(reveal, x0, toks)
            steps = jnp.where(reveal, s, steps)
            masked = masked & ~reveal
            seen.append(logits)
        _, cache = forward(cache, toks, masked, pos)        # the commit
        return cache, (toks, steps,
                       jnp.stack(seen) if with_logits else None)

    firsts = jnp.where(jnp.arange(nb) == 0, tail, 0).astype(jnp.int32)
    _, (toks, steps, logits) = jax.lax.scan(
        block, cache,
        (open_toks.reshape(b, nb, bl).transpose(1, 0, 2),
         jnp.broadcast_to(firsts[:, None, None], (nb, b, 1)),
         t_pre + bl * jnp.arange(nb, dtype=jnp.int32)))
    flat = lambda a: a.transpose(1, 0, 2).reshape(b, nb * bl)[
        :, tail:tail + max_new_tokens]
    new, steps = flat(toks), flat(steps)
    if stop_tokens:
        stopped = jnp.isin(new, jnp.asarray(stop_tokens, jnp.int32))
        after = jnp.cumsum(stopped, axis=1) - stopped > 0
        new = jnp.where(after, jnp.int32(pad_token), new)
    out = jnp.concatenate([prompt, new], axis=1), steps
    return (*out, logits) if with_logits else out


@functools.partial(
    jax.jit, static_argnames=("cfg", "max_new_tokens", "temperature",
                              "top_k", "top_p", "stop_tokens",
                              "pad_token", "prefill", "denoise_steps",
                              "reveal_rule", "reveal_threshold"),
)
def generate(params, prompt, cfg: MoEConfig, *, max_new_tokens: int = 32,
             temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
             stop_tokens: tuple = (), pad_token: int = 0, key=None,
             prefill: str = "auto", denoise_steps: int | None = None,
             reveal_rule: str = "low_confidence_static",
             reveal_threshold: float = 0.9):
    """Greedy (temperature=0) or sampled decoding.

    prompt: [B, T0] int32.  Returns [B, T0 + max_new_tokens].  A config
    with a ``block_length`` generates by blocks, greedy
    (:func:`generate_blocks`, which ``denoise_steps``, ``reveal_rule`` and
    ``reveal_threshold`` are for and which also returns the reveal steps).

    ``stop_tokens``: static tuple of token ids that retire a row — the
    stop token itself is emitted, every later position is
    ``pad_token`` and the retired row's cache stops influencing its
    outputs (other rows are unaffected).  ``prefill``: 'batched' (one
    full-sequence pass), 'loop' (one token at a time), or 'auto'
    (batched for dropless configs, loop when ``drop_tokens`` — whose
    capacity competition is per-step by definition).
    """
    if cfg.block_length:
        if temperature != 0.0:
            raise NotImplementedError(
                "a block_length with a temperature: generation by blocks "
                "is greedy here (a keyed draw a position is missing)")
        return generate_blocks(
            params, prompt, cfg, max_new_tokens=max_new_tokens,
            denoise_steps=denoise_steps, reveal_rule=reveal_rule,
            reveal_threshold=reveal_threshold, stop_tokens=stop_tokens,
            pad_token=pad_token)[0]
    b, t0 = prompt.shape
    max_len = t0 + max_new_tokens
    cache = init_cache(cfg, b, max_len)
    key = key if key is not None else jax.random.PRNGKey(0)

    if prefill == "auto":
        prefill = "loop" if cfg.drop_tokens else "batched"
    if prefill not in ("batched", "loop"):
        raise ValueError(
            f"prefill={prefill!r} not in ('auto', 'batched', 'loop')")
    if prefill == "batched":
        logits, cache = prefill_batched(params, cfg, prompt, cache)
    else:
        logits, cache = prefill_loop(params, cfg, prompt, cache)

    stops = jnp.asarray(stop_tokens, jnp.int32) if stop_tokens else None

    def sample(logits, k):
        return sample_tokens(logits, k, temperature=temperature,
                             top_k=top_k, top_p=top_p)

    def step(carry, i):
        cache, logits, key, done = carry
        key, sub = jax.random.split(key)
        tok = sample(logits, sub)
        if stops is not None:
            tok = jnp.where(done, jnp.int32(pad_token), tok)
            done = done | jnp.isin(tok, stops)
        x = embed_tokens(params, cfg, tok)[:, None, :]
        logits, cache = _decode_step(params, cfg, x, cache, t0 + i)
        return (cache, logits, key, done), tok

    done0 = jnp.zeros((b,), bool)
    (_, logits, _, _), toks = jax.lax.scan(
        step, (cache, logits, key, done0), jnp.arange(max_new_tokens)
    )
    return jnp.concatenate([prompt, toks.T], axis=1)
